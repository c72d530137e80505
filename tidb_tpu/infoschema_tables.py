"""information_schema virtual tables (ref: infoschema/tables.go — the
reference exposes ~60 memtables; these are the core inspection set).

Each table is a (schema, rows-closure) pair: rows materialize at
execution time from the live catalog/storage/observability state, so a
cached plan still reads fresh data. The reference computes its memtables
the same way (infoschema retrievers fill chunks on demand)."""

from __future__ import annotations

from typing import Callable, Dict, List, Tuple

from tidb_tpu import types as T
from tidb_tpu.errors import UnknownTableError
from tidb_tpu.executor import device_cache

# name → (column name, type) list + row builder(session) → rows
_TABLES: Dict[str, Tuple[List[Tuple[str, object]],
                         Callable[[object], List[tuple]]]] = {}


def register(name: str, columns):
    def deco(fn):
        _TABLES[name.lower()] = (columns, fn)
        return fn
    return deco


def lookup(name: str):
    hit = _TABLES.get(name.lower())
    if hit is None:
        raise UnknownTableError(
            f"Unknown table 'information_schema.{name}'")
    return hit


def table_names() -> List[str]:
    return sorted(_TABLES)


def _user_tables(session):
    return [t for t in session.engine.catalog.info_schema.list_tables()
            if not t.name.startswith("#")]


@register("tables", [("TABLE_SCHEMA", T.varchar()),
                     ("TABLE_NAME", T.varchar()),
                     ("TABLE_ROWS", T.bigint()),
                     ("TABLE_ID", T.bigint()),
                     ("REGIONS", T.bigint())])
def _tables(session):
    stats = session.engine.store.stats()
    out = []
    for t in _user_tables(session):
        regions, live = stats.get(t.id, (0, 0))
        out.append(("test", t.name, live, t.id, regions))
    return out


@register("columns", [("TABLE_NAME", T.varchar()),
                      ("COLUMN_NAME", T.varchar()),
                      ("ORDINAL_POSITION", T.bigint()),
                      ("IS_NULLABLE", T.varchar()),
                      ("DATA_TYPE", T.varchar()),
                      ("COLUMN_KEY", T.varchar())])
def _columns(session):
    out = []
    for t in _user_tables(session):
        for i, c in enumerate(t.columns):
            out.append((t.name, c.name, i + 1,
                        "YES" if c.ftype.nullable else "NO",
                        c.ftype.kind.value,
                        "PRI" if c.primary_key else ""))
    return out


@register("statistics", [("TABLE_NAME", T.varchar()),
                         ("INDEX_NAME", T.varchar()),
                         ("SEQ_IN_INDEX", T.bigint()),
                         ("COLUMN_NAME", T.varchar()),
                         ("NON_UNIQUE", T.bigint())])
def _statistics(session):
    out = []
    for t in _user_tables(session):
        if t.primary_key:
            for i, c in enumerate(t.primary_key):
                out.append((t.name, "PRIMARY", i + 1, c, 0))
        for ix in t.indexes:
            for i, c in enumerate(ix.columns):
                out.append((t.name, ix.name, i + 1, c,
                            0 if ix.unique else 1))
    return out


@register("user_privileges", [("GRANTEE", T.varchar()),
                              ("PRIVILEGE_TYPE", T.varchar()),
                              ("SCOPE", T.varchar())])
def _user_privileges(session):
    auth = session.engine.auth
    out = []
    with auth._lock:
        grants = {u: {k: set(v) for k, v in g.items()}
                  for u, g in auth.grants.items()}
    for user, scopes in sorted(grants.items()):
        for (db, tbl), privs in sorted(scopes.items()):
            for p in sorted(privs):
                out.append((f"'{user}'@'%'", p, f"{db}.{tbl}"))
    return out


@register("session_variables", [("VARIABLE_NAME", T.varchar()),
                                ("VARIABLE_VALUE", T.varchar())])
def _session_variables(session):
    return sorted((k, str(v)) for k, v in session.vars.items())


@register("processlist", [("ID", T.bigint()),
                          ("USER", T.varchar()),
                          ("TIME", T.double()),
                          ("INFO", T.varchar()),
                          ("ESCALATIONS", T.varchar()),
                          ("QUEUE_WAIT_MS", T.double())])
def _processlist(session):
    # same source as SHOW PROCESSLIST: every live connection (idle ones
    # included), each with ITS OWN user — not the querying session's —
    # and, like SHOW PROCESSLIST, only the caller's own threads unless
    # they hold the global PROCESS privilege.
    # ESCALATIONS is the running statement's capacity-ladder summary
    # (util/escalation.py): recompiles, exact resizes, shard retries,
    # degraded-mesh re-dispatches — live observability for "why is this
    # query recompiling". QUEUE_WAIT_MS is the statement's cumulative
    # device-scheduler admission wait (executor/scheduler.py) — live
    # observability for "is this query running or queued".
    from tidb_tpu.util.guard import PROCESS_REGISTRY
    see_all = session.engine.auth.has_global(session.user, "PROCESS")
    return sorted(
        (cid, user or "",
         round(guard.elapsed(), 3) if guard is not None else 0.0,
         guard.sql if guard is not None else None,
         guard.escalation.summary() if guard is not None else "",
         round(getattr(guard, "queue_wait_s", 0.0) * 1000.0, 3)
         if guard is not None else 0.0)
        for cid, user, guard, _killed in PROCESS_REGISTRY.snapshot()
        if see_all or user in (None, session.user))


@register("table_storage_stats", [("TABLE_NAME", T.varchar()),
                                  ("LIVE_ROWS", T.bigint()),
                                  ("DEAD_ROWS", T.bigint()),
                                  ("REGION_COUNT", T.bigint())])
def _table_storage_stats(session):
    out = []
    for t in _user_tables(session):
        live, dead, regions = session.engine.store.gc_stats(t.id)
        out.append((t.name, live, dead, regions))
    return out


@register("engines", [("ENGINE", T.varchar()),
                      ("SUPPORT", T.varchar()),
                      ("COMMENT", T.varchar())])
def _engines(session):
    import jax
    backend = jax.default_backend()
    return [("tidb_tpu_cpu", "YES", "vectorized numpy volcano"),
            ("tidb_tpu_device", "DEFAULT" if backend == "tpu" else "YES",
             f"fused XLA fragments ({backend})")]


@register("partitions", [("TABLE_NAME", T.varchar()),
                         ("PARTITION_NAME", T.varchar()),
                         ("PARTITION_ORDINAL_POSITION", T.bigint()),
                         ("PARTITION_METHOD", T.varchar()),
                         ("PARTITION_EXPRESSION", T.varchar()),
                         ("PARTITION_DESCRIPTION", T.varchar()),
                         ("TABLE_ROWS", T.bigint())])
def _partitions(session):
    """Ref: infoschema/tables.go tablePartitionsCols — one row per
    partition with live row counts from its region set."""
    rows = []
    snap = session.engine.store.snapshot()
    for t in _user_tables(session):
        p = getattr(t, "partition", None)
        if p is None:
            rows.append((t.name, None, None, None, None, None,
                         snap.table_data(t.id).live_rows
                         if snap.has_table(t.id) else 0))
            continue
        counts = {k: 0 for k in range(p.n_parts)}
        if snap.has_table(t.id):
            for r in snap.table_data(t.id).regions:
                if r.part is not None:
                    counts[r.part] = counts.get(r.part, 0) + r.live_rows
        for i, name in enumerate(p.names):
            if p.kind == "range":
                b = p.bounds[i]
                desc = "MAXVALUE" if b is None else str(b)
            else:
                desc = None
            rows.append((t.name, name, i + 1, p.kind.upper(), p.column,
                         desc, counts.get(i, 0)))
    return rows


@register("statements_summary",
          [("DIGEST_TEXT", T.varchar()),
           ("EXEC_COUNT", T.bigint()),
           ("SUM_LATENCY_S", T.double()),
           ("AVG_LATENCY_S", T.double()),
           ("MAX_LATENCY_S", T.double()),
           ("ROWS_SENT", T.bigint()),
           ("ENGINE", T.varchar()),
           ("DEVICE_SECONDS", T.double()),
           ("H2D_BYTES", T.bigint()),
           ("D2H_BYTES", T.bigint()),
           ("SCAN_BYTES", T.bigint()),
           ("H2D_LOGICAL_BYTES", T.bigint()),
           ("SCAN_LOGICAL_BYTES", T.bigint()),
           ("COMPILES", T.bigint()),
           ("PROGRAMS_LAUNCHED", T.bigint()),
           ("FUSED_PIPELINES", T.bigint()),
           ("SPECIALIZATION_HITS", T.bigint()),
           ("SLABS_SKIPPED", T.bigint()),
           ("H2D_SKIPPED_BYTES", T.bigint()),
           ("QUEUE_WAIT_S", T.double()),
           ("QUEUE_WAITS", T.bigint()),
           ("QUEUE_P50_MS", T.double()),
           ("QUEUE_P99_MS", T.double()),
           ("SCHED_CLASS", T.varchar())])
def _statements_summary(session):
    """TopSQL-style per-digest device-time attribution (ref:
    util/stmtsummary — here extended with the PhaseTimer ledger): every
    counter is the exact sum over that digest's statements, so a row's
    byte/compile columns equal the sum of its EXPLAIN ANALYZE totals."""
    from tidb_tpu.util.observability import REGISTRY
    return [(p["digest"], p["count"], p["sum_s"], p["avg_s"], p["max_s"],
             p["rows"], p["engine"], p["device_s"], p["h2d_bytes"],
             p["d2h_bytes"], p["scan_bytes"], p["h2d_logical_bytes"],
             p["scan_logical_bytes"], p["compiles"],
             p["programs_launched"], p["fused_pipelines"],
             p["specialization_hits"],
             p.get("slabs_skipped", 0), p.get("h2d_skipped_bytes", 0),
             p["queue_wait_s"], p["queue_waits"], p["queue_p50_ms"],
             p["queue_p99_ms"], p.get("sched_class"))
            for p in REGISTRY.summary_profiles()]


@register("slow_query", [("TIME", T.varchar()),
                         ("QUERY_TIME_S", T.double()),
                         ("DEVICE_SECONDS", T.double()),
                         ("QUEUE_WAIT_MS", T.double()),
                         ("H2D_BYTES", T.bigint()),
                         ("COMPILES", T.bigint()),
                         ("ROWS_SENT", T.bigint()),
                         ("ENGINE", T.varchar()),
                         ("QUERY", T.varchar())])
def _slow_query(session):
    """The slow-log ring (ref: infoschema slow_query memtable over the
    slow log file) with per-entry device attribution."""
    from tidb_tpu.util.observability import REGISTRY
    return REGISTRY.slow_rows_full()


@register("table_storage", [("TABLE_NAME", T.varchar()),
                            ("COLUMN_NAME", T.varchar()),
                            ("LAYOUT", T.varchar()),
                            ("PHYSICAL_BYTES", T.bigint()),
                            ("LOGICAL_BYTES", T.bigint()),
                            ("ZONE_MAP_SLABS", T.bigint()),
                            ("ZONE_MAP_MIN", T.varchar()),
                            ("ZONE_MAP_MAX", T.varchar()),
                            ("ZONE_MAP_NULLS", T.bigint())])
def _table_storage(session):
    """Per-(table, column) device residency of the HBM column cache:
    the physical (compressed) bytes actually held in HBM next to the
    raw-equivalent logical bytes, plus the layout signature that
    produced them ('raw', 'pack:wW:rREF:...', 'dict:wW:...'). The
    physical column reconciles with statements_summary's H2D/SCAN
    counters: a cold scan's H2D_BYTES is exactly the physical bytes of
    the columns it uploaded. The ZONE_MAP_* columns expose the
    encode-time per-slab statistics slab pruning consults (slab count,
    global min/max over known slabs, total null count)."""
    names = {t.id: t.name for t in _user_tables(session)}
    cols = {t.id: [c.name for c in t.columns] for t in _user_tables(session)}
    out = []
    for r in device_cache.storage_stats(id(session.engine.store)):
        tid = r["table_id"]
        cnames = cols.get(tid, [])
        cname = cnames[r["column"]] if r["column"] < len(cnames) \
            else str(r["column"])
        out.append((names.get(tid, str(tid)), cname, r["layout"],
                    r["physical_bytes"], r["logical_bytes"],
                    r["zone_map_slabs"],
                    None if r["zone_map_min"] is None
                    else str(r["zone_map_min"]),
                    None if r["zone_map_max"] is None
                    else str(r["zone_map_max"]),
                    r["zone_map_nulls"]))
    return sorted(out)


@register("engine_metrics", [("METRIC", T.varchar()),
                             ("LABELS", T.varchar()),
                             ("VALUE", T.double())])
def _engine_metrics(session):
    """Every registry counter and histogram (bucket/count/sum rows
    included) as SQL — the metrics_schema analog, so percentiles can be
    derived without scraping /metrics."""
    from tidb_tpu.util.observability import REGISTRY
    return REGISTRY.metric_rows()


@register("views", [("TABLE_NAME", T.varchar()),
                    ("VIEW_DEFINITION", T.varchar()),
                    ("IS_UPDATABLE", T.varchar()),
                    ("SECURITY_TYPE", T.varchar())])
def _views(session):
    """Ref: infoschema/tables.go viewsCols."""
    return [(v.name, v.sql, "NO", "DEFINER")
            for v in session.engine.catalog.info_schema.list_views()]
