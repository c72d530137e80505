"""What the benchmark reads of the program's own counters, and JAX's.

Copied from `chip_smoke.py` (`CompileMeter`, `summary_row`,
`fallbacks_total`) and widened to a snapshot over several statements.
"""

from __future__ import annotations

LEDGER_SUMS = ("EXEC_COUNT", "COMPILES", "PROGRAMS_LAUNCHED", "H2D_BYTES",
               "D2H_BYTES", "SCAN_BYTES", "SLABS_SKIPPED", "DEVICE_SECONDS",
               "QUEUE_WAIT_S")


class CompileMeter:
    """Counts what JAX's compiler did: every request to compile-or-load a
    program (`backend_compile_duration`, which also fires on a persistent
    cache hit), and the persistent cache's hits and misses. A miss is a real
    XLA compile; a hit is a program read back from disk."""

    def __init__(self):
        self.requests = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

    def install(self) -> "CompileMeter":
        from jax import monitoring

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        def on_duration(event, secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.requests += 1
                self.seconds += secs

        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)
        return self

    def snapshot(self) -> dict:
        return {"requests": self.requests, "seconds": self.seconds,
                "cache_hits": self.hits, "cache_misses": self.misses}


def delta(after: dict, before: dict) -> dict:
    """after − before over the numeric keys of `after`."""
    return {k: after[k] - before.get(k, 0) for k in after
            if isinstance(after[k], (int, float))
            and not isinstance(after[k], bool)}


def table(cli, sql: str) -> list:
    """A query's rows as dicts keyed by column name."""
    names, rows = cli.query(sql)
    return [dict(zip(names, r)) for r in rows]


def ledgers(cli, statements: dict) -> dict:
    """{statement name: its cumulative ledger in
    information_schema.statements_summary}, zeros before its first run;
    `engine` is what the ledger says ran it."""
    from tidb_tpu.util.observability import normalize_sql
    by_digest = {r["DIGEST_TEXT"]: r for r in table(
        cli, "SELECT * FROM information_schema.statements_summary")}
    out = {}
    for name, sql in statements.items():
        row = by_digest.get(normalize_sql(sql))
        led = {c: (float(row[c]) if row else 0.0) for c in LEDGER_SUMS}
        led["engine"] = row["ENGINE"] if row else None
        out[name] = led
    return out


def ledger_delta(after: dict, before: dict) -> dict:
    """Per statement and under "*" summed over the statements."""
    per = {name: delta(after[name], before[name]) for name in after}
    per["*"] = {c: sum(d[c] for d in per.values()) for c in LEDGER_SUMS}
    return per


def fallbacks_total(cli) -> int:
    _, rows = cli.query(
        "SELECT VALUE FROM information_schema.engine_metrics "
        "WHERE METRIC = 'tidb_tpu_device_fallbacks_total'")
    return int(sum(float(r[0]) for r in rows))


def pool_stats() -> dict:
    """The scheduler pool's counters (host clock)."""
    from tidb_tpu.executor.scheduler import POOL
    s = POOL.stats()
    return {k: s[k] for k in ("admissions", "waits", "wait_s_total")}
