#!/usr/bin/env python3
"""chip_smoke.py — the SQL main path, once, on the attached TPU.

MySQL wire server → session → planner → scheduler → device cache → fused
device fragments → fetch/decode → wire, in ONE process, at the scale the
repo's records use (TPC-H-shaped, SF=10: 60,012,150 lineitem rows), with
every answer checked against a numpy reference computed outside the engine
and every statement checked to have run on the device. It is the quickest
proof that the system still starts on the chip; nothing it prints is a
benchmark.

    python chip_smoke.py                  # one chip, SF=10, as the driver runs it
    python chip_smoke.py --sf 0.01        # rehearsal size (still needs the chip)
    python chip_smoke.py --chips 4        # ONLY the cross-chip paths, four chips

Contract: the script sets no JAX platform and starts no process that needs
the chip; it exits non-zero — and prints no result line — when JAX reports
anything but a TPU, when a phase raises, or when any check fails. Each phase
prints one JSON object; the LAST line of stdout is
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`.

Phases (one chip): device → load → serve (Q1/Q3/Q5/Q6 cold, then the
digest's second execution — ONE statement program, compiled there — then
warm; one acknowledged INSERT read back, its DELETE read back) → check →
memory.
With `--chips 4`: device → load → pod (Q1/Q3 on the pod-partitioned default
path and with `tidb_tpu_dist_devices=4`, each against the numpy reference
and against the same statement pinned to one device) → memory.
"""

from __future__ import annotations

import argparse
import datetime
import json
import sys
import time

import numpy as np

DEFAULT_SF = 10.0
SERVING_ROW_THRESHOLD = 32768   # tidb_tpu_row_threshold's default

FAILURES: list = []


class SmokeFailed(Exception):
    """A phase could not go on."""


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def check(ok: bool, what: str) -> bool:
    """Record a failed check and go on: the later statements still print
    what they saw, and the run exits non-zero at the end."""
    if not ok:
        FAILURES.append(what)
        emit("check_failed", what=what)
    return bool(ok)


def days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - datetime.date(1970, 1, 1)).days


# ---------------------------------------------------------------------------
# compile accounting: JAX's own monitoring events, counted process-wide
# ---------------------------------------------------------------------------

class CompileMeter:
    """Counts what JAX's compiler did: every request to compile-or-load a
    program (`backend_compile_duration`, which also fires on a persistent
    cache hit), and the persistent cache's hits and misses. A miss is a
    real XLA compile; a hit is a program read back from disk."""

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
        return {"compile_requests": self.requests,
                "compile_seconds": round(self.seconds, 3),
                "cache_hits": self.hits, "cache_misses": self.misses}


def delta(after: dict, before: dict) -> dict:
    return {k: (round(after[k] - before[k], 3)
                if isinstance(after[k], float) else after[k] - before[k])
            for k in after if isinstance(after[k], (int, float))
            and not isinstance(after[k], bool)}


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------

def phase_device(want_chips: int) -> dict:
    from tidb_tpu.ops import jax_env
    from tidb_tpu import native
    jax = jax_env.jax
    devs = jax.devices()
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    import jaxlib
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — reported, not needed
        libtpu = None
    emit("device", **device, jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu, compile_cache_dir=jax_env.compile_cache_dir(),
         row_codec=native.encoder(), on_tpu=jax_env.on_tpu())
    if d0.platform != "tpu":
        raise SmokeFailed(f"needs a TPU; jax reports {d0.platform!r} "
                          f"({len(devs)} device(s))")
    if len(devs) != want_chips:
        raise SmokeFailed(f"needs {want_chips} chip(s), jax sees {len(devs)}")
    return device


# ---------------------------------------------------------------------------
# phase 2: load
# ---------------------------------------------------------------------------

def phase_load(sf: float, seed: int):
    from tidb_tpu.session import Engine
    from tidb_tpu.tools import tpch_shaped as T
    n_rows = int(T.LINEITEM_ROWS_SF1 * sf)
    t0 = time.perf_counter()
    data = T.generate(n_rows, seed)
    t_gen = time.perf_counter() - t0
    eng = Engine()
    t0 = time.perf_counter()
    T.load(eng, data)
    t_load = time.perf_counter() - t0
    rows = {t: len(next(iter(cols.values()))) for t, cols in data.items()}
    scanned = sum(c.nbytes for cols in data.values() for c in cols.values())
    emit("load", sf=sf, seed=seed, rows=rows, host_column_bytes=scanned,
         generate_s=round(t_gen, 2), load_and_analyze_s=round(t_load, 2))
    check(rows == T.table_rows(n_rows), f"loaded row counts {rows}")
    return eng, data


# ---------------------------------------------------------------------------
# the numpy reference: exact integer arithmetic over the generated columns
# ---------------------------------------------------------------------------

def fmt_dec(v: int, scale: int) -> str:
    """Scaled integer → the decimal text the wire carries."""
    v = int(v)
    sign, v = ("-", -v) if v < 0 else ("", v)
    if scale == 0:
        return f"{sign}{v}"
    return f"{sign}{v // 10 ** scale}.{v % 10 ** scale:0{scale}d}"


def avg_dec(total: int, count: int, scale: int) -> str:
    """AVG of a DECIMAL(.., scale): the sum carried at scale+4 and divided
    by the count, rounding half away from zero (expression/aggfuncs.py
    AvgAgg.final) — in Python integers, so exact at any size."""
    total, count = int(total) * 10 ** 4, int(count)
    q, r = divmod(abs(total), count)
    q += 2 * r >= count
    return fmt_dec(-q if total < 0 else q, scale + 4)


def group_sums(codes: np.ndarray, n_groups: int, mask: np.ndarray, cols):
    """→ per group: (count, [int sum of each col]) over mask — int64
    partial sums are exact here (|sum| < 2^63 for every column at SF=10)
    and leave numpy as Python ints."""
    out = []
    for g in range(n_groups):
        m = mask & (codes == g)
        out.append((int(m.sum()),
                    [int(c[m].sum(dtype=np.int64)) for c in cols]))
    return out


class Reference:
    """Q1/Q3/Q5/Q6 answered from the raw columns, as wire text rows."""

    def __init__(self, data: dict):
        from tidb_tpu.tools import tpch_shaped as T
        self.T = T
        li, self.orders, self.customer = (data["lineitem"], data["orders"],
                                          data["customer"])
        self.li = li
        self.n = len(li["l_shipdate"])
        # l_extendedprice * (1 - l_discount): scale 2+2; * (1 + l_tax): +2
        self.disc_price = li["l_extendedprice"] * (100 - li["l_discount"])
        self.q1_mask = li["l_shipdate"] <= days("1998-09-02")

    def q1(self):
        T, li = self.T, self.li
        charge = self.disc_price * (100 + li["l_tax"])
        codes = li["l_returnflag"].astype(np.int16) * len(T.LINESTATUSES) \
            + li["l_linestatus"]
        groups = group_sums(
            codes, len(T.RETURNFLAGS) * len(T.LINESTATUSES), self.q1_mask,
            [li["l_quantity"], li["l_extendedprice"], self.disc_price,
             charge, li["l_discount"]])
        rows = []
        for g, (cnt, (qty, price, dp, ch, disc)) in enumerate(groups):
            if not cnt:
                continue
            rows.append((T.RETURNFLAGS[g // len(T.LINESTATUSES)],
                         T.LINESTATUSES[g % len(T.LINESTATUSES)],
                         fmt_dec(qty, 2), fmt_dec(price, 2), fmt_dec(dp, 4),
                         fmt_dec(ch, 6), avg_dec(qty, cnt, 2),
                         avg_dec(price, cnt, 2), avg_dec(disc, cnt, 2),
                         str(cnt)))
        return sorted(rows)

    def _by_name(self, names, groups):
        return sorted((names[g], str(cnt), fmt_dec(s[0], 4))
                      for g, (cnt, s) in enumerate(groups) if cnt)

    def q3(self):
        # orders is keyed 0..n-1 in row order: the join is an index
        okey = self.li["l_orderkey"]
        mask = self.q1_mask & \
            (self.orders["o_orderdate"][okey] < days("1998-01-01"))
        return self._by_name(self.T.PRIORITIES, group_sums(
            self.orders["o_orderpriority"][okey], len(self.T.PRIORITIES),
            mask, [self.disc_price]))

    def q5(self):
        seg = self.customer["c_mktsegment"][
            self.orders["o_custkey"][self.li["l_orderkey"]]]
        return self._by_name(self.T.SEGMENTS, group_sums(
            seg, len(self.T.SEGMENTS), self.q1_mask, [self.disc_price]))

    def q6(self, extra=()):
        """`extra`: written rows (qty, price, disc, shipdate) on top of
        the generated table."""
        li = self.li
        lo, hi = days("1994-01-01"), days("1995-01-01")
        m = (li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi) \
            & (li["l_discount"] >= 5) & (li["l_discount"] <= 7) \
            & (li["l_quantity"] < 2400)
        cnt = int(m.sum())
        rev = int((li["l_extendedprice"][m] * li["l_discount"][m])
                  .sum(dtype=np.int64))
        for qty, price, disc, ship in extra:
            if lo <= ship < hi and 5 <= disc <= 7 and qty < 2400:
                cnt += 1
                rev += price * disc
        return [(str(cnt), fmt_dec(rev, 4))]


# ---------------------------------------------------------------------------
# what a client can see of where a statement ran
# ---------------------------------------------------------------------------

SUMMARY_COLS = ("EXEC_COUNT", "COMPILES", "PROGRAMS_LAUNCHED", "H2D_BYTES",
                "D2H_BYTES", "SCAN_BYTES", "DEVICE_SECONDS")


def summary_row(cli, sql: str) -> dict:
    """The statement's ledger in information_schema.statements_summary
    (cumulative per digest), zeros before its first run."""
    from tidb_tpu.util.observability import normalize_sql
    digest = normalize_sql(sql)
    names, rows = cli.query(
        "SELECT * FROM information_schema.statements_summary")
    for r in rows:
        row = dict(zip(names, r))
        if row["DIGEST_TEXT"] == digest:
            out = {c.lower(): (float(row[c]) if c == "DEVICE_SECONDS"
                               else int(row[c])) for c in SUMMARY_COLS}
            out["engine"] = row["ENGINE"]
            return out
    return {**{c.lower(): 0 for c in SUMMARY_COLS}, "engine": None}


def fallbacks_total(cli) -> int:
    _, rows = cli.query(
        "SELECT VALUE FROM information_schema.engine_metrics "
        "WHERE METRIC = 'tidb_tpu_device_fallbacks_total'")
    return int(sum(float(r[0]) for r in rows))


def explain_device(cli, sql: str) -> str:
    """EXPLAIN ANALYZE's execution-info cell that names the device."""
    _, rows = cli.query("EXPLAIN ANALYZE " + sql)
    cells = [str(c) for r in rows for c in r if c is not None]
    hit = [c for c in cells if "device:" in c]
    return hit[0] if hit else ""


def run_statement(cli, meter: CompileMeter, name: str, sql: str,
                  expect, reps=("cold", "second", "warm"),
                  resident=True) -> dict:
    """Run `sql` once per rep over the wire; check every answer against
    `expect`, that the device ran it, and that the warm rep compiled
    nothing and — where the path serves from `resident` tables — uploaded
    nothing. (The cold rep launches a program a slab and settles the
    capacities; the second runs the whole statement as ONE program, which
    it compiles; from the third on a statement is warm.) → the printed
    record."""
    from tidb_tpu.executor import compile_cache
    rec: dict = {"statement": name}
    fb0 = fallbacks_total(cli)
    led = summary_row(cli, sql)
    for rep in reps:
        m0, tr0 = meter.snapshot(), compile_cache.PROGRAM_TRACES
        t0 = time.perf_counter()
        _, rows = cli.query(sql)
        wall = time.perf_counter() - t0
        led, prev = summary_row(cli, sql), led
        d = delta(led, prev)
        rec[rep] = {"wall_s": round(wall, 4),
                    "compiles": d["compiles"],
                    "launches": d["programs_launched"],
                    "h2d_bytes": d["h2d_bytes"], "d2h_bytes": d["d2h_bytes"],
                    "scan_bytes": d["scan_bytes"],
                    "device_s": d["device_seconds"],
                    "program_traces": compile_cache.PROGRAM_TRACES - tr0,
                    **{"xla_" + k: v
                       for k, v in delta(meter.snapshot(), m0).items()}}
        check(sorted(rows) == expect,
              f"{name} {rep}: rows differ from the numpy reference: "
              f"got {rows[:3]} want {expect[:3]}")
        check(rows == expect if "ORDER BY" in sql else True,
              f"{name} {rep}: rows out of order")
        check(d["exec_count"] == 1 and led["engine"] == "tpu",
              f"{name} {rep}: ledger engine={led['engine']!r} "
              f"exec_count+={d['exec_count']}")
        check(d["programs_launched"] > 0,
              f"{name} {rep}: no device program launched")
    if "warm" in rec:
        w = rec["warm"]
        check(w["compiles"] == 0 and w["program_traces"] == 0
              and w["xla_compile_requests"] == 0,
              f"{name} warm: compiled again ({w})")
        check(w["h2d_bytes"] == 0 or not resident,
              f"{name} warm: uploaded {w['h2d_bytes']} bytes again")
    rec["explain_analyze"] = explain_device(cli, sql)
    check("device:yes" in rec["explain_analyze"],
          f"{name}: EXPLAIN ANALYZE says {rec['explain_analyze']!r}")
    rec["fallbacks"] = fallbacks_total(cli) - fb0
    check(rec["fallbacks"] == 0,
          f"{name}: tidb_tpu_device_fallbacks_total moved by "
          f"{rec['fallbacks']}")
    rec["rows"] = len(expect)
    return rec


# ---------------------------------------------------------------------------
# phase 3+4: serve and check (one chip)
# ---------------------------------------------------------------------------

# the written row: inside Q6's window on every predicate
WRITE = {"qty": 1000, "price": 1234567, "disc": 6, "tax": 2,
         "ship": "1994-06-15", "okey": 0}


def connect(server):
    from tidb_tpu.client import Client
    # no reconnect-and-retry: a statement runs once or the smoke fails
    cli = Client(port=server.port, timeout=900.0, auto_reconnect=False)
    cli.execute("SET tidb_tpu_strict = 'on'")
    return cli


def lower_threshold_for_rehearsal(cli, n_rows: int) -> None:
    """Below SF=1 a table or a pruned scan can fall under
    tidb_tpu_row_threshold and bounce to the CPU engine; only then is the
    threshold touched, and said so."""
    from tidb_tpu.tools.tpch_shaped import LINEITEM_ROWS_SF1
    if n_rows < LINEITEM_ROWS_SF1:
        cli.execute("SET tidb_tpu_row_threshold = 1")
        emit("note", what="rehearsal size: SET tidb_tpu_row_threshold = 1 "
             f"(lineitem has {n_rows} rows; the default threshold is "
             f"{SERVING_ROW_THRESHOLD})")


def phase_serve(eng, data, device: dict, meter: CompileMeter) -> None:
    from tidb_tpu.server import Server
    from tidb_tpu.tools import tpch_shaped as T
    t0 = time.perf_counter()
    ref = Reference(data)
    expect = {"Q1": ref.q1(), "Q3": ref.q3(), "Q5": ref.q5(), "Q6": ref.q6()}
    emit("reference", seconds=round(time.perf_counter() - t0, 2),
         q1_groups=len(expect["Q1"]), q6=expect["Q6"][0])
    n = ref.n
    server = Server(eng, port=0).start()
    try:
        cli = connect(server)
        lower_threshold_for_rehearsal(cli, n)
        for name, sql in (("Q1", T.Q1), ("Q3", T.Q3), ("Q5", T.Q5),
                          ("Q6", T.Q6)):
            emit("serve", device=device["kind"],
                 **run_statement(cli, meter, name, sql, expect[name]))

        # one acknowledged write, read back through the delta-slab path
        w = WRITE
        li = data["lineitem"]
        clash = int(((li["l_orderkey"] == w["okey"])
                     & (li["l_extendedprice"] == w["price"])
                     & (li["l_shipdate"] == days(w["ship"]))).sum())
        if clash:
            raise SmokeFailed(f"the written row's key matches {clash} "
                              f"generated row(s); pick another --seed")
        count_sql = "SELECT COUNT(*) FROM lineitem"

        def write_path():
            """(extensions, declines by gate) of the device cache so far:
            the written row must EXTEND the resident table. One inserted
            row once cost the next Q6 22.3 s and a 226 MB re-upload (my
            chip run, PR 23) with nothing but the clock to say so."""
            _, rows = cli.query(
                "SELECT METRIC, LABELS, VALUE FROM "
                "information_schema.engine_metrics WHERE METRIC LIKE "
                "'tidb_tpu_delta_%'")
            ext = sum(float(v) for m, _l, v in rows
                      if m == "tidb_tpu_delta_extensions_total")
            fell = {l: float(v) for m, l, v in rows
                    if m == "tidb_tpu_delta_declines_total"}
            return ext, fell

        def check_extended(before, what: str) -> dict:
            ext, fell = write_path()
            check(fell == before[1], f"the read after the {what} fell to "
                  f"a rebuild: declines {before[1]} -> {fell}")
            check(ext > before[0], f"the read after the {what} extended "
                  f"nothing (extensions {before[0]:g} -> {ext:g})")
            return {"extensions": ext - before[0], "declines": fell}

        before = write_path()
        cli.execute(
            "INSERT INTO lineitem VALUES "
            f"({fmt_dec(w['qty'], 2)}, {fmt_dec(w['price'], 2)}, "
            f"{fmt_dec(w['disc'], 2)}, {fmt_dec(w['tax'], 2)}, 'N', 'O', "
            f"'{w['ship']}', {w['okey']})")
        with_row = ref.q6([(w["qty"], w["price"], w["disc"],
                            days(w["ship"]))])
        check(with_row != expect["Q6"], "the written row must move Q6")
        rec = run_statement(cli, meter, "Q6+insert", T.Q6, with_row,
                            reps=("after_insert",))
        rec["write_path"] = check_extended(before, "INSERT")
        _, cnt = cli.query(count_sql)
        rec["count"] = cnt[0][0]
        check(cnt == [(str(n + 1),)], f"COUNT(*) after INSERT: {cnt}")
        emit("write", device=device["kind"], **rec)
        before = write_path()
        cli.execute(
            f"DELETE FROM lineitem WHERE l_orderkey = {w['okey']} "
            f"AND l_extendedprice = {fmt_dec(w['price'], 2)} "
            f"AND l_shipdate = '{w['ship']}'")
        rec = run_statement(cli, meter, "Q6+delete", T.Q6, expect["Q6"],
                            reps=("after_delete",))
        rec["write_path"] = check_extended(before, "DELETE")
        _, cnt = cli.query(count_sql)
        rec["count"] = cnt[0][0]
        check(cnt == [(str(n),)], f"COUNT(*) after DELETE: {cnt}")
        emit("write", device=device["kind"], **rec)
        cli.close()
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# --chips 4: the cross-chip paths and what they are compared with
# ---------------------------------------------------------------------------

def placement(eng) -> dict:
    """Where this engine's resident tables live: per cache entry, the
    owner list of a pod-partitioned entry and the devices its slabs are
    committed to."""
    from tidb_tpu.executor import device_cache as dc
    names = {t.id: t.name for t in eng.catalog.info_schema.list_tables()}
    out = {}
    with dc.LOCK:
        entries = [(k, e) for k, e in dc.CACHE.items()
                   if k[1] == id(eng.store)]
    for k, ent in entries:
        devs = set()
        for slabs in ent.dev.values():
            for t in slabs:
                for a in (t if isinstance(t, tuple) else (t,)):
                    if hasattr(a, "devices"):
                        devs |= {d.id for d in a.devices()}
        out[f"{names.get(k[2], k[2])}@dev{k[0]}"] = {
            "owners": sorted(set(getattr(ent, "owners", None) or [])),
            "array_devices": sorted(devs)}
    return out


def phase_pod(eng, data, device: dict, meter: CompileMeter) -> None:
    from tidb_tpu.executor import device_cache as dc
    from tidb_tpu import sysvars
    from tidb_tpu.server import Server
    from tidb_tpu.tools import tpch_shaped as T
    ref = Reference(data)
    expect = {"Q1": ref.q1(), "Q3": ref.q3()}
    n_dev = device["count"]
    server = Server(eng, port=0).start()
    try:
        cli = connect(server)
        lower_threshold_for_rehearsal(cli, ref.n)
        if ref.n < n_dev * sysvars.DEFAULT_MAX_SLAB_ROWS:
            # fewer slabs than chips at the defaults: nothing to spread
            slab = 1 << max((ref.n // n_dev).bit_length() - 1, 10)
            cli.execute("SET tidb_tpu_partition_min_rows = 1024")
            cli.execute(f"SET tidb_tpu_max_slab_rows = {slab}")
            emit("note", what="rehearsal size: SET "
                 "tidb_tpu_partition_min_rows = 1024, "
                 f"tidb_tpu_max_slab_rows = {slab} (lineitem has {ref.n} "
                 f"rows; defaults {sysvars.DEFAULT_PARTITION_MIN_ROWS}, "
                 f"{sysvars.DEFAULT_MAX_SLAB_ROWS})")
        queries = (("Q1", T.Q1), ("Q3", T.Q3))
        # (0) the comparison: the same statements pinned to one device
        cli.execute("SET tidb_tpu_device_queues = 'off'")
        pinned = {}
        for name, sql in queries:
            rec = run_statement(cli, meter, f"{name}/one-device", sql,
                                expect[name])
            _, pinned[name] = cli.query(sql)
            emit("pod", device=device["kind"], **rec)
        one = placement(eng)
        emit("placement", path="one-device", entries=one)
        check(all(len(p["array_devices"]) <= 1 for p in one.values()),
              "one-device run spread arrays over several devices")
        dc.clear()
        # (a) defaults: per-device queues, lineitem partitioned over the pod
        cli.execute("SET tidb_tpu_device_queues = 'auto'")
        for name, sql in queries:
            rec = run_statement(cli, meter, f"{name}/pod-default", sql,
                                expect[name])
            _, rows = cli.query(sql)
            check(rows == pinned[name], f"{name}: pod != one-device rows")
            emit("pod", device=device["kind"], **rec)
        pod = placement(eng)
        emit("placement", path="pod-default", entries=pod)
        part = [p for k, p in pod.items() if k.endswith("@dev-1")]
        check(bool(part), "no pod-partitioned (dev == -1) entry was built")
        for p in part:
            check(len(p["owners"]) == n_dev
                  and len(p["array_devices"]) == n_dev,
                  f"pod-partitioned entry not on {n_dev} distinct "
                  f"devices: {p}")
        dc.clear()
        # (b) the distributed shard_map path over all four (staged
        # exchange on, its default)
        cli.execute(f"SET tidb_tpu_dist_devices = {n_dev}")
        for name, sql in queries:
            # the distributed path ships its shards per statement
            rec = run_statement(cli, meter, f"{name}/dist{n_dev}", sql,
                                expect[name], resident=False)
            _, rows = cli.query(sql)
            check(rows == pinned[name], f"{name}: dist != one-device rows")
            emit("pod", device=device["kind"], **rec)
        cli.close()
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# phase 5: memory
# ---------------------------------------------------------------------------

def phase_memory(meter: CompileMeter) -> None:
    from tidb_tpu.ops.jax_env import jax
    per_dev = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        per_dev.append({"id": d.id,
                        "peak_bytes_in_use": st.get("peak_bytes_in_use"),
                        "bytes_in_use": st.get("bytes_in_use"),
                        "bytes_limit": st.get("bytes_limit")})
    emit("memory", devices=per_dev, **meter.snapshot())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=DEFAULT_SF,
                    help="scale factor (default 10; smaller is a rehearsal)")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the cross-chip paths")
    args = ap.parse_args(argv)
    FAILURES.clear()
    t_start = time.perf_counter()
    device = phase_device(args.chips)
    meter = CompileMeter().install()
    if args.sf != DEFAULT_SF:
        emit("note", what=f"--sf {args.sf}: a rehearsal size, not the "
             f"deployment's SF={DEFAULT_SF:g}")
    eng, data = phase_load(args.sf, args.seed)
    try:
        if args.chips == 1:
            phase_serve(eng, data, device, meter)
        else:
            phase_pod(eng, data, device, meter)
        phase_memory(meter)
    finally:
        eng.close()
    emit("done", seconds=round(time.perf_counter() - t_start, 1),
         failures=FAILURES)
    if FAILURES:
        print(f"chip_smoke: {len(FAILURES)} check(s) failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
