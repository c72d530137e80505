"""Whole-query compilation: fused sort/TopN/DISTINCT roots
(executor/device_emit.py emit_sort/emit_topk/emit_distinct +
executor/fragment.py get_finalize_program / specialization cache).

Pinned invariants:

* an ORDER BY / TopN root over a HashAgg runs as ONE fused finalize
  launch (merge → finalize exprs → sort/topn → gather), byte-exact
  against the mega-slab tree path (`tidb_tpu_fused_pipeline='off'`,
  which orders on the host) and the CPU volcano — string ci keys,
  wide-decimal outputs and MySQL NULL ordering (NULLs first ASC, last
  DESC) included;
* single-arg DISTINCT aggs no longer exclude a query from the fused
  pipeline: the (group, value) pair sets dedup on device, and a pair
  set clipped by `tidb_tpu_distinct_pair_cap` resizes through the
  resumable 'pairs' ladder rung — never silently truncating
  (tests/test_overlap_runtime.py, with the other rungs);
* the warm whole-query launch count is slabs + 1 (slab partials + the
  one fused finalize that replaced the root merge);
* EXPLAIN ANALYZE `launches=`/`spec_hits=` and statements_summary's
  PROGRAMS_LAUNCHED / SPECIALIZATION_HITS columns are byte-exact sums
  of the per-statement PhaseTimer ledger;
* the second execution of a repeated statement shape hits the
  per-digest specialization cache and retraces NOTHING;
* a fault at the finalize boundary becomes a warned CPU fallback that
  still returns the oracle rows.
"""

import re

import pytest

from tidb_tpu.executor import (agg_slabs, compile_cache, device_cache,
                               fragment as frag_mod, run_to_completion)
from tidb_tpu.executor.builder import build
from tidb_tpu.executor.fragment import TpuFragmentExec
from tidb_tpu.parser import parse
from tidb_tpu.session import Engine
from tidb_tpu.util import failpoint, timeline


def agg_fixture(n=3000):
    """Single wide table with a NULLable int key, a lowercase ci string
    key, exact wide-decimal measures and enough rows for 3 slabs at
    max_slab_rows=1024."""
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    s.execute("CREATE TABLE ff (g INT, s VARCHAR(8), v BIGINT, "
              "w DECIMAL(30,4))")
    rows = []
    for i in range(n):
        g = "NULL" if i % 11 == 0 else str(i % 7 - 3)
        rows.append(f"({g}, 'key{i % 5}', {(i * 37) % 211 - 100}, "
                    f"{(i * 97) % 100000}.{i % 10000:04d})")
    for base in range(0, n, 500):
        s.execute("INSERT INTO ff VALUES " + ",".join(rows[base:base + 500]))
    s.execute("ANALYZE TABLE ff")
    return eng, s


def device_rows(s, sql, extra_vars=None, *, expect_fallback=None):
    """Run on the device path; assert no CPU fallback (or, when
    expect_fallback is given, that the fallback reason mentions it)."""
    base = {"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": 1,
            "tidb_tpu_max_slab_rows": 1024}
    base.update(extra_vars or {})
    saved = {k: s.vars.get(k) for k in base}
    s.vars.update(base)
    try:
        plan = s._plan(parse(sql)[0])
        root = build(plan)
        chunks = run_to_completion(root, s._exec_ctx())
        frags = []

        def walk(e):
            if isinstance(e, TpuFragmentExec):
                frags.append(e)
            for c in getattr(e, "children", []):
                walk(c)

        walk(root)
        assert frags, f"no fragment extracted for: {sql}"
        for f in frags:
            if expect_fallback is None:
                assert f.used_device, f"fell back to CPU: {f.fallback_reason}"
            else:
                assert not f.used_device and \
                    expect_fallback in (f.fallback_reason or ""), \
                    f"wanted fallback {expect_fallback!r}, got " \
                    f"used_device={f.used_device} " \
                    f"reason={f.fallback_reason!r}"
        return [r for ch in chunks for r in ch.rows()]
    finally:
        for k, v in saved.items():
            if v is None:
                s.vars.pop(k, None)
            else:
                s.vars[k] = v


ORDER_SHAPES = [
    # NULL group key, both directions: MySQL NULLs first ASC, last DESC
    "SELECT g, COUNT(*), SUM(v) FROM ff GROUP BY g ORDER BY g",
    "SELECT g, COUNT(*), SUM(v) FROM ff GROUP BY g ORDER BY g DESC",
    # string ci key root order (lowercase data: mega-slab host order
    # ranks bytes, ci dicts rank folded — keep them agreeing)
    "SELECT s, COUNT(*), AVG(v) FROM ff GROUP BY s ORDER BY s",
    # wide-decimal agg OUTPUT rides the finalize gather untouched
    "SELECT g, SUM(w) FROM ff GROUP BY g ORDER BY g DESC",
    # TopN over an agg-output key, with offset
    "SELECT g, SUM(v) FROM ff GROUP BY g ORDER BY SUM(v) DESC LIMIT 3",
    "SELECT s, COUNT(*) FROM ff GROUP BY s ORDER BY COUNT(*) DESC, s "
    "LIMIT 2 OFFSET 1",
]


@pytest.mark.parametrize("sql", ORDER_SHAPES,
                         ids=["null-asc", "null-desc", "string-ci",
                              "wide-decimal", "topn-agg-key",
                              "topn-offset"])
def test_fused_finalize_byte_exact(sql):
    _, s = agg_fixture()
    cpu = s.query(sql).rows
    fused = device_rows(s, sql)
    mega = device_rows(s, sql, {"tidb_tpu_fused_pipeline": "off"})
    assert fused == mega, "fused finalize vs mega-slab mismatch"
    assert fused == cpu, "fused finalize vs CPU volcano mismatch"


# ---------------------------------------------------------------------------
# single-arg DISTINCT aggs inside the fused pipeline
# ---------------------------------------------------------------------------

DISTINCT_CHAIN = ("SELECT g, COUNT(DISTINCT v), SUM(v) FROM ff "
                  "GROUP BY g ORDER BY g")
DISTINCT_STRING = ("SELECT s, COUNT(DISTINCT g), COUNT(*) FROM ff "
                   "GROUP BY s ORDER BY s DESC")


@pytest.mark.parametrize("sql", [DISTINCT_CHAIN, DISTINCT_STRING],
                         ids=["int-value", "null-key-value"])
def test_single_arg_distinct_fused(sql):
    _, s = agg_fixture()
    cpu = s.query(sql).rows
    fused = device_rows(s, sql)
    assert fused == cpu
    # multi-slab DISTINCT really shipped pair sets through the fused
    # path, not the mega-slab fallback
    ph = frag_mod.LAST_PHASES
    assert ph is not None and ph.programs_launched > 0


def test_distinct_join_tree_fused():
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    s.execute("CREATE TABLE dm (id INT, name VARCHAR(16))")
    s.execute("INSERT INTO dm VALUES " + ",".join(
        f"({i}, 'name{i:02d}')" for i in range(8)))
    s.execute("CREATE TABLE fx (b INT, v BIGINT)")
    for base in range(0, 3000, 500):
        s.execute("INSERT INTO fx VALUES " + ",".join(
            f"({i % 8}, {(i * 37) % 997})"
            for i in range(base, base + 500)))
    s.execute("ANALYZE TABLE dm")
    s.execute("ANALYZE TABLE fx")
    sql = ("SELECT d.name, COUNT(DISTINCT f.v) FROM fx f "
           "JOIN dm d ON f.b = d.id GROUP BY d.name ORDER BY d.name")
    cpu = s.query(sql).rows
    assert device_rows(s, sql) == cpu


def test_finalize_fault_warned_cpu_fallback():
    """A raise at the fused-finalize-overflow boundary surfaces as a
    warned CPU fallback returning the oracle rows — never a truncated
    or partial fused result."""
    _, s = agg_fixture()
    sql = ORDER_SHAPES[0]
    cpu = s.query(sql).rows
    with failpoint.enabled("fused-finalize-overflow",
                           raise_=RuntimeError("chaos: finalize"),
                           times=9):
        rows = device_rows(s, sql, expect_fallback="chaos: finalize")
    assert rows == cpu


# ---------------------------------------------------------------------------
# ledger byte-exactness: EXPLAIN ANALYZE + statements_summary
# ---------------------------------------------------------------------------

def test_explain_analyze_counts_finalize_as_one_launch():
    _, s = agg_fixture()
    sql = ORDER_SHAPES[0]
    s.vars.update({"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": 1,
                   "tidb_tpu_max_slab_rows": 1024})
    try:
        s.query(sql)                       # cold: trace + first touch
        # the spec key pins RAW SQL (literals are trace constants), so
        # the EA statement is its own shape: its first execution launches
        # a program a slab, and the fused finalize counts as exactly ONE
        # program over the slabs
        s.query("EXPLAIN ANALYZE " + sql)
        ph = s.last_guard.phases
        assert ph.programs_launched == ph.fused_pipelines + 1 == 4, \
            ph.summary()
        # ... then assert on its warm repetition: ONE statement program
        ea = s.query("EXPLAIN ANALYZE " + sql).rows
        text = " ".join(str(c) for r in ea for c in r)
        m = re.search(r"launches=(\d+)", text)
        assert m, f"no launches= in EXPLAIN ANALYZE: {text}"
        ph = s.last_guard.phases
        # byte-exact vs the ledger of the EA execution itself
        assert int(m.group(1)) == ph.programs_launched
        assert ph.programs_launched == ph.fused_pipelines == 1, ph.summary()
        sh = re.search(r"spec_hits=(\d+)", text)
        assert sh and int(sh.group(1)) == ph.specialization_hits
        assert ph.specialization_hits >= 1, \
            "second execution of the digest must hit the spec cache"
    finally:
        for k in ("tidb_tpu_engine", "tidb_tpu_row_threshold",
                  "tidb_tpu_max_slab_rows"):
            s.vars.pop(k, None)


@pytest.mark.parametrize("name", ["tidb_tpu_fused_finalize",
                                  "tidb_tpu_specialization_cache",
                                  "tidb_tpu_aligned_join"])
def test_a_removed_gate_is_an_unknown_variable(name):
    """The three gates are gone: SET stores the name as it stores any
    unknown variable, and an ORDER BY over a join's aggregate still runs
    slabs + 1 programs at its first execution and ONE statement program
    once specialized, its join aligned."""
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE gd (id INT PRIMARY KEY, name VARCHAR(16))")
    s.execute("INSERT INTO gd VALUES " + ",".join(
        f"({i}, 'name{i:02d}')" for i in range(8)))
    s.execute("CREATE TABLE gf (b INT, v BIGINT)")
    s.execute("INSERT INTO gf VALUES " + ",".join(
        f"({i % 8}, {(i * 37) % 997})" for i in range(3000)))
    s.execute("ANALYZE TABLE gd")
    s.execute("ANALYZE TABLE gf")
    assert name not in s.vars
    for k, v in (("tidb_tpu_engine", "'on'"), ("tidb_tpu_row_threshold", 1),
                 ("tidb_tpu_max_slab_rows", 1024)):
        s.execute(f"SET {k} = {v}")
    ea = ("EXPLAIN ANALYZE SELECT d.name, SUM(f.v) FROM gf f "
          "JOIN gd d ON f.b = d.id GROUP BY d.name ORDER BY d.name")

    def ledger():
        """One run → (`launches=`, `spec_hits=`, the kinds of program
        launched, aligned joins built since the cache was emptied)."""
        device_cache._ALIGNED.clear()
        with timeline.capture() as cap:
            text = " ".join(str(c) for r in s.query(ea).rows for c in r)
        hits = re.search(r"spec_hits=(\d+)", text)
        return (int(re.search(r"launches=(\d+)", text).group(1)),
                int(hits.group(1)) if hits else 0,
                [e["name"].rpartition("_")[0] for e in cap.events
                 if e["cat"] == "launch"],
                len(device_cache._ALIGNED))

    kinds = ["partial_fused"] * 3 + ["finalize"]
    try:
        assert ledger() == (4, 0, kinds, 1)     # cold: trace, first touch
        assert ledger() == (1, 1, ["stmt_fused"], 1)
        s.execute(f"SET {name} = 'off'")
        assert s.vars[name] == "off"
        assert ledger() == (1, 1, ["stmt_fused"], 1)
    finally:
        eng.close()
        device_cache.clear()


def test_statements_summary_specialization_hits_ledger():
    _, s = agg_fixture()
    sql = DISTINCT_CHAIN
    s.vars.update({"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": 1,
                   "tidb_tpu_max_slab_rows": 1024})
    q = ("SELECT digest_text, programs_launched, specialization_hits "
         "FROM information_schema.statements_summary")

    def digest_counts():
        # the registry is process-global: measure as a delta
        hits = [r for r in s.query(q).rows if r[0] == sql]
        assert len(hits) <= 1, hits
        return (hits[0][1], hits[0][2]) if hits else (0, 0)

    try:
        l0, h0 = digest_counts()
        want_launch = want_hits = 0
        for _ in range(3):
            s.query(sql)
            ph = s.last_guard.phases
            want_launch += ph.programs_launched
            want_hits += ph.specialization_hits
        l1, h1 = digest_counts()
        assert l1 - l0 == want_launch
        assert h1 - h0 == want_hits
        assert want_hits >= 2, "executions 2 and 3 must hit the cache"
    finally:
        for k in ("tidb_tpu_engine", "tidb_tpu_row_threshold",
                  "tidb_tpu_max_slab_rows"):
            s.vars.pop(k, None)


def test_specialization_distinguishes_literals():
    """Same digest, different literal: the traced programs embed the
    literal as an XLA constant, so the specialization entries must NOT
    be shared across literals."""
    _, s = agg_fixture()
    qa = "SELECT g, COUNT(*) FROM ff WHERE v > 5 GROUP BY g ORDER BY g"
    qb = "SELECT g, COUNT(*) FROM ff WHERE v > 90 GROUP BY g ORDER BY g"
    cpu_a, cpu_b = s.query(qa).rows, s.query(qb).rows
    assert cpu_a != cpu_b, "fixture must make the literals distinguish"
    assert device_rows(s, qa) == cpu_a
    assert device_rows(s, qb) == cpu_b
    # warm re-runs, reversed order: hits must serve the RIGHT programs
    assert device_rows(s, qb) == cpu_b
    assert device_rows(s, qa) == cpu_a


# ---------------------------------------------------------------------------
# perf pins: slabs + 1 launches at a digest's first execution, ONE warm,
# zero retrace on a repeated digest
# ---------------------------------------------------------------------------

@pytest.mark.perf_smoke
@pytest.mark.parametrize("sql", [ORDER_SHAPES[0], ORDER_SHAPES[2],
                                 ORDER_SHAPES[4]],
                         ids=["order-null-key", "order-string",
                              "topn-agg-key"])
def test_whole_query_is_slabs_plus_one_then_one_launch(sql):
    _, s = agg_fixture()
    s.vars.update({"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": 1,
                   "tidb_tpu_max_slab_rows": 1024})   # 3 slabs
    try:
        agg_slabs._SPEC_CACHE.clear()
        cold = s.query(sql).rows
        ph = s.last_guard.phases
        assert ph.fused_pipelines == 3, ph.summary()
        assert ph.programs_launched == ph.fused_pipelines + 1, ph.summary()
        assert s.query(sql).rows == cold    # traces the statement program
        traces = compile_cache.PROGRAM_TRACES
        for _ in range(2):
            assert s.query(sql).rows == cold
            ph = s.last_guard.phases
            assert ph.programs_launched == ph.fused_pipelines == 1, \
                ph.summary()
            assert ph.specialization_hits >= 1, ph.summary()
        assert compile_cache.PROGRAM_TRACES == traces, \
            "repeated digest must not retrace"
    finally:
        for k in ("tidb_tpu_engine", "tidb_tpu_row_threshold",
                  "tidb_tpu_max_slab_rows"):
            s.vars.pop(k, None)
