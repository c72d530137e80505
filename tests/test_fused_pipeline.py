"""Whole-pipeline fragment fusion (executor/fragment.py _run_agg_slabs
over a join tree + executor/device_emit.py emit layer).

Pinned invariants:

* the fused per-slab program (scan → filter/project → join-probe →
  partial-agg in ONE traced XLA call per slab, plus one root merge) is
  byte-exact against both the operator-at-a-time mega-slab tree path
  (`tidb_tpu_fused_pipeline='off'`) and the CPU volcano — including
  string-dictionary group keys and exact decimal sums;
* the Q1 chain shape (wide decimals included) runs its partials through
  the same emit layer and reports per-slab fused launches;
* (a group-cap overflow inside the fused pipeline re-runs only the
  overflowed slabs: tests/test_overlap_runtime.py, with the chain's);
* warm repeats retrace nothing (PROGRAM_TRACES frozen) and launch at
  most 2 device programs per slab (slab partial + amortized merge);
* fused launch spans land in the Chrome timeline one-per-slab, labeled
  with the pipeline signature digest, and cold builds charge the
  `compile:fused` lane.
"""

import pytest

from tidb_tpu.executor import (agg_slabs, compile_cache,
                               fragment as frag_mod, run_to_completion)
from tidb_tpu.executor.builder import build
from tidb_tpu.executor.fragment import TpuFragmentExec
from tidb_tpu.parser import parse
from tidb_tpu.session import Engine
from tidb_tpu.util import timeline


def run_device(s, sql, *, max_slab=None, fused=None):
    """Execute on the device path, asserting no CPU fallback."""
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    if max_slab is not None:
        s.vars["tidb_tpu_max_slab_rows"] = max_slab
    if fused is not None:
        s.vars["tidb_tpu_fused_pipeline"] = fused
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
            assert f.used_device, f"fell back to CPU: {f.fallback_reason}"
        return [r for ch in chunks for r in ch.rows()]
    finally:
        s.vars["tidb_tpu_engine"] = "off"
        for k in ("tidb_tpu_max_slab_rows", "tidb_tpu_fused_pipeline"):
            s.vars.pop(k, None)


def join_fixture(n_facts=3072):
    """Star fixture: n_facts facts → 8-row dim → 2-row reg, with a
    string-dictionary group key and exact decimal measures; every fact
    row matches exactly one dim row."""
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE dim (id INT, name VARCHAR(16), r_id INT)")
    s.execute("CREATE TABLE reg (id INT, rname VARCHAR(8))")
    s.execute("INSERT INTO reg VALUES (0,'east'),(1,'west')")
    s.execute("INSERT INTO dim VALUES " + ",".join(
        f"({i}, 'name{i:02d}', {i % 2})" for i in range(8)))
    s.execute("CREATE TABLE facts (b INT, s VARCHAR(8), v BIGINT, "
              "dec DECIMAL(12,2))")
    for base in range(0, n_facts, 512):
        vals = ", ".join(
            f"({i % 8}, 'seg{i % 5}', {(i * 37) % 211 - 100}, "
            f"{(i * 53) % 9973}.{i % 100:02d})"
            for i in range(base, min(base + 512, n_facts)))
        s.execute(f"INSERT INTO facts VALUES {vals}")
    s.execute("ANALYZE TABLE dim")
    s.execute("ANALYZE TABLE reg")
    s.execute("ANALYZE TABLE facts")
    return eng, s


Q3_SHAPE = ("SELECT d.name, COUNT(*), SUM(f.v) FROM facts f "
            "JOIN dim d ON f.b = d.id WHERE f.v > -50 "
            "GROUP BY d.name ORDER BY d.name")
Q5_SHAPE = ("SELECT r.rname, COUNT(*), SUM(f.dec) FROM facts f "
            "JOIN dim d ON f.b = d.id JOIN reg r ON d.r_id = r.id "
            "GROUP BY r.rname ORDER BY r.rname")
STR_KEY = ("SELECT f.s, COUNT(*), SUM(f.dec), SUM(f.v) FROM facts f "
           "JOIN dim d ON f.b = d.id GROUP BY f.s ORDER BY f.s")


# ---------------------------------------------------------------------------
# byte-exact: fused vs operator-at-a-time vs CPU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sql", [Q3_SHAPE, Q5_SHAPE, STR_KEY],
                         ids=["q3", "q5", "string-key"])
def test_fused_byte_exact_vs_unfused_and_cpu(sql):
    _, s = join_fixture()
    cpu = s.query(sql).rows
    fused = run_device(s, sql, max_slab=1024, fused="on")
    unfused = run_device(s, sql, max_slab=1024, fused="off")
    assert fused == unfused, "fused vs mega-slab tree mismatch"
    assert fused == cpu, "fused vs CPU volcano mismatch"


def test_fused_counters_and_chain_wide_decimal():
    # Q1 chain shape: the per-slab partial IS a fused pipeline through
    # the shared emit layer — wide decimals and string keys included
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE st (c VARCHAR(8), a BIGINT, w DECIMAL(30,4))")
    for base in range(0, 3000, 500):
        vals = ", ".join(
            f"('k{i % 7}', {i % 50 - 25}, {(i * 97) % 100000}.{i % 10000:04d})"
            for i in range(base, base + 500))
        s.execute(f"INSERT INTO st VALUES {vals}")
    sql = "SELECT c, COUNT(a), SUM(w) FROM st GROUP BY c ORDER BY c"
    cpu = s.query(sql).rows
    s.vars.update({"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": 1,
                   "tidb_tpu_max_slab_rows": 1024})
    assert s.query(sql).rows == cpu
    ph = s.last_guard.phases
    # 3 slabs → 3 fused partial launches; every launch is fused except
    # the single root merge
    assert ph.fused_pipelines == 3, ph.summary()
    assert ph.programs_launched == ph.fused_pipelines + 1, ph.summary()


def test_fused_join_launch_accounting():
    _, s = join_fixture()
    s.vars.update({"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": 1,
                   "tidb_tpu_max_slab_rows": 1024})
    agg_slabs._SPEC_CACHE.clear()
    cpu_rows = s.query(Q3_SHAPE).rows
    ph = s.last_guard.phases
    # a digest's first execution: 3 probe slabs × 1 fused program + 1 root
    # merge
    assert ph.fused_pipelines == 3, ph.summary()
    assert ph.programs_launched == 4, ph.summary()
    for _ in range(2):             # specialized: ONE statement program
        assert s.query(Q3_SHAPE).rows == cpu_rows
        ph = s.last_guard.phases
        assert ph.fused_pipelines == ph.programs_launched == 1, ph.summary()


def test_statements_summary_matches_phase_ledger():
    # satellite: the digest profile's launch counters are byte-exact
    # sums of the per-statement PhaseTimer ledger
    _, s = join_fixture()
    s.vars.update({"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": 1,
                   "tidb_tpu_max_slab_rows": 1024})
    q = ("SELECT digest_text, programs_launched, fused_pipelines"
         " FROM information_schema.statements_summary")

    def digest_counts():
        # the registry is process-global, so measure this test as a DELTA
        # over whatever earlier tests already folded into the digest
        hits = [r for r in s.query(q).rows
                if "rname" in r[0] and "facts" in r[0]]
        assert len(hits) <= 1, hits
        return (hits[0][1], hits[0][2]) if hits else (0, 0)

    l0, f0 = digest_counts()
    want_launch = want_fused = 0
    for _ in range(3):
        s.query(Q5_SHAPE)
        ph = s.last_guard.phases
        want_launch += ph.programs_launched
        want_fused += ph.fused_pipelines
    assert want_fused > 0
    l1, f1 = digest_counts()
    assert l1 - l0 == want_launch
    assert f1 - f0 == want_fused


# ---------------------------------------------------------------------------
# warm repeat: zero retraces, ONE launch (first execution: ≤2 per slab)
# ---------------------------------------------------------------------------

@pytest.mark.perf_smoke
def test_fused_warm_repeat_zero_retrace_two_launches_per_slab():
    _, s = join_fixture()
    s.vars.update({"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": 1,
                   "tidb_tpu_max_slab_rows": 1024})
    agg_slabs._SPEC_CACHE.clear()
    cold = s.query(STR_KEY).rows
    ph = s.last_guard.phases
    assert ph.fused_pipelines == 3, ph.summary()
    assert ph.programs_launched <= 2 * ph.fused_pipelines, ph.summary()
    assert s.query(STR_KEY).rows == cold    # traces the statement program
    traces = compile_cache.PROGRAM_TRACES
    for _ in range(3):
        assert s.query(STR_KEY).rows == cold
        ph = s.last_guard.phases
        assert ph.fused_pipelines == ph.programs_launched == 1, ph.summary()
    assert compile_cache.PROGRAM_TRACES == traces, \
        "warm fused repeat must not retrace"


# ---------------------------------------------------------------------------
# Chrome-trace: one labeled fused span per slab + compile:fused lane
# ---------------------------------------------------------------------------

def test_timeline_fused_spans_and_compile_lane():
    _, s = join_fixture(n_facts=1500)
    s.vars.update({"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": 1,
                   "tidb_tpu_max_slab_rows": 512})
    # the filter constant lands in the tree signature, so this variant is
    # cold even though the compile cache is process-global and q3 above
    # already built the -50 shape
    sql = Q3_SHAPE.replace("> -50", "> -49")
    with timeline.capture() as col:
        s.query(sql)
    ph = s.last_guard.phases
    fused_spans = [e for e in col.events
                   if e["cat"] == "launch"
                   and str(e.get("args", {}).get("sig", ""))
                   .startswith("fused:")]
    # exactly one labeled launch span per fused slab launch
    assert ph.fused_pipelines >= 2, ph.summary()
    assert len(fused_spans) == ph.fused_pipelines, \
        [e.get("args") for e in col.events]
    sigs = {e["args"]["sig"] for e in fused_spans}
    assert len(sigs) == 1, "one pipeline → one signature digest"
    # cold pipeline build must charge the compile:fused lane
    compiles = [e for e in col.events if e["name"] == "compile:fused"]
    assert compiles, [e["name"] for e in col.events]
