"""Tier-1 perf guardrails (tiny scale, CPU backend, fast).

Not a benchmark — these pin the two properties the overlap runtime's
speed rests on, which a correctness suite would never notice breaking:

* warm-path stability: repeating an identical query must trace ZERO new
  programs (PROGRAM_TRACES frozen) and re-upload NOTHING (the cache
  entry's device arrays keep their identities);
* phase accounting: a cold multi-slab first touch must attribute time
  to every pipeline phase (encode/upload/compute/fetch/decode) with a
  sane overlap-efficiency ratio, because EXPLAIN ANALYZE reports those
  numbers as the optimization's evidence.
"""

import numpy as np
import pytest

from tidb_tpu.executor import device_cache as dc
from tidb_tpu.executor import fragment
from tidb_tpu.executor import agg_slabs, compile_cache
from tidb_tpu.session import Engine

pytestmark = pytest.mark.perf_smoke

SQL = "SELECT c, COUNT(*), SUM(a), AVG(b) FROM p GROUP BY c"


@pytest.fixture()
def session():
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    s.execute("CREATE TABLE p (a BIGINT, b DOUBLE, c VARCHAR(8))")
    rng = np.random.default_rng(3)
    words = ["ant", "bee", "cow", "dog"]
    rows = [f"({int(rng.integers(0, 100))},{float(rng.normal()):.4f},"
            f"'{words[int(rng.integers(0, 4))]}')" for _ in range(3000)]
    s.execute("INSERT INTO p VALUES " + ",".join(rows))
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    s.vars["tidb_tpu_max_slab_rows"] = 1024   # 3 slabs → real streaming
    return eng, s


def _storage_ids(ent, base_only=False):
    """id() of every device array each column's storage holds — its slabs'
    arrays, or the ONE stacked array a leaf once a statement program has
    read the table (`SlabColumn.arrays`: nothing is sliced to ask)."""
    return {i: [id(a) for s, a in col.arrays()
                if not (base_only and s >= col.n_base)]
            for i, col in ent.dev.items()}


def _entry(eng):
    tid = eng.catalog.info_schema.table("p").id
    for (_dev, sid, t, _parts), ent in dc.CACHE.items():
        if sid == id(eng.store) and t == tid:
            return ent
    raise AssertionError("table p not cached")


def test_cold_first_touch_reports_all_phases(session):
    eng, s = session
    rows_cold = s.query(SQL).rows
    assert rows_cold
    ph = fragment.LAST_PHASES
    assert ph is not None
    d = ph.as_dict()
    # the cold run really encoded and uploaded (first touch) and computed
    assert d["encode_s"] > 0.0
    assert d["upload_s"] > 0.0
    assert d["compute_s"] > 0.0
    assert d["decode_s"] >= 0.0
    assert 0.0 <= d["overlap_efficiency"] <= 1.0
    assert ph.total > 0.0


def test_warm_concurrency_zero_retraces_zero_reuploads(session):
    """8 threads re-running the warm query concurrently: ZERO new traces
    (per-signature build locks make the compile cache single-flight) and
    ZERO re-uploads (every thread reuses the same device arrays) — the
    serving-throughput claim rests on the warm path staying warm under
    concurrency, not just in a single-threaded loop."""
    import threading
    eng, s = session
    rows_cold = s.query(SQL).rows          # cold: trace + first touch
    assert s.query(SQL).rows == rows_cold  # specialized: the statement
    ent = _entry(eng)                      # program's trace
    dev_ids = _storage_ids(ent)
    traces = compile_cache.PROGRAM_TRACES

    sessions = []
    for _ in range(8):
        ss = eng.new_session()
        ss.vars["tidb_tpu_engine"] = "on"
        ss.vars["tidb_tpu_row_threshold"] = 1
        ss.vars["tidb_tpu_max_slab_rows"] = 1024
        sessions.append(ss)
    failures = []
    barrier = threading.Barrier(8)

    def worker(k):
        barrier.wait()
        for _ in range(3):
            if sessions[k].query(SQL).rows != rows_cold:
                failures.append(f"thread {k} diverged")

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads), "warm replay hung"
    assert not failures, failures
    assert compile_cache.PROGRAM_TRACES == traces, \
        "concurrent warm replays re-traced a program"
    ent2 = _entry(eng)
    assert ent2 is ent, "concurrent warm replays rebuilt the cache entry"
    assert _storage_ids(ent) == dev_ids, \
        "a column re-uploaded (or re-stacked) under warm concurrency"


def test_repeat_query_zero_retraces_and_no_reupload(session):
    eng, s = session
    rows_cold = s.query(SQL).rows          # cold: trace + first touch
    ent = _entry(eng)
    assert ent.dev, "cold run left no device arrays cached"
    traces = compile_cache.PROGRAM_TRACES

    rows_warm = s.query(SQL).rows          # warm: must reuse everything
    assert compile_cache.PROGRAM_TRACES == traces, \
        "repeated identical query re-traced a program"
    # (the first statement program over the table makes each column's
    # slabs ONE array, on the device: from here on the storage stands)
    dev_ids = _storage_ids(ent)
    assert fragment.LAST_PHASES.as_dict()["upload_s"] == 0.0
    rows_warm = s.query(SQL).rows
    ent2 = _entry(eng)
    assert ent2 is ent, "repeated query rebuilt the cache entry"
    assert _storage_ids(ent) == dev_ids, \
        "a column re-uploaded (or re-stacked) on a warm repeat"
    assert sorted(map(str, rows_warm)) == sorted(map(str, rows_cold))
    # warm run uploads nothing: its phase record shows no upload seconds
    ph = fragment.LAST_PHASES
    assert ph is not None and ph.as_dict()["upload_s"] == 0.0


def test_warm_selective_scan_launches_only_surviving_slabs():
    """Zone-map slab skipping: a selective predicate over a sorted column
    launches exactly `surviving_slabs + 1` programs at its first execution
    (one partial per surviving slab + the merge) and ONE statement program
    over the surviving slabs once warm, re-uploads ZERO bytes, and the
    Chrome trace carries NO compute spans for the skipped slabs — the skip
    is free, not merely cheap."""
    import json
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    s.execute("CREATE TABLE q (a BIGINT, b BIGINT)")
    s.execute("INSERT INTO q VALUES " +
              ",".join(f"({i}, {i % 7})" for i in range(3072)))
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    s.vars["tidb_tpu_max_slab_rows"] = 1024   # 3 slabs, sorted → partitioned
    sel = "SELECT COUNT(*), SUM(a) FROM q WHERE a >= 1024"
    full = "SELECT COUNT(*), SUM(a) FROM q"
    agg_slabs._SPEC_CACHE.clear()
    rows_cold = s.query(sel).rows              # cold: encode + upload
    surviving = 2
    assert s.last_guard.phases.programs_launched == surviving + 1
    tid = eng.catalog.info_schema.table("q").id
    ent = next(e for (_d, sid, t, _p), e in dc.CACHE.items()
               if sid == id(eng.store) and t == tid)
    # cold-pruned slab 0 committed as a hole (None placeholder): its
    # encode+upload never happened at all
    assert any(t is None for slabs in ent.dev.values() for t in slabs), \
        "cold prune must leave holes, not upload pruned slabs"
    assert s.query(sel).rows == rows_cold      # traces the two-slab program
    traces = compile_cache.PROGRAM_TRACES
    # (its build stacked the RESIDENT slabs of each column: a hole stays one)
    assert all(col.is_stacked and col.holes() == {0}
               for col in ent.dev.values())
    dev_ids = _storage_ids(ent)

    rows_warm = s.query(sel).rows
    assert rows_warm == rows_cold
    ph = s.last_guard.phases
    assert ph.slabs_skipped == 1, "slab 0 (a in [0,1023]) must be pruned"
    assert ph.programs_launched == 1, \
        f"warm selective launches: {ph.programs_launched}"
    assert ph.h2d_bytes == 0 and ph.as_dict()["upload_s"] == 0.0
    assert compile_cache.PROGRAM_TRACES == traces, "warm repeat re-traced"
    assert _storage_ids(ent) == dev_ids, \
        "a column re-uploaded (or re-stacked) on a pruned warm repeat"

    # Chrome trace: skipping removes exactly the pruned slabs' launch
    # spans (the unfiltered warm run is the 3-slab baseline)
    s.query(full)                              # warm the unfiltered shape

    def launch_spans(sql):
        doc = json.loads(s.query("TRACE FORMAT='chrome' " + sql).rows[0][0])
        return len([e for e in doc["traceEvents"]
                    if e.get("ph") != "M" and e["cat"] == "launch"])

    assert launch_spans(full) - launch_spans(sel) == ph.slabs_skipped


def test_warm_read_after_appends_no_base_reupload_one_extra_launch(session):
    """The HTAP write-path pin: K single-row appends between two warm
    reads must cost the reader ONE delta-slab upload and at most ONE
    extra program launch over a first execution's (the delta slab's
    partial) — ZERO base slabs re-encoded or re-uploaded (they are shared
    by identity across delta generations), and the second warm read
    uploads nothing at all and is ONE launch again."""
    eng, s = session
    s.vars["tidb_tpu_compaction"] = "off"     # no async rebuild mid-test
    agg_slabs._SPEC_CACHE.clear()
    s.query(SQL)                               # cold: trace + first touch
    base_launches = s.last_guard.phases.programs_launched   # slabs + 1
    s.query(SQL)                               # warm baseline
    assert s.last_guard.phases.programs_launched == 1
    ent = _entry(eng)
    n_base = ent.base_slabs
    base_ids = _storage_ids(ent, base_only=True)

    K = 4
    for k in range(K):
        # in-range values: a within the base FoR bounds, c in the base
        # dictionary — the appends must EXTEND, not rebuild
        s.query(f"INSERT INTO p VALUES ({40 + k}, 0.5, 'ant')")

    rows = s.query(SQL).rows                   # pays the one delta upload
    ent2 = _entry(eng)
    assert ent2.is_delta and ent2.delta_rows == K, \
        "appends must ride the delta extension, not a rebuild"
    assert _storage_ids(ent2, base_only=True) == base_ids, \
        "base slabs re-uploaded (or re-stacked)"
    ph = s.last_guard.phases
    assert ph.programs_launched <= base_launches + 1, \
        (f"delta merge cost {ph.programs_launched - base_launches} "
         f"extra launches (max 1: the delta-slab partial)")

    rows2 = s.query(SQL).rows                  # fully warm again
    ph2 = s.last_guard.phases
    assert ph2.h2d_bytes == 0 and ph2.as_dict()["upload_s"] == 0.0, \
        "second warm read after appends must upload nothing"
    assert ph2.programs_launched == 1
    assert sorted(map(str, rows2)) == sorted(map(str, rows))
    # and the rows are RIGHT: the appended 'ant' rows are visible
    got = {r[0]: r[1] for r in rows}
    s.vars["tidb_tpu_engine"] = "off"
    want = {r[0]: r[1] for r in s.query(SQL).rows}
    s.vars["tidb_tpu_engine"] = "on"
    assert got == want
