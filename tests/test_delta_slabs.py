"""Delta generations: incremental extension of device-cached tables.

A committed write no longer invalidates a cached table wholesale: when
the region diff is expressible as appended rows + tombstones, the cache
grows a NEW generation that shares every base device array with its
predecessor, writes the appended rows into one raw delta slab and clears
the dead rows' bits in per-slab liveness masks (executor/delta.py +
device_emit.emit_delta_append / emit_alive_update). These tests pin:

* oracle equality through inserts, scattered deletes, mixed
  insert+delete on one generation, and deletes that land in the delta
  slab itself (cumulative re-diff);
* base-array SHARING — an extension must not re-upload base slabs;
* the decline ladder — a string outside the global dictionary rebuilds
  from scratch, never a wrong merge, and is counted by its gate; what
  USED to decline (a hole, a key out of the bounds or the packed range, a
  tombstone on or an unordered append into a table with a `delta`-kind
  column) extends;
* the `delta-merge-stale` failpoint → typed LayoutError → warned CPU
  fallback with oracle rows, then a clean extension once disarmed;
* threshold-scheduled compaction: the rebuilt generation drops
  `is_delta`, re-chooses layouts, and answers the oracle; a fault at
  `compaction-commit` abandons the rebuild (buffers deleted) while the
  old base+delta generation keeps serving byte-exactly, and the next
  extension re-schedules the job (heals);
* eviction/invalidation of a delta generation deletes the DELTA device
  arrays too — no HBM leak (the satellite-2 guarantee).
"""

import numpy as np
import pytest

from tidb_tpu.executor import delta
from tidb_tpu.executor import device_cache as dc
from tidb_tpu.session import Engine
from tidb_tpu.util import failpoint
from tidb_tpu.util.observability import REGISTRY


def _engine(compression="on"):
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    s.execute("CREATE TABLE t (a BIGINT, b BIGINT, c VARCHAR(10))")
    # non-monotonic b: choose_layout picks pack/raw here (the `delta`
    # layout has tests of its own below)
    s.execute("INSERT INTO t VALUES " + ",".join(
        f"({i % 40}, {(i * 7919) % 5000}, 'k{i % 5}')"
        for i in range(3000)))
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    s.vars["tidb_tpu_compression"] = compression
    s.vars["tidb_tpu_compaction"] = "off"   # drain by hand, deterministic
    return eng, s


Q = "SELECT a, COUNT(*), SUM(b) FROM t GROUP BY a ORDER BY a"


def _oracle(s, q=Q):
    s.vars["tidb_tpu_engine"] = "off"
    try:
        return s.query(q).rows
    finally:
        s.vars["tidb_tpu_engine"] = "on"


def _entry(eng, name="t"):
    tid = eng.catalog.info_schema.table(name).id
    for (_dev, sid, t, parts), ent in dc.CACHE.items():
        # (a plain consumer's copy sits beside it, tagged in `parts`)
        plain = parts is not None and parts[0] == "plain"
        if sid == id(eng.store) and t == tid and not plain:
            return ent
    raise AssertionError(f"table {name} not cached")


def _base_ids(ent):
    """id() of every base-slab device array, per column."""
    return {i: [(s, id(a)) for s, a in col.arrays() if s < ent.base_slabs]
            for i, col in ent.dev.items()}


@pytest.mark.parametrize("compression", ["on", "off"])
def test_insert_extends_without_reupload(compression):
    eng, s = _engine(compression)
    s.query(Q)
    ent0 = _entry(eng)
    ids0 = _base_ids(ent0)
    s.query("INSERT INTO t VALUES (3, 1234, 'k2')")
    rows = s.query(Q).rows
    ent1 = _entry(eng)
    assert ent1 is not ent0 and ent1.is_delta
    assert ent1.delta_rows == 1
    # no tombstones → every base device array is SHARED, not re-encoded
    assert _base_ids(ent1) == ids0, "extension re-uploaded base slabs"
    assert rows == _oracle(s)


@pytest.mark.parametrize("compression", ["on", "off"])
def test_tombstones_and_mixed_writes(compression):
    eng, s = _engine(compression)
    s.query(Q)
    s.query("DELETE FROM t WHERE b % 97 = 3")
    rows = s.query(Q).rows
    ent = _entry(eng)
    assert ent.is_delta and ent.dead_rows > 0 and ent.alive is not None
    assert rows == _oracle(s)
    # mixed insert + delete on the SAME generation
    s.query("INSERT INTO t VALUES (3, 1234, 'k2')")
    s.query("DELETE FROM t WHERE b = 4998")
    assert s.query(Q).rows == _oracle(s)
    # delete the row that lives in the DELTA slab (cumulative re-diff)
    s.query("DELETE FROM t WHERE b = 1234 AND a = 3")
    assert s.query(Q).rows == _oracle(s)
    # dictionary-string path still correct on the delta generation
    q2 = "SELECT c, COUNT(*) FROM t WHERE a < 10 GROUP BY c ORDER BY c"
    assert s.query(q2).rows == _oracle(s, q2)


def test_new_dictionary_string_declines_to_rebuild():
    eng, s = _engine()
    q2 = "SELECT c, COUNT(*) FROM t GROUP BY c ORDER BY c"
    s.query(q2)                     # cache covers the dictionary column
    # 'zzz' is not in the base dictionary: the extension must DECLINE
    # and the open falls back to a full rebuild — never a wrong merge
    s.query("INSERT INTO t VALUES (1, 1, 'zzz')")
    rows = s.query(q2).rows
    ent = _entry(eng)
    assert not ent.is_delta, "un-encodable append must rebuild, not merge"
    assert rows == _oracle(s, q2)
    assert s.query(Q).rows == _oracle(s)


def test_delta_version_in_plan_keys():
    """A generation carries the commit version it serves (micro-batches
    of one generation share it), and NOT in what the specialization
    cache keys: generations of one base build share their lineage, so a
    write costs the next statement no specialization and no trace."""
    from tidb_tpu.executor import agg_slabs, compile_cache
    eng, s = _engine()
    s.query(Q)
    e0 = _entry(eng)
    s.query("INSERT INTO t VALUES (3, 1234, 'k2')")
    s.query(Q)                  # first delta generation: its programs
    s.query(Q)                  # … and, specialized, its statement program
    e1 = _entry(eng)
    assert e1.delta_version > e0.delta_version
    assert e1.lineage == e0.lineage
    s.query("INSERT INTO t VALUES (4, 1235, 'k2')")
    t0 = compile_cache.PROGRAM_TRACES
    s.query(Q)
    e2 = _entry(eng)
    assert e2.delta_version > e1.delta_version
    assert agg_slabs._ent_geometry(e2) == agg_slabs._ent_geometry(e1)
    assert compile_cache.PROGRAM_TRACES == t0, "a write must not trace"


def test_delta_merge_stale_fault_warned_cpu_fallback():
    eng, s = _engine()
    s.query(Q)
    s.query("INSERT INTO t VALUES (3, 1234, 'k2')")
    oracle = _oracle(s)
    failpoint.enable("delta-merge-stale", value="test: stale diff")
    try:
        rows = s.query(Q).rows
        assert failpoint.hits("delta-merge-stale") > 0
        assert rows == oracle, "fallback must still return oracle rows"
    finally:
        failpoint.disable("delta-merge-stale")
    # disarmed: the extension engages and keeps answering the oracle
    rows2 = s.query(Q).rows
    assert rows2 == oracle
    ent = _entry(eng)
    assert ent.is_delta and ent.delta_rows == 1


@pytest.fixture
def eager_compaction(monkeypatch):
    """Compaction due after four appended rows: the trigger is a share of
    the delta slab's capacity (`delta.COMPACT_FILL`), no session option."""
    monkeypatch.setattr(delta, "COMPACT_FILL", 4 / delta.MIN_DELTA_CAP)


def test_compaction_rebuilds_and_drops_delta(eager_compaction):
    eng, s = _engine()
    s.query(Q)
    for i in range(5):
        s.query(f"INSERT INTO t VALUES ({i % 40}, {i * 7 % 5000}, 'k1')")
    s.query(Q)
    assert _entry(eng).is_delta
    assert delta.pending_compactions() >= 1
    oracle = _oracle(s)
    assert delta.run_pending_compactions() == 1
    ent = _entry(eng)
    assert not ent.is_delta, "compaction must fold the delta into base"
    assert ent.delta_rows == 0 and ent.dead_rows == 0 \
        and ent.alive is None
    assert s.query(Q).rows == oracle
    key = ("tidb_tpu_compactions_total",
           (("cause", "delta-fill"),
            ("table", str(eng.catalog.info_schema.table("t").id))))
    assert REGISTRY.counters.get(key, 0) >= 1


def test_compaction_commit_fault_old_generation_serves(eager_compaction):
    eng, s = _engine()
    s.query(Q)
    s.query("DELETE FROM t WHERE b % 499 = 7")   # tombstones too
    for i in range(5):
        s.query(f"INSERT INTO t VALUES ({i % 40}, {i * 7 % 5000}, 'k1')")
    warm = s.query(Q).rows
    ent0 = _entry(eng)
    assert ent0.is_delta and delta.pending_compactions() >= 1
    failpoint.enable("compaction-commit",
                     raise_=RuntimeError("chaos: compaction fault"))
    try:
        assert delta.run_pending_compactions() == 0
    finally:
        failpoint.disable("compaction-commit")
    assert failpoint.hits("compaction-commit") > 0
    # the old base+delta generation is UNTOUCHED and serves byte-exactly
    assert _entry(eng) is ent0
    assert s.query(Q).rows == warm == _oracle(s)
    # the next extension past the threshold re-schedules — compaction
    # HEALS once the fault clears
    s.query("INSERT INTO t VALUES (9, 99, 'k0')")
    s.query(Q)
    assert delta.pending_compactions() >= 1
    assert delta.run_pending_compactions() == 1
    ent2 = _entry(eng)
    assert not ent2.is_delta
    assert s.query(Q).rows == _oracle(s)


def test_compaction_skips_fresh_and_evicted_entries(monkeypatch):
    monkeypatch.setattr(delta, "COMPACT_FILL", 1 / delta.MIN_DELTA_CAP)
    eng, s = _engine()
    s.query(Q)
    s.query("INSERT INTO t VALUES (3, 1234, 'k2')")
    s.query(Q)
    assert delta.pending_compactions() == 1
    dc.clear()                      # entry evicted before the drain runs
    assert delta.run_pending_compactions() == 0, \
        "an evicted entry must not be rebuilt behind the cache's back"


def test_invalidation_frees_delta_device_arrays():
    """Satellite: evicting a delta generation must jax.Array.delete()
    the delta-slab and rewritten-keep arrays too — device memory for a
    dropped generation is freed NOW, not at GC time."""
    eng, s = _engine()
    s.query(Q)
    s.query("DELETE FROM t WHERE b % 97 = 3")
    s.query("INSERT INTO t VALUES (3, 1234, 'k2')")
    s.query(Q)
    ent = _entry(eng)
    assert ent.is_delta
    arrays = [a for _s, a in ent._arrays()]
    assert arrays and len(arrays) > sum(
        len(t) for slabs in ent.dev.values() for t in slabs), \
        "the liveness masks are the generation's arrays too"
    tid = eng.catalog.info_schema.table("t").id
    dc.invalidate(tid)
    leaked = [a for a in arrays if not a.is_deleted()]
    assert not leaked, \
        f"{len(leaked)} delta-generation arrays survived invalidation"


@pytest.mark.parametrize("other_reader", [True, False])
def test_a_rebuild_never_frees_what_another_statement_computes_on(
        other_reader, monkeypatch):
    """Two readers with two snapshots: the one whose extension declines
    finds the OTHER's generation installed, useless for its snapshot, and
    rebuilds over it. That generation shares its base arrays with what the
    other statement is computing on: while another thread protects the
    table they are freed by their last reference (the statement read
    `Array has been deleted` and fell back to the host); with nobody else
    on the table they are freed at once, as an evicted entry's are."""
    import copy
    import threading
    eng, s = _engine()
    s.query(Q)
    base = _entry(eng)
    shared = [a for _s, a in base._arrays()]
    tid = eng.catalog.info_schema.table("t").id

    def declined(ctx, scan, extend_from, *a, **k):
        # the other reader's install, for a snapshot that is not ours
        foreign = copy.copy(extend_from)
        foreign.td = object()
        key = next(k_ for k_, e in dc.CACHE.items() if e is extend_from)
        dc.CACHE[key] = foreign
        return None

    monkeypatch.setattr(delta, "extend_entry", declined)
    computing, done = threading.Event(), threading.Event()

    def other():
        with dc.protect_tables([(id(eng.store), tid)]):
            computing.set()
            done.wait(60)

    th = threading.Thread(target=other)
    if other_reader:
        th.start()
        assert computing.wait(10)
    try:
        s.query("INSERT INTO t VALUES (3, 1234, 'k2')")
        assert s.query(Q).rows == _oracle(s)
        assert _entry(eng) is not base and not _entry(eng).is_delta
        deleted = [a.is_deleted() for a in shared]
        assert not any(deleted) if other_reader else all(deleted)
    finally:
        done.set()
        if other_reader:
            th.join()


def test_pod_partitioned_delta_eviction_frees_every_owner():
    """Satellite 2: a pod-partitioned (dev=-1) delta generation spreads
    its slabs over SEVERAL owner devices — the delta slab and rewritten
    tombstone slabs included. Invalidation must jax.Array.delete() the
    buffers on EVERY owner, not just the tail owner that holds the
    delta slab; a survivor-device array that slips through is an HBM
    leak that outlives the table."""
    eng, s = _engine()
    s.execute("CREATE TABLE pt (a BIGINT, b BIGINT, c VARCHAR(10))")
    for base in range(0, 8192, 1024):
        s.execute("INSERT INTO pt VALUES " + ",".join(
            f"({i % 40}, {(i * 7919) % 5000}, 'k{i % 5}')"
            for i in range(base, base + 1024)))
    s.vars["tidb_tpu_max_slab_rows"] = 1024
    s.vars["tidb_tpu_partition_min_rows"] = 1000
    qp = "SELECT a, COUNT(*), SUM(b) FROM pt GROUP BY a ORDER BY a"
    s.query(qp)
    # tombstones land in non-tail slabs too, so liveness masks sit on
    # non-tail owners alongside the tail-pinned delta slab
    s.query("DELETE FROM pt WHERE b % 97 = 3")
    s.query("INSERT INTO pt VALUES (3, 1234, 'k2')")
    assert s.query(qp).rows == _oracle(s, qp)
    ent = _entry(eng, "pt")
    assert ent.is_delta
    assert len(set(ent.owners)) > 1, \
        "pod entry must span several owners for this test to bite"
    arrays = [a for _s, a in ent._arrays()]
    assert len({_dev_of(a) for a in ent.alive}) > 1, \
        "each slab's liveness mask lives with its owner"
    assert len({_dev_of(a) for a in arrays}) > 1, \
        "delta generation's arrays must live on more than one device"
    tid = eng.catalog.info_schema.table("pt").id
    dc.invalidate(tid)
    leaked = [a for a in arrays if not a.is_deleted()]
    assert not leaked, (
        f"{len(leaked)} arrays survived invalidation on devices "
        f"{sorted({str(_dev_of(a)) for a in leaked})} — every owner "
        f"device must be freed, not just the delta slab's tail owner")


def _dev_of(a):
    ds = getattr(a, "devices", None)
    if callable(ds):
        got = list(a.devices())
        assert len(got) == 1
        return got[0]
    return a.device


def test_delta_rows_in_phase_accounting():
    eng, s = _engine()
    s.query(Q)
    s.query("INSERT INTO t VALUES (3, 1234, 'k2')")
    s.query(Q)
    ph = s.last_guard.phases
    assert ph.as_dict().get("delta_rows", 0) == 1


# ---------------------------------------------------------------------------
# what used to decline, extends — one case per former gate
# ---------------------------------------------------------------------------

def _declines():
    return sum(v for (name, _l), v in REGISTRY.counters.items()
               if name == "tidb_tpu_delta_declines_total")


def _extensions():
    return sum(v for (name, _l), v in REGISTRY.counters.items()
               if name == "tidb_tpu_delta_extensions_total")


def _sorted_engine():
    """`d` arrives sorted, so it takes the `delta` layout (differences of
    neighbours); `k` is dense 0..n-1: bounds and a packed width with no
    room above n-1 = 4095."""
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    s.execute("CREATE TABLE f (k BIGINT, d DATE, v BIGINT)")
    s.execute("INSERT INTO f VALUES " + ",".join(
        f"({i}, '{1995 + i // 1500}-{1 + (i // 125) % 12:02d}-"
        f"{1 + (i // 5) % 25:02d}', {(i * 7919) % 5000})"
        for i in range(4096)))
    for k, v in dict(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                     tidb_tpu_compaction="off",
                     tidb_tpu_max_slab_rows=1024).items():
        s.vars[k] = v
    return eng, s


QF = "SELECT COUNT(*), SUM(v), MIN(k), MAX(k) FROM f"
QF_PRUNED = QF + " WHERE d >= '1997-06-01'"
QF_GROUPED = "SELECT k % 7, COUNT(*), SUM(v) FROM f GROUP BY k % 7 " \
             "ORDER BY 1"

FORMER_GATES = {
    # a pruned first touch commits holes: the entry extended all the same
    "hole": (QF_PRUNED, ["INSERT INTO f VALUES (17, '1997-07-01', 5)"],
             QF_PRUNED),
    # a key above every key loaded: outside the column's recorded bounds
    "out_of_bounds_key": (QF, ["INSERT INTO f VALUES (4096, '1996-01-01', "
                               "5), (9000, '1996-01-02', 6)"], QF),
    # ... and outside what the packed width (12 bits over 0..4095) holds
    "out_of_pack_range_key": (
        QF_GROUPED, ["INSERT INTO f VALUES (1048576, '1996-01-01', 5)"],
        QF_GROUPED),
    # tombstones in every slab of a table with a `delta`-kind column
    "tombstone_on_delta_kind": (QF, ["DELETE FROM f WHERE k % 97 = 3"], QF),
    # appended dates in no order, into the `delta`-kind column
    "unordered_append_into_delta_kind": (
        QF, ["INSERT INTO f VALUES (5000, '1997-03-03', 1), "
             "(5001, '1995-01-01', 2), (5002, '1996-08-09', 3)"],
        QF_PRUNED),
}


@pytest.mark.parametrize("gate", sorted(FORMER_GATES))
def test_what_used_to_decline_extends(gate):
    first, writes, then = FORMER_GATES[gate]
    eng, s = _sorted_engine()
    s.query(first)
    ent0 = _entry(eng, "f")
    assert any(l is not None and l.kind == "delta"
               for l in ent0.layouts.values()), "no `delta`-kind column"
    if gate == "hole":
        assert ent0.holes, "the pruned first touch left no hole"
    ext0, dec0 = _extensions(), _declines()
    for w in writes:
        s.query(w)
    rows = s.query(then).rows
    ent1 = _entry(eng, "f")
    assert _extensions() == ext0 + 1 and _declines() == dec0
    assert ent1.is_delta and ent1.lineage == ent0.lineage
    assert rows == _oracle(s, then)
    # and every other statement over the same generation agrees too
    for q in (QF, QF_PRUNED, QF_GROUPED):
        assert s.query(q).rows == _oracle(s, q), q
    assert _declines() == dec0


def test_a_string_outside_the_dictionary_is_a_counted_decline():
    eng, s = _engine()
    q2 = "SELECT c, COUNT(*) FROM t GROUP BY c ORDER BY c"
    s.query(q2)
    key = ("tidb_tpu_delta_declines_total", (("gate", "dictionary"),))
    before = REGISTRY.counters.get(key, 0)
    s.query("INSERT INTO t VALUES (1, 1, 'zzz')")
    assert s.query(q2).rows == _oracle(s, q2)
    assert REGISTRY.counters.get(key, 0) == before + 1


def test_a_plain_consumer_gets_a_counted_rebuild():
    """An ORDER BY chain assumes live prefixes and uniform slabs: over a
    delta generation it gets a rebuild, counted by gate `consumer` and
    cached BESIDE the generation, which the aggregates keep extending: a
    mix of both kinds of statement and writes does not thrash one key."""
    eng, s = _engine()
    s.query(Q)
    s.query("INSERT INTO t VALUES (3, 1234, 'k2')")
    s.query(Q)
    ent = _entry(eng)
    assert ent.is_delta
    key = ("tidb_tpu_delta_declines_total", (("gate", "consumer"),))
    before, dec0 = REGISTRY.counters.get(key, 0), _declines()
    q3 = "SELECT a, b FROM t WHERE b > 4990 ORDER BY b, a LIMIT 5"
    assert s.query(q3).rows == _oracle(s, q3)
    assert REGISTRY.counters.get(key, 0) == before + 1
    assert _entry(eng) is ent                    # not evicted
    assert s.query(q3).rows == _oracle(s, q3)    # the plain copy serves
    assert s.query(Q).rows == _oracle(s)
    assert _declines() == dec0 + 1
    # a write: the aggregate extends its generation, the plain consumer
    # rebuilds its copy (counted), neither takes the other's entry
    ext0 = _extensions()
    s.query("DELETE FROM t WHERE b = 4991")
    assert s.query(Q).rows == _oracle(s)
    assert s.query(q3).rows == _oracle(s, q3)
    assert s.query(Q).rows == _oracle(s)
    assert _extensions() == ext0 + 1 and _declines() == dec0 + 2
    assert _entry(eng).is_delta


def test_a_column_first_read_on_a_delta_generation_streams_in():
    """A column the generation did not hold: its base slabs stream from
    the build's parts (no resident row ever moved), its delta slab from
    the ledger — no rebuild."""
    eng, s = _engine()
    q1 = "SELECT COUNT(*), SUM(a) FROM t"
    s.query(q1)
    s.query("INSERT INTO t VALUES (3, 1234, 'k2'), (4, 99, 'k1')")
    s.query("DELETE FROM t WHERE a = 7")
    s.query(q1)
    ent = _entry(eng)
    dec0 = _declines()
    assert ent.is_delta and 1 not in ent.dev
    assert s.query(Q).rows == _oracle(s)          # reads b and c too
    assert _entry(eng) is ent and 1 in ent.dev and _declines() == dec0


def test_the_compaction_trigger_is_measured_on_the_entry():
    eng, s = _engine()
    s.query(Q)
    s.query("INSERT INTO t VALUES (3, 1234, 'k2')")
    s.query(Q)
    ent = _entry(eng)
    assert ent.delta_cap == delta.delta_capacity(ent.slab_cap)
    assert delta.compaction_due(ent) is None
    ent.delta_rows = ent.delta_cap // 2
    assert delta.compaction_due(ent) == "delta-fill"
    ent.delta_rows, ent.dead_rows = 1, ent.base_total // 8
    assert delta.compaction_due(ent) == "dead-rows"
    from tidb_tpu.sysvars import DEFAULT_VARS
    assert "tidb_tpu_delta_compact_rows" not in DEFAULT_VARS


@pytest.mark.parametrize("warmed", [True, False])
def test_a_compaction_commits_though_the_table_moved_on(eager_compaction,
                                                        warmed):
    """The rebuilt generation carries its snapshot's ledger: behind a
    newer commit it is stale. The compactor's warm-up (`delta._warm`: the
    fragments that lately read the table, run once before the swap)
    extends it to the newest snapshot and swaps THAT in; with no reader
    known it is swapped in stale and the next read extends it."""
    eng, s = _engine()
    s.query(Q)
    for i in range(5):
        s.query(f"INSERT INTO t VALUES ({i % 40}, {i * 7 % 5000}, 'k1')")
    s.query(Q)
    assert delta.pending_compactions() == 1
    if not warmed:
        dc._READERS.clear()
    real = dc.stream_slabs

    def moved_on(*a, **k):
        yield from real(*a, **k)
        s.query("INSERT INTO t VALUES (9, 99, 'k0')")   # mid-rebuild

    dc.stream_slabs = moved_on
    try:
        assert delta.run_pending_compactions() == 1
    finally:
        dc.stream_slabs = real
    ent = _entry(eng)
    now = eng.store.snapshot().table_data(
        eng.catalog.info_schema.table("t").id)
    assert ent.is_delta == warmed and (ent.td is now) == warmed
    ext0, dec0 = _extensions(), _declines()
    assert s.query(Q).rows == _oracle(s)
    assert _extensions() == ext0 + (not warmed) and _declines() == dec0
    assert _entry(eng).delta_rows == 1


def test_a_compaction_that_changes_a_layout_compiles_nothing_in_a_statement(
        eager_compaction):
    """Unordered dates folded into the base end the `delta` layout of `d`,
    so every statement that reads it has new programs: the compactor's
    warm-up traces them before the swap — the slab programs and, run
    twice under the statement's text, the ONE statement program of a
    specialized digest — and the statements after it none: neither over
    the swapped generation nor over its next extension, neither at their
    first execution there nor at their second."""
    from tidb_tpu.executor import compile_cache
    eng, s = _sorted_engine()
    s.vars["tidb_tpu_compaction"] = "off"
    for q in (QF, QF_PRUNED, QF_GROUPED):
        s.query(q)
    s.query("INSERT INTO f VALUES " + ",".join(
        f"({5000 + i}, '199{5 + i % 3}-0{1 + i % 9}-11', {i})"
        for i in range(6)))
    for q in (QF, QF_PRUNED, QF_GROUPED):
        s.query(q)
    ent0 = _entry(eng, "f")
    kinds0 = {i: l.kind for i, l in ent0.layouts.items() if l is not None}
    assert "delta" in kinds0.values() and delta.pending_compactions() == 1
    t0 = compile_cache.PROGRAM_TRACES
    assert delta.run_pending_compactions() == 1
    assert compile_cache.PROGRAM_TRACES > t0, "the layouts did not change"
    ent1 = _entry(eng, "f")
    assert ent1.lineage != ent0.lineage and "delta" not in {
        l.kind for l in ent1.layouts.values() if l is not None}
    t1, dec0 = compile_cache.PROGRAM_TRACES, _declines()
    for q in (QF, QF_PRUNED, QF_GROUPED) * 2:
        assert s.query(q).rows == _oracle(s, q), q
    s.query("INSERT INTO f VALUES (6000, '1996-02-02', 5)")
    s.query("DELETE FROM f WHERE k = 17")
    for q in (QF, QF_PRUNED, QF_GROUPED) * 2:
        assert s.query(q).rows == _oracle(s, q), q
    assert compile_cache.PROGRAM_TRACES == t1 and _declines() == dec0


@pytest.mark.parametrize("beside", [True, False])
def test_strings_encode_in_pieces_to_the_same_codes(beside, monkeypatch):
    """A column of strings is sorted and searched in pieces (numpy compares
    objects under the interpreter's lock); on a thread that works beside
    the statements the pieces are small and each is followed by a nap.
    Either way the dictionary and the codes are numpy's over the whole
    column, and the thread's mark ends with its block."""
    monkeypatch.setattr(dc, "STR_CHUNK", 512)
    monkeypatch.setattr(dc, "BESIDE_CHUNK", 64)
    naps = []
    monkeypatch.setattr(dc.time, "sleep", naps.append)
    rng = np.random.default_rng(7)
    vals = np.array([f"s{v:03d}" for v in rng.integers(0, 300, 3000)],
                    dtype=object)

    def encode():
        keys = dc._str_unique(vals)
        return keys, dc._str_codes(keys, vals)

    if beside:
        with pytest.raises(ZeroDivisionError):
            with dc.beside_statements():
                1 / 0
        assert list(dc._str_pieces(10)) == [(0, 512)]     # mark gone
        with dc.beside_statements():
            keys, codes = encode()
        assert len(naps) == 2 * -(-3000 // 64)
        assert set(naps) == {dc.BESIDE_NAP}
    else:
        keys, codes = encode()
        assert naps == []
    want, inverse = np.unique(vals, return_inverse=True)
    assert keys.tolist() == want.tolist()
    assert codes.dtype == np.int32
    assert codes.tolist() == inverse.tolist()
