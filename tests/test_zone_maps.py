"""Per-slab zone maps + host-side slab skipping (executor/zonemap.py
and its wiring through device_cache / fragment / dist_fragment).

Pinned invariants:

* the conjunct evaluator is sound per-op: `_range_excludes` prunes a
  slab only when NO value in [lo, hi] can pass, and `column_stats`
  reports per-slab min/max/null-count/rows in the compared space;
* skipped-vs-unskipped results are byte-exact against the CPU oracle
  for every comparison shape (range, BETWEEN, IN, string equality over
  dict codes, floats, FoR negatives, delta PKs) across the chain, tree,
  fused-pipeline and both distributed executors;
* NULL semantics are Kleene-correct: a NULL-only slab is prunable by
  any comparison and by IS NOT NULL, a no-NULL slab by IS NULL;
* all slabs pruned means ZERO program launches and still the correct
  result — the agg identity (COUNT 0, SUM/MIN/MAX NULL) for a global
  aggregate, the empty rowset for GROUP BY / ORDER BY roots;
* pruning is an encode-time artifact: `tidb_tpu_compression = off`
  disables it entirely (slabs_skipped stays 0) while results agree;
* a stale zone map at the prune decision (failpoint `zone-map-stale`)
  surfaces as a typed LayoutError and a warned CPU fallback with oracle
  rows — never silently skipped live slabs;
* a layout re-choice EVICTS the per-digest specialization entry (its
  cached signature names programs that decode the old layouts): flipping
  `tidb_tpu_compression` swaps the entry's layout signature in place and
  keeps answering the oracle;
* sorted fully-valid PK columns choose the delta layout and round-trip
  byte-exactly through numpy AND jnp decode; the `group_heavy` workload
  hint raises the dictionary cap and wins width ties.
"""

import numpy as np
import pytest

from tidb_tpu.chunk import compress
from tidb_tpu.errors import LayoutError
from tidb_tpu.executor import run_to_completion, zonemap
from tidb_tpu.executor.builder import build
from tidb_tpu.executor.fragment import TpuFragmentExec
from tidb_tpu.executor import agg_slabs
from tidb_tpu.parser import parse
from tidb_tpu.session import Engine
from tidb_tpu.util import failpoint
from tidb_tpu.util.observability import REGISTRY


# ---------------------------------------------------------------------------
# evaluator units
# ---------------------------------------------------------------------------

def test_range_excludes_truth_table():
    ex = zonemap._range_excludes
    # eq: only values outside [lo, hi] are impossible
    assert ex("eq", 10, 20, 9) and ex("eq", 10, 20, 21)
    assert not ex("eq", 10, 20, 10) and not ex("eq", 10, 20, 20)
    # ne: impossible only when the slab is the single value c
    assert ex("ne", 7, 7, 7)
    assert not ex("ne", 7, 8, 7) and not ex("ne", 6, 6, 7)
    # strict/loose bounds at the boundary
    assert ex("lt", 10, 20, 10) and not ex("lt", 9, 20, 10)
    assert ex("le", 11, 20, 10) and not ex("le", 10, 20, 10)
    assert ex("gt", 10, 20, 20) and not ex("gt", 10, 21, 20)
    assert ex("ge", 10, 19, 20) and not ex("ge", 10, 20, 20)


def test_column_stats_per_slab():
    vals = np.arange(10, dtype=np.int64)
    valid = np.ones(10, dtype=bool)
    valid[7:] = False                       # slab 1: rows 4..7 → 7 NULL
    zm = zonemap.column_stats(vals, valid, 4, 10)
    assert zm.n_slabs == 3
    assert zm.rows == [4, 4, 2]
    assert zm.lo[0] == 0 and zm.hi[0] == 3
    assert zm.lo[1] == 4 and zm.hi[1] == 6
    assert zm.nulls == [0, 1, 2]
    # NULL-only slab carries no bounds
    assert zm.lo[2] is None and zm.hi[2] is None and zm.distinct[2] == 0
    # dense int space: the distinct estimate is exact
    assert zm.distinct[0] == 4


# ---------------------------------------------------------------------------
# engine fixtures
# ---------------------------------------------------------------------------

N, SLAB = 4096, 1024   # 4 slabs; every column sorted so slab ranges partition

DEV = {"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": 1,
       "tidb_tpu_max_slab_rows": SLAB}


def _zm_engine():
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    s.execute("CREATE TABLE zm (pk BIGINT, v BIGINT, neg BIGINT, "
              "g BIGINT, w VARCHAR(8), f DOUBLE)")
    rows = [f"({i}, {i}, {i - N}, {i // SLAB}, 'w{i // SLAB}', {i / 10.0})"
            for i in range(N)]
    s.execute("INSERT INTO zm VALUES " + ",".join(rows))
    return eng, s


def q_dev(s, sql, **extra):
    """s.query on the device path → (rows, PhaseTimer). wall_s is added
    only when the device fragment SERVED (no fallback)."""
    vars_ = {**DEV, **extra}
    saved = {k: s.vars.get(k) for k in vars_}
    s.vars.update(vars_)
    try:
        rows = s.query(sql).rows
        ph = s.last_guard.phases
        assert ph.wall_s > 0.0, f"CPU fallback for: {sql}"
        return rows, ph
    finally:
        for k, v in saved.items():
            if v is None:
                s.vars.pop(k, None)
            else:
                s.vars[k] = v


# every predicate shape the pruner understands, with the slab count it
# must prove empty on the sorted fixture (4 slabs of 1024)
PRED_CASES = [
    ("v >= 3072", 3),                       # ge over a delta-layout column
    ("v < 1024", 3),                        # lt keeps only slab 0
    ("v BETWEEN 1100 AND 1200", 3),         # desugared and(ge, le)
    ("v IN (5, 2000)", 2),                  # IN over two slabs
    ("v = 9999999", 4),                     # eq outside every slab
    ("w = 'w2'", 3),                        # string eq over dict codes
    ("w IN ('w0', 'zzz')", 3),              # string IN, one absent item
    ("f < 100.0", 3),                       # float zone map
    ("neg < -3000", 2),                     # FoR negatives (min-referenced)
    ("pk >= 4000", 3),                      # sorted PK (delta layout)
    ("v >= 1024 AND v < 2048", 3),          # conjunction prunes both ends
]


@pytest.mark.parametrize("pred,expect_skip", PRED_CASES)
def test_pruning_byte_exact_chain(pred, expect_skip):
    eng, s = _zm_engine()
    q = (f"SELECT COUNT(*), COUNT(v), SUM(v), MIN(pk), MAX(f) "
         f"FROM zm WHERE {pred}")
    oracle = s.query(q).rows
    cold, ph_cold = q_dev(s, q)
    assert cold == oracle
    assert ph_cold.slabs_skipped == expect_skip, pred
    # cold prune skipped the pruned slabs' encode+upload entirely
    if expect_skip:
        assert ph_cold.h2d_skipped_bytes > 0
    warm, ph_warm = q_dev(s, q)
    assert warm == oracle
    assert ph_warm.slabs_skipped == expect_skip
    assert ph_warm.h2d_bytes == 0, "warm repeat must re-upload nothing"


def test_pruning_counters_reach_registry():
    eng, s = _zm_engine()
    key = ("tidb_tpu_slabs_skipped_total",
           (("device", "0"), ("engine", "device")))
    before = REGISTRY.counters.get(key, 0)
    h2d_before = sum(h[1] for (name, _l), h in REGISTRY.hists.items()
                     if name == "tidb_tpu_h2d_skipped_bytes")
    _, ph = q_dev(s, "SELECT COUNT(*) FROM zm WHERE v >= 3072")
    assert REGISTRY.counters.get(key, 0) == before + ph.slabs_skipped > before
    h2d_after = sum(h[1] for (name, _l), h in REGISTRY.hists.items()
                    if name == "tidb_tpu_h2d_skipped_bytes")
    assert h2d_after - h2d_before == ph.h2d_skipped_bytes > 0


# ---------------------------------------------------------------------------
# all slabs pruned: zero launches, correct identities
# ---------------------------------------------------------------------------

def test_all_pruned_global_agg_identity():
    eng, s = _zm_engine()
    q = ("SELECT COUNT(*), COUNT(v), SUM(v), MIN(v), MAX(v), AVG(f) "
         "FROM zm WHERE v > 100000")
    oracle = s.query(q).rows
    assert oracle == [(0, 0, None, None, None, None)]
    cold, _ = q_dev(s, q)
    assert cold == oracle
    warm, ph = q_dev(s, q)
    assert warm == oracle
    assert ph.slabs_skipped == 4
    assert ph.programs_launched == 0, "pruned slabs must not launch"
    assert ph.h2d_bytes == 0


def test_all_pruned_grouped_and_order_empty():
    eng, s = _zm_engine()
    for q in ("SELECT g, COUNT(*), SUM(v) FROM zm WHERE v > 100000 "
              "GROUP BY g",
              "SELECT v FROM zm WHERE v > 100000 ORDER BY v LIMIT 5"):
        assert s.query(q).rows == []
        cold, _ = q_dev(s, q)
        assert cold == []
        warm, ph = q_dev(s, q)
        assert warm == []
        assert ph.programs_launched == 0


# ---------------------------------------------------------------------------
# NULL-only slabs vs IS [NOT] NULL (Kleene soundness)
# ---------------------------------------------------------------------------

def _null_slab_engine():
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    s.execute("CREATE TABLE nl (a BIGINT, b BIGINT)")
    rows = [f"(NULL, {i})" if i < SLAB else f"({i}, {i})"
            for i in range(2 * SLAB)]
    s.execute("INSERT INTO nl VALUES " + ",".join(rows))
    return eng, s


@pytest.mark.parametrize("pred,expect_skip", [
    ("a IS NOT NULL", 1),       # slab 0 is entirely NULL
    ("a IS NULL", 1),           # slab 1 has zero NULLs
    ("a >= 0", 1),              # any comparison filters a NULL-only slab
    ("a IS NULL AND b < 500", 1),
    ("NOT (a IS NULL)", 1),
])
def test_null_slab_pruning(pred, expect_skip):
    eng, s = _null_slab_engine()
    q = f"SELECT COUNT(*), COUNT(a), SUM(b) FROM nl WHERE {pred}"
    oracle = s.query(q).rows
    got, ph = q_dev(s, q)
    assert got == oracle
    assert ph.slabs_skipped == expect_skip, pred


# ---------------------------------------------------------------------------
# compression off: no zone maps, no pruning, same answers
# ---------------------------------------------------------------------------

def test_pruning_off_without_compression():
    eng, s = _zm_engine()
    q = "SELECT COUNT(*), SUM(v) FROM zm WHERE v >= 3072"
    oracle = s.query(q).rows
    got, ph = q_dev(s, q, tidb_tpu_compression="off")
    assert got == oracle
    assert ph.slabs_skipped == 0
    assert ph.h2d_skipped_bytes == 0
    # and compression back on prunes again, same rows
    got_on, ph_on = q_dev(s, q)
    assert got_on == oracle and ph_on.slabs_skipped == 3


# ---------------------------------------------------------------------------
# tree / fused-pipeline / distributed paths
# ---------------------------------------------------------------------------

def _with_dim(s):
    s.execute("CREATE TABLE dim (id BIGINT, tag VARCHAR(8))")
    s.execute("INSERT INTO dim VALUES (0,'a'),(1,'b'),(2,'c'),(3,'d')")


JOIN_Q = ("SELECT dim.tag, COUNT(*), SUM(zm.v) FROM zm "
          "JOIN dim ON zm.g = dim.id WHERE zm.v >= 3072 "
          "GROUP BY dim.tag ORDER BY dim.tag")


def test_pruning_byte_exact_fused_pipeline():
    eng, s = _zm_engine()
    _with_dim(s)
    oracle = s.query(JOIN_Q).rows
    got, ph = q_dev(s, JOIN_Q)
    assert got == oracle
    assert ph.slabs_skipped == 3


def test_pruning_byte_exact_tree_path():
    eng, s = _zm_engine()
    _with_dim(s)
    oracle = s.query(JOIN_Q).rows
    got, ph = q_dev(s, JOIN_Q, tidb_tpu_fused_pipeline="off")
    assert got == oracle
    assert ph.slabs_skipped == 3


def test_pruning_byte_exact_staged_dist():
    eng, s = _zm_engine()
    q = "SELECT g, COUNT(*), SUM(v) FROM zm WHERE v >= 3072 GROUP BY g"
    oracle = sorted(s.query(q).rows, key=str)
    got, ph = q_dev(s, q, tidb_tpu_dist=4)
    assert sorted(got, key=str) == oracle
    # rank-sliced zone maps: 3 of the 4 sorted rank slices are empty
    assert ph.slabs_skipped == 3
    assert ph.h2d_skipped_bytes > 0


def test_byte_exact_monolithic_dist():
    eng, s = _zm_engine()
    q = "SELECT g, COUNT(*), SUM(v) FROM zm WHERE v >= 3072 GROUP BY g"
    oracle = sorted(s.query(q).rows, key=str)
    got, _ph = q_dev(s, q, tidb_tpu_dist=4, tidb_tpu_dist_staged="off")
    assert sorted(got, key=str) == oracle


# ---------------------------------------------------------------------------
# stale zone map: typed error → warned CPU fallback, oracle rows
# ---------------------------------------------------------------------------

def test_stale_zone_map_falls_back_to_cpu():
    eng, s = _zm_engine()
    q = "SELECT COUNT(*), SUM(v) FROM zm WHERE v >= 3072"
    oracle = s.query(q).rows
    s.vars.update(DEV)
    failpoint.enable("zone-map-stale", value="test: stale map")
    try:
        plan = s._plan(parse(q)[0])
        root = build(plan)
        chunks = run_to_completion(root, s._exec_ctx())
        got = [r for ch in chunks for r in ch.rows()]
        assert got == oracle, "fallback must still return oracle rows"
        frags = []

        def walk(e):
            if isinstance(e, TpuFragmentExec):
                frags.append(e)
            for c in getattr(e, "children", []):
                walk(c)

        walk(root)
        assert frags
        for f in frags:
            assert not f.used_device, "stale zone map must not serve"
            assert "zone map" in (f.fallback_reason or ""), \
                f.fallback_reason
    finally:
        failpoint.disable("zone-map-stale")
        for k in DEV:
            s.vars.pop(k, None)
    # disarmed: the device path prunes and serves the same rows again
    got2, ph = q_dev(s, q)
    assert got2 == oracle and ph.slabs_skipped == 3


def test_stale_zone_map_error_is_typed():
    eng, s = _zm_engine()
    q_dev(s, "SELECT COUNT(*) FROM zm WHERE v >= 3072")   # build zone maps
    ent = next(iter(
        __import__("tidb_tpu.executor.device_cache",
                   fromlist=["CACHE"]).CACHE.values()))
    scan = type("S", (), {"filters": [object()]})()
    failpoint.enable("zone-map-stale", value="boom")
    try:
        with pytest.raises(LayoutError, match="zone map"):
            zonemap.prune_slabs(ent, scan)
    finally:
        failpoint.disable("zone-map-stale")


# ---------------------------------------------------------------------------
# specialization cache: layout re-choice evicts, never shadows
# ---------------------------------------------------------------------------

def test_spec_cache_evicted_on_compression_flip():
    eng, s = _zm_engine()
    q = "SELECT g, COUNT(*), SUM(v) FROM zm GROUP BY g ORDER BY g"
    oracle = s.query(q).rows
    got, _ = q_dev(s, q)                    # cold: stores the spec entry
    assert got == oracle
    _, ph = q_dev(s, q)                     # warm: entry serves
    assert ph.specialization_hits >= 1

    def entries():
        return {k: v.get("lay_sig") for k, v in agg_slabs._SPEC_CACHE.items()
                if len(k) > 2 and k[2] == q}
    on_sigs = entries()
    assert on_sigs and all(sig != "-" for sig in on_sigs.values()), on_sigs

    got_off, ph_off = q_dev(s, q, tidb_tpu_compression="off")
    assert got_off == oracle
    off_sigs = entries()
    # the stale compressed-layout entry was EVICTED (not shadowed): every
    # surviving entry for this statement names the raw layout set
    assert off_sigs and all(sig == "-" for sig in off_sigs.values()), \
        (on_sigs, off_sigs)
    # and the raw entry serves warm in turn
    _, ph_off2 = q_dev(s, q, tidb_tpu_compression="off")
    assert ph_off2.specialization_hits >= 1


# ---------------------------------------------------------------------------
# workload-adaptive layouts: delta for sorted PKs, group_heavy dict cap
# ---------------------------------------------------------------------------

def test_sorted_pk_chooses_delta_and_roundtrips():
    from tidb_tpu.ops.jax_env import jnp
    vals = (10_000_000 + np.cumsum(
        np.random.default_rng(7).integers(0, 4, size=3000))).astype(np.int64)
    valid = np.ones(3000, dtype=bool)
    lay, dv = compress.choose_layout(vals, valid)
    assert lay is not None and lay.kind == "delta"
    assert lay.width == 2, "max gap 3 must pack at width 2"
    cap = 4096
    pv = np.zeros(cap, dtype=np.int64)
    pm = np.zeros(cap, dtype=bool)
    pv[:3000], pm[:3000] = vals, valid
    slab = compress.pack_slab(lay, pv, pm)
    assert len(slab) == 3, "delta slabs carry a per-slab base"
    for xp in (np, jnp):
        got_v, got_m = compress.decode_slab(lay, slab, cap, xp)
        assert np.array_equal(np.asarray(got_v)[:3000], vals)
        assert np.array_equal(np.asarray(got_m), pm)


def test_delta_beats_pack_on_dense_sorted_keys():
    # dense sorted ints over a wide range: FoR needs 16 bits, delta 1
    vals = np.arange(50_000, 50_000 + 4000, dtype=np.int64)
    lay, _ = compress.choose_layout(vals, np.ones(4000, dtype=bool),
                                    allow_dict=False)
    assert lay.kind == "delta" and lay.width == 1


def test_delta_requires_sorted_and_fully_valid():
    rng = np.random.default_rng(11)
    unsorted = rng.permutation(np.arange(4000)).astype(np.int64)
    lay, _ = compress.choose_layout(unsorted, np.ones(4000, dtype=bool),
                                    allow_dict=False)
    assert lay.kind == "pack"
    sorted_nulls = np.arange(4000, dtype=np.int64)
    lay2, _ = compress.choose_layout(sorted_nulls,
                                     rng.random(4000) > 0.1,
                                     allow_dict=False)
    assert lay2.kind == "pack"


def test_group_heavy_hint_raises_dict_cap():
    # cardinality above the base cap but under the 4× group-heavy cap,
    # spread sparsely so packing needs the full 32 bits
    rng = np.random.default_rng(13)
    uniq = rng.choice(1 << 20, size=6000, replace=False).astype(np.int64)
    vals = uniq[rng.integers(0, 6000, size=20_000)]
    valid = np.ones(20_000, dtype=bool)
    lay, _ = compress.choose_layout(vals, valid)
    assert lay.kind == "pack", "above the base cap: no dictionary"
    lay2, dv = compress.choose_layout(vals, valid,
                                      hints={"group_heavy": True})
    card = len(np.unique(vals))
    assert card > compress.DICT_CARD_CAP
    assert lay2.kind == "dict" and lay2.card == card
    assert dv is not None and len(dv) == card


def test_group_heavy_hint_wins_width_ties():
    # dense 0..255: pack and dict both land at width 8 — the hint
    # prefers dict (codes feed group factorization directly)
    vals = np.arange(256, dtype=np.int64)[
        np.random.default_rng(5).integers(0, 256, size=5000)]
    valid = np.ones(5000, dtype=bool)
    lay, _ = compress.choose_layout(vals, valid)
    assert lay.kind == "pack" and lay.width == 8
    lay2, _ = compress.choose_layout(vals, valid,
                                     hints={"group_heavy": True})
    assert lay2.kind == "dict" and lay2.width == 8
