"""Overlap-aware device runtime (streamed first-touch, resumable
escalation, donation/deletion discipline).

Three invariants pinned here:

* streamed per-slab encoding is BYTE-EXACT against the whole-column
  encode (`_encode_col` / `wide_decimal_limbs` + manual slicing) — the
  global dictionary makes per-slab searchsorted ≡ np.unique's
  return_inverse;
* a group-cap overflow re-executes ONLY the overflowed slabs: the
  checkpointed partials are merged back in, observable through the
  EscalationStats slabs_rerun/slabs_reused counters, and the resumed
  result is byte-exact against a Python oracle;
* evicted cache entries FREE their device buffers immediately
  (jax.Array.is_deleted), so a recompile right after eviction cannot
  double the HBM high-water mark.
"""

import collections
from decimal import Decimal

import numpy as np
import pytest

from tidb_tpu.chunk import compress
from tidb_tpu.executor import device_cache as dc, run_to_completion

from tidb_tpu.executor.builder import build
from tidb_tpu.executor.fragment import TpuFragmentExec
from tidb_tpu.parser import parse
from tidb_tpu.session import Engine


def run_device(s, sql, *, max_slab=None):
    """Execute on the device path, asserting no CPU fallback."""
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    if max_slab is not None:
        s.vars["tidb_tpu_max_slab_rows"] = max_slab
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
        s.vars.pop("tidb_tpu_max_slab_rows", None)


def _cache_entry(eng, table_name):
    tid = eng.catalog.info_schema.table(table_name).id
    for (_dev, sid, t, _parts), ent in dc.CACHE.items():
        if sid == id(eng.store) and t == tid:
            return ent
    raise AssertionError(f"no cache entry for {table_name}")


# ---------------------------------------------------------------------------
# streamed first-touch: byte-exact vs whole-column encode
# ---------------------------------------------------------------------------

def test_streamed_slabs_byte_exact_vs_upload_all():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE st (a BIGINT, b DOUBLE, c VARCHAR(10), "
              "d DECIMAL(10,2), w DECIMAL(30,4))")
    rng = np.random.default_rng(11)
    rows = []
    words = ["ant", "Bee", "cow", "dog", "EEL", "fox"]
    for i in range(3000):
        if i % 97 == 0:
            rows.append("(NULL,NULL,NULL,NULL,NULL)")
            continue
        rows.append(f"({int(rng.integers(-50, 50))},{float(rng.normal()):.6f},"
                    f"'{words[int(rng.integers(0, 6))]}',"
                    f"{float(rng.uniform(0, 500)):.2f},"
                    f"{float(rng.uniform(-9e9, 9e9)):.4f})")
    s.execute("INSERT INTO st VALUES " + ",".join(rows))

    cpu = sorted(s.query(
        "SELECT c, COUNT(a), SUM(b), SUM(d), SUM(w) FROM st GROUP BY c").rows,
        key=str)
    dev = sorted(run_device(
        s, "SELECT c, COUNT(a), SUM(b), SUM(d), SUM(w) FROM st GROUP BY c",
        max_slab=1024), key=str)
    assert len(cpu) == len(dev)
    for r1, r2 in zip(dev, cpu):
        for v1, v2 in zip(r1, r2):
            if isinstance(v2, float):
                assert abs(v1 - v2) <= 1e-6 * max(1.0, abs(v2))
            else:
                assert v1 == v2

    ent = _cache_entry(eng, "st")
    assert ent.n_slabs >= 3, "scenario must actually stream multiple slabs"
    fts = [c.ftype for c in eng.catalog.info_schema.table("st").columns]
    checked = 0
    for i, ft in enumerate(fts):
        if i not in ent.dev:
            continue
        vals, valid = dc.materialize_col(ent, i)
        if ft.is_wide_decimal:
            enc = compress.wide_decimal_limbs(vals, ft.wide_limb_count)
        else:
            enc, dictionary = dc.encode_col(ft, vals, valid)
            if dictionary is None:
                assert ent.dicts[i] is None
            else:
                assert np.array_equal(ent.dicts[i], dictionary)
        assert len(ent.dev[i]) == ent.n_slabs
        lay = ent.layouts.get(i)
        # compressed columns: the resident slab is packed words — decode
        # reproduces the logical column under validity (invalid slots
        # decode to the layout's reference value, not the raw bytes)
        slabs = dc.decoded_slabs(ent, i) if lay is not None \
            else ent.dev[i]
        for si, (dv, dm) in enumerate(slabs):
            start = si * ent.slab_cap
            stop = min(start + ent.slab_cap, ent.total)
            n = stop - start
            hv, hm = np.asarray(dv), np.asarray(dm)
            if ft.is_wide_decimal:
                assert np.array_equal(hv[:, :n], enc[:, start:stop])
                assert not hv[:, n:].any(), "padding must be zero"
            elif lay is not None:
                sel = np.asarray(valid[start:stop])
                assert np.array_equal(hv[:n][sel], enc[start:stop][sel])
            else:
                assert hv.dtype == enc.dtype
                assert np.array_equal(hv[:n], enc[start:stop])
                assert not hv[n:].any(), "padding must be zero"
            assert np.array_equal(hm[:n], valid[start:stop])
            assert not hm[n:].any()
        checked += 1
    assert checked >= 4, f"expected ≥4 streamed columns, saw {checked}"


# ---------------------------------------------------------------------------
# resumable escalation: rerun only the overflowed slabs
# ---------------------------------------------------------------------------

def _resumable_engine(per_slab_distinct, stride=5_000_000):
    """3 slabs × 1024 rows of `r`, per-slab key cardinality from the given
    list, and an 8-row `dim` every `r.b` matches once. Keys are spread by
    `stride` so the packed domain exceeds the perfect-hash gate
    (DOMAIN_CAP) and the agg takes the sort-factorize path whose per-slab
    group counts drive the resumable ladder. A FRESH engine per case with
    auto-analyze pinned off: reliable NDV stats would start the cap high
    enough to dodge the overflow entirely."""
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    s.execute("CREATE TABLE dim (id INT, name VARCHAR(16))")
    s.execute("INSERT INTO dim VALUES " + ",".join(
        f"({i}, 'name{i:02d}')" for i in range(8)))
    s.execute("CREATE TABLE r (k BIGINT, b INT, v BIGINT)")
    oracle = collections.defaultdict(int)
    for slab, nd in enumerate(per_slab_distinct):
        rows = []
        for i in range(1024):
            k = (slab * 1000 + i % nd) * stride
            rows.append(f"({k}, {i % 8}, {i})")
            oracle[k] += i
        s.execute("INSERT INTO r VALUES " + ",".join(rows))
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    s.vars["tidb_tpu_max_slab_rows"] = 1024
    s.vars["tidb_tpu_group_cap"] = 64
    return s, oracle


# The one slab-loop driver (fragment.TpuFragmentExec._run_agg_slabs) over
# its two sources: a chain's slabs and a join tree's probe slabs. A
# COMPUTED key has no bounds to pack into a sort word, so the grouped
# statements keep the per-slab sort-factorize whose resumable ladder they
# test (a bare bounded key with a domain this wide groups by sorted runs,
# which has no per-slab capacity to overflow — tests/test_large_groups.py)
_TREE = "FROM r f JOIN dim d ON f.b = d.id "
RESUMABLE_SQL = {
    ("group", "chain"): "SELECT k + 0, SUM(v) FROM r GROUP BY k + 0",
    ("group", "tree"): "SELECT f.k + 0, SUM(f.v) " + _TREE +
                       "GROUP BY f.k + 0",
    ("pairs", "chain"): "SELECT b, COUNT(DISTINCT k), SUM(v) FROM r "
                        "GROUP BY b ORDER BY b",
    ("pairs", "tree"): "SELECT d.name, COUNT(DISTINCT f.k), SUM(f.v) " +
                       _TREE + "GROUP BY d.name ORDER BY d.name",
}
RESUMABLE_SQL.update({("merged-only", src): RESUMABLE_SQL["group", src]
                      for src in ("chain", "tree")})


@pytest.mark.parametrize("source", ["chain", "tree"])
@pytest.mark.parametrize("rung", ["group", "merged-only", "pairs"])
def test_a_ladder_rung_reruns_only_the_slabs_that_overflowed(rung, source):
    sql = RESUMABLE_SQL[rung, source]
    # group: slab 1 overflows the 64-group cap (200 distinct), slabs 0/2
    # do not — the retry re-executes exactly one slab and merges the two
    # checkpointed partials back untouched. merged-only: every slab fits
    # (60 groups, disjoint key ranges) but the MERGED count (180) does
    # not — the retry reuses every partial and only re-merges
    s, oracle = _resumable_engine(
        (60, 60, 60) if rung == "merged-only" else (10, 200, 10))
    if rung == "pairs":
        # a pair cap (64) below a slab's distinct (b, k) pairs — 40 in
        # slabs 0/2, 200 in slab 1 — must clip, be DETECTED (true counts
        # travel with the clipped sets), resize through the 'pairs' rung
        # to the exact need, re-run the clipped slab and still answer the
        # CPU engine's rows
        s.vars["tidb_tpu_engine"] = "off"
        want = s.query(sql).rows
        s.vars.update({"tidb_tpu_engine": "on",
                       "tidb_tpu_distinct_pair_cap": 64})
        assert s.query(sql).rows == want
    else:
        _check_oracle(s.query(sql).rows, oracle)
    assert s.last_engine == "tpu"
    esc = s.last_guard.escalation
    rerun = 0 if rung == "merged-only" else 1
    assert esc.slabs_rerun == rerun, esc.summary()
    assert esc.slabs_reused == 3 - rerun, esc.summary()
    kind = "pairs" if rung == "pairs" else "group"
    assert esc.by_kind.get(kind + ":partial-reuse") == 1, esc.summary()
    # 3 cold slab launches + the re-runs (+ 2 merges)
    assert s.last_guard.phases.fused_pipelines == 3 + rerun
    assert esc.recompiles == 1, esc.summary()
    assert esc.exact_resizes == 1, esc.summary()


def _check_oracle(rows, oracle):
    got = {int(k): int(v) for k, v in rows}
    assert got == dict(oracle)


# ---------------------------------------------------------------------------
# donation / deletion discipline
# ---------------------------------------------------------------------------

def _held_arrays(ent):
    # (what the columns' storage holds: raw (v, m) or packed 2/3-tuples a
    # slab, or one stacked array a leaf; a zone-map hole holds nothing)
    return [a for col in ent.dev.values() for _s, a in col.arrays()]


def test_evicted_entries_free_device_buffers():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE d1 (a BIGINT)")
    s.execute("INSERT INTO d1 VALUES " +
              ",".join(f"({i})" for i in range(2000)))
    run_device(s, "SELECT COUNT(*), SUM(a) FROM d1")
    held = _held_arrays(_cache_entry(eng, "d1"))
    assert held and not any(a.is_deleted() for a in held)

    # LRU budget eviction mid-stream of another table's first touch must
    # delete d1's buffers NOW, not when the GC runs
    s.execute("CREATE TABLE d2 (a BIGINT)")
    s.execute("INSERT INTO d2 VALUES " +
              ",".join(f"({i})" for i in range(2000)))
    s.vars["tidb_tpu_hbm_budget"] = 1        # force eviction
    try:
        run_device(s, "SELECT COUNT(*), SUM(a) FROM d2")
    finally:
        s.vars.pop("tidb_tpu_hbm_budget", None)
    assert all(a.is_deleted() for a in held), \
        "evicted entry left device buffers resident"

    # clear() frees everything it held
    held2 = _held_arrays(_cache_entry(eng, "d2"))
    assert held2
    dc.clear()
    assert all(a.is_deleted() for a in held2)
