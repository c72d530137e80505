"""Compressed device-resident column layouts (chunk/compress.py +
device_cache encode/decode wiring).

Pinned invariants:

* the codec round-trips byte-exactly through every edge case: NULL
  validity under bit-packing, negative ints (min-as-reference FoR),
  all-NULL columns (width 0), single-distinct columns (width 0), and a
  dictionary-cardinality threshold crossing mid-table falls back to
  plain packing rather than overflowing the code width;
* a corrupted layout descriptor raises a typed LayoutError — never a
  silent mis-decode — and the `compressed-decode-mismatch` failpoint
  drives the full statement path to a warned CPU fallback that still
  returns oracle rows;
* compression on/off/CPU-oracle agree byte-exactly through the chain,
  fused-pipeline and staged-dist executors on a table built from the
  edge cases above;
* `information_schema.table_storage` physical/logical bytes reconcile
  byte-exactly with the cold statement's PhaseTimer ledger and with
  the statements_summary H2D counters;
* the HBM budget evicts on PHYSICAL bytes: two tables whose combined
  physical residency fits a budget their logical footprint does not
  both stay resident;
* EXPLAIN ANALYZE reports an effective_roofline_fraction (logical
  bytes, unclamped) strictly above the physical roofline_fraction when
  compression is active.
"""

import re

import numpy as np
import pytest

from tidb_tpu.chunk import compress
from tidb_tpu.chunk.compress import ColLayout
from tidb_tpu.errors import LayoutError
from tidb_tpu.executor import device_cache as dc, run_to_completion
from tidb_tpu.executor.builder import build
from tidb_tpu.executor.fragment import TpuFragmentExec
from tidb_tpu.parser import parse
from tidb_tpu.session import Engine
from tidb_tpu.util import failpoint


# ---------------------------------------------------------------------------
# codec round-trips (numpy oracle — the same decode the trace emits)
# ---------------------------------------------------------------------------

def _roundtrip(vals, valid, *, allow_dict=True, cap=None):
    """choose → pack → decode one padded slab; returns (layout, dv, dm)."""
    cap = cap or len(vals)
    lay, dictvals = compress.choose_layout(vals, valid,
                                           allow_dict=allow_dict)
    assert lay is not None
    pv = np.zeros(cap, dtype=vals.dtype)
    pm = np.zeros(cap, dtype=bool)
    pv[:len(vals)], pm[:len(valid)] = vals, valid
    slab = compress.pack_slab(lay, pv, pm, dictvals)
    if lay.kind == "dict":
        slab = slab + (dictvals,)
    dv, dm = compress.decode_slab(lay, slab, cap, np)
    return lay, np.asarray(dv), np.asarray(dm)


def test_null_validity_under_bitpacking():
    rng = np.random.default_rng(3)
    vals = rng.integers(0, 200, size=777).astype(np.int64)
    valid = rng.random(777) > 0.3
    lay, dv, dm = _roundtrip(vals, valid, allow_dict=False, cap=1024)
    assert lay.kind == "pack" and lay.width == 8
    # the packed mask restores validity bit-for-bit, padding included
    assert np.array_equal(dm[:777], valid) and not dm[777:].any()
    assert np.array_equal(dv[:777][valid], vals[valid])


def test_negative_ints_use_min_as_reference():
    vals = np.array([-1000, -997, -3, -1000, -500], dtype=np.int64)
    valid = np.ones(5, dtype=bool)
    lay, dv, dm = _roundtrip(vals, valid, allow_dict=False, cap=8)
    assert lay.ref == -1000, "FoR reference must be the observed min"
    assert np.array_equal(dv[:5], vals)
    assert dv.dtype == np.int64


def test_all_null_column_packs_to_width_zero():
    vals = np.zeros(300, dtype=np.int64)
    valid = np.zeros(300, dtype=bool)
    lay, dv, dm = _roundtrip(vals, valid, cap=512)
    assert lay.width == 0
    assert not dm.any()
    # width-0 slab stores a 1-word stub, not cap words
    slab = compress.pack_slab(lay, np.zeros(512, dtype=np.int64),
                              np.zeros(512, dtype=bool))
    assert slab[0].shape == (1,)


def test_single_distinct_column_packs_to_width_zero():
    vals = np.full(400, 42, dtype=np.int64)
    valid = np.ones(400, dtype=bool)
    lay, dv, dm = _roundtrip(vals, valid, cap=512)
    assert lay.width == 0 and lay.ref == 42
    assert (dv[:400] == 42).all() and dm[:400].all()


def test_dict_chosen_for_sparse_low_cardinality():
    # 7 distinct values spread over a 2^40 range: FoR needs >32 bits
    # (raw), the dictionary needs 4
    rng = np.random.default_rng(5)
    uniq = np.array([0, 1 << 20, 1 << 30, 1 << 35, 1 << 38, 1 << 39,
                     (1 << 40) - 1], dtype=np.int64)
    vals = uniq[rng.integers(0, 7, size=900)]
    valid = rng.random(900) > 0.1
    lay, dv, dm = _roundtrip(vals, valid, cap=1024)
    assert lay.kind == "dict" and lay.card == 7 and lay.width == 4
    assert np.array_equal(dv[:900][valid], vals[valid])


def test_dict_threshold_crossing_falls_back_to_pack():
    """First half low-cardinality, second half crosses DICT_CARD_CAP:
    the GLOBAL layout decision must abandon the dictionary (codes would
    overflow) and still round-trip exactly via plain packing."""
    lo = np.arange(100, dtype=np.int64) % 16
    hi = np.arange(compress.DICT_CARD_CAP + 50, dtype=np.int64)
    vals = np.concatenate([lo, hi])
    valid = np.ones(len(vals), dtype=bool)
    lay, dv, dm = _roundtrip(vals, valid, cap=8192)
    assert lay.kind == "pack", "cardinality above the cap must not dict"
    assert np.array_equal(dv[:len(vals)], vals)


@pytest.mark.parametrize("width,hi", [(1, 2), (2, 4), (4, 16), (8, 256),
                                      (16, 65536), (32, 1 << 32)])
def test_pack_roundtrip_every_width(width, hi):
    rng = np.random.default_rng(width)
    vals = rng.integers(0, hi, size=500).astype(np.int64)
    vals[0], vals[1] = 0, hi - 1                    # pin the extremes
    valid = rng.random(500) > 0.2
    valid[:2] = True
    lay, dv, dm = _roundtrip(vals, valid, allow_dict=False, cap=512)
    assert lay.width == width
    assert np.array_equal(dv[:500][valid], vals[valid])


def _delta_case(width, dtype, cap, base, seed):
    """A sorted fully-valid column of `dtype` whose widest gap needs
    `width` bits, starting at `base`, in a slab of `cap` rows with a padded
    tail where the slab has room for one → (layout, vals, n)."""
    info = np.iinfo(dtype)
    n = cap - min(cap // 3, 5)
    hi = (1 << width) - 1
    budget = info.max - base
    rng = np.random.default_rng(seed)
    d = rng.integers(0, hi, size=n, endpoint=True).astype(np.uint64)
    d[0] = 0
    if n > 1:
        d[1] = min(hi, budget)              # the width's widest gap, if it fits
    over = np.cumsum(d.astype(object)) > budget     # exact: Python integers
    d[over] = 0
    vals = np.zeros(cap, dtype=dtype)
    vals[:n] = (base + np.cumsum(d.astype(object))).astype(dtype)
    return ColLayout("delta", width, 0, np.dtype(dtype).name), vals, n


def _delta_decodes(lay, vals, n, cap):
    """pack → (jnp decode, numpy decode), each (values, validity)."""
    from tidb_tpu.ops.jax_env import jnp
    slab = compress.pack_slab(lay, vals, np.arange(cap) < n)
    dv, dm = compress.decode_slab(lay, slab, cap, np)
    jv, jm = compress.decode_slab(lay, tuple(jnp.asarray(a) for a in slab),
                                  cap, jnp)
    return (np.asarray(jv), np.asarray(jm)), (dv, dm)


_B = compress.DELTA_BLOCK


@pytest.mark.parametrize("base", ["zero", "negative", "dtype-min"])
@pytest.mark.parametrize("cap", [_B, 8 * _B, 3 * _B + 17, 1],
                         ids=["one-block", "many-blocks", "ragged", "1-row"])
@pytest.mark.parametrize("dtype", [np.int32, np.int64])
@pytest.mark.parametrize("width", [1, 2, 4, 8, 16, 32])
def test_delta_roundtrip_traced_decode_equals_numpy_and_source(
        width, dtype, cap, base):
    """The traced (jnp) decode of a `delta` column — a narrow scan inside
    blocks, the blocks' bases added in the logical width — against the
    numpy oracle (one cumsum) against the source column: byte-exact, the
    zero-padded tail included (the scan holds the last value there; the
    packed validity masks it)."""
    b = {"zero": 0, "negative": -12345,
         "dtype-min": int(np.iinfo(dtype).min) + 3}[base]
    lay, vals, n = _delta_case(width, dtype, cap, b, seed=width + cap)
    compress.validate(lay)
    (jv, jm), (dv, dm) = _delta_decodes(lay, vals, n, cap)
    assert jv.dtype == dv.dtype == np.dtype(dtype)
    assert jv.tobytes() == dv.tobytes(), "traced decode != numpy decode"
    assert np.array_equal(dv[:n], vals[:n]), "numpy decode != source"
    assert np.array_equal(jm, dm) and dm[:n].all() and not dm[n:].any()
    assert (dv[n:] == vals[n - 1]).all(), "the tail holds the last value"
    want = "plain" if cap % _B or cap <= _B else \
        "int32" if width <= 16 or dtype is np.int32 else "wide"
    assert compress.delta_scan(lay, cap) == want


@pytest.mark.parametrize("span,scan", [(1 << 15, "int32"), (1 << 16, "wide")])
def test_delta_scan_type_flips_at_the_int32_overflow_edge(monkeypatch, span,
                                                          scan):
    """Width 16 in an int64 column: a span of 32768 rows sums to at most
    32768 · 65535 = 2^31 − 32768 (fits int32), one of 65536 rows to
    2^32 − 65536 (does not) — every gap at the maximum, so a scan in the
    wrong type would wrap."""
    monkeypatch.setattr(compress, "DELTA_BLOCK", span)
    lay = ColLayout("delta", 16, 0, "int64")
    assert (span * 65535 < 1 << 31) == (scan == "int32")
    cap = 4 * span
    assert compress.delta_scan(lay, cap) == scan
    vals = -(1 << 40) + 65535 * np.arange(cap, dtype=np.int64)
    (jv, _jm), (dv, _dm) = _delta_decodes(lay, vals, cap, cap)
    assert np.array_equal(dv, vals)
    assert jv.tobytes() == dv.tobytes()


def test_validate_rejects_corrupt_descriptors():
    good = ColLayout("pack", 8, 0, "int64")
    compress.validate(good)                         # sanity: passes
    for bad in (
        "not-a-layout",
        ColLayout("zstd", 8, 0, "int64"),           # unknown kind
        ColLayout("pack", 7, 0, "int64"),           # illegal width
        ColLayout("pack", 8, 0, "float64"),         # non-integer dtype
        ColLayout("dict", 4, 0, "int64", 0),        # dict without card
    ):
        with pytest.raises(LayoutError):
            compress.validate(bad)


# ---------------------------------------------------------------------------
# engine fixtures
# ---------------------------------------------------------------------------

def run_device(s, sql, *, max_slab=None, dist=None, staged=None):
    """Execute on the device path, asserting no CPU fallback."""
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    if max_slab is not None:
        s.vars["tidb_tpu_max_slab_rows"] = max_slab
    if dist is not None:
        s.vars["tidb_tpu_dist"] = dist
    if staged is not None:
        s.vars["tidb_tpu_dist_staged"] = staged
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
        for k in ("tidb_tpu_max_slab_rows", "tidb_tpu_dist",
                  "tidb_tpu_dist_staged"):
            s.vars.pop(k, None)


def _cache_entry(eng, table_name):
    tid = eng.catalog.info_schema.table(table_name).id
    for (_dev, sid, t, _parts), ent in dc.CACHE.items():
        if sid == id(eng.store) and t == tid:
            return ent
    raise AssertionError(f"no cache entry for {table_name}")


def _edge_case_engine(n=3000):
    """One table exercising every layout edge case at once: negatives
    with NULLs (FoR), an all-NULL column, a single-distinct column, a
    sparse low-cardinality dict column and a date-like FoR column."""
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE ec (neg BIGINT, con BIGINT, nul BIGINT, "
              "dct BIGINT, d BIGINT)")
    rng = np.random.default_rng(17)
    uniq = [0, 1 << 30, 1 << 35, 1 << 39]
    rows = []
    for i in range(n):
        neg = "NULL" if i % 13 == 0 else str(int(rng.integers(-900, -100)))
        rows.append(f"({neg}, 7, NULL, {uniq[i % 4]}, "
                    f"{20200101 + int(rng.integers(0, 365))})")
    s.execute("INSERT INTO ec VALUES " + ",".join(rows))
    return eng, s


EC_Q = ("SELECT dct, COUNT(*), COUNT(neg), COUNT(nul), SUM(neg), "
        "MIN(con), MIN(d), MAX(d) FROM ec GROUP BY dct")


def _sorted_rows(rows):
    return sorted(rows, key=str)


def test_edge_cases_byte_exact_chain_on_off_oracle():
    eng, s = _edge_case_engine()
    oracle = _sorted_rows(s.query(EC_Q).rows)
    on = _sorted_rows(run_device(s, EC_Q, max_slab=1024))
    assert on == oracle
    ent = _cache_entry(eng, "ec")
    sigs = {i: l.sig() for i, l in ent.layouts.items() if l is not None}
    assert any(s_.startswith("dict:") for s_ in sigs.values()), sigs
    assert any(s_.startswith("pack:w0:") for s_ in sigs.values()), sigs
    # negatives must be min-referenced packs, not raw
    assert any(":r-" in s_ for s_ in sigs.values()), sigs
    s.vars["tidb_tpu_compression"] = "off"
    off = _sorted_rows(run_device(s, EC_Q, max_slab=1024))
    assert off == oracle
    ent2 = _cache_entry(eng, "ec")
    assert not any(l is not None for l in ent2.layouts.values())


def _delta_programs(scan: str) -> float:
    from tidb_tpu.util.observability import REGISTRY
    return REGISTRY.counters.get(
        ("tidb_tpu_delta_decode_programs_total", (("scan", scan),)), 0)


def test_delta_decode_counts_its_scan_once_per_traced_program(tmp_path):
    """A Q6-shaped statement over a sorted date column (int32, gaps of a
    day at most: `delta` width 1) bumps
    `tidb_tpu_delta_decode_programs_total{scan="int32"}` once when its
    program is traced — not per slab, not per warm run — and the launch
    that traced it carries `delta_scan=int32`; a sorted BIGINT whose gaps
    need width 32 bumps "wide"."""
    from tidb_tpu.util import timeline
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE li (ship DATE, ts BIGINT, v BIGINT)")
    n = 5000
    s.execute("INSERT INTO li VALUES " + ",".join(
        f"(DATE_ADD('1994-01-01', INTERVAL {i // 9} DAY), "
        f"{-(1 << 45) + i * (1 << 31) + (i % 7)}, {i % 11})"
        for i in range(n)))
    q6 = ("SELECT SUM(v), COUNT(*) FROM li WHERE ship >= '1994-03-01' "
          "AND ship < '1995-03-01'")
    before = {k: _delta_programs(k) for k in ("int32", "wide", "plain")}
    timeline.start_global(str(tmp_path))
    try:
        cold = run_device(s, q6, max_slab=2048)
    finally:
        timeline.stop_global()
    ent = _cache_entry(eng, "li")
    assert ent.slab_cap == 2048 and len(ent.dev[0]) == 3
    assert ent.layouts[0].sig() == "delta:w1:r0:c0:int32"
    assert _delta_programs("int32") - before["int32"] == 1
    tagged = [e for e in timeline.last_events()
              if e["ph"] == "X" and "delta_scan" in e["args"]]
    assert [(e["cat"], e["args"]["delta_scan"]) for e in tagged] == \
        [("launch", "int32")]
    assert run_device(s, q6, max_slab=2048) == cold      # warm: no trace
    assert _delta_programs("int32") - before["int32"] == 1
    wide = f"SELECT COUNT(*), MAX(ts) FROM li WHERE ts >= {5 - (1 << 45)}"
    assert run_device(s, wide, max_slab=2048) == s.query(wide).rows == \
        [(n - 1, -(1 << 45) + (n - 1) * (1 << 31) + (n - 1) % 7)]
    assert ent.layouts[1].sig() == "delta:w32:r0:c0:int64"
    assert _delta_programs("wide") - before["wide"] == 1
    assert _delta_programs("int32") - before["int32"] == 1
    assert _delta_programs("plain") == before["plain"]
    eng.close()


def test_edge_cases_byte_exact_staged_dist():
    eng, s = _edge_case_engine()
    oracle = _sorted_rows(s.query(EC_Q).rows)
    got = _sorted_rows(run_device(s, EC_Q, max_slab=1024, dist=4))
    assert got == oracle


def test_edge_cases_byte_exact_monolithic_dist():
    eng, s = _edge_case_engine()
    oracle = _sorted_rows(s.query(EC_Q).rows)
    got = _sorted_rows(
        run_device(s, EC_Q, max_slab=1024, dist=4, staged="off"))
    assert got == oracle


def test_fused_join_byte_exact_on_off_oracle():
    eng, s = _edge_case_engine()
    s.execute("CREATE TABLE dim (id BIGINT, tag VARCHAR(8))")
    s.execute("INSERT INTO dim VALUES (0,'a'),(1073741824,'b'),"
              f"({1 << 35},'c'),({1 << 39},'d')")
    q = ("SELECT dim.tag, COUNT(*), SUM(ec.neg) FROM ec "
         "JOIN dim ON ec.dct = dim.id GROUP BY dim.tag")
    oracle = _sorted_rows(s.query(q).rows)
    fused = _sorted_rows(run_device(s, q, max_slab=1024))
    assert fused == oracle
    s.vars["tidb_tpu_fused_pipeline"] = "off"
    try:
        tree = _sorted_rows(run_device(s, q, max_slab=1024))
    finally:
        s.vars.pop("tidb_tpu_fused_pipeline", None)
    assert tree == oracle


# ---------------------------------------------------------------------------
# storage accounting: table_storage ↔ PhaseTimer ↔ statements_summary
# ---------------------------------------------------------------------------

def test_table_storage_reconciles_with_phase_ledger():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE tsr (a BIGINT, b BIGINT)")
    s.execute("INSERT INTO tsr VALUES " +
              ",".join(f"({i % 50}, {i % 3})" for i in range(4000)))
    s.execute("SET tidb_tpu_engine = 'on'")
    s.execute("SET tidb_tpu_row_threshold = 1")
    q = "SELECT b, COUNT(*), SUM(a) FROM tsr GROUP BY b"
    s.query(q)                                      # cold first touch
    ph = s.last_guard.phases
    assert ph.h2d_bytes > 0
    assert ph.h2d_logical_bytes > ph.h2d_bytes, \
        "narrow ints must actually compress"
    rows = s.query(
        "SELECT COLUMN_NAME, LAYOUT, PHYSICAL_BYTES, LOGICAL_BYTES "
        "FROM information_schema.table_storage "
        "WHERE TABLE_NAME = 'tsr'").rows
    assert {r[0] for r in rows} == {"a", "b"}
    assert all(r[1].startswith("pack:") for r in rows), rows
    # the cold upload IS the physical residency — byte-exact both ways
    assert sum(r[2] for r in rows) == ph.h2d_bytes
    assert sum(r[3] for r in rows) == ph.h2d_logical_bytes
    # and the digest row aggregates the same integers
    srow = s.query(
        "SELECT H2D_BYTES, H2D_LOGICAL_BYTES, SCAN_LOGICAL_BYTES FROM "
        "information_schema.statements_summary "
        f"WHERE DIGEST_TEXT = '{q}'").rows
    assert srow == [(ph.h2d_bytes, ph.h2d_logical_bytes,
                     ph.scan_logical_bytes)]


def test_eviction_budget_charges_physical_bytes():
    """Two tables whose combined PHYSICAL bytes fit a budget their
    LOGICAL footprint does not must both stay resident — the budget
    accountant sees compressed reality, not the uncompressed fiction."""
    eng = Engine()
    s = eng.new_session()
    for t in ("ev1", "ev2"):
        s.execute(f"CREATE TABLE {t} (a BIGINT)")
        s.execute(f"INSERT INTO {t} VALUES " +
                  ",".join(f"({i % 4})" for i in range(4000)))
    run_device(s, "SELECT COUNT(*), SUM(a) FROM ev1")
    e1 = _cache_entry(eng, "ev1")
    phys, logical = e1.hbm_bytes(), e1.logical_bytes()
    assert phys * 4 < logical, (phys, logical)
    s.vars["tidb_tpu_hbm_budget"] = phys * 3        # fits 2×phys, not logical
    try:
        run_device(s, "SELECT COUNT(*), SUM(a) FROM ev2")
    finally:
        s.vars.pop("tidb_tpu_hbm_budget", None)
    # ev1 survived: charging logical bytes would have evicted it
    e1b = _cache_entry(eng, "ev1")
    assert e1b is e1
    assert not any(a.is_deleted() for _s, a in e1._arrays())


def test_effective_roofline_fraction_reported():
    from tidb_tpu.util import roofline
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE rf (a BIGINT, b BIGINT)")
    s.execute("INSERT INTO rf VALUES " +
              ",".join(f"({i % 50}, {i % 3})" for i in range(3000)))
    s.execute("SET tidb_tpu_engine = 'on'")
    s.execute("SET tidb_tpu_row_threshold = 1")
    q = "SELECT b, COUNT(*), SUM(a) FROM rf GROUP BY b"
    # 0.5 GB/s keeps the warm sub-ms fractions well above
    # the 3-decimal display rounding edge
    roofline.set_measured_gbs(0.5)
    try:
        s.query(q)
        info = "\n".join(" ".join(str(c) for c in r)
                         for r in s.query("EXPLAIN ANALYZE " + q).rows)
        m = re.search(r"(?<!effective_)roofline_fraction:(\d+\.\d+)", info)
        me = re.search(r"effective_roofline_fraction:(\d+\.\d+)", info)
        assert m and me, info
        frac, eff = float(m.group(1)), float(me.group(1))
        ph = s.last_guard.phases
        assert ph.scan_logical_bytes > ph.scan_bytes
        # logical bytes > physical bytes → the effective figure is
        # strictly the larger one (and may legitimately exceed 1.0)
        assert eff > frac > 0.0
        assert eff == pytest.approx(
            roofline.effective_fraction(ph.scan_logical_bytes, ph.wall_s,
                                        gbs=0.5), abs=1e-3)
    finally:
        roofline.set_measured_gbs(0.0)


# ---------------------------------------------------------------------------
# corruption: typed error + CPU fallback, never silent wrong rows
# ---------------------------------------------------------------------------

def test_corrupted_descriptor_falls_back_to_cpu():
    eng, s = _edge_case_engine(n=1500)
    oracle = _sorted_rows(s.query(EC_Q).rows)
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    failpoint.enable("compressed-decode-mismatch",
                     value="test: descriptor drift")
    try:
        plan = s._plan(parse(EC_Q)[0])
        root = build(plan)
        chunks = run_to_completion(root, s._exec_ctx())
        got = _sorted_rows([r for ch in chunks for r in ch.rows()])
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
            assert not f.used_device, "corrupt layout must not serve"
            assert "layout" in (f.fallback_reason or "").lower() or \
                "corrupt" in (f.fallback_reason or "").lower(), \
                f.fallback_reason
    finally:
        failpoint.disable("compressed-decode-mismatch")
        s.vars["tidb_tpu_engine"] = "off"
    # disarmed: the device path serves the same rows again
    assert _sorted_rows(run_device(s, EC_Q)) == oracle


def test_layout_error_is_typed_not_silent():
    """The failpoint surfaces as LayoutError at the cache layer — the
    executor's fallback is catching a TYPED error, not swallowing a
    wrong answer."""
    eng, s = _edge_case_engine(n=800)
    run_device(s, EC_Q)                             # populate the cache
    ent = _cache_entry(eng, "ec")
    failpoint.enable("compressed-decode-mismatch", value="boom")
    try:
        with pytest.raises(LayoutError, match="corrupted"):
            dc._validate_layouts(ent, list(ent.dev))
    finally:
        failpoint.disable("compressed-decode-mismatch")
    dc._validate_layouts(ent, list(ent.dev))        # disarmed: clean
