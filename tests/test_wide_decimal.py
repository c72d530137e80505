"""Wide decimals — DECIMAL(>18) as exact Python ints host-side and
base-10⁹ limb planes on device (ref: types/mydecimal.go:236-246 MyDecimal
9-digit words; executor/aggfuncs/func_sum.go decimal states)."""

import decimal
from decimal import Decimal

decimal.getcontext().prec = 200   # oracle math must not round (the
                                  # default 28-digit context would)

import numpy as np
import pytest

from tidb_tpu.session import Engine


@pytest.fixture(scope="module")
def s():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE w (g BIGINT, a DECIMAL(38,10), "
              "b DECIMAL(15,2))")
    rng = np.random.default_rng(4)
    rows = []
    for _ in range(30000):
        big = int(rng.integers(-10**18, 10**18))
        frac = int(rng.integers(0, 10**10))
        rows.append(f"({int(rng.integers(0, 7))},"
                    f"'{big}{int(rng.integers(0, 10**9)):09d}.{frac:010d}',"
                    f"{round(float(rng.uniform(-999, 999)), 2)})")
    for i in range(0, len(rows), 10000):
        s.execute("INSERT INTO w VALUES " + ",".join(rows[i:i + 10000]))
    s.execute("INSERT INTO w VALUES (0, NULL, NULL)")
    s.execute("ANALYZE TABLE w")
    return s


def test_exact_roundtrip(s):
    s.execute("CREATE TABLE wr (a DECIMAL(38,10))")
    lit = "1234567890123456789012345678.0123456789"
    s.execute(f"INSERT INTO wr VALUES ('{lit}'), ('-0.0000000001'), (NULL)")
    got = s.query("SELECT a FROM wr ORDER BY a").rows
    assert got[0][0] is None
    assert got[1][0] == Decimal("-0.0000000001")
    assert got[2][0] == Decimal(lit)        # all 38 digits survive


def test_wide_65_digits(s):
    s.execute("CREATE TABLE w65 (a DECIMAL(65,30))")
    lit = ("9" * 35) + "." + ("8" * 30)
    s.execute(f"INSERT INTO w65 VALUES ('{lit}'), ('{lit}')")
    got = s.query("SELECT SUM(a), MIN(a), MAX(a) FROM w65").rows[0]
    assert got[0] == Decimal(lit) * 2
    assert got[1] == got[2] == Decimal(lit)


def test_cpu_aggregates_exact(s):
    # brute-force oracle over the raw rows
    raw = s.query("SELECT g, a FROM w WHERE a IS NOT NULL").rows
    sums = {}
    for g, a in raw:
        sums.setdefault(g, []).append(a)
    got = {r[0]: r for r in s.query(
        "SELECT g, SUM(a), MIN(a), MAX(a), COUNT(a) FROM w GROUP BY g"
    ).rows}
    for g, vals in sums.items():
        assert got[g][1] == sum(vals)
        assert got[g][2] == min(vals)
        assert got[g][3] == max(vals)
        assert got[g][4] == len(vals)


def test_arithmetic_and_compare(s):
    r = s.query("SELECT a + a, a * 2 FROM w WHERE a > 0 LIMIT 5").rows
    for twice, dbl in r:
        assert twice == dbl
    n_pos = s.query("SELECT COUNT(*) FROM w WHERE a > 0").rows[0][0]
    n_neg = s.query("SELECT COUNT(*) FROM w WHERE a < 0").rows[0][0]
    n = s.query("SELECT COUNT(a) FROM w").rows[0][0]
    assert n_pos + n_neg == n       # no zeros in the generated data


def test_device_limb_aggs_match_cpu(s):
    # SUM/AVG/COUNT run on the device limb path (SumAgg._update_wide over
    # wide_decimal_limbs planes); strict mode proves no CPU fallback
    sql = "SELECT g, SUM(a), AVG(a), COUNT(a), SUM(b) FROM w GROUP BY g"
    want = sorted(map(str, s.query(sql).rows))
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                  tidb_tpu_strict="on", tidb_tpu_max_slab_rows=8192)
    try:
        got = sorted(map(str, s.query(sql).rows))   # 4 slabs, limb merge
    finally:
        s.vars.update(tidb_tpu_engine="off", tidb_tpu_strict="off")
        s.vars.pop("tidb_tpu_max_slab_rows", None)
    assert got == want


def test_device_narrow_arg_wide_result(s):
    # SUM(DECIMAL(15,2)) types as DECIMAL(37,2): the device must split
    # int64 inputs into limbs, or the accumulation overflows silently
    sql = "SELECT SUM(b) FROM w"
    want = s.query(sql).rows
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                  tidb_tpu_strict="on")
    try:
        got = s.query(sql).rows
    finally:
        s.vars.update(tidb_tpu_engine="off", tidb_tpu_strict="off")
    assert got == want


def test_device_unsupported_wide_shapes_fall_back(s):
    # MIN/MAX / filters over wide columns route to CPU (still correct)
    for sql in [
        "SELECT g, MIN(a), MAX(a) FROM w GROUP BY g",
        "SELECT COUNT(*) FROM w WHERE a > 0",
    ]:
        want = sorted(map(str, s.query(sql).rows))
        s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1)
        try:
            got = sorted(map(str, s.query(sql).rows))
        finally:
            s.vars.update(tidb_tpu_engine="off")
        assert got == want


def test_codec_roundtrip_wide(s):
    from tidb_tpu.chunk import Column, Chunk
    from tidb_tpu.chunk.codec import decode_chunk, encode_chunk
    from tidb_tpu import types as T
    ft = T.decimal(40, 5)
    col = Column.from_list(ft, ["1" * 35 + ".12345", None, "-" + "9" * 30])
    buf = encode_chunk(Chunk([col]))
    back = decode_chunk(buf, [ft]).columns[0]
    assert back.values[0] == col.values[0]
    assert back.is_null(1)
    assert back.values[2] == col.values[2]


def test_limb_split_recombine():
    from tidb_tpu.chunk.compress import (wide_decimal_limbs,
                                         wide_decimal_unlimb)
    vals = np.array([10**37 - 1, -(10**37 - 1), 0, 123456789,
                     -987654321012345678901234567], dtype=object)
    limbs = wide_decimal_limbs(vals, 5)
    assert limbs.dtype == np.int64
    # lower planes in [0, 2^30); recombination is exact
    assert (limbs[:-1] >= 0).all() and (limbs[:-1] < (1 << 30)).all()
    back = wide_decimal_unlimb(limbs)
    assert list(back) == list(vals)


def test_device_computed_wide_expression(s):
    # SUM/AVG over a COMPUTED wide-typed expression (DECIMAL×DECIMAL →
    # DECIMAL(34,4)) arrives on device as 1-D int64 and must split/
    # recombine in the SAME limb base as storage planes (round-4 review
    # catch: a base mismatch here returned silently wrong sums)
    s.execute("CREATE TABLE cw (a DECIMAL(15,2), c DECIMAL(15,2))")
    rng = np.random.default_rng(6)
    s.execute("INSERT INTO cw VALUES " + ",".join(
        f"({round(float(rng.uniform(1, 99999)), 2)},"
        f"{round(float(rng.uniform(1, 99999)), 2)})"
        for _ in range(20000)))
    s.execute("ANALYZE TABLE cw")
    sql = "SELECT SUM(a * c), AVG(a * c), COUNT(*) FROM cw"
    want = s.query(sql).rows
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                  tidb_tpu_strict="on")
    try:
        got = s.query(sql).rows
    finally:
        s.vars.update(tidb_tpu_engine="off", tidb_tpu_strict="off")
    assert got == want


def test_device_scan_root_fragment_emits_all_columns(s):
    # a bare filtered-scan fragment must upload EVERY schema column
    # (round-4 regression: only filter columns uploaded → IndexError)
    sql = "SELECT * FROM w WHERE g = 3"
    want = sorted(map(str, s.query(sql).rows))
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1)
    try:
        got = sorted(map(str, s.query(sql).rows))
    finally:
        s.vars.update(tidb_tpu_engine="off")
    assert got == want
