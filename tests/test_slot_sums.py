"""`ops/segment.slot_sums`: the sums of a slot-addressed aggregate as ONE
one-hot contraction over integer pieces of at most 8 bits, against
`np.add.at` in int64 — bit for bit, wrap included — and against the
aggregates' own `update`; and who keeps the lowering it had."""

import numpy as np
import pytest

from tidb_tpu import types as T
from tidb_tpu.expression import ColumnRef
from tidb_tpu.expression.aggfuncs import AggDesc, build_agg
from tidb_tpu.ops import segment as seg
from tidb_tpu.ops.jax_env import jax, jnp

I64 = np.iinfo(np.int64)
BLOCK = 4096        # rows of a block here; the worst-case test takes the real


@pytest.fixture
def small_blocks(monkeypatch):
    monkeypatch.setattr(seg, "SLOT_SUM_BLOCK", BLOCK)
    monkeypatch.setattr(seg, "SLOT_SUM_MIN_WORK", 2)


def _ref(gid, cap, data):
    """np.add.at in wrapping int64 over the rows whose id is a slot."""
    out = np.zeros(cap, dtype=np.int64)
    ok = (gid >= 0) & (gid < cap)
    with np.errstate(over="ignore"):
        np.add.at(out, gid[ok], np.asarray(data, dtype=np.int64)[ok])
    return out


def _run(columns, gid, cap):
    assert seg.slot_sum_lowering(jnp, len(gid), cap) == "mxu"
    out = jax.jit(lambda g: seg.slot_sums(jnp, columns, g, cap))(
        jnp.asarray(gid))
    assert all(o.dtype == jnp.int64 and o.shape == (cap,) for o in out)
    return [np.asarray(o) for o in out]


@pytest.mark.parametrize("cap", [2, 6, 12, 128, 1000, 1024])
@pytest.mark.parametrize("kind", ["bool", "int32", "int64", "planes"])
def test_sums_equal_add_at(small_blocks, cap, kind):
    """Every dtype at every cap, rows no multiple of the block, NULLs,
    dead rows (id == cap) and out-of-range ids (-1, cap + 1)."""
    n = 2 * BLOCK + 77
    rng = np.random.default_rng(cap * 7 + len(kind))
    gid = rng.integers(-1, cap + 2, n).astype(np.int32)
    valid = rng.random(n) < 0.9
    jvalid = jnp.asarray(valid)
    if kind == "bool":
        v = rng.random(n) < 0.5
        cols = [seg.SumColumn(jnp.asarray(v), jvalid),
                seg.SumColumn(jnp.asarray(v)), seg.SumColumn(None, jvalid)]
        want = [v & valid, v, valid]
    elif kind == "int32":
        v = rng.integers(-2 ** 31, 2 ** 31, n).astype(np.int32)
        v[:2] = np.iinfo(np.int32).min, np.iinfo(np.int32).max
        cols = [seg.SumColumn(jnp.asarray(v), jvalid)]
        want = [np.where(valid, v, 0)]
    elif kind == "int64":
        v = rng.integers(I64.min, I64.max, n, dtype=np.int64)
        v[:4] = I64.min, I64.max, -1, 0
        jv = jnp.asarray(v)
        v0 = np.where(valid, v, 0)
        cols = [seg.SumColumn(jv, jvalid), seg.SumColumn(jv),
                seg.SumColumn(jv, jvalid, None, 0, 30, False),
                seg.SumColumn(jv, jvalid, None, 30, 30, False),
                seg.SumColumn(jv, jvalid, None, 60, 4, True)]
        want = [v0, v, v0 & (2 ** 30 - 1), (v0 >> 30) & (2 ** 30 - 1),
                v0 >> 60]
    else:
        v = np.stack([rng.integers(0, 2 ** 30, n),
                      rng.integers(0, 2 ** 30, n),
                      rng.integers(-2 ** 62, 2 ** 62, n)]).astype(np.int64)
        jv = jnp.asarray(v)
        cols = [seg.SumColumn(jv, jvalid, k) for k in range(3)]
        want = [np.where(valid, v[k], 0) for k in range(3)]
    for got, w in zip(_run(cols, gid, cap), want):
        assert (got == _ref(gid, cap, w)).all()


@pytest.mark.parametrize("value", [0, -1, I64.max, I64.min, 255, -256])
def test_the_accumulators_worst_case(monkeypatch, value):
    """Every row in ONE slot, every piece at an end of its range (a byte
    0 is stored −128, a byte 255 as 127), over a whole block of the real
    size and one more row: the block's int32 accumulator holds 2²⁴ of
    magnitude at most, and the sum wraps as int64 addition does."""
    monkeypatch.setattr(seg, "SLOT_SUM_MIN_WORK", 2)
    n = seg.SLOT_SUM_BLOCK + 1
    assert seg.SLOT_SUM_BLOCK * 128 < 2 ** 31
    gid = np.ones(n, dtype=np.int32)
    v = np.full(n, value, dtype=np.int64)
    jv = jnp.asarray(v)
    cols = [seg.SumColumn(jv), seg.SumColumn(jv, None, None, 0, 30, False),
            seg.SumColumn(jv, None, None, 30, 30, False),
            seg.SumColumn(jv, None, None, 60, 4, True),
            seg.SumColumn(None, jnp.ones(n, dtype=bool))]
    want = [v, v & (2 ** 30 - 1), (v >> 30) & (2 ** 30 - 1), v >> 60,
            np.ones(n, dtype=np.int64)]
    for got, w in zip(_run(cols, gid, 2), want):
        assert (got == _ref(gid, 2, w)).all()
        assert got[0] == 0


def test_equal_columns_are_cut_once(small_blocks):
    """Columns that name the same arrays and field share their pieces; a
    column is answered however often it is asked."""
    n = BLOCK + 5
    rng = np.random.default_rng(3)
    gid = rng.integers(0, 6, n).astype(np.int32)
    v = jnp.asarray(rng.integers(-10 ** 15, 10 ** 15, n))
    m1 = jnp.asarray(rng.random(n) < 0.8)
    m2 = jnp.asarray(rng.random(n) < 0.8)
    limbs = [seg.SumColumn(v, m1, None, 0, 30, False),
             seg.SumColumn(v, m1, None, 30, 30, False),
             seg.SumColumn(v, m1, None, 60, 4, True)]
    once = limbs + [seg.SumColumn(None, m1)]
    # 5 pieces of the low word, 5 of the high, the validity's bit and the
    # row bit: twelve pieces fill two groups (three words, were a word's
    # pieces a group of their own)
    assert seg.slot_sum_pieces(once) == 2 * 8
    assert seg.slot_sum_pieces(once + once) == 2 * 8
    assert seg.slot_sum_pieces(once, a_word_a_group=True) == 3 * 8
    other = [seg.SumColumn(v, m2, None, 0, 30, False), seg.SumColumn(None, m2)]
    assert seg.slot_sum_pieces(once + other) == 3 * 8   # 17 pieces
    got = _run(once + once + other, gid, 6)
    for a, b in zip(got[:4], got[4:8]):
        assert (a == b).all()
    assert (got[3] == _ref(gid, 6, np.asarray(m1))).all()
    assert (got[9] == _ref(gid, 6, np.asarray(m2))).all()


def _agg(name, ftype, star=False):
    desc = AggDesc(name, [] if star else [ColumnRef(0, ftype)])
    return build_agg(desc)


AGGS = {
    "count_star": (lambda: _agg("count", None, star=True), "int64"),
    "count": (lambda: _agg("count", T.bigint(True)), "int64"),
    "sum_bigint": (lambda: _agg("sum", T.bigint(True)), "int64"),
    "avg_bigint": (lambda: _agg("avg", T.bigint(True)), "int64"),
    "sum_decimal": (lambda: _agg("sum", T.decimal(15, 2, True)), "int64"),
    "avg_decimal": (lambda: _agg("avg", T.decimal(15, 2, True)), "int64"),
    "sum_narrow_decimal": (lambda: _agg("sum", T.decimal(4, 2, True)),
                           "int64"),
    "sum_int32": (lambda: _agg("sum", T.decimal(15, 2, True)), "int32"),
    "sum_wide_column": (lambda: _agg("sum", T.decimal(30, 4, True)),
                        "planes"),
}


@pytest.mark.parametrize("name", sorted(AGGS))
def test_an_aggregates_states_are_what_update_gives(small_blocks, name):
    """`row_sums` through `slot_sums` against the aggregate's own `update`
    (the masked reduce per state): every array of the state tuple, same
    dtype, same limb base, the untouched planes left alone."""
    make, kind = AGGS[name]
    agg = make()
    n, cap = BLOCK + 9, 12
    rng = np.random.default_rng(5)
    gid = rng.integers(0, cap + 1, n).astype(np.int32)
    valid = jnp.asarray(rng.random(n) < 0.9)
    if kind == "planes":
        limbs = agg.desc.args[0].ftype.wide_limb_count
        v = jnp.asarray(np.stack(
            [rng.integers(0, 2 ** 30, n) for _ in range(limbs - 1)]
            + [rng.integers(-2 ** 20, 2 ** 20, n)]).astype(np.int64))
    elif kind == "int32":
        v = jnp.asarray(rng.integers(-2 ** 31, 2 ** 31, n).astype(np.int32))
    else:
        v = jnp.asarray(rng.integers(-10 ** 17, 10 ** 17, n))
    plan = agg.row_sums(jnp, v, valid)
    assert plan is not None and len(plan) == len(agg.init(jnp, cap))
    sums = iter(_run([c for c in plan if c is not None], gid, cap))
    got = [a if c is None else a + next(sums)
           for a, c in zip(agg.init(jnp, cap), plan)]
    want = agg.update(jnp, agg.init(jnp, cap), jnp.asarray(gid), cap, v,
                      valid)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and (np.asarray(g) == np.asarray(w)).all()


def test_who_keeps_the_lowering_it_had(monkeypatch):
    """Shapes under the threshold, one segment, numpy and the caps beyond
    the masked reduce do not go to the matrix unit; MIN/MAX, FIRST, BIT and
    float sums have no `row_sums` and keep their own update."""
    big = seg.SLOT_SUM_MIN_WORK
    assert seg.slot_sum_lowering(jnp, big, 1) == "flat"
    assert seg.slot_sum_lowering(jnp, big // 12 - 1, 12) == "masked"
    assert seg.slot_sum_lowering(jnp, big // 12 + 1, 12) == "mxu"
    assert seg.slot_sum_lowering(jnp, 6 * 1024, 1024) == "mxu"
    assert seg.slot_sum_lowering(jnp, 6 * 12, 12) == "masked"   # a merge
    assert seg.slot_sum_lowering(np, big, 12) == "masked"
    assert seg.slot_sum_lowering(jnp, big, seg.MASKED_REDUCE_CAP + 1) \
        == "scatter"
    for name, ft in [("min", T.bigint(True)), ("max", T.decimal(15, 2, True)),
                     ("first_row", T.bigint(True)), ("bit_or", T.bigint(True)),
                     ("sum", T.double(True)), ("avg", T.double(True)),
                     ("var_pop", T.double(True))]:
        v = jnp.zeros(4, dtype=jnp.float64 if ft.kind.is_float
                      else jnp.int64)
        assert _agg(name, ft).row_sums(jnp, v, jnp.ones(4, bool)) is None
    # under the threshold `slot_sums` is the masked reduce per column, and
    # never traces a contraction
    gid = jnp.asarray(np.arange(40, dtype=np.int32) % 5)
    v = jnp.arange(40, dtype=jnp.int64)
    text = jax.jit(lambda g: seg.slot_sums(
        jnp, [seg.SumColumn(v)], g, 5)).lower(gid).as_text()
    assert "dot_general" not in text
    monkeypatch.setattr(seg, "SLOT_SUM_MIN_WORK", 2)
    text = jax.jit(lambda g: seg.slot_sums(
        jnp, [seg.SumColumn(v)], g, 5)).lower(gid).as_text()
    assert text.count("dot_general") == 1


# ---------------------------------------------------------------------------
# columns that name only the bits their value can hold (PR 41)
# ---------------------------------------------------------------------------

WIDTHS = [0, 1, 7, 8, 13, 24, 30, 31, 32, 37, 59, 62]
DEC = T.decimal(15, 2, True)


def _sum_columns(name, ftype, v, valid, bits):
    plan = _agg(name, ftype).row_sums(jnp, v, valid, bits)
    return [c for c in plan if c is not None]


@pytest.mark.parametrize("edge", ["2^k-1", "2^k"])
@pytest.mark.parametrize("k", WIDTHS)
def test_ranged_columns_equal_segment_sum(small_blocks, k, edge):
    """A value known to lie in [0, hi], hi = 2^k − 1 and 2^k: a narrow
    SUM's one field, a wide SUM's three limb fields (the third empty up to
    60 bits, the second partly filled between 30 and 60) and a column of
    unknown range, all in ONE call, with NULL rows, dead rows (id = the
    slot count) and the range's ends among the values — every sum equal
    bit for bit to `segment_sum` of the column."""
    hi = 2 ** k - (edge == "2^k-1")
    bits = hi.bit_length()
    n, cap = 2 * BLOCK + 77, 12
    rng = np.random.default_rng(k * 2 + len(edge))
    gid = rng.integers(0, cap + 1, n).astype(np.int32)   # cap = a dead row
    valid = rng.random(n) < 0.9
    v = rng.integers(0, hi + 1, n, dtype=np.int64)
    v[:3] = 0, hi, hi
    valid[:3] = True
    gid[1] = 0
    other = rng.integers(I64.min, I64.max, n, dtype=np.int64)
    jv, jvalid, jother = jnp.asarray(v), jnp.asarray(valid), \
        jnp.asarray(other)
    narrow = _sum_columns("sum", T.bigint(True), jv, jvalid, bits)
    wide = _sum_columns("sum", DEC, jv, jvalid, bits)
    whole = _sum_columns("sum", T.bigint(True), jother, jvalid, None)
    assert [(c.shift, c.bits, c.signed) for c in narrow[:1]] \
        == [(0, bits, False)]
    assert [(c.shift, c.bits, c.signed) for c in wide[:3]] == [
        (0, min(bits, 30), False), (30, min(max(bits - 30, 0), 30), False),
        (60, max(bits - 60, 0), False)]
    assert (whole[0].bits, whole[0].signed) == (None, True)
    v0 = np.where(valid, v, 0)
    want = [v0, valid,
            v0 & (2 ** 30 - 1), (v0 >> 30) & (2 ** 30 - 1), v0 >> 60, valid,
            np.where(valid, other, 0), valid]
    cols = narrow + wide + whole
    for got, w in zip(_run(cols, gid, cap), want):
        assert (got == _ref(gid, cap, w)).all()
    # and the lowering of a small batch (the masked reduce a column) reads
    # the same fields
    for c, w in zip(cols, want):
        assert (np.asarray(seg._column_data(jnp, c)) == w).all()
    # the limb STATES are what they were: the same three sums as the
    # whole-width plan's, limb for limb
    unranged = _sum_columns("sum", DEC, jv, jvalid, None)
    for a, b in zip(_run(wide, gid, cap), _run(unranged, gid, cap)):
        assert (a == b).all()
    assert seg.slot_sum_pieces(wide) <= seg.slot_sum_pieces(unranged)


def test_a_range_below_zero_keeps_the_whole_width():
    """lo < 0 gives no width (`ranges.sum_bits`), and no width is today's
    plan: the signed whole-width fields."""
    from tidb_tpu.expression import ranges
    assert ranges.sum_bits((-1, 100)) is None
    v, m = jnp.arange(8, dtype=jnp.int64), jnp.ones(8, dtype=bool)
    cols = _sum_columns("sum", DEC, v, m, ranges.sum_bits((-1, 100)))
    assert [(c.shift, c.bits, c.signed) for c in cols[:3]] \
        == [(0, 30, False), (30, 30, False), (60, 4, True)]
    assert _sum_columns("sum", T.bigint(True), v, m, None)[0].bits is None
    # a scale correction that could leave 63 bits keeps it too
    up = _agg("avg", T.decimal(4, 2, True))     # DECIMAL(8,6): × 10⁴
    assert up.row_sums(jnp, v, m, 40)[0].bits == (
        (2 ** 40 - 1) * 10 ** 4).bit_length()
    assert up.row_sums(jnp, v, m, 51)[0].bits is None


def _q1_columns(ranged: bool):
    """Q1's states as `device_emit._agg_states` gathers them at the
    benchmark's TPC-H domains: SUM and AVG of one argument share its
    arrays, every argument has a validity of its own."""
    n = 16
    args = {"qty": 5000, "price": 10494950, "disc_price": 1049495000,
            "charge": 113345460000, "disc": 10}
    arrays = {a: (jnp.zeros(n, dtype=jnp.int64), jnp.ones(n, dtype=bool))
              for a in args}
    cols = []
    for name, arg in [("sum", "qty"), ("sum", "price"),
                      ("sum", "disc_price"), ("sum", "charge"),
                      ("avg", "qty"), ("avg", "price"), ("avg", "disc")]:
        v, m = arrays[arg]
        cols += _sum_columns(name, DEC, v, m,
                             args[arg].bit_length() if ranged else None)
    return cols + [seg.SumColumn(None, jnp.ones(n, dtype=bool))]


def test_q1s_piece_matrix():
    """Q1 at the benchmark's domains: 13 + 24 + 30 + 37 + 4 value bits, a
    bit a validity and the row bit fill four 32-bit words and 22 pieces:
    three groups, where the whole widths took eleven with a word's pieces
    a group (PR 33's plan, the `slot_sums` tag's yardstick) and take
    eight filled."""
    assert seg.slot_sum_pieces(_q1_columns(True)) == 24
    assert seg.slot_sum_pieces(_q1_columns(False), a_word_a_group=True) == 88
    assert seg.slot_sum_pieces(_q1_columns(False)) == 64
