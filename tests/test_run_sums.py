"""`ops/segment.run_sums`: the sums of a grouping by sorted runs, one scan a
packed int64 WORD — against `SortedRuns.sum` of each column and the
aggregates' own `update`, bit for bit; where the fields end (at bit 64, a
bit under, a bit over), what dead rows, NULLs and a short capacity do, what
shares a field, and how many words the benchmark's statements scan."""

import numpy as np
import pytest

from tidb_tpu import types as T
from tidb_tpu.expression import ColumnRef
from tidb_tpu.expression.aggfuncs import AggDesc, build_agg
from tidb_tpu.ops import segment as seg
from tidb_tpu.ops.jax_env import jax, jnp

I64 = np.iinfo(np.int64)
DEC = T.decimal(15, 2, True)
# the sorted rows of `lgstream1.sf2`: its two lineitem slabs' live rows, or
# both slabs at the first's capacity (what the shared sort is given)
SORTED_ROWS = (12_582_912, 1 << 24)


def _rows(n, groups, dead, seed):
    """Rows as `factorize.sort_rows` leaves them: sorted by group, `dead`
    dead rows last → (gid per live row, live, ends padded to n, n_runs)."""
    rng = np.random.default_rng(seed)
    gid = np.sort(rng.integers(0, groups, n - dead))
    live = np.arange(n) < n - dead
    last = np.flatnonzero(np.append(gid[1:] != gid[:-1], True))
    ends = np.full(n, 2 ** 31 - 1, dtype=np.int32)      # garbage beyond
    ends[:len(last)] = last
    return gid, live, ends, len(last)


def _ref(gid, live_n, n_runs, cap, data):
    """Per run the wrapping int64 sum, zero in the slots beyond the runs;
    with more runs than `cap`, the first `cap` of them."""
    dense = np.unique(gid, return_inverse=True)[1]
    out = np.zeros(max(n_runs, cap), dtype=np.int64)
    with np.errstate(over="ignore"):
        np.add.at(out, dense, np.asarray(data, dtype=np.int64)[:live_n])
    return out[:cap]


def _run(columns, ends, n_runs, cap, n_rows):
    def go(e, k):
        runs = seg.SortedRuns(e, k, cap)
        return (seg.run_sums(columns, runs, n_rows),
                [runs.sum(seg._column_data(jnp, c)) for c in columns])
    got, each = jax.jit(go)(jnp.asarray(ends), jnp.int32(n_runs))
    assert all(o.dtype == jnp.int64 and o.shape == (cap,) for o in got)
    return [np.asarray(o) for o in got], [np.asarray(o) for o in each]


def _field(v, valid, bits):
    return seg.SumColumn(jnp.asarray(v), jnp.asarray(valid), None, 0, bits,
                         False)


def _agg(name, ftype=None):
    return build_agg(AggDesc(name, [] if ftype is None
                             else [ColumnRef(0, ftype)]))


def _columns(plan):
    return [c for c in plan if c is not None]


@pytest.mark.parametrize("values", ["greatest", "random"])
@pytest.mark.parametrize("end", [63, 64, 65])
@pytest.mark.parametrize("bits", [1, 13, 30, 31, 37, 39, 40])
def test_fields_that_end_at_bit_64_a_bit_under_and_a_bit_over(bits, end,
                                                              values):
    """A value of `bits` bits, its count and a filler field whose width
    makes the three end at bit `end`: 63 and 64 are ONE word — at 64 the
    top field reaches the sign bit, and with every value the greatest the
    prefix sums fill every field to its last bit — 65 opens a second."""
    n = 1000 if bits <= 31 else 100
    grow = int(n).bit_length()
    filler = end - (bits + grow) - (1 + grow) - grow
    assert filler >= 1
    gid, live, ends, n_runs = _rows(n, 40, 0, bits * 3 + end)
    rng = np.random.default_rng(bits + end)
    valid = np.ones(n, dtype=bool) if values == "greatest" \
        else rng.random(n) < 0.9

    def make(b):
        if values == "greatest":
            return np.full(n, 2 ** b - 1, dtype=np.int64)
        return rng.integers(0, 2 ** b, n, dtype=np.int64)
    v, f = make(bits), make(filler)
    cols = [_field(v, valid, bits), seg.SumColumn(None, jnp.asarray(valid)),
            _field(f, valid, filler)]
    assert seg.run_sum_scans(cols, n) == (2 if end > 64 else 1, 0)
    got, each = _run(cols, ends, n_runs, n_runs + 3, n)
    want = [np.where(valid, v, 0), valid, np.where(valid, f, 0)]
    for g, e, w in zip(got, each, want):
        assert (g == e).all()
        assert (g == _ref(gid, n, n_runs, n_runs + 3, w)).all()


def test_q18s_shape_at_two_to_the_24_rows_is_one_word():
    """SUM(l_quantity) under a wide result at 2²⁴ sorted rows: 13 + 25
    bits of limb 0 and 1 + 25 of the count are the 64 bits of one word,
    limbs 1 and 2 are the constant 0; one row more is a 39-bit field and
    a second word. The cut is exact over rows that claim that many."""
    n = 4096
    gid, live, ends, n_runs = _rows(n, 300, 96, 18)
    rng = np.random.default_rng(18)
    v = np.where(live, rng.integers(0, 5001, n), 7)
    valid = live & (rng.random(n) < 0.95)
    plan = _agg("sum", DEC).row_sums(jnp, jnp.asarray(v), jnp.asarray(valid),
                                     13)
    cols = _columns(plan)
    assert seg.run_sum_scans(cols, 2 ** 24) == (1, 0)
    assert seg.run_sum_scans(cols, 2 ** 24 - 1) == (1, 0)
    assert seg.run_sum_scans(cols, 2 ** 25) == (2, 0)
    got, each = _run(cols, ends, n_runs, 512, 2 ** 24)
    v0 = np.where(valid, v, 0)
    for g, e, w in zip(got, each, [v0, 0 * v0, 0 * v0, valid]):
        assert (g == e).all()
        assert (g == _ref(gid, n - 96, n_runs, 512, w)).all()


@pytest.mark.parametrize("cap", ["short", "exact", "beyond-the-rows"])
def test_nulls_dead_rows_and_the_capacity(cap):
    """NULL rows count 0, dead rows are sorted last and reach no run, a
    capacity beyond the rows pads, and with more runs than the capacity
    the first `cap` runs and `n_runs` read as `SortedRuns.sum` gives
    them — the caller's ladder retries on the count."""
    n, dead = 3000, 500
    gid, live, ends, n_runs = _rows(n, 200, dead, 7)
    cap = {"short": n_runs - 50, "exact": n_runs, "beyond-the-rows": n + 64}[
        cap]
    rng = np.random.default_rng(11)
    v = rng.integers(0, 5001, n, dtype=np.int64)
    valid = live & (rng.random(n) < 0.8)
    other = rng.integers(I64.min, I64.max, n, dtype=np.int64)
    cols = [_field(v, valid, 13), seg.SumColumn(None, jnp.asarray(valid)),
            seg.SumColumn(None, jnp.asarray(live)),
            seg.SumColumn(jnp.asarray(other), jnp.asarray(valid))]
    assert seg.run_sum_scans(cols, n) == (1, 1)
    got, each = _run(cols, ends, n_runs, cap, n)
    want = [np.where(valid, v, 0), valid, live, np.where(valid, other, 0)]
    for g, e, w in zip(got, each, want):
        assert (g == e).all()
        assert (g == _ref(gid, n - dead, n_runs, cap, w)).all()


@pytest.mark.parametrize("bits", [13, 37, None])
def test_a_wide_sums_limbs_are_what_update_gives(bits):
    """(limb 0, limb 1, limb 2, count) of a wide SUM against its own
    `update` over the same runs: at 13 bits limbs 1 and 2 are the constant
    0 and are not scanned, at 37 limb 1 holds 7 bits, and with no width
    limb 2 is a signed word of its own."""
    n = 5000
    gid, live, ends, n_runs = _rows(n, 700, 200, 3)
    rng = np.random.default_rng(bits or 64)
    if bits is None:
        v = rng.integers(I64.min, I64.max, n, dtype=np.int64)
    else:
        v = rng.integers(0, 2 ** bits, n, dtype=np.int64)
        v[:2] = 2 ** bits - 1
    valid = live & (rng.random(n) < 0.9)
    jv, jvalid = jnp.asarray(v), jnp.asarray(valid)
    agg = _agg("sum", DEC)
    cap = 1024

    def go(e, k):
        runs = seg.SortedRuns(e, k, cap)
        plan = agg.row_sums(jnp, jv, jvalid, bits)
        sums = iter(seg.run_sums(_columns(plan), runs, n))
        got = tuple(a if c is None else a + next(sums)
                    for a, c in zip(agg.init(jnp, cap), plan))
        return got, agg.update(jnp, agg.init(jnp, cap), runs, cap, jv, jvalid)
    got, want = jax.jit(go)(jnp.asarray(ends), jnp.int32(n_runs))
    assert len(got) == len(want) == 4
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and (np.asarray(g) == np.asarray(w)).all()
    cols = _columns(agg.row_sums(jnp, jv, jvalid, bits))
    # 13: one word. 37: limb 0 (30 + 13 bits) and limb 1 (7 + 13) fill one
    # word, the count (1 + 13) opens a second. None: limbs 0 and 1 are
    # 30-bit fields whatever the value, limb 2 is signed
    assert seg.run_sum_scans(cols, n) == {13: (1, 0), 37: (2, 0),
                                          None: (2, 1)}[bits]


@pytest.mark.parametrize("name", ["sum_bigint", "avg_bigint", "sum_decimal",
                                  "avg_decimal", "count", "count_star",
                                  "sum_scaled_up"])
def test_an_aggregates_states_are_what_update_gives(name):
    """Every aggregate that groups by sorted runs, with a width and
    without: the state tuple of `row_sums` through `run_sums` is its own
    `update`'s, array for array."""
    agg = {"sum_bigint": lambda: _agg("sum", T.bigint(True)),
           "avg_bigint": lambda: _agg("avg", T.bigint(True)),
           "sum_decimal": lambda: _agg("sum", DEC),
           "avg_decimal": lambda: _agg("avg", DEC),
           "count": lambda: _agg("count", T.bigint(True)),
           "count_star": lambda: _agg("count"),
           "sum_scaled_up": lambda: _agg("avg", T.decimal(4, 2, True))}[
        name]()
    n, cap = 4000, 512
    gid, live, ends, n_runs = _rows(n, 400, 100, 5)
    rng = np.random.default_rng(len(name))
    v = jnp.asarray(rng.integers(0, 2 ** 24, n, dtype=np.int64))
    valid = jnp.asarray(live & (rng.random(n) < 0.9))
    for bits in (24, None):
        def go(e, k):
            runs = seg.SortedRuns(e, k, cap)
            plan = agg.row_sums(jnp, v, valid, bits)
            sums = iter(seg.run_sums(_columns(plan), runs, n))
            got = tuple(a if c is None else a + next(sums)
                        for a, c in zip(agg.init(jnp, cap), plan))
            return got, agg.update(jnp, agg.init(jnp, cap), runs, cap, v,
                                   valid)
        got, want = jax.jit(go)(jnp.asarray(ends), jnp.int32(n_runs))
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            assert (np.asarray(g) == np.asarray(w)).all()


def test_aggregates_over_one_argument_share_its_fields():
    """SUM(x), AVG(x), COUNT(x) and COUNT(*) over one validity: ten state
    arrays, x's one non-constant limb once and the count once — two fields
    of one word, and every state still its own."""
    n, cap = 2000, 256
    gid, live, ends, n_runs = _rows(n, 150, 0, 9)
    rng = np.random.default_rng(9)
    v = jnp.asarray(rng.integers(0, 5001, n, dtype=np.int64))
    valid = jnp.asarray(live)
    plans = [_agg("sum", DEC).row_sums(jnp, v, valid, 13),
             _agg("avg", DEC).row_sums(jnp, v, valid, 13),
             _agg("count", DEC).row_sums(jnp, v, valid, 13),
             _agg("count").row_sums(jnp, v, valid, None)]
    cols = [c for plan in plans for c in plan]
    assert [len(plan) for plan in plans] == [4, 4, 1, 1]
    plan = seg._run_sum_plan(cols, n)
    assert len(plan.words) == 1 and len(plan.words[0]) == 2
    got, each = _run(cols, ends, n_runs, cap, n)
    for g, e in zip(got, each):
        assert (g == e).all()
    want = _ref(gid, n, n_runs, cap, np.asarray(v))
    assert (got[0] == want).all() and (got[4] == want).all()
    count = _ref(gid, n, n_runs, cap, np.ones(n))
    assert all((got[i] == count).all() for i in (3, 7, 8, 9))
    assert not any(got[i].any() for i in (1, 2, 5, 6))


def test_a_field_of_its_own_word_and_no_columns():
    """A known width that leaves no room beside its rows' growth (over 63
    bits) is scanned at whole width; nothing to sum scans nothing."""
    n = 1000
    _gid, _live, ends, n_runs = _rows(n, 50, 0, 1)
    v = np.random.default_rng(1).integers(0, 2 ** 54, n, dtype=np.int64)
    cols = [_field(v, np.ones(n, dtype=bool), 54)]
    assert seg.run_sum_scans(cols, n) == (0, 1)
    assert seg.run_sum_scans([_field(v, np.ones(n, dtype=bool), 53)], n) \
        == (1, 0)
    got, each = _run(cols, ends, n_runs, 64, n)
    assert (got[0] == each[0]).all()
    assert seg.run_sum_scans([], n) == (0, 0)
    runs = seg.SortedRuns(jnp.asarray(ends), jnp.int32(n_runs), 64)
    assert seg.run_sums([], runs, n) == []


@pytest.mark.parametrize("n_rows", SORTED_ROWS)
@pytest.mark.parametrize("statement,bits,limbs,scans", [
    ("Q18", 13, 3, (1, 0)),     # SUM(l_quantity): ≤ 50.00
    ("Q3", 33, 4, (2, 0)),      # SUM(l_extendedprice * (1 - l_discount))
    ("unranged", None, 3, (3, 1)),
])
def test_the_words_the_benchmarks_statements_scan(statement, bits, limbs,
                                                  scans, n_rows):
    """`lgstream1.sf2`'s finalizes at its sorted rows: Q18's four state
    arrays are ONE word (13 + 25 and 1 + 25 bits at 2²⁴ rows: 64), Q3's
    and Q10's 33-bit revenue is two limb fields (30 + 25, 3 + 25) that
    with the count (26) fill two words where four arrays were scanned;
    with no width, the three limbs and the count are a word each."""
    v, m = jnp.zeros(8, dtype=jnp.int64), jnp.ones(8, dtype=bool)
    arg = T.decimal(31, 4, True) if limbs == 4 else DEC
    plan = _agg("sum", arg).row_sums(jnp, v, m, bits)
    assert len(plan) == limbs + 1
    assert seg.run_sum_scans(_columns(plan), n_rows) == scans
