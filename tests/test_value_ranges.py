"""`expression/ranges`: the range an aggregate's argument can hold, by
interval arithmetic on the scaled integers the evaluator computes. Sound:
for random rows inside random column bounds — both ends of every column
among them — the evaluator's value lies inside the derived range; and an
expression the arithmetic does not cover has no range at all."""

from decimal import Decimal

import numpy as np
import pytest

from tidb_tpu import types as T
from tidb_tpu.expression import (ColumnRef, EvalContext, ParamExpr, cast,
                                 func, lit)
from tidb_tpu.expression import ranges
from tidb_tpu.expression.aggfuncs import AggDesc

DEC = T.decimal(15, 2, True)        # the benchmark's quantities and prices
INT = T.bigint(True)
FINE = T.decimal(18, 17, True)      # two of these multiply past scale 30
FINE2 = T.decimal(18, 16, True)


def _c(i, ft=DEC):
    return ColumnRef(i, ft)


def _one():
    return lit(Decimal("1.00"))


# name → (expression over columns 0.., their types)
SHAPES = {
    "c": (_c(0), [DEC]),
    "int c": (_c(0, INT), [INT]),
    "c*k": (func("mul", _c(0), lit(Decimal("0.25"))), [DEC]),
    "c*int k": (func("mul", _c(0), lit(7)), [DEC]),
    "int c*int k": (func("mul", _c(0, INT), lit(-3)), [INT]),
    "c*(k-d)": (func("mul", _c(0), func("minus", _one(), _c(1))),
                [DEC, DEC]),
    "c*(k-d)*(k+t)": (
        func("mul", func("mul", _c(0), func("minus", _one(), _c(1))),
             func("plus", _one(), _c(2))), [DEC, DEC, DEC]),
    "-c": (func("unary_minus", _c(0)), [DEC]),
    "-(a-b)": (func("unary_minus", func("minus", _c(0), _c(1))), [DEC, DEC]),
    "a+b": (func("plus", _c(0), _c(1)), [DEC, DEC]),
    "a-b": (func("minus", _c(0), _c(1)), [DEC, DEC]),
    # the rescales: an integer meets a DECIMAL at the DECIMAL's scale, two
    # scales meet at the larger, a product past scale 30 is cut back
    "int+dec": (func("plus", _c(0, INT), _c(1)), [INT, DEC]),
    "dec(15,2)-dec(12,4)": (func("minus", _c(0), _c(1, T.decimal(12, 4))),
                            [DEC, T.decimal(12, 4)]),
    "dec*int col": (func("mul", _c(0), _c(1, INT)), [DEC, INT]),
    "scale 33 cut to 30": (func("mul", _c(0, FINE), _c(1, FINE2)),
                           [FINE, FINE2]),
}


def _bounds(rng, ft, sign):
    """Random (lo, hi) a column of this type can hold: small enough that
    three of them multiply inside int64, `sign` -1 / 0 / +1 = below zero,
    across it, above."""
    top = 10 ** 5 if ft.scale < 10 else 10 ** 9
    a, b = sorted(int(x) for x in rng.integers(0, top, 2))
    return {1: (a, b), -1: (-b, -a), 0: (-a, b)}[sign]


@pytest.mark.parametrize("sign", [1, 0, -1])
@pytest.mark.parametrize("name", sorted(SHAPES))
def test_the_evaluators_value_lies_inside_the_range(name, sign):
    e, fts = SHAPES[name]
    rng = np.random.default_rng(len(name) * 3 + sign)
    for _ in range(8):
        bounds = {i: _bounds(rng, ft, sign) for i, ft in enumerate(fts)}
        r = ranges.value_range(e, ranges.column_ranges(fts, bounds))
        assert r is not None, (name, bounds)
        # every corner of the columns' box first, random rows after
        corners = np.array(np.meshgrid(*[bounds[i] for i in range(len(fts))]),
                           dtype=np.int64).reshape(len(fts), -1)
        cols = [np.concatenate([corners[i], rng.integers(
            bounds[i][0], bounds[i][1] + 1, 500)]) for i in range(len(fts))]
        ctx = EvalContext(np, [(v, np.ones(len(v), dtype=bool))
                               for v in cols])
        v, m = e.eval(ctx)
        assert m.all() and v.dtype == np.int64
        assert r[0] <= int(v.min()) and int(v.max()) <= r[1], (name, bounds,
                                                               r)
        # and it is no wider than the corners make it (monotonic shapes
        # reach both ends; a product's floor cut may fall one short)
        assert int(v.min()) - r[0] <= 1 and r[1] - int(v.max()) <= 1


def test_q1_at_the_benchmarks_domains():
    """TPC-H's domains as the benchmark loads them: the widths the plan
    of Q1 is sized by."""
    fts = [DEC] * 4
    cols = ranges.column_ranges(
        fts, {0: (100, 5000), 1: (90000, (1 << 24) - 1), 2: (0, 10),
              3: (0, 8)})
    disc_price = func("mul", _c(1), func("minus", _one(), _c(2)))
    charge = func("mul", disc_price, func("plus", _one(), _c(3)))
    class Root:     # what of a PhysHashAgg the derivation reads
        aggs = [AggDesc("sum", [_c(0)]), AggDesc("sum", [_c(1)]),
                AggDesc("sum", [disc_price]), AggDesc("sum", [charge]),
                AggDesc("avg", [_c(2)]), AggDesc("count", []),
                AggDesc("min", [_c(0)]),
                AggDesc("sum", [_c(0)], distinct=True)]
    root, asked = Root(), []
    want = (13, 24, 31, 38, 4, None, None, None)
    assert ranges.agg_arg_bits(root, "these bounds",
                               lambda: asked.append(1) or cols) == want
    # remembered on the plan node while the bounds stand
    assert ranges.agg_arg_bits(root, "these bounds", None) == want
    narrower = ranges.column_ranges(fts, {0: (100, 255), 1: (0, 0)})
    assert ranges.agg_arg_bits(root, "other bounds", lambda: narrower) \
        == (8, 0, None, None, None, None, None, None)
    assert asked == [1]


def test_what_the_arithmetic_does_not_cover_has_no_range():
    cols = ranges.column_ranges([DEC, T.double(True), T.varchar(10), INT],
                                {0: (0, 9), 1: (0, 9), 2: (0, 3), 3: (0, 9)})
    # a float column and a dictionary's codes are no quantities
    assert cols == [(0, 9), None, None, (0, 9)]
    for e in [
            func("abs", _c(0)),                               # a function
            func("div", _c(0), lit(2)),                       # → DOUBLE
            func("plus", _c(0), _c(1, T.double(True))),       # a float
            func("mul", _c(0), lit(0.5)),
            func("case", func("gt", _c(0), lit(1)), _c(0), _c(3)),
            func("if", func("gt", _c(0), lit(1)), _c(0), _c(3)),
            cast(_c(0), T.decimal(10, 0)),
            func("plus", _c(0), ParamExpr(1, INT)),   # not the program's
            func("plus", _c(0), lit(None)),
            _c(2, T.varchar(10)), _c(7)]:
        assert ranges.value_range(e, cols) is None, e
    # an intermediate that could leave int64 wraps in the evaluator
    big = ranges.column_ranges([INT, INT], {0: (0, 1 << 40), 1: (0, 1 << 40)})
    assert ranges.value_range(func("mul", _c(0, INT), _c(1, INT)), big) \
        is None
    assert ranges.value_range(func("plus", _c(0, INT), _c(1, INT)), big) \
        == (0, 1 << 41)
    # a width only for what cannot be negative
    assert ranges.sum_bits((0, 0)) == 0 and ranges.sum_bits((3, 8)) == 4
    assert ranges.sum_bits((-1, 8)) is None and ranges.sum_bits(None) is None
