"""A sound integer range for an expression's value, by interval arithmetic
on the SCALED integers the evaluator computes (expression/__init__.py:
`_arith`, `_numeric_common`, `_rescale`, `_unary_minus`) — not on the SQL
types: a DECIMAL is its scaled int64, a product's scales add and are cut
back by a floor division, a sum's operands meet at the larger scale.

Who asks: the aggregate's contraction (`ops/segment.slot_sums`) cuts only
the bits a summed value can hold, and learns them here from the device
cache's per-column (min, max) (`executor/device_cache.col_bounds`).
Sound means: for rows whose every leaf lies inside its column's range,
the evaluator's value lies inside the range returned. Every step is held
to int64, the dtype the evaluator computes in: where an intermediate
could leave it the evaluator wraps, and there is NO range. So is there
none for anything but a column, an integer or DECIMAL literal, unary
minus, `+`, `-` and `*` — the caller then keeps the whole width.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from tidb_tpu.expression import ColumnRef, Constant, Expression, ScalarFunc
from tidb_tpu.types import FieldType, TypeKind

Range = Optional[Tuple[int, int]]

_I64_MIN, _I64_MAX = -(1 << 63), (1 << 63) - 1


def _held(lo: int, hi: int) -> Range:
    return (lo, hi) if _I64_MIN <= lo and hi <= _I64_MAX else None


def _scaled_int(ftype: FieldType) -> bool:
    """A column or literal the evaluator holds as ONE exact int64: an
    integer, or a DECIMAL of at most 18 digits as its scaled value (a
    wider one is limb planes, a date or a dictionary code no quantity)."""
    return ftype.kind.is_integer or (
        ftype.kind is TypeKind.DECIMAL and not ftype.is_wide_decimal)


def column_ranges(ftypes: Sequence[FieldType], bounds) -> List[Range]:
    """The ranges of a scan's columns: `bounds` {column: (min, max)} as
    the device cache keeps them, for the columns that are scaled
    integers."""
    return [bounds.get(i) if _scaled_int(ft) else None
            for i, ft in enumerate(ftypes)]


def _times(r: Range, k: int) -> Range:
    return r and _held(r[0] * k, r[1] * k)


def _product(a: Range, b: Range) -> Range:
    if a is None or b is None:
        return None
    ends = [x * y for x in a for y in b]
    return _held(min(ends), max(ends))


def value_range(e: Expression, cols: Sequence[Range]) -> Range:
    """(lo, hi) that hold `e`'s evaluated value wherever every column i it
    reads lies inside `cols[i]`; None = unknown."""
    if isinstance(e, ColumnRef):
        return cols[e.index] if e.index < len(cols) else None
    if type(e) is Constant:     # (a ParamExpr's value is not the program's)
        if e.value is None or not _scaled_int(e.ftype):
            return None
        raw = e.ftype.encode_value(e.value)
        return _held(raw, raw)
    if not isinstance(e, ScalarFunc) or e.ftype.kind.is_float or any(
            a.ftype.kind.is_float for a in e.args):
        return None
    if e.op == "unary_minus":
        r = value_range(e.args[0], cols)
        return r and _held(-r[1], -r[0])
    if e.op not in ("plus", "minus", "mul"):
        return None
    a, b = e.args
    ra, rb = value_range(a, cols), value_range(b, cols)
    decimal = TypeKind.DECIMAL in (a.ftype.kind, b.ftype.kind)
    if e.op == "mul" and e.ftype.kind is TypeKind.DECIMAL:
        # the scales add; what the result's type does not keep is cut off
        # by a FLOOR division (monotonic, so the ends map to the ends)
        r = _product(ra, rb)
        cut = a.ftype.scale + b.ftype.scale - e.ftype.scale
        if r is None or cut <= 0:
            return _times(r, 10 ** -cut)
        return r[0] // 10 ** cut, r[1] // 10 ** cut
    if decimal:
        # the operands meet at the larger scale (an integer is scale 0)
        scale = max(a.ftype.scale, b.ftype.scale)
        ra = _times(ra, 10 ** (scale - a.ftype.scale))
        rb = _times(rb, 10 ** (scale - b.ftype.scale))
    if e.op == "mul":
        return _product(ra, rb)
    if ra is None or rb is None:
        return None
    if e.op == "plus":
        return _held(ra[0] + rb[0], ra[1] + rb[1])
    return _held(ra[0] - rb[1], ra[1] - rb[0])


def sum_bits(r: Range) -> Optional[int]:
    """How many low bits hold a value of range `r`, when it cannot be
    negative — the width a program is compiled for, so that a moving
    minimum or a maximum that stays under its power of two mints no
    program; None = the whole width."""
    return r[1].bit_length() if r is not None and r[0] >= 0 else None


def agg_arg_bits(root, bounds_key, input_ranges
                 ) -> Tuple[Optional[int], ...]:
    """`sum_bits` of each argument of aggregate `root` (a plan node) over
    input columns of ranges `input_ranges()`, for the aggregates whose
    state sums it: a SUM or AVG of one argument (a DISTINCT one sums a
    value per group and value, and with several arguments a combined
    code: nothing is said of it). Remembered on the plan node for as long
    as the bounds it was derived from stand (`bounds_key`, hashable): a
    warm statement derives nothing again — 60 µs of interpreter a Q1,
    which eight connections under one interpreter lock pay in
    throughput."""
    memo = root.__dict__.get("_arg_bits")
    if memo is None or memo[0] != bounds_key:
        cols = input_ranges()
        memo = root.__dict__["_arg_bits"] = (bounds_key, tuple(
            sum_bits(value_range(d.args[0], cols))
            if d.name in ("sum", "avg") and len(d.args) == 1
            and not d.distinct else None for d in root.aggs))
    return memo[1]
