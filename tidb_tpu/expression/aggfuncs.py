"""Aggregate function framework — SoA partial states over segment ops.

Ref: /root/reference/executor/aggfuncs/aggfuncs.go:143-180 — each agg defines
a partial-result state machine (AllocPartialResult / UpdatePartialResult /
MergePartialResult / AppendFinalResult2Chunk) so the planner can split
aggregation into partial+final phases for parallel and distributed execution.

TPU-first redesign (SURVEY A.4): the per-group partial struct becomes one
array PER FIELD over dense group slots — e.g. partialResult4SumFloat64
{val; notNullRowCount} (func_sum.go:40-43) becomes (sums[G], counts[G]).
`update` scatters rows into group slots with segment ops; `merge` scatters
*partial-state rows* into coarser group slots — the same op, which is exactly
why the two-phase split (and the cross-shard psum/all-gather reduce) falls
out for free. All methods are xp-generic: numpy on host, jnp under jit.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import numpy as np

from tidb_tpu import types as T
from tidb_tpu.chunk.compress import (WIDE_LIMB_BASE, WIDE_LIMB_BITS,
                                     wide_decimal_unlimb)
from tidb_tpu.errors import PlanError
from tidb_tpu.expression import ColumnRef, Expression
from tidb_tpu.ops import segment as seg
from tidb_tpu.types import FieldType, TypeKind

AVG_EXTRA_SCALE = 4  # MySQL: AVG(DECIMAL(p,s)) → DECIMAL(p+4, s+4)


@dataclass
class AggDesc:
    """Planner-side descriptor (ref: expression/aggregation/descriptor.go:35)."""

    name: str                       # count | sum | avg | min | max | ...
    args: List[Expression]
    distinct: bool = False
    ftype: FieldType = None         # result type, filled by infer_agg_type

    def __post_init__(self):
        if self.ftype is None:
            self.ftype = infer_agg_type(self.name, self.args, self.distinct)


def infer_agg_type(name: str, args: Sequence[Expression],
                   distinct: bool) -> FieldType:
    at = args[0].ftype if args else None
    if name == "count":
        return T.bigint(False)
    if name == "sum":
        if at.kind.is_float or at.kind.is_string:
            return T.double(True)
        if at.kind is TypeKind.DECIMAL:
            return T.decimal(min(at.precision + 22, 65), at.scale, True)
        return T.bigint(True)  # deviation: int sums stay int64 (exact, fast)
    if name == "avg":
        if at.kind.is_float or at.kind.is_string:
            return T.double(True)
        if at.kind is TypeKind.DECIMAL:
            return T.decimal(min(at.precision + AVG_EXTRA_SCALE, 65),
                             min(at.scale + AVG_EXTRA_SCALE, 30), True)
        return T.decimal(24, AVG_EXTRA_SCALE, True)
    if name in ("min", "max", "first_row"):
        return at.with_nullable(True)
    if name in ("var_pop", "var_samp", "variance", "std", "stddev",
                "stddev_pop", "stddev_samp"):
        return T.double(True)
    if name == "group_concat":
        return T.varchar(nullable=True)
    if name in ("json_arrayagg", "json_objectagg"):
        return T.json_type(True)
    if name in ("bit_and", "bit_or", "bit_xor"):
        return T.bigint(False)
    raise PlanError(f"unsupported aggregate function: {name}")


class AggFunc:
    """One aggregate's state machine. State = tuple of (G,)-arrays."""

    device_capable = True  # set False for host-only (string/object states)
    # `merge` ADDS floating-point states (a float SUM/AVG, a variance): its
    # result depends on the order of the additions, which one reduction
    # over stacked partials leaves to the compiler
    float_sums = False

    def __init__(self, desc: AggDesc):
        self.desc = desc
        self.ftype = desc.ftype

    # -- state ------------------------------------------------------------
    def init(self, xp, n: int) -> Tuple:
        raise NotImplementedError

    def update(self, xp, state: Tuple, gid, n: int, values, validity) -> Tuple:
        raise NotImplementedError

    def merge(self, xp, state: Tuple, gid, n: int, partial: Tuple) -> Tuple:
        raise NotImplementedError

    def row_sums(self, xp, values, validity,
                 bits: Optional[int] = None) -> Optional[List]:
        """`update` as sums of per-row integers, for a caller that
        computes every aggregate's sums in one pass
        (ops/segment.slot_sums): → one seg.SumColumn per array of the
        state tuple — its sum by slot IS what `update` adds to that array
        — and None for an array `update` leaves alone. None altogether
        when the update is anything else (MIN/MAX, FIRST, BIT, a float
        sum). `bits`: every valid row's value is known to lie in
        [0, 2^bits) (expression/ranges.sum_bits), so the columns name no
        bit above; None = nothing is known."""
        return None

    def final(self, xp, state: Tuple):
        """→ (values, validity) arrays of length G."""
        raise NotImplementedError

    def final_narrow(self, xp, state: Tuple):
        """→ (values, validity, fits): final() as ONE 1-D array a traced
        program can go on computing with; `fits` is a scalar, False when
        some group's value cannot be held so (only a wide SUM's can
        not)."""
        v, m = self.final(xp, state)
        return v, m, True

    def order_keys(self, xp, state: Tuple) -> List[Tuple]:
        """→ [(values, validity)] most significant first: sort keys whose
        lexicographic order is the order of the aggregate's value (what
        an ORDER BY over the aggregate sorts by, in-trace)."""
        return [self.final(xp, state)]


# ---------------------------------------------------------------------------
# COUNT (ref: executor/aggfuncs/func_count.go)
# ---------------------------------------------------------------------------


class CountAgg(AggFunc):
    """COUNT(*) and COUNT(expr). State: (counts,)."""

    def __init__(self, desc: AggDesc, star: bool = False):
        super().__init__(desc)
        self.star = star

    def init(self, xp, n):
        return (xp.zeros(n, dtype=xp.int64),)

    def update(self, xp, state, gid, n, values, validity):
        (counts,) = state
        return (counts + seg.segment_count(xp, validity, gid, n),)

    def row_sums(self, xp, values, validity, bits=None):
        return [seg.SumColumn(None, validity)]

    def merge(self, xp, state, gid, n, partial):
        (counts,) = state
        (pcounts,) = partial
        return (counts + seg.segment_sum(xp, pcounts, gid, n),)

    def final(self, xp, state):
        (counts,) = state
        return counts, xp.ones(counts.shape[0], dtype=bool)


# ---------------------------------------------------------------------------
# SUM (ref: executor/aggfuncs/func_sum.go)
# ---------------------------------------------------------------------------


class SumAgg(AggFunc):
    """State: (sums, counts). Result NULL iff no non-NULL input row."""

    def __init__(self, desc: AggDesc):
        super().__init__(desc)
        self._float = self.float_sums = self.ftype.kind.is_float
        self._in_scale = desc.args[0].ftype.scale
        self._out_scale = self.ftype.scale
        # wide result (> 18 digits): EXACT Python-int accumulation on the
        # numpy side (object arrays; types/mydecimal.go arbitrary-width
        # analog). The device engine runs these through the base-10⁹ limb
        # formulation instead (executor/device_emit wide aggs).
        self._wide = self.ftype.is_wide_decimal or \
            desc.args[0].ftype.is_wide_decimal
        # wide-COLUMN args arrive as Python-int object arrays on host;
        # narrow args with a wide RESULT take the vectorized int64 limb
        # path on BOTH engines (numpy bit ops — exact without per-element
        # Python integer math)
        self._arg_obj = desc.args[0].ftype.np_dtype == np.dtype(object)

    def _acc_dtype(self, xp):
        if self._wide:
            return object
        if not self._float:
            return xp.int64
        from tidb_tpu.ops.jax_env import device_float_dtype
        return device_float_dtype() if xp is not np else xp.float64

    def _cast_in(self, xp, values):
        dt = self._acc_dtype(xp)
        v = values.astype(dt)
        if self.ftype.kind is TypeKind.DECIMAL and self._out_scale > self._in_scale:
            v = v * (10 ** (self._out_scale - self._in_scale))
        return v

    def init(self, xp, n):
        if self._float:
            # two-float (hi, lo) accumulator: f64-quality SUM(double) on an
            # f32-only device (ops/segment.segment_sum_accurate)
            dt = self._acc_dtype(xp)
            return (xp.zeros(n, dtype=dt), xp.zeros(n, dtype=dt),
                    xp.zeros(n, dtype=xp.int64))
        if self._wide and (xp is not np or not self._arg_obj):
            return self._init_wide(xp, n)
        return (xp.zeros(n, dtype=self._acc_dtype(xp)),
                xp.zeros(n, dtype=xp.int64))

    # -- wide-decimal limb path (device): state = per-limb int64 sums.
    # Per-limb sums need no carries — Σ state[k]·2^(30k) recombines
    # exactly on host even when planes exceed the base (device_cache
    # wide_decimal_limbs / wide_decimal_unlimb; types/mydecimal.go:236).
    # EVERY limb producer uses base 2³⁰: wide COLUMNS arrive as 2-D
    # storage planes; 1-D int64 inputs (narrow or computed wide-typed
    # expressions) split into three shift/mask limbs at trace time —
    # dispatch is on the ARRAY SHAPE, never on the expression's type, so
    # a computed wide expression can never be recombined in the wrong
    # base (round-4 review catch).
    def _n_limb_planes(self) -> int:
        aft = self.desc.args[0].ftype
        return max(aft.wide_limb_count if aft.is_wide_decimal else 0, 3)

    def _init_wide(self, xp, n):
        planes = self._n_limb_planes()
        return tuple(xp.zeros(n, dtype=xp.int64)
                     for _ in range(planes + 1))   # limbs… + counts

    def _input_limbs(self, xp, values):
        if getattr(values, "ndim", 1) == 2:
            return [values[k] for k in range(values.shape[0])]
        mask = xp.int64(WIDE_LIMB_BASE - 1)
        return [values & mask,
                (values >> WIDE_LIMB_BITS) & mask,
                values >> (2 * WIDE_LIMB_BITS)]   # 90 bits ⊇ int64

    def _update_wide(self, xp, state, gid, n, values, validity):
        limbs = self._input_limbs(xp, values)
        out = []
        for st, limb in zip(state, limbs):
            lv = xp.where(validity, limb, xp.zeros_like(limb))
            out.append(st + seg.segment_sum(xp, lv, gid, n))
        out.extend(state[len(limbs):-1])     # untouched higher planes
        out.append(state[-1] + seg.segment_count(xp, validity, gid, n))
        return tuple(out)

    def row_sums(self, xp, values, validity, bits=None):
        if self._float or (self._wide and xp is np and self._arg_obj):
            return None
        count = seg.SumColumn(None, validity)
        if not self._wide:
            v = self._cast_in(xp, values)
            if bits is not None:    # _cast_in's scale correction widens it
                mul = 10 ** max(self._out_scale - self._in_scale, 0) \
                    if self.ftype.kind is TypeKind.DECIMAL else 1
                bits = (((1 << bits) - 1) * mul).bit_length()
            if bits is None or bits > 63:
                return [seg.SumColumn(v, validity), count]
            return [seg.SumColumn(v, validity, None, 0, bits, False), count]
        # the limb planes of _update_wide, each a bit field of the value
        B = WIDE_LIMB_BITS
        if getattr(values, "ndim", 1) == 2:
            limbs = [seg.SumColumn(values, validity, k)
                     for k in range(values.shape[0])]
        else:
            if values.dtype != xp.int64:
                values = values.astype(xp.int64)
            def limb(at, width, top=False):
                # of its field a limb keeps the bits the value can hold
                # (none: the constant 0), unsigned once it is known ≥ 0
                if bits is None:
                    return seg.SumColumn(values, validity, None, at, width,
                                         top)
                return seg.SumColumn(values, validity, None, at,
                                     min(max(bits - at, 0), width), False)
            limbs = [limb(0, B), limb(B, B), limb(2 * B, 64 - 2 * B, True)]
        untouched = [None] * (self._n_limb_planes() - len(limbs))
        return limbs + untouched + [count]

    def _merge_wide(self, xp, state, gid, n, partial):
        out = [st + seg.segment_sum(xp, p, gid, n)
               for st, p in zip(state[:-1], partial[:-1])]
        out.append(state[-1] + seg.segment_sum(xp, partial[-1], gid, n))
        return tuple(out)

    def update(self, xp, state, gid, n, values, validity):
        if self._wide and (xp is not np or not self._arg_obj):
            return self._update_wide(xp, state, gid, n, values, validity)
        if self._float:
            hi, lo, counts = state
            v = self._cast_in(xp, values)
            v = xp.where(validity, v, xp.zeros_like(v))
            nh, nl = seg.segment_sum_accurate(xp, v, gid, n)
            hi, lo = seg.two_float_add(xp, hi, lo, nh.astype(hi.dtype),
                                       nl.astype(hi.dtype))
            return (hi, lo, counts + seg.segment_count(xp, validity, gid, n))
        sums, counts = state
        v = self._cast_in(xp, values)
        v = xp.where(validity, v, xp.zeros_like(v))
        return (sums + seg.segment_sum(xp, v, gid, n),
                counts + seg.segment_count(xp, validity, gid, n))

    def merge(self, xp, state, gid, n, partial):
        if self._wide and len(partial) > 2 and len(state) <= 2:
            # a device limb-formulation partial (per-plane sums + counts)
            # meeting the host's exact object-int narrow state — the
            # staged distributed merges land here with wide object-column
            # args. Recombining the limbs is exact (no carries, see
            # _init_wide), and the scale correction mirrors _sum_of: the
            # limb update accumulated RAW input limbs without _cast_in
            limbs = np.stack([np.asarray(a) for a in partial[:-1]])
            psums = wide_decimal_unlimb(limbs)
            if self._out_scale > self._in_scale:
                psums = psums * 10 ** (self._out_scale - self._in_scale)
            partial = (psums, np.asarray(partial[-1]))
        if self._wide and len(state) > 2:
            return self._merge_wide(xp, state, gid, n, partial)
        return self._merge_narrow(xp, state, gid, n, partial)

    def _merge_narrow(self, xp, state, gid, n, partial):
        if self._float:
            hi, lo, counts = state
            phi, plo, pcounts = partial
            mh1, ml1 = seg.segment_sum_accurate(xp, phi.astype(hi.dtype),
                                                gid, n)
            mh2, ml2 = seg.segment_sum_accurate(xp, plo.astype(hi.dtype),
                                                gid, n)
            ah, al = seg.two_float_add(xp, mh1, ml1, mh2, ml2)
            hi, lo = seg.two_float_add(xp, hi, lo, ah, al)
            return (hi, lo, counts + seg.segment_sum(xp, pcounts, gid, n))
        sums, counts = state
        psums, pcounts = partial
        return (sums + seg.segment_sum(xp, psums.astype(sums.dtype), gid, n),
                counts + seg.segment_sum(xp, pcounts, gid, n))

    def _sum_of(self, xp, state):
        if self._float:
            hi, lo, counts = state
            return hi.astype(np.float64) + lo.astype(np.float64), counts
        if self._wide and len(state) > 2:
            limbs = np.stack([np.asarray(a) for a in state[:-1]])
            sums = wide_decimal_unlimb(limbs)    # one base, all producers
            if self._out_scale > self._in_scale:
                sums = sums * 10 ** (self._out_scale - self._in_scale)
            return sums, np.asarray(state[-1])
        return state

    def final(self, xp, state):
        sums, counts = self._sum_of(xp, state)
        return sums, counts > 0

    @property
    def orders_in_trace(self) -> bool:
        """Can ORDER BY this SUM run inside a device program? A narrow sum
        is one int64. A wide RESULT over a 1-D int64 argument (narrow, or
        computed and wide-typed) is three limb planes, which `order_keys`
        normalizes into two int64 keys; a wide COLUMN argument arrives as
        its own 2-D planes and stays host-ordered."""
        arg = self.desc.args[0]
        return not (isinstance(arg, ColumnRef) and arg.ftype.is_wide_decimal)

    def final_narrow(self, xp, state):
        if not (self._wide and len(state) > 2):
            return super().final_narrow(xp, state)
        # the limb planes recombined into one int64, as long as every
        # group's sum (scale correction included) fits one
        (top, valid), (low, _) = self.order_keys(xp, state)
        mul = 10 ** max(self._out_scale - self._in_scale, 0)
        v = (top << 60) + low           # garbage unless −8 ≤ top < 8
        ok = (top >= -8) & (top < 8)
        if mul > 1:
            lim = xp.int64((1 << 63) // mul)
            ok = ok & (v >= -lim) & (v < lim)
        fits = xp.all(xp.logical_not(valid) | ok)
        return v * xp.int64(mul), valid, fits

    def order_keys(self, xp, state):
        if not (self._wide and len(state) > 2):
            return [self.final(xp, state)]
        # a 1-D input fills planes 0..2 only (_input_limbs), and per-limb
        # sums carry no normalization (_init_wide): propagate the carries
        # (arithmetic shifts floor, so negatives work), then (top, middle ·
        # 2³⁰ + low) orders like the value — the scale correction of
        # _sum_of is a positive constant and cannot reorder
        assert self.orders_in_trace
        l0, l1, l2 = state[:3]
        mask = xp.int64(WIDE_LIMB_BASE - 1)
        l1 = l1 + (l0 >> WIDE_LIMB_BITS)
        top = l2 + (l1 >> WIDE_LIMB_BITS)
        low = ((l1 & mask) << WIDE_LIMB_BITS) | (l0 & mask)
        valid = state[-1] > 0
        return [(top, valid), (low, valid)]


# ---------------------------------------------------------------------------
# AVG (ref: executor/aggfuncs/func_avg.go)
# ---------------------------------------------------------------------------


class AvgAgg(SumAgg):
    """Same state as SUM; final divides. Decimal result rounds half-away."""

    def final(self, xp, state):
        sums, counts = self._sum_of(xp, state)
        valid = counts > 0
        safe = xp.where(valid, counts, xp.ones_like(counts))
        if self.ftype.kind.is_float:
            return sums / safe.astype(sums.dtype), valid
        # decimal: sums already at out_scale; round half-away-from-zero
        q = xp.abs(sums) // safe
        r = xp.abs(sums) - q * safe
        q = q + (2 * r >= safe).astype(xp.int64)
        return xp.where(sums < 0, -q, q), valid


# ---------------------------------------------------------------------------
# MIN / MAX (ref: executor/aggfuncs/func_max_min.go)
# ---------------------------------------------------------------------------


class MinMaxAgg(AggFunc):
    """State: (vals, seen). Numeric path is segment_min/max; host strings
    sort-then-first (object arrays have no scatter identity)."""

    def __init__(self, desc: AggDesc, is_min: bool):
        super().__init__(desc)
        self.is_min = is_min
        self._is_string = self.ftype.kind.is_string
        # wide decimals ride the host-object path too: Python ints have
        # no scatter identity either, but order totally
        self._host_obj = self._is_string or self.ftype.is_wide_decimal
        if self._host_obj:
            self.device_capable = False  # dictionary codes differ per chunk

    def _identity(self, xp, n):
        if self._host_obj:
            return np.full(n, None, dtype=object)
        dt = self.desc.args[0].ftype.np_dtype
        if xp is not np and np.dtype(dt) == np.dtype(np.float64):
            from tidb_tpu.ops.jax_env import device_float_dtype
            dt = device_float_dtype()
        ident = (seg._max_identity(np.dtype(dt)) if self.is_min
                 else seg._min_identity(np.dtype(dt)))
        return xp.full(n, ident, dtype=dt)

    def init(self, xp, n):
        return (self._identity(xp, n), xp.zeros(n, dtype=bool))

    def _combine(self, xp, data, gid, n):
        return (seg.segment_min(xp, data, gid, n) if self.is_min
                else seg.segment_max(xp, data, gid, n))

    def update(self, xp, state, gid, n, values, validity):
        vals, seen = state
        if self._host_obj:
            return self._update_string(state, gid, n, values, validity)
        ident = self._identity(xp, 1)[0]
        v = xp.where(validity, values.astype(vals.dtype),
                     xp.full_like(vals[:1], ident)[0])
        vals2 = self._combine(xp, xp.concatenate([vals, v]),
                              xp.concatenate([xp.arange(n), gid]), n)
        return (vals2, seen | seg.segment_any(xp, validity, gid, n))

    def _update_string(self, state, gid, n, values, validity):
        vals, seen = state
        sort_key = values[validity]
        if self._is_string:
            sort_key = sort_key.astype(str)
            if self.ftype.is_ci:
                from tidb_tpu.types import fold_ci_array
                sort_key = fold_ci_array(
                    np.asarray(sort_key, dtype=object))
        order = np.argsort(sort_key, kind="stable")
        if not self.is_min:
            order = order[::-1]
        g = gid[validity][order]
        v = values[validity][order]
        first, found = seg.segment_first(np, v, np.ones(len(v), dtype=bool),
                                         g, n)
        out = vals.copy()
        for i in range(n):
            if found[i]:
                cand = first[i]
                cur = out[i]
                if self._is_string and self.ftype.is_ci:
                    key = (lambda x: str(x).upper())
                else:
                    key = (lambda x: x)
                if cur is None:
                    out[i] = cand
                elif self.is_min:
                    out[i] = min(cur, cand, key=key)
                else:
                    out[i] = max(cur, cand, key=key)
        return (out, seen | found)

    def merge(self, xp, state, gid, n, partial):
        pvals, pseen = partial
        return self.update(xp, state, gid, n, pvals, pseen)

    def final(self, xp, state):
        vals, seen = state
        if self._host_obj:
            fill = "" if self._is_string else 0
            return np.array([v if v is not None else fill
                             for v in vals], dtype=object), seen
        return vals, seen


# ---------------------------------------------------------------------------
# FIRST_ROW (ref: executor/aggfuncs/func_first_row.go) — planner-injected for
# non-grouped select items; any row of the group is a correct answer.
# ---------------------------------------------------------------------------


class FirstRowAgg(AggFunc):
    """State: (vals, val_validity, seen)."""

    def __init__(self, desc: AggDesc):
        super().__init__(desc)
        self._is_string = self.ftype.kind.is_string
        if self._is_string or self.ftype.is_wide_decimal:
            self.device_capable = False

    def init(self, xp, n):
        if self._is_string:
            vals = np.full(n, "", dtype=object)
        else:
            dt = self.desc.args[0].ftype.np_dtype
            if xp is not np and np.dtype(dt) == np.dtype(np.float64):
                from tidb_tpu.ops.jax_env import device_float_dtype
                dt = device_float_dtype()
            vals = xp.zeros(n, dtype=dt)
        return (vals, xp.zeros(n, dtype=bool), xp.zeros(n, dtype=bool))

    def update(self, xp, state, gid, n, values, validity):
        vals, vvalid, seen = state
        rows = xp.ones(gid.shape[0], dtype=bool)  # first row, NULL or not
        fv, found = seg.segment_first(xp, values, rows, gid, n)
        fm, _ = seg.segment_first(xp, validity, rows, gid, n)
        take = found & ~seen
        if self._is_string:
            out = vals.copy()
            out[take] = fv[take]
        else:
            out = xp.where(take, fv.astype(vals.dtype), vals)
        return (out, xp.where(take, fm, vvalid), seen | found)

    def merge(self, xp, state, gid, n, partial):
        pvals, pvalid, pseen = partial
        vals, vvalid, seen = state
        fv, found = seg.segment_first(xp, pvals, pseen, gid, n)
        fm, _ = seg.segment_first(xp, pvalid, pseen, gid, n)
        take = found & ~seen
        if self._is_string:
            out = vals.copy()
            out[take] = fv[take]
        else:
            out = xp.where(take, fv.astype(vals.dtype), vals)
        return (out, xp.where(take, fm, vvalid), seen | found)

    def final(self, xp, state):
        vals, vvalid, seen = state
        return vals, vvalid & seen


# ---------------------------------------------------------------------------
# Variance family (ref: executor/aggfuncs/func_varpop.go) — (n, Σx, Σx²)
# ---------------------------------------------------------------------------


class VarianceAgg(AggFunc):
    float_sums = True

    def __init__(self, desc: AggDesc, sample: bool, stddev: bool):
        super().__init__(desc)
        self.sample = sample
        self.stddev = stddev
        self._in_ftype = desc.args[0].ftype

    def _fdt(self, xp):
        if xp is np:
            return np.float64
        from tidb_tpu.ops.jax_env import device_float_dtype
        return device_float_dtype()

    def init(self, xp, n):
        fdt = self._fdt(xp)
        return (xp.zeros(n, dtype=xp.int64), xp.zeros(n, dtype=fdt),
                xp.zeros(n, dtype=fdt))

    def _as_float(self, xp, values):
        v = values.astype(self._fdt(xp))
        if self._in_ftype.kind is TypeKind.DECIMAL and self._in_ftype.scale:
            v = v / (10 ** self._in_ftype.scale)
        return v

    def update(self, xp, state, gid, n, values, validity):
        cnt, s1, s2 = state
        v = self._as_float(xp, values)
        v = xp.where(validity, v, xp.zeros_like(v))
        return (cnt + seg.segment_count(xp, validity, gid, n),
                s1 + seg.segment_sum(xp, v, gid, n),
                s2 + seg.segment_sum(xp, v * v, gid, n))

    def merge(self, xp, state, gid, n, partial):
        cnt, s1, s2 = state
        pc, p1, p2 = partial
        return (cnt + seg.segment_sum(xp, pc, gid, n),
                s1 + seg.segment_sum(xp, p1.astype(s1.dtype), gid, n),
                s2 + seg.segment_sum(xp, p2.astype(s2.dtype), gid, n))

    def final(self, xp, state):
        cnt, s1, s2 = state
        need = 2 if self.sample else 1
        valid = cnt >= need
        fc = cnt.astype(s1.dtype)
        safe = xp.where(valid, fc, xp.ones_like(fc))
        mean = s1 / safe
        var = s2 / safe - mean * mean
        var = xp.maximum(var, 0.0)  # numerical floor
        if self.sample:
            denom = xp.where(valid, fc - 1.0, xp.ones_like(fc))
            var = var * fc / denom
        out = xp.sqrt(var) if self.stddev else var
        return out, valid


# ---------------------------------------------------------------------------
# Bit aggregates (ref: executor/aggfuncs/func_bitfuncs.go)
# ---------------------------------------------------------------------------


class BitAgg(AggFunc):
    device_capable = False  # bitwise segment scatter: host ufunc.at only

    def __init__(self, desc: AggDesc, op: str):
        super().__init__(desc)
        self.op = op  # and | or | xor

    def init(self, xp, n):
        start = -1 if self.op == "and" else 0  # all-ones identity for AND
        return (np.full(n, start, dtype=np.int64),)

    def update(self, xp, state, gid, n, values, validity):
        (acc,) = state
        out = acc.copy()
        v = values[validity].astype(np.int64)
        g = gid[validity]
        ufn = {"and": np.bitwise_and, "or": np.bitwise_or,
               "xor": np.bitwise_xor}[self.op]
        ufn.at(out, g, v)
        return (out,)

    def merge(self, xp, state, gid, n, partial):
        (pacc,) = partial
        return self.update(xp, state, gid, n, pacc,
                           np.ones(len(pacc), dtype=bool))

    def final(self, xp, state):
        (acc,) = state
        # MySQL: unsigned 64-bit result; keep the int64 bit pattern
        return acc, np.ones(len(acc), dtype=bool)


# ---------------------------------------------------------------------------
# GROUP_CONCAT (ref: executor/aggfuncs/func_group_concat.go) — host only
# ---------------------------------------------------------------------------


class GroupConcatAgg(AggFunc):
    device_capable = False

    def __init__(self, desc: AggDesc, separator: str = ","):
        super().__init__(desc)
        self.sep = separator

    def init(self, xp, n):
        return ([[] for _ in range(n)],)

    def update(self, xp, state, gid, n, values, validity):
        (parts,) = state
        for g, v, ok in zip(np.asarray(gid), values, np.asarray(validity)):
            if ok:
                parts[int(g)].append(_display(v, self.desc.args[0].ftype))
        return (parts,)

    def merge(self, xp, state, gid, n, partial):
        (parts,) = state
        (pparts,) = partial
        for g, lst in zip(np.asarray(gid), pparts):
            parts[int(g)].extend(lst)
        return (parts,)

    def final(self, xp, state):
        (parts,) = state
        vals = np.array([self.sep.join(p) if p else "" for p in parts],
                        dtype=object)
        valid = np.array([bool(p) for p in parts], dtype=bool)
        return vals, valid


def _display(raw, ftype: FieldType) -> str:
    v = ftype.decode_value(raw)
    return str(v)


# ---------------------------------------------------------------------------
# Builder (ref: executor/aggfuncs/builder.go)
# ---------------------------------------------------------------------------


def build_agg(desc: AggDesc) -> AggFunc:
    n = desc.name
    if len(desc.args) > 1:
        # only COUNT(DISTINCT a, b, ...) takes multiple args (MySQL) —
        # JSON_OBJECTAGG's pair collapses in the builder
        if not (n == "count" and desc.distinct):
            raise PlanError(
                f"{n}() with {len(desc.args)} arguments is not supported")
    if n == "count":
        return CountAgg(desc, star=not desc.args)
    if n == "sum":
        return SumAgg(desc)
    if n == "avg":
        return AvgAgg(desc)
    if n == "min":
        return MinMaxAgg(desc, is_min=True)
    if n == "max":
        return MinMaxAgg(desc, is_min=False)
    if n == "first_row":
        return FirstRowAgg(desc)
    if n == "json_arrayagg":
        if desc.distinct:
            raise PlanError("DISTINCT is not allowed in JSON_ARRAYAGG")
        return JsonArrayAgg(desc)
    if n == "json_objectagg":
        if desc.distinct:
            raise PlanError("DISTINCT is not allowed in JSON_OBJECTAGG")
        return JsonObjectAgg(desc)
    if n in ("var_pop", "variance"):
        return VarianceAgg(desc, sample=False, stddev=False)
    if n == "var_samp":
        return VarianceAgg(desc, sample=True, stddev=False)
    if n in ("std", "stddev", "stddev_pop"):
        return VarianceAgg(desc, sample=False, stddev=True)
    if n == "stddev_samp":
        return VarianceAgg(desc, sample=True, stddev=True)
    if n == "group_concat":
        return GroupConcatAgg(desc)
    if n in ("bit_and", "bit_or", "bit_xor"):
        return BitAgg(desc, n.split("_")[1])
    raise PlanError(f"unsupported aggregate function: {n}")


AGG_NAMES = {"count", "sum", "avg", "min", "max", "first_row", "var_pop",
             "variance", "var_samp", "std", "stddev", "stddev_pop",
             "stddev_samp", "group_concat", "bit_and", "bit_or", "bit_xor",
             "json_arrayagg", "json_objectagg"}


class JsonArrayAgg(AggFunc):
    """JSON_ARRAYAGG (ref: executor/aggfuncs/func_json_arrayagg.go) —
    host-only object state; SQL NULL aggregates as JSON null."""

    device_capable = False

    def init(self, xp, n):
        return ([[] for _ in range(n)],)

    def update(self, xp, state, gid, n, values, validity):
        (parts,) = state
        ft = self.desc.args[0].ftype
        for g, v, ok in zip(np.asarray(gid), values,
                            np.asarray(validity)):
            g = int(g)
            if g >= n:
                continue          # dead row (out-of-range gid)
            parts[g].append(_json_value(v, ft) if ok else None)
        return (parts,)

    def merge(self, xp, state, gid, n, partial):
        (parts,) = state
        (pparts,) = partial
        for g, lst in zip(np.asarray(gid), pparts):
            if int(g) < n:
                parts[int(g)].extend(lst)
        return (parts,)

    def final(self, xp, state):
        (parts,) = state
        vals = np.array([_json_dump(p) for p in parts], dtype=object)
        # zero aggregated rows → SQL NULL (MySQL), not "[]"
        return vals, np.array([bool(p) for p in parts], dtype=bool)


class JsonObjectAgg(AggFunc):
    """JSON_OBJECTAGG over json_kv_pair tuples (func_json_objectagg.go);
    duplicate keys keep the LAST value (MySQL)."""

    device_capable = False

    def init(self, xp, n):
        return ([dict() for _ in range(n)],)

    def update(self, xp, state, gid, n, values, validity):
        (objs,) = state
        for g, v, ok in zip(np.asarray(gid), values,
                            np.asarray(validity)):
            g = int(g)
            if g >= n or not ok:
                continue
            k, val = v
            objs[g][k] = val
        return (objs,)

    def merge(self, xp, state, gid, n, partial):
        (objs,) = state
        (pobjs,) = partial
        for g, d in zip(np.asarray(gid), pobjs):
            if int(g) < n:
                objs[int(g)].update(d)
        return (objs,)

    def final(self, xp, state):
        (objs,) = state
        vals = np.array([_json_dump(o) for o in objs], dtype=object)
        return vals, np.array([bool(o) for o in objs], dtype=bool)


def _json_value(raw, ftype: FieldType):
    """Decoded SQL value → JSON-serializable value. JSON-typed inputs
    parse back to structures (nesting must not double-encode); DECIMALs
    stay exact (serialized as number literals by _json_dump)."""
    from decimal import Decimal
    from tidb_tpu.types import TypeKind
    if ftype.kind is TypeKind.JSON:
        import json
        try:
            return json.loads(str(raw))
        except ValueError:
            return str(raw)
    v = ftype.decode_value(raw)
    if v is None or isinstance(v, (int, float, str, bool, Decimal)):
        return v
    return str(v)


def _json_dump(v) -> str:
    """Exact JSON serializer: DECIMAL values emit as number literals
    with full precision (stdlib json would round-trip them through
    float); everything else matches json.dumps' MySQL-ish spacing."""
    import json
    from decimal import Decimal
    if isinstance(v, Decimal):
        return str(v)
    if isinstance(v, dict):
        return "{" + ", ".join(
            json.dumps(str(k)) + ": " + _json_dump(x)
            for k, x in v.items()) + "}"
    if isinstance(v, list):
        return "[" + ", ".join(_json_dump(x) for x in v) + "]"
    return json.dumps(v)
