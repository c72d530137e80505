"""Per-slab zone maps + host-side slab pruning.

At encode time (device_cache.col_prep) every cached column gets
per-slab statistics — min/max over valid values, null count, row
count, and a distinct-count estimate. Before a fragment dispatches,
`prune_slabs` evaluates the scan's conjunctive predicates
(comparisons, desugared BETWEEN, IN, IS [NOT] NULL) against those
statistics host-side and returns the set of slabs that CANNOT contain
a passing row. A pruned slab costs nothing: no H2D transfer on cold
first touch (device_cache.stream_slabs skips encode+upload), no
program launch warm, no escalation bookkeeping.

Statistics live in the space the device program compares in, so
pruning never decodes a slab:

  * numeric/temporal columns — the raw encoded integer space
    (scaled ints for DECIMAL, days-since-epoch for DATE), i.e. the
    value space UNDER the pack/dict/delta layout: a FoR base or a
    dictionary code never needs expanding to consult a zone map;
  * float columns — float64;
  * string columns — dictionary-code space; constants are located with
    the same searchsorted(left/right) the prepared device comparison
    uses, so the prune decision mirrors `_cmp_string_device` exactly.

Soundness contract: a conjunct prunes a slab only when the mirrored
device kernel would evaluate to false-or-NULL for EVERY row of the
slab (Kleene: both filter the row out). Comparisons and IN pass only
valid rows, so a slab whose column is entirely NULL is prunable by any
of them; IS NULL / IS NOT NULL prune on the null-count alone.
Anything the evaluator does not understand contributes no pruning —
the conservative direction is always "keep the slab".

The `zone-map-stale` failpoint trips at the prune decision: a
corrupted zone map surfaces as a typed LayoutError (1105) and the
statement falls back to the CPU scan — never silently wrong rows.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from tidb_tpu.errors import LayoutError
from tidb_tpu.types import TypeKind
from tidb_tpu.util import failpoint
from tidb_tpu.expression import ColumnRef, Constant, ScalarFunc
from tidb_tpu.util.observability import REGISTRY

failpoint.register(
    "zone-map-stale", "zone-map consult at the host-side slab-prune "
    "decision — a raise/value here models a stale or corrupted zone "
    "map, which must surface as a typed LayoutError + warned CPU "
    "fallback, never silently pruned rows (executor/zonemap.py "
    "prune_slabs)")

#: comparison ops the evaluator understands, and their negations
#: (NOT(cmp) over Kleene logic passes exactly the rows the negated op
#: passes — NULL operands filter out either way)
_NEG = {"eq": "ne", "ne": "eq", "lt": "ge", "ge": "lt",
        "le": "gt", "gt": "le"}
#: flipped const-OP-col reads as col FLIP(OP) const
_FLIP = {"eq": "eq", "ne": "ne", "lt": "gt", "gt": "lt",
         "le": "ge", "ge": "le"}


class ColumnZoneMap:
    """Per-slab statistics for ONE cached column. `lo`/`hi` are None
    for slabs with no valid value (NULL-only)."""

    __slots__ = ("kind", "lo", "hi", "nulls", "rows", "distinct")

    def __init__(self, kind: str, lo: List, hi: List, nulls: List[int],
                 rows: List[int], distinct: List[int]):
        self.kind = kind          # "num" | "float" | "code"
        self.lo = lo
        self.hi = hi
        self.nulls = nulls
        self.rows = rows
        self.distinct = distinct

    @property
    def n_slabs(self) -> int:
        return len(self.rows)


def column_stats(vals: np.ndarray, valid: np.ndarray, slab_cap: int,
                 total: int, kind: str = "num") -> ColumnZoneMap:
    """Build the per-slab zone map for one full host column. For
    string columns pass the dictionary CODES (int32) as `vals` —
    stats in code space are what the prepared device comparison
    consults."""
    n_slabs = max(1, -(-total // slab_cap))
    lo: List = []
    hi: List = []
    nulls: List[int] = []
    rows: List[int] = []
    distinct: List[int] = []
    as_float = kind == "float"
    for s in range(n_slabs):
        start = s * slab_cap
        stop = min(start + slab_cap, total)
        nr = stop - start
        v = vals[start:stop]
        m = valid[start:stop]
        nv = int(m.sum())
        rows.append(nr)
        nulls.append(nr - nv)
        if nv == 0:
            lo.append(None)
            hi.append(None)
            distinct.append(0)
            continue
        vv = v if nv == nr else v[m]
        slo, shi = vv.min(), vv.max()
        if as_float:
            lo.append(float(slo))
            hi.append(float(shi))
            distinct.append(nv)
        else:
            slo, shi = int(slo), int(shi)
            lo.append(slo)
            hi.append(shi)
            # range-capped estimate: exact for dense code/PK spaces,
            # an upper bound everywhere else — good enough for layout
            # and cardinality decisions, never used for pruning
            distinct.append(min(shi - slo + 1, nv))
    return ColumnZoneMap(kind, lo, hi, nulls, rows, distinct)


def prune_slabs(ent, scan) -> frozenset:
    """Slab ids of `ent` that the scan's pushed-down conjuncts prove
    empty. Empty set when the table is uncompressed (zone maps are an
    encode-time artifact), has no zone maps, or no filter is
    understood."""
    zmaps = getattr(ent, "zmaps", None)
    if not getattr(ent, "compressed", False) or not zmaps:
        return frozenset()
    filters = getattr(scan, "filters", None)
    if not filters:
        return frozenset()
    stale = failpoint.inject("zone-map-stale")
    if stale is not None:
        raise LayoutError(f"zone map failed validation: {stale}")
    # delta generations: evaluate over the BASE slabs only — the zone
    # maps were built at base-build time, so their stats are stale but
    # conservative for tombstone-compacted slabs (a removed row only
    # shrinks the true range, so the stale superset prunes strictly
    # less), and the appended-delta slab carries no stats at all, so it
    # is never pruned
    n_slabs = min(ent.n_slabs, getattr(ent, "base_slabs", ent.n_slabs))
    pruned = np.zeros(n_slabs, dtype=bool)
    for f in filters:
        mask = _prune_mask(f, ent, scan, n_slabs)
        if mask is not None:
            pruned |= mask
    return frozenset(int(s) for s in np.nonzero(pruned)[0])


def surviving(ent, scan, skipped) -> List[int]:
    """Physical slab ids NOT in `skipped`, in slab order."""
    return [s for s in range(ent.n_slabs) if s not in skipped]


# ---------------------------------------------------------------------------
# conjunct evaluation
# ---------------------------------------------------------------------------

def _prune_mask(expr, ent, scan, n_slabs) -> Optional[np.ndarray]:
    """Per-slab prune verdict for ONE conjunct, or None when the shape
    is not understood (contributes no pruning)."""
    if not isinstance(expr, ScalarFunc):
        return None
    op = expr.op
    args = expr.args
    if op == "and":
        # nested AND: either side pruning a slab prunes it
        out = np.zeros(n_slabs, dtype=bool)
        found = False
        for a in args:
            m = _prune_mask(a, ent, scan, n_slabs)
            if m is not None:
                out |= m
                found = True
        return out if found else None
    if op == "or":
        # a slab survives an OR if EITHER branch might pass
        masks = [_prune_mask(a, ent, scan, n_slabs) for a in args]
        if any(m is None for m in masks) or not masks:
            return None
        out = masks[0].copy()
        for m in masks[1:]:
            out &= m
        return out
    if op == "not":
        inner = args[0]
        if isinstance(inner, ScalarFunc) and inner.op == "isnull":
            return _isnull_mask(inner, ent, n_slabs, negate=True)
        if isinstance(inner, ScalarFunc) and inner.op in _NEG:
            neg = ScalarFunc(_NEG[inner.op], inner.args, expr.ftype)
            return _prune_mask(neg, ent, scan, n_slabs)
        return None
    if op == "isnull":
        return _isnull_mask(expr, ent, n_slabs, negate=False)
    if op == "in":
        return _in_mask(expr, ent, scan, n_slabs)
    if op in _NEG:
        return _cmp_mask(expr, ent, scan, n_slabs)
    return None


def _column_side(args):
    """(col_ref, const, flipped) for a 2-arg comparison, or None."""
    if len(args) != 2:
        return None
    a, b = args
    if isinstance(a, ColumnRef) and isinstance(b, Constant):
        return a, b, False
    if isinstance(a, Constant) and isinstance(b, ColumnRef):
        return b, a, True
    return None


def _isnull_mask(expr, ent, n_slabs, negate=False):
    arg = expr.args[0]
    if not isinstance(arg, ColumnRef):
        return None
    zm = ent.zmaps.get(arg.index)
    if zm is None or zm.n_slabs != n_slabs:
        return None
    if negate:
        # IS NOT NULL: a slab that is entirely NULL cannot pass
        return np.array([zm.nulls[s] >= zm.rows[s]
                         for s in range(n_slabs)], dtype=bool)
    # IS NULL: a slab with no NULLs cannot pass
    return np.array([zm.nulls[s] == 0 for s in range(n_slabs)],
                    dtype=bool)


def _cmp_mask(expr, ent, scan, n_slabs) -> Optional[np.ndarray]:
    side = _column_side(expr.args)
    if side is None:
        return None
    col, const, flipped = side
    op = _FLIP[expr.op] if flipped else expr.op
    zm = ent.zmaps.get(col.index)
    if zm is None or zm.n_slabs != n_slabs:
        return None
    if const.value is None:
        # NULL literal: the comparison is NULL for every row
        return np.ones(n_slabs, dtype=bool)
    if zm.kind == "code":
        return _cmp_codes(op, zm, col, const, ent, n_slabs)
    enc = _encode_const(col, const, zm)
    if enc is None:
        return None
    lo_f, hi_f, c = enc
    out = np.zeros(n_slabs, dtype=bool)
    for s in range(n_slabs):
        lo, hi = lo_f(s), hi_f(s)
        if lo is None:
            # NULL-only slab: any comparison filters every row
            out[s] = True
            continue
        out[s] = _range_excludes(op, lo, hi, c)
    return out


def _range_excludes(op, lo, hi, c) -> bool:
    """True iff no value in [lo, hi] can satisfy `value OP c`."""
    if op == "eq":
        return c < lo or c > hi
    if op == "ne":
        return lo == hi == c
    if op == "lt":
        return lo >= c
    if op == "le":
        return lo > c
    if op == "gt":
        return hi <= c
    if op == "ge":
        return hi < c
    return False


def _encode_const(col, const, zm):
    """Mirror expression._numeric_common's promotion: returns per-slab
    (lo(s), hi(s)) accessors in the common comparison space plus the
    encoded constant, or None when the pair is not comparable here."""
    cft, kft = col.ftype, const.ftype
    if cft.kind.is_string or kft.kind.is_string:
        return None
    if cft.is_wide_decimal or kft.is_wide_decimal:
        return None
    try:
        raw = kft.encode_value(const.value)
    except Exception:
        return None
    if raw is None:
        return None
    col_scale = cft.scale if cft.kind is TypeKind.DECIMAL else 0
    k_scale = kft.scale if kft.kind is TypeKind.DECIMAL else 0
    if cft.kind.is_float or kft.kind.is_float or zm.kind == "float":
        # float space: decimals divide out their scale
        def lo_f(s, _z=zm, _m=10.0 ** col_scale):
            return None if _z.lo[s] is None else float(_z.lo[s]) / _m

        def hi_f(s, _z=zm, _m=10.0 ** col_scale):
            return None if _z.hi[s] is None else float(_z.hi[s]) / _m
        c = float(raw) / (10.0 ** k_scale) if not kft.kind.is_float \
            else float(raw)
        return lo_f, hi_f, c
    if cft.kind is TypeKind.DECIMAL or kft.kind is TypeKind.DECIMAL:
        ts = max(col_scale, k_scale)
        cm = 10 ** (ts - col_scale)
        km = 10 ** (ts - k_scale)

        def lo_f(s, _z=zm, _m=cm):
            return None if _z.lo[s] is None else _z.lo[s] * _m

        def hi_f(s, _z=zm, _m=cm):
            return None if _z.hi[s] is None else _z.hi[s] * _m
        return lo_f, hi_f, int(raw) * km
    # raw integer space (ints, dates, datetimes — exactly what the
    # device kernel compares)
    return (lambda s, _z=zm: _z.lo[s]), (lambda s, _z=zm: _z.hi[s]), \
        int(raw)


def _string_locate(col, const, ent):
    """(left, right, present) — the constant's dictionary-code window,
    exactly as _prepare_string_cmp computes it. None when the column
    has no dictionary or the collation folds (conservative)."""
    if col.ftype.is_ci or const.ftype.is_ci:
        return None
    d = ent.dicts.get(col.index) if ent.dicts else None
    if d is None:
        return None
    s = const.value
    if not isinstance(s, str):
        s = str(s)
    left = int(np.searchsorted(d, s, side="left"))
    right = int(np.searchsorted(d, s, side="right"))
    return left, right, left < right


def _cmp_codes(op, zm, col, const, ent, n_slabs):
    """String comparison over dictionary-code zone maps, mirroring
    _cmp_string_device's code semantics."""
    loc = _string_locate(col, const, ent)
    if loc is None:
        return None
    left, right, present = loc
    out = np.zeros(n_slabs, dtype=bool)
    for s in range(n_slabs):
        lo, hi = zm.lo[s], zm.hi[s]
        if lo is None:
            out[s] = True
            continue
        if op == "eq":
            # passes iff code == left and present
            out[s] = (not present) or left < lo or left > hi
        elif op == "ne":
            # passes unless code == left (and present)
            out[s] = present and lo == hi == left
        elif op == "lt":
            # passes iff code < left
            out[s] = lo >= left
        elif op == "le":
            # passes iff code < right
            out[s] = lo >= right
        elif op == "gt":
            # passes iff code >= right
            out[s] = hi < right
        elif op == "ge":
            # passes iff code >= left
            out[s] = hi < left
    return out


def _in_mask(expr, ent, scan, n_slabs):
    """col IN (c1, c2, ...): a slab survives iff SOME item can fall in
    its [lo, hi] window (string items: iff present in the dictionary
    inside the window)."""
    if not expr.args or not isinstance(expr.args[0], ColumnRef):
        return None
    col = expr.args[0]
    items = expr.args[1:]
    if not items or not all(isinstance(i, Constant) for i in items):
        return None
    zm = ent.zmaps.get(col.index)
    if zm is None or zm.n_slabs != n_slabs:
        return None
    # NULL items never match anything; drop them (an all-NULL list
    # matches no row at all → prune everything)
    items = [i for i in items if i.value is not None]
    if zm.kind == "code":
        locs = []
        for it in items:
            loc = _string_locate(col, it, ent)
            if loc is None:
                return None
            locs.append(loc)
        out = np.zeros(n_slabs, dtype=bool)
        for s in range(n_slabs):
            lo, hi = zm.lo[s], zm.hi[s]
            if lo is None:
                out[s] = True
                continue
            out[s] = not any(present and lo <= left <= hi
                             for left, _right, present in locs)
        return out
    codes = []
    for it in items:
        enc = _encode_const(col, it, zm)
        if enc is None:
            return None
        codes.append(enc)
    out = np.zeros(n_slabs, dtype=bool)
    for s in range(n_slabs):
        hit = False
        empty = True
        for lo_f, hi_f, c in codes:
            lo, hi = lo_f(s), hi_f(s)
            if lo is None:
                continue
            empty = False
            if lo <= c <= hi:
                hit = True
                break
        out[s] = empty or not hit
    if not codes:
        # empty (or all-NULL) IN list matches nothing
        out[:] = True
    return out


# ---------------------------------------------------------------------------
# attribution helpers
# ---------------------------------------------------------------------------

def note_skipped(phases, n: int) -> None:
    """Attribute `n` pruned dispatch units (slabs, or staged-dist
    ranks) to the running statement and the process registry."""
    if n <= 0:
        return
    if phases is not None:
        phases.note_slabs_skipped(n)
    dev = getattr(phases, "device_index", 0) if phases is not None else 0
    REGISTRY.inc("tidb_tpu_slabs_skipped_total",
                 {"engine": "device", "device": str(dev or 0)},
                 by=n)


def note_h2d_skipped(phases, nbytes: int, table: str = "") -> None:
    """Attribute upload bytes a pruned slab never moved (cold first
    touch / staged-dist rank slices)."""
    if nbytes <= 0:
        return
    if phases is not None:
        phases.note_h2d_skipped(nbytes)
    REGISTRY.observe("tidb_tpu_h2d_skipped_bytes", nbytes,
                     {"table": table})


__all__ = ["ColumnZoneMap", "column_stats", "prune_slabs", "surviving",
           "note_skipped", "note_h2d_skipped"]
