"""TPU fragment extraction & execution (SURVEY §7 stages 3-5).

A fragment is a maximal device-capable chain `scan → selection* →
projection* → [hash-agg | topN | sort]` fused into ONE jitted XLA program —
the analog of the coprocessor DAG the reference pushes to storage
(SURVEY A.2: unistore's closure executor fuses scan→selection→agg into a
single callback, closure_exec.go; plan_to_pb.go ships subtrees to TiFlash).
Fusion at fragment granularity is the whole game on TPU: one host→HBM
transfer, one compiled program, no per-operator launch/transfer overhead
(SURVEY §7 "host↔device bandwidth").

Execution model:
  * the scan side is materialized host-side (regions are already columnar),
    string columns are dictionary-encoded ONCE (unified, sorted dictionary →
    codes are rank order, so ORDER BY / range predicates work on codes);
  * rows are padded into fixed power-of-two slabs so XLA sees a small set of
    static shapes; the logical row count rides along and becomes a `live`
    mask (the reference's sel vector / requiredRows, SURVEY §7 hard parts);
  * grouped aggregation is sort-based factorize + segment ops
    (ops/factorize.py) with a static group capacity; capacity overflow is
    detected via the returned n_groups and retried with a doubled cap;
  * filters never compact on device — they just narrow the live mask that
    every downstream kernel consumes (masking beats data movement);
  * any device failure (untraceable builtin, unsupported shape) falls back
    to building the embedded CPU subtree — the reference's allowlist
    philosophy (expression.go scalarExprSupportedByTiFlash) enforced by
    trying, not by cataloguing.

Compiled programs are cached process-wide keyed by plan structure + dtypes +
slab/group capacities, so repeated queries skip retracing (the plan-cache
analog for the device engine).
"""

from __future__ import annotations

import functools
import logging
import math
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

log = logging.getLogger("tidb_tpu.fragment")

from tidb_tpu.chunk import Chunk, Column
from tidb_tpu.errors import (CapacityError, DeviceLost, ExecutionError,
                             MemoryQuotaExceeded, QueryKilledError,
                             QueryTimeout, ShardFailure)
from tidb_tpu.expression import EvalContext, Expression, ColumnRef
from tidb_tpu.expression.aggfuncs import AggFunc, build_agg
from tidb_tpu.ops.factorize import (FACTORIZE, RUNS, SLOTS, KeyBounds,
                                    bounds_sig, choose_key_bounds,
                                    grouping_mode, widths_sig)
from tidb_tpu.planner.physical import (PhysHashAgg, PhysHashJoin,
                                       PhysLimit, PhysProjection,
                                       PhysSelection, PhysSort,
                                       PhysTableScan, PhysTopN,
                                       PhysTpuFragment, PhysWindow,
                                       PhysicalPlan)
from tidb_tpu.types import FieldType
from tidb_tpu.util import timeline
from tidb_tpu.util.phases import tree_nbytes

DEFAULT_MAX_SLAB_ROWS = 1 << 23   # 8M rows per device slab
DEFAULT_GROUP_CAP = 1 << 16
# group caps at or below this ride the flag fetch (padded keys/states are
# a few MB) — the result then needs NO second device round trip
SMALL_GROUP_CAP = 1 << 14


def _piggyback_agg(fetch: dict, out, group_cap: int) -> bool:
    if group_cap <= SMALL_GROUP_CAP:
        fetch["keys"] = out["keys"]
        fetch["states"] = out["states"]
        return True
    return False


# The closed fallback-reason taxonomy: every way a fragment can decline
# the device path maps to ONE of these stable codes. The code is what
# EXPLAIN ANALYZE prints as `device:fallback(code)` and the `reason`
# label on tidb_tpu_device_fallbacks_total — free-text detail rides
# along for logs but never reaches a metric label (bounded cardinality).
FALLBACK_REASONS = (
    "shape",          # plan not a device-eligible chain/tree
    "empty-input",    # zero-row scan: nothing to dispatch
    "group-cap",      # factorize cap overflow past the ladder ceiling
    "pair-cap",       # DISTINCT pair-set cap overflow past the ceiling
    "join-cap",       # join fan-out exceeds the device expansion cap
    "blocked-expand", # blocked multi-pass join can't serve this shape
    "mesh-size",      # dist plan wants more devices than are visible
    "string-dict",    # varlen column with no dictionary encoding
    "device-error",   # unexpected device/runtime failure
)


class FragmentFallback(Exception):
    """Raised when the device path cannot run this fragment.

    `reason` must be one of FALLBACK_REASONS (defaults to "shape"); the
    exception message keeps the free-text detail."""

    def __init__(self, detail: str = "", reason: str = "shape"):
        super().__init__(detail)
        self.reason = reason if reason in FALLBACK_REASONS else "shape"


def _var_bool(v) -> bool:
    """MySQL-ish boolean sysvar coercion: 'off'/'false'/'0'/0/'' are False."""
    if isinstance(v, str):
        return v.strip().lower() not in ("", "0", "off", "false")
    return bool(v)


# ---------------------------------------------------------------------------
# Planner side: chain detection (the engine allowlist gate)
# ---------------------------------------------------------------------------


def _order_over_agg_ok(order: PhysicalPlan, agg: PhysicalPlan) -> bool:
    """Can this ORDER BY / TopN root fuse into the device finalize of the
    HashAgg beneath it (device_emit.emit_finalize)?  Every sort key must
    be a bare ColumnRef into the agg's output row; keys referencing
    aggregate outputs additionally require order_keys() that trace (the
    count/sum/avg/min/max allowlist; of the wide decimals, whose finals
    run host-side via numpy limb math, only a SUM over a narrow argument
    orders by its limb planes) and a non-DISTINCT aggregate (device-merged
    DISTINCT states dedup per-slab only; the exact cross-slab counts
    exist solely in the host pair merge, AFTER ordering would run)."""
    if not isinstance(agg, PhysHashAgg):
        return False
    if isinstance(order, PhysTopN) and \
            getattr(order, "count", None) is None:
        return False
    nk = len(agg.group_exprs)
    for e in order.by:
        if not isinstance(e, ColumnRef):
            return False
        if e.index < nk:
            continue
        if e.index >= nk + len(agg.aggs):
            return False
        d = agg.aggs[e.index - nk]
        if d.distinct:
            return False
        if d.name not in ("count", "sum", "avg", "min", "max"):
            return False
        if d.ftype.kind.is_string:
            return False
        if d.ftype.is_wide_decimal and not (
                d.name == "sum" and build_agg(d).orders_in_trace):
            return False
    return True


def _identity_projection(p: PhysicalPlan) -> bool:
    """A planner-inserted pass-through (col#i → i, in order, dropping
    nothing): transparent between an ORDER BY / TopN root and the agg it
    orders, because its output row IS the agg's output row."""
    return (isinstance(p, PhysProjection) and p.children and
            len(p.exprs) == len(p.children[0].schema.field_types) and
            all(isinstance(e, ColumnRef) and e.index == i
                for i, e in enumerate(p.exprs)))


def _strip_order_root(root: PhysicalPlan):
    """(order_root, agg) when `root` is an ORDER BY / TopN over the agg
    (identity projections between them are transparent), else (None,
    root)."""
    if isinstance(root, (PhysTopN, PhysSort)) and root.children:
        below = root.children[0]
        while _identity_projection(below) and below.children:
            below = below.children[0]
        if isinstance(below, PhysHashAgg):
            return root, below
    return None, root


def _linearize(root: PhysicalPlan) -> Optional[List[PhysicalPlan]]:
    """root→leaf chain [root, ..., scan], or None if the shape is wrong.
    An ORDER BY / TopN root directly over a fusable HashAgg linearizes as
    [order, agg, ..., scan] — the driver strips the order root and runs
    it as the agg's fused finalize (or a host re-order)."""
    nodes: List[PhysicalPlan] = []
    cur = root
    while True:
        nodes.append(cur)
        if isinstance(cur, PhysTableScan):
            return nodes
        mid_ok = isinstance(cur, (PhysSelection, PhysProjection))
        root_ok = cur is root and isinstance(cur, (PhysHashAgg, PhysTopN,
                                                   PhysSort, PhysWindow))
        order_agg = (isinstance(cur, PhysHashAgg)
                     and isinstance(root, (PhysTopN, PhysSort))
                     and all(_identity_projection(n) for n in nodes[1:-1])
                     and _order_over_agg_ok(root, cur))
        if not (mid_ok or root_ok or order_agg) or len(cur.children) != 1:
            return None
        cur = cur.children[0]


def _string_exprs_are_refs(exprs: Sequence[Expression]) -> bool:
    return all(isinstance(e, ColumnRef) or not e.ftype.kind.is_string
               for e in exprs)


def _exprs_device_ok(exprs: Sequence[Expression],
                     wide_refs_ok: bool = False) -> bool:
    """Reject host-only builtins at plan time (quiet CPU routing instead
    of a traced failure + warning per query). Wide decimals (limb-plane
    representation) are rejected here too: only the SUM/AVG/COUNT agg
    arguments handled by _fragment_ok's special case consume limbs."""
    from tidb_tpu.expression import HOST_ONLY_OPS, Constant, ScalarFunc
    for e in exprs:
        for sub in e.walk():
            if isinstance(sub, ScalarFunc) and sub.op in HOST_ONLY_OPS:
                return False
            if isinstance(sub, ScalarFunc) and sub.op in ("like",
                                                          "regexp_like"):
                # the device lowering is a prepared per-dictionary LUT:
                # only column-vs-constant shapes can prepare
                if not (isinstance(sub.args[0], ColumnRef) and
                        isinstance(sub.args[1], Constant) and
                        sub.args[1].value is not None):
                    return False
            if isinstance(sub, ScalarFunc) and sub.op == "in" and \
                    sub.args[0].ftype.kind.is_string and \
                    not isinstance(sub.args[0], ColumnRef):
                # string IN-lists prepare a per-dictionary codeset; a
                # COMPUTED string (SUBSTRING(...) IN (...)) has no
                # dictionary to prepare against
                return False
            # wide-decimal COLUMNS arrive as 2-D limb planes no generic
            # kernel understands; computed wide-typed expressions are
            # ordinary 1-D scaled int64 and pass
            # (a nested fragment's rows are 1-D too: `wide_refs_ok`)
            if isinstance(sub, ColumnRef) and sub.ftype.is_wide_decimal \
                    and not wide_refs_ok:
                return False
    return True


def _fragment_ok(plan: PhysicalPlan, threshold: int) -> bool:
    chain = _linearize(plan)
    if chain is None:
        return False
    scan = chain[-1]
    if getattr(scan, "est_rows", 0.0) < threshold:
        # route small inputs to CPU: launch+transfer dominates (SURVEY §7
        # cost-model honesty; the reference's TiFlash row-threshold gate)
        return False
    reduction = isinstance(plan, (PhysHashAgg, PhysTopN, PhysSort))
    worthwhile = reduction or bool(scan.filters)
    order_agg = _strip_order_root(plan)[0] is not None
    for node in chain:
        stage = _stage_exprs(node)
        if isinstance(node, PhysHashAgg):
            stage = list(node.group_exprs)   # agg args validated below
        elif node is plan and order_agg:
            stage = []      # refs into the agg's row: _order_over_agg_ok's
        if not _exprs_device_ok(stage):
            return False
        if isinstance(node, PhysHashAgg):
            if getattr(node, "rollup", False) and \
                    any(d.distinct for d in node.aggs):
                return False    # pair columns assume nk key cols; the
                # rollup level column breaks that layout → host oracle
            for desc in node.aggs:
                if desc.distinct and len(desc.args) > 1 and \
                        desc.name != "count":
                    return False    # multi-arg DISTINCT is COUNT-only
                try:
                    if not build_agg(desc).device_capable:
                        return False
                except Exception:
                    return False
                if any(a.ftype.kind.is_string for a in desc.args) \
                        and desc.name != "count":
                    return False
                if not _string_exprs_are_refs(desc.args):
                    return False    # string agg args read dict codes
                if any(isinstance(sub, ColumnRef) and
                       sub.ftype.is_wide_decimal
                       for a in desc.args for sub in a.walk()):
                    # a wide-decimal COLUMN (2-D limb planes) in the args:
                    # only plain SUM/AVG/COUNT over the bare column
                    # consumes limbs (SumAgg._update_wide); anything else
                    # → CPU. Wide RESULT types over narrow/computed args
                    # need no gate — the device splits its 1-D int64
                    # input into limbs itself.
                    if desc.name not in ("sum", "avg", "count") or \
                            desc.distinct or \
                            not isinstance(desc.args[0], ColumnRef):
                        return False
                elif not _exprs_device_ok(desc.args):
                    return False
            if not _string_exprs_are_refs(node.group_exprs):
                return False
        elif isinstance(node, (PhysTopN, PhysSort)):
            if not _string_exprs_are_refs(node.by):
                return False
        elif isinstance(node, PhysWindow):
            if not _window_device_ok(node):
                return False
            worthwhile = True
        elif isinstance(node, PhysSelection):
            worthwhile = True
        elif isinstance(node, PhysProjection):
            if not _string_exprs_are_refs(node.exprs):
                return False
            if any(not isinstance(e, ColumnRef) for e in node.exprs):
                worthwhile = True
    return worthwhile


_DEVICE_WINDOW_FUNCS = ("row_number", "rank", "dense_rank", "sum",
                        "count", "avg", "min", "max", "lag", "lead",
                        "first_value", "last_value", "percent_rank",
                        "cume_dist", "ntile", "nth_value")


def _window_device_ok(node: PhysWindow) -> bool:
    for d in node.wdescs:
        if d.name not in _DEVICE_WINDOW_FUNCS:
            return False
        if d.args and d.args[0].ftype.kind.is_string:
            return False            # string lag/lead needs dict passthrough
        if d.args and d.args[0].ftype.is_wide_decimal:
            return False            # limb planes: window kernels are 1-D
        fr = getattr(d, "frame", None)
        if fr is not None and fr[0] == "range" and (
                not d.order or d.order[0].ftype.kind.is_string):
            return False            # RANGE bounds need a numeric key
        if not _string_exprs_are_refs(list(d.partition) + list(d.order)):
            return False
    return True


def extract_fragments(plan: PhysicalPlan, threshold: int) -> PhysicalPlan:
    """Top-down maximal-chain extraction: try the largest fuse at each node
    first so HashAgg(Sel(Scan)) becomes one fragment, not a CPU agg over a
    fragment filter. Join trees (the Q3/Q5 shape) fuse through
    tree_fragment when statically eligible."""
    if _fragment_ok(plan, threshold):
        frag = PhysTpuFragment(plan)
        frag.est_rows = plan.est_rows
        return frag
    from tidb_tpu.executor.tree_fragment import (nest_build_aggregates,
                                                 tree_ok)
    if tree_ok(plan, threshold):
        nest_build_aggregates(plan, threshold)
        frag = PhysTpuFragment(plan)
        frag.est_rows = plan.est_rows
        return frag
    plan.children = [extract_fragments(c, threshold) for c in plan.children]
    return plan


def check_strict_plan(plan: PhysicalPlan, threshold: int) -> None:
    """`tidb_tpu_strict = on`, the part no fragment can speak for: a plan
    that leaves a device-sized base-table scan (est_rows ≥ the row
    threshold) under a HOST join, aggregate, sort or window has fallen
    back from the device as surely as a fragment that raised, and raises
    the same typed error (counted as a `shape` fallback). A scan that
    only returns its rows (under selections, projections, limits) and an
    index read do not: there is no device work in them to lose."""
    from tidb_tpu.planner.physical import (PhysIndexLookupJoin,
                                           PhysMergeJoin, PhysStreamAgg)
    heavy = (PhysHashJoin, PhysIndexLookupJoin, PhysMergeJoin, PhysHashAgg,
             PhysStreamAgg, PhysSort, PhysTopN, PhysWindow)

    def walk(node, under):
        if isinstance(node, PhysTpuFragment):
            return
        if isinstance(node, PhysTableScan) and under is not None and \
                getattr(node, "est_rows", 0.0) >= threshold:
            from tidb_tpu.util.observability import REGISTRY
            REGISTRY.inc("tidb_tpu_device_fallbacks_total",
                         {"reason": "shape"})
            raise ExecutionError(
                f"tidb_tpu_strict: {under.name} runs on the host over a "
                f"scan of {node.table.name} (~{node.est_rows:.0f} rows, "
                f"device threshold {threshold})")
        if isinstance(node, heavy):
            under = node
        for c in node.children:
            walk(c, under)

    walk(plan, None)


# ---------------------------------------------------------------------------
# Execution
# ---------------------------------------------------------------------------


from collections import OrderedDict

# LRU of compiled programs: bounded because signatures can embed
# data-dependent key_bounds (moving min/max under writes would otherwise
# accumulate executables forever)
_COMPILE_CACHE: "OrderedDict[str, object]" = OrderedDict()
MAX_COMPILED_PROGRAMS = 64

# guards _COMPILE_CACHE / PROGRAM_TRACES / _BUILD_LOCKS — connection
# threads share one program cache
_CC_LOCK = timeline.named_lock("compile_cache", reentrant=True)
# per-signature build locks: two threads cold-compiling the SAME
# signature serialize (one trace, the loser adopts it); different
# signatures still compile concurrently
_BUILD_LOCKS: Dict[str, threading.Lock] = {}

# Incremented inside the traced _partial/_merge bodies, so it moves once
# per TRACE, not once per call — the zero-retrace assertion the perf_smoke
# tier watches (a repeated identical query must leave it unchanged).
PROGRAM_TRACES = 0

def _count_trace() -> None:
    global PROGRAM_TRACES
    with _CC_LOCK:
        PROGRAM_TRACES += 1


def _sig_dev(sig: str) -> str:
    """Scope a compile-cache signature to the statement's pool device:
    XLA executables bind to the device they were lowered for, so each
    pool member keeps its own compiled copy. Device 0 (and every
    placement-free context) keeps the bare signature — single-device
    hosts stay byte-identical to the pre-pod cache."""
    from tidb_tpu.util import phases as _ph
    cur = _ph.current()
    d = getattr(cur, "device_index", 0) if cur is not None else 0
    return f"dev{d}|{sig}" if d else sig


def _build_lock(sig: str) -> threading.Lock:
    sig = _sig_dev(sig)
    with _CC_LOCK:
        lk = _BUILD_LOCKS.get(sig)
        if lk is None:
            lk = _BUILD_LOCKS[sig] = threading.Lock()
            while len(_BUILD_LOCKS) > 4 * MAX_COMPILED_PROGRAMS:
                _BUILD_LOCKS.pop(next(iter(_BUILD_LOCKS)))
        return lk


# signature → the request (timeline `req`) building that program right now:
# a request that waits for the build records it as the wait's `cause`
_BUILDING: Dict[str, int] = {}


def _get_or_build(sig: str, kind: str, build):
    """The single-flight compile cache: the cached program of `sig`, or
    `build()`'s, built once however many statements ask at once (one trace
    per signature; the losers wait and adopt it). A cold build is charged
    to the running statement and to the `compile:<kind>` timeline lane."""
    prog = _cache_get(sig)
    if prog is not None:
        return prog
    lock = _build_lock(sig)
    if not lock.acquire(blocking=False):
        with timeline.span("compile.wait", "compile",
                           cause=_BUILDING.get(sig, 0), wait="build"):
            lock.acquire()
    try:
        prog = _cache_get(sig)      # double-checked: one trace per sig
        if prog is None:
            from tidb_tpu.util import phases as _phases
            cur = _phases.current()
            _BUILDING[sig] = cur.req if cur is not None else 0
            t0 = time.perf_counter()
            try:
                prog = build()
                _cache_put(sig, prog)
            finally:
                _BUILDING.pop(sig, None)
            _charge_compile(kind, t0)
    finally:
        lock.release()
    return prog


class DeviceAggRows:
    """What a nested device-rows fragment hands its enclosing fragment:
    the aggregate's output rows, still in HBM — `cols` [(values, valid)]
    per output column and `live`, every array `cap` slots long; `bounds`
    {column: (lo, hi)} for the group keys whose value bounds are known (a
    join over them can then probe a table instead of sorting)."""

    def __init__(self, cols, live, cap: int, bounds: dict):
        self.cols = cols
        self.live = live
        self.cap = cap
        self.bounds = bounds

    def inputs(self):
        return (self.cols, self.live)


class _AggRowsProgram:
    """Merged aggregate state → the aggregate's output rows, on the
    device: group keys and each aggregate's final as one 1-D column
    (AggFunc.final_narrow), live where a group is. One small launch after
    a nested fragment's merge; `fits` says whether every final could be
    held so."""

    def __init__(self, agg_root, sig: str):
        from tidb_tpu.ops.jax_env import named_jit, program_name
        self.agg_root = agg_root
        self.aggs = [build_agg(d) for d in agg_root.aggs]
        self.name = program_name("rows", sig)
        self.run = named_jit(self._run, self.name)

    def _run(self, keys, states, n_groups):
        from tidb_tpu.executor import device_emit
        from tidb_tpu.ops.jax_env import jnp
        _count_trace()
        with device_emit.stage("finalize"):
            live = jnp.arange(keys[0][0].shape[0],
                              dtype=jnp.int32) < n_groups
            cols = [(jnp.asarray(v), jnp.asarray(m) & live)
                    for v, m in keys[:len(self.agg_root.group_exprs)]]
            fits = jnp.bool_(True)
            for agg, st in zip(self.aggs, states):
                v, m, ok = agg.final_narrow(jnp, tuple(st))
                cols.append((v, m & live))
                fits = fits & ok
        return cols, live, fits


def _agg_rows(ctx, agg_root, out, cap: int, base_sig: str,
              key_bounds) -> DeviceAggRows:
    """Launch the rows program over a merged aggregate `out`."""
    from tidb_tpu.ops.jax_env import jax
    sig = "aggrows|" + base_sig
    prog = _get_or_build(sig, "fused",
                         lambda: _AggRowsProgram(agg_root, sig))
    ph = ctx.phases
    with ctx.device_slot():
        with ph.launch(prog.name):
            cols, live, fits = prog.run(list(out["keys"]),
                                        [tuple(st) for st in out["states"]],
                                        out["n_groups"])
    ph.note_launch()
    with ph.phase("fetch"):
        fits = bool(jax.device_get(fits))
    ph.add_d2h(1)
    if not fits:
        raise FragmentFallback("an aggregate's value exceeds 64 bits",
                               reason="shape")
    return DeviceAggRows(cols, live, cap, dict(enumerate(
        key_bounds.bounds if key_bounds is not None else ())))


def _tree_delete(tree) -> None:
    """Explicitly free every device array in a pytree of stale outputs
    (superseded slab partials / merge results on a ladder retry): without
    this, the retry's bigger-cap generation coexists with the old one
    until GC, doubling peak HBM exactly when capacity is tight."""
    from tidb_tpu.ops.jax_env import jax
    for leaf in jax.tree_util.tree_leaves(tree):
        delete = getattr(leaf, "delete", None)
        if delete is None:
            continue
        try:
            delete()
        except Exception:  # noqa: BLE001 — already donated/deleted
            pass


def _cache_get(sig: str):
    sig = _sig_dev(sig)
    with _CC_LOCK:
        prog = _COMPILE_CACHE.get(sig)
        if prog is not None:
            _COMPILE_CACHE.move_to_end(sig)
        return prog


def _cache_put(sig: str, prog) -> None:
    sig = _sig_dev(sig)
    with _CC_LOCK:
        _COMPILE_CACHE[sig] = prog
        while len(_COMPILE_CACHE) > MAX_COMPILED_PROGRAMS:
            _COMPILE_CACHE.popitem(last=False)


def _chain_signature(chain: List[PhysicalPlan], used_cols: Sequence[int],
                     in_types: Sequence[FieldType], slab_cap: int,
                     group_cap: int, key_bounds=None,
                     layouts=None) -> str:
    parts = [f"slab={slab_cap}", f"gcap={group_cap}",
             f"kb={bounds_sig(key_bounds)}",
             "cols=" + ",".join(f"{i}:{ft}" for i, ft in
                                zip(used_cols, in_types)),
             # compressed physical layouts change the traced decode and
             # the input pytree, so they key the compile cache
             "lay=" + (",".join(f"{i}:{l.sig()}"
                                for i, l in sorted(layouts.items()))
                       if layouts else "-")]
    for node in chain:
        if isinstance(node, PhysTableScan):
            parts.append(f"Scan(filters={node.filters!r}, "
                         f"parts={getattr(node, 'partitions', None)})")
        elif isinstance(node, PhysSelection):
            parts.append(f"Sel({node.conditions!r})")
        elif isinstance(node, PhysProjection):
            parts.append(f"Proj({node.exprs!r})")
        elif isinstance(node, PhysHashAgg):
            parts.append(
                f"Agg(g={node.group_exprs!r}, "
                f"a={[(d.name, repr(d.args), str(d.ftype), d.distinct) for d in node.aggs]}, "
                f"r={getattr(node, 'rollup', False)})")
        elif isinstance(node, (PhysTopN, PhysSort)):
            k = getattr(node, "count", None)
            off = getattr(node, "offset", 0)
            parts.append(f"{type(node).__name__}(by={node.by!r}, "
                         f"descs={node.descs}, k={k}, off={off})")
        elif isinstance(node, PhysWindow):
            parts.append(f"Window({node.wdescs!r})")
    return "|".join(parts)


def _used_column_indices(chain: List[PhysicalPlan]) -> List[int]:
    """Scan-schema column indices referenced anywhere in the chain.

    Only expressions evaluated against the SCAN schema matter: once a
    Projection rebinds the column space, later refs point at projection
    outputs. We walk leaf-up and stop collecting at the first Projection.
    """
    used = set()
    for node in reversed(chain):
        if isinstance(node, PhysTableScan):
            for f in node.filters:
                used.update(f.references())
            if node is chain[0]:
                # a bare filtered-scan fragment emits EVERY column
                # (regression: a Scan-root chain uploaded only the filter
                # columns, then _partial's ctx.column(i) walked the full
                # schema → IndexError)
                used.update(range(len(node.schema)))
        elif isinstance(node, PhysSelection):
            for c in node.conditions:
                used.update(c.references())
            if node is chain[0]:
                # Selection-rooted fragment emits every child column
                used.update(range(len(node.schema)))
        elif isinstance(node, PhysProjection):
            for e in node.exprs:
                used.update(e.references())
            return sorted(used)
        elif isinstance(node, PhysHashAgg):
            for e in node.group_exprs:
                used.update(e.references())
            for d in node.aggs:
                for a in d.args:
                    used.update(a.references())
        elif isinstance(node, (PhysTopN, PhysSort)):
            for e in node.by:
                used.update(e.references())
            # sort/topn emit every child column
            n_cols = len(node.schema)
            used.update(range(n_cols))
        elif isinstance(node, PhysWindow):
            n_child = len(node.children[0].schema)
            used.update(range(n_child))   # window emits every child column
            for d in node.wdescs:
                for e in list(d.args) + list(d.partition) + list(d.order):
                    used.update(e.references())
    return sorted(used)


def _stage_exprs(node: PhysicalPlan) -> List[Expression]:
    """Expressions this node evaluates against its input columns."""
    if isinstance(node, PhysTableScan):
        return list(node.filters)
    if isinstance(node, PhysSelection):
        return list(node.conditions)
    if isinstance(node, PhysProjection):
        return list(node.exprs)
    if isinstance(node, PhysHashAgg):
        out = list(node.group_exprs)
        for d in node.aggs:
            out.extend(d.args)
        return out
    if isinstance(node, (PhysTopN, PhysSort)):
        return list(node.by)
    if isinstance(node, PhysWindow):
        out: List[Expression] = []
        for d in node.wdescs:
            out.extend(d.args)
            out.extend(d.partition)
            out.extend(d.order)
        return out
    return []


class _FragmentProgram:
    """Traceable fragment: closures over the (first) plan's expression
    objects; later structurally-identical plans reuse the compiled XLA
    executable and only re-supply prepared host inputs positionally."""

    def __init__(self, chain: List[PhysicalPlan], used_cols: List[int],
                 in_types: List[FieldType], slab_cap: int, group_cap: int,
                 key_bounds=None, want_pairs: bool = False, layouts=None,
                 pair_cap: int = 0, sig: str = ""):
        from tidb_tpu.ops.jax_env import jax
        self.chain = chain
        self.used_cols = used_cols
        self.in_types = in_types
        self.slab_cap = slab_cap
        self.group_cap = group_cap
        self.pair_cap = pair_cap   # distinct pair-set output capacity
        self.key_bounds = key_bounds   # ops/factorize.KeyBounds or None
        # col → ColLayout for compressed input slabs: decode is traced
        # into the chain ahead of every other stage
        self.layouts = dict(layouts) if layouts else {}
        self.root = chain[0]
        if isinstance(self.root, PhysHashAgg):
            self.aggs: List[AggFunc] = [build_agg(d) for d in self.root.aggs]
        self.prep_nodes: List[Expression] = []  # walk order, structural
        for node in reversed(chain):
            for e in _stage_exprs(node):
                for sub in e.walk():
                    if type(sub).prepare is not Expression.prepare:
                        self.prep_nodes.append(sub)
        from tidb_tpu.ops.jax_env import named_jit, program_name
        # `sig` is the compile-cache signature: its digest names the
        # programs in the profile and in `launch` spans
        self.sig = sig
        self.partial_name = program_name("partial_chain", sig)
        self.merge_name = program_name("merge", sig)
        self.partial = named_jit(self._partial, self.partial_name)
        # the merge takes the slab partials themselves and stacks them in
        # the trace (device_emit.partials_of): nothing is donated, the
        # partials stay alive as the checkpoints a ladder retry resumes from
        self.merge = named_jit(self._merge, self.merge_name)
        # emit distinct (group, value) pair sets only when a multi-slab
        # execution will merge them — single-slab dedup is already exact
        self.has_distinct = want_pairs and \
            isinstance(self.root, PhysHashAgg) and \
            any(d.distinct and d.args for d in self.root.aggs)

    # -- host-side per-execution preparation --------------------------------
    def collect_preps(self, dicts_by_index: Dict[int, Optional[np.ndarray]]):
        """Prepared host inputs (dictionary ranks/LUTs) in structural order.

        Dictionary flow assumes string projections are bare ColumnRefs
        (enforced by _fragment_ok), so the scan dictionaries survive every
        stage unchanged modulo index remapping.
        """
        return collect_chain_preps(self.chain, dicts_by_index)

    # -- traced stages -------------------------------------------------------
    def _eval_chain(self, cols, n_rows, prep_vals):
        """cols: dict index→(values, validity); returns (ctx_cols, live,
        root_node) after all mid-chain stages."""
        from tidb_tpu.ops.jax_env import jnp
        prepared = {id(node): v for node, v in zip(self.prep_nodes, prep_vals)
                    if v is not None}
        # a delta generation hands its slab's liveness MASK where a plain
        # one hands the length of its live prefix (executor/delta.py)
        n_rows = jnp.asarray(n_rows)
        live = n_rows if n_rows.dtype == jnp.bool_ else \
            jnp.arange(self.slab_cap, dtype=jnp.int32) < n_rows
        if self.layouts:
            from tidb_tpu.executor import device_emit
            cols = {i: (device_emit.emit_decode(self.layouts[i], t,
                                                self.slab_cap)
                        if self.layouts.get(i) is not None else t)
                    for i, t in cols.items()}
        max_idx = max(cols) if cols else -1
        col_list: List = [cols.get(i) for i in range(max_idx + 1)]
        ctx = EvalContext(jnp, col_list, prepared=prepared, on_device=True,
                          n_rows=self.slab_cap)
        from tidb_tpu.executor.device_emit import stage
        for node in reversed(self.chain):
            if isinstance(node, PhysTableScan):
                with stage("filter"):
                    for f in node.filters:
                        v, m = f.eval(ctx)
                        live = live & (v != 0) & m
            elif isinstance(node, PhysSelection):
                with stage("filter"):
                    for c in node.conditions:
                        v, m = c.eval(ctx)
                        live = live & (v != 0) & m
            elif isinstance(node, PhysProjection):
                with stage("project"):
                    new_cols = [e.eval(ctx) for e in node.exprs]
                ctx = EvalContext(jnp, new_cols, prepared=prepared,
                                  on_device=True, n_rows=self.slab_cap)
        return ctx, live

    def _partial(self, cols, n_rows, prep_vals):
        # A chain partial IS a fused pipeline: scan → filter/project →
        # root reduction in one trace.  The root dispatch lives in
        # device_emit.emit_root so the linear-chain, join-tree and fused
        # per-slab programs share one emit layer.
        from tidb_tpu.executor import device_cache, device_emit
        _count_trace()
        # (a slab of a stacked column is indexed here, inside the trace)
        cols, n_rows = device_cache.in_place((cols, n_rows))
        ctx, live = self._eval_chain(cols, n_rows, prep_vals)
        return device_emit.emit_root(
            ctx, live, self.root, aggs=getattr(self, "aggs", None),
            group_cap=self.group_cap, key_bounds=self.key_bounds,
            pairs_out=self.has_distinct, slab_cap=self.slab_cap,
            pair_cap=self.pair_cap)

    def _merge(self, key_cols, states, slot_live):
        """Merge stacked slab partials: re-factorize partial keys, sanitize
        dead slots to identities, scatter-merge states (AggFunc.merge is the
        same segment op as update — SURVEY A.4)."""
        from tidb_tpu.executor import device_emit
        _count_trace()
        return device_emit.emit_merge(self.root, self.aggs, self.group_cap,
                                      key_cols, states, slot_live)


def _dict_list(dicts_by_index: Dict[int, Optional[np.ndarray]]) -> List:
    if not dicts_by_index:
        return []
    n = max(dicts_by_index) + 1
    return [dicts_by_index.get(i) for i in range(n)]


def collect_chain_preps(chain: List[PhysicalPlan],
                        dicts_by_index: Dict[int, Optional[np.ndarray]]):
    """Prepared host inputs for `chain`, positionally aligned with the
    prep_nodes of ANY structurally identical chain's program.

    Module-level on purpose: with parametrized chains the compile cache
    returns a program built from ANOTHER statement's chain (their
    value-free signatures collide — that's the point), so the parameter
    values must be collected from the CURRENT statement's own ParamExpr
    nodes. The traversal is purely structural (same walk as
    _FragmentProgram.__init__), so position k here is position k there.
    """
    vals = []
    dicts = _dict_list(dicts_by_index)
    stage_dicts = dicts
    for node in reversed(chain):
        for e in _stage_exprs(node):
            for sub in e.walk():
                if type(sub).prepare is not Expression.prepare:
                    vals.append(sub.prepare(stage_dicts))
        if isinstance(node, PhysProjection):
            stage_dicts = [
                stage_dicts[e.index] if isinstance(e, ColumnRef)
                and e.index < len(stage_dicts) else None
                for e in node.exprs]
    return vals


# comparison ops whose numeric literals are safe to parametrize: the
# kernels evaluate both sides as arrays with no host fast path keyed on
# the python value. "in" is deliberately excluded — its integer fast
# path builds a host-side sorted table from Constant values, and its
# string preparation is variable-length.
_PARAM_CMP_OPS = frozenset({"eq", "ne", "lt", "le", "gt", "ge"})


def _parametrize_expr(e: Expression):
    """→ (expr, changed): `expr` with numeric comparison literals
    replaced by ParamExpr leaves (value rides prep_vals, repr is
    value-free). Non-comparison structure is cloned only when a child
    changed."""
    from tidb_tpu.expression import Constant, ParamExpr, ScalarFunc
    if not isinstance(e, ScalarFunc):
        return e, False
    changed = False
    new_args: List[Expression] = []
    for a in e.args:
        if (e.op in _PARAM_CMP_OPS and type(a) is Constant
                and a.value is not None
                and not a.ftype.kind.is_string
                and a.ftype.np_dtype != np.dtype(object)):
            new_args.append(ParamExpr(a.value, a.ftype))
            changed = True
        else:
            na, ch = _parametrize_expr(a)
            new_args.append(na)
            changed = changed or ch
    if not changed:
        return e, False
    return e.rebuild(new_args), True


def _parametrize_chain(chain: List[PhysicalPlan]):
    """Clone the chain with scan-filter / selection comparison literals
    lifted into ParamExpr parameters, so `WHERE k = 17` and `= 42`
    share one compiled program and can micro-batch. → the cloned chain,
    or None when nothing was parametrizable (caller keeps the original
    literal-baked path). Nodes are shallow-copied; the original plan is
    never mutated (the CPU fallback re-executes it)."""
    import copy
    out: List[PhysicalPlan] = []
    any_changed = False
    for node in chain:
        if isinstance(node, PhysTableScan) and node.filters:
            new_f, ch = [], False
            for f in node.filters:
                nf, c = _parametrize_expr(f)
                new_f.append(nf)
                ch = ch or c
            if ch:
                node = copy.copy(node)
                node.filters = new_f
                any_changed = True
        elif isinstance(node, PhysSelection) and node.conditions:
            new_c, ch = [], False
            for f in node.conditions:
                nf, c = _parametrize_expr(f)
                new_c.append(nf)
                ch = ch or c
            if ch:
                node = copy.copy(node)
                node.conditions = new_c
                any_changed = True
        out.append(node)
    return out if any_changed else None


def _charge_compile(kind: str, t0: float) -> None:
    """Attribute one cold program build to the running statement: bump its
    PhaseTimer compile counter (thread-local — the single-flight builders
    have no ExecContext in reach) and emit a timeline compile event."""
    from tidb_tpu.util import phases as _phases
    cur = _phases.current()
    if cur is not None:
        cur.note_compile()
    timeline.record(f"compile:{kind}", "compile",
                    dur_us=(time.perf_counter() - t0) * 1e6,
                    pid=cur.conn_id if cur is not None else 0,
                    args={"wait": "build"})


def get_program(chain, used_cols, in_types, slab_cap, group_cap,
                key_bounds=None, want_pairs=False,
                layouts=None, pair_cap=0, sig=None) -> _FragmentProgram:
    """`sig` lets a specialization-cache hit skip signature construction
    entirely — valid because the spec key pins the same geometry, layout
    set and key bounds the signature would encode."""
    if sig is None:
        sig = _chain_signature(chain, used_cols, in_types, slab_cap,
                               group_cap, key_bounds, layouts) + \
            f"|pairs={want_pairs},{pair_cap}"
    return _get_or_build(sig, "chain", lambda: _FragmentProgram(
        chain, used_cols, in_types, slab_cap, group_cap, key_bounds,
        want_pairs, layouts, pair_cap, sig=sig))


class _BatchedProgram:
    """A base fragment program vmapped over a leading member axis: one
    launch serves `b_pad` statements whose prepared parameters are
    stacked along axis 0 (executor/microbatch.py). Shares the compile
    cache/LRU with scalar programs under sig `batched[B]|<base sig>`."""

    __slots__ = ("base", "b_pad", "partial", "partial_name")

    def __init__(self, base: _FragmentProgram, b_pad: int, sig: str = ""):
        from tidb_tpu.executor import device_emit
        from tidb_tpu.ops.jax_env import program_name
        self.base = base
        self.b_pad = b_pad
        self.partial_name = program_name("batched", sig)
        self.partial = device_emit.emit_batched(base._partial,
                                                self.partial_name)


def get_batched_program(base: _FragmentProgram, b_pad: int,
                        base_sig: str) -> _BatchedProgram:
    sig = f"batched[{b_pad}]|{base_sig}"
    return _get_or_build(sig, "batched",
                         lambda: _BatchedProgram(base, b_pad, sig))


def _get_dist_program(root, caps, group_cap, mesh, bucket_caps,
                      join_cfgs=None, scan_layouts=None):
    from tidb_tpu.executor.dist_fragment import DistTreeProgram
    from tidb_tpu.executor.tree_fragment import (_walk_nodes,
                                                 tree_signature)
    from tidb_tpu.planner.physical import PhysExchange
    bux = ",".join(str(bucket_caps[id(n)]) for n in _walk_nodes(root)
                   if isinstance(n, PhysExchange) and n.kind == "hash")
    sig = (f"dist={mesh.devices.size}|bux={bux}|" +
           tree_signature(root, caps, group_cap, join_cfgs,
                          scan_layouts=scan_layouts))
    return _get_or_build(sig, "dist", lambda: DistTreeProgram(
        root, caps, group_cap, mesh, dict(bucket_caps), join_cfgs,
        scan_layouts, kind="dist", sig=sig))


def get_tree_program(root, caps, group_cap, join_cfgs=None,
                     agg_key_bounds=None, scan_layouts=None):
    from tidb_tpu.executor.tree_fragment import TreeProgram, tree_signature
    sig = tree_signature(root, caps, group_cap, join_cfgs, agg_key_bounds,
                         scan_layouts)
    return _get_or_build(sig, "tree", lambda: TreeProgram(
        root, caps, group_cap, join_cfgs, agg_key_bounds, scan_layouts,
        sig=sig))


def get_pipeline_program(root, caps, group_cap, join_cfgs=None,
                         agg_key_bounds=None, scan_layouts=None,
                         pairs_out=False, pair_cap=0, sig=None):
    """Fused per-slab pipeline program: a TreeProgram whose probe-anchor
    scan capacity is ONE slab, so scan → filter → project → join-probe →
    partial-agg over that slab trace as a single jitted XLA program whose
    intermediates never leave registers/HBM.  The signature extends
    tree_signature — the per-scan `cap=CxN` term already distinguishes the
    per-slab anchor shape from the mega-slab tree program — and cold
    builds charge the `compile:fused` timeline lane."""
    from tidb_tpu.executor.tree_fragment import TreeProgram, tree_signature
    if sig is None:
        sig = (f"fused|pairs={pairs_out},{pair_cap}|" +
               tree_signature(root, caps, group_cap, join_cfgs,
                              agg_key_bounds, scan_layouts))
    prog = _get_or_build(sig, "fused", lambda: TreeProgram(
        root, caps, group_cap, join_cfgs, agg_key_bounds, scan_layouts,
        pairs_out, pair_cap, kind="partial_fused", sig=sig))
    return prog, sig


class _AggMergeProgram:
    """Root merge for fused-pipeline agg partials: the per-slab pipeline
    programs each emit a group_cap-slot partial, and this (single, cached)
    program re-factorizes the stacked keys and scatter-merges the states —
    the second and last device launch of a warm fused execution."""

    def __init__(self, root, group_cap: int, sig: str = ""):
        from tidb_tpu.ops.jax_env import named_jit, program_name
        self.root = root
        self.group_cap = group_cap
        self.aggs = [build_agg(d) for d in root.aggs]
        self.merge_name = program_name("merge", sig)
        self.merge = named_jit(self._merge, self.merge_name)

    def _merge(self, key_cols, states, slot_live):
        from tidb_tpu.executor import device_emit
        _count_trace()
        return device_emit.emit_merge(self.root, self.aggs, self.group_cap,
                                      key_cols, states, slot_live)


def get_merge_program(root, group_cap: int,
                      pipeline_sig: str) -> _AggMergeProgram:
    sig = "fusedmerge|" + pipeline_sig
    return _get_or_build(sig, "fused",
                         lambda: _AggMergeProgram(root, group_cap, sig))


class _SortRowsProgram:
    """The ONE sort of a grouping by sorted runs (ops/factorize.sort_rows)
    over every slab's rows at once: a program of its own whose signature
    holds shapes and nothing of a statement, so that every statement of
    the same geometry shares its executable — the TPU compiler charges
    each sort's comparator to every program that holds one (PERF.md §6,
    PR 28). Slabs are stacked in the trace."""

    def __init__(self, sig: str):
        from tidb_tpu.ops.jax_env import named_jit, program_name
        self.name = program_name("sort_rows", sig)
        self.run = named_jit(self._run, self.name)

    def _run(self, words, payloads, lives):
        from tidb_tpu.executor import device_emit
        from tidb_tpu.ops import factorize as F
        from tidb_tpu.ops.jax_env import jnp
        _count_trace()
        with device_emit.stage("agg"):
            cat = jnp.concatenate
            return F.sort_rows([cat(w) for w in words], cat(lives),
                               [cat(p) for p in payloads])


class _RunsFinalizeProgram:
    """A statement's tail over its sorted rows: states by scans, keys,
    ORDER BY … LIMIT (device_emit.emit_runs_finalize). No sort in it."""

    def __init__(self, agg_root, order_root, cap: int, key_bounds,
                 sig: str):
        from tidb_tpu.ops.jax_env import named_jit, program_name
        self.agg_root = agg_root
        self.order_root = order_root
        self.cap = cap
        self.key_bounds = key_bounds
        self.aggs = [build_agg(d) for d in agg_root.aggs]
        self.key_dtypes = [e.ftype.np_dtype for e in agg_root.group_exprs]
        self.name = program_name("finalize", sig)
        self.run = named_jit(self._run, self.name)

    def _run(self, rows):
        from tidb_tpu.executor import device_emit
        _count_trace()
        return device_emit.emit_runs_finalize(
            self.agg_root, self.order_root, self.aggs, self.cap,
            self.key_bounds.bounds, self.key_dtypes, rows,
            self.key_bounds.arg_bits)


def _sig_tag(kind: str, sig: str) -> str:
    """The `sig` tag of a `launch` span: `<kind>:<sig12>`."""
    import hashlib
    return f"{kind}:{hashlib.sha1(sig.encode()).hexdigest()[:12]}"


def _order_sig(order_root) -> str:
    k = getattr(order_root, "count", None)
    off = getattr(order_root, "offset", 0)
    return (f"{type(order_root).__name__}(by={order_root.by!r}, "
            f"descs={order_root.descs}, k={k}, off={off})")


class _FusedFinalizeProgram:
    """Whole-query tail in ONE launch: agg merge → finalize expressions →
    root ORDER BY / TopN (device_emit.emit_finalize). Replaces the plain
    merge launch when the statement root is an eligible Sort/TopN over the
    agg, keeping a warm analytic query at `slabs + 1` programs total."""

    def __init__(self, agg_root, order_root, group_cap: int,
                 sig: str = ""):
        from tidb_tpu.ops.jax_env import named_jit, program_name
        self.agg_root = agg_root
        self.order_root = order_root
        self.group_cap = group_cap
        self.aggs = [build_agg(d) for d in agg_root.aggs]
        self.name = program_name("finalize", sig)
        self.run = named_jit(self._run, self.name)

    def _run(self, key_cols, states, slot_live):
        from tidb_tpu.executor import device_emit
        _count_trace()
        return device_emit.emit_finalize(self.agg_root, self.order_root,
                                         self.aggs, self.group_cap,
                                         key_cols, states, slot_live)


def get_finalize_program(agg_root, order_root, group_cap: int,
                         base_sig: str):
    """→ (program, sig). Cold builds charge the `compile:finalize`
    timeline lane; `base_sig` is the partial/pipeline signature so the
    finalize specializes per upstream shape."""
    sig = "fusedfinal|" + _order_sig(order_root) + "|" + base_sig
    prog = _get_or_build(sig, "finalize", lambda: _FusedFinalizeProgram(
        agg_root, order_root, group_cap, sig))
    return prog, sig


def _control_of(partials, control) -> dict:
    """What the driver's ONE control fetch reads off the slab partials:
    each slab's true group count and the source's own (`control`)."""
    return {"ngs": [p["n_groups"] for p in partials], **control(partials)}


def _control_tree(ctl: dict, out, small: bool) -> dict:
    """The tree one control fetch brings to the host: the slabs' control
    values `ctl`, the merged group count, a finalize's row count, and —
    where the group capacity is `small` — the result itself, which then
    rides the same round trip."""
    fetch = {**ctl, "ng": out["n_groups"]}
    if "n_out" in out:
        fetch["no"] = out["n_out"]
    if small:
        fetch["keys"], fetch["states"] = out["keys"], out["states"]
    return fetch


def _pack_key(dtype) -> str:
    """Which packed vector a leaf of `dtype` rides: every integer and
    boolean the int64 one, anything else its own dtype's."""
    return "int64" if np.dtype(dtype).kind in "biu" else str(dtype)


def _pack(tree) -> dict:
    """`tree`'s leaves flattened into ONE vector a `_pack_key` (traced).
    What a statement program hands the host costs it by the PIECE, not by
    the byte: ≈ 45 µs an output array at the launch and ≈ 60 µs a leaf at
    the `device_get` on the chip's host (PERF.md §6, PR 39), and Q1's
    control fetch has thirty leaves."""
    from tidb_tpu.ops.jax_env import jax, jnp
    by: dict = {}
    for leaf in jax.tree.leaves(tree):
        key = _pack_key(leaf.dtype)
        by.setdefault(key, []).append(jnp.ravel(leaf).astype(key))
    return {key: jnp.concatenate(parts) for key, parts in by.items()}


def _unpack(packed: dict, like):
    """`_pack`'s vectors, on the host, cut back into the tree whose leaves'
    shapes and dtypes `like` holds, in the same order."""
    from tidb_tpu.ops.jax_env import jax
    leaves, treedef = jax.tree.flatten(like)
    at = dict.fromkeys(packed, 0)
    out = []
    for leaf in leaves:
        key, n = _pack_key(leaf.dtype), math.prod(leaf.shape)
        out.append(np.asarray(packed[key][at[key]:at[key] + n])
                   .astype(leaf.dtype).reshape(leaf.shape))
        at[key] += n
    return treedef.unflatten(out)


class _StatementProgram:
    """A warm aggregate statement as ONE jitted call (`_run_agg_slabs`,
    launch plan `whole`): the body of every surviving slab — what the
    chain's `partial` or the fused pipeline's tree program traces a launch
    each — then the merge or the fused finalize over their partials
    (`tail`, its traced function; None where one slab's partial is the
    answer), composed in one trace under the stages' own named scopes.

    The base slabs share one shape, so their body is traced ONCE, as the
    body of a loop over them (`lax.scan`): a program with a copy of the
    body a slab compiles, and loads from the persistent cache, a slab's
    worth of seconds a copy (Q1 over six 8M-row slabs: 113 s cold and 20 s
    from the cache on the chip's host, every run's set-up; PERF.md §6,
    PR 39). The base slabs enter as they lie in the device cache: ONE
    array a leaf with a leading axis of slabs (`base`, a slab's pytree
    whose stacked leaves are `device_cache.Stacked`), and each turn of the
    loop INDEXES its slab (`in_place`: a dynamic slice inside the fusion
    that reads it — no slab is copied; PERF.md §6, PR 46). Which rows of
    the stacks the turns read (`picks`: the slabs zone maps left, as small
    int32 device vectors) is an argument, so pruning names no program. A
    one-slab table's arrays come as they are: nothing to index. The raw
    delta slab has a shape of its own and its own arrays (`delta`): its
    body (`dbody`) follows the loop. Of
    the partials only what the control fetch reads leaves the program,
    packed (`_pack`; `like`, the control tree's shapes and dtypes read off
    the arguments `args` when the program is built — and compiled — says
    how to cut it): an overflow it shows sends the statement to the
    per-slab driver, whose partials are the ladder's checkpoints. `small`:
    the result itself rides the fetch, and nothing else is handed out.
    → (the result on the device, or None where it rides; the packed
    control tree)."""

    def __init__(self, kind: str, body, dbody, tail, control, small: bool,
                 sig: str, args):
        from tidb_tpu.ops.jax_env import jax, named_jit, program_name
        self.body, self.dbody = body, dbody
        self.tail, self.control, self.small = tail, control, small
        self.sig = sig
        self.name = program_name(kind, sig)
        self.run = named_jit(self._run, self.name)
        # what the trace said of itself (`slab_pick`), for the launch span
        # of the first call: the build below traces under no launch
        self.said: Optional[dict] = None
        self.like = jax.eval_shape(self._fetch, *args)[1]
        # compiled HERE — under the signature's build lock, by one
        # statement, outside the batch slot — and not by the first launch
        # inside it (the call finds the executable: JAX keeps one cache
        # for both), so a statement-sized compile holds nobody's slot
        self.run.lower(*args).compile()

    def _run(self, shared, base, delta, picks):
        _count_trace()
        out, fetch = self._fetch(shared, base, delta, picks)
        return out, _pack(fetch)

    def _fetch(self, shared, base, delta, picks):
        """→ (the result, or None where it rides the fetch; the control
        tree): what `_run` packs."""
        from tidb_tpu.executor.device_cache import in_place
        from tidb_tpu.executor.device_emit import partials_of
        from tidb_tpu.ops.jax_env import jax, jnp, lax
        partials = []       # each leaf with a leading axis of slabs
        if base is not None:
            # (no vector: a one-slab table, whose arrays are the slab)
            n_run = picks[0].shape[0] if picks else 1
            if picks:
                self.said = {"slab_pick": "index"}

            def turn(k):
                return self.body(shared, in_place(base, picks, k))
            if n_run == 1:
                partials.append(jax.tree.map(lambda a: a[None], turn(0)))
            else:
                partials.append(lax.scan(
                    lambda _c, k: (None, turn(k)), None,
                    jnp.arange(n_run, dtype=jnp.int32))[1])
        if delta is not None:
            partials.append(jax.tree.map(
                lambda a: a[None], self.dbody(shared, delta)))
        ctl = {k: jnp.concatenate(v) for k, v in
               _control_of(partials, self.control).items()}
        if self.tail is None:
            out = jax.tree.map(lambda a: a[0], partials[0])
        else:
            # a partial a slab again, as the per-slab driver hands them to
            # the same tail (whose merge folds a float sum slab by slab)
            out = self.tail(*partials_of([
                jax.tree.map(lambda a, i=i: a[i], p)
                for p in partials for i in range(p["n_groups"].shape[0])]))
        return (None if self.small else out,
                _control_tree(ctl, out, self.small))


def get_statement_program(src: "_SlabSource", prog, n_run: int, tail,
                          tail_sig: str, small: bool,
                          args) -> _StatementProgram:
    """`tail_sig` is the signature of what follows the slabs (which holds
    the slab program's own). How MANY slabs survived joins it, not which,
    and the raw delta slab's program where one runs. `args`: what the
    program will be run with (`statement_args`); a build reads shapes off
    them and keeps none."""
    delta = src.delta_id in src.run_ids
    sig = (f"stmt|slabs={n_run}|delta={src.dsig if delta else '-'}|"
           f"small={small}|{tail_sig}")
    return _get_or_build(sig, "stmt", lambda: _StatementProgram(
        src.stmt_kind, src.statement_body(prog),
        src.statement_body(src.dprog) if delta else None, tail,
        type(src).control, small, sig, args))


# ---------------------------------------------------------------------------
# Per-digest specialization cache
# ---------------------------------------------------------------------------
# Sits IN FRONT of the single-flight compile cache: keyed by the
# statement's normalize_sql digest plus everything the runtime otherwise
# re-derives per execution (slab geometry, compressed-layout set, cached
# key bounds, pair mode), it remembers the FINAL capacities a previous
# execution settled on and the exact compile-cache signature it ran with.
# A hit adopts those caps (skipping the overflow ladder's discovery
# climb) and passes the stored signature straight to the program getter
# (skipping signature construction), so the second execution of any
# statement shape dispatches fully fused warm programs directly.

_SPEC_CACHE: "OrderedDict[tuple, dict]" = OrderedDict()
MAX_SPECIALIZATIONS = 256


def _spec_key(guard, kind: str, extra: tuple):
    """None when the statement has no SQL text attached — ad-hoc plan
    executions don't specialize."""
    sql = getattr(guard, "sql", None) if guard is not None else None
    if not sql:
        return None
    from tidb_tpu.util.observability import normalize_sql
    # Raw SQL rides along with the digest: literals are baked into the
    # traced programs (filter/projection exprs are trace constants), so
    # two statements sharing a digest but differing in literals must NOT
    # share a specialization entry.
    return (kind, normalize_sql(sql), sql) + extra


def _plan_fingerprint(node) -> str:
    """Cheap per-fragment plan identity for the specialization key: one
    statement can run SEVERAL fragments under the same guard.sql (a
    plan-time uncorrelated subquery, a derived table), and geometry
    alone can't tell them apart — without this, the subquery's entry
    shadows the outer fragment's and hands it the wrong compiled
    signature (wrong agg-state layout)."""
    out = []
    stack = [node]
    while stack:
        n = stack.pop()
        out.append(n.describe())
        stack.extend(getattr(n, "children", ()))
    return "|".join(out)


def _spec_lookup(key, lay_sig: Optional[str] = None) -> Optional[dict]:
    """`lay_sig` is the statement's CURRENT layout-set signature. It is
    deliberately NOT part of the key: a table re-encode (compression
    toggled, workload-adaptive re-choice) must EVICT the stale entry —
    its cached compile-cache signature names programs that decode the
    old layouts — not orphan it under a dead key while a lookup with
    the old signature could still hit it."""
    if key is None:
        return None
    with _CC_LOCK:
        ent = _SPEC_CACHE.get(key)
        if ent is not None and ent.get("lay_sig") != lay_sig:
            del _SPEC_CACHE[key]    # layout changed: stale, evict
            return None
        if ent is not None:
            _SPEC_CACHE.move_to_end(key)
        return ent


def _spec_store(key, ent: dict) -> None:
    if key is None:
        return
    with _CC_LOCK:
        _SPEC_CACHE[key] = ent
        while len(_SPEC_CACHE) > MAX_SPECIALIZATIONS:
            _SPEC_CACHE.popitem(last=False)


def _spec_note(ph, hit: bool) -> None:
    from tidb_tpu.util.observability import REGISTRY
    timeline.tag(spec="hit" if hit else "miss")
    if hit:
        if ph is not None:
            ph.note_spec_hit()
        REGISTRY.inc("tidb_tpu_specialization_hits_total",
                     {"engine": "device"})
    else:
        REGISTRY.inc("tidb_tpu_specialization_misses_total",
                     {"engine": "device"})


# the span tag and counter label of each lowering ("bounds" is older than
# the mode's name)
_GROUPING_TAG = {SLOTS: "bounds", RUNS: "runs", FACTORIZE: "factorize"}


def _note_grouping(root: PhysHashAgg, key_bounds, group_cap: int) -> str:
    """Tag the open `device.fragment` span with how this aggregate's
    partials assign rows to slots and into how many: "global" (no GROUP
    BY, one slot), "bounds" (a packed code over known key domains),
    "runs" (sorted runs: the slab programs hand out rows, `gcap` 0 there)
    or "factorize" (sort-based partials). → the grouping, the label of
    `tidb_tpu_agg_partials_total`."""
    grouping = ("global" if not root.group_exprs else
                _GROUPING_TAG[grouping_mode(key_bounds)])
    timeline.tag(grouping=grouping, gcap=int(group_cap))
    return grouping


def _note_agg_io(partial, rows_in: int, groups: int) -> None:
    """Tag the open `device.fragment` span with what its grouping took in
    and gave out: `rows_in` (rows of the slabs whose partials launched,
    re-runs included, and those of a statement program that overflowed
    and was answered again slab by slab), `groups` (live groups out), and
    the bytes one group holds on the device, `key_bytes` and `state_bytes`
    (read off a partial's own arrays)."""
    if not timeline.ENABLED:
        return
    timeline.tag(
        rows_in=int(rows_in), groups=int(groups),
        key_bytes=sum(v.dtype.itemsize + m.dtype.itemsize
                      for v, m in partial["keys"]),
        state_bytes=sum(a.dtype.itemsize for st in partial["states"]
                        for a in st))


def _tight_cap(cap: int, groups: int) -> int:
    """The capacity the NEXT execution of a grouping by sorted runs starts
    from (kept by the specialization cache): the groups it found plus an
    eighth, not the planner's estimate. What such a finalize costs is its
    gathers at the run ends, `cap` elements each (0.37 s per 16M on a
    v5e), and an estimate can be a thousand times the groups a semijoin
    leaves."""
    from tidb_tpu.executor.device_cache import _pow2
    return min(cap, _pow2(groups + groups // 8 + 16, lo=1024))


def _count_agg_partial(grouping: str) -> None:
    """One program holding an aggregate's partial was launched (a slab's,
    or a statement program with every slab's)."""
    from tidb_tpu.util.observability import REGISTRY
    REGISTRY.inc("tidb_tpu_agg_partials_total", {"grouping": grouping})


def _launch_plan(src: "_SlabSource", spec, want_pairs: bool,
                 rows_mode: bool) -> str:
    """How `_run_agg_slabs` issues a statement's device work, from what it
    can observe: `whole` — ONE statement program — when an earlier
    execution of the digest settled the capacities (`spec`) and every slab
    is resident on one device; else `slabs:<why>`, a launch a slab and the
    merge. Sorted runs are a driver of their own, DISTINCT pair sets are
    fetched between the slabs and the merge, a pod's slabs lie on several
    devices, a cold table's first touch streams slab by slab. (An overflow
    read back from a statement program makes it `slabs:overflow`.)"""
    why = ("runs" if rows_mode else "pairs" if want_pairs else
           "pod" if src.pod else "cold" if src.stream is not None else
           "spec-miss" if spec is None else None)
    return "whole" if why is None else "slabs:" + why


def _initial_group_cap(root: PhysHashAgg, default_cap: int,
                       max_cap: int, key_bounds=None) -> int:
    """The group capacity an aggregate starts from. Keys that address
    their slots directly (`key_bounds` in SLOTS mode) need exactly their
    packed domain: a slot per value and one for NULL, per key.

    Otherwise stats-informed: when the planner's group estimate came from
    real NDV stats (est_reliable, planner/physical.estimate), a 1.5×
    headroom start avoids the overflow→retry recompile ladder both for
    high-cardinality keys (e.g. GROUP BY orderkey) and tiny ones.

    An aggregate with no GROUP BY has exactly one group whatever the
    estimate or `tidb_tpu_group_cap` say: one slot, so its partial states
    are plain masked reductions (ops/segment.py) and nothing can overflow."""
    if grouping_mode(key_bounds) == SLOTS:
        cap = 1
        for lo, hi in key_bounds.bounds:
            cap *= hi - lo + 2
        return cap
    if not root.group_exprs:
        return 1
    if not getattr(root, "est_reliable", False):
        return default_cap
    from tidb_tpu.executor.device_cache import _pow2
    want = int(root.est_rows * 1.5) + 16
    return min(_pow2(want), max_cap)


DOMAIN_CAP = 1 << 20    # max packed group-key domain for perfect hashing
# Beyond the masked reduce's slot count a directly addressed partial is an
# int64 scatter-add per state — 1.1 s a state and 8M-row slab on a v5e
# (a GROUP BY over 150K customer keys at SF=1 took 2.9 s so, against
# 0.9 s at SF=8 where its 1.2M keys already grouped by sorted runs:
# PERF.md §6, PR 28). So a
# wider key domain groups by sorted runs wherever the aggregates allow.
SLOT_ADDRESS_CAP = 1024


def _trace_to_scan_col(chain: List[PhysicalPlan], expr) -> Optional[int]:
    """Follow a ColumnRef through the chain's projections down to a scan
    column index, or None if the value is computed."""
    if not isinstance(expr, ColumnRef):
        return None
    idx = expr.index
    for node in chain[1:]:
        if isinstance(node, PhysProjection):
            e = node.exprs[idx]
            if not isinstance(e, ColumnRef):
                return None
            idx = e.index
    return idx


def _agg_key_bounds(chain: List[PhysicalPlan], ent) -> Optional[KeyBounds]:
    """What the aggregate's programs read from the cached bounds
    (ops/factorize.KeyBounds). Per-group-key (lo, hi) domains when every
    key is a scan column with cached bounds, and the lowering they allow
    (ops/factorize.choose_key_bounds): a small packed domain addresses
    the group slots directly, a large one packs the keys into sort words
    for the sorted-runs grouping where the aggregates allow it; else
    sort factorize. And the widths of the summed arguments, by interval
    arithmetic from the scan's columns up through the chain's projections
    (tree_fragment._bounds_list, expression/ranges)."""
    from tidb_tpu.executor import device_emit
    from tidb_tpu.expression import ranges
    root = chain[0]
    if not isinstance(root, PhysHashAgg) or not root.group_exprs:
        return None
    if getattr(root, "rollup", False):
        return None     # level tiling needs the sort factorize
    bounds: Optional[List[Tuple[int, int]]] = []
    domain = 1
    for e in root.group_exprs:
        idx = _trace_to_scan_col(chain, e)
        b = ent.bounds.get(idx) if idx is not None else None
        if b is None:
            bounds = None
            break
        lo, hi = b
        domain *= (hi - lo + 2)
        bounds.append((lo, hi))
    from tidb_tpu.executor import tree_fragment as TF
    return choose_key_bounds(
        bounds, domain, SLOT_ADDRESS_CAP, DOMAIN_CAP,
        device_emit.sorted_runs_ok(root), ranges.agg_arg_bits(
            root, tuple(sorted(ent.bounds.items())),
            lambda: TF._bounds_list(chain[1], {id(chain[-1]): ent.bounds},
                                    True)))


def _ent_layouts(ent, used):
    """col → ColLayout for the used columns that are stored compressed;
    None when every used column is raw (keeps signatures byte-identical
    to the pre-compression cache keys)."""
    lays = {i: ent.layouts.get(i) for i in used
            if ent.layouts.get(i) is not None}
    return lays or None


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------


def _plan_aligned_joins(ctx, root, scans, ents):
    """Serve PK-FK joins from the FK-aligned device cache where possible
    (device_cache.AlignedJoin — the join-index/coprocessor-cache analog).

    Eligible: single equi key, both sides bare ColumnRefs, the build
    subtree anchored (through its probe chain) on a scan whose key column
    has cached (lo, hi) bounds, and the probe key resolving to the fact
    scan's row space. Chains compose BOTH ways: through earlier aligned
    joins in the probe subtree ((l⋈o)⋈c — Q5's o_custkey as an aligned
    column) and through joins nested in the build subtree ((c⋈o)⋈l, the
    dimensions-first order the join reorderer prefers) — in the latter
    case every inner join is recursively re-anchored to the fact row
    space, and the whole attempt aborts unless all of them align (a
    non-aligned inner join could flip to expand mode at runtime and break
    the row-space invariant). Build-key uniqueness is VERIFIED at cache
    build, so aligned joins never lose runtime bets; a non-unique build
    caches the negative result and keeps the standard LUT/sort modes.

    → {id(join): {entry, build_scan, build_ent, cols}}"""
    from tidb_tpu.executor import device_cache
    from tidb_tpu.executor import tree_fragment as TF
    if getattr(ctx, "txn", None) is not None:
        return {}
    store = getattr(ctx.snapshot, "store", None)
    if store is None:
        return {}
    ents_by_scan = {id(s): e for s, (e, _) in zip(scans, ents)}
    info_by_join: Dict[int, dict] = {}
    # id(anchor scan) → (entry, anchor ent): scans substituted by an outer
    # aligned join — references to their columns resolve to aligned arrays
    anchor_subs: Dict[int, tuple] = {}

    def aligned_ref(entry, a_ent, idx):
        """(entry, col) → resolve() result tuple, or None."""
        if a_ent.dicts.get(idx) is not None:
            return None
        slabs = device_cache.aligned_col(entry, a_ent, idx)
        v_shape = slabs.specs()[0][0]   # (of the values; no slab is read)
        if len(v_shape) != 1:
            return None
        return (lambda: ([v for v, _ in slabs], [m for _, m in slabs]),
                (int(v_shape[-1]), len(slabs)),
                ("al", entry.key, idx), dict(entry.tds), None,
                entry.space)

    def resolve(nodeP, idx):
        """Probe key column → (() → (codes_slabs, valid_slabs), (slab
        capacity, slabs), sig, tds, (fact entry, column) or None, the
        lineages its rows are positioned in) in the fact scan's row space,
        or None. The slabs are decoded only when a
        structure has to be built: a cache hit asks for none."""
        while True:
            if isinstance(nodeP, PhysTableScan):
                sub = anchor_subs.get(id(nodeP))
                if sub is not None:
                    return aligned_ref(sub[0], sub[1], idx)
                ent = ents_by_scan.get(id(nodeP))
                if ent is None or idx not in ent.dev:
                    return None
                if ent.dicts.get(idx) is not None:
                    return None        # string probe key: KeyRemap path
                if nodeP.schema.field_types[idx].is_wide_decimal:
                    return None        # wide-decimal planes can't be keys

                def decoded(ent=ent, idx=idx):
                    slabs = device_cache._decoded_slabs(ent, idx)
                    return [v for v, _ in slabs], [m for _, m in slabs]
                return (decoded, (ent.slab_cap, ent.n_slabs),
                        ("col", nodeP.table.id, idx),
                        {nodeP.table.id:
                         ctx.snapshot.table_data(nodeP.table.id)},
                        (ent, idx), (ent.lineage,))
            if isinstance(nodeP, PhysSelection):
                nodeP = nodeP.children[0]
                continue
            if isinstance(nodeP, PhysProjection):
                e = nodeP.exprs[idx] if idx < len(nodeP.exprs) else None
                if not isinstance(e, ColumnRef):
                    return None
                idx = e.index
                nodeP = nodeP.children[0]
                continue
            if isinstance(nodeP, PhysHashJoin):
                j = nodeP
                bi = 1 if j.build_right else 0
                if j.kind in ("semi", "anti"):
                    # semi/anti preserve the probe row space in EVERY mode
                    nodeP = j.children[1 - bi]
                    continue
                if id(j) not in info_by_join:
                    # a non-aligned inner/outer join may flip to expand
                    # mode at runtime, breaking the row-space invariant —
                    # crossing it (either side) is only safe once aligned
                    return None
                nl = len(j.children[0].schema)
                if j.build_right:
                    if idx < nl:       # probe (left) side column
                        nodeP = j.children[0]
                        continue
                    b_out_idx = idx - nl
                else:
                    if idx >= nl:      # probe (right) side column
                        idx -= nl
                        nodeP = j.children[1]
                        continue
                    b_out_idx = idx
                info = info_by_join[id(j)]
                hit = TF._trace_scan_col(j.children[bi], b_out_idx)
                if hit is None:
                    return None
                bscan2, c2 = hit
                if bscan2 is not info["build_scan"]:
                    return None
                return aligned_ref(info["entry"], info["build_ent"], c2)
            return None

    def trace_col_probewise(node, idx):
        """Column index → (anchor scan, scan col), crossing joins via
        their probe side only (semi/anti emit the probe side verbatim)."""
        while True:
            if isinstance(node, PhysTableScan):
                return node, idx
            if isinstance(node, PhysSelection):
                node = node.children[0]
                continue
            if isinstance(node, PhysProjection):
                e = node.exprs[idx] if idx < len(node.exprs) else None
                if not isinstance(e, ColumnRef):
                    return None
                idx = e.index
                node = node.children[0]
                continue
            if isinstance(node, PhysHashJoin):
                bi = 1 if node.build_right else 0
                if node.kind in ("semi", "anti"):
                    node = node.children[1 - bi]
                    continue
                nl = len(node.children[0].schema)
                if node.build_right:
                    if idx >= nl:
                        return None    # build-side column: not probewise
                    node = node.children[0]
                else:
                    if idx < nl:
                        return None
                    idx -= nl
                    node = node.children[1]
                continue
            return None

    def try_align(jnode) -> bool:
        if len(jnode.equi) != 1:
            return False
        bkeys, pkeys = TF.join_key_exprs(jnode)
        bk, pk = bkeys[0], pkeys[0]
        if not (isinstance(bk, ColumnRef) and isinstance(pk, ColumnRef)):
            return False               # casts / KeyRemap: standard modes
        bi = 1 if jnode.build_right else 0
        build, probe = jnode.children[bi], jnode.children[1 - bi]
        # the SAME traversal _emit_join_aligned uses to find the scan to
        # substitute — planner and trace cannot disagree on the anchor
        anchor, crossed = TF.aligned_chain(build)
        if anchor is None:
            return False
        bhit = trace_col_probewise(build, bk.index)
        if bhit is None or bhit[0] is not anchor:
            return False
        bcol = bhit[1]
        build_ent = ents_by_scan.get(id(anchor))
        if build_ent is None or build_ent.dicts.get(bcol) is not None:
            return False               # string build key: v1 skips
        bounds = build_ent.bounds.get(bcol)
        if bounds is None:
            return False
        src = resolve(probe, pk.index)
        if src is None:
            return False
        fact_slabs, (slab_cap, n_slabs), sig, tds, fact, space = src
        key = (id(store), sig, anchor.table.id, bcol)
        tds[anchor.table.id] = ctx.snapshot.table_data(anchor.table.id)
        if fact is None and (build_ent.is_delta or any(
                e.is_delta for e in ents_by_scan.values())):
            # a chained hop's probe key lives in another structure's
            # row space: only a direct one follows delta generations
            return False
        entry = device_cache.get_aligned(
            ctx, key, tds, fact_slabs, build_ent, bcol, bounds,
            slab_cap, n_slabs, space, fact=fact)
        if entry is None:
            return False
        used = anchor.used_columns or list(range(len(anchor.schema)))
        cols = {i: device_cache.aligned_col(entry, build_ent, i)
                for i in used}
        info_by_join[id(jnode)] = {"entry": entry, "build_scan": anchor,
                                   "build_ent": build_ent, "cols": cols}
        anchor_subs[id(anchor)] = (entry, build_ent)
        # every join inside the build subtree must re-anchor to the fact
        # row space (all-or-nothing: see docstring)
        for K in crossed:
            if not try_align(K):
                return False
        return True

    # parents first, iterated to a fixpoint: a build-side chain claims its
    # inner joins in one recursive attempt, while a probe-side chain's
    # outer join only becomes resolvable after its inner join aligns in a
    # previous pass
    changed = True
    try:
        while changed:
            changed = False
            for node in reversed(TF._walk_joins(root)):
                if id(node) in info_by_join:
                    continue
                saved_info = dict(info_by_join)
                saved_subs = dict(anchor_subs)
                if try_align(node):
                    changed = True
                else:
                    info_by_join.clear()
                    info_by_join.update(saved_info)
                    anchor_subs.clear()
                    anchor_subs.update(saved_subs)
    finally:
        # try_align calls itself: the function and its own cell are a
        # reference cycle that holds every cell of this call — the scans'
        # CachedTables and aligned structures among them — until the
        # collector happens to run. A generation a write superseded must
        # free its device arrays by reference count, so the cycle ends here
        try_align = None
    # unconditional: failed attempts may have left freshly built entries
    # resident; never evict what THIS query executes with (aligned entries
    # in use + every scan's CachedTable)
    device_cache.aligned_budget_check(
        ctx, {i["entry"].key for i in info_by_join.values()},
        keep_tables={(id(store), s.table.id) for s in scans})
    return info_by_join


def _ent_geometry(ent) -> tuple:
    """What of a cached table the specialization cache keys: the base
    build it descends from and its shapes. Not the data's identity and no
    generation number — the programs hold neither, so a write costs the
    next statement no specialization."""
    return (ent.lineage, ent.slab_cap, ent.base_slabs, ent.delta_cap,
            ent.alive is not None)


def _whole_cols(cols: dict) -> dict:
    """A scan's columns as a program that reads the table WHOLE takes
    them: every slab of each (`SlabColumn.whole`: a list, or a stacked
    column's stacks themselves — `TreeProgram._run` lists their slabs
    inside the trace; no statement slices one)."""
    return {i: col.whole() for i, col in cols.items()}


def _whole_masks(alive):
    """A generation's liveness masks over every slab, likewise."""
    w = alive.whole()
    return tuple(w) if isinstance(w, list) else w


def _whole_aligned(matched, jcols):
    """An aligned join's inputs over every fact slab, likewise."""
    def whole(col):
        w = col.whole() if len(col) else ()     # (`()`: no aligned join)
        return tuple(w) if isinstance(w, list) else w
    return whole(matched), {c: whole(sl) for c, sl in jcols.items()}


class _SlabSource:
    """What `TpuFragmentExec._run_agg_slabs` asks of the slabs it
    aggregates; how a slab's arguments are laid out stays in here. A chain
    (`_ChainSlabs`) reads one table's slabs, streamed on first touch; a
    join tree (`_TreeSlabs`) reads its probe anchor's slabs against whole
    build sides and has join capacities to escalate.

    Each holds `kind` (of the specialization key), `root` (the aggregate),
    `key_bounds`, `dicts` (for the decode), `run_ids` (physical ids of the
    slabs zone maps left: the driver's per-slab lists index POSITIONS in
    it), `n_slabs` and `slab_cap` (the table's geometry, whatever was
    pruned, so signatures and ceilings don't depend on pruning), `max_cap`
    (the group ladder's ceiling), and `lay_sig` and `geometry` (what the
    specialization cache compares and keys), `stream` (a cold table's
    first touch in progress, else None), `pod` (slabs on several devices)
    and `delta_id` / `dprog` / `dsig` (the raw delta slab's physical id,
    the program of its shape and that program's signature).

    A slab's arguments come in two parts, for the per-slab launches and
    for the one statement program alike: `shared` (what every slab's body
    takes) and the slab's own; `statement_body(prog)` is the traced body
    of a slab over them. It and `control` hold nothing of a statement: a
    cached statement program keeps them."""

    kind = ""
    stmt_kind = ""      # what the statement program is called in a profile
    stream = None
    pod = False
    delta_id = -1
    dprog, dsig = None, "-"

    @staticmethod
    def control(partials) -> dict:
        """What the batched control fetch brings back besides the group
        counts (traced inside a statement program)."""
        return {}

    def overflowed(self, got) -> bool:
        """Whether what `control` fetched shows a capacity of this
        source's own exceeded (no side effect: `escalate` acts on it)."""
        return False

    def escalate(self, got, ladder):
        """Classify what `control` fetched → (retry, positions to re-run),
        or None to give the statement back to the caller."""
        return False, set()

    def statement_body(self, prog):
        """→ `body(shared, slab)`, the partial of one slab through `prog`
        (the slab program, or the delta slab's) as a traced function."""
        raise NotImplementedError

    def statement_args(self, prog, prep_vals):
        """→ (shared, base, delta, picks): what a statement program takes.
        `base`: the surviving base slabs' own arguments as ONE slab's
        pytree over the columns' stacked storage (`SlabPicks.of`: a column
        is stacked here, the first time a statement program reads it),
        None where none survived; `delta`: the raw delta slab's own, or
        None; `picks`: the stack rows the loop's turns read. Every slab is
        resident on one device."""
        raise NotImplementedError

    def _base_ids(self) -> list:
        return [s for s in self.run_ids if s != self.delta_id]

    def learned(self) -> dict:
        """What a specialization entry keeps besides capacities."""
        return {}

    def adopt(self, spec: dict) -> None:
        """Take `learned` back from an earlier execution's entry."""


class _ChainSlabs(_SlabSource):
    """The slabs of one table under a linear chain (Q1, Q6)."""

    kind = "chain"
    stmt_kind = "stmt_chain"

    def __init__(self, ex: "TpuFragmentExec", chain, ent, stream, used,
                 in_types, dicts, key_bounds, layouts, slab_ids):
        from tidb_tpu.executor import device_cache
        self.ex, self.ctx = ex, ex.ctx
        self.chain, self.root = chain, chain[0]
        self.ent, self.stream = ent, stream
        self.used, self.in_types = used, in_types
        self.dicts, self.key_bounds, self.layouts = dicts, key_bounds, layouts
        # ascending physical order — the cold stream's yield order
        self.run_ids = list(slab_ids)
        self.n_slabs, self.slab_cap = ent.n_slabs, ent.slab_cap
        self.max_cap = ent.slab_cap * max(ent.n_slabs, 1)
        self.lay_sig = ",".join(f"{i}:{l.sig()}"
                                for i, l in sorted(layouts.items())) \
            if layouts else "-"
        self.geometry = _ent_geometry(ent)
        # the raw delta slab of a delta generation runs the SAME chain as
        # a program of its own shape (its capacity, no layouts)
        self.delta_id = ent.base_slabs if ent.delta_cap else -1
        self.dprog = None
        # pod-partitioned entry: each slab's partial computes on its
        # owner device; re-pin every partial to the STATEMENT's device
        # right after dispatch so the merge/finalize graph downstream
        # (concatenate, piggyback packing, fetch) stays single-device —
        # mixing committed arrays from different devices in one op raises
        self.pod_pin = device_cache.device_handle(
            device_cache._ctx_device(self.ctx)) \
            if getattr(ent, "owners", None) is not None else None
        self.pod = self.pod_pin is not None

    def rows(self, pos: int) -> int:
        return self.ent.slab_rows(self.run_ids[pos])

    def program(self, gcap: int, pair_cap: int, want_pairs: bool, sig):
        prog = get_program(self.chain, self.used, self.in_types,
                           self.slab_cap, gcap, self.key_bounds, want_pairs,
                           self.layouts, pair_cap, sig=sig)
        if self.delta_id in self.run_ids:
            self.dprog = get_program(
                self.chain, self.used, self.in_types, self.ent.delta_cap,
                gcap, self.key_bounds, want_pairs, None, pair_cap)
            self.dsig = self.dprog.sig
        return prog, prog.sig, prog.collect_preps(self.dicts)

    def merge_program(self, prog, gcap: int, sig: str):
        return prog

    def launches(self, prog, prep_vals, to_run=None):
        """→ (position, partial) of the slabs at `to_run`, each launched
        as it is asked for; None = the first pass over every surviving
        slab, which STREAMS a cold table's first touch."""
        from tidb_tpu.ops.jax_env import jax
        ex, ent, used = self.ex, self.ent, prog.used_cols
        if to_run is None:
            to_run = range(len(self.run_ids))
            slabs = ex._slab_iter(ent, self.stream, used, self.run_ids)
        else:
            slabs = (ex._slab(ent, self.run_ids[p], used) for p in to_run)
        # (slabs first: zip must run the stream past its last slab, where
        # it commits the upload)
        for (cols, _n), pos in zip(slabs, to_run):
            rid = self.run_ids[pos]
            p = self.dprog if rid == self.delta_id else prog
            live = ent.live_arg(rid)    # on the device since the version's
            # slot per slab DISPATCH: the streamed encode of the next slab
            # (inside _slab_iter) runs slot-free, so a sibling's dispatch
            # interleaves with our host work
            with self.ctx.device_slot():
                with self.ctx.phases.launch(p.partial_name, slab=pos):
                    part = p.partial(cols, live, prep_vals)
                    if self.pod_pin is not None:
                        part = jax.device_put(part, self.pod_pin)
            yield pos, part

    @staticmethod
    def _slab_body(prog, prep_vals, slab):
        cols, live = slab
        return prog._partial(cols, live, prep_vals)

    def statement_body(self, prog):
        return functools.partial(self._slab_body, prog)

    def statement_args(self, prog, prep_vals):
        from tidb_tpu.executor import device_cache
        ent, used = self.ent, prog.used_cols
        ids, base = self._base_ids(), None
        picks = device_cache.SlabPicks(ent, ids, self.chain[-1].table.id)
        if ids:
            base = {i: picks.of(ent.dev[i]) for i in used}, picks.live()
        delta = (self.ex._slab(ent, self.delta_id, used)[0],
                 ent.live_arg(self.delta_id)) \
            if self.delta_id in self.run_ids else None
        return prep_vals, base, delta, picks.vectors()


class _TreeSlabs(_SlabSource):
    """The probe anchor's slabs under a join tree (Q3, Q5, Q10, Q18).

    Join build sides ride inside each per-slab program at their FULL
    (mega-slab) capacities — dimension tables, or FK-aligned columns
    already in the anchor's row space — so every launch joins a partition
    of the probe rows against complete build sides and the slab union of
    agg partials is exact for every join kind (tree_ok pins outer joins to
    preserve the probe side, the same argument that makes
    _run_tree_blocked's row-range passes exact).

    `join_cfgs` is the caller's list: what the join rungs learn here
    (flips, resizes) the mega-slab loop keeps if the statement goes back
    to it."""

    kind = "tree"
    stmt_kind = "stmt_fused"

    def __init__(self, ctx, root, caps, scans, ents, scan_inputs, scan_rows,
                 flow_list, flows, aligned_inputs, join_cfgs, walk_joins,
                 akb, max_cap, out_cap_max, anchor_i, scan_layouts,
                 nested_rows, scan_counts):
        self.ctx, self.root, self.key_bounds = ctx, root, akb
        # a plain table's live-row counts as the device vector its entry
        # keeps a version (slabs that zone maps zeroed as 0): no launch
        # uploads them again
        # (`scan_inputs`: a `SlabColumn` a column; `scan_rows`: the
        # anchor's place is None — its liveness comes a slab, `_slab_arg`)
        self.scan_inputs, self.scan_rows = scan_inputs, tuple(
            rows if rows is None or e.alive is not None else e.live_counts(
                frozenset(np.flatnonzero(counts == 0).tolist()))
            for (e, _u), rows, counts in zip(ents, scan_rows, scan_counts))
        self.flow_list, self.aligned_inputs = flow_list, aligned_inputs
        self.join_cfgs, self.walk_joins = join_cfgs, walk_joins
        self.max_cap, self.out_cap_max = max_cap, out_cap_max
        self.anchor_i, self.scan_layouts = anchor_i, scan_layouts
        self.nested_rows = nested_rows
        self.dicts = dict(enumerate(flows.get(id(root), [])))
        a_ent = ents[anchor_i][0]
        self.n_slabs, self.slab_cap = a_ent.n_slabs, a_ent.slab_cap
        self.caps = dict(caps)
        self.caps[id(scans[anchor_i])] = (a_ent.slab_cap, 1)
        # a zero row count IS zone maps' skip signal (_run_device_tree)
        self.anchor_rows = scan_counts[anchor_i]
        self.run_ids = [s for s in range(a_ent.n_slabs)
                        if int(self.anchor_rows[s]) > 0]
        self.lay_sig = ",".join(
            f"{si}/{i}:{l.sig()}"
            for si, slot in enumerate(scan_layouts or ())
            for i, l in slot) if scan_layouts else "-"
        self.geometry = (tuple(_ent_geometry(e) for e, _ in ents), anchor_i)
        self.pod = any(getattr(e, "owners", None) is not None
                       for e, _ in ents)
        self._launch_sig = ""
        # the anchor's raw delta slab runs the same tree as a program of
        # its own anchor shape (its capacity, no layouts)
        self.a_ent = a_ent
        self.anchor_tid = scans[anchor_i].table.id
        self.delta_id = a_ent.base_slabs if a_ent.delta_cap else -1
        self.dprog = None
        if self.delta_id >= 0:
            self.dcaps = dict(self.caps)
            self.dcaps[id(scans[anchor_i])] = (a_ent.delta_cap, 1)
            self.dlayouts = tuple(
                () if si == anchor_i else slot
                for si, slot in enumerate(scan_layouts)) \
                if scan_layouts else None
            if self.dlayouts is not None and not any(self.dlayouts):
                self.dlayouts = None

    def rows(self, pos: int) -> int:
        return int(self.anchor_rows[self.run_ids[pos]])

    def learned(self) -> dict:
        return {"join_cfgs": tuple(self.join_cfgs)}

    def adopt(self, spec: dict) -> None:
        self.join_cfgs[:] = list(spec["join_cfgs"])

    def program(self, gcap: int, pair_cap: int, want_pairs: bool, sig):
        prog, sig = get_pipeline_program(
            self.root, self.caps, gcap, self.join_cfgs, self.key_bounds,
            self.scan_layouts, want_pairs, pair_cap, sig=sig)
        self._launch_sig = _sig_tag("fused", sig)
        if self.delta_id in self.run_ids:
            self.dprog, self.dsig = get_pipeline_program(
                self.root, self.dcaps, gcap, self.join_cfgs,
                self.key_bounds, self.dlayouts, want_pairs, pair_cap)
        return prog, sig, prog.collect_preps(self.flow_list)

    def merge_program(self, prog, gcap: int, sig: str):
        return get_merge_program(self.root, gcap, sig)

    def _joins_in_anchor_space(self) -> set:
        """Joins whose aligned inputs live in the ANCHOR's row space — the
        only ones whose matched/column slabs may be sliced per anchor
        slab: the root's probe chain, plus recursively the build chains
        of its ALIGNED joins (_plan_aligned_joins re-anchored those to
        the fact row space via anchor_subs). An aligned join hanging
        off a non-aligned build subtree keeps its own fact scan's row
        space and passes its inputs through whole."""
        from tidb_tpu.executor import tree_fragment as TF
        spaced: set = set()
        stack = list(TF.aligned_chain(self.root.children[0])[1])
        while stack:
            j = stack.pop()
            spaced.add(id(j))
            if self.join_cfgs[self.walk_joins.index(j)].mode == "aligned":
                bi = 1 if j.build_right else 0
                stack.extend(TF.aligned_chain(j.children[bi])[1])
        return spaced

    def _shared(self, prep_vals, spaced: set):
        """What every slab's body takes: the build sides whole, the
        anchor's place in them left open, the joins sliced by anchor slab
        (`spaced`) likewise."""
        a = self.anchor_i
        si = [None if i == a else _whole_cols(cols)
              for i, cols in enumerate(self.scan_inputs)]
        sr = list(self.scan_rows)
        ai = tuple(((), {}) if len(matched) and id(jn) in spaced
                   else _whole_aligned(matched, jcols)
                   for jn, (matched, jcols) in zip(self.walk_joins,
                                                   self.aligned_inputs))
        return tuple(si), tuple(sr), prep_vals, ai, self.nested_rows

    def _slab_arg(self, of, live, spaced: set):
        """One anchor slab's own — or, for a statement program, the base
        slabs' as one slab's pytree over stacked storage: `of(column)` →
        its columns' arrays, `live` its liveness (mask, or the live
        prefix's length as a device scalar kept on the entry), its part of
        the joins aligned in its row space (None elsewhere)."""
        cols = {i: [of(col)] for i, col in
                self.scan_inputs[self.anchor_i].items()}
        if self.a_ent.alive is not None:
            live = (live,)
        return cols, live, tuple(
            ((of(matched),), {c: (of(sl),) for c, sl in jcols.items()})
            if len(matched) and id(jn) in spaced else None
            for jn, (matched, jcols) in zip(self.walk_joins,
                                            self.aligned_inputs))

    def _one_slab(self, s: int, spaced: set):
        return self._slab_arg(lambda col: col.at(s), self.a_ent.live_arg(s),
                              spaced)

    @staticmethod
    def _assemble(a: int, shared, slab):
        """`_shared` and `_slab_arg` → a tree program's arguments."""
        si, sr, prep_vals, ai, nested = shared
        cols, live, sliced = slab
        si, sr = list(si), list(sr)
        si[a], sr[a] = cols, live
        ai = tuple(w if sl is None else sl for w, sl in zip(ai, sliced))
        return tuple(si), tuple(sr), prep_vals, ai, nested

    def launches(self, prog, prep_vals, to_run=None):
        """→ (position, partial) of the slabs at `to_run` (None = every
        surviving slab), each launched as it is asked for."""
        spaced = self._joins_in_anchor_space()
        shared = self._shared(prep_vals, spaced)
        for pos in (range(len(self.run_ids)) if to_run is None else to_run):
            s = self.run_ids[pos]
            p = self.dprog if s == self.delta_id else prog
            si, sr, pv, ai, nested = self._assemble(
                self.anchor_i, shared, self._one_slab(s, spaced))
            # slot per slab DISPATCH (async queue) — one labeled compute
            # span per fused slab program in the trace
            with self.ctx.device_slot():
                with self.ctx.phases.launch(p.name, slab=s,
                                            sig=self._launch_sig):
                    part = p(si, sr, pv, ai, nested=nested)
            yield pos, part

    @staticmethod
    def _slab_body(prog, a: int, shared, slab):
        si, sr, pv, ai, nested = _TreeSlabs._assemble(a, shared, slab)
        return prog._run(si, sr, pv, ai, None, nested)

    def statement_body(self, prog):
        return functools.partial(self._slab_body, prog, self.anchor_i)

    def statement_args(self, prog, prep_vals):
        from tidb_tpu.executor import device_cache
        spaced = self._joins_in_anchor_space()
        ids, base = self._base_ids(), None
        picks = device_cache.SlabPicks(self.a_ent, ids, self.anchor_tid)
        if ids:
            base = self._slab_arg(picks.of, picks.live(), spaced)
        delta = self._one_slab(self.delta_id, spaced) \
            if self.delta_id in self.run_ids else None
        return self._shared(prep_vals, spaced), base, delta, picks.vectors()

    @staticmethod
    def control(partials) -> dict:
        return {"jus": [p["join_unique"] for p in partials],
                "jts": [p["join_totals"] for p in partials]}

    def _join_flags(self, got):
        """→ (unique_ok, totals), each [surviving slab, join]."""
        n_run, n_joins = len(self.run_ids), len(self.join_cfgs)
        return (np.asarray(got["jus"]).reshape(n_run, n_joins),
                np.asarray(got["jts"]).reshape(n_run, n_joins))

    def overflowed(self, got) -> bool:
        from tidb_tpu.executor import tree_fragment as TF
        jus, jts = self._join_flags(got)
        return any(
            TF.escalate_join(cfg, bool(jus[:, ji].all()),
                             int(jts[:, ji].max()), self.out_cap_max,
                             0)[1] is not None
            for ji, cfg in enumerate(self.join_cfgs))

    def escalate(self, got, ladder):
        from tidb_tpu.executor import tree_fragment as TF
        from tidb_tpu.executor.device_cache import _pow2
        n_run = len(self.run_ids)
        jus, jts = self._join_flags(got)
        retry, rerun = False, set()
        for ji, cfg in enumerate(self.join_cfgs):
            new_cfg, action = TF.escalate_join(
                cfg, bool(jus[:, ji].all()), int(jts[:, ji].max()),
                self.out_cap_max,
                flip_out_cap=_pow2(int(cfg.est * 1.3), lo=1024),
                ladder=ladder)
            if action == "over-max":
                # a join's fan-out exceeds out_cap_max: the caller's
                # mega-slab loop owns the blocked multi-pass escalation
                return None
            if new_cfg is not None:
                self.join_cfgs[ji] = new_cfg
                retry = True
                if action == "flip":
                    # the join's trace changed: every checkpoint is from
                    # the wrong program — full re-run
                    rerun.update(range(n_run))
                else:
                    # exact resize: only slabs whose OWN fan-out
                    # overflowed the old cap re-run
                    rerun.update(s for s in range(n_run)
                                 if int(jts[s, ji]) > cfg.out_cap)
        return retry, rerun


class TpuFragmentExec:
    """Volcano leaf running the fused device program (built by executor
    build(), the builder.go:144 seam)."""

    def __init__(self, plan: PhysTpuFragment):
        from tidb_tpu.executor import OperatorStats
        self.plan = plan
        self.schema = plan.schema.field_types
        self.children: List = []
        self.ctx = None
        self.stats = OperatorStats()
        self.used_device = False
        self.fallback_reason: Optional[str] = None
        self.fallback_code: Optional[str] = None
        self._result: Optional[Chunk] = None
        self._cpu_root = None
        self._offset = 0
        # set by the enclosing fragment's executor on a nested device-rows
        # fragment: the aggregate drivers then return DeviceAggRows
        self._rows_on_device = False

    def open(self, ctx) -> None:
        self.ctx = ctx
        self.stats.opens += 1
        self._result = None
        self._offset = 0
        self.used_device = False
        self.fallback_reason = None
        self.fallback_code = None

    def runtime_info(self) -> str:
        """Surfaced in EXPLAIN ANALYZE (ref: execdetails.go runtime stats)."""
        esc = getattr(self.ctx, "escalation", None)
        esc = f", escalation:{esc.summary()}" if esc is not None and \
            esc.total else ""
        ph = getattr(self.ctx, "phases", None)
        phs = f", phases:{{{ph.summary()}}}" if ph is not None and \
            ph.summary() else ""
        g = getattr(self.ctx, "guard", None)
        qw = (f", queue_wait:{g.queue_wait_s * 1000.0:.1f}ms"
              f"({g.queue_waits})"
              if g is not None and getattr(g, "queue_waits", 0) else "")
        # degraded-pod marker: how many times this statement was moved
        # off a lost/quarantined device before it completed
        mig = (f", migrated:{g.sched_migrated}"
               if g is not None and getattr(g, "sched_migrated", 0)
               else "")
        rf = ""
        if ph is not None and ph.scan_bytes and ph.wall_s > 0.0:
            from tidb_tpu.util import roofline
            frac = roofline.fraction(ph.scan_bytes, ph.wall_s)
            if frac > 0.0:
                rf = f", roofline_fraction:{frac:.3f}"
            if ph.scan_logical_bytes != ph.scan_bytes:
                # compression active: the logical-bytes figure may
                # legitimately exceed 1.0 (that's the win)
                ef = roofline.effective_fraction(ph.scan_logical_bytes,
                                                 ph.wall_s)
                if ef > 0.0:
                    rf += f", effective_roofline_fraction:{ef:.3f}"
        if self.used_device:
            return f"device:yes{esc}{phs}{qw}{mig}{rf}"
        if self.fallback_reason:
            # the parenthesized value is the STABLE taxonomy code — the
            # same string labels tidb_tpu_device_fallbacks_total{reason=}
            return f"device:fallback({self.fallback_code or 'shape'}){esc}"
        return ""

    def next(self) -> Optional[Chunk]:
        if self._cpu_root is not None:
            return self._cpu_root.next()
        if self._result is None:
            strict = _var_bool(self.ctx.vars.get("tidb_tpu_strict", False))
            # checkpoint BEFORE device dispatch: a killed/expired query
            # must not pay for compile + upload it will never use
            self.ctx.check_killed("device-dispatch")
            retried_lost = False
            while True:
                try:
                    import time as _time

                    from tidb_tpu.util.tracing import maybe_span
                    _t0 = _time.perf_counter()
                    with maybe_span(getattr(self.ctx, "tracer", None),
                                    "device.fragment",
                                    root=self.plan.root.name):
                        # mark every table this fragment reads as in
                        # active use for the statement's WHOLE device
                        # run: sibling sessions' evictions (budget, LRU,
                        # invalidation) must never free buffers
                        # mid-compute
                        with self._protect_tables():
                            self._result = self._run_device()
                        self._note_reader()
                    global LAST_PHASES
                    exec_s = _time.perf_counter() - _t0
                    self.used_device = True
                    _ph = getattr(self.ctx, "phases", None)
                    if _ph is not None:
                        _ph.add_wall(exec_s)
                        LAST_PHASES = _ph
                    _tr = getattr(self.ctx, "tracer", None)
                    _esc = getattr(self.ctx, "escalation", None)
                    if _tr is not None and _esc is not None and _esc.total:
                        # TRACE shows what the ladder did to this stmt
                        _tr.event("device.escalation",
                                  summary=_esc.summary())
                    if _tr is not None and _ph is not None and _ph.total:
                        # where the device wall went + how much host
                        # encode hid behind in-flight transfers/compute
                        _tr.event("device.phases",
                                  duration_s=exec_s,
                                  **_ph.as_dict())
                except FragmentFallback as e:
                    # expected ineligibility (shape/feature gate) — quiet
                    self._note_fallback(getattr(e, "reason", "shape"),
                                        str(e))
                    if strict:
                        raise ExecutionError(
                            f"tidb_tpu_strict: device fragment fell "
                            f"back: {self.fallback_reason}") from e
                    return self._fallback_next()
                except DeviceLost as e:
                    # degraded pod: quarantine the lost device (queued
                    # waiters migrate, its cache shard re-homes) and
                    # retry ONCE on a healthy survivor — warned with a
                    # retryable 1105 SHOW WARNINGS row, mirroring
                    # degraded-mesh semantics. A second loss, a pool
                    # that cannot degrade (single slot), or no healthy
                    # survivor surfaces the typed error instead — never
                    # a silent CPU re-run that would hide a dead device.
                    from tidb_tpu.executor import scheduler as _sched
                    tgt = None if retried_lost \
                        else _sched.device_fault(self.ctx, e)
                    if tgt is None:
                        raise
                    log.warning("device lost, retrying statement on "
                                "device %d: %s", tgt, e)
                    retried_lost = True
                    continue
                except (QueryKilledError, QueryTimeout,
                        MemoryQuotaExceeded, CapacityError, ShardFailure):
                    # lifecycle and typed capacity/shard errors unwind
                    # past the fallback ladder: a killed/expired/
                    # over-quota query must die, not retry the same work
                    # on CPU — and a shard fault that already survived
                    # its ladder retry (or an exhausted capacity ladder)
                    # surfaces typed instead of silently re-running the
                    # whole statement on the host
                    raise
                except Exception as e:  # noqa: BLE001
                    # UNEXPECTED device failure: never silent
                    self._note_fallback("device-error",
                                        f"{type(e).__name__}: {e}")
                    log.warning("device fragment failed, falling back "
                                "to CPU: %s",
                                self.fallback_reason, exc_info=True)
                    if strict:
                        raise
                    return self._fallback_next()
                break
            # checkpoint AFTER host fetch, before results flow upward
            from tidb_tpu.util import failpoint
            failpoint.inject("host-fetch")
            self.ctx.check_killed("host-fetch")
        if self._offset >= self._result.num_rows:
            return None
        size = self.ctx.chunk_size
        out = self._result.slice(
            self._offset, min(self._offset + size, self._result.num_rows))
        self._offset += out.num_rows
        return out

    def _note_fallback(self, code: str, detail: str) -> None:
        """Stamp the normalized taxonomy code + free-text detail and move
        the per-reason counter (the coverage table, EXPLAIN ANALYZE, and
        metrics all read the SAME code)."""
        from tidb_tpu.util.observability import REGISTRY
        self.fallback_code = code if code in FALLBACK_REASONS else "shape"
        detail = detail or self.fallback_code
        self.fallback_reason = f"{self.fallback_code}: {detail}" \
            if detail != self.fallback_code else self.fallback_code
        REGISTRY.inc("tidb_tpu_device_fallbacks_total",
                     {"reason": self.fallback_code})

    def _fallback_next(self) -> Optional[Chunk]:
        from tidb_tpu.executor import build
        root = self.plan.root
        if getattr(self.plan, "dist", 0) > 1:
            # distributed plans carry Exchange nodes — pure repartitioning
            # boundaries with no single-node executor; strip them
            root = _strip_exchanges(root)
        self._cpu_root = build(root)
        self._cpu_root.open(self.ctx)
        return self._cpu_root.next()

    def close(self) -> None:
        if self._cpu_root is not None:
            self._cpu_root.close()
            self._cpu_root = None
        self._result = None

    def _protect_tables(self):
        """protect_tables() context over every scan in this fragment —
        per-THREAD registration (device_cache._PROTECT), so concurrent
        statements see each other's in-flight tables as unevictable."""
        from tidb_tpu.executor import device_cache
        from tidb_tpu.executor.tree_fragment import _scans
        store = getattr(self.ctx.snapshot, "store", None)
        return device_cache.protect_tables(
            (id(store), s.table.id) for s in _scans(self.plan.root))

    def _note_reader(self) -> None:
        """This fragment read its tables on the device: a compaction of one
        of them runs it once over the rebuilt generation before the swap
        (delta._warm), so what a re-chosen layout compiles, it compiles
        there."""
        from tidb_tpu.executor import device_cache
        from tidb_tpu.executor.tree_fragment import _scans
        store = getattr(self.ctx.snapshot, "store", None)
        if store is not None and getattr(self.ctx, "txn", None) is None:
            tables = tuple(sorted({s.table.id
                                   for s in _scans(self.plan.root)}))
            sql = getattr(getattr(self.ctx, "guard", None), "sql", None)
            device_cache.note_reader(
                id(store), tables, self.plan, self.ctx.vars,
                (sql or id(self.plan), self.plan.root.name, tables))

    # ---- device pipeline ---------------------------------------------------
    def _run_device(self) -> Chunk:
        from tidb_tpu.executor import device_cache, scheduler
        from tidb_tpu.util import failpoint
        failpoint.inject("device-fragment")
        # pod placement + batch admission turnstile: pins the statement
        # to its pool device BEFORE the first open_table (so every cold
        # byte lands on the right HBM); batch-class statements queue —
        # and may be stolen to an idle sibling — here, before any byte
        # has picked a device
        scheduler.admit_statement(self.ctx)
        # the dispatch boundary of the device fault domain: a raise here
        # models the placed device failing its launch, classified into a
        # typed DeviceLost carrying the device index — next()'s retry
        # loop quarantines it and re-runs ONCE on a survivor
        try:
            failpoint.inject("device-lost-dispatch")
        except DeviceLost:
            raise
        except Exception as e:
            _g = getattr(self.ctx, "guard", None)
            raise DeviceLost(
                f"device launch failed: {e}",
                device=getattr(_g, "device_index", None)) from e

        if getattr(self.plan, "dist", 0) > 1:
            return self._run_device_dist()
        chain = _linearize(self.plan.root)
        if chain is None:
            from tidb_tpu.executor.tree_fragment import has_join, has_window
            if has_join(self.plan.root) or has_window(self.plan.root):
                # joins, and windowed shapes with no linear-chain lowering
                # (interior windows), run as tree programs
                return self._run_device_tree()
            raise FragmentFallback("not a chain", reason="shape")
        # ORDER BY / TopN directly over the agg: strip the order root and
        # run the rest agg-rooted — the ordering becomes the agg's fused
        # device finalize
        order_root = None
        if len(chain) > 1 and isinstance(chain[0], (PhysTopN, PhysSort)):
            k = 1
            while k < len(chain) and _identity_projection(chain[k]):
                k += 1
            if k < len(chain) and isinstance(chain[k], PhysHashAgg):
                order_root, chain = chain[0], chain[k:]
        scan: PhysTableScan = chain[-1]
        vars_ = self.ctx.vars
        max_slab = int(vars_.get("tidb_tpu_max_slab_rows",
                                 DEFAULT_MAX_SLAB_ROWS))
        group_cap = int(vars_.get("tidb_tpu_group_cap", DEFAULT_GROUP_CAP))

        used = _used_column_indices(chain)
        in_types = [scan.schema.field_types[i] for i in used]

        # HBM-resident columnar replica: encoded + uploaded once per table
        # version, reused across queries. First touch STREAMS: open_table
        # returns a per-slab generator the executors drive, so encode of
        # slab k+1 pipelines behind the (async) upload/compute of slab k.
        # the per-slab aggregate driver takes a delta generation as it is
        # (grouping by sorted runs apart: it stacks slabs at one shape);
        # order and filter roots assume live prefixes and uniform slabs
        delta_ok = isinstance(chain[0], PhysHashAgg)
        while True:
            with timeline.span("frag.open", "frag"):
                ent, stream = device_cache.open_table(
                    self.ctx, scan, used, max_slab, phases=self.ctx.phases,
                    prune=True, delta_ok=delta_ok)
            if delta_ok and ent.is_delta and grouping_mode(
                    _agg_key_bounds(chain, ent)) == RUNS:
                delta_ok = False
                continue
            break
        if ent.total == 0:
            raise FragmentFallback("empty input", reason="empty-input")
        dicts = {i: ent.dicts.get(i) for i in used}
        slab_cap, n_slabs = ent.slab_cap, ent.n_slabs

        # zone-map slab pruning: the scan's conjuncts evaluated host-side
        # against per-slab stats (over dict codes / encoded ints, no
        # decode). A pruned slab costs NOTHING downstream: the cold
        # stream already skipped its encode+upload, and slab_ids keeps it
        # out of every program launch and escalation checkpoint.
        from tidb_tpu.executor import zonemap
        skip = zonemap.prune_slabs(ent, scan)
        slab_ids = [s for s in range(n_slabs) if s not in skip]
        if skip:
            zonemap.note_skipped(self.ctx.phases, len(skip))

        root = chain[0]
        # multi-slab Sort: each slab sorts on device; the host performs the
        # k-way run merge in _execute_order via rank-key lexsort (numpy's
        # stable sort is a merge sort — presorted runs merge cheaply), the
        # disk-spill multiWayMerge analog of executor/sort.go:56-58
        if n_slabs > 1 and isinstance(root, PhysWindow):
            # window partitions span slabs: per-slab partials can't merge;
            # run the chain as ONE mega-slab program (slabs concatenate
            # inside the trace). DISTINCT aggs no longer take this path —
            # per-slab distinct-pair sets merge on host (_distinct_pairs +
            # _merge_distinct_states), keeping compiles per-slab-sized.
            if stream is not None:
                for _ in stream:    # commit the upload; the tree path
                    pass            # re-opens the table warm
            return self._run_device_tree()

        if not slab_ids:
            # every slab pruned: ZERO launches. Drain the stream so the
            # skip accounting + hole placeholders still commit, then
            # synthesize the result the device would have produced: an
            # aggregate's is the driver's to give, order/filter roots →
            # empty.
            if stream is not None:
                for _ in stream:
                    pass
            if not isinstance(root, PhysHashAgg):
                from tidb_tpu.executor import _empty_chunk
                return _empty_chunk(self.schema)

        layouts = _ent_layouts(ent, used)
        if isinstance(root, PhysHashAgg):
            from tidb_tpu.util.escalation import CapacityLadder
            # stats-informed grouping: small known key domains skip the
            # sort (open_table commits dictionaries/bounds EAGERLY — before
            # the stream runs — exactly so program construction can use
            # them here)
            key_bounds = _agg_key_bounds(chain, ent)
            return self._run_agg_slabs(
                _ChainSlabs(self, chain, ent, stream, used, in_types, dicts,
                            key_bounds, layouts, slab_ids),
                _initial_group_cap(root, group_cap, slab_cap, key_bounds),
                order_root,
                CapacityLadder(guard=getattr(self.ctx, "guard", None),
                               stats=self.ctx.escalation))
        # order/filter roots have no group capacity to overflow — one pass
        if isinstance(root, (PhysTopN, PhysSort)):
            prog = get_program(chain, used, in_types, slab_cap, group_cap,
                               layouts=layouts)
            prep_vals = prog.collect_preps(dicts)
            return self._execute_order(prog, root, ent, dicts, prep_vals,
                                       stream, slab_ids=slab_ids)
        # filter roots: lift comparison literals into prepared parameters
        # so `k = 17` and `k = 42` share one compiled program — and, when
        # several such statements are queued at once, ONE batched launch
        # (executor/microbatch.py). Falls back to the literal-baked
        # program when nothing is parametrizable.
        mb_max = int(vars_.get("tidb_tpu_microbatch_max", 16) or 0)
        chain_p = _parametrize_chain(chain) if mb_max >= 1 else None
        if chain_p is not None:
            sig = _chain_signature(chain_p, used, in_types, slab_cap,
                                   group_cap, None, layouts) \
                + "|pairs=False,0"
            prog = get_program(chain_p, used, in_types, slab_cap,
                               group_cap, layouts=layouts, sig=sig)
            # prep values MUST come from THIS statement's chain: the
            # cached program may hold another statement's ParamExpr nodes
            prep_vals = collect_chain_preps(chain_p, dicts)
            if mb_max >= 2 and stream is None:
                from tidb_tpu.executor import microbatch
                res = microbatch.execute(self, prog, root, ent, dicts,
                                         prep_vals, slab_ids, sig, mb_max)
                if res is not None:
                    return res
        else:
            prog = get_program(chain, used, in_types, slab_cap, group_cap,
                               layouts=layouts)
            prep_vals = prog.collect_preps(dicts)
        return self._execute_filter(prog, root, ent, dicts, prep_vals,
                                    stream, slab_ids=slab_ids)

    def _runs_finalize(self, root, order_root, partials, n_slabs: int,
                       cap: int, key_bounds, base_sig: str, sorted_rows):
        """Grouping by sorted runs, after the slabs' `group_rows`
        partials: sort every slab's rows ONCE (the shared sort program;
        `sorted_rows` from an earlier round of the capacity ladder is
        reused, the ladder only resizes the finalize) and reduce the runs.
        → (out as a merge or fused finalize gives it, sorted_rows)."""
        from tidb_tpu.ops.jax_env import jnp
        ph = self.ctx.phases
        p0 = partials[0]
        n = int(p0["live"].shape[0])
        with timeline.span("frag.merge", "frag", slots_in=0,
                           slots_out=int(cap), rows=n * n_slabs):
            if sorted_rows is None:
                # a slab that zone maps pruned has no partial: it rides
                # as dead rows, so the sort's shape (and executable) does
                # not depend on what was pruned
                pad = [dict(p0, live=jnp.zeros(n, dtype=bool))] * \
                    (n_slabs - len(partials))
                parts = list(partials) + pad
                sig = (f"sortrows|{n_slabs}x{n}|"
                       f"w={[str(w.dtype) for w in p0['words']]}|"
                       f"p={[str(a.dtype) for a in p0['payloads']]}")
                sp = _get_or_build(sig, "fused",
                                   lambda: _SortRowsProgram(sig))
                with self.ctx.device_slot():
                    with ph.launch(sp.name):
                        sorted_rows = sp.run(
                            [[p["words"][i] for p in parts]
                             for i in range(len(p0["words"]))],
                            [[p["payloads"][i] for p in parts]
                             for i in range(len(p0["payloads"]))],
                            [p["live"] for p in parts])
                ph.note_launch()
            fsig = ("runsfinal|" + (_order_sig(order_root)
                                    if order_root is not None else "-")
                    + f"|cap={cap}|" + base_sig + widths_sig(key_bounds))
            fp = _get_or_build(fsig, "finalize", lambda: _RunsFinalizeProgram(
                root, order_root, cap, key_bounds, fsig))
            with self.ctx.device_slot():
                with ph.launch(fp.name, sig=_sig_tag("fused-final", fsig)):
                    out = fp.run(sorted_rows)
            ph.note_launch()
        return out, sorted_rows

    def _run_nested(self, frag: PhysTpuFragment) -> DeviceAggRows:
        """Run a nested device-rows fragment to its merged groups, which
        stay in HBM. It is a fragment like any other — own signature,
        specialization entry, capacity ladder, launches in this
        statement's ledger — except that nothing is fetched but the
        counts its ladder validates."""
        sub = TpuFragmentExec(frag)
        sub.open(self.ctx)
        sub._rows_on_device = True
        with timeline.span("device.fragment", "frag", root=frag.root.name,
                           rows="device"):
            with sub._protect_tables():
                rows = sub._run_device()
        if not isinstance(rows, DeviceAggRows):
            # a path that answers from the host (every slab pruned, the
            # mega-slab loop): the enclosing tree cannot take host rows
            raise FragmentFallback("nested fragment left the device",
                                   reason="shape")
        return rows

    def _run_tree_plain(self) -> Chunk:
        """The tree again over plain tables: what only the mega-slab loop
        or the sorted-runs grouping can run gets rebuilds of the delta
        generations it was given (declines of gate `consumer`)."""
        self._plain_tables = True
        try:
            return self._run_device_tree()
        finally:
            self._plain_tables = False

    # ---- join-tree / mega-slab device pipeline -----------------------------
    def _run_device_tree(self) -> Chunk:
        """Q3/Q5-shaped join trees (and multi-slab chains the per-slab
        partial/merge path can't serve: DISTINCT aggs, windows) as ONE
        jitted program (tree_fragment). Multi-slab tables concatenate
        inside the program; join modes adapt at runtime (a lost uniqueness
        bet or an expansion-capacity overflow re-traces exactly once, never
        falls back to CPU)."""
        from tidb_tpu.executor import device_cache, device_emit
        from tidb_tpu.executor import tree_fragment as TF
        from tidb_tpu.executor.device_cache import _pow2
        from tidb_tpu.ops.jax_env import jax

        root = self.plan.root
        # ORDER BY / TopN over the agg runs as the agg's fused device
        # finalize (a host re-order on the mega-slab path): everything
        # below — flows, signatures, key bounds — stays agg-rooted
        order_root, root = _strip_order_root(root)
        vars_ = self.ctx.vars
        max_slab = int(vars_.get("tidb_tpu_max_slab_rows",
                                 DEFAULT_MAX_SLAB_ROWS))
        group_cap = int(vars_.get("tidb_tpu_group_cap", DEFAULT_GROUP_CAP))

        scans = TF._scans(root)
        # the fused per-slab pipeline (an aggregate over a join tree whose
        # probe chain ends in a scan) takes delta generations as they are;
        # the mega-slab loop below assumes live prefixes and uniform slabs
        is_agg = isinstance(root, PhysHashAgg)
        anchor = TF.aligned_chain(root.children[0])[0] if is_agg else None
        anchor_i = next((i for i, s in enumerate(scans) if s is anchor),
                        None)
        delta_ok = is_agg and anchor_i is not None and _var_bool(
            vars_.get("tidb_tpu_fused_pipeline", "on")) and \
            not getattr(self, "_plain_tables", False)
        ents = []
        # every scan of THIS statement is already protected from sibling
        # evictions for the whole device run: next() wrapped _run_device
        # in _protect_tables(), which registers the (store, table) pairs
        # per-THREAD in device_cache — the budget eviction a sibling
        # scan's streamed upload triggers skips them
        for scan in scans:
            used = scan.used_columns if scan.used_columns else \
                list(range(len(scan.schema)))
            with timeline.span("frag.open", "frag"):
                ent = device_cache.get_table(self.ctx, scan, used,
                                             max_slab,
                                             phases=self.ctx.phases,
                                             delta_ok=delta_ok)
            if ent.total == 0:
                raise FragmentFallback("empty input", reason="empty-input")
            ents.append((ent, used))
        caps = {id(s): ((e.slab_cap, e.base_slabs, e.delta_cap)
                        if e.delta_cap else (e.slab_cap, e.n_slabs))
                for s, (e, _) in zip(scans, ents)}
        # nested device-rows fragments (aggregates that are a join's build
        # side) run FIRST, as fragments of their own whose merged groups
        # stay in HBM; the programs below take them as inputs, shaped by
        # the capacity each settled on
        nested_rows, nested_bounds = [], {}
        for nf in TF.nested_fragments(root):
            rows = self._run_nested(nf)
            caps[id(nf)] = (rows.cap, 1)
            nested_bounds[id(nf)] = rows.bounds
            nested_rows.append(rows.inputs())
        nested_rows = tuple(nested_rows)
        # per-scan-slot ((col, ColLayout), ...) for compressed columns —
        # parallel to TF._scans(root) order, which matches the `scans`
        # walk order here (both left-to-right DFS)
        scan_layouts = tuple(
            tuple(sorted(((i, e.layouts[i]) for i in u
                          if e.layouts.get(i) is not None),
                         key=lambda t: t[0]))
            for e, u in ents)
        if not any(scan_layouts):
            scan_layouts = None
        scan_dicts = {id(s): {i: e.dicts.get(i) for i in u}
                      for s, (e, u) in zip(scans, ents)}
        scan_bounds = {id(s): e.bounds for s, (e, _) in zip(scans, ents)}
        scan_bounds.update(nested_bounds)
        flows, root_dicts = TF.dictionary_flows(root, scan_dicts)
        # (the columns themselves: what reads a table whole lists their
        # slabs where it launches, `_whole_cols`)
        scan_inputs = tuple({i: e.dev[i] for i in u} for e, u in ents)
        scan_counts = tuple(
            np.array([e.slab_rows(s) for s in range(e.n_slabs)],
                     dtype=np.int32) for e, _ in ents)
        # zone-map slab pruning, tree flavor: scan_rows is a RUNTIME
        # input (the per-slab live mask reads it), so zeroing a pruned
        # slab's row count removes its rows with NO signature change —
        # the mega-slab program stays byte-identical while pruned rows
        # never enter filters/joins/aggs. The fused per-slab driver
        # reads the zeroed counts and skips those slabs' launches
        # entirely.
        from tidb_tpu.executor import zonemap
        n_zeroed = 0
        for sc, (e, _u), rows in zip(scans, ents, scan_counts):
            for s in zonemap.prune_slabs(e, sc):
                rows[s] = 0
                n_zeroed += 1
        if n_zeroed:
            zonemap.note_skipped(self.ctx.phases, n_zeroed)
        # a delta generation's liveness is a mask a slab; a plain table's
        # the counts themselves
        def scan_rows_but(anchor=None):
            # (the anchor of the per-slab pipeline reads its own a slab)
            return tuple(
                None if i == anchor else counts if e.alive is None
                # (the masks are read where they lie, pruned slabs' too:
                # no row of one passes the scan's own predicate)
                else _whole_masks(e.alive)
                for i, ((e, _u), counts) in enumerate(
                    zip(ents, scan_counts)))
        max_cap = max(e.slab_cap * e.n_slabs for e, _ in ents)

        flow_list = [flows.get(id(n), []) for n in TF._walk_nodes(root)]
        join_cfgs = TF.plan_join_configs(root, scan_bounds)
        # FK-aligned joins: verified-unique PK-FK joins run as pure streams
        # over cached fact-rowspace build columns (no per-query gathers)
        aligned_info = _plan_aligned_joins(self.ctx, root, scans, ents)
        walk_joins = TF._walk_joins(root)
        aligned_inputs = []
        for ji, jn in enumerate(walk_joins):
            info = aligned_info.get(id(jn))
            if info is None:
                aligned_inputs.append(((), {}))
                continue
            join_cfgs[ji] = TF.JoinCfg(
                "aligned", aligned_cols=tuple(sorted(info["cols"])))
            aligned_inputs.append((info["entry"].matched, info["cols"]))
        aligned_inputs = tuple(aligned_inputs)
        akb = TF.tree_agg_key_bounds(root, scan_bounds, DOMAIN_CAP) \
            if is_agg else None
        if delta_ok and grouping_mode(akb) == RUNS and \
                any(e.is_delta for e, _ in ents):
            # sorted runs stack every slab's rows at one shape
            return self._run_tree_plain()
        gcap = _initial_group_cap(root, group_cap, max_cap, akb) \
            if is_agg else 1
        from tidb_tpu.executor.tree_fragment import JOIN_OUT_CAP
        from tidb_tpu.util.escalation import CapacityLadder
        out_cap_max = int(vars_.get("tidb_tpu_join_out_cap", JOIN_OUT_CAP))
        ladder = CapacityLadder(guard=getattr(self.ctx, "guard", None),
                                stats=self.ctx.escalation)
        # every device_get is a host↔device round trip — batch fetches
        ph = self.ctx.phases
        # ---- fused per-slab pipeline -----------------------------------
        # Agg-rooted trees (the Q3/Q5 shape) run scan → filter → project →
        # join-probe → partial-agg as ONE program PER PROBE SLAB plus one
        # root merge/finalize, instead of one mega-slab program:
        # intermediates stay in registers/HBM and warm launches drop to
        # slabs + 1. DISTINCT aggs fuse too; multi-arg DISTINCT
        # (COUNT-only) dedups on a combined dense code in-slab and ships
        # the raw argument columns in the pairs.
        if is_agg and anchor_i is not None and _var_bool(
                vars_.get("tidb_tpu_fused_pipeline", "on")):
            res = self._run_agg_slabs(
                _TreeSlabs(self.ctx, root, caps, scans, ents,
                           scan_inputs, scan_rows_but(anchor_i), flow_list,
                           flows,
                           aligned_inputs, join_cfgs, walk_joins, akb,
                           max_cap, out_cap_max, anchor_i, scan_layouts,
                           nested_rows, scan_counts),
                gcap, order_root, ladder)
            if res is not None:
                return res
            # a join's fan-out exceeded out_cap_max inside the slab
            # driver: fall through to the mega-slab loop, whose own
            # over-max rung escalates to blocked multi-pass execution
            # (learned flips/resizes persist in join_cfgs)
            if any(e.is_delta for e, _ in ents):
                return self._run_tree_plain()
        # the mega-slab program reads every table whole
        scan_inputs = tuple(_whole_cols(cols) for cols in scan_inputs)
        scan_rows = scan_rows_but()
        aligned_inputs = tuple(_whole_aligned(m, jc)
                               for m, jc in aligned_inputs)
        while True:
            prog = get_tree_program(root, caps, gcap, join_cfgs, akb,
                                    scan_layouts)
            prep_vals = prog.collect_preps(flow_list)
            # scheduler slot spans DISPATCH only (jax queues the program
            # asynchronously); the blocking fetches below run outside it,
            # so a sibling statement's encode/dispatch overlaps this
            # one's device execution
            with self.ctx.device_slot():
                with ph.launch(prog.name):
                    out = prog(scan_inputs, scan_rows, prep_vals,
                               aligned_inputs, nested=nested_rows)
            ph.note_launch()
            if is_agg:
                _count_agg_partial(_note_grouping(root, akb, gcap))
            fetch = {"ju": out["join_unique"], "jt": out["join_totals"]}
            host = None
            if is_agg:
                fetch["ng"] = out["n_groups"]
                _piggyback_agg(fetch, out, gcap)
            elif isinstance(root, (PhysTopN, PhysSort, PhysLimit)):
                fetch["no"] = out["n_out"]
                if isinstance(root, (PhysTopN, PhysLimit)) and \
                        out["cols"] and \
                        out["cols"][0][0].shape[0] <= SMALL_GROUP_CAP:
                    # the device result is ALREADY truncated to
                    # min(count+offset, rows) (ops/factorize.topn): when
                    # that static shape is small it rides the flag fetch
                    # — no second trip, even for huge LIMITs over small
                    # inputs
                    fetch["cols"] = list(out["cols"])
            else:
                # padded cols + live + flags all come in ONE bulk fetch
                with ph.phase("fetch"):
                    host = jax.device_get(out)
                ph.add_d2h(tree_nbytes(host))
                fetch = {"ju": host["join_unique"],
                         "jt": host["join_totals"]}
            if host is None:
                with ph.phase("fetch"):
                    flags = jax.device_get(fetch)
                ph.add_d2h(tree_nbytes(flags))
            else:
                flags = fetch
            retry = False
            for ji, cfg in enumerate(join_cfgs):
                uq = bool(np.asarray(flags["ju"])[ji])
                tot = int(np.asarray(flags["jt"])[ji])
                new_cfg, action = TF.escalate_join(
                    cfg, uq, tot, out_cap_max,
                    flip_out_cap=_pow2(int(cfg.est * 1.3), lo=1024),
                    ladder=ladder)
                if action == "over-max" and nested_rows:
                    raise FragmentFallback(
                        "blocked expand over a nested fragment",
                        reason="blocked-expand")
                if action == "over-max":
                    # runaway fan-out (many-to-many on a skewed key):
                    # too large to materialize in one batch — run the
                    # tree in K row-range passes over the probe anchor
                    # and merge root agg states host-side (the grace-
                    # hash partitioning analog, executor/hash_table.go
                    # grace partitions / radix-hashjoin design doc)
                    return self._run_tree_blocked(
                        root, caps, join_cfgs, ji, walk_joins, akb,
                        gcap, max_cap, scans, ents, scan_inputs,
                        scan_rows, flow_list, aligned_inputs, flows,
                        tot, scan_layouts)
                if new_cfg is not None:
                    join_cfgs[ji] = new_cfg
                    retry = True
            if is_agg and grouping_mode(akb) != SLOTS and \
                    int(flags["ng"]) > gcap:
                if gcap >= max_cap:
                    ladder.fallback("group")
                    raise FragmentFallback("group cap overflow", reason="group-cap")
                # factorize reported the TRUE distinct count: resize to
                # exact need in one recompile instead of blind doubling
                gcap = ladder.resize("group", gcap, need=int(flags["ng"]),
                                     max_cap=max_cap)
                retry = True
            if retry:
                # budget + guard checkpoint between recompiles: a KILL or
                # deadline lands here, and a recompile-storm exhausts into
                # a typed error instead of looping
                ladder.attempt("tree")
                continue
            break

        dicts_root = {i: d for i, d in enumerate(root_dicts)}
        if is_agg:
            n_final = int(flags["ng"])
            if root.group_exprs and n_final == 0:
                from tidb_tpu.executor import _empty_chunk
                return _empty_chunk(self.schema)
            inp_dicts = {i: d for i, d in
                         enumerate(flows.get(id(root), []))}
            host_tree = (flags["keys"], flags["states"]) \
                if "keys" in flags else None
            chunk = self._agg_chunk(root, out, inp_dicts, max(n_final, 1),
                                    host_tree=host_tree)
            if order_root is not None:
                # mega-slab fallback: the (small) final group rows
                # re-order on host; the fused per-slab path orders them
                # on device inside the finalize launch instead
                chunk = _host_order(chunk, order_root, root.schema)
                chunk = _topn_slice(chunk, order_root)
            return chunk
        if isinstance(root, (PhysTopN, PhysSort, PhysLimit)):
            n_out = int(flags["no"])
            if "cols" in flags:
                host_cols = [(np.asarray(v)[:n_out], np.asarray(m)[:n_out])
                             for v, m in flags["cols"]]
            else:
                dev_cols = [(v[:n_out], m[:n_out]) for v, m in out["cols"]]
                with ph.phase("fetch"):
                    host_cols = jax.device_get(dev_cols)
                ph.add_d2h(tree_nbytes(host_cols))
            cols = [_decode_col(ft, np.asarray(v), np.asarray(m),
                                dicts_root.get(ci))
                    for ci, ((v, m), ft) in
                    enumerate(zip(host_cols, root.schema.field_types))]
            return _topn_slice(Chunk(cols), root)
        # join/selection/projection/window root: compact by live on host
        return _compact_decode(host["cols"], host["live"],
                               root.schema.field_types, dicts_root)

    def _run_tree_blocked(self, root, caps, join_cfgs, bji, walk_joins,
                          akb, gcap, max_cap, scans, ents, scan_inputs,
                          scan_rows, flow_list, aligned_inputs, flows,
                          est_total, scan_layouts=None) -> Chunk:
        """Blocked (multi-pass) expand: a many-to-many join whose fan-out
        exceeds JOIN_OUT_CAP runs as K row-range passes over its probe
        anchor scan, each pass expanding at most JOIN_OUT_CAP rows on
        device; the root agg's partial states merge host-side. The device
        path never falls back to CPU on skew.

        Ref: grace-hash partitioning (executor/hash_table.go, docs/design/
        2018-09-21-radix-hashjoin.md) — partitioning by probe row ranges
        instead of key radix because ranges keep every other operator in
        the fused program untouched."""
        import math
        from dataclasses import replace as d_replace

        from tidb_tpu.executor import tree_fragment as TF
        from tidb_tpu.executor.device_cache import _pow2
        from tidb_tpu.ops.jax_env import jax

        JOIN_OUT_CAP = int(self.ctx.vars.get("tidb_tpu_join_out_cap",
                                             TF.JOIN_OUT_CAP))
        if not isinstance(root, PhysHashAgg):
            raise FragmentFallback(
                f"join fan-out {est_total} exceeds device cap "
                f"(non-agg root)", reason="join-cap")
        if any(d.distinct for d in root.aggs):
            raise FragmentFallback("blocked expand: DISTINCT aggs", reason="blocked-expand")
        if any(d.ftype.is_wide_decimal or
               any(a.ftype.is_wide_decimal for a in d.args)
               for d in root.aggs):
            raise FragmentFallback("blocked expand: wide-decimal aggs", reason="blocked-expand")
        bjoin = walk_joins[bji]
        # the blocked join must be reachable from the root agg via PROBE
        # sides only: each pass joins a slice of the probe rows against
        # FULL build sides, so the pass union is exactly the full result —
        # but if any ancestor held the blocked join in its BUILD subtree,
        # that ancestor would see a partial build side per pass
        # (double-counting semi matches, K-times-emitting anti rows)

        def probe_path_ok(node) -> bool:
            if node is bjoin:
                return True
            if isinstance(node, PhysHashJoin):
                return probe_path_ok(
                    node.children[0 if node.build_right else 1])
            if node.children:
                return probe_path_ok(node.children[0])
            return False

        if not probe_path_ok(root):
            raise FragmentFallback(
                "blocked expand: overflowing join is inside an ancestor's "
                "build subtree", reason="blocked-expand")
        bi = 1 if bjoin.build_right else 0
        anchor, crossed = TF.aligned_chain(bjoin.children[1 - bi])
        if anchor is None:
            raise FragmentFallback("blocked expand: no probe anchor", reason="blocked-expand")
        for j in crossed:
            jcfg = join_cfgs[walk_joins.index(j)]
            if not (jcfg.mode == "aligned" or j.kind in ("semi", "anti")):
                raise FragmentFallback(
                    "blocked expand: probe chain crosses a join that may "
                    "not preserve the row space", reason="blocked-expand")
        anchor_ent = next(e for s, (e, _) in zip(scans, ents)
                          if s is anchor)
        total_cap = anchor_ent.slab_cap * anchor_ent.n_slabs
        join_cfgs = list(join_cfgs)
        join_cfgs[bji] = d_replace(join_cfgs[bji], blocked=True,
                                   out_cap=JOIN_OUT_CAP)

        K = max(2, math.ceil(est_total * 1.2 / JOIN_OUT_CAP))
        while K <= 128:
            prog = get_tree_program(root, caps, gcap, join_cfgs, akb,
                                    scan_layouts)
            prep_vals = prog.collect_preps(flow_list)
            step = (total_cap + K - 1) // K
            pass_outs = []
            overflow = False
            restart = False
            for k in range(K):
                rng = (np.int32(k * step),
                       np.int32(min((k + 1) * step, total_cap)))
                with self.ctx.device_slot():
                    with self.ctx.phases.launch(prog.name, slab=k):
                        out = prog(scan_inputs, scan_rows, prep_vals,
                                   aligned_inputs, rng)
                self.ctx.phases.note_launch()
                _count_agg_partial(_note_grouping(root, akb, gcap))
                # flags first: a restart/overflow pass never transfers its
                # (discarded) group arrays, and good passes transfer only
                # ng live slots instead of the full gcap padding
                got = self.ctx.phases.fetch({
                    "ju": out["join_unique"], "jt": out["join_totals"],
                    "ng": out["n_groups"]})
                for ji, cfg in enumerate(join_cfgs):
                    uq = bool(np.asarray(got["ju"])[ji])
                    tot = int(np.asarray(got["jt"])[ji])
                    if cfg.mode == "unique" and not uq:
                        join_cfgs[ji] = d_replace(
                            cfg, mode="expand",
                            out_cap=_pow2(int(cfg.est * 1.3), lo=1024))
                        restart = True
                    elif cfg.mode == "expand" and tot > cfg.out_cap:
                        if tot > JOIN_OUT_CAP or cfg.blocked:
                            overflow = True      # split finer
                        else:
                            join_cfgs[ji] = d_replace(cfg,
                                                      out_cap=_pow2(tot))
                            restart = True
                if grouping_mode(akb) != SLOTS and int(got["ng"]) > gcap:
                    if gcap >= max_cap:
                        raise FragmentFallback("group cap overflow", reason="group-cap")
                    gcap = min(gcap * 4, max_cap)
                    restart = True
                if overflow or restart:
                    break
                ng = int(np.asarray(got["ng"]))
                got.update(self.ctx.phases.fetch({
                    "keys": [(v[:ng], m[:ng]) for v, m in out["keys"]],
                    "states": [tuple(a[:ng] for a in st)
                               for st in out["states"]]}))
                pass_outs.append(got)
            if restart:
                continue
            if overflow:
                K *= 2
                continue
            inp_dicts = {i: d for i, d in
                         enumerate(flows.get(id(root), []))}
            return self._merge_tree_agg_passes(root, pass_outs, inp_dicts)
        raise FragmentFallback("blocked expand: skew beyond 128 passes", reason="blocked-expand")

    def _merge_tree_agg_passes(self, root: PhysHashAgg, pass_outs,
                               inp_dicts) -> Chunk:
        """Host-side cross-pass group merge: concatenate each pass's live
        (key, state) slots, re-group by key tuple, AggFunc.merge with
        xp=numpy (update=merge symmetry — the same segment op either
        way)."""
        aggs = [build_agg(d) for d in root.aggs]
        n_keys = len(root.group_exprs)
        if n_keys and getattr(root, "rollup", False):
            n_keys += 1     # device partials carry a grouping-level column
        key_parts: List[List] = [[] for _ in range(n_keys)]
        state_parts: List[List] = [[] for _ in aggs]
        for got in pass_outs:
            ng = int(np.asarray(got["ng"]))
            if ng == 0:
                continue
            for kc in range(n_keys):
                v, m = got["keys"][kc]
                key_parts[kc].append((np.asarray(v)[:ng],
                                      np.asarray(m)[:ng]))
            for ai, st in enumerate(got["states"]):
                state_parts[ai].append(
                    tuple(np.asarray(a)[:ng] for a in st))
        if n_keys and not key_parts[0]:
            from tidb_tpu.executor import _empty_chunk
            return _empty_chunk(self.schema)
        key_cols = [(np.concatenate([v for v, _ in parts]),
                     np.concatenate([m for _, m in parts]))
                    for parts in key_parts]
        if n_keys:
            n_rows = key_cols[0][0].shape[0]
            # vectorized cross-pass group index (NULLs group together) —
            # the same sort-based factorize the CPU hash agg uses
            from tidb_tpu.executor.hash_agg import factorize_columns
            gids, n_final, rep = factorize_columns(key_cols)
        else:
            # global agg: every pass contributes exactly one state row
            n_rows = sum(p[0].shape[0] for p in state_parts[0]) \
                if state_parts and state_parts[0] else 0
            gids = np.zeros(n_rows, dtype=np.int64)
            n_final = 1
        merged_states = []
        for agg, parts in zip(aggs, state_parts):
            if parts:
                partial = tuple(
                    np.concatenate([p[c] for p in parts], axis=0)
                    for c in range(len(parts[0])))
            else:
                partial = agg.init(np, 0)
            st = agg.init(np, n_final)
            merged_states.append(
                agg.merge(np, st, gids, n_final, partial))
        # representative key row per group (factorize's first occurrence)
        keys_out = []
        if n_keys:
            for kc in range(n_keys):
                v, m = key_cols[kc]
                keys_out.append((v[rep], m[rep]))
        out = {"keys": keys_out, "states": merged_states}
        return self._agg_chunk(root, out, inp_dicts, max(n_final, 1))

    # ---- distributed (multi-shard) pipeline --------------------------------
    @staticmethod
    def _staged_dist_chain(root) -> Optional[List[PhysicalPlan]]:
        """Root→scan chain when this dist fragment is eligible for the
        staged checkpointable path: an agg root over an exchange-free
        Scan/Selection/Projection chain (a PhysExchange anywhere breaks
        _linearize), no DISTINCT aggs (per-rank dedup cannot merge
        without key co-location), and every stage device-capable for the
        single-device chain program."""
        if not isinstance(root, PhysHashAgg):
            return None
        if any(d.distinct and d.args for d in root.aggs):
            return None
        chain = _linearize(root)
        if chain is None or not _fragment_ok(root, 0):
            return None
        return chain

    def _run_dist_agg_staged(self, root, mesh, host_cols,
                             scan_meta) -> Optional[Chunk]:
        """Staged checkpointable dist agg (dist_fragment.StagedDistAgg):
        per-rank partials → host checkpoints → host merge. Returns None
        when the fragment is not eligible — the caller falls through to
        the monolithic shard_map program."""
        chain = self._staged_dist_chain(root)
        if chain is None or len(scan_meta) != 1:
            return None
        from tidb_tpu.executor import tree_fragment as TF
        from tidb_tpu.executor.device_cache import _pow2
        from tidb_tpu.executor.dist_fragment import StagedDistAgg
        from tidb_tpu.util.escalation import CapacityLadder
        scan, used_enc, total = scan_meta[0]
        used_cols = _used_column_indices(chain)
        if not set(used_cols) <= set(used_enc):
            return None
        nd = mesh.devices.size
        cap = _pow2((total + nd - 1) // nd, lo=8)
        # per-column compressed layouts, chosen GLOBALLY (one layout must
        # serve every rank's slab — the per-rank chain partials share one
        # traced program). Each rank packs its own slab independently, so
        # no cap/word-alignment constraint applies here; dictionaries
        # would need per-device replication, so allow_dict=False.
        from tidb_tpu.chunk import compress as _compress
        comp_on = _var_bool(self.ctx.vars.get("tidb_tpu_compression", "on"))
        layouts = {}
        if comp_on:
            for i in used_cols:
                vals, valid, _d = host_cols[(id(scan), i)]
                if vals.ndim != 1:
                    continue
                lay, _dv = _compress.choose_layout(vals, valid,
                                                   allow_dict=False)
                if lay is not None and lay.width > 0:
                    layouts[i] = lay
        dicts = {i: host_cols[(id(scan), i)][2] for i in used_cols}
        # rank-level zone maps: the per-rank slice is this path's
        # dispatch unit, so stats are built per rank (slab_cap=cap) and
        # the scan's conjuncts evaluate exactly as on the slab path. A
        # pruned rank packs nothing, uploads nothing and runs nothing —
        # its checkpoint is the ng=0 merge identity.
        skip_ranks: frozenset = frozenset()
        if comp_on and getattr(scan, "filters", None):
            from tidb_tpu.executor import zonemap
            zmaps = {}
            for i in used_cols:
                vals, valid, _d = host_cols[(id(scan), i)]
                if vals.ndim != 1:
                    continue
                kind = "code" if _d is not None else \
                    ("float" if vals.dtype.kind == "f" else "num")
                zmaps[i] = zonemap.column_stats(vals, valid, cap, total,
                                                kind=kind)
            shim = _RankZoneEnt(nd, zmaps, dicts)
            skip_ranks = zonemap.prune_slabs(shim, scan)
            if skip_ranks:
                zonemap.note_skipped(self.ctx.phases, len(skip_ranks))
                phys_b = logi_b = 0
                for i in used_cols:
                    vals, valid, _d = host_cols[(id(scan), i)]
                    lay = layouts.get(i)
                    if lay is not None:
                        phys_b += _compress.packed_slab_bytes(lay, cap)
                        logi_b += _compress.raw_slab_bytes(lay, cap)
                    else:
                        b = cap * vals.dtype.itemsize + cap
                        phys_b += b
                        logi_b += b
                zonemap.note_h2d_skipped(self.ctx.phases,
                                         phys_b * len(skip_ranks))
                self.ctx.phases.add_scan(
                    0, logical=logi_b * len(skip_ranks))
        # per-rank host slices — the checkpoint story's source of truth:
        # a retry or re-dispatch re-uploads ONLY its rank's slice
        # (pruned ranks hold None: never packed, never touched)
        rank_cols = []
        for r in range(nd):
            if r in skip_ranks:
                rank_cols.append(None)
                continue
            lo = r * cap
            cols = {}
            for i in used_cols:
                vals, valid, _d = host_cols[(id(scan), i)]
                pv = np.zeros(cap, dtype=vals.dtype)
                pm = np.zeros(cap, dtype=bool)
                seg = vals[lo:lo + cap]
                pv[:seg.shape[0]] = seg
                segm = valid[lo:lo + cap]
                pm[:segm.shape[0]] = segm
                lay = layouts.get(i)
                cols[i] = _compress.pack_slab(lay, pv, pm) \
                    if lay is not None else (pv, pm)
            rank_cols.append(cols)
        rank_rows = np.clip(total - np.arange(nd) * cap, 0,
                            cap).astype(np.int32)
        in_types = [scan.schema.field_types[i] for i in used_cols]
        vars_ = self.ctx.vars
        group_cap = int(vars_.get("tidb_tpu_group_cap",
                                  DEFAULT_GROUP_CAP))
        cap_limit = cap * nd
        gcap = _initial_group_cap(root, group_cap, cap_limit)
        ladder = CapacityLadder(guard=getattr(self.ctx, "guard", None),
                                stats=self.ctx.escalation)
        runner = StagedDistAgg(root, chain, mesh, rank_cols, rank_rows,
                               dicts, used_cols, in_types, cap, gcap,
                               cap_limit, self.ctx, ladder,
                               layouts=layouts or None,
                               skip_ranks=skip_ranks)
        pass_outs = runner.execute()
        flows, _root_dicts = TF.dictionary_flows(root, {id(scan): dicts})
        inp_dicts = {i: d for i, d in
                     enumerate(flows.get(id(root), []))}
        with self.ctx.phases.phase("decode"):
            return self._merge_tree_agg_passes(root, pass_outs, inp_dicts)

    def _run_dist_exchange_staged(self, root, mesh, host_cols,
                                  scan_meta) -> Optional[Chunk]:
        """Staged checkpointable dist exchange (dist_fragment.
        StagedDistExchange): per-rank partition programs → device→host
        bucket checkpoints + host routing → per-rank fused probe/dedup
        programs over the rewritten (exchange→leaf) plan. Returns None
        when the plan is ineligible — the caller falls through to the
        monolithic shard_map program, the byte-exactness oracle."""
        from tidb_tpu.executor.dist_fragment import (StagedDistExchange,
                                                     staged_exchange_plan)
        from tidb_tpu.util.escalation import CapacityLadder
        grafted = staged_exchange_plan(root)
        if grafted is None:
            return None
        new_root, grafts = grafted
        ladder = CapacityLadder(guard=getattr(self.ctx, "guard", None),
                                stats=self.ctx.escalation)
        runner = StagedDistExchange(root, new_root, grafts, mesh,
                                    host_cols, scan_meta, self.ctx,
                                    ladder)
        outs = runner.execute()
        if isinstance(new_root, PhysHashAgg):
            # the exchange re-keyed on the group keys, so each group's
            # rows landed wholly on ONE rank: the host merge never
            # combines two partials of one group (DISTINCT states stay
            # exact — same invariant as the monolithic owner merge)
            inp_dicts = {i: d for i, d in
                         enumerate(runner.flows2.get(id(new_root), []))}
            with self.ctx.phases.phase("decode"):
                return self._merge_tree_agg_passes(new_root, outs,
                                                   inp_dicts)
        dicts_root = {i: d for i, d in enumerate(runner.root_dicts2)}
        cols_vm = [(np.concatenate([np.asarray(o["cols"][ci][0])
                                    for o in outs]),
                    np.concatenate([np.asarray(o["cols"][ci][1])
                                    for o in outs]))
                   for ci in range(len(new_root.schema))]
        live = np.concatenate([np.asarray(o["live"]) for o in outs])
        with self.ctx.phases.phase("decode"):
            return _compact_decode(cols_vm, live,
                                   new_root.schema.field_types,
                                   dicts_root)

    def _run_device_dist(self) -> Chunk:
        # ORDER BY / TopN over the agg: shard programs compute the agg
        # only — the ordering stays a host concern after the shard merge
        # (the fused finalize is a single-device shape; a shard program
        # would pass the agg through and emit un-aggregated rows)
        order_root, root = _strip_order_root(self.plan.root)
        chunk = self._dist_exec(root)
        if order_root is not None:
            chunk = _host_order(chunk, order_root, root.schema)
            chunk = _topn_slice(chunk, order_root)
        return chunk

    def _dist_exec(self, root) -> Chunk:
        """Planner-fragmented tree as one shard_map program over the mesh
        (executor/dist_fragment.py; the MPPGather role of
        executor/mpp_gather.go:42 lives in this method)."""
        import types as pytypes

        from tidb_tpu.executor import device_cache, tree_fragment as TF
        from tidb_tpu.executor.device_cache import (_collect_parts,
                                                    _encode_col,
                                                    _materialize_col, _pow2)
        from tidb_tpu.executor.dist_fragment import DistTreeProgram
        from tidb_tpu.ops.jax_env import jax, jnp
        from tidb_tpu.parallel import make_mesh
        from tidb_tpu.planner.physical import PhysExchange

        nd = self.plan.dist
        import jax as _jax
        if len(_jax.devices()) < nd:
            raise FragmentFallback(f"mesh wants {nd} devices, "
                                   f"{len(_jax.devices())} available",
                                   reason="mesh-size")
        mesh = make_mesh(nd)
        P = jax.sharding.PartitionSpec
        sharding = jax.sharding.NamedSharding(mesh, P("shard"))

        scans = TF._scans(root)
        caps: Dict[int, int] = {}
        scan_inputs = []
        scan_rows = []
        scan_dicts = {}
        scan_bounds: Dict[int, Dict[int, Tuple[int, int]]] = {}
        host_cols: Dict[Tuple[int, int], list] = {}
        scan_meta = []
        ph = self.ctx.phases
        for scan in scans:
            used = scan.used_columns if scan.used_columns else \
                list(range(len(scan.schema)))
            parts, total = _collect_parts(self.ctx, scan)
            if total == 0:
                raise FragmentFallback("empty input", reason="empty-input")
            shim = pytypes.SimpleNamespace(parts=parts)
            ftypes = scan.schema.field_types
            with ph.phase("encode"):
                for i in used:
                    vals, valid = _materialize_col(shim, i)
                    vals, dictionary = _encode_col(ftypes[i], vals, valid)
                    host_cols[(id(scan), i)] = [vals, valid, dictionary]
            scan_meta.append((scan, used, total))
        # string equi-join keys: unify dictionaries BEFORE sharding so
        # equal strings hash equal on every shard (dist_fragment doc)
        from tidb_tpu.executor.dist_fragment import unify_string_join_dicts
        unify_string_join_dicts(root, host_cols)
        # staged checkpointable paths: an exchange-free agg chain runs as
        # per-rank single-device partials with device→host checkpoints
        # (StagedDistAgg); exchange-carrying plans (distributed joins,
        # DISTINCT re-keys, windows) cut at the exchange instead —
        # per-rank partition programs, host-routed bucket checkpoints,
        # per-rank probe programs (StagedDistExchange). Either way a
        # shard fault re-executes ONLY the failed rank through the
        # retry → re-dispatch → degraded-mesh ladder. Plans neither path
        # accepts (TopN/Sort roots, non-scan-chain exchange children)
        # keep the monolithic shard_map program below, where fault retry
        # stays full-step — it also remains the staged paths'
        # byte-exactness oracle.
        if _var_bool(self.ctx.vars.get("tidb_tpu_dist_staged", "on")):
            staged = self._run_dist_agg_staged(root, mesh, host_cols,
                                               scan_meta)
            if staged is not None:
                return staged
        if _var_bool(self.ctx.vars.get("tidb_tpu_dist_staged_exchange",
                                       "on")):
            staged = self._run_dist_exchange_staged(root, mesh, host_cols,
                                                    scan_meta)
            if staged is not None:
                return staged
        from tidb_tpu.chunk import compress as _compress
        from tidb_tpu.executor.device_cache import _col_bounds
        comp_on = _var_bool(self.ctx.vars.get("tidb_tpu_compression", "on"))
        dist_layouts = []
        for scan, used, total in scan_meta:
            cap = _pow2((total + nd - 1) // nd, lo=8)
            caps[id(scan)] = cap
            cols = {}
            dicts = {}
            bounds: Dict[int, Tuple[int, int]] = {}
            lay_pairs = []
            for i in used:
                vals, valid, dictionary = host_cols[(id(scan), i)]
                dicts[i] = dictionary
                b = _col_bounds(vals, valid, dictionary)
                if b is not None:
                    bounds[i] = b
                # each rank's rows pack on their own (the packed order is
                # planar WITHIN a slab) and the per-rank word arrays
                # concatenate into the one array that shards across the
                # mesh, so word boundaries must coincide with shard
                # boundaries: cap a multiple of WORD_BITS makes every
                # per ∈ {1,2,4,8,32} divide the shard evenly.
                # Dictionaries would need
                # replication, a width-0 (1,) stub can't shard, and a
                # delta slab can't either — its (1,) base is global while
                # each shard's cumsum would need its OWN running base.
                lay = None
                if comp_on and vals.ndim == 1 and \
                        cap % _compress.WORD_BITS == 0:
                    lay, _dv = _compress.choose_layout(vals, valid,
                                                       allow_dict=False)
                    if lay is not None and (lay.width == 0
                                            or lay.kind == "delta"):
                        lay = None
                with ph.phase("encode"):
                    pv = np.zeros(nd * cap, dtype=vals.dtype)
                    pv[:total] = vals
                    pm = np.zeros(nd * cap, dtype=bool)
                    pm[:total] = valid
                    packed = tuple(
                        np.concatenate(parts) for parts in zip(*(
                            _compress.pack_slab(
                                lay, pv[r * cap:(r + 1) * cap],
                                pm[r * cap:(r + 1) * cap])
                            for r in range(nd)))) \
                        if lay is not None else None
                logical_b = pv.nbytes + pm.nbytes
                with ph.phase("upload"):
                    if packed is not None:
                        cols[i] = tuple(jax.device_put(a, sharding)
                                        for a in packed)
                    else:
                        cols[i] = (jax.device_put(pv, sharding),
                                   jax.device_put(pm, sharding))
                phys_b = sum(a.nbytes for a in packed) \
                    if packed is not None else logical_b
                ph.add_h2d(phys_b, logical=logical_b)
                # the dist program streams these shards from HBM too
                ph.add_scan(phys_b, logical=logical_b)
                ph.mark_in_flight()
                if lay is not None:
                    lay_pairs.append((i, lay))
            dist_layouts.append(tuple(lay_pairs))
            rows = np.clip(total - np.arange(nd) * cap, 0,
                           cap).astype(np.int32)
            scan_inputs.append(cols)
            scan_rows.append(jax.device_put(rows, sharding))
            scan_dicts[id(scan)] = dicts
            scan_bounds[id(scan)] = bounds
        scan_inputs = tuple(scan_inputs)
        scan_rows = tuple(scan_rows)
        dist_layouts = tuple(dist_layouts) if any(dist_layouts) else None

        flows, root_dicts = TF.dictionary_flows(root, scan_dicts)
        flow_list = [flows.get(id(n), []) for n in TF._walk_nodes(root)]

        # initial bucket cap per hash exchange: 4× the balanced share
        # (tidb_tpu_exchange_bucket_cap overrides — skew/retry testing)
        cap_override = int(self.ctx.vars.get(
            "tidb_tpu_exchange_bucket_cap", 0) or 0)
        bucket_caps: Dict[int, int] = {}
        for node in TF._walk_nodes(root):
            if isinstance(node, PhysExchange) and node.kind == "hash":
                est = max(int(node.est_rows), 1)
                bucket_caps[id(node)] = cap_override or _pow2(
                    4 * ((est + nd - 1) // nd), lo=64)

        vars_ = self.ctx.vars
        group_cap = int(vars_.get("tidb_tpu_group_cap", DEFAULT_GROUP_CAP))
        is_agg = isinstance(root, PhysHashAgg)
        max_cap = max(caps.values())
        gcap = _initial_group_cap(root, group_cap, max_cap * nd) \
            if is_agg else 1

        hash_exchanges = [n for n in TF._walk_nodes(root)
                          if isinstance(n, PhysExchange)
                          and n.kind == "hash"]
        from dataclasses import replace as d_replace

        from tidb_tpu.executor.tree_fragment import JOIN_OUT_CAP

        def _shard_out_cap(cfg):
            # expand caps are PER SHARD: start from the balanced share of
            # the global estimate; skew comes back as join_need → 1 retry
            return _pow2(int(cfg.est * 1.3 / nd) + 16, lo=1024)

        join_cfgs = TF.plan_join_configs(root, scan_bounds)
        join_cfgs = [d_replace(c, out_cap=_shard_out_cap(c))
                     if c.mode == "expand" else c for c in join_cfgs]
        from tidb_tpu.errors import ShardFailure
        from tidb_tpu.util.escalation import CapacityLadder
        out_cap_max = int(vars_.get("tidb_tpu_join_out_cap", JOIN_OUT_CAP))
        ladder = CapacityLadder(guard=getattr(self.ctx, "guard", None),
                                stats=self.ctx.escalation)
        shard_faults = 0
        while True:
            # each retrace round is a checkpoint: a killed query must not
            # queue another multi-shard compile
            self.ctx.check_killed("device-dispatch")
            prog = _get_dist_program(root, caps, gcap, mesh, bucket_caps,
                                     join_cfgs, dist_layouts)
            prep_vals = prog.collect_preps(flow_list)
            try:
                # a shard fault (failpoint or real device error) can
                # surface at the drain OR the fetch — both stay in the
                # try. The scheduler slot covers only the async dispatch;
                # the GIL-releasing drain runs outside it so sibling
                # statements' host phases overlap the mesh execution.
                with self.ctx.device_slot():
                    with ph.launch(prog.name):
                        raw = prog(scan_inputs, scan_rows, prep_vals)
                ph.note_launch()
                if is_agg:
                    _count_agg_partial(_note_grouping(root, None, gcap))
                with ph.drain():
                    jax.block_until_ready(raw)
                with ph.phase("fetch"):
                    out = jax.device_get(raw)
                ph.add_d2h(tree_nbytes(out))
            except Exception as e:
                # one shard's step failing (the "shard-step" failpoint, or
                # a real per-device runtime fault) heals by re-dispatching
                # the WHOLE step — shard_map is deterministic over
                # host-resident inputs, so a retry recomputes every shard
                if not (isinstance(e, ShardFailure) or
                        type(e).__name__ == "XlaRuntimeError"):
                    raise
                shard_faults += 1
                if shard_faults > 1:
                    # the fault persisted through the retry: surface ONE
                    # typed error (the store and session stay usable)
                    raise ShardFailure(
                        "distributed fragment shard step failed twice: "
                        f"{e}") from e
                ladder.shard_retry(e)
                continue
            retry = False
            ju = np.asarray(out["join_unique"])
            jneed = np.asarray(out["join_need"])
            for ji, cfg in enumerate(join_cfgs):
                new_cfg, action = TF.escalate_join(
                    cfg, bool(ju[ji]), int(jneed[ji]), out_cap_max,
                    flip_out_cap=_shard_out_cap(cfg), ladder=ladder)
                if action == "over-max":
                    ladder.fallback("join")
                    raise FragmentFallback(
                        f"join fan-out {int(jneed[ji])} exceeds "
                        f"device cap", reason="join-cap")
                if new_cfg is not None:
                    # a lost PK-FK bet re-traces in expand mode; an expand
                    # overflow resizes to the largest shard's true need —
                    # one recompile either way, never a CPU fallback
                    join_cfgs[ji] = new_cfg
                    retry = True
            needs = np.asarray(out["exchange_need"])
            for need, node in zip(needs, hash_exchanges):
                if int(need) > bucket_caps[id(node)]:
                    from tidb_tpu.util import failpoint
                    failpoint.inject("exchange-overflow")
                    # resize only the overflowed exchange, to its exact
                    # reported need — one recompile, no doubling ladder
                    bucket_caps[id(node)] = ladder.resize(
                        "exchange", bucket_caps[id(node)],
                        need=int(need), lo=64)
                    retry = True
            gneed = int(out["group_need"])
            if gneed > gcap:
                if gcap >= max_cap * nd:
                    ladder.fallback("group")
                    raise FragmentFallback("group cap overflow", reason="group-cap")
                # the pmax'd true per-shard group count came back: exact
                # need, one recompile
                gcap = ladder.resize("group", gcap, need=gneed,
                                     max_cap=max_cap * nd)
                retry = True
            if not retry:
                break
            ladder.attempt("dist")

        dicts_root = {i: d for i, d in enumerate(root_dicts)}
        if is_agg:
            out_live = np.asarray(out["out_live"])
            idx = np.nonzero(out_live)[0]
            inp = flows.get(id(root), [])
            cols: List[Column] = []
            for kc, e in enumerate(root.group_exprs):
                ft = self.schema[kc]
                v, m = out["keys"][kc]
                d = inp[e.index] if isinstance(e, ColumnRef) and \
                    e.index < len(inp) else None
                cols.append(_decode_col(ft, np.asarray(v)[idx],
                                        np.asarray(m)[idx], d))
            for agg, st in zip([build_agg(d) for d in root.aggs],
                               out["states"]):
                v, m = agg.final(np, tuple(np.asarray(a) for a in st))
                cols.append(_decode_col(agg.ftype, np.asarray(v)[idx],
                                        np.asarray(m)[idx], None))
            if root.group_exprs and not len(idx):
                from tidb_tpu.executor import _empty_chunk
                return _empty_chunk(self.schema)
            return Chunk(cols)
        if isinstance(root, (PhysTopN, PhysSort)):
            # per-shard candidates arrive concatenated; the host does the
            # final k-way merge (the MPPGather role)
            n_outs = np.asarray(out["n_out"])
            per_shard = out["cols"][0][0].shape[0] // nd \
                if out["cols"] else 0
            pieces = []
            for s in range(nd):
                lo = s * per_shard
                n = int(n_outs[s])
                piece = []
                for ci, ((v, m), ft) in enumerate(
                        zip(out["cols"], root.schema.field_types)):
                    piece.append(_decode_col(
                        ft, np.asarray(v)[lo:lo + n],
                        np.asarray(m)[lo:lo + n], dicts_root.get(ci)))
                pieces.append(Chunk(piece))
            merged = Chunk.concat(pieces) if len(pieces) > 1 else pieces[0]
            merged = _host_order(merged, root, root.schema)
            return _topn_slice(merged, root)
        # window / selection / projection / join row root: compact the
        # shard-concatenated padded output by its live mask
        return _compact_decode(out["cols"], out["live"],
                               root.schema.field_types, dicts_root)

    @staticmethod
    def _slab(ent, slab_idx: int, used: Sequence[int]):
        # restrict to the program's used columns: a superset (uploaded by a
        # different query) would change the input pytree and force a retrace
        # (`at`: of a stacked column the stack and the slab's row — the
        # program reads the slab in place, nothing is sliced out for it)
        cols = {i: ent.dev[i].at(slab_idx) for i in used}
        return cols, ent.slab_live(slab_idx)

    def _slab_iter(self, ent, stream, used: Sequence[int], slab_ids=None):
        """Per-slab (cols, n_rows) source: the open_table stream on a cold
        first touch (driving it between dispatches is what overlaps encode
        with device work), the resident cache otherwise. A consumed stream
        has committed its arrays to ent.dev, so ladder retries always take
        the warm branch. `slab_ids` restricts the warm branch to the
        zone-map survivors; the stream needs no restriction — it already
        skipped pruned slabs, and both sides enumerate survivors in the
        same ascending physical order, so positional consumers align."""
        if stream is None:
            ids = slab_ids if slab_ids is not None else range(ent.n_slabs)
            for s in ids:
                yield self._slab(ent, s, used)
        else:
            for s, cols in stream:
                yield {i: cols[i] for i in used}, ent.slab_live(s)
            if ent.delta_cap and (slab_ids is None
                                  or ent.base_slabs in slab_ids):
                # the stream is the base's; the delta slab it committed
                # behind its last slab follows
                yield self._slab(ent, ent.base_slabs, used)

    # -- hash agg ------------------------------------------------------------
    @staticmethod
    def _agg_tail(src: _SlabSource, prog, order_root, gcap: int, sig: str,
                  n_run: int):
        """The program that follows the slab partials → (its traced
        function, the same jitted, its name, its launch's `sig` tag, its
        signature): the fused finalize under an ORDER BY / TopN — ONE
        launch for the whole query tail, agg merge → finalize expressions
        → root ORDER BY / TopN — else the merge; no function, and the slab
        program's signature, where one slab's partial is the answer."""
        if order_root is not None:
            fprog, fsig = get_finalize_program(src.root, order_root, gcap,
                                               sig)
            return (fprog._run, fprog.run, fprog.name,
                    _sig_tag("fused-final", fsig), fsig)
        if n_run == 1:
            return None, None, None, None, sig
        mp = src.merge_program(prog, gcap, sig)
        return mp._merge, mp.merge, mp.merge_name, None, "merge|" + sig

    def _run_agg_slabs(self, src: _SlabSource, gcap: int, order_root,
                       ladder) -> Optional[Chunk]:
        """Every per-slab partial aggregate: ONE traced XLA program per
        surviving slab of `src` (scan → filter → project → [join-probe →]
        partial-agg) plus one root merge or finalize — intermediates never
        leave registers/HBM and the warm path launches slabs + 1 programs.
        An ORDER BY / TopN over the aggregate (`order_root`) is the fused
        finalize's tail.

        RESUMABLE capacity escalation: per-slab partials are the
        checkpoints. On a group-cap overflow only the slabs whose TRUE
        group count exceeded the cap they ran at re-execute after the
        exact-need recompile — partials that fit merge back in untouched
        (ragged caps are fine: the merge re-factorizes under slot_live
        masks); a merged-count-only overflow re-runs ZERO slabs (a
        bigger-cap re-merge of the checkpoints); a clipped DISTINCT pair
        set re-runs the slabs that clipped; what else a source escalates
        (a tree's join capacities) names its own re-run set. Each retry is
        charged ONE recompile against the ladder's backoff budget, and
        EscalationStats.slabs_rerun/slabs_reused make the reuse observable
        (EXPLAIN ANALYZE). → None when the source gives the statement back
        (a join's fan-out over out_cap_max).

        A WARM statement is one launch (`_launch_plan`, `_StatementProgram`):
        once an earlier execution of its digest has settled the capacities
        and every slab is resident, the slab bodies and the merge/finalize
        run as ONE traced program under ONE hold of the batch slot. The
        loop over slabs stays the cold path (it streams a first touch) and
        the escalating one: an overflow that the statement program's
        control fetch shows sends the statement back here, at the same
        capacities, for partials to resume from."""
        from tidb_tpu.executor.device_emit import partials_of
        from tidb_tpu.ops.jax_env import jax
        from tidb_tpu.util import failpoint
        from tidb_tpu.util.observability import REGISTRY
        ph = self.ctx.phases
        root, key_bounds = src.root, src.key_bounds
        if not src.run_ids:
            # every slab pruned: ZERO launches — grouped agg → empty,
            # global agg → the CPU oracle's identity row (COUNT 0,
            # SUM/MIN/MAX NULL: the merge of zero passes)
            chunk = self._merge_tree_agg_passes(root, [], src.dicts)
            if order_root is not None:
                chunk = _host_order(chunk, order_root, root.schema)
                chunk = _topn_slice(chunk, order_root)
            return chunk
        n_run, slab_cap = len(src.run_ids), src.slab_cap
        # multi-slab DISTINCT: the slab programs emit capped, deduped
        # (group, args...) pair sets the host merges exactly. A slab can't
        # emit more pairs than it has rows, so slab_cap is both the
        # default clamp and the ladder's hard ceiling (resize through
        # "pairs" rungs, never truncate)
        want_pairs = src.n_slabs > 1 and \
            any(d.distinct and d.args for d in root.aggs)
        pair_cap = min(int(self.ctx.vars.get("tidb_tpu_distinct_pair_cap",
                                             65536)),
                       slab_cap) if want_pairs else 0
        use_fin = order_root is not None
        # per-digest specialization (the cache's own comment, above): adopt
        # the caps, and whatever else the source learned, that an earlier
        # execution of this statement settled on, and reuse its signature.
        # The key pins the data token (writes invalidate), geometry and key
        # bounds — everything the signature would otherwise re-derive — and
        # NOT the layouts, which _spec_lookup compares to evict on drift
        skey = _spec_key(
            getattr(self.ctx, "guard", None), src.kind,
            src.geometry + (
                bounds_sig(key_bounds), want_pairs,
                _order_sig(order_root) if use_fin else None,
                _plan_fingerprint(root)))
        spec = _spec_lookup(skey, src.lay_sig)
        if skey is not None:
            _spec_note(ph, spec is not None)
        spec_sig = None
        if spec is not None:
            gcap = spec["group_cap"]
            pair_cap = spec["pair_cap"] if want_pairs else 0
            src.adopt(spec)
            spec_sig = spec["sig"]
        partials: List = [None] * n_run
        rows_in = 0                     # rows of every launched slab
        # grouping by sorted runs: the slab programs only hand out rows
        # (no group capacity in them), one sort serves the statement
        rows_mode = grouping_mode(key_bounds) == RUNS
        sorted_rows = None
        plan = _launch_plan(src, spec, want_pairs, rows_mode)
        caps_ran = [0] * n_run          # group cap each partial ran at
        pcaps = [0] * n_run             # pair cap each partial ran at
        pairs_cache: List = [None] * n_run     # host distinct-pair sets
        to_run: Optional[List[int]] = None     # None = cold first pass

        while True:
            grouping = _note_grouping(root, key_bounds, gcap)
            with timeline.span("frag.program", "frag"):
                prog, sig, prep_vals = src.program(
                    0 if rows_mode else gcap, pair_cap, want_pairs,
                    spec_sig)
            spec_sig = None
            if not rows_mode:
                tail, run_tail, tail_name, tail_tag, tail_sig = \
                    self._agg_tail(src, prog, order_root, gcap, sig, n_run)
            packed = None       # a statement program packs what is fetched
            for s, part in () if plan == "whole" else \
                    src.launches(prog, prep_vals, to_run):
                stale, partials[s] = partials[s], part
                ph.note_launch()
                ph.note_fused()   # a chain partial IS a fused pipeline
                _count_agg_partial(grouping)
                rows_in += src.rows(s)
                sorted_rows = None
                caps_ran[s] = gcap
                pcaps[s] = pair_cap
                pairs_cache[s] = None
                if stale is not None:
                    _tree_delete(stale)
            if want_pairs:
                # per-slab deduped (group, value) pair sets ride inside
                # the partial outputs; slice to their true counts on
                # device and fetch in one round trip. Cached host-side
                # per slab: a resumable retry refetches only re-run slabs
                need = [s for s in range(n_run)
                        if pairs_cache[s] is None]
                if need:
                    with ph.phase("fetch"):
                        counts = jax.device_get(
                            [{ai: partials[s]["pairs"][ai][1]
                              for ai in partials[s]["pairs"]}
                             for s in need])
                    ph.add_d2h(tree_nbytes(counts))
                    # distinct-pair-cap validation: n_pairs reports the
                    # TRUE per-slab pair count, the output arrays hold
                    # only pcaps[s] — a clipped slab must resize and
                    # re-run, never silently truncate
                    failpoint.inject("fused-finalize-overflow")
                    pover = [s for si, s in enumerate(need)
                             if any(int(c) > pcaps[s]
                                    for c in counts[si].values())]
                    if pover:
                        if pair_cap >= slab_cap:
                            ladder.fallback("pairs")
                            raise FragmentFallback(
                                "distinct pair overflow",
                                reason="pair-cap")
                        worst = max(int(c) for si, s in enumerate(need)
                                    if s in pover
                                    for c in counts[si].values())
                        pair_cap = ladder.resize("pairs", pair_cap,
                                                 need=worst,
                                                 max_cap=slab_cap)
                        ladder.attempt("pairs", _GroupCapOverflow(worst))
                        ladder.partial_resume(
                            "pairs", rerun=len(pover),
                            reused=n_run - len(pover))
                        to_run = pover
                        continue
                    with ph.phase("fetch"):
                        sliced = [
                            {ai: [(v[:int(counts[si][ai])],
                                   m[:int(counts[si][ai])])
                                  for v, m in partials[s]["pairs"][ai][0]]
                             for ai in partials[s]["pairs"]}
                            for si, s in enumerate(need)]
                        per_slab = jax.device_get(sliced)
                    ph.add_d2h(tree_nbytes(per_slab))
                    for s, ps in zip(need, per_slab):
                        pairs_cache[s] = ps
            # build the whole device graph FIRST (per-slab partials +
            # merge — no host sync in between), then fetch every control
            # value in ONE batched round trip (a statement program packs
            # them besides: the host pays a fetch by the leaf)
            if plan == "whole":
                # ONE launch, ONE hold of the slot, for the whole statement
                args = src.statement_args(prog, prep_vals)
                small = not self._rows_on_device and \
                    gcap <= SMALL_GROUP_CAP
                sprog = get_statement_program(src, prog, n_run, tail,
                                              tail_sig, small, args)
                with self.ctx.device_slot():
                    with ph.launch(sprog.name,
                                   sig=_sig_tag("stmt", sprog.sig)):
                        out, packed = sprog.run(*args)
                        if sprog.said:
                            # (the first call of a program built here)
                            timeline.tag(**sprog.said)
                            sprog.said = None
                fetch = sprog.like
                ph.note_launch()
                ph.note_fused()
                _count_agg_partial(grouping)
                rows_in += sum(src.rows(s) for s in range(n_run))
                caps_ran = [gcap] * n_run
            else:
                if rows_mode:
                    out, sorted_rows = self._runs_finalize(
                        root, order_root, partials, src.n_slabs, gcap,
                        key_bounds, sig, sorted_rows)
                elif tail is None:
                    out = partials[0]
                else:
                    # either tail takes the partials as they are and
                    # stacks them in the trace: `slots_in` partial slots
                    # reduce into `slots_out`
                    with timeline.span(
                            "frag.merge", "frag", slots_out=int(gcap),
                            slots_in=sum(int(p["slot_live"].shape[0])
                                         for p in partials)), \
                            self.ctx.device_slot():
                        with ph.launch(tail_name, sig=tail_tag):
                            out = run_tail(*partials_of(partials))
                    ph.note_launch()
                with self.ctx.device_slot(), ph.glue():
                    small = not self._rows_on_device and (
                        int(out["keys"][0][0].shape[0])
                        if rows_mode and out["keys"] else gcap) \
                        <= SMALL_GROUP_CAP
                    fetch = _control_tree(
                        _control_of(partials, src.control), out, small)
            with ph.drain():
                # drain inside "compute" so the flag fetch below measures
                # pure transfer, not the device finishing its work — but
                # OUTSIDE the scheduler slot: the wait releases the GIL,
                # siblings dispatch meanwhile
                jax.block_until_ready(fetch if packed is None else packed)
            with ph.phase("fetch"):
                if packed is None:
                    got = jax.device_get(fetch)
                else:
                    packed = jax.device_get(packed)
                    got = _unpack(packed, fetch)
                    if out is None:     # small: the result rode the fetch
                        out = got
            ph.add_d2h(tree_nbytes(got if packed is None else packed))
            # the slab programs' capacity boundary: everything below
            # classifies this round's overflows into re-run sets
            forced = failpoint.inject("fused-pipeline-overflow")
            if plan == "whole" and (
                    forced or src.overflowed(got) or (
                        grouping_mode(key_bounds) != SLOTS and max(
                            int(got["ng"]), *map(int, got["ngs"])) > gcap)):
                # a capacity the digest had settled on no longer holds (or
                # a failpoint's value says so): the per-slab driver runs
                # the statement at the same capacities, finds the overflow
                # in partials it can resume from, and escalates. (The
                # launch stays counted and its rows stay in `rows_in`, as
                # a re-run slab's do: the device did read them.)
                _tree_delete(out)
                plan = "slabs:overflow"
                continue
            if use_fin:
                # TopN k is a static trace constant and an n_groups
                # overflow resizes through the group rung below, so the
                # finalize itself cannot overflow — this site is
                # defensive, and chaos injection proves a fault at the
                # finalize boundary degrades to the CPU oracle
                failpoint.inject("fused-finalize-overflow")
            esc = src.escalate(got, ladder)
            if esc is None:
                for p in partials:
                    _tree_delete(p)
                if out is not partials[0]:
                    _tree_delete(out)
                return None
            retry, rerun = esc
            charged = False
            n_final = int(got["ng"])
            if grouping_mode(key_bounds) != SLOTS:
                # a slab overflowed iff its TRUE count exceeded the cap IT
                # ran at (factorize counts before clamping to cap-1, which
                # silently conflates groups while the merged n_groups can
                # look fine; reused partials ran at an older, smaller cap
                # and stay valid)
                over = [s for s in range(n_run)
                        if int(got["ngs"][s]) > caps_ran[s]]
                if over or n_final > gcap:
                    if gcap >= src.max_cap:
                        ladder.fallback("group")
                        raise FragmentFallback("group cap overflow",
                                               reason="group-cap")
                    # clipped slabs understate the merged count, so the
                    # max overflowed per-slab count is the valid lower
                    # bound — the ladder resizes to it exactly and
                    # re-checks; a merged-only overflow is exact and
                    # re-runs NOTHING: every slab partial is a valid
                    # checkpoint, re-merged at the exact-need cap
                    need_cap = max([int(got["ngs"][s]) for s in over]
                                   + [n_final])
                    gcap = ladder.resize("group", gcap, need=need_cap,
                                         max_cap=src.max_cap)
                    ladder.attempt("group", _GroupCapOverflow(need_cap))
                    ladder.partial_resume("group", rerun=len(over),
                                          reused=n_run - len(over))
                    charged = True
                    rerun.update(over)
                    retry = True
            if retry:
                if not charged:
                    # budget + guard checkpoint between recompiles (the
                    # source's rungs already recorded their own stats)
                    ladder.attempt("fused")
                if out is not partials[0]:
                    _tree_delete(out)     # stale merge generation
                to_run = sorted(rerun)
                continue
            break
        timeline.tag(launch_plan=plan)
        REGISTRY.inc("tidb_tpu_statement_programs_total",
                     {"plan": plan.partition(":")[0]})
        cap_out = gcap
        if rows_mode:
            gcap = _tight_cap(gcap, n_final)
        ent = {"group_cap": gcap, "pair_cap": pair_cap, "sig": sig,
               "lay_sig": src.lay_sig, **src.learned()}
        if skey is not None and spec != ent:
            _spec_store(skey, ent)
        _note_agg_io(out, rows_in, n_final)
        if self._rows_on_device:
            return _agg_rows(self.ctx, root, out, cap_out, sig, key_bounds)
        if root.group_exprs and n_final == 0:
            from tidb_tpu.executor import _empty_chunk
            return _empty_chunk(self.schema)
        host_pairs = None
        if want_pairs:
            host_pairs = {ai: [pairs_cache[s][ai]
                               for s in range(n_run)]
                          for ai in pairs_cache[0]} \
                if pairs_cache[0] else {}
        host_tree = (got["keys"], got["states"]) if small else None
        n_rows = int(got["no"]) if use_fin else n_final
        with ph.phase("decode"):
            chunk = self._agg_chunk(root, out, src.dicts, max(n_rows, 1),
                                    host_pairs, host_tree=host_tree)
        if use_fin:
            chunk = _topn_slice(chunk, order_root)
        return chunk

    def _agg_chunk(self, root: PhysHashAgg, out, dicts, n_final,
                   distinct_pairs=None, host_tree=None) -> Chunk:
        if host_tree is not None:
            # keys/states already came back WITH the flag fetch (small
            # group caps piggyback on round trip #1); slice the padding
            # off host-side
            hk, hs = host_tree
            host_keys = [(np.asarray(k)[:n_final], np.asarray(m)[:n_final])
                         for k, m in hk]
            host_states = [tuple(np.asarray(a)[:n_final] for a in st)
                           for st in hs]
        else:
            # slice ON DEVICE, fetch EVERYTHING in one device_get:
            # transfers n_final rows per array in one round trip
            dev_tree = (
                [(k[:n_final], m[:n_final]) for k, m in out["keys"]],
                [tuple(a[:n_final] for a in st) for st in out["states"]],
            )
            host_keys, host_states = self.ctx.phases.fetch(dev_tree)
        if distinct_pairs:
            # multi-slab DISTINCT: the device-merged distinct states
            # deduped only within each slab — recompute them from the
            # cross-slab-deduped pair sets
            over = _merge_distinct_states(root, host_keys, distinct_pairs,
                                          n_final)
            host_states = [over.get(ai, st)
                           for ai, st in enumerate(host_states)]
        cols: List[Column] = []
        for kc, e in enumerate(root.group_exprs):
            ft = self.schema[kc]
            v, m = host_keys[kc]
            cols.append(_decode_col(ft, v, m, _expr_dict(e, dicts)))
        for agg, st in zip([build_agg(d) for d in root.aggs], host_states):
            v, m = agg.final(np, st)
            cols.append(_decode_col(agg.ftype, np.asarray(v),
                                    np.asarray(m, dtype=bool), None))
        return Chunk(cols)

    # -- topn / sort ---------------------------------------------------------
    def _execute_order(self, prog, root, ent, dicts, prep_vals,
                       stream=None, slab_ids=None) -> Chunk:
        from tidb_tpu.ops.jax_env import jax, jnp
        ph = self.ctx.phases
        outs = []
        for cols, n in self._slab_iter(ent, stream, prog.used_cols,
                                       slab_ids):
            with self.ctx.device_slot():
                with ph.launch(prog.partial_name, slab=len(outs)):
                    outs.append(prog.partial(cols, jnp.int32(n),
                                             prep_vals))
            ph.note_launch()
            ph.note_fused()
        with ph.drain():
            jax.block_until_ready([o["n_out"] for o in outs])
        with ph.phase("fetch"):
            n_outs = [int(n) for n in
                      jax.device_get([o["n_out"] for o in outs])]
            # slice on device, fetch all slabs' candidates in one trip
            dev_tree = [[(v[:n], m[:n]) for v, m in o["cols"]]
                        for o, n in zip(outs, n_outs)]
            host_tree = jax.device_get(dev_tree)
        ph.add_d2h(tree_nbytes(host_tree) + 4 * len(n_outs))
        with ph.phase("decode"):
            pieces = [self._cols_chunk(root, cols_host, dicts)
                      for cols_host in host_tree]
            if len(pieces) == 1:
                merged = pieces[0]
            else:
                # per-slab top-(k+off) candidates merged on host (small)
                merged = Chunk.concat(pieces)
                merged = _host_order(merged, root, self.plan.root.schema)
            return _topn_slice(merged, root)

    def _cols_chunk(self, root, host_cols, dicts) -> Chunk:
        child_types = [ft for ft in root.schema.field_types]
        out = []
        for ci, ((v, m), ft) in enumerate(zip(host_cols, child_types)):
            out.append(_decode_col(ft, np.asarray(v), np.asarray(m),
                                   _positional_dict(root, ci, dicts)))
        return Chunk(out)

    # -- selection / projection ----------------------------------------------
    def _execute_filter(self, prog, root, ent, dicts, prep_vals,
                        stream=None, slab_ids=None) -> Chunk:
        from tidb_tpu.ops.jax_env import jax, jnp
        ph = self.ctx.phases
        outs = []
        for cols, n in self._slab_iter(ent, stream, prog.used_cols,
                                       slab_ids):
            with self.ctx.device_slot():
                with ph.launch(prog.partial_name, slab=len(outs)):
                    outs.append(prog.partial(cols, jnp.int32(n),
                                             prep_vals))
            ph.note_launch()
            ph.note_fused()
        with ph.drain():
            jax.block_until_ready(outs)
        with ph.phase("fetch"):
            host_outs = jax.device_get(outs)   # one batched round trip
        ph.add_d2h(tree_nbytes(host_outs))
        with ph.phase("decode"):
            pieces: List[Chunk] = []
            for out in host_outs:
                live = np.asarray(out["live"])
                idx = np.nonzero(live)[0]
                piece = []
                for ci, ((v, m), ft) in enumerate(
                        zip(out["cols"], root.schema.field_types)):
                    vals = np.asarray(v)[idx]
                    mask = np.asarray(m)[idx]
                    piece.append(_decode_col(
                        ft, vals, mask, _positional_dict(root, ci, dicts)))
                pieces.append(Chunk(piece))
            return Chunk.concat(pieces) if len(pieces) > 1 else pieces[0]


def _strip_exchanges(plan: PhysicalPlan) -> PhysicalPlan:
    from tidb_tpu.planner.physical import PhysExchange
    plan.children = [_strip_exchanges(c) for c in plan.children]
    if isinstance(plan, PhysExchange):
        return plan.children[0]
    return plan


class _RankZoneEnt:
    """Duck-typed zone-map carrier for staged-dist rank pruning: the
    per-rank slice plays the slab role, so zonemap.prune_slabs runs
    unchanged over rank-granular stats."""

    __slots__ = ("compressed", "n_slabs", "zmaps", "dicts")

    def __init__(self, nd: int, zmaps: dict, dicts: dict):
        self.compressed = True
        self.n_slabs = nd
        self.zmaps = zmaps
        self.dicts = dicts


class _GroupCapOverflow(Exception):
    """Factorize saw more groups than the program's cap. `need` carries
    the observed true count (0 = unknown) so the escalation ladder can
    resize to exact need instead of blind doubling."""

    def __init__(self, need: int = 0):
        super().__init__(f"group cap overflow (need {need})")
        self.need = int(need)


# PhaseTimer of the most recent device fragment run (encode/upload/compute/
# fetch/decode seconds + overlap efficiency), for tests.
LAST_PHASES = None


def _expr_dict(e: Expression, dicts) -> Optional[np.ndarray]:
    if isinstance(e, ColumnRef):
        return dicts.get(e.index)
    return None


def _positional_dict(node: PhysicalPlan, out_idx: int, dicts
                     ) -> Optional[np.ndarray]:
    """Dictionary for output column `out_idx` of a non-agg root: identity
    through Selection/TopN/Sort; via ColumnRef for Projection outputs."""
    cur = node
    idx = out_idx
    while True:
        if isinstance(cur, PhysTableScan):
            return dicts.get(idx)
        if isinstance(cur, PhysProjection):
            e = cur.exprs[idx]
            if isinstance(e, ColumnRef):
                idx = e.index
            else:
                return None
        cur = cur.children[0] if cur.children else None
        if cur is None:
            return None


def _host_run_bounds(cols) -> Tuple[np.ndarray, np.ndarray]:
    """Lexsort rows of [(values, valid), ...] → (order, first_of_run mask
    over the sorted order). NULL slots canonicalize so all NULLs in a
    column compare equal (the host mirror of ops/factorize.py)."""
    arrays: List[np.ndarray] = []
    for v, m in cols:
        v = np.asarray(v)
        m = np.asarray(m)
        arrays.append(np.where(m, v, np.zeros((), dtype=v.dtype)))
        arrays.append(m)
    n = len(arrays[0]) if arrays else 0
    order = np.lexsort(arrays[::-1]) if arrays else np.arange(0)
    first = np.zeros(n, dtype=bool)
    if n:
        first[0] = True
        for a in arrays:
            sa = a[order]
            first[1:] |= sa[1:] != sa[:-1]
    return order, first


def _host_group_index(final_cols, query_cols) -> np.ndarray:
    """Map each query row's key tuple to its row index in final_cols
    (−1 when absent). Vectorized via one shared lexsort — no Python dict,
    so cross-slab DISTINCT merges scale to millions of pairs."""
    nf = len(final_cols[0][0]) if final_cols else 0
    nq = len(query_cols[0][0]) if query_cols else 0
    if not final_cols:
        return np.zeros(nq, dtype=np.int64)
    both = [(np.concatenate([np.asarray(fv), np.asarray(qv)]),
             np.concatenate([np.asarray(fm), np.asarray(qm)]))
            for (fv, fm), (qv, qm) in zip(final_cols, query_cols)]
    order, first = _host_run_bounds(both)
    gid_sorted = np.cumsum(first) - 1
    gid = np.empty(nf + nq, dtype=np.int64)
    gid[order] = gid_sorted
    slot_of = np.full(int(gid_sorted[-1]) + 1 if len(gid_sorted) else 1,
                      -1, dtype=np.int64)
    slot_of[gid[:nf]] = np.arange(nf)
    return slot_of[gid[nf:]]


def _merge_distinct_states(root, host_keys, distinct_pairs, n_final):
    """Cross-slab DISTINCT merge: concatenate per-slab pair sets, dedup
    globally (lexsort runs), map pairs onto the final merged groups, and
    recompute each distinct aggregate's state with the numpy side of the
    xp-generic agg framework (the distinct-partials split of
    aggfuncs/func_sum.go:49-59). → {agg_index: state_tuple}."""
    from tidb_tpu.expression.aggfuncs import build_agg
    nk = len(root.group_exprs)
    out = {}
    for ai, slabs in distinct_pairs.items():
        na = max(1, len(root.aggs[ai].args))
        cols = []
        for c in range(nk + na):
            v = np.concatenate([np.asarray(s[c][0]) for s in slabs])
            m = np.concatenate([np.asarray(s[c][1]) for s in slabs])
            cols.append((v, m))
        order, first = _host_run_bounds(cols)
        uniq = np.zeros(len(order), dtype=bool)
        uniq[order] = first
        vv = cols[nk][0]
        vm = np.ones(len(order), dtype=bool)
        for _av, am in cols[nk:]:
            vm = vm & np.asarray(am)     # any NULL arg → row never counts
        keep = uniq & vm
        if nk:
            gidx = _host_group_index(
                host_keys, [(np.asarray(v)[keep], np.asarray(m)[keep])
                            for v, m in cols[:nk]])
            ok = gidx >= 0   # every pair's group exists in the final set
            gids = np.where(ok, gidx, 0).astype(np.int32)
        else:
            ok = np.ones(int(keep.sum()), dtype=bool)
            gids = np.zeros(int(keep.sum()), dtype=np.int32)
        agg = build_agg(root.aggs[ai])
        st = agg.init(np, n_final)
        out[ai] = agg.update(np, st, gids, n_final,
                             np.asarray(vv)[keep],
                             np.asarray(vm)[keep] & ok)
    return out


def _compact_decode(cols_vm, live_mask, ftypes, dicts_root) -> Chunk:
    """Compact padded (values, validity) columns by a live mask and decode
    them into a host Chunk (shared by the single-chip and distributed
    row/window-root result paths)."""
    idx = np.nonzero(np.asarray(live_mask))[0]
    return Chunk([_decode_col(ft, np.asarray(v)[idx], np.asarray(m)[idx],
                              dicts_root.get(ci))
                  for ci, ((v, m), ft) in enumerate(zip(cols_vm, ftypes))])


def _topn_slice(chunk: Chunk, root) -> Chunk:
    if isinstance(root, (PhysTopN, PhysLimit)):
        lo = min(root.offset, chunk.num_rows)
        hi = min(root.offset + root.count, chunk.num_rows)
        return chunk.slice(lo, hi)
    return chunk


def _decode_col(ft: FieldType, vals: np.ndarray, mask: np.ndarray,
                dictionary: Optional[np.ndarray]) -> Column:
    if ft.is_varlen:
        if dictionary is None:
            if not np.asarray(mask, dtype=bool).any():
                # unused placeholder column: all-NULL is fine
                return Column.all_null(ft, len(vals))
            raise FragmentFallback("string column without dictionary", reason="string-dict")
        neg = vals < 0
        if neg.any():
            mask = mask & ~neg
        if len(dictionary):
            decoded = dictionary[np.clip(vals, 0, len(dictionary) - 1)]
            decoded = np.asarray(decoded, dtype=object)
        else:
            decoded = np.full(len(vals), "", dtype=object)
        vals = decoded
    elif vals.dtype != ft.np_dtype:
        vals = vals.astype(ft.np_dtype)
    mask = np.asarray(mask, dtype=bool)
    return Column(ft, vals, None if mask.all() else mask.copy())


def _host_order(chunk: Chunk, root, schema) -> Chunk:
    """k-way candidate merge for multi-slab TopN: re-sort the (small)
    concatenated candidates on host with MySQL NULL ordering (NULLs first
    ASC, last DESC)."""
    from tidb_tpu.expression.runner import eval_on_chunk
    lex_keys: List[np.ndarray] = []   # np.lexsort: LAST key is primary
    for e, desc in zip(root.by, root.descs):
        if isinstance(e, ColumnRef):
            col = chunk.columns[e.index]
        else:
            col = eval_on_chunk([e], chunk).columns[0]
        vals = col.values
        valid = col.valid_mask()
        if not valid.all():
            # neutralize masked-out garbage so ordering among NULL-key rows
            # falls through to the next ORDER BY key (matches CPU engine)
            fill = "" if vals.dtype == object else np.zeros(1, vals.dtype)[0]
            vals = np.where(valid, vals, fill)
        if vals.dtype == object:
            ranks = {v: i for i, v in
                     enumerate(sorted({str(x) for x in vals}))}
            vals = np.array([ranks[str(v)] for v in vals], dtype=np.int64)
        if desc:
            val_key = -vals.astype(np.float64) if vals.dtype.kind == "f" \
                else ~vals.astype(np.int64)
            null_key = ~valid            # NULLs last
        else:
            val_key = vals
            null_key = valid             # NULLs first (False < True)
        # primary-first ORDER BY list → reversed for lexsort; within one
        # column the null flag outranks the value
        lex_keys = [val_key, null_key] + lex_keys
    order = np.lexsort(lex_keys) if lex_keys else np.arange(chunk.num_rows)
    return chunk.take(order)
