"""May this plan run on the device? The planner's question, answered from
plan nodes and expressions alone — no device code is imported here.

`extract_fragments` (planner/physical.physical_optimize's last pass) wraps
every maximal device-capable subtree in a PhysTpuFragment: a linear chain
`scan → selection* → projection* → [hash-agg | topN | sort | window]`
(`fragment_ok`) or a join tree (`tree_ok`), over a scan that clears the row
threshold; `dist_ok` admits a fragment to the multi-shard compilation;
`check_strict_plan` is `tidb_tpu_strict`'s reading of what was left on the
host. The reference's allowlist philosophy (expression.go
scalarExprSupportedByTiFlash): what passes here is TRIED on the device, and
what the device then declines raises `FragmentFallback` with one of
`FALLBACK_REASONS`.

Beside the predicates live the walks over a plan that they and the drivers
must agree on (`linearize`, `stage_exprs`, `walk_nodes`, `scans_of`,
`join_key_exprs`): position k of a walk at plan time is position k in the
trace.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

from tidb_tpu.errors import ExecutionError
from tidb_tpu.expression import (HOST_ONLY_OPS, ColumnRef, Constant,
                                 EvalContext, Expression, ScalarFunc,
                                 coerce_key_pair)
from tidb_tpu.expression.aggfuncs import build_agg
from tidb_tpu.planner.physical import (PhysExchange, PhysHashAgg,
                                       PhysHashJoin, PhysIndexLookupJoin,
                                       PhysLimit, PhysMergeJoin,
                                       PhysProjection, PhysSelection,
                                       PhysSort, PhysStreamAgg,
                                       PhysTableScan, PhysTopN,
                                       PhysTpuFragment, PhysWindow,
                                       PhysicalPlan)
from tidb_tpu.types import fold_ci_array
from tidb_tpu.util.observability import REGISTRY

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


# ---------------------------------------------------------------------------
# Linear chains (the engine allowlist gate)
# ---------------------------------------------------------------------------


def order_over_agg_ok(order: PhysicalPlan, agg: PhysicalPlan) -> bool:
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


def identity_projection(p: PhysicalPlan) -> bool:
    """A planner-inserted pass-through (col#i → i, in order, dropping
    nothing): transparent between an ORDER BY / TopN root and the agg it
    orders, because its output row IS the agg's output row."""
    return (isinstance(p, PhysProjection) and p.children and
            len(p.exprs) == len(p.children[0].schema.field_types) and
            all(isinstance(e, ColumnRef) and e.index == i
                for i, e in enumerate(p.exprs)))


def strip_order_root(root: PhysicalPlan):
    """(order_root, agg) when `root` is an ORDER BY / TopN over the agg
    (identity projections between them are transparent), else (None,
    root)."""
    if isinstance(root, (PhysTopN, PhysSort)) and root.children:
        below = root.children[0]
        while identity_projection(below) and below.children:
            below = below.children[0]
        if isinstance(below, PhysHashAgg):
            return root, below
    return None, root


def linearize(root: PhysicalPlan) -> Optional[List[PhysicalPlan]]:
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
                     and all(identity_projection(n) for n in nodes[1:-1])
                     and order_over_agg_ok(root, cur))
        if not (mid_ok or root_ok or order_agg) or len(cur.children) != 1:
            return None
        cur = cur.children[0]


def string_exprs_are_refs(exprs: Sequence[Expression]) -> bool:
    return all(isinstance(e, ColumnRef) or not e.ftype.kind.is_string
               for e in exprs)


def exprs_device_ok(exprs: Sequence[Expression],
                     wide_refs_ok: bool = False) -> bool:
    """Reject host-only builtins at plan time (quiet CPU routing instead
    of a traced failure + warning per query). Wide decimals (limb-plane
    representation) are rejected here too: only the SUM/AVG/COUNT agg
    arguments handled by fragment_ok's special case consume limbs."""
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


def fragment_ok(plan: PhysicalPlan, threshold: int) -> bool:
    chain = linearize(plan)
    if chain is None:
        return False
    scan = chain[-1]
    if getattr(scan, "est_rows", 0.0) < threshold:
        # route small inputs to CPU: launch+transfer dominates (SURVEY §7
        # cost-model honesty; the reference's TiFlash row-threshold gate)
        return False
    reduction = isinstance(plan, (PhysHashAgg, PhysTopN, PhysSort))
    worthwhile = reduction or bool(scan.filters)
    order_agg = strip_order_root(plan)[0] is not None
    for node in chain:
        stage = stage_exprs(node)
        if isinstance(node, PhysHashAgg):
            stage = list(node.group_exprs)   # agg args validated below
        elif node is plan and order_agg:
            stage = []      # refs into the agg's row: order_over_agg_ok's
        if not exprs_device_ok(stage):
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
                if not string_exprs_are_refs(desc.args):
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
                elif not exprs_device_ok(desc.args):
                    return False
            if not string_exprs_are_refs(node.group_exprs):
                return False
        elif isinstance(node, (PhysTopN, PhysSort)):
            if not string_exprs_are_refs(node.by):
                return False
        elif isinstance(node, PhysWindow):
            if not window_device_ok(node):
                return False
            worthwhile = True
        elif isinstance(node, PhysSelection):
            worthwhile = True
        elif isinstance(node, PhysProjection):
            if not string_exprs_are_refs(node.exprs):
                return False
            if any(not isinstance(e, ColumnRef) for e in node.exprs):
                worthwhile = True
    return worthwhile


_DEVICE_WINDOW_FUNCS = ("row_number", "rank", "dense_rank", "sum",
                        "count", "avg", "min", "max", "lag", "lead",
                        "first_value", "last_value", "percent_rank",
                        "cume_dist", "ntile", "nth_value")


def window_device_ok(node: PhysWindow) -> bool:
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
        if not string_exprs_are_refs(list(d.partition) + list(d.order)):
            return False
    return True


def extract_fragments(plan: PhysicalPlan, threshold: int) -> PhysicalPlan:
    """Top-down maximal-chain extraction: try the largest fuse at each node
    first so HashAgg(Sel(Scan)) becomes one fragment, not a CPU agg over a
    fragment filter. Join trees (the Q3/Q5 shape) fuse through
    tree_fragment when statically eligible."""
    if fragment_ok(plan, threshold):
        frag = PhysTpuFragment(plan)
        frag.est_rows = plan.est_rows
        return frag
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
    heavy = (PhysHashJoin, PhysIndexLookupJoin, PhysMergeJoin, PhysHashAgg,
             PhysStreamAgg, PhysSort, PhysTopN, PhysWindow)

    def walk(node, under):
        if isinstance(node, PhysTpuFragment):
            return
        if isinstance(node, PhysTableScan) and under is not None and \
                getattr(node, "est_rows", 0.0) >= threshold:
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
# Join trees
# ---------------------------------------------------------------------------

JOIN_KINDS = ("inner", "left", "right", "semi", "anti")


def has_join(plan: PhysicalPlan) -> bool:
    if isinstance(plan, PhysHashJoin):
        return True
    return any(has_join(c) for c in plan.children)


def has_window(plan: PhysicalPlan) -> bool:
    if isinstance(plan, PhysWindow):
        return True
    return any(has_window(c) for c in plan.children)


def _string_key_ok(l: Expression, r: Expression) -> bool:
    """String equi keys must be bare ColumnRefs (so the probe side's codes
    can be dictionary-remapped into the build side's space) with MATCHING
    collation classes — a mixed ci/binary pair would fold one side's
    dictionary out of sorted order (and can merge two binary codes into
    one fold class), so it runs on the CPU engine instead."""
    if not (l.ftype.kind.is_string or r.ftype.kind.is_string):
        return True
    if l.ftype.is_ci != r.ftype.is_ci:
        return False
    return isinstance(l, ColumnRef) and isinstance(r, ColumnRef)


def nested_fragments(plan: PhysicalPlan) -> List[PhysicalPlan]:
    """The device-rows fragments nested in this tree as join build sides,
    in walk_nodes order."""
    return [n for n in walk_nodes(plan)
            if isinstance(n, PhysTpuFragment) and n.device_rows]


def device_rows_ok(agg: PhysicalPlan, threshold: int) -> bool:
    """Can this aggregate run as a fragment of its own whose merged groups
    stay on the device, as a join's build side? It must be a device
    fragment in its own right (chain or tree, over a scan that clears the
    row threshold), grouped, and every output column must finalize
    in-trace: plain keys (no dictionary to carry across), and
    count/sum/avg/min/max over narrow non-string results."""
    if not isinstance(agg, PhysHashAgg) or not agg.group_exprs or \
            getattr(agg, "rollup", False):
        return False
    if any(e.ftype.kind.is_string or e.ftype.is_wide_decimal
           for e in agg.group_exprs):
        return False
    for d in agg.aggs:
        if d.distinct or d.name not in ("count", "sum", "avg", "min", "max"):
            return False
        if d.ftype.kind.is_string:
            return False
        # of the wide results only a SUM over a 1-D argument has a 1-D
        # final (AggFunc.final_narrow, checked at run time to fit)
        if d.ftype.is_wide_decimal and not (
                d.name == "sum" and build_agg(d).orders_in_trace):
            return False
    return fragment_ok(agg, threshold) or tree_ok(agg, threshold)


def nest_build_aggregates(plan: PhysicalPlan, threshold: int) -> None:
    """Inside a tree that tree_ok admitted: wrap each aggregate that is a
    semijoin's build side (under its HAVING selection and projection) in a
    nested device-rows fragment."""
    for node in walk_nodes(plan):
        if not (isinstance(node, PhysHashJoin) and node.kind == "semi"
                and node.build_right):
            continue
        above = node
        below = node.children[1]
        while isinstance(below, (PhysSelection, PhysProjection)):
            above, below = below, below.children[0]
        if isinstance(below, PhysHashAgg) and \
                device_rows_ok(below, threshold):
            frag = PhysTpuFragment(below)
            frag.est_rows = below.est_rows
            frag.device_rows = True
            above.children[1 if above is node else 0] = frag


def tree_ok(plan: PhysicalPlan, threshold: int) -> bool:
    """Static eligibility of a join tree (runtime checks catch the rest)."""
    max_scan = [0.0]

    def walk(node: PhysicalPlan, is_root: bool, build: bool = False) -> bool:
        # `build`: inside a semijoin's build side, where an aggregate may
        # run as a nested fragment whose groups stay on the device
        if isinstance(node, PhysTpuFragment):
            return node.device_rows
        if build and isinstance(node, PhysHashAgg):
            return device_rows_ok(node, threshold)
        # an order root over the agg sorts by refs into the agg's row,
        # which order_over_agg_ok judges below
        if not (is_root and strip_order_root(node)[0] is not None) and \
                not exprs_device_ok(stage_exprs(node),
                                     wide_refs_ok=build):
            return False
        if isinstance(node, PhysTableScan):
            max_scan[0] = max(max_scan[0], getattr(node, "est_rows", 0.0))
            return True
        if isinstance(node, PhysSelection):
            return walk(node.children[0], False, build)
        if isinstance(node, PhysProjection):
            if not string_exprs_are_refs(node.exprs):
                return False
            return walk(node.children[0], False, build)
        if isinstance(node, PhysHashJoin):
            if node.kind not in JOIN_KINDS or not node.equi:
                return False
            # probe-anchored output ⇒ the preserved side must be the probe
            if node.kind in ("left", "semi", "anti") and not node.build_right:
                return False
            if node.kind == "right" and node.build_right:
                return False
            for le, re in node.equi:
                if not _string_key_ok(le, re):
                    return False
            return walk(node.children[0], False) and \
                walk(node.children[1], False,
                     node.kind == "semi" and node.build_right)
        if is_root and isinstance(node, PhysHashAgg):
            if getattr(node, "rollup", False) and \
                    any(d.distinct for d in node.aggs):
                return False    # DISTINCT+ROLLUP stays on the host oracle
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
                if not string_exprs_are_refs(desc.args):
                    return False    # string agg args read dict codes
            if not string_exprs_are_refs(node.group_exprs):
                return False
            return walk(node.children[0], False)
        if is_root and isinstance(node, (PhysTopN, PhysSort)):
            if not string_exprs_are_refs(node.by):
                return False
            child = node.children[0]
            while identity_projection(child) and child.children:
                child = child.children[0]
            if isinstance(child, PhysHashAgg):
                # ORDER BY / TopN over the agg (identity projections are
                # transparent): the driver strips the order root and runs
                # it as the agg's fused device finalize
                # (device_emit.emit_finalize), so the agg keeps its root
                # role here
                if not order_over_agg_ok(node, child):
                    return False
                return walk(child, True)
            return walk(node.children[0], False)
        if isinstance(node, PhysWindow):
            # root OR interior: interior windows compute their columns
            # in-trace (TreeProgram._emit) and feed the operator above —
            # the TopN-over-ROW_NUMBER / agg-over-window shapes
            return window_device_ok(node) and walk(node.children[0], False)
        if is_root and isinstance(node, PhysLimit):
            # LIMIT over a join: the program emits the first offset+count
            # live rows in probe row order (device_emit.emit_root)
            return node.count is not None and walk(node.children[0], False)
        return False

    # joinless trees are admitted when a window makes the tree program
    # worthwhile (mid-chain windows have no linear-chain lowering)
    return walk(plan, True) and (has_join(plan) or has_window(plan)) \
        and max_scan[0] >= threshold


def dist_ok(plan: PhysicalPlan, threshold: int) -> bool:
    """Eligibility for the multi-shard (shard_map) compilation: the same
    operator allowlist as tree_ok, but joins are optional (a linear Q1
    chain distributes as shard-partials + owned final merge). Reducible
    roots (agg/TopN/Sort) merge across shards; window roots repartition on
    their partition keys; selection/projection/join roots emit per-shard
    rows the host concatenates. String join keys work because the dist
    executor unifies the key dictionaries host-side before sharding, so
    equal strings hash equal on every shard (the mpp repartition invariant
    of cophandler/mpp_exec.go:158-173)."""
    if isinstance(plan, PhysExchange):
        return False               # already fragmented
    if isinstance(plan, (PhysTopN, PhysSort)) and plan.children:
        below = plan.children[0]
        while identity_projection(below) and below.children:
            below = below.children[0]
        if isinstance(below, PhysHashAgg):
            # ORDER-over-agg: the mesh driver strips the order root
            # before compiling (the shard program computes the agg; the
            # host orders after the merge) — eligibility is the agg's
            return dist_ok(below, threshold)
    if isinstance(plan, PhysHashAgg):
        if getattr(plan, "rollup", False):
            return False    # super-aggregate levels don't shard-merge yet
        if any(d.distinct for d in plan.aggs):
            # DISTINCT distributes by re-keying the exchange so every
            # group (or every distinct value, for global aggs) is wholly
            # on one shard (the repartition trick of cophandler/
            # mpp_exec.go); a global agg needs all distinct args equal to
            # pick ONE key
            if not plan.group_exprs:
                if any(d.distinct and len(d.args) != 1
                       for d in plan.aggs):
                    return False    # tuple re-key has no single column
                dargs = {repr(d.args[0]) for d in plan.aggs
                         if d.distinct and d.args}
                if len(dargs) != 1:
                    return False
    elif isinstance(plan, PhysWindow):
        pass        # the per-window spec check below covers the root too
    elif not isinstance(plan, (PhysTopN, PhysSort, PhysSelection,
                               PhysProjection, PhysHashJoin)):
        return False
    # per-shard windows need every partition wholly on one shard: all
    # specs must share ONE non-empty bare-ColumnRef partition list so a
    # single hash exchange directly below the window co-locates them
    # (insert_exchanges). Above the window only row-wise projections are
    # distributable (window root, or the select list over it) — a
    # reducing ancestor (agg/TopN/join) would need its own repartition
    # point mid-tree
    def _windows_ok(n, proj_chain):
        if isinstance(n, PhysWindow):
            if not proj_chain:
                return False
            parts = {repr(d.partition) for d in n.wdescs}
            if len(parts) != 1 or not n.wdescs[0].partition:
                return False
            if not all(isinstance(e, ColumnRef)
                       for e in n.wdescs[0].partition):
                return False
            proj_chain = False       # no second window below the first
        elif not isinstance(n, PhysProjection):
            proj_chain = False
        return all(_windows_ok(c, proj_chain) for c in n.children)

    if not _windows_ok(plan, True):
        return False
    # wide-decimal COLUMNS can't shard (the dist scan encoder is 1-D);
    # wide RESULTS over narrow/computed args are fine — limb states
    # all_gather as ordinary 1-D planes
    if isinstance(plan, PhysHashAgg) and any(
            isinstance(sub, ColumnRef) and sub.ftype.is_wide_decimal
            for d in plan.aggs for a in d.args for sub in a.walk()):
        return False
    if has_join(plan) or has_window(plan):
        # windowed shapes compile as tree programs (mirrors the
        # single-device dispatch in fragment.py)
        return tree_ok(plan, threshold)
    return fragment_ok(plan, threshold)


def scans_of(plan: PhysicalPlan) -> List[PhysTableScan]:
    if isinstance(plan, PhysTableScan):
        return [plan]
    out: List[PhysTableScan] = []
    for c in plan.children:
        out.extend(scans_of(c))
    return out


# ---------------------------------------------------------------------------
# Join key preparation (string dictionary remap)
# ---------------------------------------------------------------------------


@dataclass(eq=False)
class KeyRemap(Expression):
    """Remaps the probe side's dictionary codes into the build side's
    dictionary space so string equi keys compare as integers.

    prepare() receives the JOIN's input dictionary list (left ++ right
    children) and computes a probe-code → build-code LUT host-side
    (one searchsorted of two sorted dictionaries); codes absent from the
    build dictionary map to -1, which matches nothing. The LUT ships as a
    traced input, so dictionary changes never recompile."""

    child: Expression            # side-local probe key (ColumnRef)
    my_flow_idx: int             # my column's index in the join flow (l++r)
    build_flow_idx: int          # build key column's index in the join flow
    ci: bool = False             # compare under a ci collation

    def __post_init__(self):
        self.ftype = self.child.ftype

    def children(self):
        return [self.child]

    def prepare(self, dictionaries):
        pdict = dictionaries[self.my_flow_idx] \
            if self.my_flow_idx < len(dictionaries) else None
        bdict = dictionaries[self.build_flow_idx] \
            if self.build_flow_idx < len(dictionaries) else None
        if pdict is None or bdict is None or len(bdict) == 0:
            return np.full(max(len(pdict) if pdict is not None else 0, 1),
                           -1, np.int32)
        if self.ci:
            # ci dictionaries are representatives sorted by fold
            # (chunk/device.encode_strings): match in fold space
            pdict = fold_ci_array(np.asarray(pdict, dtype=object))
            bdict = fold_ci_array(np.asarray(bdict, dtype=object))
        pos = np.searchsorted(bdict, pdict)
        pos_c = np.clip(pos, 0, len(bdict) - 1)
        hit = bdict[pos_c] == pdict
        return np.where(hit, pos_c, -1).astype(np.int32)

    def eval(self, ctx: EvalContext):
        lut = ctx.prepared.get(id(self))
        if lut is None:
            raise AssertionError("KeyRemap without prepared LUT")
        xp = ctx.xp
        v, m = self.child.eval(ctx)
        n_lut = lut.shape[0]
        vc = xp.clip(v, 0, n_lut - 1).astype(xp.int32)
        out = xp.take(xp.asarray(lut), vc).astype(xp.int64)
        out = xp.where((v >= 0) & (v < n_lut), out, xp.int64(-1))
        return out, m

    def __repr__(self):
        return f"remap({self.child!r})"


def join_key_exprs(node: PhysHashJoin):
    """→ (build_keys, probe_keys) in equi order, coerced to a shared
    comparable domain, with probe-side string keys wrapped in KeyRemap.
    Memoized on the node (wrappers must be identical objects across the
    planner gate, prep collection, and trace)."""
    cached = getattr(node, "_dev_join_keys", None)
    if cached is not None:
        return cached
    nl = len(node.children[0].schema)
    bkeys: List[Expression] = []
    pkeys: List[Expression] = []
    for l, r in node.equi:
        lc, rc = coerce_key_pair(l, r)
        b, p = (rc, lc) if node.build_right else (lc, rc)
        if b.ftype.kind.is_string and isinstance(b, ColumnRef) \
                and isinstance(p, ColumnRef):
            b_flow = (nl if node.build_right else 0) + b.index
            p_flow = (0 if node.build_right else nl) + p.index
            p = KeyRemap(p, p_flow, b_flow,
                         ci=b.ftype.is_ci or p.ftype.is_ci)
        bkeys.append(b)
        pkeys.append(p)
    node._dev_join_keys = (bkeys, pkeys)
    return bkeys, pkeys


def stage_exprs(node: PhysicalPlan) -> List[Expression]:
    """Expressions this node evaluates against its input columns."""
    if isinstance(node, PhysHashJoin):
        bkeys, pkeys = join_key_exprs(node)
        return list(bkeys) + list(pkeys) + list(node.other_conditions or [])
    if isinstance(node, PhysExchange):
        return list(node.keys)
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


def walk_nodes(plan: PhysicalPlan) -> List[PhysicalPlan]:
    """Deterministic DFS (children first, left-to-right) — the structural
    order used for prep-value alignment across compile cache hits."""
    out: List[PhysicalPlan] = []

    def rec(n):
        for c in n.children:
            rec(c)
        out.append(n)

    rec(plan)
    return out


def walk_joins(plan: PhysicalPlan) -> List[PhysHashJoin]:
    return [n for n in walk_nodes(plan) if isinstance(n, PhysHashJoin)]
