"""Join-tree device fragments: scan→filter→join*→aggregate in ONE program.

Extends the linear-chain fragments (executor/fragment.py) to plan subtrees
containing equi hash joins — the TPC-H Q3/Q5 shape. The whole tree traces
into a single jitted XLA program per query: every table is lifted to HBM
once as padded slabs (executor/device_cache.py; multi-slab tables
concatenate inside the program), and the root reduction reuses the
factorize/segment machinery (executor/device_emit.py).

Join formulations (ops/join.py), chosen per join at execution time:

  * **LUT (perfect-hash)** when the build keys are plan-traceable to scan
    columns with cached (lo, hi) bounds and the packed domain is small —
    true for every TPC-H PK-FK key and for all dictionary-encoded string
    columns. Build = one scatter, probe = one gather; no sort.
  * **Sort + searchsorted** otherwise (the general sort-merge join,
    the TPU answer to executor/hash_table.go:110).

  * **unique mode** (PK-FK bet): probe-shaped output, no expansion. The
    bet is placed from table metadata (single-column primary key / unique
    index on the build key) or the planner's join-size estimate, and
    guarded by a runtime `unique` flag — a lost bet re-traces that join in
    expand mode (one recompile), it never falls back to CPU.
  * **expand mode**: duplicate build keys materialize via prefix-sum
    offsets into a static `out_cap`-shaped batch; the true total comes
    back with the result, so capacity overflow also retries exactly once.

Outer joins must preserve the PROBE side (kind='left' requires
build_right, 'right' requires build-left): both modes emit probe-anchored
output, so build rows that match nothing cannot be null-extended. String
equi keys are supported by remapping the probe side's dictionary codes
into the build side's dictionary space host-side (`KeyRemap` — one
searchsorted over the two sorted dictionaries per query, shipped as a
prepared LUT input).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from tidb_tpu.expression import ColumnRef, EvalContext, Expression
from tidb_tpu.expression.aggfuncs import build_agg
from tidb_tpu.ops.factorize import KeyBounds, bounds_sig
from tidb_tpu.planner.physical import (PhysHashAgg, PhysHashJoin,
                                       PhysLimit, PhysProjection,
                                       PhysSelection, PhysSort,
                                       PhysTableScan, PhysTopN,
                                       PhysTpuFragment, PhysWindow,
                                       PhysicalPlan)

JOIN_KINDS = ("inner", "left", "right", "semi", "anti")
JOIN_DOMAIN_CAP = 1 << 25      # max packed build-key domain for LUT joins
JOIN_OUT_CAP = 1 << 26         # max expand-mode output rows (HBM guard)


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
    in _walk_nodes order."""
    return [n for n in _walk_nodes(plan)
            if isinstance(n, PhysTpuFragment) and n.device_rows]


def device_rows_ok(agg: PhysicalPlan, threshold: int) -> bool:
    """Can this aggregate run as a fragment of its own whose merged groups
    stay on the device, as a join's build side? It must be a device
    fragment in its own right (chain or tree, over a scan that clears the
    row threshold), grouped, and every output column must finalize
    in-trace: plain keys (no dictionary to carry across), and
    count/sum/avg/min/max over narrow non-string results."""
    from tidb_tpu.executor.fragment import _fragment_ok
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
    return _fragment_ok(agg, threshold) or tree_ok(agg, threshold)


def nest_build_aggregates(plan: PhysicalPlan, threshold: int) -> None:
    """Inside a tree that tree_ok admitted: wrap each aggregate that is a
    semijoin's build side (under its HAVING selection and projection) in a
    nested device-rows fragment."""
    for node in _walk_nodes(plan):
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
    from tidb_tpu.executor.fragment import _string_exprs_are_refs

    max_scan = [0.0]

    def walk(node: PhysicalPlan, is_root: bool, build: bool = False) -> bool:
        # `build`: inside a semijoin's build side, where an aggregate may
        # run as a nested fragment whose groups stay on the device
        if isinstance(node, PhysTpuFragment):
            return node.device_rows
        if build and isinstance(node, PhysHashAgg):
            return device_rows_ok(node, threshold)
        from tidb_tpu.executor.fragment import (_exprs_device_ok,
                                                _strip_order_root)
        # an order root over the agg sorts by refs into the agg's row,
        # which _order_over_agg_ok judges below
        if not (is_root and _strip_order_root(node)[0] is not None) and \
                not _exprs_device_ok(_stage_exprs(node),
                                     wide_refs_ok=build):
            return False
        if isinstance(node, PhysTableScan):
            max_scan[0] = max(max_scan[0], getattr(node, "est_rows", 0.0))
            return True
        if isinstance(node, PhysSelection):
            return walk(node.children[0], False, build)
        if isinstance(node, PhysProjection):
            if not _string_exprs_are_refs(node.exprs):
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
                if not _string_exprs_are_refs(desc.args):
                    return False    # string agg args read dict codes
            if not _string_exprs_are_refs(node.group_exprs):
                return False
            return walk(node.children[0], False)
        if is_root and isinstance(node, (PhysTopN, PhysSort)):
            if not _string_exprs_are_refs(node.by):
                return False
            from tidb_tpu.executor.fragment import (_identity_projection,
                                                    _order_over_agg_ok)
            child = node.children[0]
            while _identity_projection(child) and child.children:
                child = child.children[0]
            if isinstance(child, PhysHashAgg):
                # ORDER BY / TopN over the agg (identity projections are
                # transparent): the driver strips the order root and runs
                # it as the agg's fused device finalize
                # (device_emit.emit_finalize), so the agg keeps its root
                # role here
                if not _order_over_agg_ok(node, child):
                    return False
                return walk(child, True)
            return walk(node.children[0], False)
        if isinstance(node, PhysWindow):
            # root OR interior: interior windows compute their columns
            # in-trace (TreeProgram._emit) and feed the operator above —
            # the TopN-over-ROW_NUMBER / agg-over-window shapes
            from tidb_tpu.executor.fragment import _window_device_ok
            return _window_device_ok(node) and walk(node.children[0], False)
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
    from tidb_tpu.planner.physical import PhysExchange
    if isinstance(plan, PhysExchange):
        return False               # already fragmented
    if isinstance(plan, (PhysTopN, PhysSort)) and plan.children:
        from tidb_tpu.executor.fragment import _identity_projection
        below = plan.children[0]
        while _identity_projection(below) and below.children:
            below = below.children[0]
        if isinstance(below, PhysHashAgg):
            # ORDER-over-agg: _run_device_dist strips the order root
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
    return _chain_shape_ok(plan, threshold)


def _chain_shape_ok(plan: PhysicalPlan, threshold: int) -> bool:
    from tidb_tpu.executor.fragment import _fragment_ok
    return _fragment_ok(plan, threshold)


def _scans(plan: PhysicalPlan) -> List[PhysTableScan]:
    if isinstance(plan, PhysTableScan):
        return [plan]
    out: List[PhysTableScan] = []
    for c in plan.children:
        out.extend(_scans(c))
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
            from tidb_tpu.types import fold_ci_array
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
    from tidb_tpu.executor.join import coerce_key_pair
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


def _stage_exprs(node: PhysicalPlan) -> List[Expression]:
    from tidb_tpu.executor.fragment import _stage_exprs as chain_stage
    from tidb_tpu.planner.physical import PhysExchange
    if isinstance(node, PhysHashJoin):
        bkeys, pkeys = join_key_exprs(node)
        return list(bkeys) + list(pkeys) + list(node.other_conditions or [])
    if isinstance(node, PhysExchange):
        return list(node.keys)
    return chain_stage(node)


def _walk_nodes(plan: PhysicalPlan) -> List[PhysicalPlan]:
    """Deterministic DFS (children first, left-to-right) — the structural
    order used for prep-value alignment across compile cache hits."""
    out: List[PhysicalPlan] = []

    def rec(n):
        for c in n.children:
            rec(c)
        out.append(n)

    rec(plan)
    return out


def _walk_joins(plan: PhysicalPlan) -> List[PhysHashJoin]:
    return [n for n in _walk_nodes(plan) if isinstance(n, PhysHashJoin)]


def aligned_chain(build: PhysicalPlan
                  ) -> Tuple[Optional[PhysTableScan], List[PhysHashJoin]]:
    """The build subtree's probe-chain anchor scan — the scan an aligned
    join substitutes with FK-aligned fact-rowspace columns — plus every
    join crossed on the way (outermost first). Follows Sel/Proj and each
    nested join's PROBE child (the rowspace-preserving side). The ONE
    traversal both the planner (fragment._plan_aligned_joins) and the
    trace (_emit_join_aligned) use, so they cannot disagree on the
    anchor."""
    node = build
    crossed: List[PhysHashJoin] = []
    while True:
        if isinstance(node, PhysTableScan):
            return node, crossed
        if isinstance(node, (PhysSelection, PhysProjection)):
            node = node.children[0]
            continue
        if isinstance(node, PhysHashJoin):
            crossed.append(node)
            node = node.children[0 if node.build_right else 1]
            continue
        return None, crossed


def aligned_anchor(build: PhysicalPlan) -> Optional[PhysTableScan]:
    return aligned_chain(build)[0]


# ---------------------------------------------------------------------------
# Per-join execution configuration (planner bet + runtime adaptation)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class JoinCfg:
    mode: str                                # 'unique' | 'expand' | 'aligned'
    out_cap: int = 0                              # expand-mode output shape
    bounds: Optional[Tuple[Tuple[int, int], ...]] = None   # LUT key bounds
    domain: int = 0                               # LUT table size
    est: int = 0                                  # planner output estimate
    # aligned mode: build-scan columns arriving as FK-aligned device inputs
    # (executor/device_cache.AlignedJoin) — static, part of the trace
    aligned_cols: Optional[Tuple[int, ...]] = None
    # blocked expand: this join's probe anchor scan is row-range masked and
    # the tree runs in K passes whose root agg states merge host-side —
    # a many-to-many fan-out beyond JOIN_OUT_CAP never leaves the device
    blocked: bool = False


def escalate_join(cfg: JoinCfg, unique_ok: bool, total: int,
                  out_cap_max: int, flip_out_cap: int, ladder=None):
    """One rung of the join-capacity ladder, shared by the single-chip
    tree loop and the distributed loop (executor/fragment.py):

      * a lost unique bet flips the join to expand mode at
        `flip_out_cap` (the caller's estimate policy — global for the
        tree path, per-shard balanced share for the dist path);
      * an expand overflow resizes to the EXACT reported total (one
        recompile covers it) unless the total exceeds `out_cap_max`,
        where the caller escalates further (blocked multi-pass /
        fallback).

    → (new_cfg | None, action) with action in
      {None, "flip", "resize", "over-max"}; new_cfg is None unless the
    join must re-trace. A util/escalation.CapacityLadder passed as
    `ladder` gets the rung recorded on its per-query stats."""
    from dataclasses import replace as d_replace

    from tidb_tpu.executor.device_cache import _pow2
    if cfg.mode == "unique" and not unique_ok:
        if ladder is not None:
            ladder.flip("join")
        return d_replace(cfg, mode="expand", out_cap=flip_out_cap), "flip"
    if cfg.mode == "expand" and total > cfg.out_cap:
        if total > out_cap_max:
            if ladder is not None:
                ladder.stats.note("join", "over-max")
            return None, "over-max"
        if ladder is not None:
            ladder.stats.exact_resizes += 1
            ladder.stats.note("join", "exact")
        return d_replace(cfg, out_cap=_pow2(total)), "resize"
    return None, None


def _bounds_list(node: PhysicalPlan, scan_bounds, quantities: bool = False
                 ) -> List[Optional[Tuple[int, int]]]:
    """Per output column (lo, hi) value bounds, traced from the device
    cache's per-scan-column stats; schema-length list, None = unbounded.
    For keys (group, join) a column is bounded where it IS a scan column,
    whatever it holds (a dictionary's codes too). With `quantities` the
    bounds are those of scaled integers alone and a computed projection
    is bounded by interval arithmetic (expression/ranges): what an
    aggregate's summed argument can hold."""
    from tidb_tpu.expression import ranges
    from tidb_tpu.planner.physical import PhysExchange
    if isinstance(node, (PhysTableScan, PhysTpuFragment)):
        # (a nested fragment's rows bring the bounds of their group keys)
        b = scan_bounds.get(id(node), {})
        if quantities:
            return ranges.column_ranges(node.schema.field_types, b)
        return [b.get(i) for i in range(len(node.schema))]
    if isinstance(node, (PhysSelection, PhysExchange)):
        return _bounds_list(node.children[0], scan_bounds, quantities)
    if isinstance(node, PhysProjection):
        inp = _bounds_list(node.children[0], scan_bounds, quantities)
        if quantities:
            return [ranges.value_range(e, inp) for e in node.exprs]
        return [inp[e.index] if isinstance(e, ColumnRef)
                and e.index < len(inp) else None for e in node.exprs]
    if isinstance(node, PhysHashJoin):
        l = _bounds_list(node.children[0], scan_bounds, quantities)
        r = _bounds_list(node.children[1], scan_bounds, quantities)
        nl = len(node.children[0].schema)
        nr = len(node.children[1].schema)
        l = (l + [None] * nl)[:nl]
        r = (r + [None] * nr)[:nr]
        if node.kind in ("semi", "anti"):
            return l
        return l + r
    return [None] * len(node.schema)


def _trace_scan_col(node: PhysicalPlan, idx: int):
    """Trace a column through Sel/Proj down to (scan, col) WITHOUT crossing
    joins (a join can duplicate rows, breaking uniqueness)."""
    from tidb_tpu.planner.physical import PhysExchange
    while True:
        if isinstance(node, PhysTableScan):
            return node, idx
        if isinstance(node, (PhysSelection, PhysExchange)):
            node = node.children[0]
            continue
        if isinstance(node, PhysProjection):
            e = node.exprs[idx] if idx < len(node.exprs) else None
            if not isinstance(e, ColumnRef):
                return None
            idx = e.index
            node = node.children[0]
            continue
        return None


def _build_unique_hint(node: PhysHashJoin) -> bool:
    """Is the build side unique on the join key? Exact when the key is a
    single-column primary key / unique index; otherwise bet on the
    planner's join-size estimate (which already folds NDV stats in) —
    wrong bets cost one recompile, never wrong results."""
    bi = 1 if node.build_right else 0
    build = node.children[bi]
    raw_keys = [(r if node.build_right else l) for l, r in node.equi]
    if len(raw_keys) == 1 and isinstance(raw_keys[0], ColumnRef):
        hit = _trace_scan_col(build, raw_keys[0].index)
        if hit is not None:
            scan, idx = hit
            table = scan.table
            cols = getattr(table, "columns", [])
            if idx < len(cols):
                name = cols[idx].name.lower()
                pk = [c.lower() for c in (getattr(table, "primary_key", None)
                                          or [])]
                if pk == [name]:
                    return True
                for ix in getattr(table, "indexes", []):
                    if ix.unique and len(ix.columns) == 1 and \
                            ix.columns[0].lower() == name and \
                            getattr(ix, "state", "public") == "public":
                        # write-only uniqueness is not yet VALIDATED —
                        # the PK-FK bet may only trust public indexes
                        return True
    probe = node.children[1 - bi]
    return node.est_rows <= probe.est_rows * 1.05 + 16


def plan_join_configs(root: PhysicalPlan, scan_bounds) -> List[JoinCfg]:
    """Initial per-join configs in _walk_nodes order (the runtime adapts
    mode/out_cap from the flags the program reports)."""
    from tidb_tpu.executor.device_cache import _pow2
    cfgs: List[JoinCfg] = []
    for node in _walk_joins(root):
        bi = 1 if node.build_right else 0
        build = node.children[bi]
        bkeys, _ = join_key_exprs(node)
        bb = _bounds_list(build, scan_bounds)
        bounds: Optional[List[Tuple[int, int]]] = []
        domain = 1
        for e in bkeys:
            if isinstance(e, ColumnRef) and e.index < len(bb) \
                    and bb[e.index] is not None:
                lo, hi = bb[e.index]
                domain *= (hi - lo + 1)
                if domain > JOIN_DOMAIN_CAP:
                    bounds = None
                    break
                bounds.append((lo, hi))
            else:
                bounds = None
                break
        est = max(int(node.est_rows), 1)
        mode = "unique" if _build_unique_hint(node) else "expand"
        out_cap = _pow2(int(est * 1.3), lo=1024) if mode == "expand" else 0
        cfgs.append(JoinCfg(mode, out_cap,
                            tuple(bounds) if bounds else None,
                            domain if bounds else 0, est))
    return cfgs


def tree_agg_key_bounds(root: PhysicalPlan, scan_bounds,
                        domain_cap: int) -> Optional[KeyBounds]:
    """What an agg root over a tree reads from the cached bounds
    (ops/factorize.KeyBounds): group-key domains when every group key is
    a bounded column, and the lowering they allow
    (ops/factorize.choose_key_bounds), else sort factorize; and the
    widths of the summed arguments."""
    if not isinstance(root, PhysHashAgg) or not root.group_exprs:
        return None
    if getattr(root, "rollup", False):
        return None     # level tiling needs the sort factorize
    inp = _bounds_list(root.children[0], scan_bounds)
    out: Optional[List[Tuple[int, int]]] = []
    domain = 1
    for e in root.group_exprs:
        if not (isinstance(e, ColumnRef) and e.index < len(inp)
                and inp[e.index] is not None):
            out = None
            break
        lo, hi = inp[e.index]
        domain *= (hi - lo + 2)
        out.append((lo, hi))
    from tidb_tpu.executor import device_emit
    from tidb_tpu.executor.fragment import SLOT_ADDRESS_CAP
    from tidb_tpu.expression import ranges
    from tidb_tpu.ops.factorize import choose_key_bounds
    return choose_key_bounds(
        out, domain, SLOT_ADDRESS_CAP, domain_cap,
        device_emit.sorted_runs_ok(root), ranges.agg_arg_bits(
            root, tuple((n, tuple(sorted(b.items())))
                        for n, b in scan_bounds.items()),
            lambda: _bounds_list(root.children[0], scan_bounds, True)))


# ---------------------------------------------------------------------------
# Signature (compile cache key)
# ---------------------------------------------------------------------------


def tree_signature(plan: PhysicalPlan, caps: Dict[int, Tuple[int, int]],
                   group_cap: int, join_cfgs: Optional[Sequence[JoinCfg]] = None,
                   agg_key_bounds=None, scan_layouts=None) -> str:
    parts = ["tree", f"gcap={group_cap}",
             f"akb={bounds_sig(agg_key_bounds)}"]
    ji = 0
    si = 0
    for node in _walk_nodes(plan):
        if isinstance(node, PhysTableScan):
            cap = caps[id(node)]
            cap = cap if isinstance(cap, tuple) else (cap, 1)
            # compressed physical layouts change the scan's traced decode
            # (and its input pytree), so they key the compile cache
            lays = scan_layouts[si] if scan_layouts else ()
            si += 1
            # (a delta generation: base slabs + its raw delta slab)
            shape = f"{cap[0]}x{cap[1]}" + (f"+{cap[2]}" if len(cap) > 2
                                            else "")
            parts.append(
                f"Scan(id={node.table.id}, cap={shape}, "
                f"types={[str(ft) for ft in node.schema.field_types]}, "
                f"filters={node.filters!r}, "
                f"parts={getattr(node, 'partitions', None)}, "
                f"lay={[(i, l.sig()) for i, l in lays]})")
        elif isinstance(node, PhysHashJoin):
            cfg = join_cfgs[ji] if join_cfgs else None
            ji += 1
            # est is host-side-only (seeds the retry out_cap) — keep it out
            # of the cache key or estimate drift forces spurious recompiles
            cfg_s = (f"{cfg.mode},{cfg.out_cap},{cfg.bounds},{cfg.domain},"
                     f"{cfg.aligned_cols},{cfg.blocked}" if cfg else None)
            parts.append(f"Join({node.kind}, build_right={node.build_right},"
                         f" equi={node.equi!r}, "
                         f"other={node.other_conditions!r}, cfg={cfg_s})")
        elif isinstance(node, PhysTpuFragment):
            parts.append(
                f"Rows(cap={caps[id(node)]}, "
                f"types={[str(ft) for ft in node.schema.field_types]})")
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
            parts.append(f"{type(node).__name__}(by={node.by!r}, "
                         f"descs={node.descs}, "
                         f"k={getattr(node, 'count', None)}, "
                         f"off={getattr(node, 'offset', 0)})")
        elif isinstance(node, PhysWindow):
            parts.append(f"Window({node.wdescs!r})")
        elif isinstance(node, PhysLimit):
            parts.append(f"Limit(k={node.count}, off={node.offset})")
        elif type(node).__name__ == "PhysExchange":
            parts.append(f"Exch({node.kind}, keys={node.keys!r})")
    return "|".join(parts)


# ---------------------------------------------------------------------------
# The traced program
# ---------------------------------------------------------------------------


class TreeProgram:
    """One jitted program for a join tree (or a mega-slab chain). Inputs:
    per-scan column dicts (original column index → list of per-slab
    (values, validity) pairs) + per-scan per-slab row counts + positional
    prepared values.

    Unique-mode joins emit probe-shaped output (build rows gathered
    through the per-probe-row match index); expand-mode joins emit
    out_cap-shaped output via prefix-sum expansion. Downstream shapes stay
    static either way."""

    def __init__(self, plan: PhysicalPlan, caps: Dict[int, object],
                 group_cap: int,
                 join_cfgs: Optional[Sequence[JoinCfg]] = None,
                 agg_key_bounds=None, scan_layouts=None,
                 pairs_out: bool = False, pair_cap: int = 0,
                 kind: str = "tree", sig: str = ""):
        from tidb_tpu.ops.jax_env import named_jit, program_name
        self.plan = plan
        # what the profile and the `launch` spans call this program: its
        # kind (tree | partial_fused | dist) and its signature's digest
        self.name = program_name(kind, sig)
        # DISTINCT aggs under a multi-slab driver: the partial also emits
        # per-slab (group, value) pair sets (capped at pair_cap) so the
        # host can merge exact cross-slab distinct states
        self.pairs_out = pairs_out
        self.pair_cap = pair_cap
        # id(scan-node) → (slab capacity, n_slabs); plain ints accepted
        self.caps = {k: (v if isinstance(v, tuple) else (v, 1))
                     for k, v in caps.items()}
        self.group_cap = group_cap
        self.agg_key_bounds = agg_key_bounds
        joins = _walk_joins(plan)
        if join_cfgs is None:
            join_cfgs = [JoinCfg("unique") for _ in joins]
        self.join_cfgs = {id(n): c for n, c in zip(joins, join_cfgs)}
        self.join_order = {id(n): i for i, n in enumerate(joins)}
        self.scan_order = _scans(plan)
        self.nested_order = {id(n): i
                             for i, n in enumerate(nested_fragments(plan))}
        # per-scan-slot ((col, ColLayout), ...) pairs, parallel to
        # scan_order: compressed columns decode INSIDE the trace at the
        # scan emit — raw bytes never crossed PCIe
        self.scan_layouts = tuple(scan_layouts) if scan_layouts \
            else tuple(() for _ in self.scan_order)
        # blocked expand: the probe anchor scans whose rows are range-
        # masked per pass (derived from plan structure — deterministic)
        self.ranged_scans = set()
        for n, c in zip(joins, join_cfgs):
            if c.blocked:
                bi = 1 if n.build_right else 0
                anchor = aligned_anchor(n.children[1 - bi])
                if anchor is not None:
                    self.ranged_scans.add(id(anchor))
        if isinstance(plan, PhysHashAgg):
            self.aggs = [build_agg(d) for d in plan.aggs]
        self.prep_nodes: List[Expression] = []
        for node in _walk_nodes(plan):
            for e in _stage_exprs(node):
                for sub in e.walk():
                    if type(sub).prepare is not Expression.prepare:
                        self.prep_nodes.append(sub)
        self.run = named_jit(self._run, self.name)

    def collect_preps(self, flow_list: List[List]) -> List:
        """Prepared values in structural order.

        flow_list: per-node input dictionary lists in _walk_nodes order of
        the CALLER's (structurally identical) plan. Positional alignment —
        not node identity — because compile-cache hits reuse this program
        for fresh plan objects whose node ids differ."""
        vals = []
        for node, dicts in zip(_walk_nodes(self.plan), flow_list):
            for e in _stage_exprs(node):
                for sub in e.walk():
                    if type(sub).prepare is not Expression.prepare:
                        vals.append(sub.prepare(dicts))
        return vals

    # -- trace ---------------------------------------------------------------
    def _run(self, scan_inputs, scan_rows, prep_vals, aligned_inputs=(),
             ranges=None, nested=()):
        from tidb_tpu.executor.device_cache import in_place
        from tidb_tpu.executor.fragment import _count_trace
        _count_trace()        # once per TRACE — perf_smoke retrace meter
        # (of a stacked column: a table read whole lists its slabs here,
        # the anchor's one slab is indexed here — read where they lie)
        scan_inputs, scan_rows, aligned_inputs = in_place(
            (scan_inputs, scan_rows, aligned_inputs))
        self._prepared = {id(n): v
                          for n, v in zip(self.prep_nodes, prep_vals)
                          if v is not None}
        self._join_unique_flags = []
        self._join_totals = []
        self._aligned_inputs = aligned_inputs
        self._ranges = ranges         # (start, stop) for ranged scans
        # per nested fragment: its rows, (cols, live)
        self._nested = nested
        self._scan_sub = {}   # id(scan) → (cols, live0): FK-aligned build
        cols, live = self._emit(self.plan, scan_inputs, scan_rows)
        return self._finish(cols, live)

    def _ctx(self, cols):
        from tidb_tpu.ops.jax_env import jnp
        return EvalContext(jnp, cols, prepared=self._prepared,
                           on_device=True)

    def _emit(self, node: PhysicalPlan, scan_inputs, scan_rows):
        """→ (cols [(v,m) or None per schema position], live) for non-root
        nodes; root reductions are handled in _finish. The column list is
        ALWAYS schema-length so join concatenation stays positionally
        aligned (unused columns ride as None)."""
        from tidb_tpu.executor.device_emit import stage
        from tidb_tpu.ops.jax_env import jnp
        if isinstance(node, PhysTableScan):
            sub = self._scan_sub.get(id(node))
            if sub is not None:
                # FK-aligned build scan: columns already live in the fact
                # row space; live starts from the match mask
                col_list, live = sub
                ctx = self._ctx(col_list)
                with stage("filter"):
                    for f in node.filters:
                        v, m = f.eval(ctx)
                        live = live & (v != 0) & m
                return list(col_list), live
            slot = next(i for i, s in enumerate(self.scan_order)
                        if s is node)
            in_cols = scan_inputs[slot]
            # (slab_cap, n_slabs), or for a delta generation (slab_cap,
            # base slabs, capacity of the raw delta slab that follows)
            slab_cap, n_slabs, *dcap = self.caps[id(node)]
            dcap = dcap[0] if dcap else 0
            lays = dict(self.scan_layouts[slot]) \
                if slot < len(self.scan_layouts) else {}
            col_list: List = []
            for i in range(len(node.schema)):
                c = in_cols.get(i)
                lay = lays.get(i)
                if c is not None and lay is not None:
                    # compressed slab(s): traced decode (gather-free
                    # shift/mask, fused by XLA into the scan it feeds)
                    from tidb_tpu.executor import device_emit
                    if isinstance(c, (list, tuple)) and c and \
                            isinstance(c[0], tuple):
                        c = [device_emit.emit_decode(lay, s, slab_cap)
                             for s in c[:n_slabs]] + list(c[n_slabs:])
                    else:
                        c = device_emit.emit_decode(lay, c,
                                                    slab_cap * n_slabs)
                if c is None:
                    col_list.append(None)
                elif isinstance(c, (list, tuple)) and c and \
                        isinstance(c[0], tuple):
                    if len(c) == 1:
                        col_list.append(c[0][:2])
                    else:   # mega-slab: concatenate inside the program
                        # axis -1: rows are the LAST axis (wide-decimal
                        # limb columns are (n_limbs, cap) planes)
                        col_list.append(
                            (jnp.concatenate([s[0] for s in c], axis=-1),
                             jnp.concatenate([s[1] for s in c])))
                else:
                    col_list.append(c)
            rows = scan_rows[slot]
            total_cap = slab_cap * n_slabs + dcap
            iota = jnp.arange(total_cap, dtype=jnp.int32)
            if isinstance(rows, (tuple, list)):
                # a delta generation: one liveness mask a slab
                live = rows[0] if len(rows) == 1 else \
                    jnp.concatenate(list(rows))
            elif jnp.asarray(rows).ndim == 0:
                live = iota < jnp.asarray(rows)
            else:
                rows = jnp.asarray(rows)
                live = (iota % slab_cap) < jnp.take(rows, iota // slab_cap)
            if id(node) in self.ranged_scans:
                start, stop = self._ranges
                live = live & (iota >= start) & (iota < stop)
            ctx = self._ctx(col_list)
            with stage("filter"):
                for f in node.filters:
                    v, m = f.eval(ctx)
                    live = live & (v != 0) & m
            return col_list, live
        if isinstance(node, PhysTpuFragment):
            # a nested fragment's output rows, left in HBM by its own
            # programs (fragment._AggRowsProgram)
            cols, live = self._nested[self.nested_order[id(node)]]
            return list(cols), live
        if isinstance(node, PhysSelection):
            cols, live = self._emit(node.children[0], scan_inputs, scan_rows)
            ctx = self._ctx(cols)
            with stage("filter"):
                for c in node.conditions:
                    v, m = c.eval(ctx)
                    live = live & (v != 0) & m
            return cols, live
        if isinstance(node, PhysProjection):
            cols, live = self._emit(node.children[0], scan_inputs, scan_rows)
            ctx = self._ctx(cols)
            with stage("project"):
                return [e.eval(ctx) for e in node.exprs], live
        if isinstance(node, PhysHashJoin):
            return self._emit_join(node, scan_inputs, scan_rows)
        if isinstance(node, PhysWindow) and node is not self.plan:
            # interior window: compute the window columns in-trace and
            # hand them to the operator above (a window ROOT is emitted
            # by _finish via emit_root instead)
            from tidb_tpu.executor import device_emit
            cols, live = self._emit(node.children[0], scan_inputs,
                                    scan_rows)
            out = device_emit.emit_window_cols(self._ctx(cols), live,
                                               node, cols)
            return out, live
        if isinstance(node, (PhysHashAgg, PhysTopN, PhysSort, PhysWindow,
                             PhysLimit)):
            return self._emit(node.children[0], scan_inputs, scan_rows)
        raise AssertionError(f"unexpected node {type(node).__name__}")

    # -- join ---------------------------------------------------------------
    def _emit_join(self, node: PhysHashJoin, scan_inputs, scan_rows):
        from tidb_tpu.executor.device_emit import stage
        from tidb_tpu.ops.jax_env import jnp
        from tidb_tpu.ops import join as J
        cfg = self.join_cfgs[id(node)]
        if cfg.mode == "aligned":
            return self._emit_join_aligned(node, cfg, scan_inputs,
                                           scan_rows)
        lcols, llive = self._emit(node.children[0], scan_inputs, scan_rows)
        rcols, rlive = self._emit(node.children[1], scan_inputs, scan_rows)
        if node.build_right:
            bcols, blive, pcols, plive = rcols, rlive, lcols, llive
        else:
            bcols, blive, pcols, plive = lcols, llive, rcols, rlive
        with stage("join_probe"):
            bkeys, pkeys = join_key_exprs(node)
            bctx = self._ctx(bcols)
            # the probe ctx must see the JOIN flow for KeyRemap preps, but
            # KeyRemap evals its child against probe-side columns
            pctx = self._ctx(pcols)
            bk = [e.eval(bctx) for e in bkeys]
            pk = [e.eval(pctx) for e in pkeys]
            nb = blive.shape[0]

            if cfg.bounds is not None:
                bcode, bok = J.pack_bounded_codes(bk, cfg.bounds)
                pcode, pok = J.pack_bounded_codes(pk, cfg.bounds)
                bok = bok & blive
                pok = pok & plive
                if cfg.mode == "unique":
                    match_idx, matched, unique = J.lut_probe_unique(
                        bcode, bok, cfg.domain, pcode, pok)
                else:
                    start, count, order = J.lut_probe_multi(
                        bcode, bok, cfg.domain, pcode, pok)
            else:
                # shared exact code space: factorize over build++probe concat
                both = [(jnp.concatenate([jnp.asarray(bv), jnp.asarray(pv)]),
                         jnp.concatenate([jnp.asarray(bm), jnp.asarray(pm)]))
                        for (bv, bm), (pv, pm) in zip(bk, pk)]
                both_live = jnp.concatenate([blive, plive])
                codes, cvalid = J.combine_keys(both, both_live)
                if cfg.mode == "unique":
                    match_idx, matched, unique = J.sorted_probe_unique(
                        codes[:nb], cvalid[:nb], blive,
                        codes[nb:], cvalid[nb:], plive)
                else:
                    start, count, order = J.sorted_probe_multi(
                        codes[:nb], cvalid[:nb] & blive,
                        codes[nb:], cvalid[nb:] & plive)

            if cfg.mode == "unique":
                self._join_unique_flags.append(unique)
                self._join_totals.append(jnp.int64(0))
                return self._finish_join_unique(node, bcols, pcols, plive,
                                                match_idx, matched)
            self._join_unique_flags.append(jnp.bool_(True))
            return self._finish_join_expand(node, cfg, bcols, pcols, plive,
                                            start, count, order)

    def _emit_join_aligned(self, node: PhysHashJoin, cfg: JoinCfg,
                           scan_inputs, scan_rows):
        """FK-aligned join: the build side's columns arrive pre-gathered
        into the fact row space (device_cache.AlignedJoin), so the join is
        ZERO device work beyond evaluating the build side's filters on the
        aligned columns. Probe rowspace is preserved exactly — unique-mode
        semantics with an identity gather."""
        from tidb_tpu.ops.jax_env import jnp
        bi = 1 if node.build_right else 0
        build, probe = node.children[bi], node.children[1 - bi]
        ji = self.join_order[id(node)]
        matched_slabs, col_slabs = self._aligned_inputs[ji]
        matched = (matched_slabs[0] if len(matched_slabs) == 1
                   else jnp.concatenate(list(matched_slabs)))
        bscan = aligned_anchor(build)
        sub_cols = []
        for i in range(len(bscan.schema)):
            c = col_slabs.get(i)
            if c is None:
                sub_cols.append(None)
            elif len(c) == 1:
                sub_cols.append(c[0])
            else:
                sub_cols.append(
                    (jnp.concatenate([s[0] for s in c], axis=-1),
                     jnp.concatenate([s[1] for s in c])))
        pcols, plive = self._emit(probe, scan_inputs, scan_rows)
        self._scan_sub[id(bscan)] = (sub_cols, matched)
        try:
            bcols, bmatched = self._emit(build, scan_inputs, scan_rows)
        finally:
            del self._scan_sub[id(bscan)]
        self._join_unique_flags.append(jnp.bool_(True))
        self._join_totals.append(jnp.int64(0))

        joined = (list(pcols) + list(bcols) if node.build_right
                  else list(bcols) + list(pcols))
        if node.other_conditions:
            from tidb_tpu.executor.device_emit import stage
            jctx = self._ctx(joined)
            with stage("join_probe"):
                for cond in node.other_conditions:
                    v, m = cond.eval(jctx)
                    bmatched = bmatched & (v != 0) & m
        if node.kind == "semi":
            return list(pcols), plive & bmatched
        if node.kind == "anti":
            return list(pcols), plive & jnp.logical_not(bmatched)
        # null-extend build columns wherever the match (or its filters /
        # conditions) failed — correct for outer, harmless for inner
        bcols = [None if c is None else (c[0], c[1] & bmatched)
                 for c in bcols]
        joined = (list(pcols) + list(bcols) if node.build_right
                  else list(bcols) + list(pcols))
        if node.kind == "inner":
            return joined, plive & bmatched
        return joined, plive     # left/right outer: probe side preserved

    def _finish_join_unique(self, node, bcols, pcols, plive, match_idx,
                            matched):
        from tidb_tpu.ops.jax_env import jnp

        def gather_build(keep):
            out = []
            for c in bcols:
                if c is None:
                    out.append(None)
                    continue
                v, m = c
                out.append((jnp.take(jnp.asarray(v), match_idx),
                            jnp.take(jnp.asarray(m), match_idx) & keep))
            return out

        bgathered = gather_build(matched)
        if node.build_right:
            joined = list(pcols) + bgathered
        else:
            joined = bgathered + list(pcols)
        if node.other_conditions:
            jctx = self._ctx(joined)
            ok = jnp.ones_like(matched)
            for cond in node.other_conditions:
                v, m = cond.eval(jctx)
                ok = ok & (v != 0) & m
            matched = matched & ok
            if node.kind in ("left", "right"):
                # failed condition → unmatched: null-extend, keep the row
                bgathered = gather_build(matched)
                joined = (list(pcols) + bgathered if node.build_right
                          else bgathered + list(pcols))
        if node.kind == "semi":
            return list(pcols), plive & matched
        if node.kind == "anti":
            return list(pcols), plive & jnp.logical_not(matched)
        if node.kind == "inner":
            return joined, plive & matched
        # left/right outer: tree_ok guarantees probe == preserved side, so
        # every live probe row survives (null-extended when unmatched)
        return joined, plive

    def _finish_join_expand(self, node, cfg: JoinCfg, bcols, pcols, plive,
                            start, count, order):
        from tidb_tpu.ops.jax_env import jnp
        from tidb_tpu.ops import join as J
        from tidb_tpu.ops import segment as seg
        P = plive.shape[0]
        if node.kind in ("semi", "anti") and not node.other_conditions:
            self._join_totals.append(jnp.int64(0))
            matched = count > 0
            live = plive & (matched if node.kind == "semi"
                            else jnp.logical_not(matched))
            return list(pcols), live
        outer = node.kind in ("left", "right")
        p_idx, b_idx, matched, out_live, k, total = J.expand(
            start, count, order, cfg.out_cap, outer, plive)
        self._join_totals.append(total)

        def gather(cols, idx, keep):
            out = []
            for c in cols:
                if c is None:
                    out.append(None)
                    continue
                v, m = c
                out.append((jnp.take(jnp.asarray(v), idx),
                            jnp.take(jnp.asarray(m), idx) & keep))
            return out

        pcols_e = gather(pcols, p_idx, out_live)
        bcols_e = gather(bcols, b_idx, matched)
        joined = (pcols_e + bcols_e if node.build_right
                  else bcols_e + pcols_e)
        passing = matched
        if node.other_conditions:
            jctx = self._ctx(joined)
            ok = jnp.ones_like(matched)
            for cond in node.other_conditions:
                v, m = cond.eval(jctx)
                ok = ok & (v != 0) & m
            passing = matched & ok
        if node.kind in ("semi", "anti"):
            pass_any = seg.segment_any(jnp, passing & out_live, p_idx, P)
            live = plive & (pass_any if node.kind == "semi"
                            else jnp.logical_not(pass_any))
            return list(pcols), live
        if node.kind == "inner":
            return joined, out_live & passing
        # outer: every live probe row keeps ≥1 slot; a probe row none of
        # whose matches pass emits ONE null-extended row (its first slot)
        pass_cnt = seg.segment_count(jnp, passing & out_live, p_idx, P)
        keep_extended = (k == 0) & (jnp.take(pass_cnt, p_idx) == 0)
        live = out_live & (passing | keep_extended)
        if node.other_conditions:
            # null-extend build cols on slots whose condition failed
            bcols_e = gather(bcols, b_idx, passing)
            joined = (pcols_e + bcols_e if node.build_right
                      else bcols_e + pcols_e)
        return joined, live

    # -- root reductions ------------------------------------------------------
    def _finish(self, cols, live):
        from tidb_tpu.ops.jax_env import jnp
        from tidb_tpu.executor import device_emit
        root = self.plan
        flags = self._join_unique_flags
        out_flags = {
            "join_unique": (jnp.stack(flags) if flags
                            else jnp.zeros(0, dtype=bool)),
            "join_totals": (jnp.stack(self._join_totals)
                            if self._join_totals
                            else jnp.zeros(0, dtype=jnp.int64)),
        }
        if isinstance(root, PhysHashAgg):
            ctx = self._ctx(cols)
            out = device_emit.emit_root(ctx, live, root, aggs=self.aggs,
                                        group_cap=self.group_cap,
                                        key_bounds=self.agg_key_bounds,
                                        pairs_out=self.pairs_out,
                                        pair_cap=self.pair_cap)
            out.update(out_flags)
            return out
        # non-agg roots emit every schema column; unused (None) positions
        # become all-NULL placeholders so output stays positionally aligned
        n = live.shape[0]
        cols = [(jnp.zeros(n, dtype=jnp.int64), jnp.zeros(n, dtype=bool))
                if c is None else c for c in cols]
        ctx = self._ctx(cols)
        out = device_emit.emit_root(ctx, live, root)
        out.update(out_flags)
        return out

    def __call__(self, scan_inputs, scan_rows, prep_vals,
                 aligned_inputs=(), ranges=None, nested=()):
        if nested:
            return self.run(scan_inputs, scan_rows, prep_vals,
                            aligned_inputs, ranges, nested)
        if ranges is None:
            return self.run(scan_inputs, scan_rows, prep_vals,
                            aligned_inputs)
        return self.run(scan_inputs, scan_rows, prep_vals, aligned_inputs,
                        ranges)


def dictionary_flows(plan: PhysicalPlan,
                     scan_dicts: Dict[int, Dict[int, Optional[np.ndarray]]]
                     ) -> Tuple[Dict[int, List], List]:
    """Host-side mirror of the trace: per-node input dictionaries and the
    root's output dictionary list. scan_dicts: id(scan) → {col_idx: dict}.
    Lists are schema-length, mirroring _emit's positional alignment."""
    flows: Dict[int, List] = {}

    def rec(node: PhysicalPlan) -> List:
        if isinstance(node, PhysTableScan):
            d = scan_dicts.get(id(node), {})
            out = [d.get(i) for i in range(len(node.schema))]
            flows[id(node)] = out
            return out
        if not node.children:      # a nested device-rows fragment
            out = [None] * len(node.schema)
            flows[id(node)] = out
            return out
        child_flows = [rec(c) for c in node.children]
        if isinstance(node, PhysHashJoin):
            l, r = child_flows
            nl = len(node.children[0].schema)
            nr = len(node.children[1].schema)
            l = (l + [None] * nl)[:nl]
            r = (r + [None] * nr)[:nr]
            if node.kind in ("semi", "anti"):
                out = l       # semi/anti emit the left (probe) side
            else:
                out = l + r
            flows[id(node)] = l + r
            return out
        inp = child_flows[0]
        flows[id(node)] = inp
        # PhysExchange: pure redistribution, dictionaries pass through
        if isinstance(node, PhysProjection):
            return [inp[e.index] if isinstance(e, ColumnRef)
                    and e.index < len(inp) else None for e in node.exprs]
        if isinstance(node, PhysHashAgg):
            out = []
            for e in node.group_exprs:
                out.append(inp[e.index] if isinstance(e, ColumnRef)
                           and e.index < len(inp) else None)
            out.extend([None] * len(node.aggs))
            return out
        if isinstance(node, PhysWindow):
            return inp + [None] * len(node.wdescs)
        return inp

    root_out = rec(plan)
    return flows, root_out
