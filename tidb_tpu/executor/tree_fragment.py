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

from tidb_tpu.executor import (compile_cache, device_cache, device_emit,
                               eligibility)
from tidb_tpu.executor.eligibility import (scans_of, stage_exprs, walk_joins,
                                           walk_nodes)
from tidb_tpu.expression import ColumnRef, EvalContext, Expression, ranges
from tidb_tpu.expression.aggfuncs import build_agg
from tidb_tpu.ops import join as J
from tidb_tpu.ops import segment as seg
from tidb_tpu.ops.factorize import KeyBounds, bounds_sig, choose_key_bounds
from tidb_tpu.ops.jax_env import jnp
from tidb_tpu.planner.physical import (PhysExchange, PhysHashAgg,
                                       PhysHashJoin, PhysLimit,
                                       PhysProjection, PhysSelection,
                                       PhysSort, PhysTableScan, PhysTopN,
                                       PhysTpuFragment, PhysWindow,
                                       PhysicalPlan)
from tidb_tpu.util.escalation import pow2

JOIN_DOMAIN_CAP = 1 << 25      # max packed build-key domain for LUT joins
# Beyond the masked reduce's slot count a directly addressed partial is an
# int64 scatter-add per state — 1.1 s a state and 8M-row slab on a v5e
# (a GROUP BY over 150K customer keys at SF=1 took 2.9 s so, against
# 0.9 s at SF=8 where its 1.2M keys already grouped by sorted runs:
# PERF.md §6, PR 28). So a
# wider key domain groups by sorted runs wherever the aggregates allow.
SLOT_ADDRESS_CAP = 1024


def aligned_chain(build: PhysicalPlan
                  ) -> Tuple[Optional[PhysTableScan], List[PhysHashJoin]]:
    """The build subtree's probe-chain anchor scan — the scan an aligned
    join substitutes with FK-aligned fact-rowspace columns — plus every
    join crossed on the way (outermost first). Follows Sel/Proj and each
    nested join's PROBE child (the rowspace-preserving side). The ONE
    traversal both the planner (agg_slabs.plan_aligned_joins) and the
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
    # the tree runs in K passes whose root agg states merge host-side — a
    # many-to-many fan-out beyond `tidb_tpu_join_out_cap` never leaves the
    # device
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
        return d_replace(cfg, out_cap=pow2(total, lo=1024)), "resize"
    return None, None


def bounds_list(node: PhysicalPlan, scan_bounds, quantities: bool = False
                 ) -> List[Optional[Tuple[int, int]]]:
    """Per output column (lo, hi) value bounds, traced from the device
    cache's per-scan-column stats; schema-length list, None = unbounded.
    For keys (group, join) a column is bounded where it IS a scan column,
    whatever it holds (a dictionary's codes too). With `quantities` the
    bounds are those of scaled integers alone and a computed projection
    is bounded by interval arithmetic (expression/ranges): what an
    aggregate's summed argument can hold."""
    if isinstance(node, (PhysTableScan, PhysTpuFragment)):
        # (a nested fragment's rows bring the bounds of their group keys)
        b = scan_bounds.get(id(node), {})
        if quantities:
            return ranges.column_ranges(node.schema.field_types, b)
        return [b.get(i) for i in range(len(node.schema))]
    if isinstance(node, (PhysSelection, PhysExchange)):
        return bounds_list(node.children[0], scan_bounds, quantities)
    if isinstance(node, PhysProjection):
        inp = bounds_list(node.children[0], scan_bounds, quantities)
        if quantities:
            return [ranges.value_range(e, inp) for e in node.exprs]
        return [inp[e.index] if isinstance(e, ColumnRef)
                and e.index < len(inp) else None for e in node.exprs]
    if isinstance(node, PhysHashJoin):
        l = bounds_list(node.children[0], scan_bounds, quantities)
        r = bounds_list(node.children[1], scan_bounds, quantities)
        nl = len(node.children[0].schema)
        nr = len(node.children[1].schema)
        l = (l + [None] * nl)[:nl]
        r = (r + [None] * nr)[:nr]
        if node.kind in ("semi", "anti"):
            return l
        return l + r
    return [None] * len(node.schema)


def trace_scan_col(node: PhysicalPlan, idx: int):
    """Trace a column through Sel/Proj down to (scan, col) WITHOUT crossing
    joins (a join can duplicate rows, breaking uniqueness)."""
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
        hit = trace_scan_col(build, raw_keys[0].index)
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
    cfgs: List[JoinCfg] = []
    for node in walk_joins(root):
        bi = 1 if node.build_right else 0
        build = node.children[bi]
        bkeys, _ = eligibility.join_key_exprs(node)
        bb = bounds_list(build, scan_bounds)
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
        out_cap = pow2(int(est * 1.3), lo=1024) if mode == "expand" else 0
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
    inp = bounds_list(root.children[0], scan_bounds)
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
    return choose_key_bounds(
        out, domain, SLOT_ADDRESS_CAP, domain_cap,
        device_emit.sorted_runs_ok(root), ranges.agg_arg_bits(
            root, tuple((n, tuple(sorted(b.items())))
                        for n, b in scan_bounds.items()),
            lambda: bounds_list(root.children[0], scan_bounds, True)))


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
    for node in walk_nodes(plan):
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
        joins = walk_joins(plan)
        if join_cfgs is None:
            join_cfgs = [JoinCfg("unique") for _ in joins]
        self.join_cfgs = {id(n): c for n, c in zip(joins, join_cfgs)}
        self.join_order = {id(n): i for i, n in enumerate(joins)}
        self.scan_order = scans_of(plan)
        self.nested_order = {id(n): i
                             for i, n in enumerate(
                                 eligibility.nested_fragments(plan))}
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
        for node in walk_nodes(plan):
            for e in stage_exprs(node):
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
        for node, dicts in zip(walk_nodes(self.plan), flow_list):
            for e in stage_exprs(node):
                for sub in e.walk():
                    if type(sub).prepare is not Expression.prepare:
                        vals.append(sub.prepare(dicts))
        return vals

    # -- trace ---------------------------------------------------------------
    def _run(self, scan_inputs, scan_rows, prep_vals, aligned_inputs=(),
             ranges=None, nested=()):
        compile_cache.count_trace()     # once per TRACE (the retrace meter)
        # (of a stacked column: a table read whole lists its slabs here,
        # the anchor's one slab is indexed here — read where they lie)
        scan_inputs, scan_rows, aligned_inputs = device_cache.in_place(
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
        return EvalContext(jnp, cols, prepared=self._prepared,
                           on_device=True)

    def _emit(self, node: PhysicalPlan, scan_inputs, scan_rows):
        """→ (cols [(v,m) or None per schema position], live) for non-root
        nodes; root reductions are handled in _finish. The column list is
        ALWAYS schema-length so join concatenation stays positionally
        aligned (unused columns ride as None)."""
        if isinstance(node, PhysTableScan):
            sub = self._scan_sub.get(id(node))
            if sub is not None:
                # FK-aligned build scan: columns already live in the fact
                # row space; live starts from the match mask
                col_list, live = sub
                ctx = self._ctx(col_list)
                with device_emit.stage("filter"):
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
            with device_emit.stage("filter"):
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
            with device_emit.stage("filter"):
                for c in node.conditions:
                    v, m = c.eval(ctx)
                    live = live & (v != 0) & m
            return cols, live
        if isinstance(node, PhysProjection):
            cols, live = self._emit(node.children[0], scan_inputs, scan_rows)
            ctx = self._ctx(cols)
            with device_emit.stage("project"):
                return [e.eval(ctx) for e in node.exprs], live
        if isinstance(node, PhysHashJoin):
            return self._emit_join(node, scan_inputs, scan_rows)
        if isinstance(node, PhysWindow) and node is not self.plan:
            # interior window: compute the window columns in-trace and
            # hand them to the operator above (a window ROOT is emitted
            # by _finish via emit_root instead)
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
        with device_emit.stage("join_probe"):
            bkeys, pkeys = eligibility.join_key_exprs(node)
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
            jctx = self._ctx(joined)
            with device_emit.stage("join_probe"):
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
