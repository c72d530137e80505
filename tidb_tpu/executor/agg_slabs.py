"""The aggregate main path: every per-slab partial aggregate, over a chain
or a join tree, runs through `run_agg_slabs` (`_run_agg_slabs` in notes
older than PR 47) over a `_SlabSource` — and nothing else lives here.

A fragment is a maximal device-capable subtree fused into jitted XLA
programs — the analog of the coprocessor DAG the reference pushes to
storage (SURVEY A.2: unistore's closure executor fuses scan→selection→agg
into a single callback, closure_exec.go; plan_to_pb.go ships subtrees to
TiFlash). Fusion at fragment granularity is the whole game on TPU: one
host→HBM transfer, one compiled program, no per-operator launch/transfer
overhead (SURVEY §7 "host↔device bandwidth").

Execution model:
  * the scan side lives in the device cache (executor/device_cache.py):
    string columns dictionary-encoded ONCE (unified, sorted dictionary →
    codes are rank order, so ORDER BY / range predicates work on codes),
    rows padded into fixed power-of-two slabs so XLA sees a small set of
    static shapes; the logical row count rides along and becomes a `live`
    mask (the reference's sel vector / requiredRows, SURVEY §7 hard parts);
  * grouped aggregation is one of three lowerings (ops/factorize.KeyBounds)
    with a static group capacity; capacity overflow is detected via the
    returned n_groups and retried at the exact need (util/escalation.py);
  * filters never compact on device — they just narrow the live mask that
    every downstream kernel consumes (masking beats data movement).

What is here: the programs the driver launches (`_FragmentProgram`, the
fused pipeline's `TreeProgram` getters, merge / finalize / sort programs,
`_StatementProgram`), the per-digest specialization cache in front of the
compile cache, `_launch_plan`, the slab sources (`ChainSlabs`,
`TreeSlabs`), the FK-aligned join planning, the group-capacity and
key-bounds helpers, and the driver. What is NOT: whether a plan may run on
the device (eligibility.py), the compile cache (compile_cache.py), host
decode (host_decode.py), the mega-slab tree loop and blocked passes
(tree_driver.py, above), the mesh drivers (dist_fragment.py, above), and
the executor that dispatches to them (fragment.py, on top).
"""

from __future__ import annotations

import functools
import hashlib
import math
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from tidb_tpu.executor import (compile_cache, device_cache, device_emit,
                               eligibility, empty_chunk, host_decode,
                               scheduler, tree_fragment as TF)
from tidb_tpu.executor.eligibility import FragmentFallback, stage_exprs
from tidb_tpu.expression import ColumnRef, EvalContext, Expression, ranges
from tidb_tpu.expression.aggfuncs import AggFunc, build_agg
from tidb_tpu.ops import factorize
from tidb_tpu.ops.factorize import (FACTORIZE, RUNS, SLOTS, KeyBounds,
                                    bounds_sig, choose_key_bounds,
                                    grouping_mode, widths_sig)
from tidb_tpu.ops.jax_env import jax, jnp, lax
from tidb_tpu.planner.physical import (PhysHashAgg, PhysHashJoin,
                                       PhysProjection, PhysSelection,
                                       PhysSort, PhysTableScan, PhysTopN,
                                       PhysWindow, PhysicalPlan)
from tidb_tpu.sysvars import var_int
from tidb_tpu.types import FieldType
from tidb_tpu.util import failpoint, timeline
from tidb_tpu.util.escalation import pow2
from tidb_tpu.util.observability import REGISTRY, normalize_sql
from tidb_tpu.util.phases import tree_nbytes

# group caps at or below this ride the flag fetch (padded keys/states are
# a few MB) — the result then needs NO second device round trip
SMALL_GROUP_CAP = 1 << 14


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
        compile_cache.count_trace()
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
    sig = "aggrows|" + base_sig
    prog = compile_cache.get_or_build(sig, "fused",
                         lambda: _AggRowsProgram(agg_root, sig))
    ph = ctx.phases
    with scheduler.device_slot(ctx):
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


def chain_signature(chain: List[PhysicalPlan], used_cols: Sequence[int],
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


def used_column_indices(chain: List[PhysicalPlan]) -> List[int]:
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


class _FragmentProgram:
    """Traceable fragment: closures over the (first) plan's expression
    objects; later structurally-identical plans reuse the compiled XLA
    executable and only re-supply prepared host inputs positionally."""

    def __init__(self, chain: List[PhysicalPlan], used_cols: List[int],
                 in_types: List[FieldType], slab_cap: int, group_cap: int,
                 key_bounds=None, want_pairs: bool = False, layouts=None,
                 pair_cap: int = 0, sig: str = ""):
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
            for e in stage_exprs(node):
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
        (enforced by eligibility.fragment_ok), so the scan dictionaries
        survive every stage unchanged modulo index remapping.
        """
        return collect_chain_preps(self.chain, dicts_by_index)

    # -- traced stages -------------------------------------------------------
    def _eval_chain(self, cols, n_rows, prep_vals):
        """cols: dict index→(values, validity); returns (ctx_cols, live,
        root_node) after all mid-chain stages."""
        prepared = {id(node): v for node, v in zip(self.prep_nodes, prep_vals)
                    if v is not None}
        # a delta generation hands its slab's liveness MASK where a plain
        # one hands the length of its live prefix (executor/delta.py)
        n_rows = jnp.asarray(n_rows)
        live = n_rows if n_rows.dtype == jnp.bool_ else \
            jnp.arange(self.slab_cap, dtype=jnp.int32) < n_rows
        if self.layouts:
            cols = {i: (device_emit.emit_decode(self.layouts[i], t,
                                                self.slab_cap)
                        if self.layouts.get(i) is not None else t)
                    for i, t in cols.items()}
        max_idx = max(cols) if cols else -1
        col_list: List = [cols.get(i) for i in range(max_idx + 1)]
        ctx = EvalContext(jnp, col_list, prepared=prepared, on_device=True,
                          n_rows=self.slab_cap)
        for node in reversed(self.chain):
            if isinstance(node, PhysTableScan):
                with device_emit.stage("filter"):
                    for f in node.filters:
                        v, m = f.eval(ctx)
                        live = live & (v != 0) & m
            elif isinstance(node, PhysSelection):
                with device_emit.stage("filter"):
                    for c in node.conditions:
                        v, m = c.eval(ctx)
                        live = live & (v != 0) & m
            elif isinstance(node, PhysProjection):
                with device_emit.stage("project"):
                    new_cols = [e.eval(ctx) for e in node.exprs]
                ctx = EvalContext(jnp, new_cols, prepared=prepared,
                                  on_device=True, n_rows=self.slab_cap)
        return ctx, live

    def _partial(self, cols, n_rows, prep_vals):
        # A chain partial IS a fused pipeline: scan → filter/project →
        # root reduction in one trace.  The root dispatch lives in
        # device_emit.emit_root so the linear-chain, join-tree and fused
        # per-slab programs share one emit layer.
        compile_cache.count_trace()
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
        compile_cache.count_trace()
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
        for e in stage_exprs(node):
            for sub in e.walk():
                if type(sub).prepare is not Expression.prepare:
                    vals.append(sub.prepare(stage_dicts))
        if isinstance(node, PhysProjection):
            stage_dicts = [
                stage_dicts[e.index] if isinstance(e, ColumnRef)
                and e.index < len(stage_dicts) else None
                for e in node.exprs]
    return vals



def get_program(chain, used_cols, in_types, slab_cap, group_cap,
                key_bounds=None, want_pairs=False,
                layouts=None, pair_cap=0, sig=None) -> _FragmentProgram:
    """`sig` lets a specialization-cache hit skip signature construction
    entirely — valid because the spec key pins the same geometry, layout
    set and key bounds the signature would encode."""
    if sig is None:
        sig = chain_signature(chain, used_cols, in_types, slab_cap,
                               group_cap, key_bounds, layouts) + \
            f"|pairs={want_pairs},{pair_cap}"
    return compile_cache.get_or_build(sig, "chain", lambda: _FragmentProgram(
        chain, used_cols, in_types, slab_cap, group_cap, key_bounds,
        want_pairs, layouts, pair_cap, sig=sig))


def get_tree_program(root, caps, group_cap, join_cfgs=None,
                     agg_key_bounds=None, scan_layouts=None):
    sig = TF.tree_signature(root, caps, group_cap, join_cfgs, agg_key_bounds,
                         scan_layouts)
    return compile_cache.get_or_build(sig, "tree", lambda: TF.TreeProgram(
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
    if sig is None:
        sig = (f"fused|pairs={pairs_out},{pair_cap}|" +
               TF.tree_signature(root, caps, group_cap, join_cfgs,
                              agg_key_bounds, scan_layouts))
    prog = compile_cache.get_or_build(sig, "fused", lambda: TF.TreeProgram(
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
        compile_cache.count_trace()
        return device_emit.emit_merge(self.root, self.aggs, self.group_cap,
                                      key_cols, states, slot_live)


def get_merge_program(root, group_cap: int,
                      pipeline_sig: str) -> _AggMergeProgram:
    sig = "fusedmerge|" + pipeline_sig
    return compile_cache.get_or_build(sig, "fused",
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
        compile_cache.count_trace()
        with device_emit.stage("agg"):
            cat = jnp.concatenate
            return factorize.sort_rows([cat(w) for w in words], cat(lives),
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
        compile_cache.count_trace()
        return device_emit.emit_runs_finalize(
            self.agg_root, self.order_root, self.aggs, self.cap,
            self.key_bounds.bounds, self.key_dtypes, rows,
            self.key_bounds.arg_bits)


def sig_tag(kind: str, sig: str) -> str:
    """The `sig` tag of a `launch` span: `<kind>:<sig12>`."""
    return f"{kind}:{hashlib.sha1(sig.encode()).hexdigest()[:12]}"


def order_sig(order_root) -> str:
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
        compile_cache.count_trace()
        return device_emit.emit_finalize(self.agg_root, self.order_root,
                                         self.aggs, self.group_cap,
                                         key_cols, states, slot_live)


def get_finalize_program(agg_root, order_root, group_cap: int,
                         base_sig: str):
    """→ (program, sig). Cold builds charge the `compile:finalize`
    timeline lane; `base_sig` is the partial/pipeline signature so the
    finalize specializes per upstream shape."""
    sig = "fusedfinal|" + order_sig(order_root) + "|" + base_sig
    prog = compile_cache.get_or_build(sig, "finalize", lambda: _FusedFinalizeProgram(
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
    by: dict = {}
    for leaf in jax.tree.leaves(tree):
        key = _pack_key(leaf.dtype)
        by.setdefault(key, []).append(jnp.ravel(leaf).astype(key))
    return {key: jnp.concatenate(parts) for key, parts in by.items()}


def _unpack(packed: dict, like):
    """`_pack`'s vectors, on the host, cut back into the tree whose leaves'
    shapes and dtypes `like` holds, in the same order."""
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
    """A warm aggregate statement as ONE jitted call (`run_agg_slabs`,
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
        from tidb_tpu.ops.jax_env import named_jit, program_name
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
        compile_cache.count_trace()
        out, fetch = self._fetch(shared, base, delta, picks)
        return out, _pack(fetch)

    def _fetch(self, shared, base, delta, picks):
        """→ (the result, or None where it rides the fetch; the control
        tree): what `_run` packs."""
        partials = []       # each leaf with a leading axis of slabs
        if base is not None:
            # (no vector: a one-slab table, whose arrays are the slab)
            n_run = picks[0].shape[0] if picks else 1
            if picks:
                self.said = {"slab_pick": "index"}

            def turn(k):
                return self.body(shared, device_cache.in_place(base, picks, k))
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
            out = self.tail(*device_emit.partials_of([
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
    return compile_cache.get_or_build(sig, "stmt", lambda: _StatementProgram(
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
    with compile_cache.LOCK:
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
    with compile_cache.LOCK:
        _SPEC_CACHE[key] = ent
        while len(_SPEC_CACHE) > MAX_SPECIALIZATIONS:
            _SPEC_CACHE.popitem(last=False)


def _spec_note(ph, hit: bool) -> None:
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


def note_grouping(root: PhysHashAgg, key_bounds, group_cap: int) -> str:
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
    return min(cap, pow2(groups + groups // 8 + 16, lo=1024))


def count_agg_partial(grouping: str) -> None:
    """One program holding an aggregate's partial was launched (a slab's,
    or a statement program with every slab's)."""
    REGISTRY.inc("tidb_tpu_agg_partials_total", {"grouping": grouping})


def _launch_plan(src: "_SlabSource", spec, want_pairs: bool,
                 rows_mode: bool) -> str:
    """How `run_agg_slabs` issues a statement's device work, from what it
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


def initial_group_cap(root: PhysHashAgg, default_cap: int,
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
    want = int(root.est_rows * 1.5) + 16
    return min(pow2(want, lo=1024), max_cap)


DOMAIN_CAP = 1 << 20    # max packed group-key domain for perfect hashing


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


def chain_key_bounds(chain: List[PhysicalPlan], ent) -> Optional[KeyBounds]:
    """What the aggregate's programs read from the cached bounds
    (ops/factorize.KeyBounds). Per-group-key (lo, hi) domains when every
    key is a scan column with cached bounds, and the lowering they allow
    (ops/factorize.choose_key_bounds): a small packed domain addresses
    the group slots directly, a large one packs the keys into sort words
    for the sorted-runs grouping where the aggregates allow it; else
    sort factorize. And the widths of the summed arguments, by interval
    arithmetic from the scan's columns up through the chain's projections
    (tree_fragment.bounds_list, expression/ranges)."""
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
    return choose_key_bounds(
        bounds, domain, TF.SLOT_ADDRESS_CAP, DOMAIN_CAP,
        device_emit.sorted_runs_ok(root), ranges.agg_arg_bits(
            root, tuple(sorted(ent.bounds.items())),
            lambda: TF.bounds_list(chain[1], {id(chain[-1]): ent.bounds},
                                    True)))


def ent_layouts(ent, used):
    """col → ColLayout for the used columns that are stored compressed;
    None when every used column is raw (keeps signatures byte-identical
    to the pre-compression cache keys)."""
    lays = {i: ent.layouts.get(i) for i in used
            if ent.layouts.get(i) is not None}
    return lays or None


# ---------------------------------------------------------------------------
# FK-aligned joins, slab sources
# ---------------------------------------------------------------------------


def plan_aligned_joins(ctx, root, scans, ents):
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
                    slabs = device_cache.decoded_slabs(ent, idx)
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
                hit = TF.trace_scan_col(j.children[bi], b_out_idx)
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
        bkeys, pkeys = eligibility.join_key_exprs(jnode)
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
            for node in reversed(eligibility.walk_joins(root)):
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


def whole_cols(cols: dict) -> dict:
    """A scan's columns as a program that reads the table WHOLE takes
    them: every slab of each (`SlabColumn.whole`: a list, or a stacked
    column's stacks themselves — `TreeProgram._run` lists their slabs
    inside the trace; no statement slices one)."""
    return {i: col.whole() for i, col in cols.items()}


def whole_masks(alive):
    """A generation's liveness masks over every slab, likewise."""
    w = alive.whole()
    return tuple(w) if isinstance(w, list) else w


def whole_aligned(matched, jcols):
    """An aligned join's inputs over every fact slab, likewise."""
    def whole(col):
        w = col.whole() if len(col) else ()     # (`()`: no aligned join)
        return tuple(w) if isinstance(w, list) else w
    return whole(matched), {c: whole(sl) for c, sl in jcols.items()}


class _SlabSource:
    """What `run_agg_slabs` asks of the slabs it
    aggregates; how a slab's arguments are laid out stays in here. A chain
    (`ChainSlabs`) reads one table's slabs, streamed on first touch; a
    join tree (`TreeSlabs`) reads its probe anchor's slabs against whole
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


class ChainSlabs(_SlabSource):
    """The slabs of one table under a linear chain (Q1, Q6)."""

    kind = "chain"
    stmt_kind = "stmt_chain"

    def __init__(self, ctx, chain, ent, stream, used,
                 in_types, dicts, key_bounds, layouts, slab_ids):
        self.ctx = ctx
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
            device_cache.ctx_device(self.ctx)) \
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
        ent, used = self.ent, prog.used_cols
        if to_run is None:
            to_run = range(len(self.run_ids))
            slabs = slab_iter(ent, self.stream, used, self.run_ids)
        else:
            slabs = (slab_of(ent, self.run_ids[p], used) for p in to_run)
        # (slabs first: zip must run the stream past its last slab, where
        # it commits the upload)
        for (cols, _n), pos in zip(slabs, to_run):
            rid = self.run_ids[pos]
            p = self.dprog if rid == self.delta_id else prog
            live = ent.live_arg(rid)    # on the device since the version's
            # slot per slab DISPATCH: the streamed encode of the next slab
            # (inside slab_iter) runs slot-free, so a sibling's dispatch
            # interleaves with our host work
            with scheduler.device_slot(self.ctx):
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
        ent, used = self.ent, prog.used_cols
        ids, base = self._base_ids(), None
        picks = device_cache.SlabPicks(ent, ids, self.chain[-1].table.id)
        if ids:
            base = {i: picks.of(ent.dev[i]) for i in used}, picks.live()
        delta = (slab_of(ent, self.delta_id, used)[0],
                 ent.live_arg(self.delta_id)) \
            if self.delta_id in self.run_ids else None
        return prep_vals, base, delta, picks.vectors()


class TreeSlabs(_SlabSource):
    """The probe anchor's slabs under a join tree (Q3, Q5, Q10, Q18).

    Join build sides ride inside each per-slab program at their FULL
    (mega-slab) capacities — dimension tables, or FK-aligned columns
    already in the anchor's row space — so every launch joins a partition
    of the probe rows against complete build sides and the slab union of
    agg partials is exact for every join kind (tree_ok pins outer joins to
    preserve the probe side, the same argument that makes
    tree_driver's blocked row-range passes exact).

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
        # a zero row count IS zone maps' skip signal (set by tree_driver)
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
        self._launch_sig = sig_tag("fused", sig)
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
        of its ALIGNED joins (plan_aligned_joins re-anchored those to
        the fact row space via anchor_subs). An aligned join hanging
        off a non-aligned build subtree keeps its own fact scan's row
        space and passes its inputs through whole."""
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
        si = [None if i == a else whole_cols(cols)
              for i, cols in enumerate(self.scan_inputs)]
        sr = list(self.scan_rows)
        ai = tuple(((), {}) if len(matched) and id(jn) in spaced
                   else whole_aligned(matched, jcols)
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
            with scheduler.device_slot(self.ctx):
                with self.ctx.phases.launch(p.name, slab=s,
                                            sig=self._launch_sig):
                    part = p(si, sr, pv, ai, nested=nested)
            yield pos, part

    @staticmethod
    def _slab_body(prog, a: int, shared, slab):
        si, sr, pv, ai, nested = TreeSlabs._assemble(a, shared, slab)
        return prog._run(si, sr, pv, ai, None, nested)

    def statement_body(self, prog):
        return functools.partial(self._slab_body, prog, self.anchor_i)

    def statement_args(self, prog, prep_vals):
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
        jus, jts = self._join_flags(got)
        return any(
            TF.escalate_join(cfg, bool(jus[:, ji].all()),
                             int(jts[:, ji].max()), self.out_cap_max,
                             0)[1] is not None
            for ji, cfg in enumerate(self.join_cfgs))

    def escalate(self, got, ladder):
        n_run = len(self.run_ids)
        jus, jts = self._join_flags(got)
        retry, rerun = False, set()
        for ji, cfg in enumerate(self.join_cfgs):
            new_cfg, action = TF.escalate_join(
                cfg, bool(jus[:, ji].all()), int(jts[:, ji].max()),
                self.out_cap_max,
                flip_out_cap=pow2(int(cfg.est * 1.3), lo=1024),
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


def _runs_finalize(ctx, root, order_root, partials, n_slabs: int,
                   cap: int, key_bounds, base_sig: str, sorted_rows):
    """Grouping by sorted runs, after the slabs' `group_rows`
    partials: sort every slab's rows ONCE (the shared sort program;
    `sorted_rows` from an earlier round of the capacity ladder is
    reused, the ladder only resizes the finalize) and reduce the runs.
    → (out as a merge or fused finalize gives it, sorted_rows)."""
    ph = ctx.phases
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
            sp = compile_cache.get_or_build(sig, "fused",
                               lambda: _SortRowsProgram(sig))
            with scheduler.device_slot(ctx):
                with ph.launch(sp.name):
                    sorted_rows = sp.run(
                        [[p["words"][i] for p in parts]
                         for i in range(len(p0["words"]))],
                        [[p["payloads"][i] for p in parts]
                         for i in range(len(p0["payloads"]))],
                        [p["live"] for p in parts])
            ph.note_launch()
        fsig = ("runsfinal|" + (order_sig(order_root)
                                if order_root is not None else "-")
                + f"|cap={cap}|" + base_sig + widths_sig(key_bounds))
        fp = compile_cache.get_or_build(
            fsig, "finalize", lambda: _RunsFinalizeProgram(
                root, order_root, cap, key_bounds, fsig))
        with scheduler.device_slot(ctx):
            with ph.launch(fp.name, sig=sig_tag("fused-final", fsig)):
                out = fp.run(sorted_rows)
        ph.note_launch()
    return out, sorted_rows


def slab_of(ent, slab_idx: int, used: Sequence[int]):
    """One resident slab as a slab program takes it → (cols, liveness)."""
    # restrict to the program's used columns: a superset (uploaded by a
    # different query) would change the input pytree and force a retrace
    # (`at`: of a stacked column the stack and the slab's row — the
    # program reads the slab in place, nothing is sliced out for it)
    cols = {i: ent.dev[i].at(slab_idx) for i in used}
    return cols, ent.slab_live(slab_idx)

def slab_iter(ent, stream, used: Sequence[int], slab_ids=None):
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
            yield slab_of(ent, s, used)
    else:
        for s, cols in stream:
            yield {i: cols[i] for i in used}, ent.slab_live(s)
        if ent.delta_cap and (slab_ids is None
                              or ent.base_slabs in slab_ids):
            # the stream is the base's; the delta slab it committed
            # behind its last slab follows
            yield slab_of(ent, ent.base_slabs, used)


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
                sig_tag("fused-final", fsig), fsig)
    if n_run == 1:
        return None, None, None, None, sig
    mp = src.merge_program(prog, gcap, sig)
    return mp._merge, mp.merge, mp.merge_name, None, "merge|" + sig

def run_agg_slabs(src: _SlabSource, schema, gcap: int, order_root, ladder,
                  rows_on_device: bool = False):
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
    ctx, ph = src.ctx, src.ctx.phases
    root, key_bounds = src.root, src.key_bounds
    if not src.run_ids:
        # every slab pruned: ZERO launches — grouped agg → empty,
        # global agg → the CPU oracle's identity row (COUNT 0,
        # SUM/MIN/MAX NULL: the merge of zero passes)
        chunk = host_decode.merge_tree_agg_passes(ctx, schema, root, [],
                                                     src.dicts)
        if order_root is not None:
            chunk = host_decode.host_order(chunk, order_root, root.schema)
            chunk = host_decode.topn_slice(chunk, order_root)
        return chunk
    n_run, slab_cap = len(src.run_ids), src.slab_cap
    # multi-slab DISTINCT: the slab programs emit capped, deduped
    # (group, args...) pair sets the host merges exactly. A slab can't
    # emit more pairs than it has rows, so slab_cap is both the
    # default clamp and the ladder's hard ceiling (resize through
    # "pairs" rungs, never truncate)
    want_pairs = src.n_slabs > 1 and \
        any(d.distinct and d.args for d in root.aggs)
    pair_cap = min(var_int(ctx.vars, "tidb_tpu_distinct_pair_cap"),
                   slab_cap) if want_pairs else 0
    use_fin = order_root is not None
    # per-digest specialization (the cache's own comment, above): adopt
    # the caps, and whatever else the source learned, that an earlier
    # execution of this statement settled on, and reuse its signature.
    # The key pins the data token (writes invalidate), geometry and key
    # bounds — everything the signature would otherwise re-derive — and
    # NOT the layouts, which _spec_lookup compares to evict on drift
    skey = _spec_key(
        getattr(ctx, "guard", None), src.kind,
        src.geometry + (
            bounds_sig(key_bounds), want_pairs,
            order_sig(order_root) if use_fin else None,
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
        grouping = note_grouping(root, key_bounds, gcap)
        with timeline.span("frag.program", "frag"):
            prog, sig, prep_vals = src.program(
                0 if rows_mode else gcap, pair_cap, want_pairs,
                spec_sig)
        spec_sig = None
        if not rows_mode:
            tail, run_tail, tail_name, tail_tag, tail_sig = \
                _agg_tail(src, prog, order_root, gcap, sig, n_run)
        packed = None       # a statement program packs what is fetched
        for s, part in () if plan == "whole" else \
                src.launches(prog, prep_vals, to_run):
            stale, partials[s] = partials[s], part
            ph.note_launch()
            ph.note_fused()   # a chain partial IS a fused pipeline
            count_agg_partial(grouping)
            rows_in += src.rows(s)
            sorted_rows = None
            caps_ran[s] = gcap
            pcaps[s] = pair_cap
            pairs_cache[s] = None
            if stale is not None:
                compile_cache.tree_delete(stale)
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
                    ladder.attempt("pairs", GroupCapOverflow(worst))
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
            small = not rows_on_device and \
                gcap <= SMALL_GROUP_CAP
            sprog = get_statement_program(src, prog, n_run, tail,
                                          tail_sig, small, args)
            with scheduler.device_slot(ctx):
                with ph.launch(sprog.name,
                               sig=sig_tag("stmt", sprog.sig)):
                    out, packed = sprog.run(*args)
                    if sprog.said:
                        # (the first call of a program built here)
                        timeline.tag(**sprog.said)
                        sprog.said = None
            fetch = sprog.like
            ph.note_launch()
            ph.note_fused()
            count_agg_partial(grouping)
            rows_in += sum(src.rows(s) for s in range(n_run))
            caps_ran = [gcap] * n_run
        else:
            if rows_mode:
                out, sorted_rows = _runs_finalize(
                    ctx, root, order_root, partials, src.n_slabs, gcap,
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
                        scheduler.device_slot(ctx):
                    with ph.launch(tail_name, sig=tail_tag):
                        out = run_tail(*device_emit.partials_of(partials))
                ph.note_launch()
            with scheduler.device_slot(ctx), ph.glue():
                small = not rows_on_device and (
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
            compile_cache.tree_delete(out)
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
                compile_cache.tree_delete(p)
            if out is not partials[0]:
                compile_cache.tree_delete(out)
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
                ladder.attempt("group", GroupCapOverflow(need_cap))
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
                compile_cache.tree_delete(out)     # stale merge generation
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
    if rows_on_device:
        return _agg_rows(ctx, root, out, cap_out, sig, key_bounds)
    if root.group_exprs and n_final == 0:
        return empty_chunk(schema)
    host_pairs = None
    if want_pairs:
        host_pairs = {ai: [pairs_cache[s][ai]
                           for s in range(n_run)]
                      for ai in pairs_cache[0]} \
            if pairs_cache[0] else {}
    host_tree = (got["keys"], got["states"]) if small else None
    n_rows = int(got["no"]) if use_fin else n_final
    with ph.phase("decode"):
        chunk = host_decode.agg_chunk(ctx, schema, root, out, src.dicts,
                                      max(n_rows, 1), host_pairs,
                                      host_tree=host_tree)
    if use_fin:
        chunk = host_decode.topn_slice(chunk, order_root)
    return chunk


class GroupCapOverflow(Exception):
    """Factorize saw more groups than the program's cap. `need` carries
    the observed true count (0 = unknown) so the escalation ladder can
    resize to exact need instead of blind doubling."""

    def __init__(self, need: int = 0):
        super().__init__(f"group cap overflow (need {need})")
        self.need = int(need)
