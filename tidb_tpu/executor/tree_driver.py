"""The drivers of what the per-slab aggregate driver cannot run: join
trees (and multi-slab chains: windows) as ONE mega-slab program whose
tables concatenate inside the trace, and its over-max rung, the blocked
multi-pass expand. No benchmark cell runs either (PERF.md §7): an
aggregate over a join tree is `agg_slabs.run_agg_slabs` over `TreeSlabs`,
built here, and only a non-aggregate root, `tidb_tpu_fused_pipeline = off`
or a join fan-out beyond `tidb_tpu_join_out_cap` reaches the loop below.

Above agg_slabs.py (whose driver it calls) and tree_fragment.py (whose
`TreeProgram` both launch); `TpuFragmentExec` (fragment.py) dispatches
here and is handed back as `ex` for its context, plan and schema and for
the nested fragments a tree runs first (`ex.run_nested`).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from tidb_tpu.chunk import Chunk
from tidb_tpu.executor import (agg_slabs as A, device_cache, eligibility,
                               empty_chunk, host_decode, scheduler,
                               tree_fragment as TF, zonemap)
from tidb_tpu.executor.eligibility import (FragmentFallback,
                                           nested_fragments, scans_of,
                                           strip_order_root, walk_nodes)
from tidb_tpu.ops.factorize import RUNS, SLOTS, grouping_mode
from tidb_tpu.ops.jax_env import jax
from tidb_tpu.planner.physical import (PhysHashAgg, PhysHashJoin,
                                       PhysLimit, PhysSort, PhysTopN)
from tidb_tpu.sysvars import var_int, var_on
from tidb_tpu.util import timeline
from tidb_tpu.util.escalation import CapacityLadder, pow2
from tidb_tpu.util.phases import tree_nbytes

def _piggyback_agg(fetch: dict, out, group_cap: int) -> bool:
    if group_cap <= A.SMALL_GROUP_CAP:
        fetch["keys"] = out["keys"]
        fetch["states"] = out["states"]
        return True
    return False


def run_device_tree(ex, plain_tables: bool = False) -> Chunk:
    """Q3/Q5-shaped join trees (and multi-slab chains the per-slab
    partial/merge path can't serve: DISTINCT aggs, windows) as ONE
    jitted program (tree_fragment). Multi-slab tables concatenate
    inside the program; join modes adapt at runtime (a lost uniqueness
    bet or an expansion-capacity overflow re-traces exactly once, never
    falls back to CPU). `plain_tables`: the tree again over plain tables —
    what only the mega-slab loop or the sorted-runs grouping can run gets
    rebuilds of the delta generations it was given (declines of gate
    `consumer`)."""

    ctx = ex.ctx
    root = ex.plan.root
    # ORDER BY / TopN over the agg runs as the agg's fused device
    # finalize (a host re-order on the mega-slab path): everything
    # below — flows, signatures, key bounds — stays agg-rooted
    order_root, root = strip_order_root(root)
    vars_ = ctx.vars
    max_slab = var_int(vars_, "tidb_tpu_max_slab_rows")
    group_cap = var_int(vars_, "tidb_tpu_group_cap")

    scans = scans_of(root)
    # the fused per-slab pipeline (an aggregate over a join tree whose
    # probe chain ends in a scan) takes delta generations as they are;
    # the mega-slab loop below assumes live prefixes and uniform slabs
    is_agg = isinstance(root, PhysHashAgg)
    anchor = TF.aligned_chain(root.children[0])[0] if is_agg else None
    anchor_i = next((i for i, s in enumerate(scans) if s is anchor),
                    None)
    delta_ok = is_agg and anchor_i is not None and var_on(
        vars_, "tidb_tpu_fused_pipeline") and \
        not plain_tables
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
            ent = device_cache.get_table(ctx, scan, used,
                                         max_slab,
                                         phases=ctx.phases,
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
    for nf in nested_fragments(root):
        rows = ex.run_nested(nf)
        caps[id(nf)] = (rows.cap, 1)
        nested_bounds[id(nf)] = rows.bounds
        nested_rows.append(rows.inputs())
    nested_rows = tuple(nested_rows)
    # per-scan-slot ((col, ColLayout), ...) for compressed columns —
    # parallel to scans_of(root) order, which matches the `scans`
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
    n_zeroed = 0
    for sc, (e, _u), rows in zip(scans, ents, scan_counts):
        for s in zonemap.prune_slabs(e, sc):
            rows[s] = 0
            n_zeroed += 1
    if n_zeroed:
        zonemap.note_skipped(ctx.phases, n_zeroed)
    # a delta generation's liveness is a mask a slab; a plain table's
    # the counts themselves
    def scan_rows_but(anchor=None):
        # (the anchor of the per-slab pipeline reads its own a slab)
        return tuple(
            None if i == anchor else counts if e.alive is None
            # (the masks are read where they lie, pruned slabs' too:
            # no row of one passes the scan's own predicate)
            else A.whole_masks(e.alive)
            for i, ((e, _u), counts) in enumerate(
                zip(ents, scan_counts)))
    max_cap = max(e.slab_cap * e.n_slabs for e, _ in ents)

    flow_list = [flows.get(id(n), []) for n in walk_nodes(root)]
    join_cfgs = TF.plan_join_configs(root, scan_bounds)
    # FK-aligned joins: verified-unique PK-FK joins run as pure streams
    # over cached fact-rowspace build columns (no per-query gathers)
    aligned_info = A.plan_aligned_joins(ctx, root, scans, ents)
    walk_joins = eligibility.walk_joins(root)
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
    akb = TF.tree_agg_key_bounds(root, scan_bounds, A.DOMAIN_CAP) \
        if is_agg else None
    if delta_ok and grouping_mode(akb) == RUNS and \
            any(e.is_delta for e, _ in ents):
        # sorted runs stack every slab's rows at one shape
        return run_device_tree(ex, plain_tables=True)
    gcap = A.initial_group_cap(root, group_cap, max_cap, akb) \
        if is_agg else 1
    out_cap_max = var_int(vars_, "tidb_tpu_join_out_cap")
    ladder = CapacityLadder(guard=getattr(ctx, "guard", None),
                            stats=ctx.escalation)
    # every device_get is a host↔device round trip — batch fetches
    ph = ctx.phases
    # ---- fused per-slab pipeline -----------------------------------
    # Agg-rooted trees (the Q3/Q5 shape) run scan → filter → project →
    # join-probe → partial-agg as ONE program PER PROBE SLAB plus one
    # root merge/finalize, instead of one mega-slab program:
    # intermediates stay in registers/HBM and warm launches drop to
    # slabs + 1. DISTINCT aggs fuse too; multi-arg DISTINCT
    # (COUNT-only) dedups on a combined dense code in-slab and ships
    # the raw argument columns in the pairs.
    if is_agg and anchor_i is not None and var_on(
            vars_, "tidb_tpu_fused_pipeline"):
        res = A.run_agg_slabs(
            A.TreeSlabs(ctx, root, caps, scans, ents, scan_inputs,
                        scan_rows_but(anchor_i), flow_list, flows,
                        aligned_inputs, join_cfgs, walk_joins, akb,
                        max_cap, out_cap_max, anchor_i, scan_layouts,
                        nested_rows, scan_counts),
            ex.schema, gcap, order_root, ladder, ex.rows_on_device)
        if res is not None:
            return res
        # a join's fan-out exceeded out_cap_max inside the slab
        # driver: fall through to the mega-slab loop, whose own
        # over-max rung escalates to blocked multi-pass execution
        # (learned flips/resizes persist in join_cfgs)
        if any(e.is_delta for e, _ in ents):
            return run_device_tree(ex, plain_tables=True)
    # the mega-slab program reads every table whole
    scan_inputs = tuple(A.whole_cols(cols) for cols in scan_inputs)
    scan_rows = scan_rows_but()
    aligned_inputs = tuple(A.whole_aligned(m, jc)
                           for m, jc in aligned_inputs)
    while True:
        prog = A.get_tree_program(root, caps, gcap, join_cfgs, akb,
                                scan_layouts)
        prep_vals = prog.collect_preps(flow_list)
        # scheduler slot spans DISPATCH only (jax queues the program
        # asynchronously); the blocking fetches below run outside it,
        # so a sibling statement's encode/dispatch overlaps this
        # one's device execution
        with scheduler.device_slot(ctx):
            with ph.launch(prog.name):
                out = prog(scan_inputs, scan_rows, prep_vals,
                           aligned_inputs, nested=nested_rows)
        ph.note_launch()
        if is_agg:
            A.count_agg_partial(A.note_grouping(root, akb, gcap))
        fetch = {"ju": out["join_unique"], "jt": out["join_totals"]}
        host = None
        if is_agg:
            fetch["ng"] = out["n_groups"]
            _piggyback_agg(fetch, out, gcap)
        elif isinstance(root, (PhysTopN, PhysSort, PhysLimit)):
            fetch["no"] = out["n_out"]
            if isinstance(root, (PhysTopN, PhysLimit)) and \
                    out["cols"] and \
                    out["cols"][0][0].shape[0] <= A.SMALL_GROUP_CAP:
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
                flip_out_cap=pow2(int(cfg.est * 1.3), lo=1024),
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
                return _run_tree_blocked(
                    ex, root, caps, join_cfgs, ji, walk_joins, akb,
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
            return empty_chunk(ex.schema)
        inp_dicts = {i: d for i, d in
                     enumerate(flows.get(id(root), []))}
        host_tree = (flags["keys"], flags["states"]) \
            if "keys" in flags else None
        chunk = host_decode.agg_chunk(ctx, ex.schema, root, out, inp_dicts,
                                      max(n_final, 1), host_tree=host_tree)
        if order_root is not None:
            # mega-slab fallback: the (small) final group rows
            # re-order on host; the fused per-slab path orders them
            # on device inside the finalize launch instead
            chunk = host_decode.host_order(chunk, order_root, root.schema)
            chunk = host_decode.topn_slice(chunk, order_root)
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
        cols = [host_decode.decode_col(ft, np.asarray(v), np.asarray(m),
                            dicts_root.get(ci))
                for ci, ((v, m), ft) in
                enumerate(zip(host_cols, root.schema.field_types))]
        return host_decode.topn_slice(Chunk(cols), root)
    # join/selection/projection/window root: compact by live on host
    return host_decode.compact_decode(host["cols"], host["live"],
                           root.schema.field_types, dicts_root)

def _run_tree_blocked(ex, root, caps, join_cfgs, bji, walk_joins,
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

    ctx = ex.ctx
    JOIN_OUT_CAP = var_int(ctx.vars, "tidb_tpu_join_out_cap")
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
    join_cfgs[bji] = dataclasses.replace(join_cfgs[bji], blocked=True,
                               out_cap=JOIN_OUT_CAP)

    K = max(2, math.ceil(est_total * 1.2 / JOIN_OUT_CAP))
    while K <= 128:
        prog = A.get_tree_program(root, caps, gcap, join_cfgs, akb,
                                scan_layouts)
        prep_vals = prog.collect_preps(flow_list)
        step = (total_cap + K - 1) // K
        pass_outs = []
        overflow = False
        restart = False
        for k in range(K):
            rng = (np.int32(k * step),
                   np.int32(min((k + 1) * step, total_cap)))
            with scheduler.device_slot(ctx):
                with ctx.phases.launch(prog.name, slab=k):
                    out = prog(scan_inputs, scan_rows, prep_vals,
                               aligned_inputs, rng)
            ctx.phases.note_launch()
            A.count_agg_partial(A.note_grouping(root, akb, gcap))
            # flags first: a restart/overflow pass never transfers its
            # (discarded) group arrays, and good passes transfer only
            # ng live slots instead of the full gcap padding
            got = ctx.phases.fetch({
                "ju": out["join_unique"], "jt": out["join_totals"],
                "ng": out["n_groups"]})
            for ji, cfg in enumerate(join_cfgs):
                uq = bool(np.asarray(got["ju"])[ji])
                tot = int(np.asarray(got["jt"])[ji])
                if cfg.mode == "unique" and not uq:
                    join_cfgs[ji] = dataclasses.replace(
                        cfg, mode="expand",
                        out_cap=pow2(int(cfg.est * 1.3), lo=1024))
                    restart = True
                elif cfg.mode == "expand" and tot > cfg.out_cap:
                    if tot > JOIN_OUT_CAP or cfg.blocked:
                        overflow = True      # split finer
                    else:
                        join_cfgs[ji] = dataclasses.replace(cfg,
                                                  out_cap=pow2(tot, lo=1024))
                        restart = True
            if grouping_mode(akb) != SLOTS and int(got["ng"]) > gcap:
                if gcap >= max_cap:
                    raise FragmentFallback("group cap overflow", reason="group-cap")
                gcap = min(gcap * 4, max_cap)
                restart = True
            if overflow or restart:
                break
            ng = int(np.asarray(got["ng"]))
            got.update(ctx.phases.fetch({
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
        return host_decode.merge_tree_agg_passes(
            ctx, ex.schema, root, pass_outs, inp_dicts)
    raise FragmentFallback("blocked expand: skew beyond 128 passes", reason="blocked-expand")
