"""Distributed device fragments: one shard_map program per SQL fragment.

The planner inserts PhysExchange boundaries (planner/physical.py
insert_exchanges — the fragmentation pass of planner/core/fragment.go:64);
this module compiles the WHOLE annotated fragment tree into a single
jitted shard_map program over a 1-D device mesh:

  * scans arrive row-sharded (the region→coprocessor-task parallelism of
    store/copr/coprocessor.go:178 becomes a PartitionSpec);
  * Exchange[hash] is collective.exchange — an all_to_all bucket swap on
    ICI (the ExchangeType_Hash tunnels of cophandler/mpp_exec.go:158-173);
  * Exchange[broadcast] is an all_gather (ExchangeType_Broadcast);
  * an agg root runs per-shard partials, all_gathers partial states, and
    each shard merges the groups it owns (AggFunc.MergePartialResult
    across MPP tasks, SURVEY §2.4.6);
  * a TopN/Sort root emits per-shard candidates; the host does the final
    k-way merge (the MPPGather role, executor/mpp_gather.go:42).

XLA schedules the collectives and overlaps them with per-shard compute —
the compiler replaces the reference's goroutine/gRPC exchange plumbing.

Fault recovery comes in two grades:

  * Exchange-free agg fragments (a plain group-by — the only collective
    is the final gather_partials) run STAGED via StagedDistAgg below:
    each rank's local partial aggregation is dispatched as its own
    single-device program, its result checkpointed device→host, and the
    final merge happens host-side over the checkpoints. A shard fault
    re-executes ONLY the failed rank — once on its own device, then
    re-dispatched onto a surviving device (degraded-mesh mode, recorded
    as a retryable session warning) before one typed ShardFailure ends
    the ladder. Healthy ranks' checkpoints are never recomputed
    (EscalationStats shards_rerun/shards_reused).
  * Exchange-carrying fragments (distributed joins, DISTINCT re-keys,
    windows) run the SAME per-rank ladder staged via StagedDistExchange
    below (gated by `tidb_tpu_dist_staged_exchange`, default on), cut at
    the exchange: stage 1 runs each rank's scan→filter→partition→pack as
    its own dispatchable program producing per-destination bucket
    buffers; stage 2 checkpoints every rank's outgoing buckets
    device→host — committed before ANY rank's receive stage starts — and
    routes them host-side (collective.route_buckets replaces the
    in-trace all_to_all); stage 3 re-dispatches each rank's receive/
    probe/dedup as ONE fused program over the routed buckets. A shard
    fault at any stage re-executes ONLY the failed rank's stage through
    the StagedDistAgg rungs (same-device retry → re-dispatch onto a
    surviving device with a retryable degraded-mesh warning → one typed
    ShardFailure); a bucket-cap overflow resizes only the overflowed
    rank's buckets at the exact reported need. The monolithic shard_map
    program below — where fault retry stays full-step because the
    collectives entangle every rank's state — is kept as the
    byte-exactness oracle (`set tidb_tpu_dist_staged_exchange = off`).

The drivers at the end (`run_device_dist`, what `TpuFragmentExec`
dispatches a distributed plan to) pick among the three and decode what
they gather. No benchmark cell runs any of this (PERF.md §7).
"""

from __future__ import annotations

import copy
import dataclasses
import time
import types
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from tidb_tpu.chunk import Chunk, Column, compress
from tidb_tpu.errors import ShardFailure
from tidb_tpu.executor import (agg_slabs as A, compile_cache, device_cache,
                               device_emit, empty_chunk, host_decode,
                               scheduler, zonemap)
from tidb_tpu.executor.eligibility import (FragmentFallback, fragment_ok,
                                           linearize, scans_of,
                                           strip_order_root, walk_nodes)
from tidb_tpu.executor.tree_fragment import (JoinCfg, TreeProgram,
                                             dictionary_flows,
                                             escalate_join,
                                             plan_join_configs,
                                             trace_scan_col, tree_signature)
from tidb_tpu.expression import ColumnRef
from tidb_tpu.expression.aggfuncs import build_agg
from tidb_tpu.ops import factorize
from tidb_tpu.ops.jax_env import jax, jnp, lax
from tidb_tpu.planner.physical import (PhysExchange, PhysHashAgg,
                                       PhysHashJoin, PhysProjection,
                                       PhysSelection, PhysSort,
                                       PhysTableScan, PhysTopN, PhysWindow,
                                       PhysicalPlan)
from tidb_tpu.sysvars import var_int, var_on
from tidb_tpu.util import failpoint, timeline
from tidb_tpu.util.escalation import CapacityLadder, pow2
from tidb_tpu.util.phases import tree_nbytes

AXIS = "shard"


class DistTreeProgram(TreeProgram):
    """Shard_map-compiled fragment: per-shard emission is TreeProgram's,
    plus Exchange nodes and a distributed root reduction. Join modes
    mirror the single-chip tree engine — unique (PK-FK bet) and expand
    (non-unique builds via prefix-sum expansion, per-shard out caps) —
    with lost bets / capacity overflows reported per join so the executor
    re-traces exactly once (never a CPU fallback)."""

    def __init__(self, plan: PhysicalPlan, caps: Dict[int, int],
                 group_cap: int, mesh, bucket_caps: Dict[int, int],
                 join_cfgs: Optional[Sequence[JoinCfg]] = None,
                 scan_layouts=None, kind: str = "dist", sig: str = ""):
        from tidb_tpu.ops.jax_env import named_jit, shard_map
        self.mesh = mesh
        self.n_shards = mesh.devices.size
        self.bucket_caps = bucket_caps    # id(exchange-node) → bucket cap
        # TreeProgram.__init__ builds prep_nodes and jits self._run; we
        # re-wrap with shard_map afterwards.
        super().__init__(plan, caps, group_cap, join_cfgs,
                         scan_layouts=scan_layouts, kind=kind, sig=sig)
        P = jax.sharding.PartitionSpec
        root = plan
        flags = {"join_unique": P(), "join_need": P(),
                 "group_need": P(), "exchange_need": P()}
        if isinstance(root, PhysHashAgg):
            out_specs = {"keys": P(AXIS), "states": P(AXIS),
                         "out_live": P(AXIS), **flags}
        elif isinstance(root, (PhysTopN, PhysSort)):
            out_specs = {"cols": P(AXIS), "n_out": P(AXIS), **flags}
        else:   # window / selection / projection / join row root
            out_specs = {"cols": P(AXIS), "live": P(AXIS), **flags}
        self.run = named_jit(shard_map(
            self._run, mesh=mesh,
            in_specs=(P(AXIS), P(AXIS), P()),
            out_specs=out_specs,
            check_vma=False), self.name)

    def __call__(self, scan_inputs, scan_rows, prep_vals,
                 aligned_inputs=()):
        # the dist path keeps the 3-arg shard_map signature (FK-aligned
        # join structures are a single-chip cache)
        # host-side per-shard dispatch seam: shard_map traces ONE body
        # for all shards, so a per-shard fault cannot raise inside the
        # trace — instead the "shard-step" site fires once per rank here
        # (after_hits=K selects which shard fails); real device runtime
        # errors from run() surface through the same retry handler in
        # the executor (_run_device_dist)
        for _rank in range(self.n_shards):
            failpoint.inject("shard-step")
        return self.run(scan_inputs, scan_rows, prep_vals)

    # -- traced per-shard body ----------------------------------------------
    def _run(self, scan_inputs, scan_rows, prep_vals):
        self._prepared = {id(n): v
                          for n, v in zip(self.prep_nodes, prep_vals)
                          if v is not None}
        self._join_unique_flags = []
        self._join_totals = []
        self._overflow_flags = []
        cols, live = self._emit(self.plan, scan_inputs, scan_rows)
        out = self._finish_dist(cols, live)
        # per-join global verdicts: a bet is lost if ANY shard saw dup
        # build keys; an expand cap must cover the LARGEST shard's need
        if self._join_unique_flags:
            ju = jnp.stack(self._join_unique_flags).astype(jnp.int32)
            out["join_unique"] = lax.pmin(ju, AXIS) > 0
            out["join_need"] = lax.pmax(
                jnp.stack(self._join_totals), AXIS)
        else:
            out["join_unique"] = jnp.zeros(0, dtype=bool)
            out["join_need"] = jnp.zeros(0, dtype=jnp.int64)
        # per-shard TRUE group counts (factorize counts before clamping):
        # the pmax is the exact global need, so a group-cap overflow is
        # an exact-need resize — one recompile, not a doubling ladder
        gneed = out.pop("_gneed_local", jnp.int32(0))
        out["group_need"] = lax.pmax(
            jnp.asarray(gneed).astype(jnp.int32), AXIS)
        # per-exchange NEEDED capacities (already pmax'd by exchange()):
        # the executor resizes ONLY the overflowed exchange's buckets to
        # the exact reported need — one skewed exchange costs one
        # recompile and touches nothing else
        out["exchange_need"] = (jnp.stack(self._overflow_flags)
                                if self._overflow_flags
                                else jnp.zeros(0, dtype=jnp.int32))
        return out

    def _emit(self, node: PhysicalPlan, scan_inputs, scan_rows):
        from tidb_tpu.parallel import collective as C
        if isinstance(node, PhysTableScan):
            slot = next(i for i, s in enumerate(self.scan_order)
                        if s is node)
            in_cols = scan_inputs[slot]
            cap, _ = self.caps[id(node)]
            # per-shard row count arrives as a (1,) slice of (n_shards,)
            n_local = scan_rows[slot][0]
            live = jnp.arange(cap, dtype=jnp.int32) < n_local
            lays = dict(self.scan_layouts[slot]) \
                if slot < len(self.scan_layouts) else {}
            col_list = []
            for i in range(len(node.schema)):
                c = in_cols.get(i)
                if c is not None and lays.get(i) is not None:
                    # compressed shard slab: decode inside the
                    # shard_map body, so PCIe/ICI only ever carried
                    # the packed words
                    c = device_emit.emit_decode(lays[i], c, cap)
                col_list.append(c)
            ctx = self._ctx(col_list)
            for f in node.filters:
                v, m = f.eval(ctx)
                live = live & (v != 0) & m
            return col_list, live
        if isinstance(node, PhysExchange):
            cols, live = self._emit(node.children[0], scan_inputs,
                                    scan_rows)
            if node.kind == "broadcast":
                flat, meta = _flatten_cols(cols)
                out_flat, out_live = C.broadcast_build(flat, live, AXIS)
                return _unflatten_cols(out_flat, meta), out_live
            # hash: repartition rows so equal keys co-locate
            ctx = self._ctx(cols)
            keys = [e.eval(ctx) for e in node.keys]
            code = C.mix_key_code(keys)
            dest = C.shard_of(code, self.n_shards)
            flat, meta = _flatten_cols(cols)
            cap = self.bucket_caps[id(node)]
            recv, recv_live, need = C.exchange(flat, dest, live,
                                               self.n_shards, cap, AXIS)
            self._overflow_flags.append(need)
            return _unflatten_cols(recv, meta), recv_live
        return super()._emit(node, scan_inputs, scan_rows)

    # -- distributed root reductions -----------------------------------------
    def _finish_dist(self, cols, live):
        from tidb_tpu.parallel import collective as C
        root = self.plan
        if isinstance(root, PhysHashAgg):
            cap = self.group_cap
            ctx = self._ctx(cols)
            n = live.shape[0]
            # ---- per-shard partial (the MPP task's partial agg) ----
            if root.group_exprs:
                keys = [e.eval(ctx) for e in root.group_exprs]
                gids, n_groups, rep = factorize.factorize(keys, live, cap)
                gids = jnp.where(live, gids, jnp.int32(cap))
                slot_live = jnp.arange(cap, dtype=jnp.int32) < n_groups
                key_out = [(jnp.asarray(v)[rep], jnp.asarray(m)[rep] &
                            slot_live) for v, m in keys]
                gneed = jnp.asarray(n_groups, dtype=jnp.int32)
            else:
                gids = jnp.where(live, jnp.int32(0), jnp.int32(cap))
                slot_live = jnp.arange(cap, dtype=jnp.int32) < 1
                key_out = []
                gneed = jnp.int32(0)
            # DISTINCT dedup is exact per shard: the planner re-keyed the
            # exchange on the group keys, so a group's rows never split
            states = device_emit.agg_states(ctx, live, root, self.aggs,
                                            gids, cap, n)
            # ---- gather partials, merge owned groups ----
            gkeys, gstates, gslot = C.gather_partials(
                key_out, [tuple(st) for st in states], slot_live, AXIS)
            rank = lax.axis_index(AXIS)
            if root.group_exprs:
                code = C.mix_key_code(gkeys)
                owner = C.shard_of(code, self.n_shards)
            else:
                owner = jnp.zeros(gslot.shape[0], dtype=jnp.int32)
            own = gslot & (owner == rank)
            if root.group_exprs:
                fgids, n_own, frep = factorize.factorize(gkeys, own, cap)
                fgids = jnp.where(own, fgids, jnp.int32(cap))
                out_live = jnp.arange(cap, dtype=jnp.int32) < n_own
                f_keys = [(jnp.asarray(v)[frep],
                           jnp.asarray(m)[frep] & out_live)
                          for v, m in gkeys]
                gneed = jnp.maximum(
                    gneed, jnp.asarray(n_own, dtype=jnp.int32))
            else:
                fgids = jnp.where(own, jnp.int32(0), jnp.int32(cap))
                out_live = (jnp.arange(cap, dtype=jnp.int32) < 1) & \
                    (rank == 0)
                f_keys = []
            f_states = []
            for agg, gstate in zip(self.aggs, gstates):
                clean = tuple(jnp.where(own, a, jnp.zeros_like(a))
                              for a in gstate)
                st = agg.init(jnp, cap)
                f_states.append(agg.merge(jnp, st, fgids, cap, clean))
            return {"keys": f_keys, "states": f_states,
                    "out_live": out_live, "_gneed_local": gneed}
        n = live.shape[0]
        cols = [(jnp.zeros(n, dtype=jnp.int64), jnp.zeros(n, dtype=bool))
                if c is None else c for c in cols]
        if isinstance(root, (PhysTopN, PhysSort)):
            # ---- TopN / Sort: per-shard candidates, host merges ----
            ctx = self._ctx(cols)
            keys = [e.eval(ctx) for e in root.by]
            n_out_cols = len(root.schema)
            if isinstance(root, PhysTopN):
                k = min(root.count + root.offset, n)
                idx, n_out = factorize.topn(keys, root.descs, live, k)
            else:
                idx, n_out = factorize.sort_perm(keys, root.descs, live)
            gathered = [(jnp.take(jnp.asarray(v), idx),
                         jnp.take(jnp.asarray(m), idx))
                        for v, m in cols[:n_out_cols]]
            return {"cols": gathered,
                    "n_out": jnp.reshape(n_out, (1,)),
                    "_gneed_local": jnp.int32(0)}
        if isinstance(root, PhysWindow):
            # ---- window root: the exchange co-located every partition on
            # one shard, so per-shard emit_window is globally exact ----
            ctx = self._ctx(cols)
            out = device_emit.emit_window(ctx, live, root)
            out["_gneed_local"] = jnp.int32(0)
            return out
        # ---- selection / projection / join row root: per-shard rows,
        # host compacts by live and concatenates ----
        return {"cols": [(jnp.asarray(v), jnp.asarray(m))
                         for v, m in cols[:len(root.schema)]],
                "live": live, "_gneed_local": jnp.int32(0)}


class StagedDistAgg:
    """Checkpointable staged execution of an exchange-free distributed
    agg fragment (the distributed half of fragment._execute_agg's
    resumable-escalation story).

    Stages: per-rank local partial aggregation (one single-device
    program per rank, pinned by committed `jax.device_put` transfers) →
    device-to-host checkpoint of each rank's packed (keys, states)
    partials → host-side final merge (fragment._merge_tree_agg_passes).
    The host slices in `rank_cols` are the recovery source of truth: on
    a shard fault only the failed rank's slice is re-uploaded and re-run

      1. once more on its own device          (ladder.shard_retry), then
      2. onto a surviving device — degraded-mesh mode
         (ladder.redispatch, a retryable session warning), then
      3. one typed retryable ShardFailure; the session stays usable.

    Healthy ranks' checkpoints are reused untouched (shards_reused); a
    per-rank group-cap overflow re-runs only the overflowed ranks at the
    exact-need cap, like the single-device slab ladder. Every re-run is
    charged to the shared backoff budget, and every abandoned device
    buffer of a failed attempt is `jax.Array.delete()`d before the next
    dispatch so recovery never doubles HBM residency."""

    def __init__(self, root, chain, mesh, rank_cols, rank_rows, dicts,
                 used_cols, in_types, slab_cap: int, group_cap: int,
                 cap_limit: int, ctx, ladder, layouts=None,
                 skip_ranks=None):
        self.root = root
        self.chain = chain
        self.devices = list(mesh.devices.flat)
        self.nd = len(self.devices)
        self.rank_cols = rank_cols    # rank → {col: packed/raw arrays}
        self.rank_rows = rank_rows    # (nd,) int32 true per-rank rows
        self.dicts = dicts            # col → dictionary (collect_preps)
        self.used_cols = used_cols
        self.in_types = in_types
        self.slab_cap = slab_cap
        self.group_cap = group_cap
        self.cap_limit = cap_limit
        self.ctx = ctx
        self.ladder = ladder
        # col → ColLayout for compressed rank slabs (decode happens
        # inside the per-rank chain partial)
        self.layouts = dict(layouts) if layouts else {}
        # rank ids zone-map pruning proved empty under the scan's
        # conjuncts: never uploaded, never dispatched — their
        # checkpoints are pre-filled with the ng=0 merge identity
        self.skip_ranks = frozenset(skip_ranks or ())

    def execute(self) -> List[dict]:
        """→ per-rank host checkpoints in rank order, each a pass_out
        {"ng", "keys", "states"} ready for _merge_tree_agg_passes.
        Pruned ranks carry the ng=0 identity checkpoint (the merge
        skips ng==0 passes)."""
        ckpts: List[Optional[dict]] = [None] * self.nd
        ng_true = [0] * self.nd
        caps_ran = [0] * self.nd
        for r in self.skip_ranks:
            ckpts[r] = {"ng": 0, "keys": [], "states": []}
        to_run = [r for r in range(self.nd) if r not in self.skip_ranks]
        while True:
            # between dispatch rounds is a guard checkpoint: a killed
            # query must not queue another per-rank compile
            self.ctx.check_killed("device-dispatch")
            self.grouping = A.note_grouping(self.root, None, self.group_cap)
            prog = A.get_program(self.chain, self.used_cols, self.in_types,
                               self.slab_cap, self.group_cap,
                               layouts=self.layouts or None)
            prep_vals = prog.collect_preps(self.dicts)
            for r in to_run:
                ckpts[r], ng_true[r] = self._run_rank(r, prog, prep_vals)
                caps_ran[r] = self.group_cap
            # overflow iff a rank's TRUE group count exceeded the cap IT
            # ran at (factorize counts before clamping); there is no
            # merged-count rung — the final merge is host-side, uncapped
            over = [r for r in range(self.nd) if ng_true[r] > caps_ran[r]]
            if not over:
                return ckpts
            if self.group_cap >= self.cap_limit:
                self.ladder.fallback("group")
                raise FragmentFallback("group cap overflow", reason="group-cap")
            need = max(ng_true[r] for r in over)
            self.group_cap = self.ladder.resize(
                "group", self.group_cap, need=need, max_cap=self.cap_limit)
            self.ladder.attempt("group", A.GroupCapOverflow(need))
            self.ladder.partial_resume("group", rerun=len(over),
                                       reused=self.nd - len(over))
            to_run = over

    @staticmethod
    def _is_shard_fault(e: BaseException) -> bool:
        return isinstance(e, ShardFailure) or \
            type(e).__name__ == "XlaRuntimeError"

    def _run_rank(self, r: int, prog, prep_vals):
        """One rank's local work through the per-shard recovery ladder."""
        try:
            return self._attempt(r, self.devices[r], prog, prep_vals,
                                 site="shard-step")
        except Exception as e1:
            if not self._is_shard_fault(e1):
                raise
            # rung 1: retry on the rank's own device. Healthy ranks'
            # checkpoints are untouched — only this rank re-runs.
            self.ctx.check_killed("shard-retry")
            self.ladder.shard_retry(e1)
            try:
                out = self._attempt(r, self.devices[r], prog, prep_vals,
                                    site="shard-step")
            except Exception as e2:
                if not self._is_shard_fault(e2):
                    raise
                # rung 2: the device is persistently bad — degraded-mesh
                # mode: re-plan this rank's slice onto a surviving device
                # (the re-dispatch recompile is charged to the budget)
                failpoint.inject("degraded-mesh-replan")
                self.ctx.check_killed("shard-redispatch")
                self.ladder.redispatch(e2)
                spare = self.devices[(r + 1) % self.nd]
                try:
                    out = self._attempt(r, spare, prog, prep_vals,
                                        site="shard-redispatch")
                except Exception as e3:
                    if not self._is_shard_fault(e3):
                        raise
                    # ladder exhausted: ONE typed retryable error — the
                    # store and session stay fully usable
                    raise ShardFailure(
                        f"shard {r} failed on its device and on "
                        f"re-dispatch to a surviving device: {e3}") from e3
                self._warn_degraded(r, e2)
            self.ladder.shard_resume(rerun=1, reused=self.nd - 1)
            return out

    def _attempt(self, r: int, dev, prog, prep_vals, site: str):
        """Upload rank r's host slice onto `dev`, run the partial there,
        fetch its checkpoint → ({"ng", "keys", "states"}, true_count)."""
        ph = self.ctx.phases
        dcols = None
        out = None
        try:
            failpoint.inject(site)
            with ph.phase("upload"):
                # committed transfers pin the jitted partial to `dev` —
                # this is how one rank's program lands on one device (and
                # how a re-dispatch lands on a DIFFERENT one)
                dcols = {i: tuple(jax.device_put(a, dev)
                                  for a in self.rank_cols[r][i])
                         for i in prog.used_cols}
            _rank_b = sum(a.nbytes for i in prog.used_cols
                          for a in self.rank_cols[r][i])
            _rank_lb = sum(
                (compress.raw_slab_bytes(self.layouts[i], self.slab_cap)
                 if self.layouts.get(i) is not None
                 else sum(a.nbytes for a in self.rank_cols[r][i]))
                for i in prog.used_cols)
            ph.add_h2d(_rank_b, logical=_rank_lb)
            # the rank's partial streams these slabs
            ph.add_scan(_rank_b, logical=_rank_lb)
            with scheduler.device_slot(self.ctx):
                with ph.launch(prog.partial_name, slab=r):
                    out = prog.partial(dcols,
                                       jnp.int32(int(self.rank_rows[r])),
                                       prep_vals)
            ph.note_launch()
            ph.note_fused()   # per-rank chain partial = fused local stage
            A.count_agg_partial(self.grouping)
            with ph.drain():
                # drain outside the scheduler slot (GIL-released wait):
                # sibling statements dispatch while this rank executes
                jax.block_until_ready(out)
            failpoint.inject("shard-checkpoint-write")
            with ph.phase("fetch"):
                ngt = int(np.asarray(jax.device_get(out["n_groups"])))
                live_n = ngt if self.root.group_exprs else 1
                # factorize packs live groups into slots 0..ng-1, so the
                # checkpoint is the sliced prefix — exactly a pass_out
                k = min(live_n, prog.group_cap)
                got = jax.device_get(
                    {"keys": [(v[:k], m[:k]) for v, m in out["keys"]],
                     "states": [tuple(a[:k] for a in st)
                                for st in out["states"]]})
            ph.add_d2h(tree_nbytes(got) + 4)
            return ({"ng": k, "keys": got["keys"],
                     "states": got["states"]}, ngt)
        finally:
            # eager-delete discipline: free the rank's device buffers —
            # on success the host checkpoint is now authoritative, on a
            # fault the abandoned buffers must be gone BEFORE the retry /
            # re-dispatch uploads its generation (never 2× HBM residency)
            compile_cache.tree_delete(dcols)
            compile_cache.tree_delete(out)

    def _warn_degraded(self, r: int, err: BaseException) -> None:
        """Degraded-mesh completion is a typed, retryable warning on the
        statement guard (surfaced by SHOW WARNINGS), NOT an error — the
        result is complete and exact; only the mesh shrank."""
        guard = getattr(self.ctx, "guard", None)
        if guard is not None and hasattr(guard, "warnings"):
            guard.warnings.append(
                ("Warning", ShardFailure.code,
                 f"shard {r} persistently failed and was re-dispatched "
                 f"onto a surviving device (degraded mesh, retryable): "
                 f"{err}"))


# ---------------------------------------------------------------------------
# Staged (checkpointable) exchanges — StagedDistAgg's story cut at the
# exchange boundary, covering distributed joins, DISTINCT re-keys, windows
# ---------------------------------------------------------------------------


def _exchange_scan_chain(node: PhysicalPlan) -> Optional[PhysTableScan]:
    """The scan at the bottom of an exchange child when the child is a
    plain Scan/Selection/Projection chain — the shape whose stage-1
    partition program is one single-device TreeProgram per rank. A join,
    agg or nested exchange below an exchange has no per-rank cut BEFORE
    the collective, so such plans stay monolithic."""
    while isinstance(node, (PhysSelection, PhysProjection)):
        node = node.children[0]
    return node if isinstance(node, PhysTableScan) else None


def _has_exchange(node: PhysicalPlan) -> bool:
    return any(isinstance(n, PhysExchange) for n in walk_nodes(node))


class _ExchangeLeaf(PhysTableScan):
    """Stage-3 stand-in scan for a checkpointed exchange: the upper plan
    recompiles with each PhysExchange replaced by one of these, so every
    rank's receive/probe/dedup stage is ONE fused TreeProgram whose
    'table' is the routed bucket payload uploaded for that rank. The
    synthetic table id keeps compile-cache signatures distinct per
    exchange position; no filters/partitions — stage 1 already applied
    the pushed-down conjuncts before partitioning."""

    def __init__(self, exch: PhysExchange, tag: int):
        PhysicalPlan.__init__(self, exch.schema)
        self.table = types.SimpleNamespace(id=f"staged-exch:{tag}")
        self.alias = None
        self.filters = []
        self.used_columns = None
        self.partitions = None
        self.est_rows = exch.est_rows


def staged_exchange_plan(root: PhysicalPlan):
    """Eligibility + stage-3 rewrite for the staged exchange path.

    → None when the fragment must stay monolithic (no exchange; a TopN/
    Sort root, whose per-shard candidate emission + host k-way merge IS
    the monolithic root reduction; or an exchange whose child is not a
    plain scan chain), else (new_root, grafts) where grafts pairs each
    PhysExchange with its stage-3 _ExchangeLeaf in walk_nodes order.
    new_root is a CLONE of the upper plan — ancestors of an exchange are
    copy.copy'd with fresh children lists, never mutated, because cached
    TreePrograms hold references into the original plan. Exchange-free
    subtrees (e.g. a broadcast join's probe side) are reused as-is so
    their scan/prep identities survive into the rewritten plan."""
    exchanges = [n for n in walk_nodes(root) if isinstance(n, PhysExchange)]
    if not exchanges:
        return None
    if isinstance(root, (PhysTopN, PhysSort)):
        return None
    for exch in exchanges:
        if _exchange_scan_chain(exch.children[0]) is None:
            return None
    grafts = [(exch, _ExchangeLeaf(exch, k))
              for k, exch in enumerate(exchanges)]
    by_id = {id(exch): leaf for exch, leaf in grafts}

    def graft(node: PhysicalPlan) -> PhysicalPlan:
        leaf = by_id.get(id(node))
        if leaf is not None:
            return leaf
        if not _has_exchange(node):
            return node
        clone = copy.copy(node)
        clone.children = [graft(c) for c in node.children]
        return clone

    return graft(root), grafts


class _PartitionProgram(TreeProgram):
    """Stage 1 of a staged exchange: ONE rank's scan→filter→project→
    partition→pack as a single-device fused program. The plan is the
    PhysExchange node itself (so prep collection and the compile-cache
    signature see the exchange keys); _finish replaces the monolithic
    path's in-trace all_to_all with fixed-capacity per-destination
    bucket buffers ready for a device→host checkpoint — the host does
    the routing (collective.route_buckets). The bucket arithmetic is
    collective.exchange()'s exactly (dense per-destination ranking, so
    within each bucket the live prefix preserves source row order and
    the routed payload is byte-identical to the all_to_all's)."""

    def __init__(self, exch: PhysExchange, caps, n_shards: int,
                 bucket_cap: int, scan_layouts=None):
        self.n_shards = n_shards
        self.bucket_cap = bucket_cap
        super().__init__(exch, caps, 0, scan_layouts=scan_layouts)

    def _emit(self, node, scan_inputs, scan_rows):
        if isinstance(node, PhysExchange):
            return super()._emit(node.children[0], scan_inputs, scan_rows)
        return super()._emit(node, scan_inputs, scan_rows)

    def _finish(self, cols, live):
        from tidb_tpu.parallel import collective as C
        exch = self.plan
        present = [i for i, c in enumerate(cols) if c is not None]
        if exch.kind != "hash":
            # broadcast: no partitioning — the checkpoint carries the
            # rank's filtered rows; the host compacts by `live` and
            # replicates the concatenation to every destination
            return {"bufs": {i: (jnp.asarray(cols[i][0]),
                                 jnp.asarray(cols[i][1]))
                             for i in present},
                    "live": live}
        ctx = self._ctx(cols)
        keys = [e.eval(ctx) for e in exch.keys]
        dest = C.shard_of(C.mix_key_code(keys), self.n_shards)
        arrays = []
        for i in present:
            v, m = cols[i]
            arrays.append(jnp.asarray(v))
            arrays.append(jnp.asarray(m))
        bufs, _sent, counts, mx = device_emit.emit_partition(
            arrays, dest, live, self.n_shards, self.bucket_cap)
        return {"bufs": {i: (bufs[2 * k], bufs[2 * k + 1])
                         for k, i in enumerate(present)},
                "counts": counts, "need": mx}


class StagedDistExchange:
    """Checkpointable staged execution of an exchange-carrying
    distributed fragment (see the module docstring's recovery grades):

      stage 1  per rank: one _PartitionProgram dispatch producing that
               rank's per-destination bucket buffers;
      stage 2  every rank's outgoing buckets checkpoint device→host —
               all committed before ANY rank's receive stage starts —
               then collective.route_buckets routes them host-side;
      stage 3  per rank: receive/probe/dedup over the routed buckets
               (plus this rank's slices of any non-exchanged scans,
               e.g. a broadcast join's probe side) as ONE fused
               TreeProgram via device_emit's root emission.

    Any stage's shard fault rides the StagedDistAgg ladder — same-device
    retry → re-dispatch onto a surviving device (degraded mesh, one
    retryable warning per recovered rank) → typed ShardFailure — and
    re-executes ONLY the failed rank's stage; healthy ranks' checkpoints
    are never recomputed. A stage-1 bucket-cap overflow resizes ONLY the
    overflowed rank's buckets at the exact reported need (the monolithic
    exchange_need contract: one skewed rank costs one recompile — the
    per-rank cap lives in the compile-cache signature, so the other
    ranks keep hitting their cached program). Stage-3 group overflows
    rerun only the overflowed ranks; a lost join bet reruns all ranks
    (unique-mode checkpoints under the old cfg are not trustworthy).
    Abandoned device buffers are delete()d before any retry uploads its
    generation (never 2× HBM residency)."""

    def __init__(self, root, new_root, grafts, mesh, host_cols, scan_meta,
                 ctx, ladder):
        from dataclasses import replace as d_replace
        self.root = root
        self.new_root = new_root
        self.mesh = mesh
        self.devices = list(mesh.devices.flat)
        self.nd = len(self.devices)
        self.ctx = ctx
        self.ladder = ladder
        nd = self.nd
        vars_ = ctx.vars
        comp_on = var_on(vars_, "tidb_tpu_compression")
        meta = {id(s): (s, u, t) for s, u, t in scan_meta}
        scan_dicts_all = {id(s): {i: host_cols[(id(s), i)][2] for i in u}
                          for s, u, t in scan_meta}
        flows1, _ = dictionary_flows(root, scan_dicts_all)

        def prep_scan(scan, used, total, zone_prune):
            """Per-rank host slices of one scan (the checkpoint story's
            source of truth: a retry or re-dispatch re-uploads ONLY its
            rank's slice), compressed per rank like StagedDistAgg's —
            each rank packs its own slab, so no word-alignment
            constraint applies and layouts are chosen globally."""
            cap = pow2((total + nd - 1) // nd, lo=8)
            layouts = {}
            if comp_on:
                for i in used:
                    vals, valid, _d = host_cols[(id(scan), i)]
                    if vals.ndim != 1:
                        continue
                    lay, _dv = compress.choose_layout(vals, valid,
                                                       allow_dict=False)
                    if lay is not None and lay.width > 0:
                        layouts[i] = lay
            dicts = {i: host_cols[(id(scan), i)][2] for i in used}
            skip: frozenset = frozenset()
            if zone_prune and comp_on and getattr(scan, "filters", None):
                zmaps = {}
                for i in used:
                    vals, valid, _d = host_cols[(id(scan), i)]
                    if vals.ndim != 1:
                        continue
                    kind = "code" if _d is not None else \
                        ("float" if vals.dtype.kind == "f" else "num")
                    zmaps[i] = zonemap.column_stats(vals, valid, cap,
                                                    total, kind=kind)
                skip = zonemap.prune_slabs(_RankZoneEnt(nd, zmaps, dicts),
                                           scan)
                if len(skip) >= nd:
                    skip = frozenset()
                if skip:
                    zonemap.note_skipped(ctx.phases, len(skip))
            rank_cols = []
            for r in range(nd):
                if r in skip:
                    rank_cols.append(None)
                    continue
                lo = r * cap
                cols = {}
                for i in used:
                    vals, valid, _d = host_cols[(id(scan), i)]
                    pv = np.zeros(cap, dtype=vals.dtype)
                    pm = np.zeros(cap, dtype=bool)
                    seg = vals[lo:lo + cap]
                    pv[:seg.shape[0]] = seg
                    segm = valid[lo:lo + cap]
                    pm[:segm.shape[0]] = segm
                    lay = layouts.get(i)
                    cols[i] = compress.pack_slab(lay, pv, pm) \
                        if lay is not None else (pv, pm)
                rank_cols.append(cols)
            rank_rows = np.clip(total - np.arange(nd) * cap, 0,
                                cap).astype(np.int32)
            return {"scan": scan, "used": list(used), "cap": cap,
                    "layouts": layouts,
                    "lay_pairs": tuple(sorted(layouts.items())),
                    "dicts": dicts, "rank_cols": rank_cols,
                    "rank_rows": rank_rows, "skip": skip}

        # stage-1 sources: one per exchange, zone-map rank pruning on (a
        # pruned rank partitions nothing — its checkpoint is the empty-
        # buckets identity, filled after a real checkpoint fixes dtypes)
        cap_override = var_int(vars_, "tidb_tpu_exchange_bucket_cap")
        self.exchanges: List[dict] = []
        for tag, (exch, leaf) in enumerate(grafts):
            scan = _exchange_scan_chain(exch.children[0])
            _s, used, total = meta[id(scan)]
            info = prep_scan(scan, used, total, zone_prune=True)
            est = max(int(exch.est_rows), 1)
            info.update({
                "exch": exch, "leaf": leaf, "tag": tag,
                "bcaps": [cap_override
                          or pow2(4 * ((est + nd - 1) // nd), lo=64)] * nd,
            })
            fl, _ = dictionary_flows(exch, {id(scan): info["dicts"]})
            info["flow_list"] = [fl.get(id(n), [])
                                 for n in walk_nodes(exch)]
            # the exchange's dictionary_flows entry IS its output dict
            # list — the leaf's scan dictionaries for the stage-3 flows
            info["leaf_dicts"] = {i: d for i, d in
                                  enumerate(flows1.get(id(exch), []))}
            self.exchanges.append(info)

        # direct (non-exchanged) scans surviving into the stage-3 plan
        self.direct: Dict[int, dict] = {}
        for scan in scans_of(new_root):
            if isinstance(scan, _ExchangeLeaf):
                continue
            _s, used, total = meta[id(scan)]
            self.direct[id(scan)] = prep_scan(scan, used, total,
                                              zone_prune=False)

        scan_dicts3 = {id(i["leaf"]): i["leaf_dicts"]
                       for i in self.exchanges}
        for sid, d in self.direct.items():
            scan_dicts3[sid] = d["dicts"]
        self.flows2, self.root_dicts2 = dictionary_flows(new_root,
                                                         scan_dicts3)
        self.flow_list2 = [self.flows2.get(id(n), [])
                           for n in walk_nodes(new_root)]

        scan_bounds = {}
        for sid, d in self.direct.items():
            b = {}
            for i in d["used"]:
                vals, valid, dictionary = host_cols[(sid, i)]
                bb = device_cache.col_bounds(vals, valid, dictionary)
                if bb is not None:
                    b[i] = bb
            scan_bounds[sid] = b
        self.join_cfgs = plan_join_configs(new_root, scan_bounds)
        self.join_cfgs = [d_replace(c, out_cap=self._shard_out_cap(c))
                          if c.mode == "expand" else c
                          for c in self.join_cfgs]
        self.out_cap_max = var_int(vars_, "tidb_tpu_join_out_cap")
        caps_all = [d["cap"] for d in self.direct.values()] + \
            [i["cap"] for i in self.exchanges]
        self.cap_limit = max(caps_all) * nd
        if isinstance(new_root, PhysHashAgg):
            self.gcap = A.initial_group_cap(
                new_root, var_int(vars_, "tidb_tpu_group_cap"),
                self.cap_limit)
        else:
            self.gcap = 1
        self.stage3_order: List[dict] = []

    def _shard_out_cap(self, cfg) -> int:
        # expand caps are PER SHARD: the balanced share of the global
        # estimate; skew comes back as join_need → 1 retry
        return pow2(int(cfg.est * 1.3 / self.nd) + 16, lo=1024)

    # -- per-rank fault ladder (shared by every stage) ----------------------

    def _run_rank(self, r: int, attempt):
        """One rank's stage through the per-shard recovery ladder —
        StagedDistAgg._run_rank's rungs with the staged-exchange
        degraded/re-dispatch failpoints. `attempt(device, site)` runs
        the stage once; only the failed rank climbs the ladder."""
        try:
            return attempt(self.devices[r], "shard-step")
        except Exception as e1:
            if not StagedDistAgg._is_shard_fault(e1):
                raise
            self.ctx.check_killed("shard-retry")
            self.ladder.shard_retry(e1)
            try:
                out = attempt(self.devices[r], "shard-step")
            except Exception as e2:
                if not StagedDistAgg._is_shard_fault(e2):
                    raise
                failpoint.inject("exchange-degraded-replan")
                self.ctx.check_killed("shard-redispatch")
                self.ladder.redispatch(e2)
                spare = self.devices[(r + 1) % self.nd]
                try:
                    out = attempt(spare, "exchange-redispatch")
                except Exception as e3:
                    if not StagedDistAgg._is_shard_fault(e3):
                        raise
                    raise ShardFailure(
                        f"shard {r} failed on its device and on "
                        f"re-dispatch to a surviving device: {e3}") from e3
                self._warn_degraded(r, e2)
            self.ladder.shard_resume(rerun=1, reused=self.nd - 1)
            return out

    def _warn_degraded(self, r: int, err: BaseException) -> None:
        """One retryable warning per RECOVERED RANK (not per surviving
        rank): degraded-mesh completion is complete and exact — only the
        mesh shrank (surfaced by SHOW WARNINGS / EXPLAIN ANALYZE)."""
        guard = getattr(self.ctx, "guard", None)
        if guard is not None and hasattr(guard, "warnings"):
            guard.warnings.append(
                ("Warning", ShardFailure.code,
                 f"shard {r} persistently failed and was re-dispatched "
                 f"onto a surviving device (degraded mesh, retryable): "
                 f"{err}"))

    # -- stage 1: partition programs + bucket checkpoints -------------------

    def _stage1_program(self, info: dict, bcap: int) -> _PartitionProgram:
        exch, scan = info["exch"], info["scan"]
        caps = {id(scan): (info["cap"], 1)}
        # the PER-RANK bucket cap is part of the signature: a skewed
        # rank's exact-need resize builds one fresh program while every
        # other rank keeps hitting this cache — one recompile per skew
        sig = (f"stagedx1|nd={self.nd}|bcap={bcap}|" +
               tree_signature(exch, caps, 0,
                              scan_layouts=(info["lay_pairs"],)))
        prog = compile_cache.cache_get(sig)
        if prog is None:
            with compile_cache.build_lock(sig):
                prog = compile_cache.cache_get(sig)
                if prog is None:
                    t0 = time.perf_counter()
                    prog = _PartitionProgram(
                        exch, caps, self.nd, bcap,
                        scan_layouts=(info["lay_pairs"],))
                    compile_cache.cache_put(sig, prog)
                    compile_cache.charge_compile("dist", t0)
        return prog

    def _attempt_stage1(self, r: int, dev, prog, prep_vals, info: dict,
                        bcap: int, site: str):
        ph = self.ctx.phases
        dcols = None
        out = None
        try:
            with timeline.span("partition", "partition", pid=ph.conn_id,
                               req=ph.req, rank=r,
                               exchange=info["tag"]):
                failpoint.inject(site)
                with ph.phase("upload"):
                    dcols = {i: tuple(jax.device_put(a, dev) for a in t)
                             for i, t in info["rank_cols"][r].items()}
                phys_b = logi_b = 0
                for i, t in info["rank_cols"][r].items():
                    b = sum(a.nbytes for a in t)
                    phys_b += b
                    lay = info["layouts"].get(i)
                    logi_b += compress.raw_slab_bytes(lay, info["cap"]) \
                        if lay is not None else b
                ph.add_h2d(phys_b, logical=logi_b)
                ph.add_scan(phys_b, logical=logi_b)
                with scheduler.device_slot(self.ctx):
                    with ph.launch(prog.name, slab=r):
                        out = prog((dcols,),
                                   (jnp.int32(int(info["rank_rows"][r])),),
                                   prep_vals)
                ph.note_launch()
                ph.note_fused()
                with ph.drain():
                    jax.block_until_ready(out)
                # commit point of the rank's partition output: a fault here
                # loses ONLY this rank's buckets — the retry re-runs stage 1
                # for this rank alone
                failpoint.inject("exchange-checkpoint-write")
                with ph.phase("fetch"):
                    if info["exch"].kind == "hash":
                        need = int(np.asarray(jax.device_get(out["need"])))
                        if need > bcap:
                            # rows past the cap were dropped in the scatter —
                            # don't checkpoint; report exact need instead
                            return {"overflow": need}
                        got = jax.device_get({"bufs": out["bufs"],
                                              "counts": out["counts"]})
                        ck = {"bufs": got["bufs"],
                              "counts": np.asarray(got["counts"]),
                              "cap": bcap}
                    else:
                        got = jax.device_get({"bufs": out["bufs"],
                                              "live": out["live"]})
                        idx = np.nonzero(np.asarray(got["live"]))[0]
                        ck = {"rows": {i: (np.asarray(v)[idx],
                                           np.asarray(m)[idx])
                                       for i, (v, m) in got["bufs"].items()}}
                ph.add_d2h(tree_nbytes(got) + 4)
                return ck
        finally:
            # eager-delete discipline (StagedDistAgg._attempt): abandoned
            # buffers must be gone BEFORE a retry / re-dispatch uploads
            # its generation — never 2× HBM residency
            compile_cache.tree_delete(dcols)
            compile_cache.tree_delete(out)

    def _run_stage1(self, info: dict) -> List[dict]:
        """All ranks' bucket checkpoints for one exchange. Faults climb
        the per-rank ladder; a bucket-cap overflow resizes ONLY the
        overflowed rank at its exact reported need and re-runs it."""
        nd = self.nd
        ckpts: List[Optional[dict]] = [None] * nd
        to_run = [r for r in range(nd) if r not in info["skip"]]
        rounds = 0
        while to_run:
            self.ctx.check_killed("device-dispatch")
            over = []
            for r in to_run:
                bcap = info["bcaps"][r]
                prog = self._stage1_program(info, bcap)
                prep_vals = prog.collect_preps(info["flow_list"])
                ck = self._run_rank(
                    r, lambda dev, site, r=r, prog=prog, pv=prep_vals,
                    bcap=bcap: self._attempt_stage1(r, dev, prog, pv,
                                                    info, bcap, site))
                if "overflow" in ck:
                    over.append((r, ck["overflow"]))
                else:
                    ckpts[r] = ck
            if not over:
                break
            rounds += 1
            if rounds > 8:
                self.ladder.fallback("exchange")
                raise FragmentFallback(
                    "staged exchange: bucket resize did not converge",
                    reason="group-cap")
            for r, need in over:
                failpoint.inject("exchange-overflow")
                info["bcaps"][r] = self.ladder.resize(
                    "exchange", info["bcaps"][r], need=int(need), lo=64)
            self.ladder.attempt("exchange")
            self.ladder.partial_resume("exchange", rerun=len(over),
                                       reused=nd - len(over))
            to_run = [r for r, _ in over]
        # pruned ranks: empty-bucket identity (dtypes from a real rank's
        # checkpoint — route_buckets concatenates per column)
        ref = next(c for c in ckpts if c is not None)
        for r in range(nd):
            if ckpts[r] is not None:
                continue
            if info["exch"].kind == "hash":
                ckpts[r] = {"bufs": {i: (np.zeros(0, v.dtype),
                                         np.zeros(0, bool))
                                     for i, (v, m) in ref["bufs"].items()},
                            "counts": np.zeros(nd, np.int32), "cap": 0}
            else:
                ckpts[r] = {"rows": {i: (np.zeros(0, v.dtype),
                                         np.zeros(0, bool))
                                     for i, (v, m) in ref["rows"].items()}}
        return ckpts

    # -- stage 2: host routing + stage-3 source construction ----------------

    def _route(self, info: dict, ckpts: List[dict]) -> dict:
        """Route one exchange's committed checkpoints to their
        destination ranks and zero-pad each rank's receive payload to a
        shared power-of-two capacity — the stage-3 leaf's slab. The
        shared cap keeps stage 3 ONE program for all ranks (skew shows
        up as padding, not as per-rank recompiles)."""
        from tidb_tpu.parallel import collective as C
        nd = self.nd
        ph = self.ctx.phases
        with timeline.span("checkpoint", "checkpoint", pid=ph.conn_id,
                           req=ph.req, exchange=info["tag"]):
            if info["exch"].kind == "hash":
                routed, recv_rows = C.route_buckets(ckpts, nd)
            else:
                cols = list(ckpts[0]["rows"].keys())
                full = {i: (np.concatenate([ck["rows"][i][0] for ck in ckpts]),
                            np.concatenate([ck["rows"][i][1] for ck in ckpts]))
                        for i in cols}
                n = full[cols[0]][0].shape[0] if cols else 0
                routed = [full] * nd
                recv_rows = [n] * nd
            recv_cap = pow2(max(max(recv_rows), 1), lo=64)

            def pad(bufs):
                cols = {}
                for i, (v, m) in bufs.items():
                    pv = np.zeros(recv_cap, dtype=v.dtype)
                    pm = np.zeros(recv_cap, dtype=bool)
                    pv[:v.shape[0]] = v
                    pm[:m.shape[0]] = m
                    cols[i] = (pv, pm)
                return cols

            if info["exch"].kind == "hash":
                rank_cols = [pad(routed[r]) for r in range(nd)]
            else:
                shared = pad(routed[0])      # replicated build: pad once
                rank_cols = [shared] * nd
        timeline.tag(recv_rows=[int(x) for x in recv_rows])
        return {"rank_cols": rank_cols,
                "rank_rows": np.asarray(recv_rows, dtype=np.int32),
                "cap": recv_cap, "layouts": {}, "lay_pairs": ()}

    # -- stage 3: per-rank receive/probe/dedup programs ----------------------

    def _attempt_stage3(self, r: int, dev, prog, prep_vals, site: str):
        ph = self.ctx.phases
        root = self.new_root
        dcols = None
        out = None
        try:
            with timeline.span("probe", "probe", pid=ph.conn_id,
                               req=ph.req, rank=r):
                failpoint.inject(site)
                with ph.phase("upload"):
                    dcols = tuple(
                        {i: tuple(jax.device_put(a, dev) for a in t)
                         for i, t in src["rank_cols"][r].items()}
                        for src in self.stage3_order)
                phys_b = logi_b = 0
                for src in self.stage3_order:
                    for i, t in src["rank_cols"][r].items():
                        b = sum(a.nbytes for a in t)
                        phys_b += b
                        lay = src["layouts"].get(i)
                        logi_b += compress.raw_slab_bytes(lay, src["cap"]) \
                            if lay is not None else b
                ph.add_h2d(phys_b, logical=logi_b)
                ph.add_scan(phys_b, logical=logi_b)
                rows = tuple(jnp.int32(int(src["rank_rows"][r]))
                             for src in self.stage3_order)
                with scheduler.device_slot(self.ctx):
                    with ph.launch(prog.name, slab=r):
                        out = prog(dcols, rows, prep_vals)
                ph.note_launch()
                ph.note_fused()
                if isinstance(root, PhysHashAgg):
                    # the tag lands on this rank's `probe` span
                    A.count_agg_partial(A.note_grouping(root, None, self.gcap))
                with ph.drain():
                    jax.block_until_ready(out)
                failpoint.inject("shard-checkpoint-write")
                with ph.phase("fetch"):
                    ju = np.asarray(jax.device_get(out["join_unique"]),
                                    dtype=bool)
                    jt = np.asarray(jax.device_get(out["join_totals"]))
                    if isinstance(root, PhysHashAgg):
                        ngt = int(np.asarray(jax.device_get(out["n_groups"])))
                        live_n = ngt if root.group_exprs else 1
                        k = min(live_n, prog.group_cap)
                        got = jax.device_get(
                            {"keys": [(v[:k], m[:k]) for v, m in out["keys"]],
                             "states": [tuple(a[:k] for a in st)
                                        for st in out["states"]]})
                        ck = {"ng": k, "keys": got["keys"],
                              "states": got["states"]}
                    else:
                        got = jax.device_get({"cols": out["cols"],
                                              "live": out["live"]})
                        ck = got
                        ngt = 0
                ph.add_d2h(tree_nbytes(got) + 4)
                return ck, ngt, ju, jt
        finally:
            compile_cache.tree_delete(dcols)
            compile_cache.tree_delete(out)

    def _run_stage3(self) -> List[dict]:
        nd = self.nd
        outs: List[Optional[dict]] = [None] * nd
        ng_true = [0] * nd
        caps_ran = [0] * nd
        n_joins = len(self.join_cfgs)
        rank_ju = np.ones((nd, max(n_joins, 1)), dtype=bool)
        rank_jt = np.zeros((nd, max(n_joins, 1)), dtype=np.int64)
        caps3 = {id(src["scan"]): (src["cap"], 1)
                 for src in self.stage3_order}
        lays3 = tuple(src["lay_pairs"] for src in self.stage3_order)
        to_run = list(range(nd))
        rounds = 0
        while True:
            self.ctx.check_killed("device-dispatch")
            prog = A.get_tree_program(self.new_root, caps3, self.gcap,
                                    join_cfgs=list(self.join_cfgs),
                                    scan_layouts=lays3)
            prep_vals = prog.collect_preps(self.flow_list2)
            for r in to_run:
                ck, ngt, ju, jt = self._run_rank(
                    r, lambda dev, site, r=r, prog=prog, pv=prep_vals:
                    self._attempt_stage3(r, dev, prog, pv, site))
                outs[r] = ck
                ng_true[r] = ngt
                caps_ran[r] = self.gcap
                if n_joins:
                    rank_ju[r, :n_joins] = ju
                    rank_jt[r, :n_joins] = jt
            rounds += 1
            if rounds > 8:
                self.ladder.fallback("dist")
                raise FragmentFallback(
                    "staged exchange: escalation did not converge",
                    reason="group-cap")
            # lost join bets / out-cap overflows first: a changed cfg
            # invalidates EVERY rank's checkpoint (unique-mode results
            # under the old bet are not trustworthy) — rerun all
            retry_all = False
            for ji, cfg in enumerate(self.join_cfgs):
                new_cfg, action = escalate_join(
                    cfg, bool(rank_ju[:, ji].all()),
                    int(rank_jt[:, ji].max()), self.out_cap_max,
                    flip_out_cap=self._shard_out_cap(cfg),
                    ladder=self.ladder)
                if action == "over-max":
                    self.ladder.fallback("join")
                    raise FragmentFallback(
                        f"join fan-out {int(rank_jt[:, ji].max())} "
                        f"exceeds the per-shard device cap",
                        reason="join-cap")
                if new_cfg is not None:
                    self.join_cfgs[ji] = new_cfg
                    retry_all = True
            if retry_all:
                self.ladder.attempt("dist")
                to_run = list(range(nd))
                continue
            over = [r for r in range(nd) if ng_true[r] > caps_ran[r]]
            if not over:
                return outs
            if self.gcap >= self.cap_limit:
                self.ladder.fallback("group")
                raise FragmentFallback("group cap overflow", reason="group-cap")
            self.gcap = self.ladder.resize(
                "group", self.gcap, need=max(ng_true[r] for r in over),
                max_cap=self.cap_limit)
            self.ladder.attempt("group")
            self.ladder.partial_resume("group", rerun=len(over),
                                       reused=nd - len(over))
            to_run = over

    # -- driver ---------------------------------------------------------------

    def execute(self) -> List[dict]:
        """Stages 1→2→3 across every exchange; → per-rank stage-3
        checkpoints ({ng, keys, states} for an agg root, {cols, live}
        for window/row roots) for the caller's host merge/decode."""
        stage3_srcs = {}
        for info in self.exchanges:
            ckpts = self._run_stage1(info)
            stage3_srcs[id(info["leaf"])] = \
                dict(self._route(info, ckpts), scan=info["leaf"])
        self.stage3_order = []
        for scan in scans_of(self.new_root):
            if isinstance(scan, _ExchangeLeaf):
                self.stage3_order.append(stage3_srcs[id(scan)])
            else:
                self.stage3_order.append(self.direct[id(scan)])
        return self._run_stage3()


def unify_string_join_dicts(root: PhysicalPlan, host_cols) -> None:
    """Exchange-side dictionary unification for string equi-join keys.

    Each class of scan columns transitively connected by string equi
    joins is re-encoded into ONE shared sorted dictionary host-side,
    before sharding. Equal strings then carry equal codes on every side,
    so hash exchanges co-locate them (the repartition invariant of
    cophandler/mpp_exec.go:158-173) and the probe-side KeyRemap LUT
    degenerates to identity. host_cols: (id(scan), col_idx) →
    [codes, valid, dictionary], mutated in place."""
    parent: Dict = {}

    def find(x):
        root_ = x
        while parent.get(root_, root_) != root_:
            root_ = parent[root_]
        while parent.get(x, x) != x:
            parent[x], x = root_, parent[x]
        return root_

    def union(a, b):
        parent.setdefault(a, a)
        parent.setdefault(b, b)
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[rb] = ra

    for node in walk_nodes(root):
        if not isinstance(node, PhysHashJoin):
            continue
        for l, r in node.equi or []:
            if not (l.ftype.kind.is_string or r.ftype.kind.is_string):
                continue
            if l.ftype.is_ci or r.ftype.is_ci:
                raise FragmentFallback(
                    "ci-collated join keys need fold-aware dictionary "
                    "unification (single-chip / CPU only)",
                    reason="string-dict")
            lh = trace_scan_col(node.children[0], l.index) \
                if isinstance(l, ColumnRef) else None
            rh = trace_scan_col(node.children[1], r.index) \
                if isinstance(r, ColumnRef) else None
            if lh is None or rh is None:
                raise FragmentFallback(
                    "string join key is not a scan column",
                    reason="string-dict")
            union((id(lh[0]), lh[1]), (id(rh[0]), rh[1]))

    groups: Dict = {}
    for m in parent:
        groups.setdefault(find(m), []).append(m)
    for members in groups.values():
        if len(members) < 2:
            continue
        dicts = [host_cols[m][2] for m in members
                 if m in host_cols and host_cols[m][2] is not None]
        if len(dicts) < len(members):
            raise FragmentFallback("string join key without dictionary",
                                   reason="string-dict")
        union_d = np.unique(np.concatenate(dicts))
        for m in members:
            codes, _valid, d = host_cols[m]
            remap = np.searchsorted(union_d, d).astype(np.int32)
            host_cols[m][0] = remap[codes]
            host_cols[m][2] = union_d


def _flatten_cols(cols):
    """[(v,m) or None...] → (flat arrays for the collective, meta)."""
    flat: List = []
    meta: List[Optional[int]] = []
    for c in cols:
        if c is None:
            meta.append(None)
        else:
            meta.append(len(flat))
            flat.append(c[0])
            flat.append(c[1])
    return flat, meta


def _unflatten_cols(flat, meta):
    out = []
    for m in meta:
        out.append(None if m is None else (flat[m], flat[m + 1]))
    return out


# ---------------------------------------------------------------------------
# Drivers (the MPPGather role of executor/mpp_gather.go:42)
# ---------------------------------------------------------------------------


def _get_dist_program(root, caps, group_cap, mesh, bucket_caps,
                      join_cfgs=None, scan_layouts=None):
    bux = ",".join(str(bucket_caps[id(n)]) for n in walk_nodes(root)
                   if isinstance(n, PhysExchange) and n.kind == "hash")
    sig = (f"dist={mesh.devices.size}|bux={bux}|" +
           tree_signature(root, caps, group_cap, join_cfgs,
                          scan_layouts=scan_layouts))
    return compile_cache.get_or_build(sig, "dist", lambda: DistTreeProgram(
        root, caps, group_cap, mesh, dict(bucket_caps), join_cfgs,
        scan_layouts, kind="dist", sig=sig))


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


def _staged_dist_chain(root) -> Optional[List[PhysicalPlan]]:
    """Root→scan chain when this dist fragment is eligible for the
    staged checkpointable path: an agg root over an exchange-free
    Scan/Selection/Projection chain (a PhysExchange anywhere breaks
    linearize), no DISTINCT aggs (per-rank dedup cannot merge
    without key co-location), and every stage device-capable for the
    single-device chain program."""
    if not isinstance(root, PhysHashAgg):
        return None
    if any(d.distinct and d.args for d in root.aggs):
        return None
    chain = linearize(root)
    if chain is None or not fragment_ok(root, 0):
        return None
    return chain

def _run_dist_agg_staged(ctx, schema, root, mesh, host_cols,
                          scan_meta) -> Optional[Chunk]:
    """Staged checkpointable dist agg (dist_fragment.StagedDistAgg):
    per-rank partials → host checkpoints → host merge. Returns None
    when the fragment is not eligible — the caller falls through to
    the monolithic shard_map program."""
    chain = _staged_dist_chain(root)
    if chain is None or len(scan_meta) != 1:
        return None
    scan, used_enc, total = scan_meta[0]
    used_cols = A.used_column_indices(chain)
    if not set(used_cols) <= set(used_enc):
        return None
    nd = mesh.devices.size
    cap = pow2((total + nd - 1) // nd, lo=8)
    # per-column compressed layouts, chosen GLOBALLY (one layout must
    # serve every rank's slab — the per-rank chain partials share one
    # traced program). Each rank packs its own slab independently, so
    # no cap/word-alignment constraint applies here; dictionaries
    # would need per-device replication, so allow_dict=False.
    comp_on = var_on(ctx.vars, "tidb_tpu_compression")
    layouts = {}
    if comp_on:
        for i in used_cols:
            vals, valid, _d = host_cols[(id(scan), i)]
            if vals.ndim != 1:
                continue
            lay, _dv = compress.choose_layout(vals, valid,
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
            zonemap.note_skipped(ctx.phases, len(skip_ranks))
            phys_b = logi_b = 0
            for i in used_cols:
                vals, valid, _d = host_cols[(id(scan), i)]
                lay = layouts.get(i)
                if lay is not None:
                    phys_b += compress.packed_slab_bytes(lay, cap)
                    logi_b += compress.raw_slab_bytes(lay, cap)
                else:
                    b = cap * vals.dtype.itemsize + cap
                    phys_b += b
                    logi_b += b
            zonemap.note_h2d_skipped(ctx.phases,
                                     phys_b * len(skip_ranks))
            ctx.phases.add_scan(
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
            cols[i] = compress.pack_slab(lay, pv, pm) \
                if lay is not None else (pv, pm)
        rank_cols.append(cols)
    rank_rows = np.clip(total - np.arange(nd) * cap, 0,
                        cap).astype(np.int32)
    in_types = [scan.schema.field_types[i] for i in used_cols]
    vars_ = ctx.vars
    group_cap = var_int(vars_, "tidb_tpu_group_cap")
    cap_limit = cap * nd
    gcap = A.initial_group_cap(root, group_cap, cap_limit)
    ladder = CapacityLadder(guard=getattr(ctx, "guard", None),
                            stats=ctx.escalation)
    runner = StagedDistAgg(root, chain, mesh, rank_cols, rank_rows,
                           dicts, used_cols, in_types, cap, gcap,
                           cap_limit, ctx, ladder,
                           layouts=layouts or None,
                           skip_ranks=skip_ranks)
    pass_outs = runner.execute()
    flows, _root_dicts = dictionary_flows(root, {id(scan): dicts})
    inp_dicts = {i: d for i, d in
                 enumerate(flows.get(id(root), []))}
    with ctx.phases.phase("decode"):
        return host_decode.merge_tree_agg_passes(
            ctx, schema, root, pass_outs, inp_dicts)

def _run_dist_exchange_staged(ctx, schema, root, mesh, host_cols,
                               scan_meta) -> Optional[Chunk]:
    """Staged checkpointable dist exchange (dist_fragment.
    StagedDistExchange): per-rank partition programs → device→host
    bucket checkpoints + host routing → per-rank fused probe/dedup
    programs over the rewritten (exchange→leaf) plan. Returns None
    when the plan is ineligible — the caller falls through to the
    monolithic shard_map program, the byte-exactness oracle."""
    grafted = staged_exchange_plan(root)
    if grafted is None:
        return None
    new_root, grafts = grafted
    ladder = CapacityLadder(guard=getattr(ctx, "guard", None),
                            stats=ctx.escalation)
    runner = StagedDistExchange(root, new_root, grafts, mesh,
                                host_cols, scan_meta, ctx,
                                ladder)
    outs = runner.execute()
    if isinstance(new_root, PhysHashAgg):
        # the exchange re-keyed on the group keys, so each group's
        # rows landed wholly on ONE rank: the host merge never
        # combines two partials of one group (DISTINCT states stay
        # exact — same invariant as the monolithic owner merge)
        inp_dicts = {i: d for i, d in
                     enumerate(runner.flows2.get(id(new_root), []))}
        with ctx.phases.phase("decode"):
            return host_decode.merge_tree_agg_passes(
                ctx, schema, new_root, outs, inp_dicts)
    dicts_root = {i: d for i, d in enumerate(runner.root_dicts2)}
    cols_vm = [(np.concatenate([np.asarray(o["cols"][ci][0])
                                for o in outs]),
                np.concatenate([np.asarray(o["cols"][ci][1])
                                for o in outs]))
               for ci in range(len(new_root.schema))]
    live = np.concatenate([np.asarray(o["live"]) for o in outs])
    with ctx.phases.phase("decode"):
        return host_decode.compact_decode(cols_vm, live,
                               new_root.schema.field_types,
                               dicts_root)

def run_device_dist(ctx, plan, schema) -> Chunk:
    # ORDER BY / TopN over the agg: shard programs compute the agg
    # only — the ordering stays a host concern after the shard merge
    # (the fused finalize is a single-device shape; a shard program
    # would pass the agg through and emit un-aggregated rows)
    order_root, root = strip_order_root(plan.root)
    chunk = _dist_exec(ctx, plan, schema, root)
    if order_root is not None:
        chunk = host_decode.host_order(chunk, order_root, root.schema)
        chunk = host_decode.topn_slice(chunk, order_root)
    return chunk

def _dist_exec(ctx, plan, schema, root) -> Chunk:
    """Planner-fragmented tree as one shard_map program over the mesh
    (executor/dist_fragment.py; the MPPGather role of
    executor/mpp_gather.go:42 lives in this method)."""
    from tidb_tpu.parallel import make_mesh

    nd = plan.dist
    if len(jax.devices()) < nd:
        raise FragmentFallback(f"mesh wants {nd} devices, "
                               f"{len(jax.devices())} available",
                               reason="mesh-size")
    mesh = make_mesh(nd)
    P = jax.sharding.PartitionSpec
    sharding = jax.sharding.NamedSharding(mesh, P("shard"))

    scans = scans_of(root)
    caps: Dict[int, int] = {}
    scan_inputs = []
    scan_rows = []
    scan_dicts = {}
    scan_bounds: Dict[int, Dict[int, Tuple[int, int]]] = {}
    host_cols: Dict[Tuple[int, int], list] = {}
    scan_meta = []
    ph = ctx.phases
    for scan in scans:
        used = scan.used_columns if scan.used_columns else \
            list(range(len(scan.schema)))
        parts, total = device_cache.collect_parts(ctx, scan)
        if total == 0:
            raise FragmentFallback("empty input", reason="empty-input")
        shim = types.SimpleNamespace(parts=parts)
        ftypes = scan.schema.field_types
        with ph.phase("encode"):
            for i in used:
                vals, valid = device_cache.materialize_col(shim, i)
                vals, dictionary = device_cache.encode_col(ftypes[i], vals,
                                                           valid)
                host_cols[(id(scan), i)] = [vals, valid, dictionary]
        scan_meta.append((scan, used, total))
    # string equi-join keys: unify dictionaries BEFORE sharding so
    # equal strings hash equal on every shard (dist_fragment doc)
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
    if var_on(ctx.vars, "tidb_tpu_dist_staged"):
        staged = _run_dist_agg_staged(ctx, schema, root, mesh,
                                          host_cols, scan_meta)
        if staged is not None:
            return staged
    if var_on(ctx.vars, "tidb_tpu_dist_staged_exchange"):
        staged = _run_dist_exchange_staged(ctx, schema, root, mesh,
                                               host_cols, scan_meta)
        if staged is not None:
            return staged
    comp_on = var_on(ctx.vars, "tidb_tpu_compression")
    dist_layouts = []
    for scan, used, total in scan_meta:
        cap = pow2((total + nd - 1) // nd, lo=8)
        caps[id(scan)] = cap
        cols = {}
        dicts = {}
        bounds: Dict[int, Tuple[int, int]] = {}
        lay_pairs = []
        for i in used:
            vals, valid, dictionary = host_cols[(id(scan), i)]
            dicts[i] = dictionary
            b = device_cache.col_bounds(vals, valid, dictionary)
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
                    cap % compress.WORD_BITS == 0:
                lay, _dv = compress.choose_layout(vals, valid,
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
                        compress.pack_slab(
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

    flows, root_dicts = dictionary_flows(root, scan_dicts)
    flow_list = [flows.get(id(n), []) for n in walk_nodes(root)]

    # initial bucket cap per hash exchange: 4× the balanced share
    # (tidb_tpu_exchange_bucket_cap overrides — skew/retry testing)
    cap_override = var_int(
        ctx.vars, "tidb_tpu_exchange_bucket_cap")
    bucket_caps: Dict[int, int] = {}
    for node in walk_nodes(root):
        if isinstance(node, PhysExchange) and node.kind == "hash":
            est = max(int(node.est_rows), 1)
            bucket_caps[id(node)] = cap_override or pow2(
                4 * ((est + nd - 1) // nd), lo=64)

    vars_ = ctx.vars
    group_cap = var_int(vars_, "tidb_tpu_group_cap")
    is_agg = isinstance(root, PhysHashAgg)
    max_cap = max(caps.values())
    gcap = A.initial_group_cap(root, group_cap, max_cap * nd) \
        if is_agg else 1

    hash_exchanges = [n for n in walk_nodes(root)
                      if isinstance(n, PhysExchange)
                      and n.kind == "hash"]
    def _shard_out_cap(cfg):
        # expand caps are PER SHARD: start from the balanced share of
        # the global estimate; skew comes back as join_need → 1 retry
        return pow2(int(cfg.est * 1.3 / nd) + 16, lo=1024)

    join_cfgs = plan_join_configs(root, scan_bounds)
    join_cfgs = [dataclasses.replace(c, out_cap=_shard_out_cap(c))
                 if c.mode == "expand" else c for c in join_cfgs]
    out_cap_max = var_int(vars_, "tidb_tpu_join_out_cap")
    ladder = CapacityLadder(guard=getattr(ctx, "guard", None),
                            stats=ctx.escalation)
    shard_faults = 0
    while True:
        # each retrace round is a checkpoint: a killed query must not
        # queue another multi-shard compile
        ctx.check_killed("device-dispatch")
        prog = _get_dist_program(root, caps, gcap, mesh, bucket_caps,
                                 join_cfgs, dist_layouts)
        prep_vals = prog.collect_preps(flow_list)
        try:
            # a shard fault (failpoint or real device error) can
            # surface at the drain OR the fetch — both stay in the
            # try. The scheduler slot covers only the async dispatch;
            # the GIL-releasing drain runs outside it so sibling
            # statements' host phases overlap the mesh execution.
            with scheduler.device_slot(ctx):
                with ph.launch(prog.name):
                    raw = prog(scan_inputs, scan_rows, prep_vals)
            ph.note_launch()
            if is_agg:
                A.count_agg_partial(A.note_grouping(root, None, gcap))
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
            new_cfg, action = escalate_join(
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
            ft = schema[kc]
            v, m = out["keys"][kc]
            d = inp[e.index] if isinstance(e, ColumnRef) and \
                e.index < len(inp) else None
            cols.append(host_decode.decode_col(ft, np.asarray(v)[idx],
                                    np.asarray(m)[idx], d))
        for agg, st in zip([build_agg(d) for d in root.aggs],
                           out["states"]):
            v, m = agg.final(np, tuple(np.asarray(a) for a in st))
            cols.append(host_decode.decode_col(agg.ftype, np.asarray(v)[idx],
                                    np.asarray(m)[idx], None))
        if root.group_exprs and not len(idx):
            return empty_chunk(schema)
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
                piece.append(host_decode.decode_col(
                    ft, np.asarray(v)[lo:lo + n],
                    np.asarray(m)[lo:lo + n], dicts_root.get(ci)))
            pieces.append(Chunk(piece))
        merged = Chunk.concat(pieces) if len(pieces) > 1 else pieces[0]
        merged = host_decode.host_order(merged, root, root.schema)
        return host_decode.topn_slice(merged, root)
    # window / selection / projection / join row root: compact the
    # shard-concatenated padded output by its live mask
    return host_decode.compact_decode(out["cols"], out["live"],
                           root.schema.field_types, dicts_root)
