"""The device fragment executor (SURVEY §7 stages 3-5): `TpuFragmentExec`,
the Volcano leaf a PhysTpuFragment builds, on top of the three device
drivers it dispatches to.

  * open / next / retry / fallback: any device failure (untraceable
    builtin, unsupported shape) falls back to building the embedded CPU
    subtree — the reference's allowlist philosophy (expression.go
    scalarExprSupportedByTiFlash) enforced by trying, not by cataloguing;
    a lost device retries once on a survivor; `tidb_tpu_strict` raises
    instead of falling back;
  * `_run_device`: a linear chain under an aggregate runs through
    `agg_slabs.run_agg_slabs` (the main path: every benchmark cell), a
    join tree or window through `tree_driver.run_device_tree`, a
    distributed plan through `dist_fragment.run_device_dist`; ORDER BY and
    filter roots of a chain run here, a program a slab.

Which plans become fragments is eligibility.py's (the planner asks it);
this module imports the drivers, none of them imports it.
"""

from __future__ import annotations

import copy
import logging
import time
from typing import List, Optional

import numpy as np

from tidb_tpu.chunk import Chunk
from tidb_tpu.errors import (CapacityError, DeviceLost, ExecutionError,
                             MemoryQuotaExceeded, QueryKilledError,
                             QueryTimeout, ShardFailure)
from tidb_tpu.executor import (OperatorStats, agg_slabs, device_cache,
                               dist_fragment, eligibility, empty_chunk,
                               host_decode, microbatch, scheduler,
                               tree_driver, zonemap)
from tidb_tpu.executor.eligibility import (identity_projection, linearize,
                                           scans_of)
from tidb_tpu.expression import Constant, Expression, ParamExpr, ScalarFunc
from tidb_tpu.ops.factorize import RUNS, grouping_mode
from tidb_tpu.ops.jax_env import jax, jnp
from tidb_tpu.planner.physical import (PhysExchange, PhysHashAgg,
                                       PhysSelection, PhysSort,
                                       PhysTableScan, PhysTopN,
                                       PhysTpuFragment, PhysWindow,
                                       PhysicalPlan)
from tidb_tpu.sysvars import var_int, var_on
from tidb_tpu.util import failpoint, roofline, timeline
from tidb_tpu.util.escalation import CapacityLadder
from tidb_tpu.util.observability import REGISTRY
from tidb_tpu.util.phases import tree_nbytes
from tidb_tpu.util.tracing import maybe_span

log = logging.getLogger("tidb_tpu.fragment")

# PhaseTimer of the most recent device fragment run (encode/upload/compute/
# fetch/decode seconds + overlap efficiency), for tests.
LAST_PHASES = None


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


class TpuFragmentExec:
    """Volcano leaf running the fused device program (built by
    builder.build(), the builder.go:144 seam)."""

    def __init__(self, plan: PhysTpuFragment, build=None):
        self.plan = plan
        # the builder's `build`, for the CPU subtree a fallback runs (None
        # where nothing falls back: a nested fragment, a compaction's warm
        # run — both call `_run_device` themselves)
        self._build = build
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
        self.rows_on_device = False

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
            strict = var_on(self.ctx.vars, "tidb_tpu_strict")
            # checkpoint BEFORE device dispatch: a killed/expired query
            # must not pay for compile + upload it will never use
            self.ctx.check_killed("device-dispatch")
            retried_lost = False
            while True:
                try:
                    _t0 = time.perf_counter()
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
                    exec_s = time.perf_counter() - _t0
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
                except eligibility.FragmentFallback as e:
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
                    tgt = None if retried_lost \
                        else scheduler.device_fault(self.ctx, e)
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
        self.fallback_code = code \
            if code in eligibility.FALLBACK_REASONS else "shape"
        detail = detail or self.fallback_code
        self.fallback_reason = f"{self.fallback_code}: {detail}" \
            if detail != self.fallback_code else self.fallback_code
        REGISTRY.inc("tidb_tpu_device_fallbacks_total",
                     {"reason": self.fallback_code})

    def _fallback_next(self) -> Optional[Chunk]:
        root = self.plan.root
        if getattr(self.plan, "dist", 0) > 1:
            # distributed plans carry Exchange nodes — pure repartitioning
            # boundaries with no single-node executor; strip them
            root = _strip_exchanges(root)
        self._cpu_root = self._build(root)
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
        store = getattr(self.ctx.snapshot, "store", None)
        return device_cache.protect_tables(
            (id(store), s.table.id) for s in scans_of(self.plan.root))

    def _note_reader(self) -> None:
        """This fragment read its tables on the device: a compaction of one
        of them runs it once over the rebuilt generation before the swap
        (delta._warm), so what a re-chosen layout compiles, it compiles
        there."""
        store = getattr(self.ctx.snapshot, "store", None)
        if store is not None and getattr(self.ctx, "txn", None) is None:
            tables = tuple(sorted({s.table.id
                                   for s in scans_of(self.plan.root)}))
            sql = getattr(getattr(self.ctx, "guard", None), "sql", None)
            device_cache.note_reader(
                id(store), tables, self.plan, self.ctx.vars,
                (sql or id(self.plan), self.plan.root.name, tables),
                run_fragment)

    # ---- device pipeline ---------------------------------------------------
    def _run_device(self) -> Chunk:
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
            return dist_fragment.run_device_dist(self.ctx, self.plan,
                                                 self.schema)
        chain = linearize(self.plan.root)
        if chain is None:
            if eligibility.has_join(self.plan.root) or \
                    eligibility.has_window(self.plan.root):
                # joins, and windowed shapes with no linear-chain lowering
                # (interior windows), run as tree programs
                return tree_driver.run_device_tree(self)
            raise eligibility.FragmentFallback("not a chain", reason="shape")
        # ORDER BY / TopN directly over the agg: strip the order root and
        # run the rest agg-rooted — the ordering becomes the agg's fused
        # device finalize
        order_root = None
        if len(chain) > 1 and isinstance(chain[0], (PhysTopN, PhysSort)):
            k = 1
            while k < len(chain) and identity_projection(chain[k]):
                k += 1
            if k < len(chain) and isinstance(chain[k], PhysHashAgg):
                order_root, chain = chain[0], chain[k:]
        scan: PhysTableScan = chain[-1]
        vars_ = self.ctx.vars
        max_slab = var_int(vars_, "tidb_tpu_max_slab_rows")
        group_cap = var_int(vars_, "tidb_tpu_group_cap")

        used = agg_slabs.used_column_indices(chain)
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
                    agg_slabs.chain_key_bounds(chain, ent)) == RUNS:
                delta_ok = False
                continue
            break
        if ent.total == 0:
            raise eligibility.FragmentFallback("empty input",
                                               reason="empty-input")
        dicts = {i: ent.dicts.get(i) for i in used}
        slab_cap, n_slabs = ent.slab_cap, ent.n_slabs

        # zone-map slab pruning: the scan's conjuncts evaluated host-side
        # against per-slab stats (over dict codes / encoded ints, no
        # decode). A pruned slab costs NOTHING downstream: the cold
        # stream already skipped its encode+upload, and slab_ids keeps it
        # out of every program launch and escalation checkpoint.
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
            return tree_driver.run_device_tree(self)

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
                return empty_chunk(self.schema)

        layouts = agg_slabs.ent_layouts(ent, used)
        if isinstance(root, PhysHashAgg):
            # stats-informed grouping: small known key domains skip the
            # sort (open_table commits dictionaries/bounds EAGERLY — before
            # the stream runs — exactly so program construction can use
            # them here)
            key_bounds = agg_slabs.chain_key_bounds(chain, ent)
            return agg_slabs.run_agg_slabs(
                agg_slabs.ChainSlabs(self.ctx, chain, ent, stream, used,
                                     in_types, dicts, key_bounds, layouts,
                                     slab_ids),
                self.schema,
                agg_slabs.initial_group_cap(root, group_cap, slab_cap,
                                            key_bounds),
                order_root,
                CapacityLadder(guard=getattr(self.ctx, "guard", None),
                               stats=self.ctx.escalation),
                self.rows_on_device)
        # order/filter roots have no group capacity to overflow — one pass
        if isinstance(root, (PhysTopN, PhysSort)):
            prog = agg_slabs.get_program(chain, used, in_types, slab_cap,
                                         group_cap, layouts=layouts)
            prep_vals = prog.collect_preps(dicts)
            return self._execute_order(prog, root, ent, dicts, prep_vals,
                                       stream, slab_ids=slab_ids)
        # filter roots: lift comparison literals into prepared parameters
        # so `k = 17` and `k = 42` share one compiled program — and, when
        # several such statements are queued at once, ONE batched launch
        # (executor/microbatch.py). Falls back to the literal-baked
        # program when nothing is parametrizable.
        mb_max = var_int(vars_, "tidb_tpu_microbatch_max")
        chain_p = _parametrize_chain(chain) if mb_max >= 1 else None
        if chain_p is not None:
            sig = agg_slabs.chain_signature(
                chain_p, used, in_types, slab_cap, group_cap, None,
                layouts) + "|pairs=False,0"
            prog = agg_slabs.get_program(chain_p, used, in_types, slab_cap,
                                         group_cap, layouts=layouts,
                                         sig=sig)
            # prep values MUST come from THIS statement's chain: the
            # cached program may hold another statement's ParamExpr nodes
            prep_vals = agg_slabs.collect_chain_preps(chain_p, dicts)
            if mb_max >= 2 and stream is None:
                res = microbatch.execute(self, prog, root, ent, dicts,
                                         prep_vals, slab_ids, sig, mb_max)
                if res is not None:
                    return res
        else:
            prog = agg_slabs.get_program(chain, used, in_types, slab_cap,
                                         group_cap, layouts=layouts)
            prep_vals = prog.collect_preps(dicts)
        return self._execute_filter(prog, root, ent, dicts, prep_vals,
                                    stream, slab_ids=slab_ids)


    def run_nested(self, frag: PhysTpuFragment) -> agg_slabs.DeviceAggRows:
        """Run a nested device-rows fragment to its merged groups, which
        stay in HBM. It is a fragment like any other — own signature,
        specialization entry, capacity ladder, launches in this
        statement's ledger — except that nothing is fetched but the
        counts its ladder validates."""
        sub = TpuFragmentExec(frag)
        sub.open(self.ctx)
        sub.rows_on_device = True
        with timeline.span("device.fragment", "frag", root=frag.root.name,
                           rows="device"):
            with sub._protect_tables():
                rows = sub._run_device()
        if not isinstance(rows, agg_slabs.DeviceAggRows):
            # a path that answers from the host (every slab pruned, the
            # mega-slab loop): the enclosing tree cannot take host rows
            raise eligibility.FragmentFallback(
                "nested fragment left the device", reason="shape")
        return rows

    # -- topn / sort ---------------------------------------------------------
    def _execute_order(self, prog, root, ent, dicts, prep_vals,
                       stream=None, slab_ids=None) -> Chunk:
        ph = self.ctx.phases
        outs = []
        for cols, n in agg_slabs.slab_iter(ent, stream, prog.used_cols,
                                       slab_ids):
            with scheduler.device_slot(self.ctx):
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
            pieces = [host_decode.cols_chunk(root, cols_host, dicts)
                      for cols_host in host_tree]
            if len(pieces) == 1:
                merged = pieces[0]
            else:
                # per-slab top-(k+off) candidates merged on host (small)
                merged = Chunk.concat(pieces)
                merged = host_decode.host_order(merged, root,
                                                self.plan.root.schema)
            return host_decode.topn_slice(merged, root)


    # -- selection / projection ----------------------------------------------
    def _execute_filter(self, prog, root, ent, dicts, prep_vals,
                        stream=None, slab_ids=None) -> Chunk:
        ph = self.ctx.phases
        outs = []
        for cols, n in agg_slabs.slab_iter(ent, stream, prog.used_cols,
                                       slab_ids):
            with scheduler.device_slot(self.ctx):
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
                    piece.append(host_decode.decode_col(
                        ft, vals, mask,
                        host_decode.positional_dict(root, ci, dicts)))
                pieces.append(Chunk(piece))
            return Chunk.concat(pieces) if len(pieces) > 1 else pieces[0]



def _strip_exchanges(plan: PhysicalPlan) -> PhysicalPlan:
    plan.children = [_strip_exchanges(c) for c in plan.children]
    if isinstance(plan, PhysExchange):
        return plan.children[0]
    return plan


def run_fragment(plan: PhysTpuFragment, ctx) -> None:
    """`plan`'s device run once more under `ctx`, its result dropped: what
    a compaction does with a table's recent readers before its swap
    (`device_cache.note_reader` keeps this beside each, `delta._warm`
    calls it), so that what a rebuilt generation compiles, it compiles
    there."""
    ex = TpuFragmentExec(plan)
    ex.open(ctx)
    with ex._protect_tables():
        ex._run_device()
