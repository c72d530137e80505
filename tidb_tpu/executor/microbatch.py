"""Same-plan micro-batching: N queued statements, ONE device program.

A burst of point reads / prepared executes sharing a plan shape used to
pay N independent dispatches through the device scheduler. Accelerator
SQL serving (the Presto-on-GPU line of work) wins exactly this case by
coalescing: statements whose compiled program would be byte-identical
except for their comparison literals execute as one traced program with
the parameters stacked along a leading batch axis.

Protocol (rendezvous while queued, not a background batcher thread):

  1. A dispatcher arriving at the device with a batchable fragment looks
     up its batch key — (digest, value-free chain signature [which pins
     the raw SQL shape + layout set + geometry], table version, zone-map
     survivor set). First arrival registers an OPEN batch and becomes
     the LEADER; it then queues for the device slot normally (keeping
     the KILL-while-queued guard polling).
  2. Later same-key dispatchers join as FOLLOWERS — up to
     `tidb_tpu_microbatch_max - 1` of them — parking on a per-member
     event instead of the scheduler queue. They poll their guard every
     POLL_S, so KILL / deadline land while parked: a WAITING member
     leaves the batch and raises its typed error alone.
  3. When the leader is granted the slot it CLOSES the batch, claims the
     compatible members (prepared-input pytrees must match structurally;
     mismatches are demoted to individual execution), pads the member
     count to the next power of two (padding repeats the leader's
     parameters; padded lanes are discarded at demux) and launches the
     batched program (device_emit.emit_batched — jit(vmap(partial)))
     once per surviving slab.
  4. Results de-multiplex by slicing each output leaf's leading axis:
     every member gets its own Chunk and its event is set. Error
     isolation is per member: a member killed mid-dispatch raises its
     own typed error and its lane's rows are simply never read; ANY
     fault in batched execution or demux (the `microbatch-demux`
     failpoint injects here) wakes every member for warned individual
     re-execution — a batch can degrade, it can never fail shared.

A solo leader (no followers by grant time) returns to the individual
path untouched — batch-of-1 through vmap is pure overhead and the
individual path is the byte-exactness oracle.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as np

from tidb_tpu.chunk import Chunk
from tidb_tpu.errors import QueryInterrupted, QueryTimeout
from tidb_tpu.executor import compile_cache, device_emit, scheduler
from tidb_tpu.executor.agg_slabs import slab_iter
from tidb_tpu.executor.host_decode import decode_col, positional_dict
from tidb_tpu.ops.jax_env import jnp
from tidb_tpu.util import failpoint, timeline
from tidb_tpu.util.observability import REGISTRY, normalize_sql
from tidb_tpu.util.phases import tree_nbytes

# follower guard-poll cadence while parked on the batch event
POLL_S = 0.02

_LOCK = threading.Lock()
_BATCHES: Dict[tuple, "_Batch"] = {}


class _Member:
    __slots__ = ("event", "guard", "conn_id", "prep_vals", "claimed",
                 "result", "fallback")

    def __init__(self, guard, conn_id: int, prep_vals):
        self.event = threading.Event()
        self.guard = guard
        self.conn_id = conn_id
        self.prep_vals = prep_vals
        self.claimed = False       # leader took this member at grant time
        self.result = None         # Chunk, set by the leader
        self.fallback = False      # woken for individual re-execution


class _Batch:
    __slots__ = ("key", "members", "closed", "leader_req")

    def __init__(self, key, leader_req: int = 0):
        self.key = key
        self.members: List[_Member] = []
        self.closed = False
        # the leader's request id: what a follower's wait names as its
        # `cause` on the timeline
        self.leader_req = leader_req


def queued_members() -> int:
    """Followers currently parked on open batches (test/ bench probe)."""
    with _LOCK:
        return sum(len(b.members) for b in _BATCHES.values()
                   if not b.closed)


def batch_key(guard, sig: str, ent, slab_ids) -> tuple:
    """(digest, value-free signature, table + delta version, survivor
    slabs). The signature already pins the chain shape, column types,
    layout set and slab geometry; `id(ent.td)` is the table-version
    token (writes rebuild the TableData) and `delta_version` is the
    store's monotonic commit version the entry serves — id() alone is
    an ABA hazard now that delta extension installs a NEW entry for a
    NEW TableData whose id may be recycled, and a write landing between
    rendezvous and launch must never serve stale rows to the whole
    batch. The zone-map survivor set must match because members share
    one launch per surviving slab."""
    digest = normalize_sql(getattr(guard, "sql", "") or "")
    return (digest, sig, id(ent.td), getattr(ent, "delta_version", 0),
            tuple(slab_ids))


def execute(exec_, prog, root, ent, dicts, prep_vals, slab_ids, sig,
            mb_max: int):
    """Try to serve this statement through a micro-batch. → Chunk, or
    None when the caller must run the individual path (no rendezvous,
    solo batch, demotion, or fault fallback)."""
    ctx = exec_.ctx
    guard = getattr(ctx, "guard", None)
    conn_id = getattr(guard, "conn_id", 0) if guard is not None else 0
    key = batch_key(guard, sig, ent, slab_ids)

    with _LOCK:
        b = _BATCHES.get(key)
        if b is not None and not b.closed and len(b.members) < mb_max - 1:
            m = _Member(guard, conn_id, prep_vals)
            b.members.append(m)
            joined = b
        else:
            joined = None
            mine = _Batch(key, ctx.phases.req)
            _BATCHES[key] = mine     # replaces a closed/full batch

    if joined is not None:
        return _follow(joined, m, guard)

    try:
        return _lead(exec_, mine, prog, root, ent, dicts, prep_vals,
                     slab_ids, sig)
    except BaseException:
        _abort(mine)
        raise


# ---------------------------------------------------------------------------
# follower side
# ---------------------------------------------------------------------------

def _follow(batch: _Batch, m: _Member, guard) -> Optional[object]:
    """Park on the member event; KILL/deadline isolation via guard
    polling. → the demuxed Chunk, or None for individual fallback."""
    t0 = time.monotonic()
    with timeline.span("microbatch.wait", "sched", pid=m.conn_id,
                       cause=batch.leader_req, wait="queue"):
        while not m.event.wait(POLL_S):
            if guard is None:
                continue
            try:
                guard.check("microbatch-wait")
            except BaseException:
                with _LOCK:
                    if not m.claimed and m in batch.members:
                        # still WAITING: leave the batch; only THIS
                        # member surfaces the typed error
                        batch.members.remove(m)
                # claimed members raise too — the leader's lane for them
                # computes rows nobody reads; isolation is the point
                raise
    waited = time.monotonic() - t0
    if guard is not None and waited > 0.0:
        # parked time is queue time: same ledger the scheduler charges
        guard.queue_wait_s += waited
        guard.queue_waits += 1
    if m.fallback or m.result is None:
        return None
    return m.result


# ---------------------------------------------------------------------------
# leader side
# ---------------------------------------------------------------------------

def _abort(batch: _Batch, fallback: bool = True) -> None:
    """Wake every member for individual re-execution and retire the
    batch key. Never raises."""
    with _LOCK:
        if _BATCHES.get(batch.key) is batch:
            del _BATCHES[batch.key]
        batch.closed = True
        members = list(batch.members)
    for m in members:
        m.fallback = fallback
        m.event.set()


def _structure_matches(jax, ref_pv, pv) -> bool:
    tu = jax.tree_util
    if tu.tree_structure(ref_pv) != tu.tree_structure(pv):
        return False
    for a, b in zip(tu.tree_leaves(ref_pv), tu.tree_leaves(pv)):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape or a.dtype != b.dtype:
            return False
    return True


class _BatchedProgram:
    """A base fragment program vmapped over a leading member axis: one
    launch serves `b_pad` statements whose prepared parameters are
    stacked along axis 0 (`_lead`). Shares the compile
    cache/LRU with scalar programs under sig `batched[B]|<base sig>`."""

    __slots__ = ("base", "b_pad", "partial", "partial_name")

    def __init__(self, base, b_pad: int, sig: str = ""):
        from tidb_tpu.ops.jax_env import program_name
        self.base = base
        self.b_pad = b_pad
        self.partial_name = program_name("batched", sig)
        self.partial = device_emit.emit_batched(base._partial,
                                                self.partial_name)


def get_batched_program(base, b_pad: int,
                        base_sig: str) -> _BatchedProgram:
    """`base`: the chain's `agg_slabs` fragment program."""
    sig = f"batched[{b_pad}]|{base_sig}"
    return compile_cache.get_or_build(sig, "batched",
                         lambda: _BatchedProgram(base, b_pad, sig))


def _lead(exec_, batch: _Batch, prog, root, ent, dicts, prep_vals,
          slab_ids, sig) -> Optional[object]:
    from tidb_tpu.ops.jax_env import jax

    ctx = exec_.ctx
    ph = ctx.phases
    guard = getattr(ctx, "guard", None)

    with scheduler.device_slot(ctx):
        # grant time: close the batch and claim compatible members
        with _LOCK:
            batch.closed = True
            if _BATCHES.get(batch.key) is batch:
                del _BATCHES[batch.key]
            members = list(batch.members)
        claimed: List[_Member] = []
        demoted: List[_Member] = []
        for m in members:
            if _structure_matches(jax, prep_vals, m.prep_vals):
                m.claimed = True
                claimed.append(m)
            else:
                demoted.append(m)
        for m in demoted:
            m.fallback = True
            m.event.set()
        if not claimed:
            # solo: the individual path is the byte-exactness oracle
            return None

        b_real = 1 + len(claimed)
        b_pad = 1 << (b_real - 1).bit_length()
        all_pvs = [prep_vals] + [m.prep_vals for m in claimed]
        all_pvs += [prep_vals] * (b_pad - b_real)   # padding lanes
        try:
            stacked = jax.tree_util.tree_map(
                lambda *xs: np.stack([np.asarray(x) for x in xs]),
                *all_pvs)
            bprog = get_batched_program(prog, b_pad, sig)
            outs = []
            for cols, n in slab_iter(ent, None, prog.used_cols,
                                            slab_ids):
                with ph.launch(bprog.partial_name, sig=f"batched:{sig}"):
                    outs.append(bprog.partial(cols, jnp.int32(n),
                                              stacked))
                ph.note_launch()
                ph.note_fused()
        except BaseException as e:
            _abort(batch)
            if _is_guard_error(e):
                raise
            _warn(guard, f"micro-batch launch degraded to individual "
                         f"execution: {e}")
            return None

    # fetch + demux OUTSIDE the slot (matching _execute_filter's shape)
    try:
        with ph.drain():
            jax.block_until_ready(outs)
        with ph.phase("fetch"):
            host_outs = jax.device_get(outs)
        ph.add_d2h(tree_nbytes(host_outs))
        failpoint.inject("microbatch-demux")
        with ph.phase("decode"):
            chunks = _demux(host_outs, b_real, root, dicts)
    except BaseException as e:
        _abort(batch)
        if _is_guard_error(e):
            raise
        _warn(guard, f"micro-batch demux degraded to individual "
                     f"execution: {e}")
        return None

    REGISTRY.inc("tidb_tpu_microbatch_batches_total")
    REGISTRY.inc("tidb_tpu_microbatch_members_total", by=b_real)
    for m, chunk in zip(claimed, chunks[1:]):
        m.result = chunk
        m.fallback = False
        m.event.set()
    return chunks[0]


def _demux(host_outs, b_real: int, root, dicts) -> List[object]:
    """Slice each slab output's leading member axis into per-member
    (live-compacted, dictionary-decoded) Chunks — the batched twin of
    _execute_filter's decode loop."""
    chunks: List[object] = []
    for k in range(b_real):
        pieces = []
        for out in host_outs:
            live = np.asarray(out["live"])[k]
            idx = np.nonzero(live)[0]
            piece = []
            for ci, ((v, m), ft) in enumerate(
                    zip(out["cols"], root.schema.field_types)):
                vals = np.asarray(v)[k][idx]
                mask = np.asarray(m)[k][idx]
                piece.append(decode_col(
                    ft, vals, mask, positional_dict(root, ci, dicts)))
            pieces.append(Chunk(piece))
        chunks.append(Chunk.concat(pieces) if len(pieces) > 1
                      else pieces[0])
    return chunks


def _is_guard_error(e: BaseException) -> bool:
    return isinstance(e, (QueryInterrupted, QueryTimeout)) \
        or not isinstance(e, Exception)


def _warn(guard, msg: str) -> None:
    REGISTRY.inc("tidb_tpu_microbatch_fallbacks_total")
    if guard is not None:
        guard.warnings.append(("Warning", 1105, msg))


__all__ = ["execute", "batch_key", "queued_members", "POLL_S"]
