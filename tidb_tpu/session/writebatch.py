"""Coalesced single-row ingest: N queued autocommit writes, ONE commit.

Steady single-row ingest concurrent with analytical reads is the HTAP
write pattern this engine's delta slabs (executor/delta.py) exist for —
and its cost is dominated by commit count: every committed write bumps
the table's delta version, and every version bump costs the readers one
delta extension (a diff + a delta-slab re-encode). N writers committing
individually produce N generations; coalesced, they produce ONE.

This module reuses the rendezvous shape of executor/microbatch.py (the
same-plan read micro-batcher) on the write side:

  1. An eligible autocommit write (single-row INSERT VALUES, or a
     single-table UPDATE/DELETE — statements whose staging validates
     BEFORE it mutates the transaction) registers under its batch key
     (store, table, statement digest). First arrival is the LEADER;
     later same-key arrivals park as FOLLOWERS on a per-member event,
     polling their guard every POLL_S so KILL / max_execution_time land
     while queued: a WAITING member leaves the batch and raises its
     typed error alone — its write is never applied.
  2. The leader acquires the per-(store, table) COMMIT GATE (the lock
     that serializes write batches per table — acquisition is the
     natural rendezvous window: while a prior batch commits, this
     batch's membership grows). Then it closes the batch, claims the
     members, and applies every member's staging closure into ONE
     shared transaction, in arrival order.
  3. Error isolation is per member and relies on the DML discipline the
     session already enforces (validate-then-stage: _enforce_unique and
     _validate_routing raise BEFORE txn.delete/_append_routed mutate):
     a member whose closure raises a typed TiDBTPUError gets exactly
     that error; the shared transaction is untouched by it and the
     other members commit normally.
  4. ONE txn.commit() — one `delta-append` failpoint crossing, one
     store version bump, one delta extension for every reader. A
     commit-time fault (conflict, schema lease, an armed delta-append
     failpoint) is delivered to every applied member: the transaction
     rolled back atomically, so "all applied members succeed" and "all
     applied members fail" are the only outcomes — never torn.
  5. A member claimed after its guard fired keeps the batch's verdict:
     its write either committed (reporting the kill would lie to the
     client) or failed with the batch's error. Only WAITING members
     honor the kill — that is the exactly-once boundary.

Any unexpected (non-typed) fault rolls the transaction back and wakes
every member for individual re-execution — nothing was committed, so
the retry preserves exactly-once; a batch can degrade, never fail
shared. `tidb_tpu_write_coalesce = off` disables the rendezvous
entirely (every write takes the individual path).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

from tidb_tpu.errors import TiDBTPUError
from tidb_tpu.sysvars import var_on
from tidb_tpu.util import timeline
from tidb_tpu.util.observability import REGISTRY, normalize_sql

# follower guard-poll cadence while parked (matches microbatch.POLL_S)
POLL_S = 0.02

_LOCK = threading.Lock()
_BATCHES: Dict[tuple, "_Batch"] = {}
# (store_id, table_id) → the table's commit gate
_GATES: Dict[tuple, threading.Lock] = {}

MAX_MEMBERS = 64


class _Member:
    __slots__ = ("event", "guard", "stage", "n_claim", "claimed",
                 "result", "error", "fallback")

    def __init__(self, guard, stage):
        self.event = threading.Event()
        self.guard = guard
        self.stage = stage         # callable(txn) -> affected row count
        self.claimed = False
        self.result: Optional[int] = None    # affected rows on success
        self.error: Optional[BaseException] = None
        self.fallback = False      # woken for individual re-execution


class _Batch:
    __slots__ = ("key", "members", "closed")

    def __init__(self, key):
        self.key = key
        self.members: List[_Member] = []
        self.closed = False


def queued_members() -> int:
    """Followers currently parked on open write batches (test probe)."""
    with _LOCK:
        return sum(len(b.members) for b in _BATCHES.values()
                   if not b.closed)


def commit_gate(store, table_id: int) -> threading.Lock:
    """The per-(store, table) batch commit gate (exposed so tests can
    hold it to force a rendezvous window deterministically)."""
    key = (id(store), table_id)
    with _LOCK:
        gate = _GATES.get(key)
        if gate is None:
            gate = _GATES[key] = threading.Lock()
        return gate


def enabled(sess) -> bool:
    return var_on(sess.vars, "tidb_tpu_write_coalesce")


def coalesce(sess, table_id: int, stage) -> Optional[int]:
    """Run `stage(txn)` (validate-then-stage closure returning affected
    rows) through a coalesced write batch. → affected row count, raises
    the member's own typed error, or returns None when the caller must
    run the individual write path (coalescing off / degraded)."""
    if not enabled(sess):
        return None
    guard = sess._guard
    store = sess.engine.store
    digest = normalize_sql(sess._current_sql or "")
    key = (id(store), table_id, digest)

    with _LOCK:
        b = _BATCHES.get(key)
        if b is not None and not b.closed \
                and len(b.members) < MAX_MEMBERS - 1:
            m = _Member(guard, stage)
            b.members.append(m)
            joined = b
        else:
            joined = None
            mine = _Batch(key)
            _BATCHES[key] = mine     # replaces a closed/full batch

    if joined is not None:
        return _follow(joined, m, guard)
    try:
        return _lead(sess, mine, store, table_id, stage, guard)
    except BaseException:
        _abort(mine)
        raise


def _follow(batch: _Batch, m: _Member, guard) -> Optional[int]:
    """Park until the leader delivers a verdict. KILL / deadline honored
    only while WAITING (unclaimed) — see the module docstring's
    exactly-once boundary."""
    t0 = time.monotonic()
    while not m.event.wait(POLL_S):
        if guard is None:
            continue
        try:
            guard.check("write-coalesce-wait")
        except BaseException:
            with _LOCK:
                if not m.claimed and m in batch.members:
                    batch.members.remove(m)
                    claimed = False
                else:
                    claimed = True
            if not claimed:
                raise      # WAITING victim: own typed error, never applied
            # claimed: the write may already be committing — the batch's
            # verdict is authoritative; keep waiting for it
            m.event.wait()
            break
    waited = time.monotonic() - t0
    if guard is not None and waited > 0.0:
        guard.queue_wait_s += waited
        guard.queue_waits += 1
    if m.error is not None:
        raise m.error
    if m.fallback or m.result is None:
        return None
    return m.result


def _abort(batch: _Batch, fallback: bool = True) -> None:
    """Wake every member for individual re-execution (nothing was
    committed) and retire the batch key. Never raises."""
    with _LOCK:
        if _BATCHES.get(batch.key) is batch:
            del _BATCHES[batch.key]
        batch.closed = True
        members = list(batch.members)
    for m in members:
        m.fallback = fallback
        m.event.set()


def _lead(sess, batch: _Batch, store, table_id: int, stage,
          guard) -> Optional[int]:
    gate = commit_gate(store, table_id)
    # gate acquisition IS the rendezvous window: poll so KILL/deadline
    # land on a queued leader too (its batch aborts → members retry
    # individually; nothing was staged yet)
    t0 = time.monotonic()
    while not gate.acquire(timeout=POLL_S):
        if guard is not None:
            guard.check("write-coalesce-wait")
    waited = time.monotonic() - t0
    if guard is not None and waited >= POLL_S:
        guard.queue_wait_s += waited
        guard.queue_waits += 1
    try:
        with _LOCK:
            batch.closed = True
            if _BATCHES.get(batch.key) is batch:
                del _BATCHES[batch.key]
            members = list(batch.members)
            for m in members:
                m.claimed = True

        txn = store.begin()
        txn.schema_version0 = sess.engine.catalog.user_version
        my_result: Optional[int] = None
        my_error: Optional[BaseException] = None
        applied: List[_Member] = []
        try:
            try:
                my_result = stage(txn)
            except TiDBTPUError as e:
                # validate-then-stage: the txn is untouched by a typed
                # failure, so the leader's own error never sinks members
                my_error = e
            for m in members:
                try:
                    m.result = m.stage(txn)
                    applied.append(m)
                except TiDBTPUError as e:
                    m.error = e
                except BaseException as e:
                    # a member's unexpected fault may have staged rows:
                    # the shared txn is suspect — degrade the whole batch
                    txn.rollback()
                    for mm in members:
                        mm.result, mm.error = None, None
                    _abort(batch)
                    raise e if my_error is None else my_error
            if my_result is None and not applied:
                # nothing staged successfully: no commit, no version
                # bump, no spurious delta extension for the readers
                txn.rollback()
                raise my_error if my_error is not None else \
                    TiDBTPUError("write batch applied no member")
            try:
                sess._commit_auto(txn)   # ONE commit == ONE delta-append
            except TiDBTPUError as e:
                # atomic failure: every applied member gets the commit
                # error (rolled back as a unit — never torn)
                for m in applied:
                    m.result, m.error = None, e
                if my_error is None and my_result is not None:
                    my_error, my_result = e, None
                txn.rollback()
        finally:
            for m in members:
                m.event.set()
    finally:
        gate.release()
    n_committed = (1 if my_result is not None else 0) + len(
        [m for m in applied if m.error is None])
    if n_committed:
        total = (my_result or 0) + sum(m.result or 0 for m in applied
                                       if m.error is None)
        sess.engine.note_modified(table_id, total)
        REGISTRY.inc("tidb_tpu_write_batches_total")
        REGISTRY.inc("tidb_tpu_write_members_total", by=n_committed)
        if timeline.ENABLED:
            timeline.instant("delta-append", "write",
                             pid=getattr(guard, "conn_id", 0) or 0,
                             args={"table": table_id,
                                   "members": n_committed,
                                   "rows": total})
    if my_error is not None:
        raise my_error
    return my_result


__all__ = ["coalesce", "enabled", "queued_members", "commit_gate",
           "POLL_S", "MAX_MEMBERS"]
