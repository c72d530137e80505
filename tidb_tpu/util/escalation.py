"""Capacity escalation ladder for static-shape overflows.

Static-shape discipline means every distributed structure — exchange
buckets, factorize group caps, join out-caps — can overflow BY DESIGN:
the device reports what it actually needed (exchange `need`, factorize
`n_groups`, join totals) and the host re-executes with bigger shapes.
This module is the one place that policy lives:

  exact-need resize  — the flag carries the true requirement: recompile
                       ONCE at the next power of two (exchange needs,
                       join totals, observed group counts);
  bounded doubling   — the flag is only a bool / a lower bound: grow
                       geometrically under a hard cap;
  host/CPU fallback  — the cap limit is reached: the executor falls back
                       (FragmentFallback) or raises a typed CapacityError
                       — never truncated rows.

Every rung is charged against a util/backoff.py budget (a pathological
workload cannot recompile-storm: the budget exhausts into a typed
error) and guard-checkpointed BETWEEN attempts, so KILL / deadline /
OOM land before the next compile is queued, not after. Per-query
counters live in EscalationStats, published on the statement's
ExecutionGuard and surfaced through information_schema.processlist and
EXPLAIN ANALYZE runtime info.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

from tidb_tpu.errors import BackoffExhausted
from tidb_tpu.util import failpoint
from tidb_tpu.util.backoff import Backoffer


def pow2(n: int, lo: int = 1) -> int:
    """The capacity a need of `n` rounds up to: the next power of two, at
    least `lo` (a power of two). Every static shape the executor sizes —
    slabs, group and join capacities, exchange and delta buckets — is
    rounded HERE, so the ladder's rungs and the first guess agree."""
    c = max(int(n), lo, 1)
    return 1 << (c - 1).bit_length()


class EscalationStats:
    """Per-query escalation counters (the ladder's observability half)."""

    __slots__ = ("recompiles", "exact_resizes", "doublings", "mode_flips",
                 "shard_retries", "fallbacks", "slabs_rerun", "slabs_reused",
                 "shards_rerun", "shards_reused", "degraded_mesh", "by_kind",
                 "_lk")

    def __init__(self):
        # counters are written by the statement's own thread only, but
        # processlist / EXPLAIN ANALYZE read them from OTHER connections'
        # threads mid-flight — the lock keeps by_kind iteration safe
        self._lk = threading.Lock()
        self.recompiles = 0      # re-executions the ladder charged
        self.exact_resizes = 0   # rung 1: resize to a reported exact need
        self.doublings = 0       # rung 2: bounded geometric growth
        self.mode_flips = 0      # join unique→expand re-traces
        self.shard_retries = 0   # whole-step retries after a shard fault
        self.fallbacks = 0       # rung 3: cap limit hit, CPU/host fallback
        # resumable-escalation reuse counters: on a retry, how many slab
        # partials were re-executed vs merged back in from the checkpoint
        self.slabs_rerun = 0
        self.slabs_reused = 0
        # per-shard fault recovery: after a shard fault, how many ranks'
        # local work re-executed vs restored from host checkpoints, and
        # whether the fragment completed on a degraded (N-1) mesh
        self.shards_rerun = 0
        self.shards_reused = 0
        self.degraded_mesh = 0
        self.by_kind: Dict[str, int] = {}   # "exchange:exact" → count

    def note(self, kind: str, rung: str) -> None:
        k = f"{kind}:{rung}"
        with self._lk:
            self.by_kind[k] = self.by_kind.get(k, 0) + 1

    @property
    def total(self) -> int:
        return (self.recompiles + self.mode_flips + self.shard_retries +
                self.fallbacks)

    def summary(self) -> str:
        """Compact 'recompiles=2 exchange:exact=1 ...' line for the
        processlist / EXPLAIN ANALYZE."""
        if not self.total:
            return ""
        parts = []
        for name in ("recompiles", "exact_resizes", "doublings",
                     "mode_flips", "shard_retries", "fallbacks",
                     "slabs_rerun", "slabs_reused",
                     "shards_rerun", "shards_reused", "degraded_mesh"):
            v = getattr(self, name)
            if v:
                parts.append(f"{name}={v}")
        with self._lk:
            by_kind = sorted(self.by_kind.items())
        parts.extend(f"{k}={v}" for k, v in by_kind)
        return " ".join(parts)


class CapacityLadder:
    """One statement's escalation scope: budget + checkpoints + stats.

    Typical use (the executor's recompile loops):

        ladder = CapacityLadder(guard=ctx.guard, stats=ctx.escalation)
        while True:
            out = run(program)
            if need > cap:
                cap = ladder.resize("exchange", cap, need=need)
                ladder.attempt("exchange", err)   # budget + checkpoint
                continue
            break
    """

    def __init__(self, guard=None, stats: Optional[EscalationStats] = None,
                 name: str = "device-recompile", base_ms: float = 1.0,
                 max_ms: float = 50.0, budget_ms: float = 500.0):
        self.guard = guard
        self.stats = stats if stats is not None else (
            guard.escalation if guard is not None else EscalationStats())
        self.bo = Backoffer(name, base_ms=base_ms, max_ms=max_ms,
                            budget_ms=budget_ms, guard=guard)

    def attempt(self, kind: str, err: Optional[BaseException] = None):
        """Charge one re-execution against the budget. Fires the
        device-recompile failpoint, counts the attempt, checkpoints the
        guard (KILL/deadline/OOM observed BETWEEN attempts — inside the
        sliced backoff sleep), and raises BackoffExhausted (chained to
        `err`) once a recompile-storm spends the budget."""
        from tidb_tpu.util import timeline
        from tidb_tpu.util.observability import REGISTRY
        REGISTRY.inc("tidb_tpu_ladder_retries_total", {"rung": kind})
        with timeline.span("ladder.retry", "frag", rung=kind,
                           need=int(getattr(err, "need", 0) or 0)):
            failpoint.inject("device-recompile")
            self.stats.recompiles += 1
            self.bo.backoff(err)

    def resize(self, kind: str, current: int, need: Optional[int] = None,
               max_cap: Optional[int] = None, factor: int = 4,
               lo: int = 1) -> int:
        """One resize rung → the new capacity. `need` known → exact-need
        power of two (one recompile covers it); unknown → bounded
        doubling by `factor`. Growth past `current` is guaranteed; the
        result is clamped to `max_cap` when given (callers detect the
        exhausted ladder as current >= max_cap BEFORE calling)."""
        if need is not None:
            new = pow2(max(int(need), current + 1), lo=lo)
            self.stats.exact_resizes += 1
            self.stats.note(kind, "exact")
        else:
            new = pow2(current * factor, lo=lo)
            self.stats.doublings += 1
            self.stats.note(kind, "double")
        if max_cap is not None:
            new = min(new, int(max_cap))
        return new

    def partial_resume(self, kind: str, rerun: int, reused: int) -> None:
        """Record a resumable retry's reuse split: `rerun` slab partials
        re-executed after the recompile, `reused` checkpointed partials
        merged back in untouched. Only the re-run slabs cost device time,
        so the retry's backoff charge already reflects one recompile —
        these counters make the saved work observable."""
        self.stats.slabs_rerun += int(rerun)
        self.stats.slabs_reused += int(reused)
        if reused:
            self.stats.note(kind, "partial-reuse")

    def flip(self, kind: str = "join") -> None:
        """A mode flip re-trace (join unique→expand bet lost)."""
        self.stats.mode_flips += 1
        self.stats.note(kind, "flip")

    def shard_retry(self, err: Optional[BaseException] = None) -> None:
        """One whole-step retry after a shard fault, through the same
        budget/checkpoint path as a capacity recompile."""
        self.stats.shard_retries += 1
        self.stats.note("shard", "retry")
        failpoint.inject("device-recompile")
        self.bo.backoff(err)

    def shard_resume(self, rerun: int, reused: int) -> None:
        """Record a per-shard recovery's reuse split: `rerun` ranks'
        local work re-executed, `reused` ranks' partials restored from
        their host checkpoints untouched."""
        self.stats.shards_rerun += int(rerun)
        self.stats.shards_reused += int(reused)
        if reused:
            self.stats.note("shard", "partial-reuse")

    def redispatch(self, err: Optional[BaseException] = None) -> None:
        """One degraded-mesh re-dispatch: a persistently failing rank's
        work moves onto a surviving device. The recompile (the program
        is re-pinned to a different device) is charged to the shared
        backoff budget exactly like a capacity recompile."""
        self.stats.degraded_mesh += 1
        self.stats.note("shard", "redispatch")
        failpoint.inject("device-recompile")
        self.bo.backoff(err)

    def fallback(self, kind: str) -> None:
        """The cap limit rung: record that the ladder handed this
        overflow to the CPU/host fallback (or a typed CapacityError)."""
        self.stats.fallbacks += 1
        self.stats.note(kind, "fallback")

    def remaining_ms(self) -> float:
        return self.bo.remaining_ms()


__all__ = ["EscalationStats", "CapacityLadder", "BackoffExhausted"]
