"""Priority-aware serving tier: per-device admission queues in front of
device dispatch.

The wire server runs one OS thread per connection (server/__init__.py),
but the engine owns a small number of accelerators (usually one). Left
alone, concurrent statements would interleave their XLA dispatches
arbitrarily: no fairness, no queue-time observability, and a KILL aimed
at a statement stuck behind a long device program would only land after
the device freed up.

Architecture (the multi-queue design):

  SchedulerPool ── one DeviceScheduler per visible device ── per-class
  priority queues inside each scheduler.

* `SchedulerPool` owns one `DeviceScheduler(device_index)` per device
  slot. Statements are routed by `place_statement()` — BY LOCALITY: the
  device already holding the tables the statement's digest touches
  (Registry.digest_tables × device_cache.locate_tables), falling back
  to least-queue-depth (ties to the lowest index, so serial workloads
  deterministically stay on device 0) for cold digests. The placement
  is stamped once on the guard (guard.device_index) and every later
  acquire of the statement reuses it. `tidb_tpu_device_queues` defaults
  to `auto`: the pool activates only when >1 device is visible, so a
  single-accelerator process keeps the PR 5 single-slot semantics
  byte-identically.

* Work stealing: when a scheduler's release leaves it IDLE (no holder,
  empty queue) it pulls the best-ranked steal-eligible waiter from the
  deepest sibling queue (`SchedulerPool.steal_into`). Only batch-class
  statements parked at their ADMISSION acquire (`admit_statement`, the
  turnstile a batch statement passes BEFORE its first table byte
  uploads) are eligible — a statement is never migrated after it
  started uploading or dispatching, and a statement whose partitioned
  working set lives elsewhere is pinned (guard.sched_steal_ok=False).
  The complementary bootstrap: a steal-eligible waiter queued past
  STEAL_PATIENCE_S migrates itself onto a FULLY idle sibling — a
  device that has never run anything has no release to trigger a pull,
  so the first spill must come from the stalled queue's side.
  The handoff passes the `steal-migrate` failpoint: an injected fault
  re-queues the waiter on its HOME device with a Backoffer charge —
  the thread itself migrates, so the statement is never lost and never
  runs twice.

* Each `DeviceScheduler` keeps ONE logical queue whose grant order is
  computed per wakeup from (priority level, arrival ticket):

    level 0  interactive — point reads, prepared COM_STMT_EXECUTE,
             metadata queries (classified by session/__init__.py from
             the statement AST + digest profile), and any waiter whose
             aging credit expired;
    level 1  cheap batch — scans/joins whose digest's historical device
             seconds fall under CHEAP_BATCH_S;
    level 2  heavy batch — everything else.

  Strict priority between levels, FIFO (arrival ticket) within a level.
  Anti-starvation: a batch waiter queued longer than AGING_S is
  promoted to level 0, so a flood of interactive statements bounds a
  scan's extra wait at AGING_S per slot acquisition, never unbounded.
  Statements with no class (priority scheduling off, or internal
  acquires) rank at level 0 by ticket — with classification disabled
  the grant order therefore degenerates to EXACTLY the PR 5 FIFO,
  including which admissions count as waits and when fairness yields
  fire.

Scope of the slot — dispatch, not residency:

  * A statement holds the slot while it ENQUEUES device work (the jitted
    program call and, on a cold path, its compile). JAX dispatch is
    asynchronous, so the slot is held for the host-side cost of queueing
    the program, not for the device execution itself — the accelerator's
    own in-order execution stream serializes the actual compute.
  * Host-side phases — parse/plan, slab encode, result decode, and the
    GIL-released blocking waits (block_until_ready / device_get) — run
    OUTSIDE the slot. Query B's encode therefore overlaps query A's XLA
    execution exactly as the phase machinery (util/phases.py) names it.

Degraded-pod serving (the device fault domain): a DeviceLost fault at a
dispatch or upload boundary reports to the pool's DeviceHealthMonitor,
which quarantines the device (flap-guarded by one shared
util/backoff.py budget charge per quarantine). A quarantined device
stops receiving placements and steal pulls, its steal-eligible queued
waiters migrate to healthy survivors through the same _Migrated handoff
work stealing uses (KILL/deadline still land on migrated waiters), its
HBM cache shard is evicted / re-homed (device_cache.evict_device), and
the in-flight victim retries ONCE on a survivor with a retryable 1105
SHOW WARNINGS row (device_fault). Once the flap-guard delay passes, a
health probe through the device-readmit failpoint gate readmits the
device to placement; it repopulates lazily. report_fault refuses to
quarantine the LAST healthy device — a pool of one keeps serving and
the typed error surfaces instead.

Fairness (orthogonal to class): a connection which has taken
FAIRNESS_CAP consecutive grants while another connection waits yields to
the best-ranked waiter from a different connection — a tight
repeated-query loop cannot starve a sibling session.

Lifecycle: a queued waiter polls its ExecutionGuard every POLL_S, so
KILL / deadline / OOM land as typed errors (1317 et al.) WHILE QUEUED,
before the statement ever reaches the device. Queue-wait seconds are
charged to the guard (queue_wait_s / queue_waits) and surfaced through
information_schema.processlist, EXPLAIN ANALYZE runtime info, and the
per-class `sched-queue:<class>` timeline lanes.

Counters: `stats()` / `reset_stats()` snapshot and clear under the same
condition lock every mutation takes, so the benchmark's
`sched_wait_ms_per_op` reader and tests never read a torn admissions/wait_s_total pair against concurrent dispatchers. Each
counter also keeps a per-class breakdown (`stats()["classes"]`).
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

from tidb_tpu.sysvars import var_str
from tidb_tpu.util import failpoint, timeline
from tidb_tpu.util.backoff import BackoffExhausted, Backoffer
from tidb_tpu.util.observability import REGISTRY

# consecutive grants one connection may take while another conn waits
DEFAULT_FAIRNESS_CAP = 4
# guard-poll cadence while queued (KILL latency bound when the holder
# does not release for a long time; release itself wakes waiters)
POLL_S = 0.02
# anti-starvation: a batch waiter queued this long ranks as interactive
AGING_S = 0.5
# work-steal bootstrap: a steal-eligible waiter queued this long scans
# the pool for a FULLY idle sibling and migrates itself there — the
# release-into-empty hook alone can't start the chain when a sibling has
# never run anything (it has nothing to release). Short waits stay local
# (locality wins); only a stalled queue spills onto idle devices.
STEAL_PATIENCE_S = 0.3
# historical avg device seconds under which a batch digest is "cheap"
CHEAP_BATCH_S = 0.05

# priority classes (guard.sched_class values); None = unclassified/FIFO
CLASSES = ("interactive", "batch")

# queue-entry field indices (kept as a list for in-place mutation).
# _STEAL: this waiter may be migrated to an idle sibling (batch-class
# admission acquires only). _MOVED: set by the stealer (under the
# victim's _cv) to the target device index — the waiter observes it in
# its poll loop and raises _Migrated to re-acquire over there. _DRAINED:
# set with _MOVED when the mover was a quarantine drain, not a steal —
# stamped by the mover, because by the time the waiter wakes the device
# may already be readmitted (the first flap-guard step is 25 ms) and
# re-reading its health would book the migration as a steal.
_TICKET, _CONN, _TID, _CLASS, _ENQ_T, _COST, _STEAL, _MOVED, _DRAINED = \
    range(9)


class _Migrated(BaseException):
    """Internal: a queued waiter was stolen — re-acquire on `target`.
    BaseException so no generic `except Exception` on the wait path can
    swallow the handoff."""

    def __init__(self, target: int, waited: float, drained: bool = False):
        super().__init__(f"migrated to device {target}")
        self.target = target
        self.waited = waited
        self.drained = drained      # moved by a quarantine drain


class DeviceScheduler:
    """Priority-class + fairness-capped admission queue for the dispatch
    slot of ONE device."""

    def __init__(self, device_index: int = 0,
                 fairness_cap: int = DEFAULT_FAIRNESS_CAP, pool=None):
        self.device_index = device_index
        # owning SchedulerPool (None for standalone schedulers in tests):
        # release-into-idle consults the pool's steal hook
        self._pool = pool
        # steal-eligible waiters currently queued — read RACILY by
        # sibling releases as a cheap pre-screen; every mutation happens
        # under _cv and steal_into re-verifies under the lock
        self._stealable = 0
        self._cv = threading.Condition()
        self._holder: Optional[int] = None     # thread ident
        self._depth = 0                        # reentrant holds
        self._next_ticket = 0
        self._queue: list = []   # [ticket, conn_id, tid, cls, enq_t, cost]
        self._last_conn: Optional[int] = None
        self._consecutive = 0
        self.fairness_cap = fairness_cap
        # cumulative counters (read through stats() — every mutation AND
        # every read happens under _cv)
        self.admissions = 0
        self.waits = 0               # admissions that actually queued
        self.wait_s_total = 0.0
        self.yields = 0              # fairness-cap rotations
        self.steals = 0              # waiters stolen INTO this device
        # per-class breakdowns, keyed by class name ("interactive" /
        # "batch"); unclassified admissions don't appear here
        self.class_admissions: Dict[str, int] = {}
        self.class_waits: Dict[str, int] = {}
        self.class_wait_s: Dict[str, float] = {}

    # -- grant policy --------------------------------------------------------
    def _rank(self, e, now: float):
        """(priority level, arrival ticket) — the grant order key.
        Unclassified entries rank level 0 by ticket, which makes the
        whole policy collapse to plain FIFO when classification is off."""
        cls = e[_CLASS]
        if cls is None or cls == "interactive":
            return (0, e[_TICKET])
        if now - e[_ENQ_T] >= AGING_S:         # aged batch → interactive
            return (0, e[_TICKET])
        if e[_COST] is not None and e[_COST] < CHEAP_BATCH_S:
            return (1, e[_TICKET])
        return (2, e[_TICKET])

    def _grantee(self):
        """Entry to admit next: the best-ranked waiter, unless its
        connection just exhausted its consecutive-grant cap while a
        different connection waits behind it."""
        if not self._queue:
            return None
        now = time.monotonic()
        head = min(self._queue, key=lambda e: self._rank(e, now))
        if self._consecutive >= self.fairness_cap \
                and head[_CONN] == self._last_conn:
            other = [e for e in self._queue if e[_CONN] != self._last_conn]
            if other:
                return min(other, key=lambda e: self._rank(e, now))
        return head

    # -- acquire / release ---------------------------------------------------
    def acquire(self, guard=None, conn_id: int = 0,
                steal_ok: bool = False) -> float:
        """Block until admitted; → seconds spent queued. Reentrant per
        thread. Raises the guard's typed error (QueryInterrupted /
        QueryTimeout / OOM action) if the statement is killed or expires
        while queued. The priority class and cost hint ride on the guard
        (guard.sched_class / guard.sched_cost, set by the session's
        admission classifier). `steal_ok` marks the waiter migratable:
        a sibling going idle may move it (the entry leaves this queue
        and the blocked thread raises _Migrated — admit_statement
        re-acquires on the target)."""
        tid = threading.get_ident()
        cls = getattr(guard, "sched_class", None) if guard is not None \
            else None
        cost = getattr(guard, "sched_cost", None) if guard is not None \
            else None
        with self._cv:
            if self._holder == tid:
                self._depth += 1
                return 0.0
            ent = [self._next_ticket, conn_id, tid, cls,
                   time.monotonic(), cost, bool(steal_ok), None, False]
            self._next_ticket += 1
            self._queue.append(ent)
            if ent[_STEAL]:
                self._stealable += 1
            t0 = time.monotonic()
            queued = False
            try:
                while True:
                    if ent[_MOVED] is not None:
                        # a stealer dequeued us (and decremented
                        # _stealable) under this lock — hand off
                        raise _Migrated(ent[_MOVED],
                                        time.monotonic() - t0,
                                        ent[_DRAINED])
                    if self._holder is None and self._grantee() is ent:
                        break
                    if ent[_STEAL] and self._pool is not None and \
                            time.monotonic() - ent[_ENQ_T] \
                            >= STEAL_PATIENCE_S:
                        # patience expired with the queue still stalled:
                        # spill onto a fully idle sibling (the bootstrap
                        # half of work stealing — release-into-empty
                        # keeps the chain going once a device is warm).
                        # Ticket-mod spread keeps a woken herd from all
                        # picking the same target.
                        idle = self._pool.idle_siblings(self)
                        if idle:
                            tgt = idle[ent[_TICKET] % len(idle)]
                            ent[_MOVED] = tgt
                            self._queue.remove(ent)
                            self._stealable -= 1
                            self._cv.notify_all()
                            raise _Migrated(tgt, time.monotonic() - t0)
                    queued = True
                    self._cv.wait(POLL_S)
                    if guard is not None:
                        guard.check("device-queue")
            except _Migrated:
                raise
            except BaseException:
                # a steal may have already removed the entry: the typed
                # error (KILL/deadline) wins — the statement unwinds to
                # the client either way, never runs anywhere
                if ent in self._queue:
                    self._queue.remove(ent)
                    if ent[_STEAL]:
                        self._stealable -= 1
                self._cv.notify_all()
                raise
            self._queue.remove(ent)
            if ent[_STEAL]:
                self._stealable -= 1
            self._holder = tid
            self._depth = 1
            waited = time.monotonic() - t0
            if conn_id == self._last_conn:
                self._consecutive += 1
            else:
                if self._consecutive >= self.fairness_cap \
                        and self._queue:
                    self.yields += 1
                self._last_conn = conn_id
                self._consecutive = 1
            self.admissions += 1
            if cls is not None:
                self.class_admissions[cls] = \
                    self.class_admissions.get(cls, 0) + 1
            if queued:
                self.waits += 1
                self.wait_s_total += waited
                if cls is not None:
                    self.class_waits[cls] = self.class_waits.get(cls, 0) + 1
                    self.class_wait_s[cls] = \
                        self.class_wait_s.get(cls, 0.0) + waited
            # uncontended admissions report zero wait: the few-µs lock
            # acquisition is not queue time and must not show up in
            # processlist / EXPLAIN ANALYZE as one
            return waited if queued else 0.0

    def release(self) -> None:
        idle = False
        with self._cv:
            if self._holder != threading.get_ident():
                return                      # defensive: never held
            if self._depth > 1:
                self._depth -= 1
                return
            self._depth = 0
            self._holder = None
            idle = not self._queue
            self._cv.notify_all()
        if idle and self._pool is not None:
            # released into an EMPTY queue: this device is about to sit
            # idle — pull a migratable waiter from the deepest sibling
            # (outside our own lock; steal_into locks one victim at a
            # time, so no two scheduler locks are ever held together)
            self._pool.steal_into(self)

    @contextmanager
    def slot(self, guard=None, conn_id: int = 0):
        """Admission-scoped context. Charges queue wait to the guard and
        records the wait on the class-labelled timeline lane."""
        waited = self.acquire(guard=guard, conn_id=conn_id)
        cls = getattr(guard, "sched_class", None) if guard is not None \
            else None
        # one sched-queue/sched-slot lane SET per device: device 0 keeps
        # the PR 5 lane names, siblings suffix @devN so the Chrome trace
        # shows each chip's queue and occupancy separately
        dev_sfx = f"@dev{self.device_index}" if self.device_index else ""
        if waited > 0.0:
            # the wait was timed inside acquire(): a duration measured
            # elsewhere, recorded as ending now
            lane = "sched-queue" if cls is None else f"sched-queue:{cls}"
            timeline.record(lane + dev_sfx, "sched", dur_us=waited * 1e6,
                            pid=conn_id, args={"wait": "queue"})
        with timeline.span("sched-slot" + dev_sfx, "sched", pid=conn_id):
            try:
                if waited and guard is not None:
                    guard.queue_wait_s += waited
                    guard.queue_waits += 1
                yield waited
            finally:
                self.release()

    def queue_depth(self) -> int:
        with self._cv:
            return len(self._queue) + (1 if self._holder is not None else 0)

    def stats(self) -> dict:
        """Consistent snapshot of every counter — taken under _cv, so a
        reader racing concurrent dispatchers never sees a torn
        admissions/wait_s_total pair."""
        with self._cv:
            return {"admissions": self.admissions, "waits": self.waits,
                    "wait_s_total": round(self.wait_s_total, 6),
                    "yields": self.yields, "steals": self.steals,
                    "classes": {
                        c: {"admissions": self.class_admissions.get(c, 0),
                            "waits": self.class_waits.get(c, 0),
                            "wait_s_total": round(
                                self.class_wait_s.get(c, 0.0), 6)}
                        for c in sorted(set(self.class_admissions)
                                        | set(self.class_waits))}}

    def reset_stats(self) -> None:
        with self._cv:
            self.admissions = 0
            self.waits = 0
            self.wait_s_total = 0.0
            self.yields = 0
            self.steals = 0
            self.class_admissions = {}
            self.class_waits = {}
            self.class_wait_s = {}


class DeviceHealthMonitor:
    """Device-level fault domain for the serving pool (degraded-pod
    serving). Per-device records exist ONLY after a first fault — a
    fault-free pod takes the empty-dict fast path on every placement and
    steal decision, so its behavior stays byte-identical to a pool with
    no health tracking at all.

    Lifecycle of one device:

      healthy ──report_fault──▶ QUARANTINED: placements stop, queued
      steal-eligible waiters migrate to survivors (drain_queue), the
      HBM cache shard is evicted / re-homed (device_cache.evict_device)
      ──flap-guard delay (one charge() of the shared util/backoff.py
      budget per quarantine)──▶ health probe (the device-readmit
      failpoint gate + a tiny transfer) ──pass──▶ healthy again,
      repopulating lazily ──fail──▶ next exponential delay; a spent
      budget quarantines the device permanently (it flapped too often).

    report_fault REFUSES to quarantine the last healthy device: a pool
    of one keeps serving and the typed DeviceLost surfaces instead."""

    def __init__(self, pool):
        self._pool = pool
        self._lock = threading.Lock()
        self._rec: Dict[int, dict] = {}

    def active(self) -> bool:
        """Any device ever faulted? False = the fault-free fast path."""
        return bool(self._rec)

    def healthy(self, idx: int) -> bool:
        rec = self._rec.get(idx)
        return rec is None or not rec["quarantined"]

    def healthy_indexes(self) -> List[int]:
        with self._pool._lock:
            n = len(self._pool.schedulers)
        return [i for i in range(n) if self.healthy(i)]

    def quarantined_indexes(self) -> List[int]:
        with self._lock:
            return sorted(i for i, r in self._rec.items()
                          if r["quarantined"])

    def report_fault(self, idx: int, err=None) -> bool:
        """Quarantine `idx` after a device-level fault. → True when the
        device was quarantined (survivors exist); False when it is the
        last healthy device or outside the pool."""
        idx = int(idx)
        with self._pool._lock:
            n = len(self._pool.schedulers)
        if idx < 0 or idx >= n:
            return False
        with self._lock:
            survivors = [i for i in range(n)
                         if i != idx and self.healthy(i)]
            if not survivors:
                return False
            rec = self._rec.get(idx)
            if rec is None:
                rec = self._rec[idx] = {
                    "quarantined": False, "faults": 0, "readmissions": 0,
                    "bo": Backoffer("device-readmit", base_ms=25.0,
                                    max_ms=2000.0, budget_ms=10000.0),
                    "not_before": None, "probing": False}
            rec["faults"] += 1
            already = rec["quarantined"]
            rec["quarantined"] = True
            # flap guard: every quarantine charges one exponential step
            # of the shared backoff budget; a spent budget means the
            # device flapped too often — no more probes, permanent out
            try:
                delay_ms = rec["bo"].charge(err)
                rec["not_before"] = time.monotonic() + delay_ms / 1000.0
            except BackoffExhausted:
                rec["not_before"] = None
        if not already:
            REGISTRY.inc("tidb_tpu_device_quarantines_total",
                         {"device": str(idx)})
            REGISTRY.set_gauge("tidb_tpu_device_healthy", 0.0,
                               {"device": str(idx)})
            timeline.instant(f"device-quarantine dev{idx}", "sched")
        # queued waiters migrate to survivors; the dead shard's HBM is
        # evicted and pod-partitioned slab ranges re-own onto survivors
        # (best effort — the pool must keep serving even if cleanup
        # itself trips on the dead device)
        self._pool.drain_queue(idx)
        try:
            from tidb_tpu.executor import device_cache
            device_cache.evict_device(idx, survivors)
        except Exception:  # noqa: BLE001 — eviction is best-effort
            pass
        return True

    def maybe_readmit(self) -> None:
        """Opportunistic readmission sweep, called from placement while
        quarantined devices exist: every device past its flap-guard
        delay gets ONE health probe; a clean pass rejoins placement (and
        repopulates its cache shard lazily on first touch)."""
        now = time.monotonic()
        due = []
        with self._lock:
            for idx, rec in self._rec.items():
                if rec["quarantined"] and not rec["probing"] \
                        and rec["not_before"] is not None \
                        and now >= rec["not_before"]:
                    rec["probing"] = True
                    due.append(idx)
        for idx in due:
            self._probe(idx)

    def _probe(self, idx: int) -> None:
        """One health probe of a quarantined device: the device-readmit
        failpoint gate, then a tiny best-effort transfer onto the real
        device handle. Pass → readmitted; fail → next flap-guard step."""
        ok, probe_err = True, None
        try:
            failpoint.inject("device-readmit")
            from tidb_tpu.executor import device_cache
            h = device_cache.device_handle(idx)
            if h is not None:
                from tidb_tpu.ops.jax_env import jax
                import numpy as np
                jax.device_put(np.zeros((1,), np.int32), h)
        except Exception as err:  # noqa: BLE001 — probe failed
            ok, probe_err = False, err
        with self._lock:
            rec = self._rec.get(idx)
            if rec is None:
                return
            rec["probing"] = False
            if ok:
                rec["quarantined"] = False
                rec["readmissions"] += 1
                rec["not_before"] = None
            else:
                try:
                    delay_ms = rec["bo"].charge(probe_err)
                    rec["not_before"] = \
                        time.monotonic() + delay_ms / 1000.0
                except BackoffExhausted:
                    rec["not_before"] = None
        if ok:
            REGISTRY.set_gauge("tidb_tpu_device_healthy", 1.0,
                               {"device": str(idx)})
            timeline.instant(f"device-readmit dev{idx}", "sched")

    def snapshot(self) -> Dict[int, dict]:
        """Per-device health for stats(): faults / readmissions /
        quarantined, without the live Backoffer."""
        with self._lock:
            return {i: {"quarantined": r["quarantined"],
                        "faults": r["faults"],
                        "readmissions": r["readmissions"]}
                    for i, r in self._rec.items()}


class SchedulerPool:
    """One DeviceScheduler per visible device slot, with locality-aware
    placement (place_statement), the work-steal hook (steal_into) and a
    device fault domain (DeviceHealthMonitor) — the pod-scale serving
    half of the tier."""

    def __init__(self, n: int = 1,
                 fairness_cap: int = DEFAULT_FAIRNESS_CAP):
        self._lock = timeline.named_lock("device_pool")
        self.schedulers: List[DeviceScheduler] = [
            DeviceScheduler(i, fairness_cap, pool=self)
            for i in range(max(1, n))]
        self.health = DeviceHealthMonitor(self)

    def ensure(self, n: int) -> None:
        """Grow to `n` slots (never shrinks: a statement may still hold
        a ticket on an existing queue)."""
        with self._lock:
            while len(self.schedulers) < n:
                self.schedulers.append(
                    DeviceScheduler(len(self.schedulers), pool=self))

    def size(self) -> int:
        with self._lock:
            return len(self.schedulers)

    def placement(self, conn_id: int = 0) -> DeviceScheduler:
        """Legacy guard-less hook: statement → device queue by
        connection id (stable across a statement's acquires)."""
        with self._lock:
            return self.schedulers[conn_id % len(self.schedulers)]

    def place_statement(self, guard, conn_id: int = 0,
                        store_id: Optional[int] = None) -> int:
        """→ device index for this statement, stamped once on the guard.

        Priority: (1) the guard's existing pin (placement is decided
        exactly once per statement, so every slab acquire lands on the
        same queue); (2) the device already holding the tables the
        statement's digest touches (guard.sched_tables, stamped by the
        session's admission classifier from the digest profile, located
        against the per-device HBM cache OF THE STATEMENT'S OWN STORE
        (`store_id` — table ids restart per engine, so another engine's
        resident table with the same id must neither attract this
        statement nor, when it is pod-partitioned, pin it against
        stealing); (3) least queue depth, ties
        to the LOWEST index — cold serial workloads deterministically
        stay on device 0, preserving the PR 5/15 shapes. A digest whose
        working set is pod-PARTITIONED (spans every device) pins
        guard.sched_steal_ok=False: migrating it buys nothing and
        strands nothing — it must simply never bounce."""
        with self._lock:
            n = len(self.schedulers)
        # degraded pod: probe overdue quarantined devices for
        # readmission, then keep new placements off the ones still out.
        # active() is an empty-dict check — a fault-free pod pays one
        # attribute load here and places byte-identically to PR 18.
        avoid: set = set()
        if self.health.active():
            self.health.maybe_readmit()
            avoid = {i for i in range(n) if not self.health.healthy(i)}
            if len(avoid) >= n:
                avoid = set()      # nothing healthy: serve anyway
        if guard is None:
            return conn_id % n
        idx = getattr(guard, "device_index", None)
        if idx is not None:
            return min(int(idx), n - 1)
        if n == 1:
            idx = 0
        else:
            idx = None
            tables = getattr(guard, "sched_tables", None)
            if tables:
                try:
                    from tidb_tpu.executor import device_cache
                    located = device_cache.locate_tables(tables, store_id)
                except Exception:  # noqa: BLE001 — placement is advisory
                    located = {}
                votes: Dict[int, int] = {}
                for devs in located.values():
                    if -1 in devs:
                        # pod-partitioned working set: resident on every
                        # device — no vote, but pin against stealing
                        guard.sched_steal_ok = False
                        continue
                    for d in devs:
                        if d in avoid:
                            continue
                        votes[d] = votes.get(d, 0) + 1
                if votes:
                    best = max(votes.values())
                    idx = min(d for d, v in votes.items() if v == best)
                    idx = min(idx, n - 1)
            if idx is None:
                cand = [i for i in range(n) if i not in avoid] \
                    or list(range(n))
                depths = [self.schedulers[i].queue_depth() for i in cand]
                idx = cand[depths.index(min(depths))]
        guard.device_index = idx
        ph = getattr(guard, "phases", None)
        if ph is not None:
            ph.device_index = idx
        return idx

    def idle_siblings(self, sched) -> List[int]:
        """Device indexes of FULLY idle members (no holder, empty
        queue), lowest first. Racy attribute reads — advisory, exactly
        like steal_into's _stealable pre-screen: a wrong answer costs a
        queued hop, never correctness."""
        with self._lock:
            members = list(self.schedulers)
        return [s.device_index for s in members
                if s is not sched and s._holder is None and not s._queue
                and self.health.healthy(s.device_index)]

    @staticmethod
    def _claim_waiter(sib: DeviceScheduler, e, target_idx: int,
                      drained: bool = False) -> bool:
        """Claim ONE queued waiter for migration — caller holds sib._cv.
        Re-verifies the entry is still queued and unclaimed before
        stamping _MOVED: the exactly-once guard when a release-into-empty
        steal races a quarantine drain of the same home queue. Both
        paths claim through here under the same lock, so the second
        claimant always observes the first's stamp and backs off — a
        waiter is migrated once, never lost, never doubled."""
        if e[_MOVED] is not None or e not in sib._queue:
            return False
        e[_MOVED] = int(target_idx)
        e[_DRAINED] = drained
        sib._queue.remove(e)
        sib._stealable -= 1
        return True

    def steal_into(self, target: DeviceScheduler) -> bool:
        """Pull the best-ranked steal-eligible waiter from the deepest
        sibling queue into the (idle) `target`. The victim entry is
        dequeued under its own scheduler's lock with _MOVED set; the
        blocked waiter thread observes the move and re-acquires on the
        target itself — the statement migrates, its thread never
        changes. → True when a waiter was moved. A quarantined target
        refuses to pull (it must stop receiving work, not attract it)."""
        if not self.health.healthy(target.device_index):
            return False
        with self._lock:
            sibs = [s for s in self.schedulers if s is not target]
        # racy pre-screen (plain int reads): the common all-idle release
        # costs N-1 attribute loads and zero lock traffic
        sibs = [s for s in sibs if s._stealable > 0]
        if not sibs:
            return False
        sibs.sort(key=lambda s: -len(s._queue))
        now = time.monotonic()
        for sib in sibs:
            with sib._cv:
                elig = [e for e in sib._queue
                        if e[_STEAL] and e[_MOVED] is None]
                if not elig:
                    continue
                e = min(elig, key=lambda e: sib._rank(e, now))
                if not self._claim_waiter(sib, e, target.device_index):
                    continue
                sib._cv.notify_all()
            return True
        return False

    def drain_queue(self, idx: int) -> int:
        """Migrate every steal-eligible waiter off a quarantined
        device's queue onto healthy survivors (round-robin across them).
        Claims go through _claim_waiter — the same under-lock discipline
        steal_into uses — so a concurrent release-into-empty steal of
        this same queue migrates each waiter exactly once. Waiters that
        cannot migrate (interactive acquires, pod-pinned statements)
        stay queued: the quarantined scheduler still grants its queue —
        quarantine stops NEW placements, not drainage — and KILL or a
        deadline still lands through the acquire poll loop either way.
        → number of waiters migrated."""
        with self._lock:
            if idx < 0 or idx >= len(self.schedulers):
                return 0
            sched = self.schedulers[idx]
        targets = [i for i in self.health.healthy_indexes() if i != idx]
        if not targets:
            return 0
        moved = 0
        with sched._cv:
            for e in [e for e in sched._queue
                      if e[_STEAL] and e[_MOVED] is None]:
                if self._claim_waiter(sched, e,
                                      targets[moved % len(targets)],
                                      drained=True):
                    moved += 1
            if moved:
                sched._cv.notify_all()
        return moved

    def stats(self) -> dict:
        """Aggregate counters across EVERY pool member (top-level keys
        match DeviceScheduler.stats(), so existing readers keep working
        when the pool is active) plus the per-device breakdown under
        ["devices"]."""
        with self._lock:
            members = list(self.schedulers)
        per = {f"device{s.device_index}": s.stats() for s in members}
        health = self.health.snapshot()
        for s in members:
            d = per[f"device{s.device_index}"]
            d["healthy"] = self.health.healthy(s.device_index)
            h = health.get(s.device_index)
            if h is not None:
                d["faults"] = h["faults"]
                d["readmissions"] = h["readmissions"]
        agg: dict = {"admissions": 0, "waits": 0, "wait_s_total": 0.0,
                     "yields": 0, "steals": 0, "classes": {}}
        for s in per.values():
            for k in ("admissions", "waits", "yields", "steals"):
                agg[k] += s.get(k, 0)
            agg["wait_s_total"] += s.get("wait_s_total", 0.0)
            for c, cs in s.get("classes", {}).items():
                t = agg["classes"].setdefault(
                    c, {"admissions": 0, "waits": 0, "wait_s_total": 0.0})
                t["admissions"] += cs.get("admissions", 0)
                t["waits"] += cs.get("waits", 0)
                t["wait_s_total"] = round(
                    t["wait_s_total"] + cs.get("wait_s_total", 0.0), 6)
        agg["wait_s_total"] = round(agg["wait_s_total"], 6)
        agg["devices"] = per
        return agg

    def reset_stats(self) -> None:
        with self._lock:
            members = list(self.schedulers)
        for s in members:
            s.reset_stats()


POOL = SchedulerPool(1)
# the single-device default queue — the module-level handle tests
# address directly (POOL.schedulers[0] is always this object)
SCHEDULER = POOL.schedulers[0]


@contextmanager
def _null_slot():
    yield 0.0


def _visible_devices() -> int:
    try:
        from tidb_tpu.ops.jax_env import jax
        return int(jax.local_device_count())
    except Exception:  # noqa: BLE001 — no backend yet
        return 1


def _queues_on(ctx) -> bool:
    """tidb_tpu_device_queues resolution: on/off are explicit; the
    default `auto` activates the pool exactly when >1 device is visible
    (a single-device host keeps PR 5/15 semantics byte-identically)."""
    queues = var_str(ctx.vars, "tidb_tpu_device_queues").lower()
    if queues in ("on", "1", "true"):
        return True
    if queues in ("off", "0", "false"):
        return False
    return _visible_devices() > 1


def pool_devices(ctx) -> int:
    """Serving peers the statement can be placed across: the visible
    device count when the pool is active, else 1. device_cache consults
    this for its replicate-vs-partition placement decisions."""
    if getattr(ctx, "unscheduled", False) or not _queues_on(ctx):
        return 1
    return _visible_devices()


def device_slot(ctx):
    """The executor-facing entry: the routed scheduler's slot bound to
    the statement's guard/conn, or a no-op for a context made
    `unscheduled` (`ExecContext.unscheduled`: the compactor's warm runs).
    With the pool active (device_queues on, or auto with >1 device) the
    statement's guard carries its placement — stamped here on first
    acquire if admit_statement didn't already — and every acquire of
    the statement lands on that one queue."""
    if getattr(ctx, "unscheduled", False):
        return _null_slot()
    guard = getattr(ctx, "guard", None)
    conn_id = getattr(guard, "conn_id", 0) if guard is not None else 0
    if _queues_on(ctx):
        POOL.ensure(_visible_devices())
        idx = POOL.place_statement(guard, conn_id, _ctx_store_id(ctx))
        with POOL._lock:
            sched = POOL.schedulers[idx]
    else:
        sched = SCHEDULER
    return sched.slot(guard=guard, conn_id=conn_id)


def _ctx_store_id(ctx) -> Optional[int]:
    """The device cache's key for the store this statement reads (None
    when it has no snapshot: placement then sees every store)."""
    store = getattr(getattr(ctx, "snapshot", None), "store", None)
    return None if store is None else id(store)


def admit_statement(ctx) -> None:
    """Admission → placement handoff, called by the device executor
    BEFORE the statement's first open_table (so before any byte picks a
    device). Places the statement (stamping guard.device_index), and
    parks BATCH-class statements at their placed queue's turnstile —
    the one window in a statement's life where an idle sibling may
    steal it (its working set hasn't landed anywhere yet). Interactive
    and unclassified statements only get the placement stamp: their
    point reads go straight to the dispatch slot, exactly the PR 15
    flow (and the microbatch rendezvous depends on that)."""
    if getattr(ctx, "unscheduled", False) or not _queues_on(ctx):
        return
    guard = getattr(ctx, "guard", None)
    if guard is None:
        return
    POOL.ensure(_visible_devices())
    conn_id = getattr(guard, "conn_id", 0)
    home = POOL.place_statement(guard, conn_id, _ctx_store_id(ctx))
    if getattr(guard, "sched_class", None) != "batch" \
            or getattr(guard, "sched_admitted", False):
        return
    guard.sched_admitted = True
    steal_ok = bool(getattr(guard, "sched_steal_ok", True)) \
        and POOL.size() > 1
    idx = home
    waited_total = 0.0
    while True:
        with POOL._lock:
            sched = POOL.schedulers[min(idx, len(POOL.schedulers) - 1)]
        try:
            waited_total += sched.acquire(guard=guard, conn_id=conn_id,
                                          steal_ok=steal_ok)
        except _Migrated as m:
            waited_total += m.waited
            try:
                failpoint.inject("steal-migrate")
            except Exception as err:
                # injected fault at the handoff: re-queue on the HOME
                # device with the backoff charged to the guard. The
                # waiter thread itself performs the migration, so the
                # statement is never lost (this thread still owns it)
                # and never runs twice (no other thread ever could).
                Backoffer("steal-migrate", base_ms=1.0, max_ms=20.0,
                          budget_ms=1000.0,
                          guard=guard).backoff(err)
                idx, steal_ok = home, False
                continue
            idx, steal_ok = int(m.target), False
            if m.drained:
                # quarantine drain, not a steal: the waiter left a
                # quarantined home queue for a healthy survivor
                guard.sched_migrated = \
                    getattr(guard, "sched_migrated", 0) + 1
                REGISTRY.inc("tidb_tpu_statements_migrated_total",
                             {"device": str(idx)})
                continue
            guard.sched_steals = getattr(guard, "sched_steals", 0) + 1
            with POOL._lock:
                tgt = POOL.schedulers[min(idx, len(POOL.schedulers) - 1)]
            with tgt._cv:
                tgt.steals += 1
            REGISTRY.inc("tidb_tpu_work_steals_total",
                         {"device": str(idx)})
            continue
        break
    sched.release()
    # re-pin to wherever admission finally granted: uploads, dispatch
    # acquires and compile-cache keys all follow this index from here on
    guard.device_index = idx
    ph = getattr(guard, "phases", None)
    if ph is not None:
        ph.device_index = idx
    if waited_total > 0.0:
        guard.queue_wait_s += waited_total
        guard.queue_waits += 1
        timeline.record("sched-queue:batch"
                        + (f"@dev{idx}" if idx else ""), "sched",
                        dur_us=waited_total * 1e6, pid=conn_id,
                        args={"wait": "queue"})


def device_fault(ctx, err) -> Optional[int]:
    """Degraded-pod handoff for an in-flight DeviceLost: report the
    fault to the pool's health monitor (quarantine, queue drain, cache
    re-homing), pick the least-loaded healthy survivor, and re-pin the
    statement onto it for its ONE retry — recording a retryable 1105
    SHOW WARNINGS row, mirroring degraded-mesh semantics. → the
    survivor's index, or None when the pool cannot degrade (scheduler
    off, single slot, or no healthy survivor) — the caller lets the
    typed error surface instead."""
    if getattr(ctx, "unscheduled", False) or not _queues_on(ctx):
        return None
    guard = getattr(ctx, "guard", None)
    dev = getattr(err, "device", None)
    if dev is None and guard is not None:
        dev = getattr(guard, "device_index", None)
    dev = int(dev) if dev is not None else 0
    POOL.ensure(_visible_devices())
    if not POOL.health.report_fault(dev, err):
        return None
    survivors = [i for i in POOL.health.healthy_indexes() if i != dev]
    if not survivors:
        return None
    with POOL._lock:
        scheds = [POOL.schedulers[i] for i in survivors]
    depths = [s.queue_depth() for s in scheds]
    idx = survivors[depths.index(min(depths))]
    if guard is not None:
        guard.device_index = idx
        ph = getattr(guard, "phases", None)
        if ph is not None:
            ph.device_index = idx
        guard.sched_migrated = getattr(guard, "sched_migrated", 0) + 1
        guard.warnings.append(
            ("Warning", 1105,
             f"device {dev} lost ({err}); statement retried on device "
             f"{idx}"))
    REGISTRY.inc("tidb_tpu_statements_migrated_total",
                 {"device": str(idx)})
    return idx


__all__ = ["DeviceScheduler", "SchedulerPool", "DeviceHealthMonitor",
           "SCHEDULER", "POOL",
           "device_slot", "admit_statement", "pool_devices",
           "device_fault",
           "DEFAULT_FAIRNESS_CAP", "POLL_S", "AGING_S",
           "CHEAP_BATCH_S", "CLASSES"]
