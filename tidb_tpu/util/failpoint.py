"""Failpoints — deterministic fault injection (ref: pingcap/failpoint,
enabled across ~hundreds of reference sites via make failpoint-enable;
kv/fault_injection.go wraps storage the same way).

Usage at a site:    failpoint.inject("commit-error")
In a test:          with failpoint.enabled("commit-error", raise_=TxnError("boom")): ...

Actions: raise an exception, return a value (site decides how to use it),
or call a hook. Triggering modifiers (all composable):

  * after_hits=N — the first N hits pass through untouched, the action
    fires from hit N+1 on (the reference's `N*return` marker);
  * one_in=N    — deterministic 1-in-N: fire on every Nth eligible hit
    (counter-based, not random, so runs reproduce);
  * times=N     — fire at most N times, then the site passes through
    (the `N*off` marker — transient faults that heal).

Every inject() call is also counted per site while any failpoint is
enabled or a `counting()` scope is open — the chaos sweep uses those
per-site counters to know which faults a workload actually reached.
Zero overhead when nothing is enabled (one dict probe).

The module-level catalog below names every injection site in the tree so
tools (chaos_sweep) can enumerate them without importing the world.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Callable, Dict, Optional

_lock = threading.Lock()
_active: Dict[str, dict] = {}
_counters: Dict[str, int] = {}       # site → inject() calls observed
_counting = 0                        # >0: count even with nothing enabled

# ---------------------------------------------------------------------------
# Site catalog — name → where it trips (keep in sync with inject() sites)
# ---------------------------------------------------------------------------
_catalog: Dict[str, str] = {}
_mesh_only: set = set()     # sites only reachable on a multi-device mesh


def register(name: str, desc: str = "", mesh_only: bool = False) -> None:
    """Declare an injection site so sweep tools can enumerate it.
    mesh_only marks sites that only a distributed (multi-device) workload
    can reach — the chaos sweep's coverage gate exempts them when it runs
    without a mesh."""
    _catalog.setdefault(name, desc)
    if mesh_only:
        _mesh_only.add(name)


def catalog() -> Dict[str, str]:
    """Registered site name → description (a copy)."""
    return dict(_catalog)


def mesh_only_sites() -> set:
    """Sites a single-device workload cannot reach (a copy)."""
    return set(_mesh_only)


for _site, _desc in (
    ("device-fragment", "entry of the jitted device-fragment pipeline "
                        "(executor/fragment.py _run_device)"),
    ("device-recompile", "group-cap overflow recompile retry "
                         "(executor/fragment.py)"),
    ("device-transfer", "HBM column upload (executor/device_cache.py "
                        "open_table streamed first-touch)"),
    ("host-fetch", "device→host result fetch after a fragment runs "
                   "(executor/fragment.py next)"),
    ("scan-next", "per-chunk boundary of the CPU table scan "
                  "(executor/scan.py next)"),
    ("spill-write", "spill container write (util/memory.py add)"),
    ("spill-read", "spill container read-back (util/memory.py read)"),
    ("tracker-quota", "memory tracker consume/quota check "
                      "(util/memory.py Tracker.consume)"),
    ("store-commit", "storage commit entry (storage/__init__.py)"),
    ("commit-conflict", "transient commit conflict before apply "
                        "(storage/__init__.py — retryable errors hit the "
                        "backoff loop)"),
    ("index-backfill", "between DDL unique-backfill batches (ddl.py)"),
    ("backup-table", "between tables during BACKUP (tools)"),
    ("restore-table", "between tables during RESTORE (tools)"),
    ("backoff-sleep", "inside Backoffer.backoff — value 'skip' elides "
                      "the real sleep (util/backoff.py)"),
):
    register(_site, _desc)

# distributed-only sites: a single-device workload never traces an
# exchange or dispatches per-shard steps, so the sweep's coverage gate
# only demands them when it runs with a mesh (--mesh N)
register("exchange-overflow", "distributed exchange bucket resize/retrace "
         "(executor/dist_fragment.py _dist_exec)", mesh_only=True)
register("shard-step", "host-side per-shard dispatch of a distributed "
         "fragment step (executor/dist_fragment.py) — a raise here models "
         "ONE shard failing; the staged agg path retries only that rank, "
         "then re-dispatches it onto a surviving device (degraded mesh); "
         "the monolithic path retries the whole step once",
         mesh_only=True)
register("shard-checkpoint-write", "device→host checkpoint of one rank's "
         "partial-agg results in the staged distributed path "
         "(executor/dist_fragment.py StagedDistAgg)", mesh_only=True)
register("shard-redispatch", "re-dispatch of a persistently failing "
         "rank's local work onto a surviving device — a raise here models "
         "the recovery path ALSO failing, exhausting the ladder into a "
         "typed ShardFailure (executor/dist_fragment.py)", mesh_only=True)
register("degraded-mesh-replan", "entry of degraded-mesh mode: the "
         "fragment re-plans the failed rank's work on the N-1 surviving "
         "ranks (executor/dist_fragment.py)", mesh_only=True)
register("exchange-checkpoint-write", "device→host checkpoint of one "
         "rank's outgoing exchange buckets in the staged exchange path — "
         "committed before ANY rank's receive stage starts, so a raise "
         "here models losing one rank's partition output, which must "
         "re-run only that rank's stage-1 program "
         "(executor/dist_fragment.py StagedDistExchange)", mesh_only=True)
register("exchange-redispatch", "re-dispatch of a persistently failing "
         "rank's exchange stage onto a surviving device — a raise here "
         "models the degraded-mesh recovery ALSO failing, exhausting the "
         "ladder into a typed ShardFailure "
         "(executor/dist_fragment.py StagedDistExchange)", mesh_only=True)
register("exchange-degraded-replan", "entry of degraded-mesh mode for an "
         "exchange-carrying fragment: the failed rank's partition or "
         "probe stage re-plans onto a surviving device "
         "(executor/dist_fragment.py StagedDistExchange)", mesh_only=True)
register("fused-pipeline-overflow", "capacity boundary of the slab-loop "
         "aggregate driver, over a chain's slabs as over a join tree's — "
         "hit after every round's batched flag fetch, right before "
         "join/group overflows are classified into rerun sets; a VALUE "
         "reads as an overflow of a whole-statement program, which the "
         "per-slab driver then answers "
         "(executor/agg_slabs.py run_agg_slabs)")
register("compressed-decode-mismatch", "layout-descriptor validation of "
         "the compressed device-resident columns a statement is about to "
         "decode — a value here models a corrupted descriptor, which must "
         "surface as a typed LayoutError + CPU fallback, never silent "
         "wrong rows (executor/device_cache.py _validate_layouts)")
register("fused-finalize-overflow", "TopN / distinct-pair-cap validation "
         "of the fused whole-query finalize — hit at the per-slab "
         "distinct-pair count check (before clipped pair sets could be "
         "consumed) and after the finalize's flag fetch; overflow resizes "
         "through the resumable 'pairs' ladder rung, re-running only the "
         "slabs that clipped (executor/agg_slabs.py run_agg_slabs)")
register("delta-append", "atomic apply point of a staged write — hit "
         "inside Store.commit after validation, before the locked "
         "apply+version bump; a retryable raise here heals through the "
         "commit backoff loop, a non-retryable one surfaces typed with "
         "the old delta version intact, never a torn delta "
         "(storage/__init__.py Store.commit)")
register("compaction-commit", "atomic install point of a compacted "
         "device-cache generation — hit after the rebuilt base slabs are "
         "resident, before the cache-slot swap; a raise here abandons the "
         "rebuild (its buffers are deleted) and the old base+delta keep "
         "serving reads byte-exactly (executor/delta.py)")
register("delta-merge-stale", "entry of the incremental delta-extension "
         "path when a cached table went stale — a raise here models a "
         "diff/encode fault, which must surface as a typed LayoutError + "
         "warned CPU fallback, never silent wrong rows "
         "(executor/delta.py extend_entry)")
register("microbatch-demux", "result de-multiplex of a same-plan "
         "micro-batch — hit after the batched program's fetch, before "
         "per-member rows are sliced off the leading batch axis; a raise "
         "here models a demux fault, which must degrade to warned "
         "per-member individual re-execution, never a shared typed error "
         "(executor/microbatch.py)")
register("steal-migrate", "work-steal handoff of a queued batch-class "
         "statement — hit after the waiter is pulled off its home "
         "device's queue, before it runs on the stealing device; a fault "
         "here re-queues the waiter on its home device with the backoff "
         "charged, so the statement is never lost and never run twice "
         "(executor/scheduler.py admit_statement)")
register("device-lost-dispatch", "dispatch boundary of the device "
         "fragment path, right after scheduler admission — a raise here "
         "models a serving-pool device failing its launch; it is "
         "classified into a typed DeviceLost, the health monitor "
         "quarantines the device (queued waiters migrate to survivors), "
         "and the in-flight victim retries ONCE on a survivor with a "
         "retryable 1105 SHOW WARNINGS entry "
         "(executor/fragment.py _run_device)")
register("device-lost-upload", "HBM column upload onto a serving-pool "
         "device (device_put) — a raise here models a transfer failure "
         "on a pool member; classified into a typed DeviceLost at the "
         "upload boundary, same quarantine + one-retry contract as "
         "device-lost-dispatch (executor/device_cache.py _stream_slabs)")
register("device-readmit", "health probe of a quarantined device once "
         "its flap-guard delay passes — a raise here keeps the device "
         "quarantined (the backoff budget is charged); a clean pass "
         "readmits it to placement and it repopulates lazily "
         "(executor/scheduler.py DeviceHealthMonitor.probe)")


def enable(name: str, *, raise_: Optional[BaseException] = None,
           value=None, hook: Optional[Callable] = None,
           after_hits: int = 0, one_in: int = 1,
           times: Optional[int] = None) -> None:
    register(name)
    with _lock:
        _counters.pop(name, None)    # fresh scope: stale counts mislead
        _active[name] = {"raise": raise_, "value": value, "hook": hook,
                         "hits": 0, "after_hits": int(after_hits),
                         "one_in": max(int(one_in), 1), "times": times,
                         "fired": 0}


def disable(name: str) -> None:
    with _lock:
        _active.pop(name, None)


def disable_all() -> None:
    with _lock:
        _active.clear()


def hits(name: str) -> int:
    """inject() calls observed at `name` — while the site was enabled, or
    inside a counting() scope."""
    with _lock:
        ent = _active.get(name)
        if ent is not None:
            return ent["hits"]
        return _counters.get(name, 0)


def counters() -> Dict[str, int]:
    """Per-site observed inject() counts (a copy)."""
    with _lock:
        return dict(_counters)


def reset_counters() -> None:
    with _lock:
        _counters.clear()


def inject(name: str):
    """Trip the failpoint if enabled: runs the hook, raises, or returns
    the configured value (None when disabled or suppressed by a
    modifier)."""
    if not _active and not _counting:    # fast path: nothing anywhere
        return None
    with _lock:
        if _counting or name in _active:
            _counters[name] = _counters.get(name, 0) + 1
        ent = _active.get(name)
        if ent is None:
            return None
        ent["hits"] += 1
        h = ent["hits"]
        if h <= ent["after_hits"]:
            return None
        if (h - ent["after_hits"] - 1) % ent["one_in"] != 0:
            return None
        if ent["times"] is not None and ent["fired"] >= ent["times"]:
            return None
        ent["fired"] += 1
        exc = ent["raise"]
        hook = ent["hook"]
        value = ent["value"]
    if hook is not None:
        hook()
    if exc is not None:
        raise exc
    return value


@contextlib.contextmanager
def enabled(name: str, **kwargs):
    enable(name, **kwargs)
    try:
        yield
    finally:
        disable(name)


@contextlib.contextmanager
def counting():
    """Count inject() calls at EVERY site (not only enabled ones) for the
    duration — the chaos sweep's coverage meter."""
    global _counting
    with _lock:
        _counting += 1
    try:
        yield
    finally:
        with _lock:
            _counting -= 1
