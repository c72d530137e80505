"""The process-wide cache of compiled device programs.

Compiled programs are cached process-wide keyed by plan structure + dtypes
+ slab/group capacities, so repeated queries skip retracing (the plan-cache
analog for the device engine). Single-flight: one trace a signature however
many statements ask at once. A leaf of the executor: every module that
builds a program imports it, it imports none of them.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Dict

from tidb_tpu.ops.jax_env import jax
from tidb_tpu.util import phases, timeline

# LRU of compiled programs: bounded because signatures can embed
# data-dependent key_bounds (moving min/max under writes would otherwise
# accumulate executables forever)
_COMPILE_CACHE: "OrderedDict[str, object]" = OrderedDict()
MAX_COMPILED_PROGRAMS = 64

# guards _COMPILE_CACHE / PROGRAM_TRACES / _BUILD_LOCKS (and the specialization
# cache in front of them, agg_slabs.py) — connection
# threads share one program cache
LOCK = timeline.named_lock("compile_cache", reentrant=True)
# per-signature build locks: two threads cold-compiling the SAME
# signature serialize (one trace, the loser adopts it); different
# signatures still compile concurrently
_BUILD_LOCKS: Dict[str, threading.Lock] = {}

# Incremented inside the traced _partial/_merge bodies, so it moves once
# per TRACE, not once per call — the zero-retrace assertion the perf_smoke
# tier watches (a repeated identical query must leave it unchanged).
PROGRAM_TRACES = 0


def count_trace() -> None:
    global PROGRAM_TRACES
    with LOCK:
        PROGRAM_TRACES += 1


def sig_dev(sig: str) -> str:
    """Scope a compile-cache signature to the statement's pool device:
    XLA executables bind to the device they were lowered for, so each
    pool member keeps its own compiled copy. Device 0 (and every
    placement-free context) keeps the bare signature — single-device
    hosts stay byte-identical to the pre-pod cache."""
    cur = phases.current()
    d = getattr(cur, "device_index", 0) if cur is not None else 0
    return f"dev{d}|{sig}" if d else sig


def build_lock(sig: str) -> threading.Lock:
    sig = sig_dev(sig)
    with LOCK:
        lk = _BUILD_LOCKS.get(sig)
        if lk is None:
            lk = _BUILD_LOCKS[sig] = threading.Lock()
            while len(_BUILD_LOCKS) > 4 * MAX_COMPILED_PROGRAMS:
                _BUILD_LOCKS.pop(next(iter(_BUILD_LOCKS)))
        return lk


# signature → the request (timeline `req`) building that program right now:
# a request that waits for the build records it as the wait's `cause`
_BUILDING: Dict[str, int] = {}


def get_or_build(sig: str, kind: str, build):
    """The single-flight compile cache: the cached program of `sig`, or
    `build()`'s, built once however many statements ask at once (one trace
    per signature; the losers wait and adopt it). A cold build is charged
    to the running statement and to the `compile:<kind>` timeline lane."""
    prog = cache_get(sig)
    if prog is not None:
        return prog
    lock = build_lock(sig)
    if not lock.acquire(blocking=False):
        with timeline.span("compile.wait", "compile",
                           cause=_BUILDING.get(sig, 0), wait="build"):
            lock.acquire()
    try:
        prog = cache_get(sig)      # double-checked: one trace per sig
        if prog is None:
            cur = phases.current()
            _BUILDING[sig] = cur.req if cur is not None else 0
            t0 = time.perf_counter()
            try:
                prog = build()
                cache_put(sig, prog)
            finally:
                _BUILDING.pop(sig, None)
            charge_compile(kind, t0)
    finally:
        lock.release()
    return prog


def tree_delete(tree) -> None:
    """Explicitly free every device array in a pytree of stale outputs
    (superseded slab partials / merge results on a ladder retry): without
    this, the retry's bigger-cap generation coexists with the old one
    until GC, doubling peak HBM exactly when capacity is tight."""
    for leaf in jax.tree_util.tree_leaves(tree):
        delete = getattr(leaf, "delete", None)
        if delete is None:
            continue
        try:
            delete()
        except Exception:  # noqa: BLE001 — already donated/deleted
            pass


def cache_get(sig: str):
    sig = sig_dev(sig)
    with LOCK:
        prog = _COMPILE_CACHE.get(sig)
        if prog is not None:
            _COMPILE_CACHE.move_to_end(sig)
        return prog


def cache_put(sig: str, prog) -> None:
    sig = sig_dev(sig)
    with LOCK:
        _COMPILE_CACHE[sig] = prog
        while len(_COMPILE_CACHE) > MAX_COMPILED_PROGRAMS:
            _COMPILE_CACHE.popitem(last=False)


def charge_compile(kind: str, t0: float) -> None:
    """Attribute one cold program build to the running statement: bump its
    PhaseTimer compile counter (thread-local — the single-flight builders
    have no ExecContext in reach) and emit a timeline compile event."""
    cur = phases.current()
    if cur is not None:
        cur.note_compile()
    timeline.record(f"compile:{kind}", "compile",
                    dur_us=(time.perf_counter() - t0) * 1e6,
                    pid=cur.conn_id if cur is not None else 0,
                    args={"wait": "build"})
