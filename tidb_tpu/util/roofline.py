"""Per-query roofline accounting (PAPER.md §roofline), for EXPLAIN
ANALYZE. The benchmark does not use it: `scan_hbm_share` divides by the
published peak in `benchmarks/peaks.json` (PERF.md §3).

A scan-bound query's floor is `bytes the program must move / sustained
stream bandwidth`.  For in-engine attribution we need something cheap
enough to run lazily inside a session, so `measured_gbs()` times a
single ~64 MiB device round trip once per process and caches it.  The
per-query figure is then

    roofline_fraction = (scan_bytes / measured_gbs) / device_wall_s

i.e. what fraction of the query's device wall the pure memory-stream
floor explains.  1.0 = the query runs at the bandwidth roofline; the
Q3/Q5 fusion gap shows up as fractions ≪ 1 (host round trips between
operators dominating the wall).  Clamped to [0, 1] — timer jitter on
sub-millisecond walls can push the raw ratio over 1."""

from __future__ import annotations

import threading

_LOCK = threading.Lock()
_GBS: float = 0.0          # cached sustained stream bandwidth, GB/s


def measured_gbs() -> float:
    """Sustained device stream bandwidth (GB/s), measured once per
    process with a ~64 MiB float32 triad and cached.  Returns 0.0 when
    no backend is usable (callers must treat 0 as 'unknown')."""
    global _GBS
    if _GBS:
        return _GBS
    with _LOCK:
        if _GBS:
            return _GBS
        try:
            _GBS = _measure()
        except Exception:
            _GBS = 0.0
    return _GBS


def _measure() -> float:
    import time

    import jax
    import jax.numpy as jnp

    n = 16 * 1024 * 1024                      # 64 MiB per operand
    x = jnp.ones((n,), dtype=jnp.float32)
    y = jnp.full((n,), 2.0, dtype=jnp.float32)

    @jax.jit
    def triad(a, b):
        return a + 0.5 * b

    triad(x, y).block_until_ready()           # compile outside the timing
    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        triad(x, y).block_until_ready()
    dt = time.perf_counter() - t0
    if dt <= 0.0:
        return 0.0
    moved = 3.0 * 4.0 * n * reps              # read a, read b, write out
    return moved / dt / 1e9


def set_measured_gbs(gbs: float) -> None:
    """Override the cached bandwidth (tests inject a constant)."""
    global _GBS
    with _LOCK:
        _GBS = float(gbs)


def fraction(scan_bytes: int, device_wall_s: float,
             gbs: float = None) -> float:
    """Roofline fraction for one statement: stream-floor seconds over
    actual device wall, clamped to [0, 1].  0.0 when unmeasurable (no
    bytes, no wall, or no bandwidth figure)."""
    if gbs is None:
        gbs = measured_gbs()
    if scan_bytes <= 0 or device_wall_s <= 0.0 or gbs <= 0.0:
        return 0.0
    floor_s = scan_bytes / (gbs * 1e9)
    return max(0.0, min(1.0, floor_s / device_wall_s))


def effective_fraction(logical_bytes: int, device_wall_s: float,
                       gbs: float = None) -> float:
    """Roofline fraction against LOGICAL (uncompressed-equivalent)
    bytes. Deliberately NOT clamped above 1: a compressed scan that
    delivers logical bytes faster than the raw stream floor shows up as
    >1x effective bandwidth — that's the win, not a measurement error.
    `fraction()` (physical bytes actually streamed) stays the honest
    hardware-utilization figure; this one is the workload-throughput
    figure. 0.0 when unmeasurable."""
    if gbs is None:
        gbs = measured_gbs()
    if logical_bytes <= 0 or device_wall_s <= 0.0 or gbs <= 0.0:
        return 0.0
    floor_s = logical_bytes / (gbs * 1e9)
    return max(0.0, floor_s / device_wall_s)


__all__ = ["measured_gbs", "set_measured_gbs", "fraction",
           "effective_fraction"]
