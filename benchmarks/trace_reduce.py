"""From a profiler trace (`.xplane.pb`) to device busy time, idle gaps and
the device operations that took most time. The benchmark's own reduction:
read with `jax.profiler.ProfileData`, arithmetic on plain intervals so that
it can be checked on a synthetic set (benchmarks/tests/test_trace_reduce.py).

An interval is `(start, end)` in seconds on one clock. A device *line* is a
list of intervals (the operations of one stream of one chip); a chip's busy
time is the union over its lines, and the run's `busy_s` is the mean over the
chips.
"""

from __future__ import annotations

import glob
import os
import re

SYNC_NAME = "benchmarks_clock_sync"
# Where a platform's device operations are in the profiler's file: the prefix
# of the planes that are chips, and the prefix of the lines of such a plane
# that hold the operations themselves (a TPU plane's other lines, "Steps",
# "XLA Modules", "XLA TraceMe", span or repeat them). The "cpu" row serves
# the rehearsal only: XLA's host threads stand in for a device there.
DEVICE_LINES = {"tpu": ("/device:TPU:", "XLA Ops"),
                "cpu": ("/host:CPU", "tf_XLA")}
# the line of a chip's plane with one event per launched program
PROGRAM_LINE = "XLA Modules"
NAME_CHARS = 96


def union(intervals) -> list:
    """Sorted, disjoint intervals covering exactly what `intervals` cover."""
    out: list = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy, lo: float, hi: float) -> list:
    """The idle intervals of [lo, hi] given disjoint sorted `busy`."""
    out, at = [], lo
    for s, e in clip(busy, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def overlap(a, b) -> float:
    """Seconds covered by both of two disjoint sorted interval lists."""
    i = j = 0
    acc = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            acc += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return acc


def busy_and_idle(chips: dict, lo: float, hi: float) -> dict:
    """chips: {chip name: [line intervals, ...]} → busy_s (mean over chips
    of the union over each chip's lines, clipped to [lo, hi]), window_s,
    idle_share, and the idle gaps of the busiest-union over all chips
    (no chip busy)."""
    if not chips or hi <= lo:
        raise ValueError("no device line, or an empty window")
    per_chip = {name: union(clip([iv for line in lines for iv in line],
                                 lo, hi))
                for name, lines in chips.items()}
    busy = sum(total(u) for u in per_chip.values()) / len(per_chip)
    any_busy = union([iv for u in per_chip.values() for iv in u])
    return {"busy_s": busy, "window_s": hi - lo,
            "idle_share": 1.0 - busy / (hi - lo),
            "idle_gaps": gaps(any_busy, lo, hi)}


def attribute_gaps(idle, lanes: dict, top: int = 10) -> list:
    """Idle seconds by what the host was doing: for each host lane (a list
    of intervals, possibly from several connections) the idle seconds its
    union covers, and under "(no lane)" the idle seconds no lane covers.
    With several connections the lanes overlap, so the parts can sum to more
    than the idle time. → [[name, seconds], ...], longest first."""
    idle = union(idle)
    unions = {name: union(iv) for name, iv in lanes.items()}
    out = [[name, overlap(idle, u)] for name, u in unions.items()]
    covered = union([iv for u in unions.values() for iv in u])
    out.append(["(no lane)", total(idle) - overlap(idle, covered)])
    out = [[n, s] for n, s in out if s > 0]
    return sorted(out, key=lambda r: -r[1])[:top]


def short_name(name: str) -> str:
    """A TPU trace names an operation by its whole HLO text,
    `%fusion.3 = (types) fusion(operands), kind=..., calls=...`: keep the
    result's name, the opcode and what it calls."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:NAME_CHARS]
    opcode = re.search(r"[\s)]([a-z][a-z0-9_-]*)\(", " " + rest)
    calls = re.search(r"(?:calls|body)=(%[\w.-]+)", rest)
    return " ".join(x for x in (head, opcode and opcode.group(1),
                                calls and calls.group(1)) if x)[:NAME_CHARS]


def top_ops(named, top: int = 10) -> list:
    """[(name, seconds), ...] events → total seconds by (shortened) name,
    longest first."""
    acc: dict = {}
    for name, secs in named:
        name = short_name(name)
        acc[name] = acc.get(name, 0.0) + secs
    return [[n, s] for n, s in
            sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


# ---------------------------------------------------------------------------
# reading the profiler's file
# ---------------------------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str, platform: str) -> dict:
    """→ {"chips": {plane name: [line intervals, ...]}, "ops": [(name,
    seconds), ...], "programs": the same per launched program, "sync_s": start of the `SYNC_NAME` annotation or None,
    "planes": [(plane, line, events)] for the earlier lines of a run}.
    Times are seconds on the trace's own clock. Which planes are chips and
    which of their lines are read is in `DEVICE_LINES`; an unknown platform
    is an error."""
    from jax.profiler import ProfileData
    plane_prefix, line_prefix = DEVICE_LINES[platform]
    data = ProfileData.from_file(path)
    chips: dict = {}
    ops: list = []
    programs: list = []
    seen: list = []
    sync = None
    for plane in data.planes:
        for ln in plane.lines:
            evs = [(e.name, e.start_ns * 1e-9,
                    (e.start_ns + e.duration_ns) * 1e-9) for e in ln.events]
            seen.append((plane.name, ln.name, len(evs)))
            if sync is None:
                sync = next((s for n, s, _e in evs if n == SYNC_NAME), None)
            if plane.name.startswith(plane_prefix) \
                    and ln.name.startswith(line_prefix):
                chips.setdefault(plane.name, []).append(
                    [(s, e) for _n, s, e in evs])
                ops += [(n, e - s) for n, s, e in evs]
            elif plane.name.startswith(plane_prefix) \
                    and ln.name == PROGRAM_LINE:
                programs += [(n, e - s) for n, s, e in evs]
    return {"chips": chips, "ops": ops, "programs": programs,
            "sync_s": sync, "planes": seen}


def read_timeline(path: str) -> dict:
    """The program's `util/timeline.py` file → {lane: [(start, end)]} in
    seconds on the timeline's clock."""
    import json
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    lanes: dict = {}
    for ev in events:
        if ev.get("ph") == "X" and ev.get("dur", 0) > 0:
            lanes.setdefault(ev["cat"], []).append(
                (ev["ts"] * 1e-6, (ev["ts"] + ev["dur"]) * 1e-6))
    return lanes
