"""A builder's read, not a benchmark cell: how long the threads of ONE
benchmark window stood RUNNABLE in the operating system's run queue.

A span's off-CPU time (`tidb_tpu/util/timeline.py`, field `cpu`) is the time
its thread did not run. What no site tagged as a designed wait is booked as
the wait for the interpreter's lock — plus the kernel's run-queue delay,
which off-CPU cannot tell from it. `/proc/self/task/<tid>/schedstat` can: its
second number is the nanoseconds the thread was runnable and not running; a
thread asleep on the interpreter's lock is not runnable.

Runs `benchmarks/run.py` in this process with the same arguments, reads
every thread's schedstat just before and just after the window's `drive`
call, and prints one JSON line before the result line: for the threads alive
at both ends, seconds on a CPU, seconds of run-queue delay, and the eight
threads with the most delay. A kernel that offers no `schedstat` (a
sandboxed one: the chip hosts', `PERF.md` §6, PR 38) reads `"schedstat":
false` and nulls, and the involuntary context switches of
`/proc/<pid>/task/<tid>/status` where it has those.

    chiprun -- python3 tools/schedstat_window.py --workload qstream8.sf1 \\
        --seed <n> --seconds 40 --trace 1
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks")]

import run as bench                                           # noqa: E402


def _read(path: str):
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None                     # no such file, or the thread ended


def snapshot() -> dict:
    """→ {tid: (name, on-CPU ns, run-queue delay ns, involuntary context
    switches)} of this process; a number the kernel does not offer (a
    sandboxed kernel has no `schedstat`) is None."""
    out = {}
    for tid in os.listdir("/proc/self/task"):
        base = f"/proc/self/task/{tid}/"
        name = _read(base + "comm")
        if name is None:
            continue
        ran = delay = forced = None
        stat = (_read(base + "schedstat") or "").split()
        if len(stat) == 3:
            ran, delay = int(stat[0]), int(stat[1])
        for line in (_read(base + "status") or "").splitlines():
            if line.startswith("nonvoluntary_ctxt_switches"):
                forced = int(line.split()[-1])
        out[tid] = (name.strip(), ran, delay, forced)
    return out


def _delta(before: dict, after: dict, i: int, scale: float = 1.0):
    """Σ over the threads alive at both ends of column `i`; None where the
    kernel offers none."""
    both = [(after[t][i], before[t][i]) for t in after if t in before]
    if not both or any(a is None or b is None for a, b in both):
        return None
    return sum(a - b for a, b in both) * scale


def main() -> int:
    real = bench.drive

    def drive(*args, **kw):
        if kw.get("until") is None:     # first touch, warm cycles
            return real(*args, **kw)
        before = snapshot()
        out = real(*args, **kw)
        after = snapshot()
        delayed = []
        if _delta(before, after, 2) is not None:
            delayed = sorted(
                ((after[t][0], (after[t][1] - before[t][1]) * 1e-9,
                  (after[t][2] - before[t][2]) * 1e-9)
                 for t in after if t in before), key=lambda x: -x[2])[:8]
        print(json.dumps({
            "phase": "schedstat",
            "threads": sum(t in before for t in after),
            # false: this kernel has no /proc/<pid>/task/<tid>/schedstat
            "schedstat": _delta(before, after, 2) is not None,
            "on_cpu_s": _delta(before, after, 1, 1e-9),
            "run_queue_delay_s": _delta(before, after, 2, 1e-9),
            "involuntary_switches": _delta(before, after, 3),
            "most_delayed": [
                {"thread": n, "on_cpu_s": round(ran, 4),
                 "run_queue_delay_s": round(d, 4)}
                for n, ran, d in delayed]}),
            flush=True)
        return out

    bench.drive = drive
    return bench.main()


if __name__ == "__main__":
    sys.exit(main())
