"""A builder's read, not a benchmark cell: the latencies of ONE benchmark
window BY ROLE, in an untraced run.

`point-beside-scan.sf8` reports `op_p50_ms` and `op_p95_ms` over every
operation of its window, the scanner's Q1s beside the seven readers' point
reads. A Q1 lasts longer than any point read, so the MORE statements the
scanner finishes the higher the cell's percentiles sit in the readers' own
distribution, with no reader any slower (`PERF.md` §6, PR 39: 92 → 810 Q1s a
window moved the cell's p95 from the readers' 95.3rd to their 97.7th
percentile). The harness keeps every operation's (role, sent, done) in
`benchmarks/point_roles.py` and prints them by role in a traced run only,
whose window the recorder slows.

Runs `benchmarks/run.py` in this process with the same arguments and, after
its result line, prints one JSON line `window_by_role`: operations and
p50 · p90 · p95 · p97.5 · p99 ms by role over the window's operations (the
last `attempted` samples, as `point_roles.window` takes them), and the
collector's (collections, collected) by generation over the process. A cell
whose operation kind samples no role prints the collector's alone.

    chiprun -- python3 tools/window_by_role.py --workload \\
        point-beside-scan.sf8 --seed <n> --seconds 40 --trace 0
"""
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks")]

import point_roles                                            # noqa: E402
import run as bench                                           # noqa: E402
import stats                                                  # noqa: E402

QUANTILES = (50, 90, 95, 97.5, 99)


def by_role(samples) -> dict:
    out = {}
    for role in sorted({r for r, _s, _d in samples}):
        ms = [(d - s) * 1e3 for r, s, d in samples if r == role]
        out[role] = {"operations": len(ms),
                     **{f"p{q}_ms": stats.percentile(ms, q)
                        for q in QUANTILES}}
    return out


def main(argv=None) -> int:
    attempted = []
    run = bench.run

    def run_and_keep(args):
        result = run(args)
        attempted.append(result["attempted"])
        return result

    bench.run = run_and_keep
    rc = bench.main(argv)
    n = attempted[0] if attempted else 0
    samples = point_roles.SAMPLES
    print(json.dumps({
        "phase": "window_by_role",
        **(by_role(samples[-n:]) if 0 < n <= len(samples) else {}),
        "gc": [(g["collections"], g["collected"])
               for g in gc.get_stats()]}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
