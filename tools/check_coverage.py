#!/usr/bin/env python
"""Device-coverage ratchet: the committed COVERAGE.json pins which of
the 22 TPC-H-shaped coverage queries (tidb_tpu/tools/coverage.py) run
their analytic core as fused device fragments.  A fresh small-SF sweep
must keep every pinned-fused query fused — a regression (query that was
fused now reports a fallback) fails, as does a fallback whose reason
code drifts off the committed one or out of the fragment taxonomy.

Newly-fused queries (fallback → fused) are NOT failures; they print as
ratchet advances so the baseline can be re-pinned.

Run directly (`python tools/check_coverage.py`) or via the chaos-sweep
preflight beside check_metrics/check_failpoints.  Exit 0 = clean,
1 = regression.  `python tools/check_coverage.py --update` rewrites
COVERAGE.json from the fresh sweep."""

import json
import os
import sys

BASELINE = "COVERAGE.json"
SWEEP_ROWS = 6000        # small-SF: seconds, not minutes


def _sweep(root: str):
    sys.path.insert(0, root)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from tidb_tpu.tools import coverage as C
    _eng, s = C.fresh_session(SWEEP_ROWS)
    rows = C.run_coverage(s, time_cpu=False)
    return {r["query"]: {"fused": r["fused"], "fallback": r["fallback"]}
            for r in rows}


def run(root: str = None):
    """→ problem list (empty = ratchet holds)."""
    if root is None:
        root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..")
    root = os.path.abspath(root)
    base_path = os.path.join(root, BASELINE)
    if not os.path.exists(base_path):
        return [f"{BASELINE} missing — generate with "
                f"`python tools/check_coverage.py --update`"]
    with open(base_path) as f:
        baseline = json.load(f)["queries"]
    fresh = _sweep(root)
    from tidb_tpu.executor.eligibility import FALLBACK_REASONS
    problems = []
    for q in sorted(baseline, key=lambda n: int(n[1:])):
        pin = baseline[q]
        now = fresh.get(q)
        if now is None:
            problems.append(f"coverage: {q} pinned in {BASELINE} but "
                            f"missing from the sweep")
            continue
        if pin["fused"] and not now["fused"]:
            problems.append(
                f"coverage: {q} REGRESSED fused -> fallback"
                f"({now['fallback']})")
        elif not pin["fused"] and not now["fused"]:
            if now["fallback"] not in FALLBACK_REASONS:
                problems.append(
                    f"coverage: {q} fallback reason {now['fallback']!r} "
                    f"not in the fragment taxonomy {FALLBACK_REASONS}")
            elif now["fallback"] != pin["fallback"]:
                problems.append(
                    f"coverage: {q} fallback reason drifted "
                    f"{pin['fallback']!r} -> {now['fallback']!r} "
                    f"(re-pin if intentional)")
        elif not pin["fused"] and now["fused"]:
            print(f"coverage: {q} newly fused — ratchet can advance "
                  f"(re-pin {BASELINE})")
    for q in sorted(fresh):
        if q not in baseline:
            problems.append(f"coverage: {q} in the sweep but not pinned "
                            f"in {BASELINE} — re-pin")
    return problems


def update(root: str = None) -> str:
    if root is None:
        root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..")
    root = os.path.abspath(root)
    fresh = _sweep(root)
    path = os.path.join(root, BASELINE)
    fused = sum(1 for v in fresh.values() if v["fused"])
    with open(path, "w") as f:
        json.dump({"fused": fused, "total": len(fresh), "queries": fresh},
                  f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    if argv and argv[0] == "--update":
        path = update(argv[1] if len(argv) > 1 else None)
        print(f"check_coverage: wrote {path}")
        return 0
    problems = run(argv[0] if argv else None)
    for p in problems:
        print(p, file=sys.stderr)
    if problems:
        print(f"check_coverage: {len(problems)} regression(s)",
              file=sys.stderr)
        return 1
    print("check_coverage: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
