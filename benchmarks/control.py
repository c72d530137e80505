#!/usr/bin/env python3
"""benchmarks/control.py — the control of the `correct` comparison, at a
cell's own size.

    python3 benchmarks/control.py --config <file under benchmarks/configs> --seeds 1 2 3

`correct` is text equality of every answer with the exact reference (limit:
0 operations wrong). The control is the reference put in the program's
place, computed in the step below the exact DECIMAL arithmetic that the
configuration guarantees: every SUM accumulated in a float64. It touches no
device and nothing of the program; it has to come out as NOT correct, and
this prints, for each seed, the statements whose rows differ.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent


def dataset_module(name: str):
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_datasets_{name}", HERE / "datasets" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def compare(dataset: str, scale: float, seed: int) -> dict:
    ds = dataset_module(dataset)
    t0 = time.perf_counter()
    data = ds.generate(scale, seed)
    exact = ds.reference(data)
    lower = ds.reference(data, arithmetic="float64")
    wrong = sorted(n for n in exact if lower[n] != exact[n])
    example = None
    if wrong:
        n = wrong[0]
        i = next(i for i, (a, b) in enumerate(zip(lower[n], exact[n]))
                 if a != b)
        example = {"statement": n, "row": i, "control": lower[n][i],
                   "reference": exact[n][i]}
    return {"seed": seed, "scale": scale, "statements": len(exact),
            "statements_wrong": len(wrong), "limit": 0, "wrong": wrong,
            "example": example,
            "seconds": round(time.perf_counter() - t0, 2)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a configuration's file name under configs/")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cfg = json.loads((HERE / "configs" / args.config).read_text())
    failed_to_fail = 0
    for seed in args.seeds:
        got = compare(cfg["dataset"], cfg["scale"], seed)
        print(json.dumps({"control": cfg["name"], **got}), flush=True)
        failed_to_fail += got["statements_wrong"] == 0
    return 1 if failed_to_fail else 0


if __name__ == "__main__":
    sys.exit(main())
