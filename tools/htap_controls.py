"""A builder's read, not a benchmark cell: the two controls of
`point-beside-scan.sf8` — the seven point connections WITHOUT the scanner,
and the scanner alone — so that one can say how much of a point read is its
own cost and how much is waiting for the scan's host work, and what Q1
loses to seven light neighbours.

One set-up (the cell's data set, operation kind, connections, first touch
and warm cycles, through `benchmarks/run.py`'s own loop), then three
windows on the same server: `points` (connections 2..8 only), `scan`
(connection 1 only), `both` (all eight: the cell's traffic). Every answer
is checked against the data set's reference. One JSON line a window:
operations, operations a second, p50 and p95 by role on the client's clock.

    chiprun --timeout 600 -- python3 tools/htap_controls.py --seed <n>

Without a TPU (`JAX_PLATFORMS=cpu`, `--scale` below 1) it rehearses the
control flow and its numbers are no device's.
"""
import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks")]

import run as bench                                           # noqa: E402
import stats                                                  # noqa: E402

CELL = "point-beside-scan.sf8"


def window(name, clients, ops, kinds, reference, seconds):
    t0 = time.perf_counter()
    per_client, _ = bench.drive(clients, ops, kinds, [0] * len(clients),
                                until=t0 + seconds)
    records = [r for recs in per_client for r in recs]
    wrong = [w for w in bench.judge(records, ops, kinds, reference) if w]
    out = {"window": name, "connections": len(clients), "seconds": seconds,
           "operations": len(records), "wrong": len(wrong),
           "first_wrong": wrong[:2],
           "ops_per_s": sum(r[2] <= t0 + seconds for r in records)
           / seconds}
    for role in ("point", "scan"):
        ms = [(r[2] - r[1]) * 1e3 for r in records
              if r[3] is not None and r[3]["role"] == role]
        if ms:
            out[role] = {"operations": len(ms),
                         "p50_ms": stats.percentile(ms, 50),
                         "p95_ms": stats.percentile(ms, 95),
                         "mean_ms": sum(ms) / len(ms)}
    print(json.dumps(out), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--scale", type=float, default=None,
                    help="NOT for measuring: rehearse at this scale")
    args = ap.parse_args()
    import numpy as np
    from tidb_tpu.server import Server
    from tidb_tpu.session import Engine

    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = bench.by_name(spec["workloads"], CELL, "workload")
    config = json.load(open(os.path.join(
        ROOT, bench.by_name(spec["configs"], cell["config"],
                            "configuration")["file"])))
    traffic = json.load(open(os.path.join(
        ROOT, "benchmarks", "traffic", cell["traffic"] + ".json")))
    rehearsal = args.scale is not None
    device = bench.check_device(cell["chips"], rehearsal)
    dataset = bench.load_module("datasets", config["dataset"])
    data = dataset.generate(args.scale if rehearsal else config["scale"],
                            args.seed)
    reference = {}
    ref_thread = threading.Thread(
        target=lambda: reference.update(dataset.reference(data)))
    ref_thread.start()
    eng = Engine()
    dataset.load(eng, data)
    settings = dict(config["session"])
    if rehearsal:
        settings.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1)
    server = Server(eng, port=0).start()
    clients = [bench.connect(server, settings)
               for _ in range(traffic["clients"])]
    try:
        kinds = {o["kind"]: bench.load_module("ops", o["kind"])
                 for o in traffic["ops"]}
        rng = np.random.default_rng([args.seed, 2])
        ops = [dict(kinds[o["kind"]].bind(o, dataset, rng), kind=o["kind"])
               for o in traffic["ops"]]
        # the cell's warm-up: first touch on the first connection (the
        # scanner), then every connection's warm cycles
        warm = bench.run_loop(clients[0], ops, kinds, 0, cycles=1)
        for recs in bench.drive(clients, ops, kinds, [0] * len(clients),
                                cycles=traffic["warmup_cycles"])[0]:
            warm += recs
        ref_thread.join()
        bad = [w for w in bench.judge(warm, ops, kinds, reference) if w]
        print(json.dumps({"window": "setup", "device": device,
                          "seconds": time.perf_counter() - bench.T_PROCESS,
                          "operations": len(warm), "wrong": len(bad)}),
              flush=True)
        for name, some in (("points", clients[1:]), ("scan", clients[:1]),
                           ("both", clients)):
            window(name, some, ops, kinds, reference, args.seconds)
    finally:
        for cli in clients:
            cli.close()
        server.stop()
        eng.close()


if __name__ == "__main__":
    main()
