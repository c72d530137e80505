"""A builder's read, not a benchmark cell: what a compaction costs the
statements that run beside it.

`refresh-read.sf4`'s window is too short to cross a compaction (the first
falls due after about 870 operations), so this drives the cell's own
operation kind (`benchmarks/ops/refresh_pair.py`, every answer checked
against `benchmarks/datasets/tpch_refresh.py`'s reference) in a loop with
the trigger `delta.COMPACT_FILL` lowered, times the compactor's phases
and keeps every operation's latency. One JSON line per phase on stdout;
the line `done` answers the question: `compactions`, `over_10x_p50`,
`traces_in_window_by_thread`.

    chiprun --timeout 420 -- python3 tools/compaction_under_load.py \
        --scale 4 --seed <n> --fill 0.003 --once --tail 8

Run it at a scale whose programs the machine's compile cache holds (the
cell's, 4): at a scale never compiled on the chip, first touch alone
compiles for two minutes. Without a TPU (`JAX_PLATFORMS=cpu`, a scale
below 1) it rehearses the control flow and its numbers are no device's.
"""
import argparse
import importlib.util
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks")]

from tidb_tpu.client import Client                            # noqa: E402
from tidb_tpu.executor import (compile_cache, delta,   # noqa: E402
                               device_cache)
from tidb_tpu.server import Server                            # noqa: E402
from tidb_tpu.session import Engine                           # noqa: E402
from tidb_tpu.util.observability import REGISTRY              # noqa: E402

T0 = time.perf_counter()


def say(**kw):
    print(json.dumps({"t": round(time.perf_counter() - T0, 2), **kw}),
          flush=True)


def bench_module(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"cul_{name}", os.path.join(ROOT, "benchmarks", kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=float, default=4.0)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=150.0,
                    help="the window's length at most")
    ap.add_argument("--fill", type=float, default=0.003,
                    help="delta.COMPACT_FILL inside the window")
    ap.add_argument("--once", action="store_true",
                    help="put the trigger back when the first rebuild starts")
    ap.add_argument("--tail", type=float, default=8.0,
                    help="with --once: seconds to go on after that rebuild")
    args = ap.parse_args()
    import jax

    ds = bench_module("datasets", "tpch_refresh")
    kind = bench_module("ops", "refresh_pair")
    data = ds.generate(args.scale, args.seed)
    ref = {}
    ref_thread = threading.Thread(
        target=lambda: ref.setdefault("r", ds.reference(data, "exact")))
    ref_thread.start()
    eng = Engine()
    ds.load(eng, data)
    srv = Server(eng, port=0).start()
    cli = Client(port=srv.port, timeout=900.0, auto_reconnect=False)
    cli.execute("SET tidb_tpu_strict = 'on'")
    if args.scale < 1:
        cli.execute("SET tidb_tpu_engine = 'on'")
        cli.execute("SET tidb_tpu_row_threshold = 1")
    op = kind.bind({"kind": "refresh_pair", "orders": 150,
                    "reads": ["Q1", "Q3", "Q6"]}, ds, None)
    ref_thread.join()
    say(phase="loaded", device=str(jax.devices()[0].device_kind))

    traces, events, rebuilt_at = {}, [], []
    count = compile_cache.count_trace

    def count_by_thread():
        name = threading.current_thread().name
        traces[name] = traces.get(name, 0) + 1
        count()
    compile_cache.count_trace = count_by_thread

    def timed(mod, name):
        inner = getattr(mod, name)

        def outer(*a, **k):
            t = time.perf_counter()
            if args.once and name == "_compact_one":
                delta.COMPACT_FILL = 0.5
            ok = True
            try:
                return inner(*a, **k)
            except BaseException as e:
                ok = repr(e)
                raise
            finally:
                ev = {"what": name, "from": round(t - T0, 2),
                      "s": round(time.perf_counter() - t, 2), "ok": ok}
                if name == "_compact_one":
                    ev["table"] = a[0]["scan"].table.name
                    ev["cause"] = a[0].get("cause")
                    rebuilt_at.append(time.perf_counter())
                events.append(ev)
                say(phase="event", **ev)
        setattr(mod, name, outer)
    for mod, name in ((delta, "_compact_one"), (delta, "_warm"),
                      (device_cache, "install_preview")):
        timed(mod, name)

    lat, bad, t_win, traces_before, n = [], [], None, {}, 0
    while True:
        if n == 3:       # first touch and two warm cycles, as the cell has
            delta.COMPACT_FILL = args.fill
            t_win, traces_before = time.perf_counter(), dict(traces)
            say(phase="window", fill=args.fill)
        now = time.perf_counter()
        if t_win is not None and now - t_win > args.seconds:
            break
        if args.once and rebuilt_at and now - rebuilt_at[0] > args.tail:
            break
        answers = kind.run(cli, op)
        took = time.perf_counter() - now
        if not kind.check(op, answers, ref["r"]):
            bad.append(n)
        if t_win is not None:
            lat.append((round(now - T0, 2), took))
        n += 1

    ms = sorted(d for _, d in lat)
    p50 = ms[len(ms) // 2]
    say(phase="done", ops=len(lat), incorrect=bad,
        p50_ms=round(p50 * 1e3, 1),
        p95_ms=round(ms[int(len(ms) * .95)] * 1e3, 1),
        max_ms=round(ms[-1] * 1e3),
        over_3x_p50=[(t, round(d * 1e3)) for t, d in lat if d > 3 * p50],
        over_10x_p50=[(t, round(d * 1e3)) for t, d in lat if d > 10 * p50],
        compactions=sum(e["what"] == "_compact_one" and e["ok"] is True
                        for e in events),
        traces_in_window_by_thread={
            k: v - traces_before.get(k, 0) for k, v in traces.items()
            if v - traces_before.get(k, 0)},
        counters={f"{k[0]}{dict(k[1])}": v
                  for k, v in REGISTRY.counters.items()
                  if any(w in k[0] for w in
                         ("declines", "compactions", "extensions"))},
        memory_peak_bytes=(jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use"))
    buckets = {}
    for t, d in lat:
        buckets.setdefault(int((t - (t_win - T0)) // 10), []).append(d)
    say(phase="buckets",
        p50_ms_by_10s={k: round(sorted(v)[len(v) // 2] * 1e3)
                       for k, v in sorted(buckets.items())},
        ops_by_10s={k: len(v) for k, v in sorted(buckets.items())})
    cli.close()
    srv.stop()
    eng.close()
    sys.stdout.flush()
    os._exit(0)      # the compactor's daemon thread may still hold the GIL


if __name__ == "__main__":
    main()
