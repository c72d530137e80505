#!/usr/bin/env python3
"""benchmarks/run.py — one run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, modelled on `chip_smoke.py`: it checks the device, makes the
cell's data from `--seed`, loads it into an `Engine`, starts the MySQL wire
`Server` in-process, connects the traffic mix's clients over TCP loopback,
warms exactly the cell's statements, then drives the mix for `--seconds` and
compares every answer with the data set's plain reference. Everything that
belongs to one cell is found by the names in `BENCHMARK.json`:

    configs/<file named by the configuration>   the deployment
    datasets/<dataset>.py                       generate, load, statements, reference
    traffic/<traffic>.json                      clients, loop, operations
    ops/<kind>.py                               one kind of operation
    end_to_end/<metric>.py, layer_metrics/<metric>.py   one reader per metric

The run fails, and prints no result line, when JAX reports anything but a TPU
with the cell's number of chips. Earlier lines are one JSON object per phase;
the LAST line of stdout is the result object: `correct`, `attempted`,
`failed`, `metrics`, `device` and, with `--trace 1`, `breakdown`. With
`--trace 0` the metrics are the cell's end-to-end metrics; with `--trace 1`
its per-layer metrics, read from a window that is slowed by the tracers.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))       # the program: `tidb_tpu`
sys.path.insert(0, str(HERE))       # the harness's own modules

# JAX's persistent cache evicts the least recently used entries beyond this
# many bytes. The program bakes table data into a few gather programs (170 MB
# of them at SF=10, new for every seed): under the 192 MiB that the chip
# machines set, one run's gathers evict every shared program and the next
# run compiles them all again. Two GiB keep the shared programs (read, so
# recent, in every run) and let the gathers of old seeds go.
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = str(2 << 30)

import meters  # noqa: E402
import scan_bytes  # noqa: E402
import stats  # noqa: E402
import trace_reduce  # noqa: E402

# the traced run profiles this much of the window, from this far into it
TRACE_SECONDS = 4.0
TRACE_AFTER = 1.0
# a client whose operations keep raising has lost its connection
MAX_CONSECUTIVE_ERRORS = 3
CLIENT_TIMEOUT_S = 900.0
# what a statement's ledger says when the device ran it
DEVICE_ENGINE = "tpu"


class BenchFailed(Exception):
    """The run cannot give a result."""


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def load_module(kind: str, name: str):
    """benchmarks/<kind>/<name>.py, found by name."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise BenchFailed(f"{path.relative_to(ROOT)} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name}".replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchFailed(f"BENCHMARK.json has no {what} named {name!r}")


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def check_device(chips: int, rehearsal: bool) -> dict:
    from tidb_tpu.ops import jax_env
    devs = jax_env.jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    emit("device", **device, compile_cache_dir=jax_env.compile_cache_dir(),
         rehearsal=rehearsal)
    if rehearsal:
        return device
    if device["platform"] != "tpu":
        raise BenchFailed(f"needs a TPU; jax reports {device['platform']!r} "
                          f"({device['count']} device(s))")
    if device["count"] != chips:
        raise BenchFailed(f"the cell needs {chips} chip(s), jax sees "
                          f"{device['count']}")
    return device


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    from tidb_tpu.ops.jax_env import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


# ---------------------------------------------------------------------------
# clients and the loop they run
# ---------------------------------------------------------------------------

def connect(server, settings: dict):
    from tidb_tpu.client import Client
    # no reconnect-and-retry: an operation runs once or it has failed
    cli = Client(port=server.port, timeout=CLIENT_TIMEOUT_S,
                 auto_reconnect=False)
    for var, value in settings.items():
        cli.execute(f"SET {var} = {value!r}" if isinstance(value, str)
                    else f"SET {var} = {value}")
    return cli


def run_loop(cli, ops: list, kinds: dict, offset: int, *, until=None,
             cycles=None, think_s: float = 0.0) -> list:
    """One client's closed loop: operation (offset + i) mod len(ops), the
    next sent when the last has answered. Runs `cycles` whole cycles, or
    until the clock passes `until` (an operation in flight then is
    finished). → [(op index, sent, done, answer or None, error or None)]
    on `time.perf_counter`."""
    out, errors, i = [], 0, 0
    while True:
        if cycles is not None and i >= cycles * len(ops):
            break
        if until is not None and time.perf_counter() >= until:
            break
        k = (offset + i) % len(ops)
        op = ops[k]
        sent = time.perf_counter()
        try:
            answer, error = kinds[op["kind"]].run(cli, op), None
            errors = 0
        except Exception as e:  # noqa: BLE001 — a failed operation, counted
            answer, error = None, f"{type(e).__name__}: {e}"
            errors += 1
        out.append((k, sent, time.perf_counter(), answer, error))
        if errors >= MAX_CONSECUTIVE_ERRORS:
            break
        if think_s:
            time.sleep(think_s)
        i += 1
    return out


def drive(clients: list, ops: list, kinds: dict, offsets: list,
          meanwhile=None, **how):
    """Every client's loop at once, one thread each, and `meanwhile()` in
    the calling thread beside them. → (one record list per client, what
    `meanwhile` returned)."""
    with ThreadPoolExecutor(max_workers=len(clients)) as pool:
        futures = [pool.submit(run_loop, cli, ops, kinds, off, **how)
                   for cli, off in zip(clients, offsets)]
        beside = meanwhile() if meanwhile else None
        return [f.result() for f in futures], beside


def judge(records: list, ops: list, kinds: dict, reference: dict) -> list:
    """→ for each record: None where the answer equals the reference's,
    else what is wrong with it."""
    out = []
    for k, _sent, _done, answer, error in records:
        op = ops[k]
        if error is not None:
            out.append(f"{op['name']}: raised {error}")
        elif not kinds[op["kind"]].check(op, answer, reference):
            out.append(f"{op['name']}: rows differ from the reference: "
                       f"got {str(answer)[:160]}")
        else:
            out.append(None)
    return out


# ---------------------------------------------------------------------------
# the traced part of a traced window
# ---------------------------------------------------------------------------

def trace_window(trace_dir: str, seconds: float) -> dict:
    """Profile the device for `seconds` from now, in the calling thread,
    while the clients go on. → the span on the timeline's clock and the
    same instant on both clocks (the sync annotation)."""
    from tidb_tpu.ops.jax_env import jax
    from tidb_tpu.util import timeline
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # the host's Python is not slowed
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        sync = timeline.now_us() * 1e-6
        with jax.profiler.TraceAnnotation(trace_reduce.SYNC_NAME):
            pass
        time.sleep(seconds)
        end = timeline.now_us() * 1e-6
    finally:
        jax.profiler.stop_trace()
    return {"sync_timeline_s": sync, "end_timeline_s": end}


def reduce_trace(trace_dir: str, span: dict, platform: str,
                 timeline_path) -> dict:
    """The profiler's file and the program's timeline → busy and idle time
    of the traced span, the device operations by time, and the idle gaps by
    the host lane that covers them."""
    found = trace_reduce.read_xplane(trace_reduce.find_xplane(trace_dir),
                                     platform)
    emit("trace_planes", planes=[p for p in found["planes"] if p[2]][:40])
    emit("trace_programs", by_seconds=trace_reduce.top_ops(found["programs"]))
    if found["sync_s"] is None:
        raise BenchFailed("the clock-sync annotation is not in the trace")
    if not found["chips"]:
        raise BenchFailed("the trace has no device line: "
                          f"{sorted({p[0] for p in found['planes']})}")
    lo = found["sync_s"]
    hi = lo + span["end_timeline_s"] - span["sync_timeline_s"]
    out = trace_reduce.busy_and_idle(found["chips"], lo, hi)
    if out["busy_s"] <= 0:
        raise BenchFailed("no operation ran on the device in the traced "
                          "span")
    shift = lo - span["sync_timeline_s"]     # timeline clock → trace clock
    lanes = {}
    if timeline_path and Path(timeline_path).is_file():
        lanes = {name: [(s + shift, e + shift) for s, e in ivs]
                 for name, ivs in
                 trace_reduce.read_timeline(timeline_path).items()}
    out["breakdown"] = {
        "device_ops": trace_reduce.top_ops(found["ops"]),
        "idle_gaps": trace_reduce.attribute_gaps(out.pop("idle_gaps"),
                                                 lanes)}
    out["chips"] = len(found["chips"])
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(args) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = by_name(bench["workloads"], args.workload, "workload")
    config_entry = by_name(bench["configs"], cell["config"], "configuration")
    config = json.loads((ROOT / config_entry["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    if traffic["loop"] != "closed":
        raise BenchFailed(f"loop {traffic['loop']!r}: only the closed loop "
                          "is built")
    rehearsal = args.rehearsal_scale is not None
    scale = args.rehearsal_scale if rehearsal else config["scale"]
    trace = bool(args.trace)
    emit("cell", workload=cell["name"], config=config["name"],
         traffic=cell["traffic"], chips=cell["chips"], scale=scale,
         seed=args.seed, seconds=args.seconds, trace=trace,
         rehearsal=rehearsal)

    device = check_device(cell["chips"], rehearsal)
    peaks = json.loads((HERE / "peaks.json").read_text())
    if device["kind"] not in peaks and not rehearsal:
        raise BenchFailed(f"peaks.json has no device kind {device['kind']!r}")
    meter = meters.CompileMeter().install()
    from tidb_tpu.server import Server
    from tidb_tpu.session import Engine
    import numpy as np
    parts = {"import_and_device_s": time.perf_counter() - T_PROCESS}

    # data, and the reference beside the load: it reads the raw columns
    # only, on a thread of its own, and is waited for before the window
    dataset = load_module("datasets", config["dataset"])
    t0 = time.perf_counter()
    data = dataset.generate(scale, args.seed)
    parts["generate_s"] = time.perf_counter() - t0
    reference: dict = {}

    def make_reference():
        t = time.perf_counter()
        reference.update(dataset.reference(data))
        parts["reference_s_beside_setup"] = time.perf_counter() - t

    ref_thread = threading.Thread(target=make_reference, daemon=True)
    ref_thread.start()

    eng = Engine()
    server = admin = None
    clients: list = []
    trace_dir = timeline_dir = None
    try:
        t0 = time.perf_counter()
        dataset.load(eng, data)
        parts["load_and_analyze_s"] = time.perf_counter() - t0
        rows = {t: len(next(iter(cols.values()))) for t, cols in data.items()}
        emit("load", rows=rows, **{k: round(v, 3) for k, v in parts.items()})
        if not rehearsal and rows != config["rows"]:
            raise BenchFailed(f"loaded {rows}, the configuration states "
                              f"{config['rows']}")

        settings = dict(config["session"])
        if rehearsal:
            # off the TPU `auto` takes the CPU engine, and a tiny table
            # falls under the row threshold: the rehearsal forces the
            # device path, and its numbers are never recorded
            settings.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1)
        server = Server(eng, port=0).start()
        admin = connect(server, {})
        clients = [connect(server, settings)
                   for _ in range(traffic["clients"])]

        kinds = {k: load_module("ops", k)
                 for k in {o["kind"] for o in traffic["ops"]}}
        rng = np.random.default_rng([args.seed, 2])
        ops = [dict(kinds[o["kind"]].bind(o, dataset, rng), kind=o["kind"])
               for o in traffic["ops"]]
        statements = {n: s for op in ops for n, s in op["statements"].items()}
        off = traffic["start_offset"]
        offsets = [(off["seed"] * args.seed + off["client"] * k) % len(ops)
                   for k in range(len(clients))]

        # warm-up, part 1: first touch, each operation once on the first
        # connection (encode, upload, compile or load from the cache)
        fb0 = meters.fallbacks_total(admin)
        first_touch, setup_records = [], []
        for k, op in enumerate(ops):
            m0 = meter.snapshot()
            rec = run_loop(clients[0], [op], kinds, 0, cycles=1)
            setup_records += [(k,) + r[1:] for r in rec]
            d = meters.delta(meter.snapshot(), m0)
            first_touch.append({
                "op": op["name"], "wall_s": rec[0][2] - rec[0][1],
                "compile_s": d["seconds"], "compile_requests": d["requests"],
                "cache_hits": d["cache_hits"],
                "cache_misses": d["cache_misses"], "error": rec[0][4]})
            emit("first_touch", **first_touch[-1])
        # part 2: every connection runs the mix as the window will, so each
        # has its plans and the programs of concurrent statements exist
        t0 = time.perf_counter()
        m0 = meter.snapshot()
        for recs in drive(clients, ops, kinds, offsets,
                          cycles=traffic["warmup_cycles"])[0]:
            setup_records += recs
        emit("warm_cycles", cycles=traffic["warmup_cycles"],
             wall_s=round(time.perf_counter() - t0, 3),
             compile=meters.delta(meter.snapshot(), m0))
        ref_thread.join()
        storage = scan_bytes.storage_by_column(meters.table(
            admin, "SELECT * FROM information_schema.table_storage"))

        if trace:
            from tidb_tpu.util import timeline
            trace_dir = tempfile.mkdtemp(prefix="bench_xplane_")
            timeline_dir = tempfile.mkdtemp(prefix="bench_timeline_")
            admin.execute(f"SET tidb_tpu_trace_dir = '{timeline_dir}'")
            admin.query("SELECT 1")     # the recorder starts on a statement

        setup_compile = meter.snapshot()
        led0 = meters.ledgers(admin, statements)
        pool0 = meters.pool_stats()
        mem_before = memory_peak_bytes()

        # ------------------------------------------------------- the window
        t_begin = time.perf_counter()
        setup_s = t_begin - T_PROCESS
        until = t_begin + args.seconds

        def profile_a_part():
            time.sleep(min(TRACE_AFTER, args.seconds / 4))
            return trace_window(trace_dir,
                                min(TRACE_SECONDS, args.seconds / 2))

        per_client, span = drive(
            clients, ops, kinds, offsets, until=until,
            think_s=traffic["think_s"],
            meanwhile=profile_a_part if trace else None)
        t_end = time.perf_counter()
        # ------------------------------------------------------------------

        window_compile = meters.delta(meter.snapshot(), setup_compile)
        led1 = meters.ledgers(admin, statements)
        led = meters.ledger_delta(led1, led0)
        pool_d = meters.delta(meters.pool_stats(), pool0)
        fallbacks = meters.fallbacks_total(admin) - fb0
        timeline_path = None
        if trace:
            timeline.flush()
            timeline_path = timeline.global_path()
            timeline.stop_global()

        records = [r for recs in per_client for r in recs]
        wrong = judge(records, ops, kinds, reference)
        setup_wrong = [w for w in judge(setup_records, ops, kinds, reference)
                       if w]
        in_window = [r for r, w in zip(records, wrong)
                     if w is None and r[2] <= until]
        latencies = [r[2] - r[1] for r in records]
        failed = [w for w in wrong if w]
        not_on_device = sorted(n for n, l in led1.items()
                               if l["engine"] != DEVICE_ENGINE)
        compared = {
            "operations_compared": len(records),
            "operations_wrong": len(failed), "limit_wrong": 0,
            "setup_operations_compared": len(setup_records),
            "setup_operations_wrong": len(setup_wrong),
            "limit_setup_wrong": 0,
            "device_fallbacks": fallbacks, "limit_fallbacks": 0,
            "statements_not_on_engine_" + DEVICE_ENGINE: not_on_device,
            "limit_not_on_engine": 0,
            "first_wrong": (failed + setup_wrong)[:3]}
        emit("compared", **compared)
        correct = (bool(records) and not failed and not setup_wrong
                   and fallbacks == 0 and not not_on_device)

        ctx = {
            "window_s": args.seconds, "attempted": len(records),
            "completed_correct_in_window": len(in_window),
            "latencies_s": latencies, "setup_s": setup_s,
            "setup_parts": parts, "first_touch": first_touch,
            "setup_compile": setup_compile, "window_compile": window_compile,
            "ledger": led, "pool": pool_d, "clients": len(clients),
            "peaks": peaks.get(device["kind"]), "trace": None,
            "scan_needed_bytes": None}
        emit("window", attempted=len(records),
             completed_correct_in_window=len(in_window),
             finished_after_the_window=sum(r[2] > until for r in records),
             window_s=args.seconds, drained_s=round(t_end - until, 4),
             latency_samples=len(latencies),
             p95_has_ten_samples_beyond=stats.supported(len(latencies), 95),
             ops_by_name={op["name"]: sum(r[0] == k for r in records)
                          for k, op in enumerate(ops)},
             p50_ms_by_name={
                 op["name"]: round(stats.percentile(
                     [r[2] - r[1] for r in records if r[0] == k], 50) * 1e3,
                     3)
                 for k, op in enumerate(ops)
                 if any(r[0] == k for r in records)},
             slowest=[{"op": ops[r[0]]["name"],
                       "ms": round((r[2] - r[1]) * 1e3, 3),
                       "sent_at_s": round(r[1] - t_begin, 3)}
                      for r in sorted(records,
                                      key=lambda r: r[1] - r[2])[:3]],
             compile=window_compile, pool=pool_d,
             ledger={k: round(v, 6) for k, v in led["*"].items()})
        emit("setup", setup_s=round(setup_s, 3),
             parts={k: round(v, 3) for k, v in parts.items()},
             first_touch_s=round(sum(f["wall_s"] for f in first_touch), 3),
             compile={k: (round(v, 3) if isinstance(v, float) else v)
                      for k, v in setup_compile.items()},
             memory_peak_before_window=mem_before)

        result_device = dict(device, memory_peak_bytes=memory_peak_bytes())
        result: dict = {}
        if trace:
            needed = sum(scan_bytes.needed_bytes(
                dataset.COLUMNS[n], dataset.PRUNED_TABLE, storage,
                int(led[n]["EXEC_COUNT"]), int(led[n]["SLABS_SKIPPED"]))
                for n in statements)
            ctx["scan_needed_bytes"] = needed
            emit("scan_bytes", needed_by_the_benchmark=needed,
                 counted_by_the_program=int(led["*"]["SCAN_BYTES"]),
                 ratio=(needed / led["*"]["SCAN_BYTES"]
                        if led["*"]["SCAN_BYTES"] else None))
            ctx["trace"] = reduce_trace(trace_dir, span, device["platform"],
                                        timeline_path)
            result_device.update(busy_s=ctx["trace"]["busy_s"],
                                 window_s=ctx["trace"]["window_s"])
            result["breakdown"] = ctx["trace"]["breakdown"]

        group = "per_layer" if trace else "end_to_end"
        metrics = {}
        for m in bench[group]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            kind = "layer_metrics" if trace else "end_to_end"
            value = load_module(kind, m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        return {"correct": correct, "attempted": len(records),
                "failed": len(failed), "metrics": metrics,
                "device": result_device, **result,
                **({"rehearsal": True} if rehearsal else {})}
    finally:
        for cli in clients + ([admin] if admin else []):
            try:
                cli.close()
            except OSError:
                pass
        if server is not None:
            server.stop()
        eng.close()
        for d in (trace_dir, timeline_dir):
            if d:
                shutil.rmtree(d, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal-scale", type=float, default=None,
                    help="NOT for measuring: run at this scale on whatever "
                    "device JAX has (the CPU, in the tests); the result "
                    "line is marked as a rehearsal")
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except BenchFailed as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
