"""Benchmark: TPC-H-shaped queries, device engine vs the CPU vectorized
volcano baseline (BASELINE.json north-star ladder at SF=10).

Generates lineitem/orders/customer-shaped columns (the mockDataSource
pattern of the reference's executor/benchmark_test.go — no storage round
trip), loads them into the columnar region store, then times three query
shapes through the CPU pipeline and the fused TPU fragments:

  Q1  hash-agg over one table          (BASELINE config #2, headline)
  Q3  join + agg                       (BASELINE config #3)
  Q5  3-table join chain + agg         (BASELINE config #5 shape)

Prints ONE JSON line: value = device Q1 rows/sec, vs_baseline = speedup
over the CPU engine on this host. Extras carry Q3/Q5 numbers, exec-only
device seconds (device compute + transfers, no host decode/plan), and an
absolute host reference: the measured host memory stream bandwidth and the
implied Q1 roofline time (bytes touched / bandwidth) — the fastest ANY
host CPU engine could run Q1, making `vs_baseline` non-self-referential.

Methodology (pinned after the round-3 review flagged CPU-baseline
variance): every timing is BEST-OF-N wall seconds in one process on an
otherwise idle host — BENCH_REPS (default 2) device reps, BENCH_CPU_REPS
(default 2) CPU reps. The JSON carries every individual CPU rep
(q*_cpu_reps_s) plus the host's 1-minute load average sampled before
timing, so a perturbed run is visible in the artifact instead of
shifting a ratio silently. Q1/Q3/Q5 each get a bytes-touched roofline
(minimum column bytes streamed / measured host bandwidth): the fastest
ANY host CPU engine could answer, making every multiplier
non-self-referential rather than a ratio against this repo's own
single-threaded volcano.

Throughput: a "Concurrent serving" section runs a mixed repeated-Q1/Q3
warm workload at concurrency 1 and 8 through the device scheduler
(executor/scheduler.py) and reports qps_c1 / qps_c8 / qps_scaling plus
the scheduler's admission counters: 8 threads overlapping host
encode/decode with each other's device waits should scale where the device
round trip is latency-bound (what a round trip costs on the chip: not
measured).

A "Priority serving tier" section then mixes interactive point reads
against batch Q1 scans at concurrency 1/8/64 and reports per-class
p50/p99 plus the micro-batch coalescing rate, with a same-process
flag-off FIFO baseline at the top contention level: the PR's acceptance
claim is interactive p99 (classification on) ≤ interactive p99 (FIFO),
emitted as priority_serving.interactive_p99_improves.

A "Whole-query compilation coverage" section runs the 22 TPC-H-shaped
queries of tidb_tpu/tools/coverage.py against a fresh small-SF engine
and embeds the per-query table in the JSON (`coverage`: fused?,
fragment count, fallback-taxonomy reason, warm programs-per-slab,
vs-CPU speedup; `coverage_fused` = the suite-wide fused count that
tools/check_coverage.py ratchets against COVERAGE.json).

Env: BENCH_SF (default 10) scales row count (SF=1 → 6,001,215 lineitem
rows); BENCH_REPS / BENCH_CPU_REPS as above; BENCH_TIME_BUDGET_S
(default 840) is the wall-clock budget for the WHOLE run — when it runs
short the bench degrades (fewer CPU reps, then skipped secondary
queries, each flagged in the JSON) and a SIGALRM backstop emits the
partial JSON rather than dying silently inside a rep.

The bench measures the chip and nothing else: it fails at start-up when JAX
reports no TPU, a section that fails is recorded under `failed_sections` and
lets the later ones run, and a partial or failed run exits non-zero.
"""

from __future__ import annotations

import itertools
import json
import os
import signal
import sys
import threading
import time

import numpy as np

from tidb_tpu.tools.tpch_shaped import Q1, Q3, Q5, Q6, build_engine  # noqa: E402


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


# Every section pins the session row threshold through this one helper.
# Two regimes: the production default (32768 — only slabs past it route
# to the device) and force-device (threshold 1 — every eligible fragment
# takes the device path regardless of cardinality).  Force-device dates
# to PR 14's Q6 zone-map section: Q6's pruned scan can leave fewer live
# rows than the default threshold, silently bouncing the section back to
# the CPU path, so the bench pins threshold=1 wherever it is measuring
# the device path by name.  Temporary until the threshold is plan-shape
# aware instead of a single row count.
PRODUCTION_ROW_THRESHOLD = 32768


def set_row_threshold(ss, force_device: bool):
    ss.vars["tidb_tpu_row_threshold"] = \
        1 if force_device else PRODUCTION_ROW_THRESHOLD


def emit(value: float, vs: float, extra: dict | None = None):
    row = {
        "metric": "tpch_q1_hashagg_rows_per_sec",
        "value": round(value, 1),
        "unit": "rows/sec",
        "vs_baseline": round(vs, 3),
    }
    if extra:
        row.update(extra)
    print(json.dumps(row), flush=True)


# Partial-result state the SIGALRM backstop emits: extras accrue here as
# each section completes, and HEADLINE flips once the device Q1 timing
# lands — so a budget overrun mid-Q5 still reports the headline number.
EXTRA: dict = {}
HEADLINE = {"value": 0.0, "vs": 0.0}


def section_failed(name: str, e: BaseException):
    """A section that fails is recorded and lets the later ones run; the
    process still exits non-zero (see __main__)."""
    log(f"section {name} FAILED: {type(e).__name__}: {e}")
    EXTRA.setdefault("failed_sections", {})[name] = \
        f"{type(e).__name__}: {e}"[:200]


class BenchBudgetExceeded(Exception):
    """SIGALRM fired: the wall-clock budget ran out mid-section."""


def _on_alarm(signum, frame):
    raise BenchBudgetExceeded()


_DEADLINE: list = []


def bench_deadline() -> float:
    """Absolute epoch deadline for this bench invocation, fixed at the
    first call."""
    if not _DEADLINE:
        _DEADLINE.append(
            time.time() + float(os.environ.get("BENCH_TIME_BUDGET_S", "840")))
    return _DEADLINE[0]


def remaining_s() -> float:
    return bench_deadline() - time.time()


def probe_backend() -> str:
    """Initialize the JAX backend BEFORE any expensive work — datagen takes
    a while, and a bench that finds no chip fails here: there is no other
    backend a device number may come from."""
    from tidb_tpu.ops.jax_env import jax, jnp, on_tpu
    devs = jax.devices()
    if not on_tpu():
        raise RuntimeError(
            f"bench needs the chip: jax reports platform "
            f"{devs[0].platform!r} ({len(devs)} device(s))")
    float(jnp.ones(8).sum())    # force real device initialization
    log(f"jax backend ready: {jax.default_backend()} "
        f"({len(devs)} x {devs[0].device_kind})")
    return jax.default_backend()


def host_stream_gbs() -> float:
    """Measured host memory stream bandwidth (GB/s): sum-reduce a 1-GiB
    array, best of 7 after a warmup pass — the roofline any host CPU
    engine is bound by. Best-of-many because a transiently busy host
    (page cache churn, a sibling process) must not DEFLATE the roofline
    and flatter the `*_vs_roofline` ratios; captures this round varied
    3.5-8.3 GB/s under best-of-3."""
    a = np.ones(1 << 27, dtype=np.float64)      # 1 GiB
    a.sum()                                      # touch pages / warm
    best = float("inf")
    for _ in range(7):
        t0 = time.perf_counter()
        a.sum()
        best = min(best, time.perf_counter() - t0)
    return a.nbytes / best / 1e9


def time_query(s, reps: int, sql: str = Q1, reserve_s: float = 90.0):
    """→ (best wall seconds, device-exec seconds of the best run,
    [every rep's wall seconds]). Budget-aware: after each rep, if
    another rep of the same duration would eat into `reserve_s` of
    wall budget kept for the rest of the run, stop early — a truncated
    best-of-N (visible as len(walls) < reps in the artifact) beats an
    rc:124 with no JSON at all."""
    from tidb_tpu.executor import fragment as frag_mod
    best = float("inf")
    exec_s = 0.0
    walls = []
    for i in range(max(reps, 1)):
        frag_mod.LAST_DEVICE_EXEC_S = 0.0
        t0 = time.perf_counter()
        rs = s.query(sql)
        dt = time.perf_counter() - t0
        walls.append(round(dt, 3))
        if dt < best:
            best = dt
            exec_s = frag_mod.LAST_DEVICE_EXEC_S
        assert rs.rows, "query returned no rows"
        if i + 1 < max(reps, 1) and \
                remaining_s() - reserve_s < dt * 1.5:
            log(f"  rep budget: stopping after {i + 1}/{reps} reps "
                f"({remaining_s():.0f}s left)")
            break
    return best, exec_s, walls


def check_device_used(s, sql: str) -> bool:
    from tidb_tpu.executor import build as build_exec
    from tidb_tpu.executor import run_to_completion
    from tidb_tpu.executor.fragment import TpuFragmentExec
    from tidb_tpu.parser import parse
    plan = s._plan(parse(sql)[0])
    root = build_exec(plan)
    run_to_completion(root, s._exec_ctx())
    frags = []

    def walk(e):
        if isinstance(e, TpuFragmentExec):
            frags.append(e)
        for c in getattr(e, "children", []):
            walk(c)

    walk(root)
    for f in frags:
        if not f.used_device:
            log(f"  fragment fell back: {f.fallback_reason}")
    return bool(frags) and all(f.used_device for f in frags)


def run_mix(eng, conc: int, total: int, section_budget_s: float):
    """Mixed warm Q1/Q3 workload on `conc` sessions (one thread each,
    the wire server's threading model) pulling query indices from one
    shared counter — even index Q1, odd Q3. → (completed, wall seconds,
    scheduler stats over the window, [errors])."""
    from tidb_tpu.executor.scheduler import POOL
    sessions = []
    for _ in range(conc):
        ss = eng.new_session()
        ss.vars["tidb_tpu_engine"] = "on"
        set_row_threshold(ss, force_device=False)
        sessions.append(ss)
    counter = itertools.count()
    done = [0] * conc
    lat_s: list = [[] for _ in range(conc)]   # per-query wall seconds
    errors: list = []
    stop_at = time.monotonic() + section_budget_s

    def worker(k: int):
        ss = sessions[k]
        try:
            while True:
                i = next(counter)
                if i >= total or time.monotonic() > stop_at:
                    break
                q0 = time.perf_counter()
                rs = ss.query(Q1 if i % 2 == 0 else Q3)
                lat_s[k].append(time.perf_counter() - q0)
                assert rs.rows, "mix query returned no rows"
                done[k] += 1
        except Exception as e:  # noqa: BLE001 — reported in the JSON
            errors.append(f"{type(e).__name__}: {e}"[:200])

    POOL.reset_stats()
    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(k,), daemon=True)
               for k in range(conc)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    all_lat = sorted(x for per in lat_s for x in per)
    return sum(done), wall, POOL.stats(), errors, all_lat


def run_priority_mix(eng, conc: int, total: int, section_budget_s: float,
                     prio_on: bool):
    """Mixed-priority serving window: interactive point reads racing
    batch Q1 scans through the device scheduler, with classification on
    or off (off = the plain FIFO baseline). At conc == 1 a single thread
    interleaves 3 points : 1 scan; at conc > 1, conc//8 (min 1) threads
    loop scans and the rest serve points — same-digest probes, so queued
    bursts coalesce through the micro-batcher. → (completed, wall
    seconds, per-class latency lists, scheduler stats, micro-batch
    counter deltas, [errors])."""
    from tidb_tpu.executor.scheduler import POOL
    from tidb_tpu.util.observability import REGISTRY
    sessions = []
    for _ in range(conc):
        ss = eng.new_session()
        ss.vars["tidb_tpu_engine"] = "on"
        set_row_threshold(ss, force_device=True)
        ss.vars["tidb_tpu_priority_scheduling"] = \
            "on" if prio_on else "off"
        sessions.append(ss)
    counter = itertools.count()
    lat = {"interactive": [], "batch": []}
    lat_lock = threading.Lock()
    errors: list = []
    stop_at = time.monotonic() + section_budget_s
    n_batch = max(1, conc // 8) if conc > 1 else 0

    def worker(k: int):
        ss = sessions[k]
        scan_role = k < n_batch
        try:
            while True:
                i = next(counter)
                if i >= total or time.monotonic() > stop_at:
                    break
                cls = "batch" if (scan_role
                                  or (conc == 1 and i % 4 == 3)) \
                    else "interactive"
                sql = Q1 if cls == "batch" \
                    else f"SELECT v FROM pr WHERE k = {i % 1024}"
                q0 = time.perf_counter()
                rs = ss.query(sql)
                dt = time.perf_counter() - q0
                assert rs.rows, "priority mix query returned no rows"
                with lat_lock:
                    lat[cls].append(dt)
        except Exception as e:  # noqa: BLE001 — reported in the JSON
            errors.append(f"{type(e).__name__}: {e}"[:200])

    def mb():
        return (REGISTRY.counters.get(
                    ("tidb_tpu_microbatch_batches_total", ()), 0),
                REGISTRY.counters.get(
                    ("tidb_tpu_microbatch_members_total", ()), 0))

    POOL.reset_stats()
    b0, m0 = mb()
    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(k,), daemon=True)
               for k in range(conc)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    b1, m1 = mb()
    done = len(lat["interactive"]) + len(lat["batch"])
    return done, wall, lat, POOL.stats(), \
        {"batches": b1 - b0, "members": m1 - m0}, errors


def run_pod_mix(eng, conc: int, total: int, section_budget_s: float,
                device_queues: str):
    """The PR 15 interactive-vs-batch mix with each statement's LANDING
    device recorded — the pod-scale serving section's worker.
    `device_queues` pins `tidb_tpu_device_queues` (`off` = the
    single-scheduler same-process baseline, `on` = one queue per visible
    device with locality placement + work stealing). → (per-(device,
    class) latency lists, wall seconds, pool stats, [errors])."""
    from tidb_tpu.executor.scheduler import POOL
    sessions = []
    for _ in range(conc):
        ss = eng.new_session()
        ss.vars["tidb_tpu_engine"] = "on"
        set_row_threshold(ss, force_device=True)
        ss.vars["tidb_tpu_device_queues"] = device_queues
        sessions.append(ss)
    counter = itertools.count()
    dev_lat: dict = {}                 # (device, class) → [wall seconds]
    lat_lock = threading.Lock()
    errors: list = []
    stop_at = time.monotonic() + section_budget_s
    n_batch = max(1, conc // 8)

    def worker(k: int):
        ss = sessions[k]
        scan_role = k < n_batch
        try:
            while True:
                i = next(counter)
                if i >= total or time.monotonic() > stop_at:
                    break
                cls = "batch" if scan_role else "interactive"
                sql = Q1 if scan_role \
                    else f"SELECT v FROM pr WHERE k = {i % 1024}"
                q0 = time.perf_counter()
                rs = ss.query(sql)
                dt = time.perf_counter() - q0
                assert rs.rows, "pod mix query returned no rows"
                dev = getattr(ss.last_guard, "device_index", None) or 0
                with lat_lock:
                    dev_lat.setdefault((dev, cls), []).append(dt)
        except Exception as e:  # noqa: BLE001 — reported in the JSON
            errors.append(f"{type(e).__name__}: {e}"[:200])

    POOL.reset_stats()
    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(k,), daemon=True)
               for k in range(conc)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return dev_lat, wall, POOL.stats(), errors


def query_roofline_fraction(s, gbs: float) -> float:
    """Roofline fraction of the session's LAST statement: the HBM bytes
    its device program streamed (PhaseTimer scan_bytes) at the measured
    stream bandwidth, over the measured device wall — the fraction of
    the wall the pure memory floor explains (1.0 = bandwidth-bound)."""
    from tidb_tpu.util import roofline
    g = s.last_guard
    if g is None:
        return 0.0
    ph = g.phases
    return round(roofline.fraction(ph.scan_bytes, ph.wall_s, gbs=gbs), 4)


def latency_percentiles_ms(lat_s) -> dict:
    """Tail-latency summary of a sorted per-query wall list — p99 is the
    first-class serving metric (interactive/batch separation needs it),
    not derivable from throughput alone."""
    if not lat_s:
        return {"latency_p50_ms": 0.0, "latency_p95_ms": 0.0,
                "latency_p99_ms": 0.0}

    def pct(q):
        i = min(len(lat_s) - 1, int(q * (len(lat_s) - 1) + 0.5))
        return round(lat_s[i] * 1000.0, 2)

    return {"latency_p50_ms": pct(0.50), "latency_p95_ms": pct(0.95),
            "latency_p99_ms": pct(0.99)}


def main():
    sf = float(os.environ.get("BENCH_SF", "10"))
    reps = int(os.environ.get("BENCH_REPS", "2"))
    cpu_reps = int(os.environ.get("BENCH_CPU_REPS", "2"))
    n_rows = int(sf * 6_001_215)

    # arm the wall-clock backstop: if any single section overruns the
    # budget, SIGALRM lands and __main__ emits the partial JSON
    deadline = bench_deadline()
    if hasattr(signal, "SIGALRM"):
        signal.signal(signal.SIGALRM, _on_alarm)
        # fire 15s BEFORE the budget line: the partial-JSON emit and
        # interpreter teardown must finish inside the driver's window
        signal.alarm(max(int(deadline - time.time()) - 15, 1))

    # probe/initialize the backend FIRST — datagen takes a while and a dead
    # backend must be discovered (and retried/re-execed) before spending it
    backend_name = probe_backend()
    # opt-in cross-session Chrome trace for the whole bench run (QPS
    # storm included): start BEFORE warmup so cold compiles land in it
    trace_dir = os.environ.get("BENCH_TRACE_DIR")
    if trace_dir:
        from tidb_tpu.util import timeline
        extra_trace_path = timeline.start_global(trace_dir)
        log(f"chrome trace → {extra_trace_path}")
    try:
        # BEFORE datagen: the bench's own burn would dominate load1 and
        # hide a genuinely busy host
        load1 = round(os.getloadavg()[0], 2)
    except OSError:
        load1 = None
    gbs = host_stream_gbs()
    # the engine's per-query roofline fractions (EXPLAIN ANALYZE, bench
    # JSON) divide by the SAME measured bandwidth as the bench rooflines
    from tidb_tpu.util import roofline as roofline_mod
    roofline_mod.set_measured_gbs(gbs)
    # bytes-touched rooflines: the minimum column bytes any columnar CPU
    # engine must stream per query (host-width: 8B decimals/keys/codes,
    # 4B dates), over the measured bandwidth
    q1_bytes = n_rows * (4 * 8 + 2 * 8 + 4)
    # Q3: lineitem price+disc+shipdate+orderkey, orders key+date+prio
    q3_bytes = n_rows * (8 + 8 + 4 + 8) + (n_rows // 4) * (8 + 4 + 8)
    # Q5: lineitem price+disc+shipdate+orderkey, orders key+cust,
    # customer key+segment
    q5_bytes = n_rows * (8 + 8 + 4 + 8) + (n_rows // 4) * (8 + 8) + \
        (n_rows // 40) * (8 + 8)
    roofline_s = q1_bytes / (gbs * 1e9)
    join_roofline = {"q3": q3_bytes / (gbs * 1e9),
                     "q5": q5_bytes / (gbs * 1e9)}
    log(f"host stream bandwidth {gbs:.1f} GB/s; rooflines "
        f"Q1 {roofline_s:.2f}s Q3 {join_roofline['q3']:.2f}s "
        f"Q5 {join_roofline['q5']:.2f}s at SF={sf}")

    log(f"generating TPC-H-shaped data SF={sf} ({n_rows:,} lineitem rows)")
    eng, s = build_engine(n_rows)

    extra = EXTRA
    extra.update({"backend": backend_name, "scale_factor": sf,
                  "host_stream_gbs": round(gbs, 1),
                  "host_load1": load1,
                  "cpu_best_of": cpu_reps, "device_best_of": reps,
                  "q1_cpu_roofline_s": round(roofline_s, 3)})

    # CPU baseline (the reference-equivalent vectorized volcano engine).
    # The headline ratio needs at least ONE CPU rep; degrade rather than
    # skip when the budget is already short after datagen.
    q1_cpu_reps = cpu_reps
    if remaining_s() < 300.0 and cpu_reps > 1:
        q1_cpu_reps = 1
        extra["q1_cpu_reps_degraded"] = True
        log(f"budget short ({remaining_s():.0f}s left): Q1 CPU reps → 1")
    s.vars["tidb_tpu_engine"] = "off"
    log("timing CPU Q1…")
    cpu_t, _, cpu_walls = time_query(s, q1_cpu_reps)
    log(f"CPU engine Q1: best {cpu_t:.3f}s of {cpu_walls} "
        f"({n_rows / cpu_t / 1e6:.1f}M rows/s)")
    extra["q1_cpu_reps_s"] = cpu_walls

    # Device path (fused fragment)
    from tidb_tpu.executor import fragment as frag_mod
    s.vars["tidb_tpu_engine"] = "on"
    set_row_threshold(s, force_device=False)
    log("warming device path (compile + first-touch stream)…")
    q1_cold_t, _, _ = time_query(s, 1)
    # phase split of the COLD run — the one with real encode/upload work;
    # capture before check_device_used overwrites LAST_PHASES
    ph = frag_mod.LAST_PHASES
    if ph is not None:
        extra["q1_phases"] = {k: (round(v, 4) if isinstance(v, float) else v)
                              for k, v in ph.as_dict().items()}
        extra["q1_overlap_efficiency"] = round(ph.overlap_efficiency(), 3)
        log(f"Q1 cold phases: {ph.summary()}")
    used_device = check_device_used(s, Q1)
    log(f"device fragment active: {used_device}")
    dev_t, dev_exec, _ = time_query(s, reps)
    log(f"TPU engine Q1: {dev_t:.3f}s wall / {dev_exec:.3f}s exec "
        f"({n_rows / dev_t / 1e6:.1f}M rows/s)")
    extra.update({"device_fragment": used_device,
                  "cpu_rows_per_sec": round(n_rows / cpu_t, 1),
                  "q1_device_exec_s": round(dev_exec, 3),
                  "q1_vs_roofline": round(roofline_s / dev_t, 3),
                  "q1_roofline_fraction": query_roofline_fraction(s, gbs)})
    # warm/cold latency: the cold wall paid trace+stream once; the warm
    # ratio is what the compile + specialization caches buy a re-run
    if q1_cold_t > 0:
        extra["q1_warm_over_cold_latency_ratio"] = round(dev_t / q1_cold_t, 4)
    # fused launch accounting from the LAST warm rep — the whole-query
    # target is slabs + 1 programs (slab partials + ONE fused finalize),
    # i.e. programs_per_slab → ~1 as slab count grows
    q1ph = frag_mod.LAST_PHASES
    if q1ph is not None and q1ph.fused_pipelines:
        extra.update({
            "q1_fused_pipelines": q1ph.fused_pipelines,
            "q1_programs_launched": q1ph.programs_launched,
            "q1_programs_per_slab": round(
                q1ph.programs_launched / q1ph.fused_pipelines, 2)})
        log(f"q1 fused: {q1ph.fused_pipelines} slab programs, "
            f"{q1ph.programs_launched} launches warm "
            f"({extra['q1_programs_per_slab']}/slab)")
    # shard-recovery accounting (util/escalation.py): on a healthy run
    # all three stay 0 — nonzero values flag that the timing above
    # includes rank re-execution or a degraded mesh
    esc = s.last_guard.escalation if s.last_guard is not None else None
    if esc is not None:
        extra.update({"q1_shards_rerun": esc.shards_rerun,
                      "q1_shards_reused": esc.shards_reused,
                      "q1_degraded_mesh": esc.degraded_mesh})
    HEADLINE["value"] = n_rows / dev_t
    HEADLINE["vs"] = cpu_t / dev_t

    # ---- compressed layouts: bytes saved + first-touch A/B ----------------
    # The cold Q1 ledger above ran with compressed layouts (the default):
    # its logical/physical byte pair IS the bytes-saved figure. The A/B
    # re-touches the table raw (compression off invalidates the cache
    # entry) and then compressed again, so both first-touch walls and
    # both PCIe byte totals come from the same warm process.
    try:
        if ph is not None and ph.h2d_logical_bytes > ph.h2d_bytes:
            extra["q1_bytes_saved"] = ph.h2d_logical_bytes - ph.h2d_bytes
        log("compression A/B: raw first touch…")
        s.vars["tidb_tpu_compression"] = "off"
        raw_touch_t, _, _ = time_query(s, 1, reserve_s=60.0)
        ph_raw = frag_mod.LAST_PHASES
        log("compression A/B: compressed first touch…")
        s.vars["tidb_tpu_compression"] = "on"
        comp_touch_t, _, _ = time_query(s, 1, reserve_s=60.0)
        ph_comp = frag_mod.LAST_PHASES
        if ph_raw is not None and ph_comp is not None and \
                ph_raw.h2d_bytes and ph_comp.h2d_bytes:
            red = ph_raw.h2d_bytes / ph_comp.h2d_bytes
            extra.update({
                "q1_first_touch_raw_s": round(raw_touch_t, 3),
                "q1_first_touch_compressed_s": round(comp_touch_t, 3),
                "q1_h2d_bytes_raw": ph_raw.h2d_bytes,
                "q1_h2d_bytes_compressed": ph_comp.h2d_bytes,
                "q1_h2d_reduction_x": round(red, 2),
                "q1_bytes_saved": ph_raw.h2d_bytes - ph_comp.h2d_bytes,
            })
            log(f"compression: h2d {ph_raw.h2d_bytes}B raw → "
                f"{ph_comp.h2d_bytes}B compressed ({red:.1f}x less PCIe), "
                f"first touch {raw_touch_t:.3f}s → {comp_touch_t:.3f}s")
    except BenchBudgetExceeded:
        raise
    except Exception as e:
        section_failed("compression_ab", e)

    # ---- zone-map slab skipping: selective Q6-style scan ------------------
    # lineitem is shipdate-clustered, so the per-slab zone maps partition
    # the date range: the one-year predicate proves most slabs empty
    # HOST-side and the warm scan dispatches only the survivors — no
    # H2D, no launch for the rest. effective_roofline_fraction divides
    # the LOGICAL scan bytes (pruned slabs included: they were answered
    # without being read) by the measured wall, so a figure above 1.0 is
    # the pruning win made visible against the physical-stream floor.
    try:
        log("zone-map skip: warming selective Q6…")
        # Q6's post-filter cardinality sits under the serving threshold —
        # exactly the query shape pruning exists for, so force the device
        # path for this section (the per-statement guard's phases, not
        # the module-global LAST_PHASES, meter it: a CPU fallback would
        # leave wall_s at 0 and be visible as q6_device=False)
        set_row_threshold(s, force_device=True)
        time_query(s, 1, Q6, reserve_s=60.0)
        # upload-avoided bytes are a FIRST-touch artifact (warm slabs are
        # already resident or holes) — read them off the warming run
        ph6c = s.last_guard.phases if s.last_guard is not None else None
        h2d_skip6 = ph6c.h2d_skipped_bytes if ph6c is not None else 0
        q6_t, _, _ = time_query(s, 1, Q6, reserve_s=60.0)
        ph6 = s.last_guard.phases if s.last_guard is not None else None
        if ph6 is not None:
            ef6 = roofline_mod.effective_fraction(
                ph6.scan_logical_bytes, ph6.wall_s)
            extra.update({
                "q6_warm_s": round(q6_t, 3),
                "q6_device": ph6.wall_s > 0.0,
                "q6_slabs_skipped": ph6.slabs_skipped,
                "q6_h2d_skipped_bytes": h2d_skip6,
                # warm re-upload ledger: MUST be 0 — pruned or resident,
                # no slab crosses PCIe on a warm repeat
                "q6_warm_h2d_bytes": ph6.h2d_bytes,
                "q6_programs_launched": ph6.programs_launched,
                "q6_effective_roofline_fraction": round(ef6, 4),
            })
            log(f"q6 warm {q6_t:.3f}s: {ph6.slabs_skipped} slabs skipped, "
                f"{h2d_skip6}B upload avoided, "
                f"{ph6.programs_launched} launches, "
                f"effective roofline {ef6:.2f}x")
    except BenchBudgetExceeded:
        raise
    except Exception as e:
        section_failed("q6", e)
    finally:
        set_row_threshold(s, force_device=False)

    # ---- concurrent serving: warm mixed Q1/Q3 throughput ------------------
    # concurrency 1 vs 8 through the device scheduler. Runs right after
    # the Q1 device section so qps_c1/qps_c8 land even if a later join
    # section dies; budget-degraded totals shrink rather than skip — the
    # fields must always be in the artifact. Q3 is compile-warmed first
    # so the mix measures serving, not tracing.
    try:
        log("concurrent serving: warming Q3 device path…")
        time_query(s, 1, Q3, reserve_s=60.0)
        q3_warm, _, _ = time_query(s, 1, Q3, reserve_s=60.0)
        per_pair = max(dev_t + q3_warm, 1e-3)
        section_s = max(10.0, min(90.0, remaining_s() * 0.2))
        total = int(max(16, min(96, 2 * section_s / per_pair)))
        log(f"concurrent serving: {total} queries per level, "
            f"~{section_s:.0f}s budget per level")
        n1, w1, _, err1, lat1 = run_mix(eng, 1, total, section_s)
        n8, w8, sched, err8, lat8 = run_mix(eng, 8, total, section_s)
        qps_c1 = n1 / w1 if w1 > 0 and n1 else 0.0
        qps_c8 = n8 / w8 if w8 > 0 and n8 else 0.0
        scaling = qps_c8 / qps_c1 if qps_c1 else 0.0
        p1, p8 = latency_percentiles_ms(lat1), latency_percentiles_ms(lat8)
        log(f"latency c1 p50/p95/p99 {p1['latency_p50_ms']}/"
            f"{p1['latency_p95_ms']}/{p1['latency_p99_ms']}ms, c8 "
            f"{p8['latency_p50_ms']}/{p8['latency_p95_ms']}/"
            f"{p8['latency_p99_ms']}ms")
        extra.update({
            "qps_c1": round(qps_c1, 2), "qps_c8": round(qps_c8, 2),
            "qps_latency_c1": p1, "qps_latency_c8": p8,
            "qps_scaling": round(scaling, 3),
            # fraction of perfect linear scaling achieved at c8: how
            # much of the 8 threads' host work overlapped device time
            "qps_overlap_efficiency": round(scaling / 8.0, 3),
            "qps_queries": {"c1": n1, "c8": n8, "target": total},
            "qps_scheduler": sched})
        if err1 or err8:
            extra["qps_errors"] = (err1 + err8)[:4]
        log(f"concurrent serving: c1 {qps_c1:.2f} qps ({n1} in "
            f"{w1:.1f}s), c8 {qps_c8:.2f} qps ({n8} in {w8:.1f}s), "
            f"scaling {scaling:.2f}x, scheduler {sched}")
    except Exception as e:  # noqa: BLE001 — later sections still run
        section_failed("qps", e)
        extra.update({"qps_c1": 0.0, "qps_c8": 0.0})

    # ---- priority serving tier: per-class tails + micro-batching ----------
    # interactive point reads vs batch Q1 scans at c1/c8/c64, then the
    # same contention with classification OFF (plain FIFO) in the same
    # process: the acceptance claim is that strict priority + coalescing
    # keeps interactive p99 at or under the FIFO baseline's.
    try:
        left = remaining_s()
        if left < 75.0:
            raise RuntimeError(f"{left:.0f}s left in wall budget")
        log("priority serving tier: warming point-read path…")
        set_row_threshold(s, force_device=True)
        s.query("SELECT v FROM pr WHERE k = 17")   # parametrized compile
        level_s = max(6.0, min(30.0, remaining_s() * 0.06))
        prio: dict = {}
        for conc in (1, 8, 64):
            done, wall, lat, sched, mbd, errs = run_priority_mix(
                eng, conc, 100000, level_s, prio_on=True)
            pts = len(lat["interactive"])
            prio[f"c{conc}"] = {
                "qps": round(done / wall, 2) if wall > 0 and done else 0.0,
                "interactive": latency_percentiles_ms(
                    sorted(lat["interactive"])),
                "batch": latency_percentiles_ms(sorted(lat["batch"])),
                "queries": {"interactive": pts, "batch": len(lat["batch"])},
                # fraction of point reads served through a micro-batch
                # (coalesced members / point queries)
                "microbatch_rate": round(mbd["members"] / pts, 4)
                if pts else 0.0,
                "microbatch": mbd,
                "scheduler": sched}
            if errs:
                prio[f"c{conc}"]["errors"] = errs[:4]
            log(f"priority c{conc}: {prio[f'c{conc}']['qps']} qps, "
                f"interactive p99 "
                f"{prio[f'c{conc}']['interactive']['latency_p99_ms']}ms, "
                f"batch p99 "
                f"{prio[f'c{conc}']['batch']['latency_p99_ms']}ms, "
                f"mb rate {prio[f'c{conc}']['microbatch_rate']}")
        done0, wall0, lat0, sched0, mbd0, errs0 = run_priority_mix(
            eng, 64, 100000, level_s, prio_on=False)
        base = latency_percentiles_ms(sorted(lat0["interactive"]))
        prio["fifo_baseline_c64"] = {
            "qps": round(done0 / wall0, 2) if wall0 > 0 and done0 else 0.0,
            "interactive": base,
            "batch": latency_percentiles_ms(sorted(lat0["batch"])),
            "microbatch": mbd0}
        # acceptance: interactive tails (classification on) at or under
        # the FIFO baseline's. On a single-core CPU host the batched
        # vmap program serializes (a 16-wide batch costs ~16 solo
        # launches), so coalescing can inflate p99 there while p50
        # still shows the priority win; both land in the artifact.
        on_i = prio["c64"]["interactive"]
        prio["interactive_p50_improves"] = \
            bool(on_i["latency_p50_ms"] <= base["latency_p50_ms"])
        prio["interactive_p99_improves"] = \
            bool(on_i["latency_p99_ms"] <= base["latency_p99_ms"])
        if not prio["interactive_p99_improves"]:
            log(f"WARNING: interactive p99 {on_i['latency_p99_ms']}ms "
                f"did not beat the FIFO baseline "
                f"{base['latency_p99_ms']}ms "
                f"(p50 {on_i['latency_p50_ms']}ms vs "
                f"{base['latency_p50_ms']}ms)")
        else:
            log(f"priority tier: interactive p99 "
                f"{on_i['latency_p99_ms']}ms vs FIFO "
                f"{base['latency_p99_ms']}ms — acceptance holds")
        extra["priority_serving"] = prio
    except Exception as e:  # noqa: BLE001 — later sections still run
        section_failed("priority_serving", e)
    finally:
        set_row_threshold(s, force_device=False)

    # ---- pod-scale serving: per-device queues, locality, stealing ---------
    # the PR 15 c64 mix twice in the SAME process: device_queues off
    # (every statement through one scheduler/one device) vs on (a queue
    # per visible device, locality placement, replication, work
    # stealing). qps_scaling_x is the pod speedup; the >= 4x acceptance
    # gate only arms on a real multi-device backend — on the forced
    # multi-device CPU mesh the GIL serializes every dispatch, so the
    # ratio is informational there.
    try:
        left = remaining_s()
        if left < 60.0:
            raise RuntimeError(f"{left:.0f}s left in wall budget")
        import jax
        from tidb_tpu.executor import device_cache as _dcache
        n_dev = jax.local_device_count()
        platform = jax.devices()[0].platform
        log(f"pod serving: {n_dev} visible {platform} device(s)")
        set_row_threshold(s, force_device=True)
        s.query("SELECT v FROM pr WHERE k = 17")   # warm the point path
        level_s = max(6.0, min(30.0, remaining_s() * 0.08))
        lat_off, w_off, sched_off, err_off = run_pod_mix(
            eng, 64, 100000, level_s, "off")
        lat_on, w_on, sched_on, err_on = run_pod_mix(
            eng, 64, 100000, level_s, "on")
        done_off = sum(len(v) for v in lat_off.values())
        done_on = sum(len(v) for v in lat_on.values())
        qps_off = done_off / w_off if w_off > 0 and done_off else 0.0
        qps_on = done_on / w_on if w_on > 0 and done_on else 0.0
        scaling = qps_on / qps_off if qps_off else 0.0
        per_device: dict = {}
        for (dev, cls), lats in sorted(lat_on.items()):
            per_device.setdefault(f"device{dev}", {})[cls] = \
                latency_percentiles_ms(sorted(lats))
        pod = {
            "devices": n_dev, "platform": platform,
            "qps_1dev": round(qps_off, 2), "qps_pod": round(qps_on, 2),
            "qps_scaling_x": round(scaling, 3),
            "per_device": per_device,
            "work_steals": sched_on["steals"],
            "replica_hbm_overhead_bytes":
                _dcache.replica_overhead_bytes(),
            "queries": {"off": done_off, "on": done_on},
            "scheduler": sched_on}
        if err_off or err_on:
            pod["errors"] = (err_off + err_on)[:4]
        # degraded rep (robustness numbers): arm a ONE-SHOT
        # device-lost-dispatch fault and run the mix again — the first
        # dispatched statement loses its device, the pool quarantines
        # it (queued waiters migrate, its cache shard re-homes) and the
        # mix keeps serving on survivors until the flap-guard readmits.
        # pod_degraded_qps = qps with the loss AND the recovery inside
        # the window; pod_recovery_s = quarantine→readmission wall
        # (a sidecar thread samples the health monitor);
        # statements_migrated = queue-drain + in-flight handoffs. A
        # 1-device host grows the pool to two host-side queues first
        # (the chaos sweep's trick) so the fault domain still
        # exercises — informational there, like qps_scaling_x.
        from tidb_tpu.executor.scheduler import POOL
        from tidb_tpu.util import failpoint as _fp
        from tidb_tpu.util.observability import REGISTRY as _reg

        def _migrated():
            return sum(v for (n, _l), v in _reg.counters.items()
                       if n == "tidb_tpu_statements_migrated_total")

        POOL.ensure(2)
        mig0 = _migrated()
        hb = {"fault": None, "heal": None}
        hb_stop = threading.Event()

        def _health_watch():
            while not hb_stop.is_set():
                q = POOL.health.quarantined_indexes()
                if q and hb["fault"] is None:
                    hb["fault"] = time.monotonic()
                elif hb["fault"] is not None and not q:
                    hb["heal"] = time.monotonic()
                    return
                time.sleep(0.005)

        wt = threading.Thread(target=_health_watch, daemon=True)
        _fp.enable("device-lost-dispatch",
                   raise_=RuntimeError("bench: device lost"), times=1)
        try:
            wt.start()
            lat_deg, w_deg, _sched_deg, err_deg = run_pod_mix(
                eng, 64, 100000, level_s, "on")
            # the mix usually heals in-window (25ms flap delay); give a
            # quarantine that outlived it a placement-driven grace loop
            ps = eng.new_session()
            ps.vars["tidb_tpu_engine"] = "on"
            set_row_threshold(ps, force_device=True)
            ps.vars["tidb_tpu_device_queues"] = "on"
            t_grace = time.monotonic()
            while hb["fault"] is not None and hb["heal"] is None and \
                    time.monotonic() - t_grace < 5.0:
                ps.query("SELECT v FROM pr WHERE k = 17")
                time.sleep(0.02)
        finally:
            _fp.disable("device-lost-dispatch")
            hb_stop.set()
            wt.join(1.0)
        done_deg = sum(len(v) for v in lat_deg.values())
        qps_deg = done_deg / w_deg if w_deg > 0 and done_deg else 0.0
        pod["pod_degraded_qps"] = round(qps_deg, 2)
        pod["pod_recovery_s"] = \
            round(hb["heal"] - hb["fault"], 3) \
            if hb["heal"] is not None and hb["fault"] is not None else None
        pod["statements_migrated"] = _migrated() - mig0
        if err_deg:
            pod.setdefault("errors", []).extend(err_deg[:2])
        log(f"pod degraded: {qps_deg:.2f} qps during loss, recovery "
            f"{pod['pod_recovery_s']}s, migrated "
            f"{pod['statements_migrated']}")
        gate = platform != "cpu" and n_dev > 1
        pod["scaling_gate_armed"] = gate
        extra["pod_serving"] = pod
        log(f"pod serving: 1dev {qps_off:.2f} qps, pod {qps_on:.2f} "
            f"qps, scaling {scaling:.2f}x, steals "
            f"{sched_on['steals']}, replica overhead "
            f"{pod['replica_hbm_overhead_bytes']}B")
        if gate:
            assert scaling >= 4.0, \
                f"pod qps_scaling_x {scaling:.2f} < 4 on {n_dev}-device " \
                f"{platform} mesh"
    except AssertionError:
        raise                              # acceptance gate must FAIL loud
    except Exception as e:  # noqa: BLE001 — later sections still run
        section_failed("pod_serving", e)
    finally:
        set_row_threshold(s, force_device=False)

    # secondary metrics: Q3 join and Q5 3-table join (configs #3/#5) —
    # each checks the wall budget first: skip entirely under ~90s left,
    # degrade to 1 CPU rep under ~240s, flagging either in the JSON so
    # the artifact says WHY a field is missing or noisier than usual
    for name, sql in (("q3", Q3), ("q5", Q5)):
        left = remaining_s()
        if left < 90.0:
            log(f"{name} skipped: {left:.0f}s left in wall budget")
            extra[f"{name}_skipped_budget"] = True
            continue
        q_cpu_reps = cpu_reps
        if left < 240.0 and cpu_reps > 1:
            q_cpu_reps = 1
            extra[f"{name}_cpu_reps_degraded"] = True
            log(f"budget short ({left:.0f}s left): {name} CPU reps → 1")
        try:
            s.vars["tidb_tpu_engine"] = "off"
            c_t, _, c_walls = time_query(s, q_cpu_reps, sql)
            s.vars["tidb_tpu_engine"] = "on"
            cc0 = dict(frag_mod.COMPILE_COUNTS)
            cold_t, _, _ = time_query(s, 1, sql)   # compile warmup
            used = check_device_used(s, sql)
            d_t, d_exec, _ = time_query(s, reps, sql)
            # per-kind compile split for this query's cold trace: a fused
            # pipeline shows {"fused": …} here, a mega-slab fallback
            # shows {"tree": …} — the warm reps above must add ZERO
            cc_delta = {k: v - cc0.get(k, 0)
                        for k, v in frag_mod.COMPILE_COUNTS.items()
                        if v > cc0.get(k, 0)}
            rl = join_roofline[name]
            log(f"{name.upper()} join: CPU best {c_t:.3f}s of {c_walls}, "
                f"TPU {d_t:.3f}s wall / {d_exec:.3f}s exec "
                f"({c_t / d_t:.1f}x CPU, {rl / d_t:.2f}x roofline, "
                f"device={used})")
            extra.update({
                f"{name}_join_rows_per_sec": round(n_rows / d_t, 1),
                f"{name}_vs_cpu": round(c_t / d_t, 3),
                f"{name}_device_exec_s": round(d_exec, 3),
                f"{name}_device_fragment": used,
                f"{name}_cpu_s": round(c_t, 3),
                f"{name}_cpu_reps_s": c_walls,
                f"{name}_cpu_roofline_s": round(rl, 3),
                f"{name}_vs_roofline": round(rl / d_t, 3),
                f"{name}_roofline_fraction":
                    query_roofline_fraction(s, gbs),
                f"{name}_compiles": cc_delta})
            if cold_t > 0:
                extra[f"{name}_warm_over_cold_latency_ratio"] = round(
                    d_t / cold_t, 4)
            # fused-pipeline launch accounting from the LAST warm rep:
            # programs_per_slab = (slab partials + the ONE fused
            # finalize that replaced the root merge) / slabs — the warm
            # whole-query target is slabs + 1 programs total
            qph = frag_mod.LAST_PHASES
            if qph is not None and qph.fused_pipelines:
                extra.update({
                    f"{name}_fused_pipelines": qph.fused_pipelines,
                    f"{name}_programs_launched": qph.programs_launched,
                    f"{name}_programs_per_slab": round(
                        qph.programs_launched / qph.fused_pipelines, 2)})
                log(f"{name} fused: {qph.fused_pipelines} slab programs, "
                    f"{qph.programs_launched} launches warm "
                    f"({extra[f'{name}_programs_per_slab']}/slab)")
        except Exception as e:  # noqa: BLE001 — later sections still run
            section_failed(name, e)

    # ---- mesh Q3: distributed join + staged-exchange recovery -------------
    # Q3 again, but as a DISTRIBUTED join over every visible device: the
    # staged exchange partitions each rank's rows, checkpoints the bucket
    # buffers device→host, routes them, and probes per rank. The JSON
    # carries the per-shard recovery counters (both 0 on a healthy run)
    # and one chaos-injected rep that must produce either the clean
    # result or a typed error within the deadline — never a hang, never
    # silent truncation.
    try:
        import jax as _jax
        mesh_n = min(8, len(_jax.devices()))
        left = remaining_s()
        if mesh_n < 2:
            log(f"mesh Q3 skipped: {mesh_n} device(s) visible")
            extra["q3_mesh_skipped_devices"] = mesh_n
        elif left < 90.0:
            log(f"mesh Q3 skipped: {left:.0f}s left in wall budget")
            extra["q3_mesh_skipped_budget"] = True
        else:
            from tidb_tpu.errors import ShardFailure, TiDBTPUError
            from tidb_tpu.util import failpoint
            saved_mesh = {k: s.vars.get(k) for k in
                          ("tidb_tpu_dist_devices",
                           "tidb_tpu_row_threshold")}
            s.vars["tidb_tpu_engine"] = "on"
            set_row_threshold(s, force_device=True)
            s.vars["tidb_tpu_dist_devices"] = mesh_n
            try:
                clean_rows = s.query(Q3).rows      # compile warmup
                m_t, _, _ = time_query(s, 1, Q3, reserve_s=60.0)
                esc = s.last_guard.escalation \
                    if s.last_guard is not None else None
                extra.update({
                    "q3_mesh_devices": mesh_n,
                    "q3_mesh_wall_s": round(m_t, 3),
                    "q3_mesh_shards_rerun":
                        esc.shards_rerun if esc else 0,
                    "q3_mesh_degraded":
                        esc.degraded_mesh if esc else 0})
                log(f"mesh Q3: {m_t:.3f}s over {mesh_n} ranks "
                    f"(shards_rerun={extra['q3_mesh_shards_rerun']} "
                    f"degraded={extra['q3_mesh_degraded']})")
                # chaos rep: one rank's device fails its dispatch AND the
                # same-device retry — the run must heal onto a surviving
                # device (re-running ONLY that rank) or surface a typed
                # error, inside the deadline
                t0 = time.monotonic()
                with failpoint.enabled(
                        "shard-step",
                        raise_=ShardFailure("bench chaos: device bad"),
                        times=2):
                    try:
                        chaos_rows = s.query(Q3).rows
                        chaos_err = None
                    except TiDBTPUError as e:
                        chaos_rows, chaos_err = None, e
                chaos_dt = time.monotonic() - t0
                esc = s.last_guard.escalation \
                    if s.last_guard is not None else None
                ok = chaos_dt <= 30.0 and (
                    chaos_err is not None or chaos_rows == clean_rows)
                extra.update({
                    "q3_mesh_chaos_wall_s": round(chaos_dt, 3),
                    "q3_mesh_chaos_ok": ok,
                    "q3_mesh_chaos_typed_error":
                        type(chaos_err).__name__ if chaos_err else None,
                    "q3_mesh_chaos_shards_rerun":
                        esc.shards_rerun if esc else 0,
                    "q3_mesh_chaos_degraded":
                        esc.degraded_mesh if esc else 0})
                if not ok:
                    raise RuntimeError(
                        f"mesh Q3 chaos rep violated the lifecycle "
                        f"contract: wall {chaos_dt:.1f}s, "
                        f"rows_match={chaos_rows == clean_rows}")
                log(f"mesh Q3 chaos rep: {chaos_dt:.3f}s, "
                    f"{'typed ' + type(chaos_err).__name__ if chaos_err else 'healed to clean rows'} "
                    f"(shards_rerun="
                    f"{extra['q3_mesh_chaos_shards_rerun']} degraded="
                    f"{extra['q3_mesh_chaos_degraded']})")
            finally:
                failpoint.disable_all()
                for k, v in saved_mesh.items():
                    if v is None:
                        s.vars.pop(k, None)
                    else:
                        s.vars[k] = v
    except Exception as e:  # noqa: BLE001 — later sections still run
        section_failed("q3_mesh", e)

    # ---- HTAP ingest: single-row writes streaming under Q1/Q6 reads -------
    # The crash-consistent write path under its intended load: writer
    # sessions stream autocommit single-row INSERTs (coalesced behind the
    # per-table commit gate into shared delta-appends) while reader
    # sessions keep answering warm Q1/Q6 over the growing base∪delta
    # view. The JSON carries the ingest rate, the coalescing ratio
    # (members per committed batch), read tail latency DURING ingest,
    # delta extensions and compactions folded, and an exactly-once count
    # probe. One fault-injected rep then arms a retryable fault at the
    # `delta-append` boundary and must HEAL: the in-gate retry lands the
    # row exactly once.
    try:
        from tidb_tpu.util import failpoint
        left = remaining_s()
        if left < 90.0:
            log(f"HTAP ingest skipped: {left:.0f}s left in wall budget")
            extra["htap_skipped_budget"] = True
        else:
            from tidb_tpu.errors import TxnError
            from tidb_tpu.executor import delta as delta_mod
            from tidb_tpu.util.observability import REGISTRY

            def ctr(name: str) -> float:
                return sum(v for (n, _l), v in REGISTRY.counters.items()
                           if n == name)

            def store_count(where: str) -> int:
                s.vars["tidb_tpu_engine"] = "off"
                try:
                    return s.query("SELECT COUNT(*) FROM lineitem "
                                   f"WHERE {where}").rows[0][0]
                finally:
                    s.vars["tidb_tpu_engine"] = "on"

            s.vars["tidb_tpu_engine"] = "on"
            set_row_threshold(s, force_device=False)
            clean_q1 = s.query(Q1).rows         # warm both read shapes
            s.query(Q6)
            base_ctr = {k: ctr(k) for k in (
                "tidb_tpu_write_batches_total",
                "tidb_tpu_write_members_total",
                "tidb_tpu_delta_extensions_total",
                "tidb_tpu_compactions_total")}
            # appended rows: shipdate '1998-12-29' sits at the TOP of the
            # generated range, so both FoR-bounded and monotonic
            # (delta-kind) base layouts accept the append, and Q1/Q6's
            # date windows exclude it — reader results stay byte-stable
            # while every read still crosses the delta merge
            okey0 = 1 << 40
            seq = itertools.count()
            ingest_s = 8.0 if left > 240.0 else 4.0
            n_writers, n_readers = 4, 2
            stop_at = time.monotonic() + ingest_s
            written = [0] * n_writers
            read_lat: list = [[] for _ in range(n_readers)]
            htap_errors: list = []

            def htap_writer(k: int):
                ws = eng.new_session()
                try:
                    while time.monotonic() < stop_at:
                        ws.query(
                            "INSERT INTO lineitem VALUES (25.00, "
                            "50000.00, 0.06, 0.04, 'N', 'F', "
                            f"'1998-12-29', {okey0 + next(seq)})")
                        written[k] += 1
                except Exception as e:  # noqa: BLE001 — in the JSON
                    htap_errors.append(
                        f"writer: {type(e).__name__}: {e}"[:200])

            def htap_reader(k: int):
                rs_ = eng.new_session()
                rs_.vars["tidb_tpu_engine"] = "on"
                set_row_threshold(rs_, force_device=False)
                # a low fold threshold so compaction demonstrably fires
                # inside the ingest window
                rs_.vars["tidb_tpu_delta_compact_rows"] = 256
                j = k
                try:
                    while time.monotonic() < stop_at:
                        q0 = time.perf_counter()
                        rows = rs_.query(Q1 if j % 2 == 0 else Q6).rows
                        read_lat[k].append(time.perf_counter() - q0)
                        if j % 2 == 0 and rows != clean_q1:
                            raise RuntimeError(
                                "Q1 drifted during ingest: the appended "
                                "rows are outside its date window")
                        j += 1
                except Exception as e:  # noqa: BLE001 — in the JSON
                    htap_errors.append(
                        f"reader: {type(e).__name__}: {e}"[:200])

            t0 = time.perf_counter()
            threads = [threading.Thread(target=htap_writer, args=(k,),
                                        daemon=True)
                       for k in range(n_writers)]
            threads += [threading.Thread(target=htap_reader, args=(k,),
                                         daemon=True)
                        for k in range(n_readers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            compact_sync = delta_mod.run_pending_compactions()
            total = sum(written)
            landed = store_count(f"l_orderkey >= {okey0}")
            batches = ctr("tidb_tpu_write_batches_total") - \
                base_ctr["tidb_tpu_write_batches_total"]
            members = ctr("tidb_tpu_write_members_total") - \
                base_ctr["tidb_tpu_write_members_total"]
            lat = sorted(x for per in read_lat for x in per)
            pct = latency_percentiles_ms(lat)
            extra.update({
                "htap_ingest_rows": total,
                "htap_ingest_rows_per_s": round(total / wall, 1),
                "htap_write_batches": int(batches),
                "htap_coalesce_members_per_batch":
                    round(members / batches, 2) if batches else 0.0,
                "htap_reads": len(lat),
                "htap_read_p50_ms": pct["latency_p50_ms"],
                "htap_read_p99_ms": pct["latency_p99_ms"],
                "htap_delta_extensions": int(
                    ctr("tidb_tpu_delta_extensions_total")
                    - base_ctr["tidb_tpu_delta_extensions_total"]),
                # the counter covers both the async worker's folds and
                # the final sync drain (compact_sync of them)
                "htap_compactions": int(
                    ctr("tidb_tpu_compactions_total")
                    - base_ctr["tidb_tpu_compactions_total"]),
                "htap_compactions_drained": compact_sync,
                "htap_write_atomic": landed == total,
                "htap_errors": htap_errors[:5]})
            log(f"HTAP ingest: {total} rows in {wall:.1f}s "
                f"({extra['htap_ingest_rows_per_s']}/s, "
                f"{extra['htap_coalesce_members_per_batch']} members/"
                f"batch), {len(lat)} reads p99 "
                f"{extra['htap_read_p99_ms']}ms, "
                f"{extra['htap_delta_extensions']} extensions, "
                f"{extra['htap_compactions']} compactions")
            if htap_errors or landed != total:
                raise RuntimeError(
                    f"HTAP ingest violated exactly-once: wrote {total}, "
                    f"store has {landed}; errors={htap_errors[:3]}")
            # chaos rep: a transient fault at the delta-append boundary —
            # the coalesced commit's in-gate retry must land the row
            # exactly once, never torn, never doubled
            fault = TxnError("bench chaos: delta append transient")
            fault.retryable = True
            probe_key = okey0 + next(seq)
            with failpoint.enabled("delta-append", raise_=fault,
                                   times=2), \
                    failpoint.enabled("backoff-sleep", value="skip"):
                rs = s.query("INSERT INTO lineitem VALUES (25.00, "
                             "50000.00, 0.06, 0.04, 'N', 'F', "
                             f"'1998-12-29', {probe_key})")
            heal_ok = rs.affected_rows == 1 and \
                store_count(f"l_orderkey = {probe_key}") == 1
            extra["htap_fault_heal_ok"] = heal_ok
            if not heal_ok:
                raise RuntimeError(
                    "HTAP chaos rep did not heal: the retryable "
                    "delta-append fault must commit exactly once")
            log("HTAP chaos rep: retryable delta-append fault healed, "
                "row landed exactly once")
    except Exception as e:  # noqa: BLE001 — later sections still run
        section_failed("htap", e)
    finally:
        from tidb_tpu.util import failpoint
        failpoint.disable_all()

    # ---- Whole-query compilation coverage: 22 TPC-H-shaped queries --------
    # The coverage ratchet's sweep surfaced in the bench JSON: a fresh
    # small-SF engine runs tidb_tpu.tools.coverage's 22 queries and the
    # table lands in the log plus per-query rows in the JSON — fused?,
    # fragment count, fallback reason (the tidb_tpu_device_fallbacks_total
    # taxonomy), programs per slab, speedup vs the CPU path.
    # tools/check_coverage.py pins the same sweep against COVERAGE.json
    # as a chaos-sweep preflight; here it also times the CPU side.
    try:
        left = remaining_s()
        if left < 60.0:
            log(f"coverage sweep skipped: {left:.0f}s left < 60s")
            extra["coverage_skipped"] = True
        else:
            from tidb_tpu.tools import coverage as cov
            _ceng, cs = cov.fresh_session(6000)
            cov_rows = cov.run_coverage(cs, time_cpu=True)
            log(cov.coverage_table(cov_rows))
            extra["coverage"] = {
                r["query"]: {
                    "fused": r["fused"],
                    "fragments": r["n_fragments"],
                    "fallback": r["fallback"],
                    "programs_per_slab": r["programs_per_slab"],
                    "speedup": r["speedup"],
                } for r in cov_rows}
            extra["coverage_fused"] = \
                sum(1 for r in cov_rows if r["fused"])
    except Exception as e:  # noqa: BLE001 — later sections still run
        section_failed("coverage", e)

    if hasattr(signal, "SIGALRM"):
        signal.alarm(0)
    if trace_dir:
        from tidb_tpu.util import timeline
        path = timeline.flush()
        extra["chrome_trace_path"] = path
    emit(HEADLINE["value"], HEADLINE["vs"], extra)


if __name__ == "__main__":
    try:
        main()
    except BenchBudgetExceeded:
        log("wall-clock budget exhausted; emitting partial results")
        EXTRA["budget_exceeded"] = True
        emit(HEADLINE["value"], HEADLINE["vs"], EXTRA)
        sys.exit(1)
    except Exception as e:  # noqa: BLE001
        if hasattr(signal, "SIGALRM"):
            signal.alarm(0)
        import traceback
        traceback.print_exc(file=sys.stderr)
        # still hand the driver a JSON line carrying the failure state
        EXTRA["error"] = f"{type(e).__name__}: {e}"[:500]
        emit(HEADLINE["value"], HEADLINE["vs"], EXTRA)
        sys.exit(1)
    sys.exit(1 if EXTRA.get("failed_sections") else 0)
