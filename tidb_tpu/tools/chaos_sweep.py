"""Chaos / failpoint sweep: fault-inject every registered site under a
live workload and assert the lifecycle contract — every statement either
returns the oracle answer or raises a TYPED TiDBTPUError, within a
deadline; writes are atomic (COUNT advances exactly when the INSERT
succeeded); the session stays usable afterwards. Never a hang, never
silent corruption (ref: the reference's failpoint-enabled CI runs,
pingcap/failpoint + tests/realtikvtest).

Runnable three ways:

    python -m tidb_tpu.tools.chaos_sweep          # CLI, nonzero on fail
    python tools/chaos_sweep.py [--mesh N]        # repo-root wrapper
    pytest -m chaos                               # via tests/test_guardrails

The sweep builds its fixture CLEANLY first (faults off), records oracle
results, then runs one scenario per fault. Each scenario is
(site, fault, workload): read workloads re-check every query against the
oracle; write workloads re-count the table. failpoint.counting() meters
which sites the workload actually reached, so a refactor that silently
moves a site out of the hot path shows up as lost coverage — and the CLI
exits non-zero when a site the run was supposed to reach stayed cold
(mesh-only sites are exempt unless --mesh N forces a multi-device CPU
mesh, which makes the distributed scenarios — skewed exchange overflow,
shard-step faults — runnable too)."""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Dict, List, Optional

from tidb_tpu.errors import (ExecutionError, MemoryQuotaExceeded,
                             ShardFailure, TiDBTPUError, TxnError)
from tidb_tpu.util import failpoint
from tidb_tpu.executor import (delta as _delta, device_cache as _dc,
                               microbatch as _mb,
                               zonemap)  # noqa: F401 — zonemap registers
from tidb_tpu.executor.scheduler import POOL, SCHEDULER

# every statement must finish (result or typed error) inside this
DEADLINE_S = 30.0

QUERIES = [
    "select count(*), sum(a) from cs_facts",
    "select b, count(*) from cs_facts group by b order by b",
    "select d.name, count(*) from cs_facts f join cs_dim d "
    "on f.b = d.id group by d.name order by d.name",
    "select a from cs_facts order by a limit 5",
    # high-cardinality group key: under a squeezed quota this one is what
    # drives the agg's spill container (thousands of string groups)
    "select c, count(*) from cs_facts group by c order by c limit 3",
]

# ~3001 distinct doubles behind an EXPRESSION key: no cached bounds to
# perfect-hash, no column NDV stats to pre-size the cap — with
# tidb_tpu_group_cap squeezed the factorize cap overflows and the
# escalation ladder recompiles exactly once (the only single-process
# road to the device-recompile site). Compared as sorted row sets:
# without an ORDER BY the engines may emit groups in any order.
RECOMPILE_QUERY = "select d + 0.0, count(*) from cs_facts group by d + 0.0"

# join + EXPRESSION group key: the agg-over-join shape rides the fused
# per-slab pipeline, and the expression key (no cached bounds, no NDV
# stats) keeps the factorize cap at the session var — squeezing
# tidb_tpu_group_cap makes the overflow land INSIDE the fused driver's
# batched flag round, where the resumable retry re-runs only the
# overflowed slabs. ~997 distinct keys; compared as sorted row sets.
FUSED_QUERY = ("select f.a + 0, count(*) from cs_facts f "
               "join cs_dim d on f.b = d.id group by f.a + 0")

# single-arg DISTINCT agg under an ORDER BY root: the shape that rides
# the fused finalize (agg merge → finalize exprs → root ORDER BY in ONE
# launch) with per-slab (group, value) pair sets for the DISTINCT.
# Squeezing tidb_tpu_distinct_pair_cap below the per-slab distinct pair
# count (~1000 pairs per 1024-row slab here) makes the pair transfer cap
# overflow, which must resize through the resumable 'pairs' ladder rung
# — a clipped pair set must never be consumed
FINALIZE_QUERY = ("select b, count(distinct a) from cs_facts "
                  "group by b order by b")

# selective scan whose WHERE rides the zone maps: with compression on
# (the default) the host consults per-slab min/max BEFORE dispatch, so
# this query walks the prune decision — the zone-map-stale site —
# on every device attempt
PRUNE_QUERY = "select count(*), sum(a) from cs_facts where a > 100"

# distributed shapes — integer results, so dist vs CPU comparison is
# exact. The DISTINCT agg and the join matter: a plain group-by
# distributes through gather_partials (no re-key), so only the DISTINCT
# re-key exchange and a non-broadcast join carry exchanges — by default
# these now run STAGED (per-rank partition programs, device→host bucket
# checkpoints, host routing, per-rank probes), which is what puts the
# exchange-checkpoint-write / exchange-redispatch /
# exchange-degraded-replan sites in reach of the mesh coverage gate
MESH_QUERIES = [
    QUERIES[1],
    FINALIZE_QUERY,
    QUERIES[2],
]


def _retryable_txn(msg: str) -> TxnError:
    e = TxnError(msg)
    e.retryable = True
    return e


class Scenario:
    def __init__(self, name: str, site: Optional[str], enable_kw: dict,
                 run: str = "read", vars: Optional[Dict[str, str]] = None,
                 extra: Optional[Dict[str, dict]] = None,
                 mesh: bool = False, require_error: bool = False):
        self.name = name
        self.site = site
        self.enable_kw = enable_kw
        self.run = run               # read | write | ddl | backup | ...
        self.vars = vars or {}
        self.extra = extra or {}     # additional site → enable kwargs
        self.mesh = mesh             # needs run_sweep(mesh=N)
        self.require_error = require_error   # fault must SURFACE typed


def _scenarios(mesh: Optional[int] = None) -> List[Scenario]:
    device_on = {"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": "0"}
    out = [
        # -- CPU pipeline faults ------------------------------------------
        Scenario("scan transient fault", "scan-next",
                 dict(raise_=ExecutionError("chaos: scan-next"), times=1)),
        Scenario("scan fault after warmup", "scan-next",
                 dict(raise_=ExecutionError("chaos: scan-late"),
                      after_hits=2, times=1)),
        Scenario("scan flaky one-in-3", "scan-next",
                 dict(raise_=ExecutionError("chaos: scan-flaky"),
                      one_in=3, times=2)),
        Scenario("tracker quota blown", "tracker-quota",
                 dict(raise_=MemoryQuotaExceeded("chaos: quota"),
                      after_hits=5, times=1)),
        # -- spill path (quota squeezed so the agg engages its spill) -----
        Scenario("spill write I/O error", "spill-write",
                 dict(raise_=ExecutionError("chaos: spill-write"), times=1),
                 vars={"tidb_mem_quota_query": "8000"}),
        Scenario("spill read-back error", "spill-read",
                 dict(raise_=ExecutionError("chaos: spill-read"), times=1),
                 vars={"tidb_mem_quota_query": "8000"}),
        # -- commit path ---------------------------------------------------
        Scenario("commit hard conflict", "store-commit",
                 dict(raise_=TxnError("chaos: conflict"), times=1),
                 run="write"),
        Scenario("commit transient conflict (heals)", "commit-conflict",
                 dict(raise_=_retryable_txn("chaos: transient"), times=2),
                 run="write"),
        Scenario("commit retry budget exhausted", "commit-conflict",
                 dict(raise_=_retryable_txn("chaos: hot key")),
                 run="write",
                 extra={"backoff-sleep": dict(value="skip")}),
        # -- device path (engine forced on; CPU backend still JITs) -------
        Scenario("device fragment crash → CPU fallback", "device-fragment",
                 dict(raise_=RuntimeError("chaos: device down"), times=9),
                 vars=dict(device_on)),
        Scenario("HBM upload failure → CPU fallback", "device-transfer",
                 dict(raise_=RuntimeError("chaos: transfer"), times=9),
                 vars=dict(device_on)),
        Scenario("host fetch interrupted", "host-fetch",
                 dict(raise_=ExecutionError("chaos: host-fetch"), times=9),
                 vars=dict(device_on)),
        # group-cap overflow engages the escalation ladder; the fault
        # lands on its first recompile attempt → warned CPU fallback,
        # still the oracle answer (never truncated rows)
        Scenario("recompile ladder fault → CPU fallback", "device-recompile",
                 dict(raise_=RuntimeError("chaos: recompile"), times=1),
                 run="recompile",
                 vars={**device_on, "tidb_tpu_group_cap": "64"}),
        # the fused per-slab pipeline's capacity boundary: the site is
        # armed with NO action — it purely meters that the fused driver's
        # overflow-classification round ran — while the squeezed group
        # cap forces an in-pipeline escalation whose resumable retry is
        # asserted through the capacity ladder (slabs_rerun, exact
        # resize), results staying byte-equal to the oracle
        Scenario("fused pipeline overflow → resumable in-pipeline retry",
                 "fused-pipeline-overflow", dict(), run="fused",
                 vars={**device_on, "tidb_tpu_group_cap": "64",
                       "tidb_tpu_max_slab_rows": "1024"}),
        # a fault AT the fused capacity boundary: the per-statement guard
        # converts it to a warned CPU fallback — oracle rows, never a
        # truncated fused result
        Scenario("fused boundary fault → CPU fallback",
                 "fused-pipeline-overflow",
                 dict(raise_=RuntimeError("chaos: fused boundary"),
                      times=9),
                 run="fused", vars=dict(device_on)),
        # the fused finalize's distinct-pair transfer cap: armed with NO
        # action, the site purely meters that the per-slab pair-count
        # validation round ran — while the squeezed pair cap forces the
        # resumable 'pairs' escalation (exact resize to the true pair
        # count, only clipped slabs re-run) and the ordered result stays
        # byte-equal to the oracle
        Scenario("fused finalize pair overflow → resumable resize",
                 "fused-finalize-overflow", dict(), run="finalize",
                 vars={**device_on, "tidb_tpu_max_slab_rows": "1024",
                       "tidb_tpu_distinct_pair_cap": "64"}),
        # a fault AT the finalize boundary: the per-statement guard
        # converts it to a warned CPU fallback — oracle rows, never a
        # truncated ORDER BY/TopN result
        Scenario("fused finalize fault → CPU fallback",
                 "fused-finalize-overflow",
                 dict(raise_=RuntimeError("chaos: finalize boundary"),
                      times=9),
                 run="finalize", vars=dict(device_on)),
        # a corrupted compressed-layout descriptor: the serving path's
        # validation failpoint stands in for a descriptor that no longer
        # matches its packed words — open_table raises a typed
        # LayoutError, the executor converts it into a warned CPU
        # fallback, and rows stay byte-equal to the oracle (NEVER a
        # silent wrong decode)
        Scenario("compressed descriptor corrupt → CPU fallback",
                 "compressed-decode-mismatch",
                 dict(value="chaos: descriptor drift", times=9),
                 vars=dict(device_on)),
        # a stale zone map at the host-side slab-prune decision: the
        # consult raises a typed LayoutError, the per-statement guard
        # converts it into a warned CPU fallback, and the selective
        # query still answers the oracle — a stale map must NEVER
        # silently skip slabs that hold passing rows
        Scenario("stale zone map → CPU fallback", "zone-map-stale",
                 dict(value="chaos: stale zone map", times=9),
                 run="prune", vars=dict(device_on)),
        # a fault at the micro-batch result de-multiplex: 8 concurrent
        # same-digest point reads coalesce into ONE batched launch, the
        # demux raises once — every member must degrade to warned
        # individual re-execution with ITS OWN oracle rows; a member must
        # never see a sibling's rows or a shared typed error
        Scenario("micro-batch demux fault → warned per-member fallback",
                 "microbatch-demux",
                 dict(raise_=RuntimeError("chaos: demux"), times=1),
                 run="microbatch",
                 vars={**device_on, "tidb_tpu_microbatch_max": "8"}),
        # a fault at the work-steal handoff: a batch statement parked at
        # its admission turnstile is pulled by an idle sibling, the
        # migration faults once — the waiter must re-queue on its HOME
        # device (backoff charged), run exactly once, and still answer
        # the oracle within the deadline; never lost, never doubled
        Scenario("work-steal handoff fault → re-queued home, never lost",
                 "steal-migrate",
                 dict(raise_=RuntimeError("chaos: steal handoff"),
                      times=1),
                 run="steal",
                 vars={**device_on, "tidb_tpu_device_queues": "on"},
                 extra={"backoff-sleep": dict(value="skip")}),
        # -- degraded pod (device fault domain) ---------------------------
        # a pool device dies at its DISPATCH boundary mid-concurrent-mix:
        # the in-flight victim classifies into a typed DeviceLost, the
        # health monitor quarantines the device (queued waiters migrate
        # to survivors, its HBM shard is evicted/re-homed) and the victim
        # retries ONCE on a survivor with a retryable 1105 warning —
        # EVERY statement in the mix must still answer the oracle within
        # the deadline (zero lost, zero doubled). Once the one-shot fault
        # is spent, the flap-guard delay elapses, the placement-driven
        # readmit probe (metered through the armed device-readmit gate)
        # rejoins the device, and placements land on it again
        Scenario("device lost at dispatch → quarantine, migrate, readmit",
                 "device-lost-dispatch",
                 dict(raise_=RuntimeError("chaos: device lost"), times=1),
                 run="podfault",
                 vars={**device_on, "tidb_tpu_device_queues": "on"},
                 extra={"backoff-sleep": dict(value="skip"),
                        "device-readmit": dict()}),
        # the same fault domain at the UPLOAD boundary: the device dies
        # while its cold cache shard is streaming in (device_put). The
        # partially-committed shard is evicted with the quarantine and
        # the statement re-streams onto a survivor — same
        # exactly-once/readmission contract as the dispatch fault
        Scenario("device lost at upload → quarantine, re-stream, readmit",
                 "device-lost-upload",
                 dict(raise_=RuntimeError("chaos: upload lost"), times=1),
                 run="podfault",
                 vars={**device_on, "tidb_tpu_device_queues": "on"},
                 extra={"backoff-sleep": dict(value="skip"),
                        "device-readmit": dict()}),
        # -- HTAP write path (delta slabs) --------------------------------
        # a transient fault at the two-phase delta append's atomic apply
        # point: the commit backoff loop retries and the write lands
        # exactly once (the post-scenario count probe asserts that)
        Scenario("delta append transient fault (heals)", "delta-append",
                 dict(raise_=_retryable_txn("chaos: delta append"),
                      times=2),
                 run="write",
                 extra={"backoff-sleep": dict(value="skip")}),
        # a hard fault at the same boundary: ONE typed error surfaces
        # with the old delta version intact — the count probe proves the
        # append was never torn (all-or-nothing)
        Scenario("delta append hard fault → typed, never torn",
                 "delta-append",
                 dict(raise_=TxnError("chaos: torn append"), times=1),
                 run="write"),
        # a diff/encode fault at the delta-extension entry while a
        # cached table is stale: typed LayoutError → warned CPU
        # fallback, still the oracle answer — never a wrong merge
        Scenario("delta merge stale → CPU fallback", "delta-merge-stale",
                 dict(value="chaos: stale diff", times=9),
                 run="delta", vars=dict(device_on)),
        # a fault at the compaction's atomic install point: the rebuilt
        # generation is abandoned (buffers deleted) and the old
        # base+delta keeps serving byte-exactly; once the fault clears,
        # the next extension re-schedules and the compaction heals
        Scenario("compaction commit fault → old generation serves",
                 "compaction-commit",
                 dict(raise_=RuntimeError("chaos: compaction fault"),
                      times=1),
                 run="compact",
                 vars={**device_on, "tidb_tpu_compaction": "off"}),
        # -- DDL -----------------------------------------------------------
        Scenario("unique backfill dies mid-reorg", "index-backfill",
                 dict(raise_=ExecutionError("chaos: backfill"), times=1),
                 run="ddl"),
        # -- tools ---------------------------------------------------------
        Scenario("backup dies between tables", "backup-table",
                 dict(raise_=TiDBTPUError("chaos: backup"), times=1),
                 run="backup"),
        Scenario("restore dies between tables", "restore-table",
                 dict(raise_=TiDBTPUError("chaos: restore"), times=1),
                 run="restore"),
    ]
    if mesh:
        dist_on = {"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": "1",
                   "tidb_tpu_dist_devices": str(mesh)}
        out += [
            # squeezed bucket cap: every hash exchange overflows, reports
            # its exact need, and the ladder resizes ONCE — the site is
            # armed with no action, purely metering that the resize path
            # ran while results stay byte-equal to the CPU oracle
            Scenario("mesh exchange overflow → exact-need resize",
                     "exchange-overflow", dict(), run="mesh-read",
                     vars={**dist_on, "tidb_tpu_exchange_bucket_cap": "8"},
                     mesh=True),
            # one shard's step raises once: every distributed shape now
            # re-runs only that rank against its checkpoints — the staged
            # agg for the plain group-by, the staged exchange for the
            # DISTINCT re-key and the join (its stage-1 partition and
            # stage-3 probe attempts trace the same shard-step site)
            Scenario("mesh shard fault heals after retry", "shard-step",
                     dict(raise_=ShardFailure("chaos: shard down"),
                          times=1),
                     run="mesh-read", vars=dict(dist_on), mesh=True),
            # losing one rank's device→host checkpoint re-runs only that
            # rank (staged path only — hence the mesh-agg workload)
            Scenario("mesh checkpoint write fails once → heals",
                     "shard-checkpoint-write",
                     dict(raise_=ShardFailure("chaos: checkpoint lost"),
                          times=1),
                     run="mesh-agg", vars=dict(dist_on), mesh=True),
            # a persistently bad device: dispatch AND same-device retry
            # fail, so the rank's work re-dispatches onto a surviving
            # device (degraded mesh) and the result still matches the
            # oracle; the extras are armed with no action purely to meter
            # that the recovery sites actually fired
            Scenario("mesh device persistently bad → degraded-mesh heal",
                     "shard-step",
                     dict(raise_=ShardFailure("chaos: device bad"),
                          times=2),
                     run="mesh-agg", vars=dict(dist_on), mesh=True,
                     extra={"degraded-mesh-replan": dict(),
                            "shard-redispatch": dict()}),
            # the fault persists through every recovery rung — the
            # same-device retry AND the re-dispatch onto a spare: ONE
            # typed ShardFailure must surface (a silent CPU re-run would
            # hide a dead shard). Both re-dispatch rungs are armed: the
            # staged agg's shard-redispatch AND the staged exchange's
            # exchange-redispatch (the DISTINCT re-key / join shapes
            # would otherwise heal onto the spare device)
            Scenario("mesh shard fault persists → typed error",
                     "shard-step",
                     dict(raise_=ShardFailure("chaos: shard down")),
                     run="mesh-read", vars=dict(dist_on), mesh=True,
                     require_error=True,
                     extra={"shard-redispatch":
                            dict(raise_=ShardFailure("chaos: spare down")),
                            "exchange-redispatch":
                            dict(raise_=ShardFailure("chaos: spare down"))
                            }),
            # -- staged exchanges (joins, DISTINCT re-keys, windows) -----
            # losing one rank's stage-1 bucket checkpoint re-runs only
            # that rank's partition program; the other ranks' committed
            # checkpoints are routed untouched. times=1 so only the FIRST
            # exchange-carrying shape (the DISTINCT re-key) takes the
            # fault and its same-device retry heals cleanly; the join
            # runs clean after it (both-shapes recovery is pinned per
            # failpoint in tests/test_staged_exchange.py)
            Scenario("mesh exchange checkpoint lost → heals one rank",
                     "exchange-checkpoint-write",
                     dict(raise_=ShardFailure("chaos: bucket ckpt lost"),
                          times=1),
                     run="mesh-read", vars=dict(dist_on), mesh=True),
            # a persistently bad device under a DISTRIBUTED JOIN: the
            # rank's stage fails on its device and on the same-device
            # retry, re-dispatches onto a surviving device through the
            # exchange-degraded-replan / exchange-redispatch rungs
            # (armed with no action purely to meter reachability), and
            # the join still answers the oracle on N-1 devices
            Scenario("mesh join device bad → degraded-mesh heal",
                     "shard-step",
                     dict(raise_=ShardFailure("chaos: device bad"),
                          times=2),
                     run="mesh-join", vars=dict(dist_on), mesh=True,
                     extra={"exchange-degraded-replan": dict(),
                            "exchange-redispatch": dict()}),
            # the join's shard is fully dead — its own device AND the
            # re-dispatch spare both fail: ONE typed retryable
            # ShardFailure surfaces and the session stays usable (the
            # post-scenario count probe asserts that)
            Scenario("mesh join shard fully dead → typed error",
                     "shard-step",
                     dict(raise_=ShardFailure("chaos: device down")),
                     run="mesh-join", vars=dict(dist_on), mesh=True,
                     require_error=True,
                     extra={"exchange-redispatch":
                            dict(raise_=ShardFailure("chaos: spare down"))
                            }),
            # two-session isolation: session A takes a shard fault on
            # the mesh path while session B serves the single-process
            # device path CONCURRENTLY — B must stay byte-exact and
            # error-free throughout (the fault, the retry, the shared
            # HBM/compile caches and scheduler never leak across
            # sessions), and A still heals to the oracle answer
            Scenario("shard fault isolated from concurrent session",
                     "shard-step",
                     dict(raise_=ShardFailure("chaos: shard down"),
                          times=1),
                     run="mesh-isolation", vars=dict(dist_on), mesh=True),
        ]
    return out


def list_sites() -> Dict[str, str]:
    """The sweep's authoritative failpoint catalog: every site
    registered in util/failpoint.py PLUS module-scope registrations
    (executor/zonemap.py's zone-map-stale) — imported here so the
    enumeration matches what the coverage gate sweeps.
    → {site: description} (tools/check_failpoints.py cross-checks the
    count, keeping the advertised site number honest)."""
    return failpoint.catalog()


def _run_statement(session, sql: str):
    """→ (rows|None, error|None, elapsed). Non-TiDBTPUError escapes —
    that IS a sweep failure."""
    t0 = time.monotonic()
    try:
        rs = session.query(sql)
        return rs.rows, None, time.monotonic() - t0
    except TiDBTPUError as e:
        return None, e, time.monotonic() - t0


def run_sweep(verbose: bool = False, mesh: Optional[int] = None,
              mesh_only: bool = False) -> dict:
    """mesh=N runs the distributed scenarios over an N-device mesh (the
    process must already see ≥N devices — the CLI's --mesh forces a host
    CPU mesh via XLA_FLAGS before jax loads). mesh_only skips the
    single-process scenarios: the cheap pytest `-m chaos` mesh variant."""
    from tidb_tpu.session import Engine
    if mesh:
        import jax
        if len(jax.devices()) < mesh:
            raise RuntimeError(
                f"--mesh {mesh} needs {mesh} devices, jax sees "
                f"{len(jax.devices())}; set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={mesh} before "
                f"jax loads (tools/chaos_sweep.py --mesh does this)")
    failpoint.disable_all()
    eng = Engine()
    s = eng.new_session()

    # fixture FIRST, faults off — the oracle must be trustworthy
    s.execute("create table cs_dim (id int, name varchar(16))")
    s.execute("create table cs_facts (a int, b int, c varchar(24), "
              "d double)")
    dim = ", ".join(f"({i}, 'name{i:02d}')" for i in range(8))
    s.execute(f"insert into cs_dim values {dim}")
    for base in range(0, 4000, 500):
        vals = ", ".join(
            f"({(i * 37) % 997 - 200}, {i % 8}, 'payload-{i:05d}', "
            f"{((i * 53) % 3001) / 8.0})"
            for i in range(base, base + 500))
        s.execute(f"insert into cs_facts values {vals}")
    # NDV stats so the distributed planner trusts its row estimates
    s.execute("analyze table cs_dim")
    s.execute("analyze table cs_facts")

    # coverage meter: which sites does the clean workload even reach?
    failpoint.reset_counters()
    with failpoint.counting():
        for q in QUERIES:
            s.query(q)
        s.execute("insert into cs_facts values (1, 1, 'probe', 0.0)")
    coverage = failpoint.counters()

    # oracle recorded AFTER the probe write; re-recorded after every
    # mutating scenario, so "correct result" always means "what a clean
    # run over the CURRENT data returns"
    oracle_qs = QUERIES + [RECOMPILE_QUERY, FUSED_QUERY, PRUNE_QUERY] + \
        [q for q in MESH_QUERIES if q not in QUERIES]
    oracle = {q: s.query(q).rows for q in oracle_qs}
    base_count = s.query("select count(*) from cs_facts").scalar()

    failures: List[str] = []
    results: List[dict] = []
    reached = {k for k, v in coverage.items() if v > 0}
    write_seq = 0

    for sc in _scenarios(mesh):
        if mesh_only and not sc.mesh:
            continue
        saved = {k: s.vars.get(k) for k in sc.vars}
        s.vars.update(sc.vars)
        if sc.site is not None:
            failpoint.enable(sc.site, **sc.enable_kw)
        for site, kw in sc.extra.items():
            failpoint.enable(site, **kw)
        errors, wrong, slow = 0, 0, 0
        try:
            if sc.run == "read":
                for q in QUERIES:
                    rows, err, dt = _run_statement(s, q)
                    if dt > DEADLINE_S:
                        slow += 1
                        failures.append(f"{sc.name}: {q!r} took {dt:.1f}s")
                    if err is not None:
                        errors += 1
                    elif rows != oracle[q]:
                        wrong += 1
                        failures.append(
                            f"{sc.name}: {q!r} SILENT WRONG RESULT")
            elif sc.run == "recompile":
                q = RECOMPILE_QUERY
                rows, err, dt = _run_statement(s, q)
                if dt > DEADLINE_S:
                    slow += 1
                    failures.append(f"{sc.name}: {q!r} took {dt:.1f}s")
                if err is not None:
                    errors += 1
                elif sorted(rows) != sorted(oracle[q]):
                    wrong += 1
                    failures.append(f"{sc.name}: {q!r} SILENT WRONG RESULT")
            elif sc.run == "prune":
                q = PRUNE_QUERY
                rows, err, dt = _run_statement(s, q)
                if dt > DEADLINE_S:
                    slow += 1
                    failures.append(f"{sc.name}: {q!r} took {dt:.1f}s")
                if err is not None:
                    errors += 1
                elif rows != oracle[q]:
                    wrong += 1
                    failures.append(f"{sc.name}: {q!r} SILENT WRONG RESULT")
            elif sc.run == "fused":
                q = FUSED_QUERY
                rows, err, dt = _run_statement(s, q)
                if dt > DEADLINE_S:
                    slow += 1
                    failures.append(f"{sc.name}: {q!r} took {dt:.1f}s")
                if err is not None:
                    errors += 1
                elif sorted(rows) != sorted(oracle[q]):
                    wrong += 1
                    failures.append(f"{sc.name}: {q!r} SILENT WRONG RESULT")
                elif sc.enable_kw.get("raise_") is None:
                    # site armed with no action → the fused driver must
                    # have taken its RESUMABLE escalation: the squeezed
                    # group cap overflows inside the pipeline, the ladder
                    # records one exact resize, and only overflowed slab
                    # partials re-run (uniform key spread here → all of
                    # them overflow; reuse-split skew is pinned down in
                    # tests/test_fused_pipeline.py)
                    esc = s.last_guard.escalation
                    if esc.slabs_rerun == 0 or esc.exact_resizes == 0:
                        failures.append(
                            f"{sc.name}: fused driver skipped the "
                            f"resumable retry (slabs_rerun="
                            f"{esc.slabs_rerun} exact_resizes="
                            f"{esc.exact_resizes})")
            elif sc.run == "finalize":
                q = FINALIZE_QUERY
                rows, err, dt = _run_statement(s, q)
                if dt > DEADLINE_S:
                    slow += 1
                    failures.append(f"{sc.name}: {q!r} took {dt:.1f}s")
                if err is not None:
                    errors += 1
                elif rows != oracle[q]:
                    wrong += 1
                    failures.append(f"{sc.name}: {q!r} SILENT WRONG RESULT")
                elif sc.enable_kw.get("raise_") is None:
                    # site armed with no action → the driver must have
                    # taken the resumable 'pairs' escalation: the
                    # squeezed pair cap clips every slab's pair set, the
                    # ladder records one exact resize to the true count,
                    # and the clipped slabs re-run against the original
                    # resident columns
                    esc = s.last_guard.escalation
                    if esc.slabs_rerun == 0 or esc.exact_resizes == 0:
                        failures.append(
                            f"{sc.name}: finalize driver skipped the "
                            f"resumable pairs retry (slabs_rerun="
                            f"{esc.slabs_rerun} exact_resizes="
                            f"{esc.exact_resizes})")
            elif sc.run in ("mesh-read", "mesh-agg", "mesh-join"):
                # mesh-agg: only the plain group-by (the staged-AGG
                # checkpoint ladder); mesh-join: only the distributed
                # join (the staged-EXCHANGE ladder — stage-1 partition
                # checkpoints, host bucket routing, stage-3 probe).
                # mesh-read runs all three shapes — since the staged
                # exchange landed, the DISTINCT re-key and the join ride
                # the same per-rank recovery as the agg
                if sc.run == "mesh-agg":
                    qs = MESH_QUERIES[:1]
                elif sc.run == "mesh-join":
                    qs = MESH_QUERIES[2:3]
                else:
                    qs = MESH_QUERIES
                for q in qs:
                    rows, err, dt = _run_statement(s, q)
                    if dt > DEADLINE_S:
                        slow += 1
                        failures.append(f"{sc.name}: {q!r} took {dt:.1f}s")
                    if err is not None:
                        errors += 1
                        if not sc.require_error:
                            failures.append(
                                f"{sc.name}: {q!r} unexpected typed error "
                                f"{type(err).__name__}: {err}")
                    elif sc.require_error:
                        failures.append(
                            f"{sc.name}: {q!r} expected a typed error, "
                            f"got a silent result")
                    elif rows != oracle[q]:
                        wrong += 1
                        failures.append(
                            f"{sc.name}: {q!r} SILENT WRONG RESULT")
            elif sc.run == "mesh-isolation":
                # session B: single-process device path (no mesh vars →
                # it never traces shard-step), looping a read the whole
                # time session A's mesh query faults and heals
                s2 = eng.new_session()
                s2.vars["tidb_tpu_engine"] = "on"
                s2.vars["tidb_tpu_row_threshold"] = "1"
                b_query = QUERIES[1]
                b_fail: List[str] = []
                b_done = [0]
                stop = threading.Event()

                def sibling():
                    try:
                        while not stop.is_set() and b_done[0] < 24:
                            rows = s2.query(b_query).rows
                            if rows != oracle[b_query]:
                                b_fail.append(
                                    "sibling session WRONG RESULT while "
                                    "peer shard faulted")
                                return
                            b_done[0] += 1
                    except BaseException as e:  # noqa: BLE001
                        b_fail.append(
                            f"sibling session error during peer fault: "
                            f"{type(e).__name__}: {e}")

                th = threading.Thread(target=sibling, daemon=True)
                th.start()
                try:
                    for q in MESH_QUERIES:
                        rows, err, dt = _run_statement(s, q)
                        if dt > DEADLINE_S:
                            slow += 1
                            failures.append(
                                f"{sc.name}: {q!r} took {dt:.1f}s")
                        if err is not None:
                            errors += 1
                            failures.append(
                                f"{sc.name}: {q!r} did not heal: "
                                f"{type(err).__name__}: {err}")
                        elif rows != oracle[q]:
                            wrong += 1
                            failures.append(
                                f"{sc.name}: {q!r} SILENT WRONG RESULT")
                finally:
                    stop.set()
                    th.join(DEADLINE_S)
                if th.is_alive():
                    failures.append(f"{sc.name}: sibling session HUNG")
                failures.extend(f"{sc.name}: {m}" for m in b_fail)
                if b_done[0] == 0 and not b_fail:
                    failures.append(
                        f"{sc.name}: sibling session made no progress")
            elif sc.run == "microbatch":
                from tidb_tpu.util.observability import REGISTRY
                # oracle per member, run SOLO (a solo leader takes the
                # individual path, so the armed demux site never fires)
                # no ORDER BY: order roots don't micro-batch; the filter
                # path emits rows in slab order, which is deterministic,
                # so raw row-list comparison is exact
                mb_qs = [f"select a, c from cs_facts where b = {k}"
                         for k in range(8)]
                mb_sessions = []
                for _ in mb_qs:
                    s_i = eng.new_session()
                    s_i.vars.update(sc.vars)
                    mb_sessions.append(s_i)
                mb_oracle = [s.query(q).rows for q in mb_qs]
                mb_rows: List[Optional[list]] = [None] * len(mb_qs)
                mb_errs: List[Optional[BaseException]] = \
                    [None] * len(mb_qs)

                def mb_run(i):
                    try:
                        mb_rows[i] = mb_sessions[i].query(mb_qs[i]).rows
                    except BaseException as e:  # noqa: BLE001
                        mb_errs[i] = e

                fb0 = REGISTRY.counters.get(
                    ("tidb_tpu_microbatch_fallbacks_total", ()), 0)
                # hold the device slot so every dispatcher queues, then
                # release once the followers are parked on the batch
                SCHEDULER.acquire(conn_id=-1)
                try:
                    ths = [threading.Thread(target=mb_run, args=(i,))
                           for i in range(len(mb_qs))]
                    for th in ths:
                        th.start()
                    t_park = time.monotonic()
                    while _mb.queued_members() < len(mb_qs) - 1 and \
                            time.monotonic() - t_park < 5.0:
                        time.sleep(0.01)
                finally:
                    SCHEDULER.release()
                for th in ths:
                    th.join(DEADLINE_S)
                    if th.is_alive():
                        slow += 1
                        failures.append(f"{sc.name}: member HUNG")
                for i, (rows, err) in enumerate(zip(mb_rows, mb_errs)):
                    if err is not None:
                        errors += 1
                        failures.append(
                            f"{sc.name}: member {i} surfaced "
                            f"{type(err).__name__}: {err} — a demux "
                            f"fault must never fail a member")
                    elif rows != mb_oracle[i]:
                        wrong += 1
                        failures.append(
                            f"{sc.name}: member {i} SILENT WRONG ROWS")
                if failpoint.hits("microbatch-demux") > 0:
                    fb1 = REGISTRY.counters.get(
                        ("tidb_tpu_microbatch_fallbacks_total", ()), 0)
                    if fb1 <= fb0:
                        failures.append(
                            f"{sc.name}: demux faulted but no fallback "
                            f"was recorded")
            elif sc.run == "steal":
                q = QUERIES[1]
                # a second serving peer even on a 1-device host: the
                # steal protocol is pure host-side queue mechanics, so
                # the CPU sweep exercises it with device_queues forced
                # on and the pool grown explicitly
                POOL.ensure(2)
                dev0, dev1 = POOL.schedulers[0], POOL.schedulers[1]
                st_rows: List[Optional[list]] = [None]
                st_err: List[Optional[BaseException]] = [None]

                def st_run():
                    try:
                        st_rows[0] = s.query(q).rows
                    except BaseException as e:  # noqa: BLE001
                        st_err[0] = e

                # hold BOTH dispatch slots so the batch statement parks
                # at its admission turnstile (placement ties to device 0)
                dev0.acquire(conn_id=-1)
                dev1.acquire(conn_id=-1)
                th = threading.Thread(target=st_run, daemon=True)
                stole = False
                try:
                    th.start()
                    t_park = time.monotonic()
                    while time.monotonic() - t_park < 5.0:
                        with dev0._cv:
                            if dev0._stealable > 0:
                                break
                        time.sleep(0.01)
                    # the idle sibling pulls the parked waiter; the
                    # armed failpoint faults the handoff
                    stole = POOL.steal_into(dev1)
                finally:
                    dev1.release()
                    dev0.release()
                th.join(DEADLINE_S)
                if th.is_alive():
                    slow += 1
                    failures.append(f"{sc.name}: stolen statement HUNG")
                elif not stole:
                    failures.append(
                        f"{sc.name}: no steal-eligible waiter parked "
                        f"(batch admission never reached the turnstile)")
                elif st_err[0] is not None:
                    errors += 1
                    failures.append(
                        f"{sc.name}: statement must re-queue home and "
                        f"heal, not fail: {type(st_err[0]).__name__}: "
                        f"{st_err[0]}")
                elif st_rows[0] != oracle[q]:
                    wrong += 1
                    failures.append(f"{sc.name}: {q!r} SILENT WRONG "
                                    f"RESULT after faulted steal")
            elif sc.run == "podfault":
                from tidb_tpu.util.observability import REGISTRY

                def _ctr(name):
                    return sum(v for (n, _l), v in
                               REGISTRY.counters.items() if n == name)

                # a pod of two serving peers even on a 1-device host (the
                # fault domain is host-side pool mechanics), and a COLD
                # cache so the upload-boundary site actually streams
                POOL.ensure(2)
                _dc.clear()
                q_before = _ctr("tidb_tpu_device_quarantines_total")
                m_before = _ctr("tidb_tpu_statements_migrated_total")
                pf_qs = QUERIES * 2
                pf_sessions = []
                for _ in pf_qs:
                    s_i = eng.new_session()
                    s_i.vars.update(sc.vars)
                    pf_sessions.append(s_i)
                pf_rows: List[Optional[list]] = [None] * len(pf_qs)
                pf_errs: List[Optional[BaseException]] = \
                    [None] * len(pf_qs)

                def pf_run(i):
                    try:
                        pf_rows[i] = pf_sessions[i].query(pf_qs[i]).rows
                    except BaseException as e:  # noqa: BLE001
                        pf_errs[i] = e

                ths = [threading.Thread(target=pf_run, args=(i,),
                                        daemon=True)
                       for i in range(len(pf_qs))]
                for th in ths:
                    th.start()
                for i, th in enumerate(ths):
                    th.join(DEADLINE_S)
                    if th.is_alive():
                        slow += 1
                        failures.append(
                            f"{sc.name}: statement {i} HUNG past the "
                            f"deadline (lost to the dead device?)")
                # exactly-once: every statement must come back with the
                # oracle rows — the one victim heals through its single
                # survivor retry, so even a typed error is a failure here
                for i, (rows, err) in enumerate(zip(pf_rows, pf_errs)):
                    if err is not None:
                        errors += 1
                        failures.append(
                            f"{sc.name}: statement {i} must retry on a "
                            f"survivor, not fail: "
                            f"{type(err).__name__}: {err}")
                    elif rows != oracle[pf_qs[i]]:
                        wrong += 1
                        failures.append(
                            f"{sc.name}: statement {i} SILENT WRONG "
                            f"ROWS after device loss")
                if failpoint.hits(sc.site) == 0:
                    failures.append(
                        f"{sc.name}: the armed fault never fired — the "
                        f"mix missed the {sc.site} boundary")
                else:
                    if _ctr("tidb_tpu_device_quarantines_total") \
                            <= q_before:
                        failures.append(
                            f"{sc.name}: device fault fired but no "
                            f"device was quarantined")
                    if _ctr("tidb_tpu_statements_migrated_total") \
                            <= m_before:
                        failures.append(
                            f"{sc.name}: device fault fired but the "
                            f"victim statement never migrated")
                    victims = sorted(
                        i for i, r in POOL.health.snapshot().items()
                        if r["faults"] > 0)
                    if not victims:
                        failures.append(
                            f"{sc.name}: fault fired but the health "
                            f"monitor recorded no victim")
                    # heal: the one-shot fault is spent; placement drives
                    # the readmit sweep, so issuing statements past the
                    # flap-guard delay must readmit every quarantined
                    # device (the probe passes through the armed
                    # device-readmit gate, which also meters it)
                    t_heal = time.monotonic()
                    healed = False
                    while time.monotonic() - t_heal < 10.0:
                        _run_statement(s, QUERIES[0])
                        if not POOL.health.quarantined_indexes():
                            healed = True
                            break
                        time.sleep(0.05)
                    if not healed:
                        failures.append(
                            f"{sc.name}: device(s) "
                            f"{POOL.health.quarantined_indexes()} never "
                            f"readmitted after the fault cleared")
                    elif failpoint.hits("device-readmit") == 0:
                        failures.append(
                            f"{sc.name}: device readmitted without a "
                            f"health probe")
                    elif victims:
                        # placements return: park every OTHER member so
                        # least-depth placement of an uncached table must
                        # pick the readmitted device (locality votes
                        # can't — its shard was evicted, so the probe
                        # table is cold everywhere after the clear())
                        try:
                            s.execute("create table cs_pod (x int)")
                            s.execute("insert into cs_pod values "
                                      "(1), (2), (3)")
                        except TiDBTPUError:
                            pass        # second podfault scenario
                        with POOL._lock:
                            members = list(POOL.schedulers)
                        parked = [m for m in members
                                  if m.device_index not in victims]
                        a0 = sum(m.stats()["admissions"] for m in members
                                 if m.device_index in victims)
                        for m in parked:
                            m.acquire(conn_id=-1)
                        try:
                            _, perr, _ = _run_statement(
                                s, "select count(*) from cs_pod")
                        finally:
                            for m in parked:
                                m.release()
                        a1 = sum(m.stats()["admissions"] for m in members
                                 if m.device_index in victims)
                        if perr is not None:
                            failures.append(
                                f"{sc.name}: probe statement on the "
                                f"readmitted device failed: {perr}")
                        elif a1 <= a0:
                            failures.append(
                                f"{sc.name}: readmitted device(s) "
                                f"{victims} received no placements")
            elif sc.run == "delta":
                # warm the device cache, then commit an IN-RANGE row so
                # the next device read must extend the stale entry —
                # with the diff fault armed the extension must fall back
                # warned and still answer the post-write CPU oracle
                q = QUERIES[0]
                s.query(q)
                write_seq += 1
                _, werr, _ = _run_statement(
                    s, f"insert into cs_facts values "
                       f"(500, {write_seq % 8}, 'dl{write_seq}', 0.0)")
                if werr is not None:
                    failures.append(f"{sc.name}: fixture write failed "
                                    f"{werr}")
                else:
                    base_count += 1
                eng_saved = s.vars.get("tidb_tpu_engine")
                s.vars["tidb_tpu_engine"] = "off"
                cpu = s.query(q).rows
                s.vars["tidb_tpu_engine"] = eng_saved
                rows, err, dt = _run_statement(s, q)
                if dt > DEADLINE_S:
                    slow += 1
                    failures.append(f"{sc.name}: {q!r} took {dt:.1f}s")
                if err is not None:
                    errors += 1
                    failures.append(
                        f"{sc.name}: {q!r} must fall back, not fail: "
                        f"{type(err).__name__}: {err}")
                elif rows != cpu:
                    wrong += 1
                    failures.append(f"{sc.name}: {q!r} SILENT WRONG RESULT")
            elif sc.run == "compact":
                q = QUERIES[0]
                # compaction due after four appended rows: the trigger is
                # a share of the delta slab's capacity, squeezed for the
                # scenario (restored where the scenario's vars are)
                fill_saved = _delta.COMPACT_FILL
                _delta.COMPACT_FILL = 4 / _delta.MIN_DELTA_CAP
                s.query(q)
                # pile IN-RANGE appends past the squeezed threshold so
                # the next read's extension schedules a compaction job
                for _i in range(4):
                    write_seq += 1
                    _, werr, _ = _run_statement(
                        s, f"insert into cs_facts values "
                           f"(501, {write_seq % 8}, 'cp{write_seq}', 0.0)")
                    if werr is None:
                        base_count += 1
                s.query(q)
                if _delta.pending_compactions() == 0:
                    failures.append(
                        f"{sc.name}: extension never scheduled a "
                        f"compaction job")
                committed = _delta.run_pending_compactions()
                if committed != 0:
                    failures.append(
                        f"{sc.name}: compaction committed THROUGH an "
                        f"armed commit fault")
                eng_saved = s.vars.get("tidb_tpu_engine")
                s.vars["tidb_tpu_engine"] = "off"
                cpu = s.query(q).rows
                s.vars["tidb_tpu_engine"] = eng_saved
                rows, err, dt = _run_statement(s, q)
                if err is not None:
                    errors += 1
                    failures.append(
                        f"{sc.name}: old generation failed to serve: "
                        f"{type(err).__name__}: {err}")
                elif rows != cpu:
                    wrong += 1
                    failures.append(
                        f"{sc.name}: old base+delta generation served "
                        f"WRONG ROWS after an abandoned rebuild")
                # fault clears → the next extension re-schedules and the
                # compaction HEALS
                failpoint.disable(sc.site)
                write_seq += 1
                _, werr, _ = _run_statement(
                    s, f"insert into cs_facts values "
                       f"(502, {write_seq % 8}, 'cp{write_seq}', 0.0)")
                if werr is None:
                    base_count += 1
                s.query(q)
                if _delta.run_pending_compactions() < 1:
                    failures.append(
                        f"{sc.name}: compaction did not heal after the "
                        f"fault cleared")
                s.vars["tidb_tpu_engine"] = "off"
                cpu2 = s.query(q).rows
                s.vars["tidb_tpu_engine"] = eng_saved
                rows2, err2, _ = _run_statement(s, q)
                if err2 is not None or rows2 != cpu2:
                    failures.append(
                        f"{sc.name}: compacted generation diverged")
                _delta.COMPACT_FILL = fill_saved
            elif sc.run == "write":
                write_seq += 1
                ins = (f"insert into cs_facts values "
                       f"(9000, {write_seq % 8}, 'w{write_seq}', 0.0)")
                _, err, dt = _run_statement(s, ins)
                if dt > DEADLINE_S:
                    slow += 1
                    failures.append(f"{sc.name}: insert took {dt:.1f}s")
                if err is not None:
                    errors += 1
                else:
                    base_count += 1
                failpoint.disable_all()
                now = s.query("select count(*) from cs_facts").scalar()
                if now != base_count:
                    wrong += 1
                    failures.append(
                        f"{sc.name}: NON-ATOMIC WRITE "
                        f"(count {now} != expected {base_count})")
            elif sc.run == "ddl":
                _, err, dt = _run_statement(
                    s, "create unique index cs_uk on cs_facts (c)")
                if err is None:
                    # injected fault didn't stop it — clean up
                    s.execute("drop index cs_uk on cs_facts")
                else:
                    errors += 1
                if dt > DEADLINE_S:
                    slow += 1
                    failures.append(f"{sc.name}: ddl took {dt:.1f}s")
            elif sc.run in ("backup", "restore"):
                import tempfile
                with tempfile.TemporaryDirectory() as d:
                    if sc.run == "restore":
                        # backup runs CLEAN (only restore-table is armed):
                        # the restore then re-applies identical data, so a
                        # partial restore is detectable as count drift
                        s.query(f"backup to '{d}/bk'")
                        stmt = f"restore from '{d}/bk'"
                    else:
                        stmt = f"backup to '{d}/bk'"
                    _, err, dt = _run_statement(s, stmt)
                    if err is not None:
                        errors += 1
                    if dt > DEADLINE_S:
                        slow += 1
                        failures.append(
                            f"{sc.name}: {sc.run} took {dt:.1f}s")
        except BaseException as e:  # noqa: BLE001 — untyped escape = bug
            failures.append(
                f"{sc.name}: UNTYPED ERROR {type(e).__name__}: {e}")
        finally:
            # hits() survives disable (counters persist), so meter the
            # scenario's own coverage before clearing faults
            for site in ([sc.site] if sc.site else []) + list(sc.extra):
                if failpoint.hits(site) > 0:
                    reached.add(site)
            failpoint.disable_all()
            for k, v in saved.items():
                if v is None:
                    s.vars.pop(k, None)
                else:
                    s.vars[k] = v

        # the session must still work after every scenario
        after = s.query("select count(*) from cs_facts").scalar()
        if after != base_count:
            failures.append(f"{sc.name}: count drifted after scenario")
        if sc.run not in ("read", "recompile", "fused", "finalize",
                          "mesh-read", "mesh-agg", "mesh-join"):
            # mutating scenarios move the goalposts: refresh the oracle
            oracle = {q: s.query(q).rows for q in oracle_qs}
            base_count = after
        results.append({"scenario": sc.name, "site": sc.site,
                        "errors": errors, "wrong": wrong, "slow": slow})
        if verbose:
            print(f"  {sc.name:45s} errors={errors} wrong={wrong}")

    unreached = sorted(set(failpoint.catalog()) - reached)
    # the coverage GATE: a cold site the run was supposed to exercise.
    # Without a mesh, mesh-only sites are exempt (a single-process
    # workload cannot trace an exchange); mesh_only conversely gates only
    # the distributed sites (the CPU scenarios were skipped on purpose).
    exempt = set()
    if not mesh:
        exempt = failpoint.mesh_only_sites()
    elif mesh_only:
        exempt = set(failpoint.catalog()) - failpoint.mesh_only_sites()
    gated_unreached = sorted(set(unreached) - exempt)
    report = {"scenarios": len(results), "results": results,
              "failures": failures, "coverage": coverage,
              "unreached": unreached,
              "gated_unreached": gated_unreached}
    eng.close()
    return report


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="chaos_sweep")
    ap.add_argument("-v", "--verbose", action="store_true")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="also run the distributed scenarios over an "
                         "N-device forced host CPU mesh")
    ap.add_argument("--mesh-only", action="store_true",
                    help="with --mesh: run ONLY the distributed scenarios")
    ap.add_argument("--list-sites", action="store_true",
                    help="print the failpoint catalog (site, description,"
                         " mesh-only tag) and exit without sweeping")
    args = ap.parse_args(argv)
    if args.list_sites:
        sites = list_sites()
        mesh_sites = failpoint.mesh_only_sites()
        for name in sorted(sites):
            tag = " [mesh-only]" if name in mesh_sites else ""
            print(f"{name}{tag}: {sites[name]}")
        print(f"{len(sites)} sites")
        return 0
    # drift lints FIRST: a drifting metric name/label or a failpoint
    # site missing from the catalog fails the sweep before any scenario
    # spends wall time (tools/check_metrics.py, tools/check_failpoints.py
    # — the latter is what keeps the coverage gate below trustworthy).
    # check_coverage is the device-coverage ratchet: it replays the 22
    # TPC-H-shaped coverage queries at small SF against COVERAGE.json,
    # so a planner/fragment change that silently de-fuses a pinned query
    # fails here before any chaos scenario runs.
    import importlib.util as _ilu
    _repo = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "..", "..")
    for _tool in ("check_metrics", "check_failpoints", "check_coverage"):
        _path = os.path.join(_repo, "tools", f"{_tool}.py")
        if not os.path.exists(_path):
            continue
        _spec = _ilu.spec_from_file_location(_tool, _path)
        _cm = _ilu.module_from_spec(_spec)
        _spec.loader.exec_module(_cm)
        _problems = _cm.run(_repo)
        if _problems:
            for p in _problems:
                print(p)
            print(f"chaos sweep: {_tool} lint failed "
                  f"({len(_problems)} violation(s))")
            return 1
        print(f"chaos sweep: {_tool} lint ok")
    t0 = time.monotonic()
    report = run_sweep(verbose=args.verbose, mesh=args.mesh or None,
                       mesh_only=args.mesh_only)
    dt = time.monotonic() - t0
    print(f"chaos sweep: {report['scenarios']} scenarios in {dt:.1f}s")
    print(f"  sites reached by clean workload: "
          f"{sorted(k for k, v in report['coverage'].items() if v)}")
    if report["unreached"]:
        print(f"  unreached sites: {report['unreached']}")
    if report["failures"]:
        print(f"FAILURES ({len(report['failures'])}):")
        for f in report["failures"]:
            print(f"  - {f}")
        return 1
    if report["gated_unreached"]:
        print(f"COVERAGE GATE: sites this run should have reached stayed "
              f"cold: {report['gated_unreached']}")
        return 1
    print("OK — every fault produced a correct result or a typed error")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
