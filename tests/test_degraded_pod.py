"""Degraded-pod serving: device health, quarantine, queue migration,
cache re-homing, and healed readmission (over the conftest's forced
8-device CPU mesh).

Pins the PR's acceptance contract:

* fault-free pods stay on the empty-record fast path — no health
  records, placement byte-identical to the pre-health pool — and a
  single-slot pool REFUSES to quarantine its last healthy device;
* an in-flight DeviceLost (the `device-lost-dispatch` /
  `device-lost-upload` boundaries) quarantines the device and retries
  the victim ONCE on a survivor with a retryable 1105 SHOW WARNINGS
  row and the `migrated:` marker in EXPLAIN ANALYZE — a second loss
  surfaces the typed error, never a silent CPU re-run;
* quarantine drains the dead device's queue: every steal-eligible
  waiter migrates to survivors (counted as migration, not stealing)
  and still answers the oracle;
* a release-into-empty steal racing the quarantine drain of the same
  home queue migrates the waiter EXACTLY once (the _claim_waiter
  rendezvous — satellite 1);
* KILL (1317) and an expired deadline (3024) land on a waiter that was
  migrated off a quarantined device while queued (satellite 3);
* `evict_device` re-homes a pod-partitioned entry: only the lost slab
  ranges are nulled + re-owned onto survivors (holes + `lost` set),
  untouched owners keep their arrays by IDENTITY, and the next touch
  refills exactly the lost slabs;
* readmission is gated by the `device-readmit` probe: an armed gate
  keeps the device out, a clean pass past the flap-guard delay rejoins
  placement.
"""

import threading
import time

import pytest

from tidb_tpu.errors import DeviceLost, TiDBTPUError
from tidb_tpu.executor import device_cache as dc
from tidb_tpu.executor.scheduler import POOL, SchedulerPool
from tidb_tpu.session import Engine
from tidb_tpu.util import failpoint
from tidb_tpu.util.observability import REGISTRY

DIM_SQL = "SELECT g, COUNT(*), SUM(a) FROM dim GROUP BY g ORDER BY g"


@pytest.fixture()
def pod(monkeypatch):
    # the parking tests hold device 0 and count on its waiters STAYING
    # queued until the test quarantines it. A waiter queued longer than
    # STEAL_PATIENCE_S (0.3 s) legitimately spills itself onto an idle
    # sibling — on a loaded host the test's own poll-then-fault step can
    # take that long, and the spilled waiter then counts as a steal, not
    # a migration. No test of this file exercises the patience spill
    # (tests/test_pod_serving.py does), so it is parked out of reach here.
    from tidb_tpu.executor import scheduler
    monkeypatch.setattr(scheduler, "STEAL_PATIENCE_S", 3600.0)
    # the pool is a process singleton: a health record left by whatever
    # file this worker ran before (the schedule moves with every file the
    # suite gains) must not decide `test_fault_free_pod_stays_on_fast_path`
    with POOL.health._lock:
        POOL.health._rec.clear()
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    s.execute("CREATE TABLE dim (a BIGINT, g BIGINT)")
    s.execute("INSERT INTO dim VALUES " +
              ", ".join(f"({i}, {i % 5})" for i in range(600)))

    def new_session():
        ss = eng.new_session()
        ss.vars["tidb_tpu_engine"] = "on"
        ss.vars["tidb_tpu_row_threshold"] = 1
        return ss

    yield eng, new_session
    failpoint.disable_all()
    # restore the fault-free fast path for the rest of the suite: the
    # pool is a process singleton, and a lingering health record would
    # put every later test on the (behavior-identical but guarded)
    # degraded-placement branch
    with POOL.health._lock:
        POOL.health._rec.clear()
    eng.close()


def _ctr_sum(name: str) -> int:
    return sum(v for (n, _lbl), v in REGISTRY.counters.items()
               if n == name)


def _counter(name: str, dev: int):
    return REGISTRY.counters.get((name, (("device", str(dev)),)), 0)


def _dev_of(a):
    ds = getattr(a, "devices", None)
    if callable(ds):
        got = list(a.devices())
        assert len(got) == 1
        return got[0]
    return a.device


# ---------------------------------------------------------------------------
# fault-free fast path + single-slot refusal
# ---------------------------------------------------------------------------

def test_fault_free_pod_stays_on_fast_path(pod):
    """No faults → no health records: active() stays False through
    serving, placement lands on device 0 exactly as before the fault
    domain existed, and stats report healthy with no fault fields."""
    eng, new_session = pod
    s = new_session()
    assert not POOL.health.active()
    assert s.query(DIM_SQL).rows
    assert s.last_guard.device_index == 0
    assert not POOL.health.active()
    d0 = POOL.stats()["devices"]["device0"]
    assert d0["healthy"] is True
    assert "faults" not in d0 and "readmissions" not in d0


def test_single_slot_pool_refuses_quarantine():
    """A pool of one keeps serving: report_fault refuses the last
    healthy device and leaves no record behind (the typed DeviceLost
    surfaces to the caller instead)."""
    p = SchedulerPool(1)
    assert p.health.report_fault(0, RuntimeError("x")) is False
    assert not p.health.active()
    assert p.health.healthy(0)


def test_last_healthy_device_never_quarantined(pod):
    """With every other device already out, the last healthy member
    refuses quarantine — a fully degraded pod still serves."""
    eng, new_session = pod
    s = new_session()
    s.query(DIM_SQL)                       # sizes the pool to the mesh
    n = POOL.size()
    assert n >= 2
    for i in range(n - 1):
        assert POOL.health.report_fault(i, RuntimeError("test: dead"))
    assert POOL.health.report_fault(n - 1, RuntimeError("test: dead")) \
        is False
    assert POOL.health.healthy(n - 1)
    assert s.query(DIM_SQL).rows           # the survivor serves


# ---------------------------------------------------------------------------
# in-flight DeviceLost: classify, quarantine, retry once
# ---------------------------------------------------------------------------

def test_device_lost_dispatch_retries_once_on_survivor(pod):
    """The dispatch boundary fault classifies into DeviceLost: the
    placed device is quarantined, the statement retries ONCE on a
    survivor, answers the oracle, and records the retryable 1105
    warning + migration accounting."""
    eng, new_session = pod
    s = new_session()
    oracle = s.query(DIM_SQL).rows         # warm → home is device 0
    mig0 = _ctr_sum("tidb_tpu_statements_migrated_total")
    q0 = _counter("tidb_tpu_device_quarantines_total", 0)
    # hold the readmission gate shut: placement runs opportunistic
    # probes, and on the CPU mesh a bare probe would heal device 0
    # right back mid-test
    failpoint.enable("device-readmit",
                     raise_=RuntimeError("test: still dead"))
    failpoint.enable("device-lost-dispatch",
                     raise_=RuntimeError("test: device lost"), times=1)
    try:
        rows = s.query(DIM_SQL).rows
    finally:
        failpoint.disable("device-lost-dispatch")
        failpoint.disable("device-readmit")
    assert rows == oracle
    g = s.last_guard
    assert g.sched_migrated == 1
    assert g.device_index != 0             # survivor, not the victim
    assert not POOL.health.healthy(0)
    snap = POOL.health.snapshot()
    assert snap[0]["faults"] == 1 and snap[0]["quarantined"]
    assert _counter("tidb_tpu_device_quarantines_total", 0) == q0 + 1
    assert _ctr_sum("tidb_tpu_statements_migrated_total") == mig0 + 1
    warns = s.query("SHOW WARNINGS").rows
    assert any(int(w[1]) == 1105 and "lost" in str(w[2]) for w in warns), \
        warns
    # the dead device's cache shard was evicted with the quarantine
    tid = eng.catalog.info_schema.table("dim").id
    assert not any(k[0] == 0 and k[1] == id(eng.store) and k[2] == tid
                   for k in dc.CACHE), \
        "quarantine must evict the dead device's cache shard"


def test_device_lost_upload_classifies_and_heals(pod):
    """A transfer fault while the COLD shard streams in classifies at
    the upload boundary: same quarantine + one-retry contract, and the
    survivor's re-stream serves the oracle."""
    eng, new_session = pod
    s = new_session()
    s.vars["tidb_tpu_engine"] = "off"
    oracle = s.query(DIM_SQL).rows
    s.vars["tidb_tpu_engine"] = "on"
    failpoint.enable("device-readmit",
                     raise_=RuntimeError("test: still dead"))
    failpoint.enable("device-lost-upload",
                     raise_=RuntimeError("test: transfer fault"), times=1)
    try:
        rows = s.query(DIM_SQL).rows
        assert POOL.health.quarantined_indexes()
    finally:
        failpoint.disable("device-lost-upload")
        failpoint.disable("device-readmit")
    assert failpoint.hits("device-lost-upload") >= 1
    assert rows == oracle
    assert s.last_guard.sched_migrated == 1
    assert s.query(DIM_SQL).rows == oracle     # warm on the survivor


def test_second_device_loss_surfaces_typed_error(pod):
    """The retry is ONCE: a fault that also kills the survivor attempt
    surfaces the typed retryable DeviceLost — never a silent CPU re-run
    that would hide a dead pod."""
    eng, new_session = pod
    s = new_session()
    s.query(DIM_SQL)
    failpoint.enable("device-lost-dispatch",
                     raise_=RuntimeError("test: device lost"))
    try:
        with pytest.raises(DeviceLost) as ei:
            s.query(DIM_SQL)
    finally:
        failpoint.disable("device-lost-dispatch")
    assert ei.value.code == 1105 and ei.value.retryable
    assert failpoint.hits("device-lost-dispatch") == 2
    assert s.query(DIM_SQL).rows               # session still serves


def test_explain_analyze_shows_migrated_marker(pod):
    """EXPLAIN ANALYZE of a statement that survived a device loss shows
    the migrated marker in its runtime info."""
    eng, new_session = pod
    s = new_session()
    s.query(DIM_SQL)
    failpoint.enable("device-lost-dispatch",
                     raise_=RuntimeError("test: device lost"), times=1)
    try:
        rows = s.query("EXPLAIN ANALYZE " + DIM_SQL).rows
    finally:
        failpoint.disable("device-lost-dispatch")
    text = "\n".join(str(c) for r in rows for c in r)
    assert "migrated:1" in text, text


# ---------------------------------------------------------------------------
# quarantine drains the dead device's queue
# ---------------------------------------------------------------------------

def test_quarantine_drains_queued_waiters_to_survivors(pod):
    """Waiters queued on a device when it is quarantined migrate to
    healthy survivors, run exactly once, answer the oracle — and the
    moves are counted as migrations, not steals."""
    eng, new_session = pod
    warm = new_session()
    oracle = warm.query(DIM_SQL).rows
    dev0 = POOL.schedulers[0]
    mig0 = _ctr_sum("tidb_tpu_statements_migrated_total")
    steals0 = sum(sch.stats()["steals"] for sch in POOL.schedulers)

    n = 6
    sessions = [new_session() for _ in range(n)]
    results: dict = {}

    def worker(i):
        try:
            results[i] = sessions[i].query(DIM_SQL).rows
        except TiDBTPUError as e:
            results[i] = ("error", getattr(e, "code", None))

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(n)]
    dev0.acquire(conn_id=-1)
    try:
        for th in threads:
            th.start()
        deadline = time.monotonic() + 15.0
        while True:
            with dev0._cv:
                if dev0._stealable >= n:
                    break
            assert time.monotonic() < deadline, "waiters never parked"
            time.sleep(0.005)
        assert POOL.health.report_fault(0, RuntimeError("test: dead"))
        for th in threads:
            th.join(30.0)
            assert not th.is_alive(), "migrated waiter hung"
    finally:
        dev0.release()
    assert all(results.get(i) == oracle for i in range(n)), results
    assert all(sessions[i].last_guard.device_index != 0
               for i in range(n))
    assert _ctr_sum("tidb_tpu_statements_migrated_total") >= mig0 + n
    assert sum(sch.stats()["steals"] for sch in POOL.schedulers) \
        == steals0


def test_steal_race_quarantine_drain_migrates_exactly_once(pod):
    """Satellite 1: a release-into-empty steal racing the quarantine
    drain of the same home queue — both claim through _claim_waiter
    under the home lock, so the waiter is migrated exactly once, runs
    exactly once, and total (steal + migration) accounting is 1."""
    eng, new_session = pod
    s = new_session()
    oracle = s.query(DIM_SQL).rows         # warm → home is device 0
    dev0, dev1 = POOL.schedulers[0], POOL.schedulers[1]
    mig0 = _ctr_sum("tidb_tpu_statements_migrated_total")
    steals0 = sum(sch.stats()["steals"] for sch in POOL.schedulers)
    result: dict = {}

    def rerun():
        try:
            result["rows"] = s.query(DIM_SQL).rows
        except TiDBTPUError as e:  # pragma: no cover — must not happen
            result["err"] = e

    barrier = threading.Barrier(2)

    def do_steal():
        barrier.wait()
        result["stole"] = POOL.steal_into(dev1)

    def do_drain():
        barrier.wait()
        result["quarantined"] = \
            POOL.health.report_fault(0, RuntimeError("test: dead"))

    dev0.acquire(conn_id=-1)
    try:
        th = threading.Thread(target=rerun, daemon=True)
        th.start()
        deadline = time.monotonic() + 10.0
        while True:
            with dev0._cv:
                if dev0._stealable >= 1:
                    break
            assert time.monotonic() < deadline, "waiter never parked"
            time.sleep(0.005)
        racers = [threading.Thread(target=do_steal),
                  threading.Thread(target=do_drain)]
        for r in racers:
            r.start()
        for r in racers:
            r.join(10.0)
            assert not r.is_alive()
    finally:
        dev0.release()
    th.join(15.0)
    assert not th.is_alive(), "raced waiter hung"
    assert result.get("rows") == oracle
    assert result.get("quarantined") is True
    moved = (_ctr_sum("tidb_tpu_statements_migrated_total") - mig0) + \
        (sum(sch.stats()["steals"] for sch in POOL.schedulers) - steals0)
    assert moved == 1, f"waiter must migrate exactly once, moved={moved}"


# ---------------------------------------------------------------------------
# lifecycle on a migrated waiter (satellite 3)
# ---------------------------------------------------------------------------

def _park_migrate(pod, act):
    """Park one victim statement on device 0 (all pool slots held),
    quarantine device 0 so the waiter migrates to a held survivor's
    queue, then run `act(victim)` and return the victim's outcome."""
    eng, new_session = pod
    victim = new_session()
    victim.query(DIM_SQL)                  # warm → home is device 0
    scheds = list(POOL.schedulers)
    result: dict = {}

    def run_victim():
        try:
            victim.execute(DIM_SQL)
            result["outcome"] = "completed"
        except TiDBTPUError as e:
            result["outcome"] = "error"
            result["code"] = getattr(e, "code", None)

    for sch in scheds:
        sch.acquire(conn_id=-1)
    try:
        th = threading.Thread(target=run_victim, daemon=True)
        th.start()
        deadline = time.monotonic() + 10.0
        while True:
            with scheds[0]._cv:
                if scheds[0]._stealable >= 1:
                    break
            assert time.monotonic() < deadline, "victim never parked"
            time.sleep(0.005)
        assert POOL.health.report_fault(0, RuntimeError("test: dead"))
        # migrated onto SOME held survivor's queue (depth 2 = holder +
        # the migrant)
        while not any(sch.queue_depth() > 1 for sch in scheds[1:]):
            assert time.monotonic() < deadline, "migrant never queued"
            time.sleep(0.005)
        t_act = time.monotonic()
        act(victim, new_session)
        th.join(10.0)
        assert not th.is_alive(), "migrated waiter hung"
        assert time.monotonic() - t_act < 5.0
    finally:
        for sch in scheds:
            sch.release()
    assert all(sch.queue_depth() == 0 for sch in scheds)
    return victim, result


def test_kill_lands_on_waiter_migrated_off_quarantined_device(pod):
    """KILL while queued on the migrated-to device: typed 1317."""
    def kill(victim, new_session):
        new_session().execute(f"KILL QUERY {victim.conn_id}")

    victim, result = _park_migrate(pod, kill)
    assert result.get("outcome") == "error", result
    assert result.get("code") == 1317, result
    assert victim.query(DIM_SQL).rows      # session still serves


def test_deadline_lands_on_waiter_migrated_off_quarantined_device(pod):
    """max_execution_time expiring while queued on the migrated-to
    device: typed 3024 (the deadline was armed at admission and rides
    the migration)."""
    def expire(victim, _new_session):
        victim.last_guard.deadline = time.monotonic()

    victim, result = _park_migrate(pod, expire)
    assert result.get("outcome") == "error", result
    assert result.get("code") == 3024, result
    assert victim.query(DIM_SQL).rows


# ---------------------------------------------------------------------------
# cache re-homing (evict_device on a pod-partitioned entry)
# ---------------------------------------------------------------------------

def test_evict_device_rehomes_lost_slabs_onto_survivors(pod):
    """Losing one owner of a pod-partitioned entry nulls ONLY its slab
    ranges (holes + `lost`), re-owns them onto survivors, frees the
    dead buffers, and keeps every untouched owner's arrays by identity;
    the next touch refills exactly the lost slabs onto the new owners
    and still answers the oracle."""
    import jax
    eng, new_session = pod
    s = new_session()
    s.execute("CREATE TABLE facts (a BIGINT, g BIGINT)")
    for base in range(0, 8192, 1024):
        s.execute("INSERT INTO facts VALUES " + ", ".join(
            f"({i}, {i % 7})" for i in range(base, base + 1024)))
    s.vars["tidb_tpu_max_slab_rows"] = 1024
    s.vars["tidb_tpu_partition_min_rows"] = 1000
    full = "SELECT g, COUNT(*), SUM(a) FROM facts GROUP BY g ORDER BY g"
    s.vars["tidb_tpu_engine"] = "off"
    oracle = s.query(full).rows
    s.vars["tidb_tpu_engine"] = "on"
    assert s.query(full).rows == oracle

    tid = eng.catalog.info_schema.table("facts").id
    key = next(k for k in dc.CACHE
               if k[0] == -1 and k[1] == id(eng.store) and k[2] == tid)
    ent = dc.CACHE[key]
    owners0 = list(ent.owners)
    assert len(set(owners0)) > 1
    victim = owners0[0]
    lost = {si for si, o in enumerate(owners0) if o == victim}
    kept = {i: {si: t for si, t in enumerate(slabs)
                if t is not None and si not in lost}
            for i, slabs in ent.dev.items()}
    victim_arrays = [a for slabs in ent.dev.values()
                     for si in sorted(lost) if slabs[si] is not None
                     for a in slabs[si]]
    assert victim_arrays
    survivors = [d for d in range(POOL.size()) if d != victim]

    dc.evict_device(victim, survivors)
    assert ent.lost == lost
    assert all(o != victim for o in ent.owners)
    for i, slabs in ent.dev.items():
        for si in lost:
            assert slabs[si] is None       # lost range nulled
        for si, t in kept[i].items():
            assert slabs[si] is t          # untouched slabs untouched
            assert ent.owners[si] == owners0[si]
    assert all(a.is_deleted() for a in victim_arrays), \
        "dead owner's buffers must be freed NOW, not at GC time"

    # next touch: partial refill of EXACTLY the lost slabs, onto the
    # re-homed owners — untouched arrays stay by identity
    assert s.query(full).rows == oracle
    ent2 = dc.CACHE[key]
    assert ent2 is ent, "partial refill must reuse the entry in place"
    assert not ent.lost
    devs = jax.devices()
    for i, slabs in ent.dev.items():
        for si, t in enumerate(slabs):
            if t is None:
                continue
            for a in t:
                assert _dev_of(a) == devs[ent.owners[si]], \
                    f"col {i} slab {si} off its re-homed owner"
        for si, t in kept[i].items():
            assert slabs[si] is t, "untouched slab was re-uploaded"


# ---------------------------------------------------------------------------
# readmission
# ---------------------------------------------------------------------------

class _G:
    """Bare placement guard stub (no pin, no table profile)."""


def test_readmission_gated_by_probe_then_rejoins(pod):
    """An armed device-readmit gate keeps the device quarantined (the
    flap budget is charged); once the gate clears, the next due probe
    readmits it and least-depth placement returns to device 0."""
    eng, new_session = pod
    s = new_session()
    s.query(DIM_SQL)                       # sizes the pool
    failpoint.enable("device-readmit",
                     raise_=RuntimeError("test: still dead"))
    try:
        assert POOL.health.report_fault(0, RuntimeError("test: dead"))
        assert POOL.place_statement(_G(), conn_id=0) != 0
        deadline = time.monotonic() + 5.0
        while failpoint.hits("device-readmit") == 0:
            assert time.monotonic() < deadline, "probe never ran"
            POOL.health.maybe_readmit()
            time.sleep(0.01)
        assert not POOL.health.healthy(0), \
            "an armed probe gate must keep the device out"
    finally:
        failpoint.disable("device-readmit")

    deadline = time.monotonic() + 10.0
    while not POOL.health.healthy(0):
        assert time.monotonic() < deadline, "device never readmitted"
        POOL.health.maybe_readmit()
        time.sleep(0.01)
    snap = POOL.health.snapshot()
    assert snap[0]["readmissions"] == 1
    assert not snap[0]["quarantined"]
    # placements return: no votes, all queues idle → least depth picks
    # the lowest healthy index again
    assert POOL.place_statement(_G(), conn_id=0) == 0
    d0 = POOL.stats()["devices"]["device0"]
    assert d0["healthy"] is True and d0["readmissions"] == 1
