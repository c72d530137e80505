"""Pod-scale serving: per-device HBM caches, locality-aware placement,
replication vs partitioning, and work stealing (over the conftest's
forced 8-device CPU mesh, where `tidb_tpu_device_queues=auto` activates
the pool for the whole suite).

Pins the PR's acceptance contract:

* locality routing: a repeat digest routes to the device already
  holding its tables — even when that queue is deeper — so a warm dim
  table is uploaded exactly ONCE pool-wide (no thundering replicas);
* replication: a second device touching the same small table lazily
  builds its own replica, counted by `tidb_tpu_table_replicas_total`
  and visible to `locate_tables`;
* partitioning: a fact table past `tidb_tpu_partition_min_rows` gets
  ONE pod-wide entry (cache key device -1) whose slab ranges spread
  contiguously across the mesh — each resident slab's buffers live on
  exactly its owner device, never double-resident — and the routed
  result stays byte-exact vs the CPU oracle;
* work stealing: an idle sibling drains a 16-deep admission queue while
  the home device stays held (every waiter migrates, none lost, none
  run twice);
* lifecycle on a STOLEN waiter: KILL lands as a typed 1317 while the
  migrated statement is queued on its new device;
* steal-migrate fault: an injected fault at the handoff re-queues the
  waiter on its HOME device (backoff charged) — the statement still
  runs exactly once and answers the oracle.
"""

import threading
import time

import pytest

from tidb_tpu.errors import TiDBTPUError
from tidb_tpu.executor import device_cache as dc
from tidb_tpu.executor.scheduler import POOL
from tidb_tpu.session import Engine
from tidb_tpu.util import failpoint
from tidb_tpu.util.observability import REGISTRY

DIM_SQL = "SELECT g, COUNT(*), SUM(a) FROM dim GROUP BY g ORDER BY g"


@pytest.fixture()
def pod():
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
    eng.close()


def _counter(name: str, dev: int):
    return REGISTRY.counters.get((name, (("device", str(dev)),)), 0)


def _table_keys(eng, name: str):
    tid = eng.catalog.info_schema.table(name).id
    return [k for k in dc.CACHE
            if k[1] == id(eng.store) and k[2] == tid]


def _dev_of(a):
    """The single jax device an array is committed to."""
    ds = getattr(a, "devices", None)
    if callable(ds):
        got = list(a.devices())
        assert len(got) == 1
        return got[0]
    return a.device


# ---------------------------------------------------------------------------
# locality routing + lazy replication
# ---------------------------------------------------------------------------

def test_repeat_digest_routes_to_resident_device(pod):
    """Warm digest → locality placement beats least-queue-depth: the
    statement waits for device 0 (where its table lives) instead of
    hopping to an idle sibling, so the dim table uploads exactly once
    pool-wide."""
    eng, new_session = pod
    s = new_session()
    assert s.query(DIM_SQL).rows  # cold: all queues idle → device 0
    assert s.last_guard.device_index == 0
    assert POOL.size() >= 8       # auto sized the pool to the mesh
    keys = _table_keys(eng, "dim")
    assert len(keys) == 1 and keys[0][0] == 0

    oracle = s.query(DIM_SQL).rows
    result: dict = {}

    def rerun():
        try:
            result["rows"] = s.query(DIM_SQL).rows
        except TiDBTPUError as e:  # pragma: no cover — must not happen
            result["err"] = e

    # device 0 busy, devices 1..7 idle: least-depth would route away,
    # locality must NOT
    POOL.schedulers[0].acquire(conn_id=-1)
    try:
        th = threading.Thread(target=rerun, daemon=True)
        th.start()
        deadline = time.monotonic() + 10.0
        while POOL.schedulers[0].queue_depth() < 2:
            assert time.monotonic() < deadline, "repeat never queued"
            time.sleep(0.005)
    finally:
        POOL.schedulers[0].release()
    th.join(10.0)
    assert not th.is_alive() and result.get("rows") == oracle
    assert s.last_guard.device_index == 0
    # still exactly one resident copy — routing made replication moot
    assert _table_keys(eng, "dim") == keys


def test_cold_digest_on_busy_device_builds_replica(pod):
    """A DIFFERENT digest over the same table, placed while device 0 is
    busy, lands on an idle sibling and lazily replicates the table
    there — counted and locatable."""
    eng, new_session = pod
    s = new_session()
    s.query(DIM_SQL)                      # dim resident on device 0
    tid = eng.catalog.info_schema.table("dim").id
    before = _counter("tidb_tpu_table_replicas_total", 1)

    s2 = new_session()
    cold = "SELECT g, COUNT(*) FROM dim WHERE a < 500 GROUP BY g"
    result: dict = {}

    def run_cold():
        try:
            result["rows"] = s2.query(cold).rows
        except TiDBTPUError as e:  # pragma: no cover
            result["err"] = e

    POOL.schedulers[0].acquire(conn_id=-1)
    try:
        th = threading.Thread(target=run_cold, daemon=True)
        th.start()
        th.join(10.0)
    finally:
        POOL.schedulers[0].release()
    assert not th.is_alive() and "rows" in result
    assert s2.last_guard.device_index == 1    # least depth, lowest idx
    devs = {k[0] for k in _table_keys(eng, "dim")}
    assert devs == {0, 1}, devs
    assert dc.locate_tables([tid]).get(tid) == {0, 1}
    assert _counter("tidb_tpu_table_replicas_total", 1) == before + 1
    assert dc.replica_overhead_bytes() > 0


# ---------------------------------------------------------------------------
# pod-partitioned fact table
# ---------------------------------------------------------------------------

def test_partitioned_fact_slabs_spread_single_resident(pod):
    """A fact table past tidb_tpu_partition_min_rows gets ONE pod-wide
    cache entry: contiguous slab ranges owned per device, each resident
    slab's buffers on exactly its owner, results byte-exact vs CPU."""
    import jax
    eng, new_session = pod
    s = new_session()
    s.execute("CREATE TABLE facts (a BIGINT, g BIGINT)")
    for base in range(0, 8192, 1024):
        s.execute("INSERT INTO facts VALUES " + ", ".join(
            f"({i}, {i % 7})" for i in range(base, base + 1024)))
    s.vars["tidb_tpu_max_slab_rows"] = 1024
    s.vars["tidb_tpu_partition_min_rows"] = 1000

    sel = "SELECT COUNT(*), SUM(a) FROM facts WHERE a >= 1024"
    full = "SELECT g, COUNT(*), SUM(a) FROM facts GROUP BY g ORDER BY g"
    s.vars["tidb_tpu_engine"] = "off"
    oracle = {q: s.query(q).rows for q in (sel, full)}
    s.vars["tidb_tpu_engine"] = "on"
    for q in (sel, full):
        assert s.query(q).rows == oracle[q], q

    keys = _table_keys(eng, "facts")
    assert len(keys) == 1 and keys[0][0] == -1, keys
    ent = dc.CACHE[keys[0]]
    owners = ent.owners
    assert owners is not None and len(owners) == 8
    # contiguous non-decreasing ranges over the mesh
    assert owners == sorted(owners) and len(set(owners)) > 1
    devs = jax.devices()
    for i, slabs in ent.dev.items():
        for sl, t in enumerate(slabs):
            if t is None:
                continue                  # cold-pruned hole
            for arr in t:
                assert _dev_of(arr) == devs[owners[sl]], \
                    f"col {i} slab {sl} off its owner device"


# ---------------------------------------------------------------------------
# work stealing
# ---------------------------------------------------------------------------

def test_steal_drains_deep_queue_while_home_idles(pod):
    """16 batch statements parked on a held device 0 all migrate to
    idle siblings — via the release-into-empty pull chain and the
    patience-based self-spill — the queue drains with device 0 never
    granting, and every result matches the oracle."""
    eng, new_session = pod
    warm = new_session()
    oracle = warm.query(DIM_SQL).rows      # dim → device 0, digest warm
    dev0, dev1 = POOL.schedulers[0], POOL.schedulers[1]
    steals0 = sum(s.stats()["steals"] for s in POOL.schedulers)
    ctr0 = sum(_counter("tidb_tpu_work_steals_total", d)
               for d in range(POOL.size()))
    adm0 = dev0.stats()["admissions"]

    n = 16
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
        # kick the pull chain immediately (before the patience spill):
        # device 1's release-into-empty steals the first parked waiter
        deadline = time.monotonic() + 15.0
        while True:
            with dev0._cv:
                if dev0._stealable >= 1:
                    break
            assert time.monotonic() < deadline, "no waiter parked"
            time.sleep(0.005)
        dev1.acquire(conn_id=-1)
        dev1.release()
        for th in threads:
            th.join(30.0)
            assert not th.is_alive(), "stolen statement hung"
    finally:
        dev0.release()
    assert all(results[i] == oracle for i in range(n)), results
    # every one of the 16 migrated exactly once (device 0 never granted
    # a single statement — it was held throughout) and landed off-home
    steals = sum(s.stats()["steals"] for s in POOL.schedulers) - steals0
    ctr = sum(_counter("tidb_tpu_work_steals_total", d)
              for d in range(POOL.size())) - ctr0
    assert steals == n and ctr == n
    # +1 is this test's own hold — no STATEMENT was granted on device 0
    assert dev0.stats()["admissions"] == adm0 + 1
    assert all(sessions[i].last_guard.device_index != 0 for i in range(n))
    # aggregate stats expose the per-device breakdown
    agg = POOL.stats()
    assert agg["steals"] >= n and "device1" in agg["devices"]


def test_kill_lands_on_stolen_waiter(pod):
    """KILL while queued on the STOLEN-to device: typed 1317 within
    ~2s, and both queues are clean afterwards."""
    eng, new_session = pod
    victim = new_session()
    victim.query(DIM_SQL)                 # warm → locality pins device 0
    killer = new_session()
    dev0, dev1 = POOL.schedulers[0], POOL.schedulers[1]
    result: dict = {}

    def run_victim():
        try:
            victim.execute(DIM_SQL)
            result["outcome"] = "completed"
        except TiDBTPUError as e:
            result["outcome"] = "error"
            result["code"] = getattr(e, "code", None)

    dev0.acquire(conn_id=-1)
    dev1.acquire(conn_id=-1)
    try:
        th = threading.Thread(target=run_victim, daemon=True)
        th.start()
        deadline = time.monotonic() + 10.0
        while True:
            with dev0._cv:
                if dev0._stealable >= 1:
                    break
            assert time.monotonic() < deadline, "victim never parked"
            time.sleep(0.005)
        assert POOL.steal_into(dev1)      # migrate; dev1 held → re-queues
        while dev1.queue_depth() < 2:
            assert time.monotonic() < deadline, "migrant never queued"
            time.sleep(0.005)
        t_kill = time.monotonic()
        killer.execute(f"KILL QUERY {victim.conn_id}")
        th.join(10.0)
        assert not th.is_alive(), "KILLed stolen waiter hung"
        assert result.get("outcome") == "error", result
        assert result.get("code") == 1317, result
        assert time.monotonic() - t_kill < 2.0
    finally:
        dev1.release()
        dev0.release()
    assert dev0.queue_depth() == 0 and dev1.queue_depth() == 0
    assert victim.query(DIM_SQL).rows    # session still serves


def test_steal_migrate_fault_requeues_home(pod):
    """An injected fault at the steal handoff re-queues the waiter on
    its HOME device with the backoff charged — the statement runs
    exactly once, on home, and answers the oracle."""
    eng, new_session = pod
    s = new_session()
    oracle = s.query(DIM_SQL).rows        # warm → home is device 0
    dev0, dev1 = POOL.schedulers[0], POOL.schedulers[1]
    steals0 = dev1.stats()["steals"]
    ctr0 = _counter("tidb_tpu_work_steals_total", 1)
    result: dict = {}

    def rerun():
        try:
            result["rows"] = s.query(DIM_SQL).rows
        except TiDBTPUError as e:
            result["err"] = e

    failpoint.enable("steal-migrate",
                     raise_=RuntimeError("test: handoff fault"), times=1)
    failpoint.enable("backoff-sleep", value="skip")
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
        assert POOL.steal_into(dev1)
        # the fault bounces it home: back on device 0's queue, no
        # longer steal-eligible
        while True:
            with dev0._cv:
                if dev0._queue and dev0._stealable == 0:
                    break
            assert time.monotonic() < deadline, "waiter never came home"
            time.sleep(0.005)
    finally:
        dev0.release()
        failpoint.disable_all()
    th.join(10.0)
    assert not th.is_alive()
    assert result.get("rows") == oracle
    assert s.last_guard.device_index == 0          # ran at home
    assert failpoint.hits("steal-migrate") == 1
    assert dev1.stats()["steals"] == steals0       # never counted
    assert _counter("tidb_tpu_work_steals_total", 1) == ctr0
