"""Finalizers that run inside the collector must not take a lock, and one
engine's resident tables must not steer another engine's statements.

These faults made the tier-1 run unsteady before PR 23:

* `ProcessRegistry` dropped a collected Session from its table in a
  `weakref.finalize` callback that took the registry's plain Lock. The
  collector runs finalizers on whatever thread allocates next; when that
  thread was already inside a locked block of the registry it waited on
  itself for ever, and every later statement, KILL and SHOW PROCESSLIST
  of the process queued behind it (the driver's run was cut at 1470 s).
* the device cache evicted a collected store the same way, re-entrantly
  under its RLock, popping `_CACHE` entries under a holder that was
  iterating it.
* the scheduler's locality oracle matched cached tables by table id
  alone. Table ids restart per engine, so a table left resident on
  device 1 by an engine that is dead but not yet collected pulled the
  next engine's statements to device 1 — `test_degraded_pod.py`'s parking
  tests, which hold device 0 and wait for waiters there, then waited in
  vain ("waiters never parked"), depending on when the collector ran.
"""

import gc
import threading

from tidb_tpu.executor import device_cache as dc
from tidb_tpu.executor.scheduler import POOL
from tidb_tpu.session import Engine
from tidb_tpu.util.guard import ProcessRegistry

DIM_SQL = "SELECT g, COUNT(*), SUM(a) FROM dim GROUP BY g ORDER BY g"


class _Sess:
    """Stand-in for Session: a conn id, a user, weakref-able."""

    def __init__(self, cid):
        self.conn_id = cid
        self.user = "u"


def _run_bounded(fn, bound_s=10.0):
    """Run `fn` on a thread; False if it is still blocked after the bound
    (a deadlocked thread cannot be reclaimed — it is left as a daemon)."""
    th = threading.Thread(target=fn, daemon=True)
    th.start()
    th.join(bound_s)
    return not th.is_alive()


def test_registry_finalizer_inside_locked_block_does_not_deadlock():
    reg = ProcessRegistry()
    live = _Sess(1)
    reg.register(live)
    reg.kill(1, query_only=False)      # stmt_begin now kills under the lock
    dead = _Sess(2)
    reg.register(dead)
    dead.cycle = dead                  # only the collector can free it
    del dead

    class _Guard:
        """stmt_begin calls kill() INSIDE the registry's locked block for
        a killed connection — the collection lands exactly there."""

        def kill(self):
            gc.collect()

    gc.disable()                       # nothing frees `dead` early
    try:
        assert _run_bounded(lambda: reg.stmt_begin(1, _Guard())), \
            "stmt_begin deadlocked on its own finalizer"
    finally:
        gc.enable()
    # the collected session is gone for every reader, the live one stays
    assert reg.info(2) is None
    assert reg.kill(2) is False
    assert [cid for cid, *_ in reg.snapshot()] == [1]
    assert reg.info(1)["session"] is live


def test_registry_forgets_collected_sessions():
    reg = ProcessRegistry()
    keep = [_Sess(i) for i in range(3)]
    for s in keep:
        reg.register(s)
    del keep[1]
    gc.collect()
    assert [cid for cid, *_ in reg.snapshot()] == [0, 2]
    assert reg.info(1) is None and not reg.conn_killed(1)
    assert 1 not in reg._conns         # reaped, not just hidden


def _pod_engine():
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    s.execute("CREATE TABLE dim (a BIGINT, g BIGINT)")
    s.execute("INSERT INTO dim VALUES " +
              ", ".join(f"({i}, {i % 5})" for i in range(600)))
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    return eng, s


def test_store_collected_under_cache_lock_defers_eviction():
    eng, s = _pod_engine()
    assert s.query(DIM_SQL).rows
    sid = id(eng.store)
    assert any(k[1] == sid for k in dc.CACHE)
    eng.close()
    holder = [eng, s]
    del eng, s
    with dc.LOCK:
        for _k in dc.CACHE:           # a holder mid-iteration
            holder.clear()
            gc.collect()               # the store dies HERE
        # the finalizer only queued the id: nothing changed under us
        assert any(k[1] == sid for k in dc.CACHE)
    # the next cache entry point reaps it
    assert dc.storage_stats(sid) == []
    assert not any(k[1] == sid for k in dc.CACHE)
    assert sid not in dc._STORE_FINALIZERS


def test_locality_oracle_is_scoped_to_the_statements_store():
    """Engine A leaves `dim` (table id N) resident on a device other than
    0; engine B's own `dim` has the same id. B's statement of the same
    digest must be placed as if A did not exist: least depth → device 0
    (unscoped, A's copy attracted it)."""
    sql = "SELECT g, MAX(a) FROM dim GROUP BY g"   # digest of this file only
    eng_a, sa = _pod_engine()
    done = {}
    # device 0 busy + unknown digest → least depth places A elsewhere
    POOL.schedulers[0].acquire(conn_id=-1)
    try:
        th = threading.Thread(
            target=lambda: done.update(rows=sa.query(sql).rows),
            daemon=True)
        th.start()
        th.join(30.0)
    finally:
        POOL.schedulers[0].release()
    assert "rows" in done
    dev_a = sa.last_guard.device_index
    assert dev_a != 0
    tid = eng_a.catalog.info_schema.table("dim").id
    assert dc.locate_tables([tid], id(eng_a.store)) == {tid: {dev_a}}

    eng_b, sb = _pod_engine()
    try:
        assert eng_b.catalog.info_schema.table("dim").id == tid
        assert dc.locate_tables([tid], id(eng_b.store)) == {}
        assert sb.query(sql).rows == done["rows"]   # digest known, A alive
        assert sb.last_guard.device_index == 0
        assert dc.locate_tables([tid], id(eng_b.store)) == {tid: {0}}
        # unscoped, the oracle still sees every store
        assert {0, dev_a} <= dc.locate_tables([tid])[tid]
    finally:
        eng_a.close()
        eng_b.close()


def test_every_test_runs_under_the_alarm_bound(_bounded):
    import signal
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert _bounded is not None and 0.0 < left <= _bounded
