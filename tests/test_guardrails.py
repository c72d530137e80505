"""Query lifecycle guardrails: cooperative KILL, statement timeouts,
OOM/spill cancellation, retry budgets, and the chaos sweep (ref:
util/sqlkiller/sqlkiller.go, executor/executor.go QueryTimeLimit,
server's killConn path)."""

import threading
import time

import pytest

from tidb_tpu.errors import (BackoffExhausted, MemoryQuotaExceeded,
                             NoSuchThreadError, QueryInterrupted,
                             QueryTimeout, TxnError)
from tidb_tpu.session import Engine
from tidb_tpu.util import failpoint
from tidb_tpu.util.guard import PROCESS_REGISTRY


@pytest.fixture(scope="module")
def eng():
    e = Engine()
    s = e.new_session()
    s.execute("CREATE TABLE gt (a BIGINT, b BIGINT, c VARCHAR(24))")
    for base in range(0, 6000, 1000):
        vals = ", ".join(f"({i}, {i % 7}, 'v{i:05d}')"
                         for i in range(base, base + 1000))
        s.execute(f"INSERT INTO gt VALUES {vals}")
    yield e
    e.close()


@pytest.fixture()
def session(eng):
    s = eng.new_session()
    saved = dict(s.vars)
    yield s
    failpoint.disable_all()
    s.vars.clear()
    s.vars.update(saved)


# ---- cooperative KILL ------------------------------------------------------

def test_kill_query_mid_next(session):
    """KILL QUERY flips the guard; the NEXT chunk boundary raises 1317
    and the session survives to run the following statement."""
    s = session
    with failpoint.enabled(
            "scan-next",
            hook=lambda: PROCESS_REGISTRY.kill(s.conn_id,
                                               query_only=True)):
        with pytest.raises(QueryInterrupted) as ei:
            s.query("SELECT COUNT(*), SUM(a) FROM gt")
    assert ei.value.code == 1317
    g = s.last_guard                 # capture before the next statement
    # the scan polled the flag at chunk boundaries before dying
    assert sum(g.checkpoints.values()) >= 1, g.checkpoints
    # session is still usable — KILL QUERY keeps the connection
    assert s.query("SELECT COUNT(*) FROM gt").scalar() == 6000


def test_kill_query_from_other_session(eng):
    """The real shape: session B interrupts session A's running
    statement through the registry, cross-thread."""
    s1, s2 = eng.new_session(), eng.new_session()
    started = threading.Event()

    def slow_chunk():
        started.set()
        time.sleep(0.05)

    result = {}

    def victim():
        try:
            result["rows"] = s1.query("SELECT SUM(a) FROM gt").rows
        except Exception as e:  # noqa: BLE001
            result["err"] = e

    failpoint.enable("scan-next", hook=slow_chunk)
    try:
        t = threading.Thread(target=victim)
        t.start()
        assert started.wait(5.0)
        s2.execute(f"KILL QUERY {s1.conn_id}")
        t.join(10.0)
        assert not t.is_alive()
    finally:
        failpoint.disable_all()
    assert isinstance(result.get("err"), QueryInterrupted), result
    # and s1's connection survived the QUERY-only kill
    assert s1.query("SELECT 1 + 1").scalar() == 2


def test_kill_connection_poisons_session(eng):
    s1, s2 = eng.new_session(), eng.new_session()
    s2.execute(f"KILL {s1.conn_id}")
    with pytest.raises(QueryInterrupted):
        s1.query("SELECT 1")
    assert PROCESS_REGISTRY.conn_killed(s1.conn_id)


def test_kill_unknown_thread(session):
    with pytest.raises(NoSuchThreadError) as ei:
        session.execute("KILL QUERY 99999999")
    assert ei.value.code == 1094


def test_show_processlist_lists_this_connection(session):
    rows = session.query("SHOW PROCESSLIST").rows
    assert any(str(session.conn_id) == str(r[0]) for r in rows), rows


# ---- PROCESS / SUPER privileges --------------------------------------------

def test_kill_other_user_without_super_is_1095(eng):
    """MySQL's error split: unknown thread → 1094; thread exists but is
    someone else's and the killer lacks SUPER → 1095; with a global
    SUPER grant the kill goes through."""
    from tidb_tpu.errors import KillDeniedError
    root_s = eng.new_session()
    root_s.execute("CREATE USER IF NOT EXISTS killer IDENTIFIED BY 'x'")
    s_eve = eng.new_session()
    s_eve.user = "killer"
    # unknown id stays strictly 1094 — even for an unprivileged user
    with pytest.raises(NoSuchThreadError) as ei:
        s_eve.execute("KILL QUERY 99999999")
    assert ei.value.code == 1094
    # root's live thread: exists, not yours, no SUPER → 1095
    with pytest.raises(KillDeniedError) as ei:
        s_eve.execute(f"KILL QUERY {root_s.conn_id}")
    assert ei.value.code == 1095
    assert str(root_s.conn_id) in str(ei.value)
    # ...and the target was NOT killed
    assert root_s.query("SELECT 1 + 1").scalar() == 2
    # SUPER must be a *.* grant; a db-scoped one must not escalate
    root_s.execute("GRANT SUPER ON test.* TO killer")
    with pytest.raises(KillDeniedError):
        s_eve.execute(f"KILL QUERY {root_s.conn_id}")
    root_s.execute("GRANT SUPER ON *.* TO killer")
    s_eve.execute(f"KILL QUERY {root_s.conn_id}")   # idle target: no-op
    assert root_s.query("SELECT 1 + 1").scalar() == 2
    root_s.execute("DROP USER killer")


def test_processlist_requires_process_priv_to_see_others(eng):
    """Without the global PROCESS privilege SHOW PROCESSLIST (and
    information_schema.processlist) lists only the caller's own
    threads (sql/sql_show.cc mysqld_list_processes)."""
    root_s = eng.new_session()
    root_s.execute("CREATE USER IF NOT EXISTS watcher IDENTIFIED BY 'x'")
    root_s.execute("GRANT SELECT ON *.* TO watcher")
    s_w = eng.new_session()
    s_w.user = "watcher"

    def visible(sess):
        return {int(r[0]) for r in sess.query("SHOW PROCESSLIST").rows}

    assert root_s.conn_id not in visible(s_w)
    assert s_w.conn_id in visible(s_w)
    ids = {int(r[0]) for r in s_w.query(
        "SELECT ID FROM information_schema.processlist").rows}
    assert root_s.conn_id not in ids and s_w.conn_id in ids
    # root (ALL on *.*) sees everyone
    assert {root_s.conn_id, s_w.conn_id} <= visible(root_s)
    # a db-scoped PROCESS grant must not unlock the global view
    root_s.execute("GRANT PROCESS ON test.* TO watcher")
    assert root_s.conn_id not in visible(s_w)
    root_s.execute("GRANT PROCESS ON *.* TO watcher")
    assert {root_s.conn_id, s_w.conn_id} <= visible(s_w)
    root_s.execute("DROP USER watcher")


# ---- statement timeout -----------------------------------------------------

def test_max_execution_time_interrupts_multichunk_scan(session):
    s = session
    s.vars["max_execution_time"] = 60          # ms
    with failpoint.enabled("scan-next", hook=lambda: time.sleep(0.03)):
        with pytest.raises(QueryTimeout) as ei:
            s.query("SELECT COUNT(*), SUM(a) FROM gt")
    assert ei.value.code == 3024
    g = s.last_guard
    assert sum(g.checkpoints.values()) >= 1, g.checkpoints
    # clearing the var restores normal execution
    s.vars["max_execution_time"] = 0
    assert s.query("SELECT COUNT(*) FROM gt").scalar() == 6000


def test_timeout_zero_means_no_deadline(session):
    session.vars["max_execution_time"] = 0
    session.query("SELECT COUNT(*) FROM gt")
    assert session.last_guard.deadline is None


def test_timeout_scoped_to_read_only_select(session):
    """MySQL semantics: max_execution_time arms ONLY read-only SELECTs.
    A write slower than the deadline must run to completion (aborting a
    half-applied mutation on a timer would corrupt), and SELECT ... FOR
    UPDATE locks so it is exempt too — only explicit KILL stops those."""
    s = session
    s.vars["max_execution_time"] = 40          # ms
    before = s.query("SELECT COUNT(*) FROM gt").scalar()
    with failpoint.enabled("store-commit",
                           hook=lambda: time.sleep(0.08)):
        s.execute("INSERT INTO gt VALUES (100001, 1, 'slowwrite')")
    assert s.last_guard.deadline is None       # write ran unarmed
    assert s.query("SELECT COUNT(*) FROM gt").scalar() == before + 1
    s.execute("DELETE FROM gt WHERE a = 100001")
    # FOR UPDATE: exempt even though it reads
    s.query("SELECT a FROM gt WHERE a < 3 FOR UPDATE")
    assert s.last_guard.deadline is None
    # the same sysvar still times out a plain SELECT
    with failpoint.enabled("scan-next", hook=lambda: time.sleep(0.03)):
        with pytest.raises(QueryTimeout):
            s.query("SELECT COUNT(*), SUM(a) FROM gt")


def test_processlist_exposes_escalations(session):
    """information_schema.processlist grows an ESCALATIONS column fed by
    the running statement's guard (util/escalation.py EscalationStats) —
    a squeezed group cap makes the device fragment recompile, and the
    summary shows up on the SAME statement's guard."""
    s = session
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                  tidb_tpu_group_cap=64)
    # a + 0 is an expression key: no cached bounds, no NDV pre-sizing —
    # 6000 distinct values overflow cap 64 → exact-need ladder recompile
    s.query("SELECT a + 0, COUNT(*) FROM gt GROUP BY a + 0")
    esc = s.last_guard.escalation
    assert esc.recompiles >= 1 and esc.exact_resizes >= 1, esc.summary()
    assert "group:exact" in esc.summary()
    # the column exists and is well-formed for every live connection
    rows = s.query("SELECT ID, ESCALATIONS FROM "
                   "information_schema.processlist").rows
    assert any(str(r[0]) == str(s.conn_id) for r in rows), rows


# ---- lifecycle errors vs the device fallback ladder ------------------------

def test_kill_not_swallowed_by_cpu_fallback(session):
    """A lifecycle error raised while the device fragment runs must
    unwind — the generic except clause retries plain device faults on
    CPU, and before the guardrails it would have eaten the kill too."""
    s = session
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1)
    with failpoint.enabled(
            "device-fragment",
            raise_=QueryInterrupted("Query execution was interrupted")):
        with pytest.raises(QueryInterrupted):
            s.query("SELECT b, SUM(a) FROM gt GROUP BY b")
    # plain device faults still fall back quietly
    with failpoint.enabled("device-fragment",
                           raise_=RuntimeError("chaos: device down"),
                           times=1):
        rows = s.query("SELECT COUNT(*) FROM gt").rows
    assert rows == [(6000,)]
    assert s.last_guard.hits("device-dispatch") >= 1


# ---- OOM actions: spill, then cancel ---------------------------------------

def test_quota_spills_then_kill_cancels_spill(session):
    s = session
    q = ("SELECT c, COUNT(*) FROM gt GROUP BY c ORDER BY c LIMIT 3")
    s.vars["tidb_mem_quota_query"] = 8000
    # under quota pressure the agg spills and still answers correctly
    assert s.query(q).rows == [("v00000", 1), ("v00001", 1),
                               ("v00002", 1)]
    g = s.last_guard
    assert g.hits("spill") >= 1, g.checkpoints
    # a kill landing during spill I/O cancels instead of grinding on
    with failpoint.enabled(
            "spill-write",
            hook=lambda: PROCESS_REGISTRY.kill(s.conn_id,
                                               query_only=True)):
        with pytest.raises(QueryInterrupted):
            s.query(q)
    s.vars.pop("tidb_mem_quota_query")
    assert s.query("SELECT COUNT(*) FROM gt").scalar() == 6000


def test_unspillable_quota_is_typed(session):
    session.vars["tidb_mem_quota_query"] = 8000
    with failpoint.enabled("tracker-quota",
                           raise_=MemoryQuotaExceeded("chaos: quota"),
                           times=1):
        with pytest.raises(MemoryQuotaExceeded):
            session.query("SELECT c, COUNT(*) FROM gt GROUP BY c")


# ---- retry budgets ---------------------------------------------------------

def test_commit_retry_budget_exhausts(eng):
    s = eng.new_session()
    s.execute("CREATE TABLE bo (a BIGINT)")
    conflict = TxnError("chaos: hot key")
    conflict.retryable = True
    failpoint.enable("commit-conflict", raise_=conflict)
    failpoint.enable("backoff-sleep", value="skip")   # budget, no wall-clock
    try:
        with pytest.raises(BackoffExhausted) as ei:
            s.execute("INSERT INTO bo VALUES (1)")
        assert failpoint.hits("commit-conflict") > 3   # it really retried
        assert isinstance(ei.value.__cause__, TxnError)
    finally:
        failpoint.disable_all()
    # transient conflicts (heal after 2) are absorbed by the retry loop
    conflict2 = TxnError("chaos: transient")
    conflict2.retryable = True
    failpoint.enable("commit-conflict", raise_=conflict2, times=2)
    failpoint.enable("backoff-sleep", value="skip")
    try:
        s.execute("INSERT INTO bo VALUES (2)")
    finally:
        failpoint.disable_all()
    assert s.query("SELECT COUNT(*) FROM bo").scalar() == 1


# ---- ADVICE regressions ----------------------------------------------------

def test_ci_group_by_folds_case_despite_index(eng):
    """A _ci key's index view is raw-ordered, so stream-agg over it
    split case-variant groups; the planner must refuse that path."""
    s = eng.new_session()
    s.execute("CREATE TABLE ci_t (a BIGINT, "
              "s VARCHAR(16) COLLATE utf8mb4_general_ci)")
    s.execute("CREATE INDEX ci_s ON ci_t (s)")
    s.execute("INSERT INTO ci_t VALUES (1, 'Alpha'), (2, 'alpha'), "
              "(3, 'BETA'), (4, 'beta'), (5, 'beta')")
    rows = s.query("SELECT COUNT(*) FROM ci_t GROUP BY s").rows
    assert sorted(c for (c,) in rows) == [2, 3], rows


def test_ci_order_by_uses_collation_not_index(eng):
    s = eng.new_session()
    s.execute("CREATE TABLE ci_o (s VARCHAR(16) COLLATE "
              "utf8mb4_general_ci)")
    s.execute("CREATE INDEX ci_os ON ci_o (s)")
    s.execute("INSERT INTO ci_o VALUES ('b'), ('A'), ('a'), ('B')")
    got = [r[0] for r in s.query("SELECT s FROM ci_o ORDER BY s").rows]
    folded = [v.lower() for v in got]
    assert folded == sorted(folded), got   # collation order, not raw


def test_device_cache_eviction_keeps_partitioned_entries():
    from tidb_tpu.executor import device_cache as dc

    class _Ent:
        def hbm_bytes(self):
            return 100

    saved = dict(dc.CACHE)
    dc.CACHE.clear()
    try:
        dc.CACHE[(0, 1, 10, None)] = _Ent()     # evictable
        dc.CACHE[(0, 1, 20, (0,))] = _Ent()     # partitioned, protected
        dc.CACHE[(0, 1, 20, (1,))] = _Ent()     # partitioned, protected
        dc._evict_to_budget(150, keep=None,
                            keep_tables=frozenset({(1, 20)}))
        assert (0, 1, 20, (0,)) in dc.CACHE
        assert (0, 1, 20, (1,)) in dc.CACHE
        assert (0, 1, 10, None) not in dc.CACHE
    finally:
        dc.CACHE.clear()
        dc.CACHE.update(saved)


def test_hash_partition_routes_negative_keys_like_mysql(eng):
    """MySQL hash partitioning is ABS(truncated MOD); routing and
    pruning must agree or equality lookups on negative keys lose rows."""
    s = eng.new_session()
    s.execute("CREATE TABLE hp (a BIGINT) "
              "PARTITION BY HASH (a) PARTITIONS 4")
    keys = [-7, -3, -1, 0, 1, 3, 7]
    s.execute("INSERT INTO hp VALUES " +
              ", ".join(f"({k})" for k in keys))
    for k in keys:
        assert s.query(
            f"SELECT COUNT(*) FROM hp WHERE a = {k}").scalar() == 1, k
    assert s.query("SELECT COUNT(*) FROM hp").scalar() == len(keys)


# ---- chaos sweep -----------------------------------------------------------

@pytest.mark.chaos
def test_chaos_sweep_contract():
    from tidb_tpu.tools.chaos_sweep import run_sweep
    report = run_sweep()
    assert not report["failures"], report["failures"]
    assert report["scenarios"] >= 12
    # the clean workload must exercise the core CPU-path sites, or the
    # sweep is faulting dead code
    covered = {k for k, v in report["coverage"].items() if v > 0}
    assert {"scan-next", "store-commit", "tracker-quota"} <= covered
    # the coverage GATE: without a mesh only the mesh-only sites may stay
    # cold — everything else must have a working scenario
    assert not report["gated_unreached"], report["gated_unreached"]


@pytest.mark.chaos
def test_chaos_sweep_mesh_contract(eight_devices):
    # the distributed scenarios only: skewed-exchange escalation and
    # shard-step fault recovery over a 4-device mesh (the tests already
    # run under the forced 8-device host platform, so no re-exec needed)
    from tidb_tpu.tools.chaos_sweep import run_sweep
    report = run_sweep(mesh=4, mesh_only=True)
    assert not report["failures"], report["failures"]
    assert report["scenarios"] >= 3
    assert not report["gated_unreached"], report["gated_unreached"]


@pytest.mark.chaos
def test_check_failpoints_clean_on_repo_and_catches_drift(tmp_path):
    """The failpoint drift lint (tools/check_failpoints.py) the sweep
    runs as preflight: clean on this repo, and it actually catches both
    drift directions on a synthetic bad file."""
    import importlib.util
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "check_failpoints", os.path.join(repo, "tools",
                                         "check_failpoints.py"))
    cf = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cf)
    assert cf.run(repo) == []
    bad = tmp_path / "bad.py"
    bad.write_text(
        'from tidb_tpu.util import failpoint\n'
        'failpoint.inject("never-registered-site")\n'
        'failpoint.inject(some_variable)\n')
    inj, dyn, reg, strings, errs = cf.scan_file(str(bad))
    assert errs == []
    assert inj == [("never-registered-site", 2)]
    assert dyn == [3]
    assert "never-registered-site" in strings
