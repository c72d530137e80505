"""Priority-aware serving tier: per-class admission queues and same-plan
micro-batching.

Pins the PR's acceptance contract:

* coalescing: 8 queued same-digest point reads with
  tidb_tpu_microbatch_max=8 execute as ONE device launch (summed
  programs_launched across all 8 guards == 1, exactly one
  `batched:<sig>` compute span in the cross-session trace), every
  member byte-exact vs its individual-path oracle;
* priority: an interactive statement queued behind a batch scan is
  granted before the scan's conn re-acquires; aged batch entries are
  promoted (anti-starvation), so nothing waits forever;
* flag-off equivalence: with classification off the scheduler is the
  PR-5 FIFO — grant order is arrival order and the waits/yields
  counters keep their semantics;
* isolation: a member KILLed (or deadline-expired) while parked in a
  micro-batch surfaces its own typed error and leaves the batch; the
  survivors still coalesce and stay byte-exact;
* degradation: a demux fault (microbatch-demux failpoint) falls back to
  warned per-member individual execution — never a shared error;
* digesting: IN-list arity does not fork the micro-batch digest.
"""

import json
import threading
import time

import pytest

from tidb_tpu.errors import TiDBTPUError
from tidb_tpu.executor import microbatch
from tidb_tpu.executor.scheduler import SCHEDULER, DeviceScheduler, AGING_S
from tidb_tpu.session import Engine
from tidb_tpu.util import failpoint, timeline
from tidb_tpu.util.observability import REGISTRY, normalize_sql

N_MEMBERS = 8
MB_ROWS = 256


def _mb_sql(i: int) -> str:
    # mid-range literals: every k is inside the single slab's zone-map
    # range, so all members share one survivor set (one batch key)
    return f"SELECT v FROM mb WHERE k = {40 + i}"


@pytest.fixture()
def tier():
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    s.execute("CREATE TABLE mb (k BIGINT, v BIGINT)")
    s.execute("INSERT INTO mb VALUES " +
              ", ".join(f"({i}, {i * i})" for i in range(MB_ROWS)))
    s.execute("CREATE TABLE big (a BIGINT, g BIGINT)")
    s.execute("INSERT INTO big VALUES " +
              ", ".join(f"({i}, {i % 7})" for i in range(3000)))

    def new_session(mb_max: int = N_MEMBERS):
        ss = eng.new_session()
        ss.vars["tidb_tpu_engine"] = "on"
        ss.vars["tidb_tpu_row_threshold"] = 1
        ss.vars["tidb_tpu_microbatch_max"] = mb_max
        return ss

    yield eng, new_session
    eng.close()


def _counter(name: str) -> float:
    return REGISTRY.counters.get((name, ()), 0)


def _pile_up(new_session, n=N_MEMBERS, mb_max=N_MEMBERS):
    """Warm + oracle each member query, then dispatch all n concurrently
    with the device slot held so they rendezvous into one open batch.
    → (sessions, threads, results dict, oracle dict). The caller gets
    control while the slot is still held (leader queued on the
    scheduler, n-1 followers parked) and must release via the returned
    closure."""
    sessions = [new_session(mb_max) for _ in range(n)]
    oracle = {}
    for i in range(n):
        # solo runs take the individual path (a solo leader returns to
        # it untouched) — they are the byte-exactness oracle AND they
        # warm the parametrized program + the resident table
        oracle[i] = sessions[i].query(_mb_sql(i)).rows
        assert oracle[i] == [((40 + i) ** 2,)]
    results: dict = {}

    def worker(i):
        try:
            results[i] = sessions[i].query(_mb_sql(i)).rows
        except TiDBTPUError as e:
            results[i] = ("error", getattr(e, "code", None))

    threads = {i: threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(n)}
    SCHEDULER.acquire(conn_id=-1)
    released = []

    def release():
        if not released:
            released.append(True)
            SCHEDULER.release()

    try:
        # first dispatcher in alone → it registers the batch and becomes
        # the leader queued on the (held) scheduler slot
        threads[0].start()
        deadline = time.monotonic() + 10.0
        while SCHEDULER.queue_depth() < 2:
            assert time.monotonic() < deadline, "leader never queued"
            time.sleep(0.005)
        for i in range(1, n):
            threads[i].start()
        want = n - 1
        while microbatch.queued_members() < want:
            assert time.monotonic() < deadline, \
                f"followers parked: {microbatch.queued_members()}/{want}"
            time.sleep(0.005)
    except BaseException:
        release()
        raise
    return sessions, threads, results, oracle, release


def test_eight_point_reads_one_launch_byte_exact(tier, tmp_path):
    """THE acceptance pin: 8 queued same-digest point reads, mb_max=8 →
    ONE device program launch, one `batched:<sig>` trace span, every
    member's rows byte-exact vs its individual run."""
    eng, new_session = tier
    batches0 = _counter("tidb_tpu_microbatch_batches_total")
    members0 = _counter("tidb_tpu_microbatch_members_total")
    timeline.start_global(str(tmp_path))
    sessions = threads = None
    try:
        sessions, threads, results, oracle, release = \
            _pile_up(new_session)
        release()
        for t in threads.values():
            t.join(timeout=30.0)
        assert not any(t.is_alive() for t in threads.values())
        for i in range(N_MEMBERS):
            assert results[i] == oracle[i], f"member {i}: {results[i]}"
        launches = sum(s.last_guard.phases.programs_launched
                       for s in sessions)
        assert launches == 1, \
            f"8 coalesced point reads dispatched {launches} programs"
        # every member was charged its parked/queued time
        assert all(s.last_guard.queue_waits >= 1 for s in sessions)
        assert _counter("tidb_tpu_microbatch_batches_total") \
            == batches0 + 1
        assert _counter("tidb_tpu_microbatch_members_total") \
            == members0 + N_MEMBERS
        # exactly one batched launch span in the cross-session trace
        path = timeline.flush()
        doc = json.loads(open(path).read())
        spans = [e for e in doc["traceEvents"]
                 if e.get("ph") != "M" and e.get("cat") == "launch"
                 and str((e.get("args") or {}).get("sig", ""))
                 .startswith("batched:")]
        assert len(spans) == 1, f"batched spans: {len(spans)}"
    finally:
        if threads is not None:
            release()
        timeline.stop_global()


class _FakeGuard:
    """Minimal guard: classification fields + an inert kill-check."""

    def __init__(self, cls, cost=None):
        self.sched_class = cls
        self.sched_cost = cost
        self.queue_wait_s = 0.0
        self.queue_waits = 0

    def check(self, site):
        return None


def _grant_order(holder_sched, arrivals):
    """Enqueue `arrivals` = [(name, guard, conn_id), ...] one at a time
    (strictly ordered tickets) against a held scheduler, then release →
    the order the scheduler granted them."""
    order = []
    done = threading.Event()

    def worker(name, guard, conn_id):
        holder_sched.acquire(guard=guard, conn_id=conn_id)
        order.append(name)
        holder_sched.release()
        if len(order) == len(arrivals):
            done.set()

    depth = holder_sched.queue_depth()         # holder + pre-queued
    threads = []
    for name, guard, conn_id in arrivals:
        t = threading.Thread(target=worker, args=(name, guard, conn_id),
                             daemon=True)
        t.start()
        threads.append(t)
        depth += 1
        deadline = time.monotonic() + 5.0
        while holder_sched.queue_depth() < depth:
            assert time.monotonic() < deadline
            time.sleep(0.002)
    holder_sched.release()
    assert done.wait(timeout=10.0)
    for t in threads:
        t.join(timeout=5.0)
    return order


def test_interactive_overtakes_queued_batch():
    """An interactive statement that arrives AFTER a heavy batch scan is
    already queued is granted first — strict priority by class."""
    ds = DeviceScheduler()
    ds.acquire(conn_id=-1)
    order = _grant_order(ds, [
        ("batch", _FakeGuard("batch", cost=1.0), 1),
        ("interactive", _FakeGuard("interactive"), 2),
    ])
    assert order == ["interactive", "batch"]
    assert ds.stats()["classes"]["interactive"]["waits"] == 1


def test_aged_batch_is_promoted_over_fresh_interactive():
    """Anti-starvation: a batch entry parked past AGING_S ranks as
    interactive, so its earlier ticket wins over a later arrival."""
    ds = DeviceScheduler()
    ds.acquire(conn_id=-1)
    start = threading.Event()
    order = []

    def batch_worker():
        start.set()
        ds.acquire(guard=_FakeGuard("batch", cost=1.0), conn_id=1)
        order.append("batch")
        ds.release()

    t = threading.Thread(target=batch_worker, daemon=True)
    t.start()
    start.wait(5.0)
    deadline = time.monotonic() + 5.0
    while ds.queue_depth() < 2:
        assert time.monotonic() < deadline, "batch entry never queued"
        time.sleep(0.002)
    time.sleep(AGING_S + 0.1)                  # let the entry age
    rest = _grant_order(ds, [
        ("interactive", _FakeGuard("interactive"), 2),
    ])
    t.join(timeout=5.0)
    assert order + rest == ["batch", "interactive"]


def test_flag_off_is_plain_fifo():
    """Unclassified admissions (priority scheduling off → sched_class
    None) collapse to the PR-5 FIFO: grant order is arrival order, and
    the waits counter charges exactly the queued admissions."""
    ds = DeviceScheduler()
    ds.reset_stats()
    ds.acquire(conn_id=-1)
    names = [f"q{i}" for i in range(4)]
    order = _grant_order(ds, [(n, None, 10 + i)
                              for i, n in enumerate(names)])
    assert order == names, f"flag-off grant order not FIFO: {order}"
    st = ds.stats()
    assert st["admissions"] == 5               # holder + 4 waiters
    assert st["waits"] == 4
    assert st["classes"] == {}                 # nothing was classified


def test_priority_flag_off_leaves_guard_unclassified(tier):
    eng, new_session = tier
    s = new_session()
    s.vars["tidb_tpu_priority_scheduling"] = "off"
    s.query(_mb_sql(0))
    assert s.last_guard.sched_class is None
    s.vars["tidb_tpu_priority_scheduling"] = "on"
    s.query(_mb_sql(0))
    assert s.last_guard.sched_class == "interactive"
    s.query("SELECT g, COUNT(*) FROM big GROUP BY g")
    assert s.last_guard.sched_class == "batch"


def test_kill_and_deadline_isolation_inside_microbatch(tier):
    """One parked member KILLed and one deadline-expired: each surfaces
    its own typed error (1317 / 3024) and leaves the batch; the six
    survivors still coalesce into one launch, byte-exact."""
    eng, new_session = tier
    members0 = _counter("tidb_tpu_microbatch_members_total")
    sessions, threads, results, oracle, release = _pile_up(new_session)
    try:
        # threads 1..7 are followers (thread 0 queued alone first and is
        # the leader). Kill follower 3; expire follower 5's deadline
        # directly on its parked guard (the deadline is armed at
        # admission, so a sysvar change can't reach the in-flight stmt).
        killer = new_session()
        sessions[5].last_guard.deadline = time.monotonic()
        killer.execute(f"KILL QUERY {sessions[3].conn_id}")
        deadline = time.monotonic() + 10.0
        while not (isinstance(results.get(3), tuple)
                   and isinstance(results.get(5), tuple)):
            assert time.monotonic() < deadline, \
                f"victims never errored: {results}"
            time.sleep(0.01)
    finally:
        release()
    for t in threads.values():
        t.join(timeout=30.0)
    assert results[3] == ("error", 1317), results[3]
    assert results[5] == ("error", 3024), results[5]
    survivors = [i for i in range(N_MEMBERS) if i not in (3, 5)]
    for i in survivors:
        assert results[i] == oracle[i], f"member {i}: {results[i]}"
    launches = sum(sessions[i].last_guard.phases.programs_launched
                   for i in survivors)
    assert launches == 1, f"survivors dispatched {launches} programs"
    assert _counter("tidb_tpu_microbatch_members_total") \
        == members0 + len(survivors)
    # victims' sessions still serve afterwards
    assert sessions[3].query(_mb_sql(3)).rows == oracle[3]


def test_demux_fault_degrades_to_warned_individual(tier):
    """microbatch-demux fault: every member still gets exactly its own
    rows (via individual fallback), the leader carries a 1105 warning,
    and the fallbacks counter advances — never a shared typed error."""
    eng, new_session = tier
    fallbacks0 = _counter("tidb_tpu_microbatch_fallbacks_total")
    sessions, threads, results, oracle, release = _pile_up(new_session)
    try:
        failpoint.enable("microbatch-demux",
                         raise_=RuntimeError("test: demux fault"),
                         times=1)
        release()
        for t in threads.values():
            t.join(timeout=30.0)
        assert failpoint.hits("microbatch-demux") > 0, \
            "batch never reached demux"
    finally:
        release()
        failpoint.disable("microbatch-demux")
    for i in range(N_MEMBERS):
        assert results[i] == oracle[i], f"member {i}: {results[i]}"
    assert _counter("tidb_tpu_microbatch_fallbacks_total") \
        == fallbacks0 + 1
    warned = [s for s in sessions
              if any(w[1] == 1105 and "micro-batch" in w[2]
                     for w in s.warnings)]
    assert len(warned) == 1, \
        f"exactly the leader warns, got {len(warned)}"


def test_in_list_arity_shares_digest():
    """normalize_sql collapses IN lists, so prepared bursts differing
    only in IN-arity rendezvous on one micro-batch digest."""
    a = normalize_sql("SELECT v FROM mb WHERE k IN (1, 2, 3)")
    b = normalize_sql("SELECT v FROM mb WHERE k IN (1,2,3,4,5)")
    c = normalize_sql("SELECT v FROM mb WHERE k IN (9)")
    assert a == b == c
    assert "(?)" in a
    # ...but a different shape still forks the digest
    d = normalize_sql("SELECT v FROM mb WHERE k IN (1,2) AND v > 0")
    assert d != a
    # unary minus folds into the placeholder: x = -5 and x = 5 coalesce
    assert normalize_sql("SELECT v FROM mb WHERE k = -5") \
        == normalize_sql("SELECT v FROM mb WHERE k = 5")


def test_the_cost_hint_forgets_a_first_touch_and_leaves_out_the_wait():
    """The scheduler's cheap/heavy line is drawn by `digest_cost`: what a
    digest's device path takes once it HAS the slot, as of its last few
    executions. A first touch of seconds is forgotten within a few dozen
    runs (the lifetime mean kept Q1 heavy for hundreds), and the wait for
    the slot is no cost of the statement's (counted, it kept a statement
    that was made to wait heavy: PERF.md §6 PR 41)."""
    from types import SimpleNamespace
    from tidb_tpu.executor import scheduler
    from tidb_tpu.util.observability import Registry
    from tidb_tpu.util.phases import PhaseTimer

    def run(reg, sql, device_path_s, waited_s):
        ph = PhaseTimer()
        ph.add_wall(device_path_s + waited_s)   # the slot is taken inside
        reg.record_stmt(sql, device_path_s + waited_s, 1, "tpu", 10.0,
                        SimpleNamespace(phases=ph, queue_wait_s=waited_s,
                                        queue_waits=1))
    reg = Registry()
    assert reg.digest_cost("SELECT 1 FROM t") is None
    run(reg, "SELECT 1 FROM t", 17.0, 0.0)           # first touch
    assert reg.digest_cost("SELECT 1 FROM t") == 17.0
    for _ in range(60):
        run(reg, "SELECT 2 FROM t", 0.020, 0.200)    # same digest, waiting
    cost = reg.digest_cost("SELECT 3 FROM t")
    assert 0.020 <= cost < scheduler.CHEAP_BATCH_S
    lifetime = reg.stmt_summary[next(iter(reg.stmt_summary))]
    assert lifetime["device_s"] / lifetime["count"] > scheduler.CHEAP_BATCH_S


def test_a_client_cannot_set_itself_out_of_admission(tier):
    """`tidb_tpu_scheduler` was an internal flag any client could SET (the
    compactor's warm runs read it). It is no variable now: a session that
    sets it is admitted like any other — "unscheduled" is a field of the
    context the compactor builds (`ExecContext.unscheduled`)."""
    from tidb_tpu.executor.scheduler import POOL
    _eng, new_session = tier
    s = new_session()
    s.execute("SET tidb_tpu_scheduler = 'off'")
    before = POOL.stats()["admissions"]
    assert s.query("SELECT g, COUNT(*) FROM big GROUP BY g ORDER BY g") \
        .rows[0] == (0, 429)
    assert POOL.stats()["admissions"] > before
