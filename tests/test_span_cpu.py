"""Waiting told from working (PR 38): every span carries its thread's CPU
time, every designed wait names what it waits for, the program's own locks
have a lane, and the benchmark's reducer (`benchmarks/span_cpu.py`: pure
arithmetic, so tier-1 checks it on a synthetic nest) closes a request's
account: root = CPU + named waits + what is left, the interpreter's lock."""

import gc
import importlib.util
import json
import os
import sys
import threading
import time
import types

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import span_cpu  # noqa: E402

from tidb_tpu.session import Engine  # noqa: E402
from tidb_tpu.util import timeline  # noqa: E402

READERS = ("host_cpu_ms_per_op", "lock_wait_ms_per_op",
           "own_lock_wait_ms_per_op", "slot_hold_ms_per_op",
           "point_cpu_ms", "point_lock_wait_ms")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"tests_layer_metrics_{name}",
        os.path.join(BENCH, "layer_metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def recorder(tmp_path):
    timeline.start_global(str(tmp_path))
    try:
        yield tmp_path
    finally:
        timeline.stop_global()
    assert timeline.ENABLED is False


# ---- (a) the reducer's arithmetic on a synthetic nest ----------------------

def ev(cat, name, ts, dur, req, id_=None, parent=0, cpu=None, **tags):
    args = dict(tags, req=req, parent=parent)
    if id_ is not None:
        args["id"] = id_
    if cpu is not None:
        args["cpu"] = float(cpu)
    return {"name": name, "cat": cat, "ph": "X", "ts": float(ts),
            "dur": float(dur), "pid": 1, "tid": 1, "args": args}


# one operation of 1000 us: the thread ran 285 of them
NEST = [
    ev("stmt", "stmt", 0, 1000, 1, id_=1, cpu=285, **{"class": "batch"}),
    ev("parse", "parse", 0, 100, 1, id_=2, parent=1, cpu=90),
    # a queue wait the scheduler timed (`record`: no id, no cpu), then
    # the hold with everything under it
    ev("sched", "sched-queue:batch", 100, 200, 1, parent=1, wait="queue"),
    ev("sched", "sched-slot", 300, 600, 1, id_=3, parent=1, cpu=175),
    ev("frag", "device.fragment", 300, 600, 1, id_=4, parent=3, cpu=175),
    ev("launch", "partial_chain_ab", 320, 80, 1, id_=5, parent=4, cpu=70),
    ev("lock", "lock.wait", 420, 30, 1, id_=6, parent=4, cpu=1,
       wait="lock"),
    ev("drain", "drain", 500, 300, 1, id_=7, parent=4, cpu=5,
       wait="device"),
    # a collection the recorder timed inside the fragment's own time:
    # neither `cpu` nor `wait`
    ev("gc", "gc.gen2", 460, 20, 1, parent=4),
    ev("wire", "wire.write", 900, 100, 1, id_=8, parent=1, cpu=20),
    # a point read of 400 us on another connection: ran 50
    ev("stmt", "stmt", 2000, 400, 2, id_=10, cpu=50,
       **{"class": "interactive"}),
    ev("index", "index.probe", 2100, 200, 2, id_=11, parent=10, cpu=30),
    ev("wire", "wire.write", 2300, 100, 2, id_=12, parent=10, cpu=10),
    # the server's wait for the client is under no request
    ev("client", "client.wait", 1000, 900, 0, id_=13, cpu=2,
       wait="socket"),
]

# self wall · self CPU of the operation's events (us):
#   stmt 0·0           parse 100·90       sched-queue 200 (queue)
#   sched-slot 0·0     device.fragment 170·99   launch 80·70
#   lock.wait 30·1 (lock)   drain 300·5 (device)   gc.gen2 20 (unclocked)
#   wire.write 100·20
OPS_US = {"cpu": 0 + 90 + 0 + 99 + 70 + 1 + 5 + 20,
          "lock_wait": 10 + 0 + 71 + 10 + 80,
          "queue": 200, "lock": 29, "device": 295, "unclocked": 20}


@pytest.mark.parametrize("term", sorted(OPS_US))
def test_the_operations_account_by_term(term):
    got = span_cpu.reduce(NEST)["ops"]
    assert got["n"] == 1
    assert got["terms"][term] == pytest.approx(OPS_US[term] * 1e-6)


def test_the_account_closes_on_the_stmt_root():
    for who, root_us in (("ops", 1000), ("points", 400)):
        got = span_cpu.reduce(NEST)[who]
        assert got["stmt_s"] == pytest.approx(root_us * 1e-6)
        assert sum(got["terms"].values()) == pytest.approx(got["stmt_s"])


@pytest.mark.parametrize("lane,want", [
    ("frag", {"cpu": 99, "lock_wait": 71}),
    ("drain", {"cpu": 5, "device": 295}),
    ("lock", {"cpu": 1, "lock": 29}),
    ("sched", {"cpu": 0, "queue": 200, "lock_wait": 0}),
    ("gc", {"cpu": 0, "unclocked": 20})])
def test_the_account_by_lane(lane, want):
    got = span_cpu.reduce(NEST)["ops"]["by_lane"][lane]
    assert got == pytest.approx({k: v * 1e-6 for k, v in want.items()})


def test_self_cpu_is_the_spans_less_its_childrens():
    parts = {e["args"].get("id"): (term, cpu, off)
             for e, term, cpu, off in span_cpu.split(NEST)}
    # the fragment ran 175 us, its launch 70, lock wait 1 and drain 5 of
    # them; the collection inside it has no `cpu` to subtract
    assert parts[4][0] == "lock_wait"
    assert parts[4][1:] == pytest.approx((99e-6, 71e-6))
    # the hold did nothing of its own: its fragment covers it and ran it all
    assert parts[3][1:] == pytest.approx((0.0, 0.0))
    # the sums telescope: what the request's spans ran is what its root ran
    assert span_cpu.reduce(NEST)["ops"]["terms"]["cpu"] == \
        pytest.approx(285e-6)
    assert span_cpu.reduce(NEST)["cpu_step_us"] == 1.0


def test_a_coarse_clock_bends_one_span_and_no_sum():
    """A thread clock that ticks 10 ms a step (the chip hosts' kernel):
    one span reads 0 or a whole step whatever it ran. Nothing is floored
    or capped span by span, so the sums still telescope and close."""
    nest = [ev("stmt", "stmt", 0, 400, 1, id_=1, cpu=10000),
            ev("frag", "device.fragment", 0, 100, 1, id_=2, parent=1,
               cpu=0),
            ev("launch", "p", 100, 200, 1, id_=3, parent=1, cpu=10000)]
    got = {e["name"]: (cpu, off) for e, _t, cpu, off in span_cpu.split(nest)}
    assert got["stmt"] == pytest.approx((0.0, 100e-6))
    assert got["device.fragment"] == pytest.approx((0.0, 100e-6))
    assert got["p"] == pytest.approx((10000e-6, -9800e-6))
    got = span_cpu.reduce(nest)
    assert got["cpu_step_us"] == 10000
    assert got["ops"]["terms"]["cpu"] == pytest.approx(10000e-6)
    assert sum(got["ops"]["terms"].values()) == pytest.approx(400e-6)


def test_the_slot_hold_is_split_by_what_its_subtree_did():
    slot = span_cpu.reduce(NEST)["ops"]["slot"]
    assert slot["holds"] == 1
    assert slot["hold_s"] == pytest.approx(600e-6)
    # everything under the hold: fragment, launch, lock wait, drain, gc
    assert slot["terms"] == pytest.approx(
        {"cpu": 175e-6, "lock_wait": (71 + 10) * 1e-6, "lock": 29e-6,
         "device": 295e-6, "unclocked": 20e-6})
    assert sum(slot["terms"].values()) == pytest.approx(slot["hold_s"])


def test_the_point_reads_are_split_as_point_spans_splits_them():
    got = span_cpu.reduce(NEST)["points"]
    assert got["n"] == 1
    assert got["terms"] == pytest.approx(
        {"cpu": (10 + 30 + 10) * 1e-6, "lock_wait": (90 + 170 + 90) * 1e-6})
    # no interactive root: no point side, the operations as before
    batch_only = [e for e in NEST if e["args"]["req"] == 1]
    got = span_cpu.reduce(batch_only)
    assert got["points"] is None and got["ops"]["n"] == 1


def test_the_printed_line_is_ms_per_request(capsys):
    ctx = {"_span_events": NEST}
    assert span_cpu.window(ctx) is span_cpu.window(ctx)     # reduced once
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["phase"] for x in lines] == ["span_cpu"]
    per_op = lines[0]["per_op"]
    assert per_op["stmt_ms"] == pytest.approx(1.0)
    assert per_op["cpu"] == pytest.approx(0.285)
    assert per_op["lock_wait"] == pytest.approx(0.171)
    assert per_op["wait"] == pytest.approx(
        {"device": 0.295, "lock": 0.029, "queue": 0.2})
    assert per_op["unclocked"] == pytest.approx(0.02)
    assert per_op["sum_over_stmt"] == pytest.approx(1.0)
    assert per_op["sched_slot"]["hold_ms"] == pytest.approx(0.6)
    assert per_op["sched_slot"]["wait"]["device"] == pytest.approx(0.295)
    assert per_op["by_span"]["drain/drain"]["wait"] == pytest.approx(
        {"device": 0.295})
    assert lines[0]["per_point_read"]["cpu"] == pytest.approx(0.05)


@pytest.mark.parametrize("name,want_ms", [
    ("host_cpu_ms_per_op", 0.285), ("lock_wait_ms_per_op", 0.171),
    ("own_lock_wait_ms_per_op", 0.030), ("slot_hold_ms_per_op", 0.6),
    ("point_cpu_ms", 0.05), ("point_lock_wait_ms", 0.35)])
def test_a_reader_reads_its_term(name, want_ms, capsys):
    assert _reader(name).read({"_span_events": NEST}) == \
        pytest.approx(want_ms)
    capsys.readouterr()


# ---- (f) a program whose spans carry no `cpu`: nothing to read -------------

@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_on_spans_without_cpu(name, capsys):
    bare = []
    for e in NEST:
        args = {k: v for k, v in e["args"].items()
                if k not in ("cpu", "wait")}
        bare.append(dict(e, args=args))
    assert span_cpu.reduce(bare) is None
    assert _reader(name).read({"_span_events": bare}) is None
    assert _reader(name).read({"_span_events": []}) is None
    assert capsys.readouterr().out == ""        # and no line


def test_no_contended_lock_reads_zero_not_nothing(capsys):
    calm = [e for e in NEST if e["cat"] != "lock"]
    assert _reader("own_lock_wait_ms_per_op").read(
        {"_span_events": calm}) == 0.0
    capsys.readouterr()


# ---- (b) (c) the field itself ----------------------------------------------

def _spin(cpu_s):
    """Python work until this thread has RUN `cpu_s` more seconds."""
    until = time.thread_time() + cpu_s
    n = 0
    while time.thread_time() < until:
        n += 1
    return n


def _last(name):
    return [e for e in timeline.last_events() if e["name"] == name][-1]


def test_a_sleep_reads_no_cpu_and_a_spin_reads_its_duration(recorder):
    with timeline.span("nap", "exec"):
        time.sleep(0.05)
    with timeline.span("spin", "exec"):
        _spin(0.05)
    nap, spin = _last("nap"), _last("spin")
    assert nap["dur"] >= 50_000 and nap["args"]["cpu"] < 5_000
    assert 45_000 <= spin["args"]["cpu"] <= spin["dur"] + 1_000
    # an event measured elsewhere carries none
    timeline.record("sched-queue:batch", "sched", dur_us=10.0,
                    args={"wait": "queue"})
    assert "cpu" not in _last("sched-queue:batch")["args"]
    assert _last("sched-queue:batch")["args"]["wait"] == "queue"


def test_a_child_runs_on_its_parents_clock(recorder):
    with timeline.span("outer", "exec"):
        _spin(0.01)
        with timeline.span("inner", "exec"):
            _spin(0.02)
    outer, inner = _last("outer"), _last("inner")
    assert inner["args"]["parent"] == outer["args"]["id"]
    assert inner["args"]["cpu"] <= outer["args"]["cpu"]
    assert outer["args"]["cpu"] - inner["args"]["cpu"] == \
        pytest.approx(10_000, abs=5_000)


def _off_cpu_share(name, beside):
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            pass
    others = [threading.Thread(target=spin) for _ in range(beside)]
    for t in others:
        t.start()
    try:
        with timeline.span(name, "exec"):
            _spin(0.08)
    finally:
        stop.set()
        for t in others:
            t.join()
    e = _last(name)
    return 1.0 - e["args"]["cpu"] / e["dur"]


def test_off_cpu_is_the_interpreter_lock_beside_spinning_threads(recorder):
    t0 = time.monotonic()
    for attempt in range(3):    # the suite runs six workers wide
        alone = _off_cpu_share("alone", 0)
        crowded = _off_cpu_share("crowded", 3)
        if alone < 0.2 and crowded > 0.3:
            break
    assert alone < 0.2 and crowded > 0.3, (alone, crowded)
    assert time.monotonic() - t0 < 2.0 * (attempt + 1)


# ---- (d) the program's own locks -------------------------------------------

def _lock_waits():
    return [e for e in timeline.last_events() if e["cat"] == "lock"]


def _contend(lock, hold_s=0.05):
    """Another thread holds `lock` for `hold_s`; → once it does."""
    held = threading.Event()

    def hold():
        with lock:
            held.set()
            time.sleep(hold_s)
    t = threading.Thread(target=hold)
    t.start()
    held.wait()
    return t


@pytest.mark.parametrize("reentrant", [False, True])
def test_a_contended_acquire_is_a_lock_wait_span(recorder, reentrant):
    lock = timeline.named_lock("things", reentrant=reentrant)
    with lock:                      # uncontended: nothing
        pass
    assert _lock_waits() == []
    t = _contend(lock)
    with timeline.span("stmt", "stmt", pid=7, req=3):
        with lock:
            pass
    t.join()
    (e,) = _lock_waits()
    assert e["name"] == "lock.wait" and e["tid"] == timeline.STREAMS["lock"]
    assert e["args"]["name"] == "things" and e["args"]["wait"] == "lock"
    assert e["args"]["req"] == 3 and e["pid"] == 7
    assert e["dur"] >= 20_000 and e["args"]["cpu"] < 5_000


def test_a_lock_does_its_plain_acquire_when_the_recorder_is_off(monkeypatch):
    assert timeline.ENABLED is False
    monkeypatch.setattr(timeline, "_Span", None)    # would raise if made
    lock = timeline.named_lock("things")
    t = _contend(lock)
    with lock:                      # contended, blocks, takes it
        pass
    t.join()


@pytest.mark.parametrize("reentrant", [False, True])
def test_a_named_lock_behaves_as_the_lock_it_wraps(recorder, reentrant):
    lock = timeline.named_lock("things", reentrant=reentrant)
    assert lock.acquire() is True
    # re-entry: an RLock's owner takes it again, a Lock's does not
    assert lock.acquire(False) is reentrant
    if reentrant:
        lock.release()
    got = []
    t = threading.Thread(target=lambda: got.append(
        (lock.acquire(False), lock.acquire(True, 0.01))))
    t.start()
    t.join()
    assert got == [(False, False)]          # held: another thread gets none
    lock.release()
    with pytest.raises(RuntimeError):       # released once too often
        lock.release()
    with pytest.raises(KeyError):           # an exception releases it
        with lock:
            raise KeyError("x")
    assert lock.acquire(False) is True
    lock.release()


LOCKS = [("tidb_tpu.executor.device_cache", "LOCK", "device_cache"),
         ("tidb_tpu.executor.compile_cache", "LOCK", "compile_cache"),
         ("tidb_tpu.executor.index_scan", "_LOCK", "index_views"),
         ("tidb_tpu.executor.delta", "_EXT_LOCK", "delta_extend"),
         ("tidb_tpu.native", "_lock", "rowcodec")]


@pytest.mark.parametrize("module,attr,name", LOCKS)
def test_the_statement_paths_module_locks_are_named(module, attr, name):
    lock = getattr(importlib.import_module(module), attr)
    assert isinstance(lock, timeline._NamedLock) and lock.name == name


def test_the_statement_paths_instance_locks_are_named():
    from tidb_tpu.executor.scheduler import POOL
    from tidb_tpu.util.guard import PROCESS_REGISTRY
    from tidb_tpu.util.observability import REGISTRY
    eng = Engine()
    try:
        got = {lk.name for lk in (
            REGISTRY._lock, PROCESS_REGISTRY._lock, POOL._lock,
            eng.store._lock, eng.stats_lock, eng.auth._lock)}
    finally:
        eng.close()
    assert got == {"metrics", "processlist", "device_pool", "store",
                   "table_stats", "auth"}


# ---- (e) off: no clock, no object ------------------------------------------

class _NoClock:
    def __getattr__(self, name):
        raise AssertionError(f"time.{name} read while the recorder is off")


def test_off_reads_no_clock_and_makes_no_object(monkeypatch):
    assert timeline.ENABLED is False
    monkeypatch.setattr(timeline, "time", _NoClock())
    monkeypatch.setattr(timeline, "_Span", None)
    assert timeline.span("a", "exec", wait="device") is timeline._NO_SPAN
    with timeline.span("a", "exec") as s:
        assert s is timeline._NO_SPAN
    assert timeline.tag(rows=1) is None
    assert timeline.record("sched-queue", "sched", dur_us=5.0,
                           args={"wait": "queue"}) is None
    assert timeline.instant("evict", "cache") is None
    lock = timeline.named_lock("things", reentrant=True)
    with lock:
        with lock:
            pass


# ---- the designed waits at their sites -------------------------------------

@pytest.fixture(scope="module")
def traced_statements(tmp_path_factory):
    """Spans of a warm device aggregate run twice over a small table."""
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE t (k BIGINT PRIMARY KEY, g BIGINT, v BIGINT)")
    s.execute("INSERT INTO t VALUES " + ",".join(
        f"({i},{i % 5},{i * 3})" for i in range(3000)))
    s.execute("SET tidb_tpu_engine = 'on'")
    s.execute("SET tidb_tpu_row_threshold = 1")
    sql = "SELECT g, COUNT(*), SUM(v) FROM t GROUP BY g ORDER BY g"
    want = s.query(sql).rows
    s.execute(f"SET tidb_tpu_trace_dir = '{tmp_path_factory.mktemp('tl')}'")
    try:
        for _ in range(3):      # the recorder starts ON the first: no root
            assert s.query(sql).rows == want
    finally:
        timeline.stop_global()
        eng.close()
    return timeline.last_events()


def test_every_span_carries_cpu_and_the_device_waits_say_so(
        traced_statements):
    spans = [e for e in traced_statements if e["ph"] == "X"]
    clocked = [e for e in spans if "id" in e["args"]]
    assert clocked and all("cpu" in e["args"] for e in clocked)
    assert all(0 <= e["args"]["cpu"] <= e["dur"] + 500 for e in clocked)
    for lane in ("drain", "fetch"):
        got = [e for e in spans if e["cat"] == lane]
        assert got and all(e["args"]["wait"] == "device" for e in got), lane
    untagged = {e["cat"] for e in spans if "wait" not in e["args"]}
    assert {"stmt", "parse", "plan", "frag", "launch", "sched"} <= untagged


def test_a_real_requests_account_closes_within_one_percent(
        traced_statements):
    got = span_cpu.reduce(traced_statements)["ops"]
    assert got["n"] == 2 and got["slot"]["holds"] >= 2
    assert sum(got["terms"].values()) == pytest.approx(got["stmt_s"],
                                                       rel=0.01)
    assert 0 < got["terms"]["cpu"] <= got["stmt_s"]
    assert got["terms"].get("unclocked", 0.0) <= 0.01 * got["stmt_s"]


def test_lane_compute_went_and_lane_lock_came():
    assert "compute" not in timeline.STREAMS
    assert timeline.STREAMS["lock"] not in [
        v for k, v in timeline.STREAMS.items() if k != "lock"]
    assert "named_lock" in timeline.__all__
    assert not hasattr(timeline, "FLUSH_COST_SHARE")


# ---- the periodic write -----------------------------------------------------

def _file_events(path):
    with open(path) as f:
        return [e for e in json.load(f)["traceEvents"] if e["ph"] != "M"]


def test_each_write_renders_only_the_events_since_the_last(recorder,
                                                           monkeypatch):
    rendered = []
    dumps = json.dumps

    def counting(obj, *a, **k):
        if isinstance(obj, list) and obj and obj[0].get("ph") != "M":
            rendered.append(len(obj))
        return dumps(obj, *a, **k)
    monkeypatch.setattr(timeline.json, "dumps", counting)
    # (a full collection that happens to fall due in here — it depends on
    # what the tests before this one allocated — records a `gc.gen2` span
    # of its own while the recorder is on)
    gc.disable()
    try:
        for n in (500, 20, 3):
            for i in range(n):
                with timeline.span(f"s{i}", "exec", pid=n):
                    pass
            path = timeline.flush()
        assert rendered == [500, 20, 3]
        assert timeline.flush() == path and rendered == [500, 20, 3]  # clean
        monkeypatch.undo()
        got = _file_events(path)
        assert len(got) == 523 == len(timeline.last_events())
    finally:
        gc.enable()
    with open(path) as f:
        meta = [e for e in json.load(f)["traceEvents"] if e["ph"] == "M"]
    assert {m["pid"] for m in meta} == {500, 20, 3}     # later lanes named


def test_the_statement_path_renders_a_batch_at_a_time(recorder,
                                                      monkeypatch):
    """A statement pays for about its own events: `flush_if_due` renders
    what is pending once RENDER_BATCH events are, and the write that falls
    due later has only the rest to render, however much the collector
    holds."""
    rendered = []
    dumps = json.dumps

    def counting(obj, *a, **k):
        if isinstance(obj, list) and obj and obj[0].get("ph") != "M":
            rendered.append(len(obj))
        return dumps(obj, *a, **k)
    monkeypatch.setattr(timeline.json, "dumps", counting)
    monkeypatch.setattr(timeline, "RENDER_BATCH", 50)
    monkeypatch.setattr(timeline, "_NEXT_FLUSH", time.monotonic() + 3600)
    for i in range(40):             # 40 statements of 12 spans
        for k in range(12):
            with timeline.span(f"s{i}.{k}", "exec", pid=1):
                pass
        timeline.flush_if_due()
    assert rendered == [60] * 8     # every fifth statement, 60 pending
    path = timeline.flush()
    assert rendered == [60] * 8     # nothing left for the write
    with timeline.span("late", "exec", pid=1):
        pass
    assert timeline.flush() == path and rendered[-1] == 1
    monkeypatch.undo()
    assert len(_file_events(path)) == 481 == len(timeline.last_events())


def test_the_file_goes_out_in_few_large_pieces(recorder, monkeypatch):
    """Every `write` gives the interpreter away: the rendered chunks are
    joined into pieces of WRITE_PIECE bytes, a full piece is kept as it
    is, and the file reads the same."""
    monkeypatch.setattr(timeline, "WRITE_PIECE", 10)
    assert timeline._pieces([b"aaa", b"bbbb", b"cccccc", b"d" * 12, b"e",
                             b"f" * 16, b"g"]) == \
        [b"aaa, bbbb, cccccc", b"d" * 12, b"e, " + b"f" * 16, b"g"]
    assert timeline._pieces([]) == []
    monkeypatch.setattr(timeline, "WRITE_PIECE", 2000)
    monkeypatch.setattr(timeline, "RENDER_BATCH", 5)
    monkeypatch.setattr(timeline, "_NEXT_FLUSH", time.monotonic() + 3600)
    for i in range(60):
        with timeline.span(f"s{i}", "exec", pid=1):
            pass
        timeline.flush_if_due()
    path = timeline.flush()
    kept = timeline._GLOBAL.chunks
    assert len(kept) < 12 and all(len(p) >= 2000 for p in kept[:-1])
    assert [e["name"] for e in _file_events(path)] == \
        [f"s{i}" for i in range(60)]
    with timeline.span("late", "exec", pid=1):
        pass
    assert len(_file_events(timeline.flush())) == 61
    assert timeline._GLOBAL.chunks[:len(kept) - 1] == kept[:-1]   # uncopied


def test_the_statement_path_writes_every_interval(recorder, monkeypatch):
    clock = [1000.0]
    writes = []
    monkeypatch.setattr(timeline, "_NEXT_FLUSH",
                        clock[0] + timeline.FLUSH_INTERVAL_S)
    monkeypatch.setattr(timeline, "time",
                        types.SimpleNamespace(monotonic=lambda: clock[0]))
    monkeypatch.setattr(timeline, "flush", lambda: writes.append(clock[0]))
    for _ in range(395):            # a statement every 0.1 s for 39.5 s
        clock[0] += 0.1
        timeline.flush_if_due()
    monkeypatch.undo()
    assert len(writes) == 7
    gaps = [b - a for a, b in zip(writes, writes[1:])]
    assert all(g == pytest.approx(timeline.FLUSH_INTERVAL_S, abs=0.11)
               for g in gaps)


def test_a_failed_write_loses_nothing(recorder, monkeypatch):
    with timeline.span("first", "exec"):
        pass
    def full(*_a):
        raise OSError("no space left on device")
    monkeypatch.setattr(timeline.os, "replace", full)
    assert timeline.flush() is None
    monkeypatch.undo()
    with timeline.span("second", "exec"):
        pass
    assert [e["name"] for e in _file_events(timeline.flush())] == \
        ["first", "second"]
