"""Query-time attribution layer: TopSQL-style digest profiles,
cross-session Chrome-trace export, roofline accounting, metric lint.

Tier-1 (CPU-jax): the PhaseTimer ledger (device seconds, h2d/d2h/scan
bytes, compile counts, queue waits) must flow byte-exactly from the
executor through ExecutionGuard into information_schema tables, the
slow log, /statements and the timeline — and cost nothing when off."""

import json
import os
import re
import threading

import pytest

from tidb_tpu.session import Engine
from tidb_tpu.util import timeline
from tidb_tpu.util.observability import (REGISTRY, Registry, hist_quantile,
                                         normalize_sql)


@pytest.fixture()
def dev_session():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE li (a BIGINT PRIMARY KEY, b BIGINT, c DOUBLE)")
    s.execute("INSERT INTO li VALUES " +
              ",".join(f"({i},{i % 5},{i * 0.5})" for i in range(3000)))
    s.execute("SET tidb_tpu_engine = 'on'")
    s.execute("SET tidb_tpu_row_threshold = 1")
    return s


AGG = "SELECT b, COUNT(*), SUM(c) FROM li GROUP BY b"


# ---- digest profiles ------------------------------------------------------

def test_statements_summary_matches_phase_ledger_byte_exact(dev_session):
    """The digest row's device/byte/compile counters equal the exact sum
    of the per-statement PhaseTimer ledgers (the same ledger EXPLAIN
    ANALYZE renders) — integer counters match to the byte."""
    s = dev_session
    want = {"h2d": 0, "d2h": 0, "scan": 0, "compiles": 0, "wall": 0.0}
    reps = 3
    for _ in range(reps):
        assert s.query(AGG).row_count == 5
        ph = s.last_guard.phases
        want["h2d"] += ph.h2d_bytes
        want["d2h"] += ph.d2h_bytes
        want["scan"] += ph.scan_bytes
        want["compiles"] += ph.compiles
        want["wall"] += ph.wall_s
    assert want["scan"] > 0 and want["d2h"] > 0     # device path ran
    row = s.query(
        "SELECT EXEC_COUNT, DEVICE_SECONDS, H2D_BYTES, D2H_BYTES, "
        "SCAN_BYTES, COMPILES, QUEUE_P99_MS FROM "
        "information_schema.statements_summary "
        f"WHERE DIGEST_TEXT = '{AGG}'").rows
    assert len(row) == 1
    cnt, dev_s, h2d, d2h, scan, compiles, p99 = row[0]
    assert cnt == reps
    assert (h2d, d2h, scan, compiles) == (
        want["h2d"], want["d2h"], want["scan"], want["compiles"])
    assert dev_s == pytest.approx(want["wall"], abs=1e-3)
    assert p99 >= 0.0
    # warm reps re-read the resident slabs: scan accumulates every rep,
    # upload bytes only on the cold first touch
    assert scan > h2d


def test_explain_analyze_bytes_match_summary_row(dev_session):
    """The h2d/d2h bytes EXPLAIN ANALYZE prints are the same integers
    its own digest row aggregates."""
    s = dev_session
    s.query(AGG)                                    # warm compile + cache
    ea = "EXPLAIN ANALYZE " + AGG
    info = "\n".join(" ".join(str(c) for c in r) for r in s.query(ea).rows)
    m = re.search(r"h2d=(\d+)B d2h=(\d+)B", info)
    assert m, info
    h2d_printed, d2h_printed = int(m.group(1)), int(m.group(2))
    row = s.query(
        "SELECT H2D_BYTES, D2H_BYTES, EXEC_COUNT FROM "
        "information_schema.statements_summary "
        f"WHERE DIGEST_TEXT = '{ea}'").rows
    assert row == [(h2d_printed, d2h_printed, 1)]


def test_slow_query_table_carries_device_attribution(dev_session):
    s = dev_session
    s.execute("SET long_query_time = 0")            # everything is "slow"
    s.query(AGG)
    ph = s.last_guard.phases
    rows = s.query(
        "SELECT QUERY_TIME_S, DEVICE_SECONDS, H2D_BYTES, COMPILES, QUERY "
        "FROM information_schema.slow_query").rows
    mine = [r for r in rows if r[4].startswith("SELECT b, COUNT(*)")]
    assert mine
    qt, dev_s, h2d, compiles, _q = mine[0]          # newest first
    assert qt > 0.0 and dev_s > 0.0
    assert h2d == ph.h2d_bytes and compiles == ph.compiles


def test_explain_analyze_reports_roofline_fraction(dev_session):
    from tidb_tpu.util import roofline
    s = dev_session
    # deterministic denom; 0.5 GB/s keeps the warm sub-ms fraction
    # well above the 3-decimal display rounding edge
    roofline.set_measured_gbs(0.5)
    try:
        s.query(AGG)
        info = "\n".join(" ".join(str(c) for c in r)
                         for r in s.query("EXPLAIN ANALYZE " + AGG).rows)
        m = re.search(r"roofline_fraction:(\d+\.\d+)", info)
        assert m, info
        frac = float(m.group(1))
        assert 0.0 < frac <= 1.0
        ph = s.last_guard.phases
        assert frac == pytest.approx(
            roofline.fraction(ph.scan_bytes, ph.wall_s, gbs=0.5),
            abs=1e-3)
    finally:
        roofline.set_measured_gbs(0.0)


# ---- satellite: registry fixes -------------------------------------------

def test_metric_rows_include_histogram_buckets():
    r = Registry()
    for v in (0.003, 0.003, 0.05, 1.0):
        r.observe("tidb_tpu_stmt_seconds", v, {"stmt": "Q"})
    rows = {(n, lbl): v for n, lbl, v in r.metric_rows()}
    # cumulative per-bucket rows, matching render_prometheus semantics
    assert rows[("tidb_tpu_stmt_seconds_bucket", "stmt=Q,le=0.005")] == 2.0
    assert rows[("tidb_tpu_stmt_seconds_bucket", "stmt=Q,le=0.1")] == 3.0
    assert rows[("tidb_tpu_stmt_seconds_bucket", "stmt=Q,le=2.0")] == 4.0
    assert rows[("tidb_tpu_stmt_seconds_bucket", "stmt=Q,le=+Inf")] == 4.0
    assert rows[("tidb_tpu_stmt_seconds_count", "stmt=Q")] == 4.0
    # SQL-derivable p50 from the buckets (the point of the fix)
    h = r.hists[("tidb_tpu_stmt_seconds", (("stmt", "Q"),))]
    assert 0.001 <= hist_quantile(h, 0.5) <= 0.005
    assert hist_quantile([[0] * 8, 0.0, 0], 0.99) == 0.0


def test_normalize_sql_collapses_negative_literals():
    pos = normalize_sql("SELECT * FROM t WHERE x = 5")
    neg = normalize_sql("SELECT * FROM t WHERE x = -5")
    assert pos == neg == "SELECT * FROM t WHERE x = ?"
    assert normalize_sql("SELECT * FROM t WHERE x IN (-1, 2, -3)") == \
        "SELECT * FROM t WHERE x IN (?)"
    # binary minus between operands is NOT a sign — keep it
    assert normalize_sql("SELECT a - 5 FROM t") == "SELECT a - ? FROM t"
    assert normalize_sql("SELECT 1 - -2") == "SELECT ? - ?"


def test_registry_processlist_delegates_to_session_registry():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE pr (a BIGINT)")
    seen = {}

    def probe():
        # capture the registry's view WHILE a statement is running
        seen["rows"] = REGISTRY.process_rows()
        return 1

    import tidb_tpu.session as sess_mod
    orig = sess_mod.Session._execute_stmt

    def wrapper(self, stmt):
        rs = orig(self, stmt)
        if not seen:
            probe()
        return rs

    try:
        sess_mod.Session._execute_stmt = wrapper
        s.query("SELECT COUNT(*) FROM pr")
    finally:
        sess_mod.Session._execute_stmt = orig
    rows = seen["rows"]
    assert any(cid == s.conn_id and "pr" in (sql or "")
               for cid, _t, sql in rows)
    # the registry holds NO duplicate processlist state of its own
    assert not hasattr(REGISTRY, "processlist")


# ---- timeline -------------------------------------------------------------

def test_timeline_off_by_default_and_zero_events(dev_session, monkeypatch):
    assert timeline.ENABLED is False
    s = dev_session
    made = []

    class CountingAnnotation:
        def __init__(self, *a, **kw):
            made.append(a)

    # a statement with the timeline off makes no event, no span object and
    # no TraceAnnotation: span() hands out one shared no-op
    monkeypatch.setattr(timeline, "_ANNOTATION", CountingAnnotation)
    before = len(timeline.last_events())
    s.query(AGG)
    assert timeline.ENABLED is False
    assert timeline.global_path() is None
    assert made == [] and len(timeline.last_events()) == before
    assert timeline.span("a", "stmt") is timeline.span("b", "wire", k=1)
    with timeline.span("a", "stmt") as sp:
        assert sp is timeline.span("c", "plan")
    # record() / tag() are no-ops without a collector attached
    timeline.record("x", "sched", dur_us=5.0, pid=1)
    timeline.tag(cache="hit")
    assert len(timeline.last_events()) == before


def test_trace_format_chrome_single_statement(dev_session):
    s = dev_session
    rs = s.query("TRACE FORMAT='chrome' " + AGG)
    assert rs.names == ["trace"]
    doc = json.loads(rs.rows[0][0])
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    assert evs, "no events captured"
    cats = {e["cat"] for e in evs}
    assert {"launch", "drain", "fetch"} <= cats
    assert {e["pid"] for e in evs} == {s.conn_id}
    # scoped capture must detach afterwards
    assert timeline.ENABLED is False
    with pytest.raises(Exception):
        s.query("TRACE FORMAT='bogus' SELECT 1")


def test_cross_session_trace_c8_storm(tmp_path, monkeypatch):
    """8 concurrent sessions with tidb_tpu_trace_dir set produce ONE
    Chrome-trace JSON: parseable, ts monotonic per (pid, tid) within what
    one rendering holds, with scheduler-queue, compile, upload-stream and
    eviction events from at least 2 distinct connections."""
    # ONE rendering: an event is rendered once, a batch at a time, so a
    # span open across a rendering lands in the later batch
    monkeypatch.setattr(timeline, "FLUSH_INTERVAL_S", 3600.0)
    monkeypatch.setattr(timeline, "RENDER_BATCH", 10 ** 9)
    eng = Engine()
    boot = eng.new_session()
    boot.execute(
        "CREATE TABLE st (a BIGINT PRIMARY KEY, b BIGINT, c DOUBLE)")
    boot.execute("INSERT INTO st VALUES " +
                 ",".join(f"({i},{i % 9},{i * 1.5})" for i in range(4000)))
    try:
        sessions = []
        for _ in range(8):
            ss = eng.new_session()
            ss.execute("SET tidb_tpu_engine = 'on'")
            ss.execute("SET tidb_tpu_row_threshold = 1")
            ss.execute(f"SET tidb_tpu_trace_dir = '{tmp_path}'")
            sessions.append(ss)
        errors = []

        def worker(k):
            try:
                for i in range(3):
                    # per-thread distinct aggregate → distinct compile
                    sessions[k].query(
                        f"SELECT b, COUNT(*), SUM(c + {k}) FROM st "
                        f"GROUP BY b")
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        # force evictions into the trace: shrink the HBM budget so the
        # next table the engine opens must evict st's resident slabs
        sessions[0].execute(
            "CREATE TABLE st2 (a BIGINT PRIMARY KEY, b BIGINT)")
        sessions[0].execute("INSERT INTO st2 VALUES " +
                            ",".join(f"({i},{i % 3})" for i in range(2000)))
        sessions[0].execute("SET tidb_tpu_hbm_budget = 1024")
        sessions[0].query("SELECT b, COUNT(*) FROM st2 GROUP BY b")
        path = timeline.flush()
        assert path is not None and os.path.dirname(path) == str(tmp_path)
        files = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
        assert len(files) == 1                      # ONE cross-session file
        doc = json.loads(open(path).read())         # parses cleanly
        evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
        last = {}
        for e in evs:                               # monotonic ts per lane
            key = (e["pid"], e["tid"])
            assert e["ts"] >= last.get(key, -1.0)
            last[key] = e["ts"]
        cats = {e["cat"] for e in evs}
        assert {"sched", "compile", "upload", "cache"} <= cats, cats
        assert len({e["pid"] for e in evs}) >= 2
        names = {e["name"] for e in evs}
        assert "evict" in names
        # process/thread metadata lanes exist for the viewer
        metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert any(m["name"] == "process_name" for m in metas)
        assert any(m["name"] == "thread_name" for m in metas)
    finally:
        timeline.stop_global()
    assert timeline.ENABLED is False


# ---- satellite: status server under concurrency --------------------------

def test_status_server_concurrent_storm_and_clean_shutdown():
    import urllib.request
    from tidb_tpu.util.status_server import StatusServer
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE ss (a BIGINT, b BIGINT)")
    s.execute("INSERT INTO ss VALUES " +
              ",".join(f"({i},{i % 4})" for i in range(500)))
    srv = StatusServer(eng, port=0).start()
    stop = threading.Event()
    errors = []

    def querier():
        ses = eng.new_session()
        while not stop.is_set():
            try:
                ses.query("SELECT b, COUNT(*) FROM ss GROUP BY b")
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return

    def prom_parse(text):
        """Minimal Prometheus text parser: name{labels} value."""
        out = []
        for line in text.splitlines():
            if not line or line.startswith("#"):
                continue
            name_part, _, val = line.rpartition(" ")
            assert name_part, line
            float(val)                               # value must be numeric
            out.append(name_part)
        return out

    def getter(path, check):
        url = f"http://127.0.0.1:{srv.port}{path}"
        for _ in range(10):
            try:
                check(urllib.request.urlopen(url, timeout=5).read())
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return

    qthreads = [threading.Thread(target=querier) for _ in range(8)]
    gthreads = [
        threading.Thread(target=getter, args=(
            "/metrics", lambda b: prom_parse(b.decode()))),
        threading.Thread(target=getter, args=(
            "/status", lambda b: json.loads(b))),
        threading.Thread(target=getter, args=(
            "/statements", lambda b: json.loads(b))),
    ]
    for t in qthreads + gthreads:
        t.start()
    for t in gthreads:
        t.join()
    stop.set()
    for t in qthreads:
        t.join()
    srv.stop()                                       # clean shutdown
    assert not errors, errors[:3]
    # the extended payload keeps the original keys AND the profile ones
    import urllib.error
    with pytest.raises(urllib.error.URLError):
        urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=1)


def test_statements_payload_has_attribution_keys(dev_session):
    import urllib.request
    from tidb_tpu.util.status_server import StatusServer
    s = dev_session
    s.query(AGG)
    srv = StatusServer(port=0).start()
    try:
        data = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/statements"))
        hit = [r for r in data if r["digest"] == AGG]
        assert hit
        for k in ("digest", "count", "sum_s", "device_s", "h2d_bytes",
                  "d2h_bytes", "scan_bytes", "compiles", "queue_p50_ms",
                  "queue_p99_ms", "phase_s"):
            assert k in hit[0], k
        assert hit[0]["scan_bytes"] > 0
    finally:
        srv.stop()


# ---- satellite: metrics lint ---------------------------------------------

def test_check_metrics_clean_on_repo_and_catches_drift(tmp_path):
    import importlib.util
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "check_metrics", os.path.join(repo, "tools", "check_metrics.py"))
    cm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cm)
    assert cm.run(repo) == []
    bad = tmp_path / "bad.py"
    bad.write_text(
        'REGISTRY.inc("queries")\n'
        'REGISTRY.inc("tidb_tpu_fooTotal_total")\n'
        'REGISTRY.observe("tidb_tpu_x_total", 1.0)\n'
        'REGISTRY.inc("tidb_tpu_ok_total", {"weird_label": "v"})\n'
        'REGISTRY.inc(name_var)\n')
    problems = cm.check_file(str(bad))
    assert len(problems) >= 5
    assert any("snake_case" in p for p in problems)
    assert any("unit suffix" in p or "_total" in p for p in problems)
    assert any("vocabulary" in p for p in problems)
