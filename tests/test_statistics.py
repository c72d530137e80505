"""Statistics: histogram/NDV/TopN build + selectivity + planner wiring
(ref: statistics/histogram.go, statistics/selectivity.go,
planner/core/find_best_task.go)."""

import numpy as np
import pytest

from tidb_tpu.statistics import (ColumnStats, analyze_columns,
                                 build_column_stats, expr_selectivity)
from tidb_tpu.parser import parse
from tidb_tpu.session import Engine


def test_column_stats_exact_small():
    vals = np.array([1, 2, 2, 3, 3, 3, 4, 4, 4, 4], dtype=np.int64)
    valid = np.ones(10, dtype=bool)
    cs = build_column_stats(vals, valid, 10)
    assert cs.ndv == 4
    assert cs.null_count == 0
    assert cs.min_val == 1 and cs.max_val == 4
    assert abs(cs.eq_selectivity(4) - 0.4) < 1e-9
    assert abs(cs.eq_selectivity(1) - 0.1) < 1e-9
    assert cs.eq_selectivity(99) <= 0.1
    # range: values ≤ 2 are 3 of 10
    assert abs(cs.range_selectivity(hi=2) - 0.3) < 0.05


def test_column_stats_nulls():
    vals = np.arange(100, dtype=np.int64)
    valid = np.ones(100, dtype=bool)
    valid[:25] = False
    cs = build_column_stats(vals, valid, 100)
    assert cs.null_count == 25
    assert abs(cs.null_fraction() - 0.25) < 1e-9
    assert cs.ndv == 75


def test_column_stats_sampled_ndv():
    rng = np.random.default_rng(3)
    # 4M rows, 1000 distinct values → sampling path, NDV estimate close
    vals = rng.integers(0, 1000, 4_000_000).astype(np.int64)
    cs = build_column_stats(vals, np.ones(len(vals), bool), len(vals))
    assert 900 <= cs.ndv <= 1100
    sel = cs.eq_selectivity(5)
    assert 0.0005 <= sel <= 0.002


def test_string_stats():
    vals = np.array(["ant", "bee", "ant", "cow", "ant"], dtype=object)
    cs = build_column_stats(vals, np.ones(5, bool), 5)
    assert cs.ndv == 3
    assert abs(cs.eq_selectivity("ant") - 0.6) < 1e-9
    # prefix range [a, b): the three 'ant's
    assert abs(cs.range_selectivity(lo="a", hi="b", hi_incl=False) - 0.6) \
        < 0.05


@pytest.fixture()
def session():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE st (a BIGINT, b BIGINT, c VARCHAR(8), "
              "d DECIMAL(8,2))")
    rng = np.random.default_rng(9)
    rows = []
    for i in range(20000):
        a = int(rng.integers(0, 10))          # ndv 10
        b = i                                 # ndv 20000 (unique)
        c = ["x", "y"][int(rng.integers(0, 2))]
        d = round(float(rng.uniform(0, 100)), 2)
        rows.append(f"({a},{b},'{c}',{d})")
    s.execute("INSERT INTO st VALUES " + ",".join(rows))
    s.execute("ANALYZE TABLE st")
    return s


def _plan(s, sql):
    return s._plan(parse(sql)[0])


def _find(plan, name):
    if type(plan).__name__ == name:
        return plan
    for c in plan.children:
        hit = _find(c, name)
        if hit is not None:
            return hit
    if hasattr(plan, "root"):
        return _find(plan.root, name)
    return None


def test_scan_filter_selectivity(session):
    p = _plan(session, "SELECT * FROM st WHERE a = 3")
    scan = _find(p, "PhysTableScan")
    assert 1200 <= scan.est_rows <= 2800     # ~1/10 of 20000

    p = _plan(session, "SELECT * FROM st WHERE d < 25.0")
    scan = _find(p, "PhysTableScan")
    assert 3500 <= scan.est_rows <= 6500     # ~25%


def test_agg_group_estimate(session):
    p = _plan(session, "SELECT a, COUNT(*) FROM st GROUP BY a")
    agg = _find(p, "PhysHashAgg")
    assert agg.est_reliable
    assert 8 <= agg.est_rows <= 13

    p = _plan(session, "SELECT b, COUNT(*) FROM st GROUP BY b")
    agg = _find(p, "PhysHashAgg")
    assert agg.est_reliable
    assert 15000 <= agg.est_rows <= 25000


def test_join_estimate(session):
    eng = session.engine
    s2 = eng.new_session()
    s2.execute("CREATE TABLE dim (k BIGINT, v BIGINT)")
    s2.execute("INSERT INTO dim VALUES " +
               ",".join(f"({i},{i * 2})" for i in range(100)))
    s2.execute("ANALYZE TABLE dim")
    # FK join: |st| rows survive ≈ |st| * |dim| / ndv(b)=20000 = 100
    p = _plan(s2, "SELECT * FROM st JOIN dim ON b = k")
    join = _find(p, "PhysHashJoin")
    assert 50 <= join.est_rows <= 300


def test_stats_feed_group_cap(session):
    from tidb_tpu.executor.agg_slabs import initial_group_cap
    p = _plan(session, "SELECT b, COUNT(*) FROM st GROUP BY b")
    agg = _find(p, "PhysHashAgg")
    cap = initial_group_cap(agg, 1 << 16, 1 << 23)
    assert cap >= 32768          # ≥ ndv(b)=20000 with headroom

    p = _plan(session, "SELECT a, COUNT(*) FROM st GROUP BY a")
    agg = _find(p, "PhysHashAgg")
    cap = initial_group_cap(agg, 1 << 16, 1 << 23)
    assert cap == 1024           # small reliable estimate → floor

    # no GROUP BY: one group, whatever the estimate or the default say
    p = _plan(session, "SELECT COUNT(*), SUM(a) FROM st WHERE b > 5")
    agg = _find(p, "PhysHashAgg")
    assert not agg.group_exprs
    assert initial_group_cap(agg, 1 << 16, 1 << 23) == 1
    agg.est_reliable = False
    assert initial_group_cap(agg, 1 << 16, 1 << 23) == 1


def _wait_stats(eng, tid, pred=lambda st: True, timeout=5.0):
    import time as _t
    deadline = _t.time() + timeout
    while _t.time() < deadline:
        st = eng.table_stats.get(tid)
        if st is not None and pred(st):
            return st
        _t.sleep(0.02)
    raise AssertionError("auto-analyze did not fire in time")


def test_auto_analyze_lifecycle():
    # BACKGROUND auto-analyze (statistics/handle/update.go:939 on the
    # domain loop, domain/domain.go:1249): stats appear with NO query at
    # all after a write burst — the triggering statement pays nothing —
    # refresh after 10x growth, and the plan keyed on the stale stats
    # version is replanned
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE aa (a BIGINT, b BIGINT)")
    s.execute("INSERT INTO aa VALUES " +
              ",".join(f"({i},{i % 7})" for i in range(2000)))
    tid = eng.catalog.info_schema.table("aa").id
    # no SELECT issued: the background worker alone produces the stats
    _wait_stats(eng, tid, lambda st: st.row_count == 2000)
    sql = "SELECT b, COUNT(*) FROM aa GROUP BY b"
    plan1 = s._plan(parse(sql)[0])
    # 10x growth → ratio trigger → fresh stats + replanned estimate
    s.execute("INSERT INTO aa VALUES " +
              ",".join(f"({i},{i % 7})" for i in range(2000, 20000)))
    _wait_stats(eng, tid, lambda st: st.row_count == 20000)
    plan2 = s._plan(parse(sql)[0])
    assert plan2 is not plan1              # stats version keyed the cache
    assert plan2.est_rows == plan1.est_rows == 7  # NDV(b) stays 7


def test_auto_analyze_disabled_and_small_tables():
    import time as _t
    eng = Engine()
    s = eng.new_session()
    # disable GLOBALLY first: the analyzer is engine-wide (global scope,
    # like the reference's tidb_enable_auto_analyze)
    s.execute("SET GLOBAL tidb_enable_auto_analyze = 'off'")
    s.execute("CREATE TABLE small (a BIGINT)")
    s.execute("INSERT INTO small VALUES (1),(2),(3)")
    tid = eng.catalog.info_schema.table("small").id
    s.execute("CREATE TABLE big (a BIGINT)")
    s.execute("INSERT INTO big VALUES " +
              ",".join(f"({i})" for i in range(1500)))
    bid = eng.catalog.info_schema.table("big").id
    _t.sleep(0.6)                          # > one worker lease
    assert bid not in eng.table_stats      # disabled
    s.execute("SET GLOBAL tidb_enable_auto_analyze = 'on'")
    eng._kick_analyze()
    _wait_stats(eng, bid)
    assert tid not in eng.table_stats      # under min_rows, never fires


def test_auto_analyze_ignores_rolled_back_writes():
    # modify counts flush at COMMIT: a rolled-back INSERT must not
    # trigger a spurious re-ANALYZE (statistics/handle/update.go flushes
    # modifyCount on commit)
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE rbk (a BIGINT)")
    s.execute("INSERT INTO rbk VALUES " +
              ",".join(f"({i})" for i in range(1500)))
    tid = eng.catalog.info_schema.table("rbk").id
    v0 = _wait_stats(eng, tid).version           # baseline auto-analyze
    s.execute("BEGIN")
    s.execute("INSERT INTO rbk VALUES " +
              ",".join(f"({i})" for i in range(50000, 70000)))
    s.execute("ROLLBACK")
    import time as _t
    _t.sleep(0.6)                                # > one worker lease
    assert eng.table_stats[tid].version == v0    # no spurious re-analyze
    assert eng.modify_counts.get(tid, 0) == 0
    # committed writes DO count
    s.execute("BEGIN")
    s.execute("INSERT INTO rbk VALUES " +
              ",".join(f"({i})" for i in range(50000, 70000)))
    s.execute("COMMIT")
    _wait_stats(eng, tid, lambda st: st.row_count == 21500)


def test_cmsketch_skew_plan_choice():
    """CM-sketch point estimates (statistics/cmsketch.go:46): on a
    skewed column, equality against a hot mid-tail value (outside TopN's
    reach in a wide-key table) estimates high and keeps the table scan,
    while a rare value estimates low and flips to the index path —
    pinned via EXPLAIN in both directions."""
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE sk (k BIGINT, v BIGINT, INDEX ik (k))")
    rows = []
    # values 0..39 hot (1000 rows each = beyond TOPN_SIZE=32 slots),
    # values 1000..10999 rare (1 row each)
    for hot in range(40):
        rows.extend(f"({hot},{i})" for i in range(1000))
    rows.extend(f"({1000 + i},0)" for i in range(10000))
    s.execute("INSERT INTO sk VALUES " + ",".join(rows))
    s.execute("ANALYZE TABLE sk")
    st = eng.table_stats[eng.catalog.info_schema.table("sk").id]
    cs = st.columns[0]
    assert cs.cms is not None
    # hot mid-tail value (39 may fall outside the 32-slot TopN):
    # estimate must be ~1000 rows, not the uniform ~5
    hot_est = cs.eq_selectivity(39) * st.row_count
    rare_est = cs.eq_selectivity(5000) * st.row_count
    assert hot_est > 200, hot_est
    assert rare_est < 50, rare_est
    plan_hot = "\n".join(str(r) for r in s.query(
        "EXPLAIN SELECT SUM(v) FROM sk WHERE k = 39").rows)
    plan_rare = "\n".join(str(r) for r in s.query(
        "EXPLAIN SELECT SUM(v) FROM sk WHERE k = 5000").rows)
    # the sketch's 1000x estimate difference is visible in EXPLAIN
    import re as _re
    est_hot = int(_re.search(r"IndexScan', '(\d+)'", plan_hot).group(1))
    est_rare = int(_re.search(r"IndexScan', '(\d+)'", plan_rare).group(1))
    assert est_hot > 500 and est_rare <= 50, (est_hot, est_rare)
    # ...and flips a real operator choice: the join build side (the
    # smaller side builds; a TopN-missed hot key must not look small)
    s.execute("CREATE TABLE mid (k BIGINT, w BIGINT)")
    s.execute("INSERT INTO mid VALUES " + ",".join(
        f"({i},{i})" for i in range(100)))
    s.execute("ANALYZE TABLE mid")
    jh = "\n".join(str(r) for r in s.query(
        "EXPLAIN SELECT COUNT(*) FROM sk JOIN mid ON sk.v = mid.w "
        "WHERE sk.k = 39").rows)
    jr = "\n".join(str(r) for r in s.query(
        "EXPLAIN SELECT COUNT(*) FROM sk JOIN mid ON sk.v = mid.w "
        "WHERE sk.k = 5000").rows)
    assert "build:right" in jh     # hot side is BIG: build the 100-row mid
    assert "build:left" in jr      # rare side is tiny: it builds
