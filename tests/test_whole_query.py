"""Whole-query compilation over the shapes PR 12 left on the host:
EXISTS/IN semijoins, uncorrelated scalar subqueries, LIMIT-over-join
roots, and multi-arg / multiple-DISTINCT aggregates — each fused vs the
CPU volcano oracle, plus warm launch-count pins."""

import numpy as np
import pytest

from tidb_tpu.executor import run_to_completion

from tidb_tpu.executor.builder import build
from tidb_tpu.executor.fragment import TpuFragmentExec
from tidb_tpu.parser import parse
from tidb_tpu.session import Engine


@pytest.fixture(scope="module")
def session():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE ord (ok BIGINT, pri VARCHAR(8), "
              "odate BIGINT, ck BIGINT)")
    s.execute("CREATE TABLE li (ok BIGINT, qty BIGINT, price DOUBLE, "
              "disc DOUBLE, sdate BIGINT, cdate BIGINT)")
    rng = np.random.default_rng(23)
    orows = []
    for k in range(1500):
        pri = ["'1-URG'", "'2-HIGH'", "'3-MED'", "'4-LOW'"][
            int(rng.integers(0, 4))]
        orows.append(f"({k},{pri},{int(rng.integers(0, 1000))},"
                     f"{int(rng.integers(0, 200))})")
    for i in range(0, len(orows), 500):
        s.execute("INSERT INTO ord VALUES " + ",".join(orows[i:i + 500]))
    lrows = []
    for _ in range(5000):
        ok = int(rng.integers(0, 1800))       # some orders have no items
        sd = int(rng.integers(0, 1000))
        lrows.append(f"({ok},{int(rng.integers(1, 50))},"
                     f"{round(float(rng.uniform(1, 1000)), 2)},"
                     f"{round(float(rng.uniform(0, 0.1)), 2)},"
                     f"{sd},{sd + int(rng.integers(-30, 30))})")
    for i in range(0, len(lrows), 500):
        s.execute("INSERT INTO li VALUES " + ",".join(lrows[i:i + 500]))
    s.execute("CREATE TABLE md (g BIGINT, a BIGINT, b BIGINT, "
              "v BIGINT)")
    mrows = []
    for _ in range(3000):
        mrows.append(f"({int(rng.integers(0, 6))},"
                     f"{int(rng.integers(0, 12))},"
                     f"{int(rng.integers(0, 9))},"
                     f"{int(rng.integers(0, 400))})")
    for i in range(0, len(mrows), 500):
        s.execute("INSERT INTO md VALUES " + ",".join(mrows[i:i + 500]))
    return s


def run_plan(s, sql):
    plan = s._plan(parse(sql)[0])
    root = build(plan)
    chunks = run_to_completion(root, s._exec_ctx())
    frags = []

    def walk(e):
        if isinstance(e, TpuFragmentExec):
            frags.append(e)
        for ch in getattr(e, "children", []):
            walk(ch)

    walk(root)
    return [r for ch in chunks for r in ch.rows()], frags


def device_vs_host(s, sql):
    host, _ = run_plan(s, sql)
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    try:
        dev, frags = run_plan(s, sql)
    finally:
        s.vars["tidb_tpu_engine"] = "off"
    assert frags, f"no fragment extracted for: {sql}"
    for f in frags:
        assert f.used_device, f"fell back ({f.fallback_reason}): {sql}"
    hs, ds = sorted(host, key=repr), sorted(dev, key=repr)
    assert len(hs) == len(ds), (len(hs), len(ds), sql)
    for h, d in zip(hs, ds):
        for x, y in zip(h, d):
            if isinstance(x, float) and y is not None:
                assert abs(x - y) <= 1e-6 * max(1.0, abs(x)), (h, d)
            else:
                assert x == y, (h, d)


# ---- semijoins and scalar subqueries --------------------------------------

def test_exists_semijoin_fused(session):
    device_vs_host(session,
                   "SELECT pri, COUNT(*) FROM ord WHERE odate < 800 "
                   "AND EXISTS (SELECT 1 FROM li WHERE li.ok = ord.ok "
                   "AND li.cdate < li.sdate) GROUP BY pri")


def test_in_semijoin_fused(session):
    device_vs_host(session,
                   "SELECT pri, COUNT(*) FROM ord WHERE ok IN "
                   "(SELECT ok FROM li WHERE qty > 40) GROUP BY pri")


def test_scalar_subquery_in_where_fused(session):
    device_vs_host(session,
                   "SELECT COUNT(*), SUM(price) FROM li WHERE qty < "
                   "(SELECT AVG(qty) FROM li WHERE sdate < 500)")


def test_scalar_subquery_in_having_fused(session):
    device_vs_host(session,
                   "SELECT ok, SUM(price * qty) FROM li GROUP BY ok "
                   "HAVING SUM(price * qty) > (SELECT "
                   "SUM(price * qty) * 0.002 FROM li)")


# ---- LIMIT pushdown over join roots ---------------------------------------

def test_limit_over_join_fused(session):
    s = session
    full_sql = ("SELECT ord.pri, li.qty, li.price FROM li "
                "JOIN ord ON li.ok = ord.ok WHERE li.sdate < 700")
    sql = full_sql + " LIMIT 13"
    full = {repr(r) for r in s.query(full_sql).rows}
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    try:
        dev, frags = run_plan(s, sql)
    finally:
        s.vars["tidb_tpu_engine"] = "off"
    assert frags and all(f.used_device for f in frags), \
        [f.fallback_reason for f in frags]
    # LIMIT without ORDER BY picks ANY 13 rows — pin count + membership
    assert len(dev) == 13
    assert all(repr(r) in full for r in dev)


# ---- multi-arg and multiple DISTINCT aggregates ---------------------------

def test_multi_arg_count_distinct_fused(session):
    device_vs_host(session,
                   "SELECT g, COUNT(DISTINCT a, b), COUNT(*) FROM md "
                   "GROUP BY g")


def test_multiple_distinct_aggs_fused(session):
    device_vs_host(session,
                   "SELECT g, COUNT(DISTINCT a), COUNT(DISTINCT b), "
                   "SUM(v) FROM md GROUP BY g")


def test_multiple_distinct_scalar_root_fused(session):
    device_vs_host(session,
                   "SELECT COUNT(DISTINCT a), COUNT(DISTINCT b), "
                   "COUNT(DISTINCT a, b) FROM md WHERE v < 300")


# ---- warm launch-count pins -----------------------------------------------

@pytest.mark.parametrize("sql,max_launches", [
    # single slab: partial + fused finalize
    ("SELECT g, COUNT(DISTINCT a, b), SUM(v) FROM md GROUP BY g", 2),
    ("SELECT pri, COUNT(*) FROM ord WHERE ok IN "
     "(SELECT ok FROM li WHERE qty > 40) GROUP BY pri", 3),
    ("SELECT ord.pri, li.qty FROM li JOIN ord ON li.ok = ord.ok "
     "WHERE li.sdate < 700 LIMIT 13", 3),
])
def test_warm_launch_counts(session, sql, max_launches):
    s = session
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    try:
        s.query(sql)               # compile + first touch
        s.query(sql)               # warm
        ph = s.last_guard.phases
        assert 1 <= ph.programs_launched <= max_launches, \
            ph.programs_launched
    finally:
        s.vars["tidb_tpu_engine"] = "off"


def test_same_statement_subquery_does_not_poison_specialization(session):
    """Regression: a plan-time uncorrelated subquery executes its own
    fragment under the SAME guard.sql as the outer statement; the
    specialization key must tell the two chains apart or the outer
    fragment adopts the subquery's compiled signature (wrong agg-state
    layout → device-error fallback)."""
    s = session
    sql = ("SELECT COUNT(*), SUM(price) FROM li WHERE qty > "
           "(SELECT AVG(qty) FROM li)")
    host = s.query(sql).rows
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    s.vars["tidb_tpu_strict"] = True      # any device fallback raises
    try:
        cold = s.query(sql).rows
        warm = s.query(sql).rows          # spec-cache hit path
    finally:
        s.vars["tidb_tpu_strict"] = False
        s.vars["tidb_tpu_engine"] = "off"
    for got in (cold, warm):
        assert len(got) == len(host)
        for h, d in zip(host, got):
            assert h[0] == d[0]
            assert abs(h[1] - d[1]) <= 1e-6 * max(1.0, abs(h[1]))
