"""SQL → distributed execution: planner-inserted exchanges compiled to
shard_map programs over the 8-device virtual mesh, results equal to the
single-device CPU engine (the reference's MPP tests over unistore,
executor/tiflash_test.go pattern — a real cluster faked in-process)."""

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
    s.execute("CREATE TABLE orders (o_id BIGINT, o_prio BIGINT, "
              "o_seg VARCHAR(12))")
    s.execute("CREATE TABLE li (l_oid BIGINT, l_price DECIMAL(12,2), "
              "l_disc DECIMAL(12,2), l_flag VARCHAR(4), l_ship DATE)")
    rng = np.random.default_rng(23)
    n_orders, n_li = 800, 12000
    rows = []
    for i in range(n_orders):
        seg = ["BUILDING", "AUTO", "STEEL"][int(rng.integers(0, 3))]
        rows.append(f"({i},{int(rng.integers(0, 5))},'{seg}')")
    s.execute("INSERT INTO orders VALUES " + ",".join(rows))
    rows = []
    for _ in range(n_li):
        k = int(rng.integers(0, n_orders + 100))
        key = "NULL" if rng.random() < 0.02 else str(k)
        flag = ["A", "N", "R"][int(rng.integers(0, 3))]
        rows.append(f"({key},{round(float(rng.uniform(1, 900)), 2)},"
                    f"{round(float(rng.uniform(0, 0.1)), 2)},'{flag}',"
                    f"'199{int(rng.integers(5, 9))}-0"
                    f"{int(rng.integers(1, 10))}-11')")
    s.execute("INSERT INTO li VALUES " + ",".join(rows))
    # dup_orders: each id appears 1-3 times → a NON-unique join build side
    s.execute("CREATE TABLE dup_orders (d_id BIGINT, d_prio BIGINT, "
              "d_seg VARCHAR(12))")
    rows = []
    for i in range(n_orders):
        seg = ["BUILDING", "AUTO", "STEEL"][int(rng.integers(0, 3))]
        for _ in range(int(rng.integers(1, 4))):
            rows.append(f"({i},{int(rng.integers(0, 5))},'{seg}')")
    s.execute("INSERT INTO dup_orders VALUES " + ",".join(rows))
    s.execute("CREATE TABLE segs (s_name VARCHAR(12), s_rank BIGINT)")
    s.execute("INSERT INTO segs VALUES ('BUILDING',1),('AUTO',2),"
              "('STEEL',3)")
    s.execute("ANALYZE TABLE orders")
    s.execute("ANALYZE TABLE li")
    s.execute("ANALYZE TABLE dup_orders")
    s.execute("ANALYZE TABLE segs")
    return s


def run_dist(s, sql, shards=8):
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    s.vars["tidb_tpu_dist_devices"] = shards
    try:
        plan = s._plan(parse(sql)[0])
        root = build(plan)
        chunks = run_to_completion(root, s._exec_ctx())
        frags = []

        def walk(e):
            if isinstance(e, TpuFragmentExec):
                frags.append(e)
            for c in getattr(e, "children", []):
                walk(c)

        walk(root)
        assert frags, f"no fragment extracted for: {sql}"
        for f in frags:
            assert f.plan.dist == shards, \
                f"fragment not distributed for: {sql}"
            assert f.used_device, \
                f"fell back ({f.fallback_reason}) for: {sql}"
        return [r for ch in chunks for r in ch.rows()]
    finally:
        s.vars["tidb_tpu_engine"] = "off"
        s.vars.pop("tidb_tpu_dist_devices", None)


def assert_same(rows1, rows2, ordered=False):
    assert len(rows1) == len(rows2), (len(rows1), len(rows2))
    if not ordered:
        rows1 = sorted(rows1, key=str)
        rows2 = sorted(rows2, key=str)
    for r1, r2 in zip(rows1, rows2):
        for v1, v2 in zip(r1, r2):
            if isinstance(v1, float) and v2 is not None:
                assert abs(v1 - v2) <= 1e-5 * max(1.0, abs(v2)), (r1, r2)
            else:
                assert v1 == v2, (r1, r2)


# ---- Q1 shape: sharded chain, two-phase distributed aggregate -------------

def test_dist_q1_chain(session):
    sql = ("SELECT l_flag, COUNT(*), SUM(l_price), AVG(l_disc), "
           "MIN(l_price), MAX(l_price) FROM li "
           "WHERE l_ship <= '1998-09-02' GROUP BY l_flag")
    assert_same(run_dist(session, sql), session.query(sql).rows)


def test_dist_ungrouped_agg(session):
    sql = "SELECT COUNT(*), SUM(l_price), MIN(l_disc) FROM li"
    assert_same(run_dist(session, sql), session.query(sql).rows)


def test_dist_high_cardinality_groups(session):
    sql = "SELECT l_oid, COUNT(*), SUM(l_price) FROM li GROUP BY l_oid"
    assert_same(run_dist(session, sql), session.query(sql).rows)


# ---- Q3 shape: exchanges under joins --------------------------------------

def test_dist_q3_join_agg(session):
    sql = ("SELECT o_prio, COUNT(*), SUM(l_price * (1 - l_disc)) FROM li "
           "JOIN orders ON l_oid = o_id GROUP BY o_prio")
    assert_same(run_dist(session, sql), session.query(sql).rows)


def test_dist_join_filters_both_sides(session):
    sql = ("SELECT o_seg, COUNT(*), SUM(l_price) FROM li "
           "JOIN orders ON l_oid = o_id "
           "WHERE o_prio < 3 AND l_ship < '1998-01-01' GROUP BY o_seg")
    assert_same(run_dist(session, sql), session.query(sql).rows)


def test_dist_left_join(session):
    sql = ("SELECT o_prio, COUNT(*), COUNT(o_id) FROM li "
           "LEFT JOIN orders ON l_oid = o_id GROUP BY o_prio")
    assert_same(run_dist(session, sql), session.query(sql).rows)


def test_dist_topn_over_join(session):
    sql = ("SELECT l_oid, l_price, o_prio FROM li JOIN orders "
           "ON l_oid = o_id ORDER BY l_price DESC, l_oid LIMIT 9")
    assert_same(run_dist(session, sql), session.query(sql).rows,
                ordered=True)


def test_exchange_in_explain(session):
    session.vars["tidb_tpu_engine"] = "on"
    session.vars["tidb_tpu_row_threshold"] = 1
    session.vars["tidb_tpu_dist_devices"] = 8
    try:
        rows = session.query(
            "EXPLAIN SELECT o_prio, COUNT(*) FROM li JOIN orders "
            "ON l_oid = o_id GROUP BY o_prio").rows
        txt = "\n".join(str(r) for r in rows)
        assert "Exchange" in txt, txt
        assert "shards:8" in txt, txt
    finally:
        session.vars["tidb_tpu_engine"] = "off"
        session.vars.pop("tidb_tpu_dist_devices", None)


def test_dist_distinct_grouped(session):
    # DISTINCT distributes via a re-keyed exchange on the group keys
    sql = ("SELECT l_flag, COUNT(DISTINCT l_oid), COUNT(*) FROM li "
           "GROUP BY l_flag")
    assert_same(run_dist(session, sql), session.query(sql).rows)


def test_dist_distinct_global(session):
    sql = "SELECT COUNT(DISTINCT l_oid) FROM li"
    assert_same(run_dist(session, sql), session.query(sql).rows)


def test_skewed_exchange_retries_exactly_once(session):
    # 3 distinct group keys hash onto ≤3 of 8 shards: the re-key exchange
    # overflows a deliberately tiny initial bucket cap; the exchange
    # reports its exact need, so recovery is ONE recompile (per-exchange
    # needs). This pins the MONOLITHIC oracle path —
    # the staged exchange's per-rank equivalent (one skewed rank = one
    # recompile) is pinned in tests/test_staged_exchange.py
    from tidb_tpu.executor import dist_fragment as DF
    sql = ("SELECT l_flag, COUNT(DISTINCT l_oid) FROM li GROUP BY l_flag")
    compiles = []
    orig = DF.DistTreeProgram.__init__

    def counting(self, *a, **k):
        compiles.append(1)
        return orig(self, *a, **k)

    DF.DistTreeProgram.__init__ = counting
    session.vars["tidb_tpu_exchange_bucket_cap"] = 64
    session.vars["tidb_tpu_dist_staged_exchange"] = "off"
    try:
        from tidb_tpu.executor.compile_cache import _COMPILE_CACHE
        _COMPILE_CACHE.clear()
        got = run_dist(session, sql)
    finally:
        DF.DistTreeProgram.__init__ = orig
        session.vars.pop("tidb_tpu_exchange_bucket_cap", None)
        session.vars.pop("tidb_tpu_dist_staged_exchange", None)
    assert_same(got, session.query(sql).rows)
    assert len(compiles) == 2, compiles    # initial + exactly one retry


def test_dist_fallback_strips_exchanges(session):
    # a runtime fallback of a DISTRIBUTED fragment must run on CPU even
    # though the plan carries Exchange nodes (regression: 'no executor
    # for PhysExchange')
    from tidb_tpu.util import failpoint
    sql = ("SELECT o_prio, COUNT(*) FROM li JOIN orders ON l_oid = o_id "
           "GROUP BY o_prio")
    failpoint.enable("device-fragment",
                     raise_=RuntimeError("injected device loss"))
    session.vars["tidb_tpu_engine"] = "on"
    session.vars["tidb_tpu_row_threshold"] = 1
    session.vars["tidb_tpu_dist_devices"] = 8
    try:
        got = session.query(sql).rows
    finally:
        failpoint.disable("device-fragment")
        session.vars["tidb_tpu_engine"] = "off"
        session.vars.pop("tidb_tpu_dist_devices", None)
    assert_same(got, session.query(sql).rows)


# ---- single-chip parity: non-unique builds, string keys, window/row roots


def test_dist_nonunique_build_join(session):
    # duplicate build keys: the unique bet is lost on some shard; the
    # expand-mode re-trace (per-shard out caps) must recover, not fall
    # back (round-3 seam: FragmentFallback("non-unique join build side"))
    sql = ("SELECT d_prio, COUNT(*), SUM(l_price) FROM li "
           "JOIN dup_orders ON l_oid = d_id GROUP BY d_prio")
    assert_same(run_dist(session, sql), session.query(sql).rows)


def test_dist_nonunique_left_join(session):
    sql = ("SELECT d_seg, COUNT(*), COUNT(d_id) FROM li "
           "LEFT JOIN dup_orders ON l_oid = d_id GROUP BY d_seg")
    assert_same(run_dist(session, sql), session.query(sql).rows)


def test_dist_varchar_join_key(session):
    # string equi keys: dictionaries unified host-side before sharding so
    # equal strings hash equal across scans (round-3 seam: "exchange-side
    # dictionary unification TBD")
    sql = ("SELECT s_rank, COUNT(*) FROM li "
           "JOIN orders ON l_oid = o_id "
           "JOIN segs ON o_seg = s_name GROUP BY s_rank")
    assert_same(run_dist(session, sql), session.query(sql).rows)


def test_dist_varchar_key_groupby_string(session):
    sql = ("SELECT o_seg, s_rank, COUNT(*) FROM orders "
           "JOIN segs ON o_seg = s_name GROUP BY o_seg, s_rank")
    assert_same(run_dist(session, sql), session.query(sql).rows)


def test_dist_window_root(session):
    # window root: the planner inserts a hash exchange on the partition
    # keys so per-shard windows are globally exact
    sql = ("SELECT l_flag, l_price, "
           "SUM(l_price) OVER (PARTITION BY l_flag ORDER BY l_price), "
           "ROW_NUMBER() OVER (PARTITION BY l_flag ORDER BY l_price DESC)"
           " FROM li")
    assert_same(run_dist(session, sql), session.query(sql).rows)


def test_dist_row_root_join(session):
    # selection/join row root: per-shard rows, host concatenates
    sql = ("SELECT l_oid, l_price, o_prio FROM li "
           "JOIN orders ON l_oid = o_id WHERE l_price > 890")
    assert_same(run_dist(session, sql), session.query(sql).rows)


def test_dist_matches_single_device_tree(session):
    # same SQL through the single-shard tree path and 8-shard dist path
    sql = ("SELECT o_seg, COUNT(*), SUM(l_price) FROM li "
           "JOIN orders ON l_oid = o_id GROUP BY o_seg")
    dist = run_dist(session, sql)
    session.vars["tidb_tpu_engine"] = "on"
    session.vars["tidb_tpu_row_threshold"] = 1
    try:
        single = session.query(sql).rows
    finally:
        session.vars["tidb_tpu_engine"] = "off"
    assert_same(dist, single)


def test_dist_partitioned_table_pruned_scan(session):
    """Partition pruning composes with the multi-chip path: the pruned
    region set is what gets slabbed and sharded across the mesh."""
    s = session
    s.vars["tidb_tpu_engine"] = "off"
    s.execute("CREATE TABLE pt (id BIGINT, g BIGINT, v BIGINT) "
              "PARTITION BY RANGE (id) ("
              "PARTITION p0 VALUES LESS THAN (4000), "
              "PARTITION p1 VALUES LESS THAN (8000), "
              "PARTITION p2 VALUES LESS THAN (MAXVALUE))")
    rng = np.random.default_rng(31)
    s.execute("INSERT INTO pt VALUES " + ",".join(
        f"({int(rng.integers(0, 12000))},{int(rng.integers(0, 7))},"
        f"{int(rng.integers(0, 100))})" for _ in range(12000)))
    s.execute("ANALYZE TABLE pt")
    sql = ("SELECT g, COUNT(*), SUM(v) FROM pt WHERE id < 8000 "
           "GROUP BY g ORDER BY g")
    want = s.query(sql).rows
    got = run_dist(s, sql)
    assert got == want
