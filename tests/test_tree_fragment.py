"""Join-tree device fragments vs CPU volcano oracle (the Q3 shape).

Differential pattern of the reference's vec-vs-scalar twin tests
(expression/builtin_*_vec_test.go): every device tree result must equal the
CPU hash-join pipeline, including NULL keys and outer/semi/anti semantics
(executor/joiner.go:60 variants)."""

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
    # orders: unique PK (o_id); lineitem: FK with NULLs and misses
    s.execute("CREATE TABLE orders (o_id BIGINT, o_date DATE, "
              "o_prio BIGINT, o_seg VARCHAR(12))")
    s.execute("CREATE TABLE li (l_oid BIGINT, l_price DECIMAL(12,2), "
              "l_disc DECIMAL(12,2), l_ship DATE)")
    rng = np.random.default_rng(11)
    n_orders, n_li = 500, 5000
    rows = []
    for i in range(n_orders):
        seg = ["BUILDING", "AUTO", "STEEL"][int(rng.integers(0, 3))]
        rows.append(f"({i},'199{int(rng.integers(5, 9))}-0{int(rng.integers(1, 10))}-15',"
                    f"{int(rng.integers(0, 5))},'{seg}')")
    s.execute("INSERT INTO orders VALUES " + ",".join(rows))
    rows = []
    for _ in range(n_li):
        # keys beyond n_orders miss; a few NULL keys
        k = int(rng.integers(0, n_orders + 60))
        key = "NULL" if rng.random() < 0.02 else str(k)
        rows.append(f"({key},{round(float(rng.uniform(1, 900)), 2)},"
                    f"{round(float(rng.uniform(0, 0.1)), 2)},"
                    f"'199{int(rng.integers(5, 9))}-0{int(rng.integers(1, 10))}-10')")
    s.execute("INSERT INTO li VALUES " + ",".join(rows))
    return s


def run_device(s, sql, expect_fallback=None):
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
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
        if expect_fallback is None:
            for f in frags:
                assert f.used_device, \
                    f"fell back ({f.fallback_reason}) for: {sql}"
        else:
            assert any(not f.used_device and
                       expect_fallback in (f.fallback_reason or "")
                       for f in frags), \
                f"expected fallback {expect_fallback!r}, got " \
                f"{[f.fallback_reason for f in frags]}"
        return [r for ch in chunks for r in ch.rows()]
    finally:
        s.vars["tidb_tpu_engine"] = "off"


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


TREE_QUERIES = [
    # Q3 shape: join + group + aggregate
    "SELECT o_prio, COUNT(*), SUM(l_price * (1 - l_disc)) FROM li "
    "JOIN orders ON l_oid = o_id GROUP BY o_prio",
    # filters on both sides
    "SELECT o_prio, SUM(l_price) FROM li JOIN orders ON l_oid = o_id "
    "WHERE o_seg = 'BUILDING' AND l_ship < '1998-01-01' GROUP BY o_prio",
    # ungrouped agg over join
    "SELECT COUNT(*), SUM(l_price), MIN(l_disc) FROM li "
    "JOIN orders ON l_oid = o_id WHERE o_prio < 3",
    # string group key from the build side (dictionary flows through join)
    "SELECT o_seg, COUNT(*) FROM li JOIN orders ON l_oid = o_id "
    "GROUP BY o_seg",
]


@pytest.mark.parametrize("sql", TREE_QUERIES)
def test_join_tree_matches_cpu(session, sql):
    dev = run_device(session, sql)
    cpu = session.query(sql).rows
    assert_same(dev, cpu)


def test_left_join_tree(session):
    sql = ("SELECT o_prio, COUNT(*), COUNT(o_id), SUM(l_price) FROM li "
           "LEFT JOIN orders ON l_oid = o_id GROUP BY o_prio")
    assert_same(run_device(session, sql), session.query(sql).rows)


def test_semi_anti_join_tree(session):
    for kw in ("IN", "NOT IN"):
        sql = (f"SELECT COUNT(*), SUM(l_price) FROM li WHERE l_oid "
               f"{kw} (SELECT o_id FROM orders WHERE o_prio = 1)")
        assert_same(run_device(session, sql), session.query(sql).rows)


def test_topn_over_join_tree(session):
    sql = ("SELECT l_oid, l_price, o_prio FROM li JOIN orders "
           "ON l_oid = o_id ORDER BY l_price DESC, l_oid LIMIT 7")
    assert_same(run_device(session, sql), session.query(sql).rows,
                ordered=True)


def test_three_table_tree(session):
    # self-join chain: li ⋈ orders ⋈ orders-copy (both unique builds)
    session.execute("CREATE TABLE prio_names (p_id BIGINT, p_name VARCHAR(8))")
    session.execute("INSERT INTO prio_names VALUES (0,'p0'),(1,'p1'),"
                    "(2,'p2'),(3,'p3'),(4,'p4')")
    sql = ("SELECT p_name, COUNT(*) FROM li JOIN orders ON l_oid = o_id "
           "JOIN prio_names ON o_prio = p_id GROUP BY p_name")
    assert_same(run_device(session, sql), session.query(sql).rows)


def test_non_unique_build_runs_on_device(session):
    # join key o_prio is NOT unique in orders (~100 rows per key): the
    # expansion path materializes every match on device, no CPU fallback
    sql = ("SELECT COUNT(*), SUM(l_price) FROM li JOIN orders "
           "ON l_oid = o_prio")
    dev = run_device(session, sql)
    assert_same(dev, session.query(sql).rows)


def test_non_unique_left_join_device(session):
    # duplicate build keys + probe rows with no match (null-extended) +
    # NULL probe keys, all through the expansion path
    sql = ("SELECT COUNT(*), COUNT(o_id), SUM(o_date) FROM li "
           "LEFT JOIN orders ON l_oid = o_prio")
    dev = run_device(session, sql)
    assert_same(dev, session.query(sql).rows)


def test_string_key_join_device(session):
    # VARCHAR equi key: probe codes remap into the build dictionary space
    session.execute("CREATE TABLE segs (s_name VARCHAR(12), s_rank BIGINT)")
    session.execute("INSERT INTO segs VALUES ('BUILDING',1),('AUTO',2),"
                    "('STEEL',3),('GHOST',4)")
    sql = ("SELECT s_rank, COUNT(*) FROM orders JOIN segs "
           "ON o_seg = s_name GROUP BY s_rank")
    dev = run_device(session, sql)
    assert_same(dev, session.query(sql).rows)


def test_repeat_query_hits_compile_cache(session):
    # second run re-plans (fresh node objects) but reuses the compiled
    # program — prep alignment must be structural, not id-based
    sql = ("SELECT o_seg, COUNT(*), SUM(l_price) FROM li "
           "JOIN orders ON l_oid = o_id WHERE l_ship < '1998-01-01' "
           "GROUP BY o_seg")
    first = run_device(session, sql)
    second = run_device(session, sql)
    assert_same(first, session.query(sql).rows)
    assert_same(second, session.query(sql).rows)


def test_explain_analyze_tree_uses_device(session):
    sql = ("SELECT o_seg, COUNT(*) FROM li JOIN orders ON l_oid = o_id "
           "GROUP BY o_seg")
    run_device(session, sql)
    session.vars["tidb_tpu_engine"] = "on"
    session.vars["tidb_tpu_row_threshold"] = 1
    try:
        rows = session.query("EXPLAIN ANALYZE " + sql).rows
        frag_rows = [r for r in rows if "TpuFragment" in str(r[0])]
        assert frag_rows and "device:yes" in frag_rows[0][2], frag_rows
    finally:
        session.vars["tidb_tpu_engine"] = "off"


def test_multi_slab_join_device(session):
    # slab cap 1024 → li (5000 rows) splits into 5 slabs that concatenate
    # inside the program (the SF=10 shape scaled down)
    session.vars["tidb_tpu_max_slab_rows"] = 1000
    try:
        sql = ("SELECT o_prio, COUNT(*), SUM(l_price * (1 - l_disc)) "
               "FROM li JOIN orders ON l_oid = o_id GROUP BY o_prio")
        assert_same(run_device(session, sql), session.query(sql).rows)
        # non-unique build + multi-slab probe
        sql2 = "SELECT COUNT(*), SUM(l_price) FROM li JOIN orders ON l_oid = o_prio"
        assert_same(run_device(session, sql2), session.query(sql2).rows)
    finally:
        session.vars.pop("tidb_tpu_max_slab_rows", None)


def test_multi_slab_distinct_agg_device(session):
    session.vars["tidb_tpu_max_slab_rows"] = 1000
    try:
        sql = ("SELECT COUNT(DISTINCT l_oid), COUNT(*) FROM li "
               "WHERE l_ship < '1999-01-01'")
        assert_same(run_device(session, sql), session.query(sql).rows)
        sql2 = ("SELECT l_ship, COUNT(DISTINCT l_oid) FROM li "
                "GROUP BY l_ship")
        assert_same(run_device(session, sql2), session.query(sql2).rows)
    finally:
        session.vars.pop("tidb_tpu_max_slab_rows", None)


def test_multi_slab_distinct_mixed_aggs(session):
    # cross-slab pair-set merge (_distinct_pairs + _merge_distinct_states):
    # SUM/AVG over DISTINCT values, several distinct aggs with different
    # args alongside plain aggs, and a dictionary-coded (string) arg
    # (slab cap 300 splits orders too, so the string query is multi-slab)
    session.vars["tidb_tpu_max_slab_rows"] = 300
    try:
        for sql in [
            "SELECT SUM(DISTINCT l_oid), AVG(DISTINCT l_oid), COUNT(*) "
            "FROM li",
            "SELECT o_prio, COUNT(DISTINCT l_oid), SUM(DISTINCT l_oid), "
            "SUM(l_price) FROM li JOIN orders ON l_oid = o_id "
            "GROUP BY o_prio",
            "SELECT o_prio, COUNT(DISTINCT o_seg), COUNT(DISTINCT o_id) "
            "FROM orders GROUP BY o_prio",
        ]:
            assert_same(run_device(session, sql), session.query(sql).rows)
    finally:
        session.vars.pop("tidb_tpu_max_slab_rows", None)


def test_multi_slab_window_device(session):
    session.vars["tidb_tpu_max_slab_rows"] = 1000
    try:
        sql = ("SELECT l_oid, l_price, "
               "RANK() OVER (PARTITION BY l_ship ORDER BY l_price DESC), "
               "SUM(l_price) OVER (PARTITION BY l_ship) FROM li")
        assert_same(run_device(session, sql), session.query(sql).rows)
    finally:
        session.vars.pop("tidb_tpu_max_slab_rows", None)


def test_group_cap_retry_over_join(session):
    # group by the join key itself: ~500 groups, cap 64 forces retry
    session.vars["tidb_tpu_group_cap"] = 64
    try:
        sql = ("SELECT l_oid, COUNT(*), SUM(l_price) FROM li "
               "JOIN orders ON l_oid = o_id GROUP BY l_oid")
        assert_same(run_device(session, sql), session.query(sql).rows)
    finally:
        session.vars.pop("tidb_tpu_group_cap", None)
