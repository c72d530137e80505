"""Views (CREATE/DROP/SHOW CREATE VIEW, builder expansion — ref:
ddl/ddl_api.go:2186, logical_plan_builder.go:4376 BuildDataSourceFromView)
and optimizer hints (/*+ ... */ steering the physical search — ref:
planner/optimize.go:138)."""

import numpy as np
import pytest

from tidb_tpu.errors import DDLError, PlanError, TableExistsError
from tidb_tpu.session import Engine


def _explain(s, sql):
    return "\n".join(str(r) for r in s.query("EXPLAIN " + sql).rows)


@pytest.fixture()
def s():
    eng = Engine()
    # stats_version is part of the plan-cache key: a background analyze
    # pass landing between two identical queries (it does, on a loaded
    # host) re-plans the second one and breaks the cache-hit assertions
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    s.execute("CREATE TABLE t (a BIGINT, b BIGINT, g VARCHAR(4))")
    s.execute("INSERT INTO t VALUES " + ",".join(
        f"({i},{i % 10},'g{i % 3}')" for i in range(1000)))
    s.execute("ANALYZE TABLE t")
    return s


def test_view_basics(s):
    s.execute("CREATE VIEW v AS SELECT g, SUM(a) AS total FROM t GROUP BY g")
    rows = s.query("SELECT * FROM v ORDER BY g").rows
    assert len(rows) == 3 and rows[0][0] == "g0"
    # views join with tables and take aliases
    r = s.query("SELECT v.total FROM v JOIN t ON v.g = t.g "
                "WHERE t.a = 0").rows
    assert len(r) == 1
    # WHERE over the view projects through
    assert s.query("SELECT total FROM v WHERE g = 'g1'").rows == \
        s.query("SELECT SUM(a) FROM t WHERE g = 'g1'").rows


def test_view_column_list_and_or_replace(s):
    s.execute("CREATE VIEW v2 (grp, cnt) AS SELECT g, COUNT(*) FROM t "
              "GROUP BY g")
    assert s.query("SELECT grp, cnt FROM v2 ORDER BY grp").rows[0] == \
        ("g0", 334)
    with pytest.raises(TableExistsError):
        s.execute("CREATE VIEW v2 AS SELECT 1")
    s.execute("CREATE OR REPLACE VIEW v2 AS SELECT a FROM t WHERE a < 3")
    assert len(s.query("SELECT * FROM v2").rows) == 3
    with pytest.raises(TableExistsError):
        s.execute("CREATE VIEW t AS SELECT 1")   # name clash with table


def test_view_nesting_and_drop(s):
    s.execute("CREATE VIEW base AS SELECT a, b FROM t WHERE a < 100")
    s.execute("CREATE VIEW top1 AS SELECT b, COUNT(*) AS n FROM base "
              "GROUP BY b")
    assert len(s.query("SELECT * FROM top1").rows) == 10
    names = [r[0] for r in s.query("SHOW TABLES").rows]
    assert "base" in names and "top1" in names
    ddl = s.query("SHOW CREATE VIEW base").rows[0][1]
    assert ddl.startswith("CREATE VIEW `base` AS SELECT")
    s.execute("DROP VIEW top1, base")
    with pytest.raises(Exception):
        s.query("SELECT * FROM base")
    s.execute("DROP VIEW IF EXISTS base")   # no error


def test_view_dml_rejected_and_schema_tracking(s):
    s.execute("CREATE VIEW vd AS SELECT a FROM t")
    with pytest.raises(DDLError):
        s.execute("INSERT INTO vd VALUES (1)")
    with pytest.raises(DDLError):
        s.execute("DELETE FROM vd")
    # invalid definitions fail at CREATE time
    with pytest.raises(Exception):
        s.execute("CREATE VIEW bad AS SELECT nosuch FROM t")
    # view over a dropped table errors at USE time (MySQL behavior)
    s.execute("CREATE TABLE tmp (x BIGINT)")
    s.execute("CREATE VIEW vtmp AS SELECT x FROM tmp")
    s.execute("DROP TABLE tmp")
    with pytest.raises(Exception):
        s.query("SELECT * FROM vtmp")


def test_view_on_device_engine(s):
    s.execute("CREATE TABLE big (k BIGINT, v BIGINT)")
    rng = np.random.default_rng(2)
    s.execute("INSERT INTO big VALUES " + ",".join(
        f"({int(rng.integers(0, 50))},{int(rng.integers(0, 100))})"
        for _ in range(50000)))
    s.execute("ANALYZE TABLE big")
    s.execute("CREATE VIEW vb AS SELECT k, SUM(v) AS sv FROM big GROUP BY k")
    want = sorted(s.query("SELECT * FROM vb").rows)
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                  tidb_tpu_strict="on")
    try:
        got = sorted(s.query("SELECT * FROM vb").rows)
    finally:
        s.vars.update(tidb_tpu_engine="off", tidb_tpu_strict="off")
    assert got == want


# ---- optimizer hints --------------------------------------------------------


@pytest.fixture()
def hs():
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE inner_t (k BIGINT PRIMARY KEY, v BIGINT)")
    s.execute("CREATE TABLE outer_t (k BIGINT, w BIGINT, INDEX ik (k))")
    s.execute("INSERT INTO inner_t VALUES " + ",".join(
        f"({i},{i % 7})" for i in range(20000)))
    s.execute("INSERT INTO outer_t VALUES " + ",".join(
        f"({i % 20000},{i})" for i in range(20000)))
    s.execute("ANALYZE TABLE inner_t")
    s.execute("ANALYZE TABLE outer_t")
    return s


def test_join_hints_flip_plan(hs):
    s = hs
    sql = "SELECT {} COUNT(*) FROM outer_t JOIN inner_t ON outer_t.k = inner_t.k"
    base = _explain(s, sql.format(""))
    # cost picks merge join for this shape; hints force the others
    assert "MergeJoin" in base
    hinted = _explain(s, sql.format("/*+ HASH_JOIN(inner_t) */"))
    assert "HashJoin" in hinted and "MergeJoin" not in hinted
    hinted = _explain(s, sql.format("/*+ INL_JOIN(inner_t) */"))
    assert "IndexLookupJoin" in hinted
    # results identical under every forced shape
    want = s.query(sql.format("")).rows
    for h in ("/*+ HASH_JOIN(inner_t) */", "/*+ INL_JOIN(inner_t) */",
              "/*+ MERGE_JOIN(inner_t) */"):
        assert s.query(sql.format(h)).rows == want, h


def test_agg_hints_flip_plan(hs):
    s = hs
    sql = "SELECT {} k, COUNT(*) FROM outer_t GROUP BY k"
    base = _explain(s, sql.format(""))
    assert "StreamAgg" in base          # near-unique key → stream by cost
    hinted = _explain(s, sql.format("/*+ HASH_AGG() */"))
    assert "HashAgg" in hinted and "StreamAgg" not in hinted
    assert sorted(s.query(sql.format("/*+ HASH_AGG() */")).rows) == \
        sorted(s.query(sql.format("")).rows)
    # STREAM_AGG() forces the other direction on a low-NDV key
    s.execute("CREATE TABLE lo2 (k BIGINT, INDEX ik (k))")
    s.execute("INSERT INTO lo2 VALUES " + ",".join(
        f"({i % 3})" for i in range(5000)))
    s.execute("ANALYZE TABLE lo2")
    assert "HashAgg" in _explain(s, "SELECT k, COUNT(*) FROM lo2 GROUP BY k")
    forced = _explain(
        s, "SELECT /*+ STREAM_AGG() */ k, COUNT(*) FROM lo2 GROUP BY k")
    assert "StreamAgg" in forced


def test_review_r5_view_findings(s):
    # CTE must not hijack a view's base table (isolation)
    s.execute("CREATE VIEW iso AS SELECT a FROM t WHERE a = 1")
    rows = s.query("WITH t AS (SELECT 99 AS a) SELECT * FROM iso").rows
    assert rows == [(1,)]
    # CREATE TABLE over a view name is rejected (one namespace)
    with pytest.raises(TableExistsError):
        s.execute("CREATE TABLE iso (x BIGINT)")
    # circular views hit the depth cap, not the Python recursion limit
    s.execute("CREATE VIEW ca AS SELECT 1 AS x")
    s.execute("CREATE VIEW cb AS SELECT (SELECT MAX(x) FROM ca) AS x")
    s.execute("CREATE OR REPLACE VIEW ca AS "
              "SELECT (SELECT MAX(x) FROM cb) AS x")
    with pytest.raises(Exception, match="[Vv]iew"):
        s.query("SELECT * FROM ca")
    # view plans are cacheable: repeated queries hit the plan cache
    s.query("SELECT * FROM iso")
    before = len(s._plan_cache)
    s.query("SELECT * FROM iso")
    assert len(s._plan_cache) == before and before > 0
    # hints in non-SELECT positions parse as plain comments
    s.execute("INSERT /*+ IGNORE_PLAN_CACHE() */ INTO t VALUES (5000,0,'gx')")
    assert s.query("SELECT COUNT(*) FROM t WHERE a = 5000").rows == [(1,)]
