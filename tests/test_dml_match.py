"""DELETE and UPDATE match the rows the same WHERE selects.

`Session._match_masks` compares a SIGNED INTEGER column with an integer
literal on the stored values themselves; every other column type stores a
scaled or coded integer (DECIMAL value * 10^scale, DATE days, DATETIME
microseconds, ENUM index) whose literal the expression evaluator has to
coerce. The oracle is SELECT with the same predicate: whatever the
evaluator makes of a literal, the write has to make the same of it."""

import pytest

from tidb_tpu.session import Engine

ROWS = 240

PREDICATES = [
    pytest.param("k >= 60 AND k < 130", id="bigint-range"),
    pytest.param("100 > k", id="bigint-mirrored"),
    pytest.param("q < 24", id="decimal"),
    pytest.param("q >= 10 AND q < 30 AND k < 200", id="decimal-and-bigint"),
    pytest.param("d < 9000", id="date"),
    pytest.param("d < 19940101 AND k >= 100", id="date-and-bigint"),
    pytest.param("ts > 800000000000000", id="datetime"),
    pytest.param("u > 3", id="unsigned"),
    pytest.param("u > -1", id="unsigned-negative-literal"),
    pytest.param("e = 2", id="enum-index"),
]


def _session():
    s = Engine().new_session()
    s.vars["tidb_tpu_engine"] = "off"
    s.execute("CREATE TABLE t (k BIGINT, q DECIMAL(12,2), d DATE, "
              "ts DATETIME, u BIGINT UNSIGNED, e ENUM('a','b','c'), "
              "hit INT)")
    # three statements: three regions, so a mask per region is built
    for lo in range(0, ROWS, 80):
        s.execute("INSERT INTO t VALUES " + ",".join(
            f"({i}, {i % 50}.{i % 100:02d}, "
            f"'199{3 + i % 3}-{1 + i % 12:02d}-{1 + i % 28:02d}', "
            f"'199{3 + i % 3}-{1 + i % 12:02d}-{1 + i % 28:02d} 10:00:00', "
            f"{i % 7}, '{'abc'[i % 3]}', 0)"
            for i in range(lo, lo + 80)))
    return s


def _ids(s, where):
    return sorted(r[0] for r in s.query(
        f"SELECT k FROM t WHERE {where}").rows)


@pytest.mark.parametrize("where", PREDICATES)
def test_delete_matches_what_select_selects(where):
    s = _session()
    want = _ids(s, where)
    assert 0 < len(want), where
    res = s.query(f"DELETE FROM t WHERE {where}")
    assert res.affected_rows == len(want)
    assert _ids(s, "k >= 0") == sorted(set(range(ROWS)) - set(want))


@pytest.mark.parametrize("where", PREDICATES)
def test_update_matches_what_select_selects(where):
    s = _session()
    want = _ids(s, where)
    res = s.query(f"UPDATE t SET hit = 1 WHERE {where}")
    assert res.affected_rows == len(want)
    assert _ids(s, "hit = 1") == want
