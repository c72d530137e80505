"""Builtin breadth: math/string/date functions + DISTINCT aggregates.

Two tiers (the reference's builtin_*_vec_test.go discipline): python-oracle
checks on the CPU engine, and CPU-vs-device differential for everything the
fragment engine claims (the vec == scalar twin-test, SURVEY §4)."""

import datetime as dt

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
    s.execute("CREATE TABLE b (d DATE, ts DATETIME, x DOUBLE, "
              "s VARCHAR(24), n BIGINT, dec DECIMAL(10,3))")
    rng = np.random.default_rng(31)
    rows = []
    for i in range(4000):
        y, m, day = int(rng.integers(1990, 2025)), \
            int(rng.integers(1, 13)), int(rng.integers(1, 29))
        hh, mm, ss = (int(rng.integers(0, 24)), int(rng.integers(0, 60)),
                      int(rng.integers(0, 60)))
        x = round(float(rng.normal(0, 50)), 4)
        sv = ["alpha", "beta,gamma", "Hello World", "x"][
            int(rng.integers(0, 4))]
        n = int(rng.integers(-20, 21))
        dec = round(float(rng.uniform(-99, 99)), 3)
        rows.append(f"('{y}-{m:02d}-{day:02d}',"
                    f"'{y}-{m:02d}-{day:02d} {hh:02d}:{mm:02d}:{ss:02d}',"
                    f"{x},'{sv}',{n},{dec})")
    rows.append("(NULL,NULL,NULL,NULL,NULL,NULL)")
    s.execute("INSERT INTO b VALUES " + ",".join(rows))
    s.execute("ANALYZE TABLE b")
    return s


def q1(s, sql):
    return s.query(sql).rows[0][0]


# ---- python-oracle checks --------------------------------------------------

def test_date_arithmetic_oracle(session):
    s = session
    assert q1(s, "SELECT DATE_ADD('2020-01-31', INTERVAL 1 MONTH) FROM b "
                 "LIMIT 1") == dt.date(2020, 2, 29)
    assert q1(s, "SELECT DATE_SUB('2020-03-31', INTERVAL 1 MONTH) FROM b "
                 "LIMIT 1") == dt.date(2020, 2, 29)
    assert q1(s, "SELECT DATE_ADD('2020-02-29', INTERVAL 1 YEAR) FROM b "
                 "LIMIT 1") == dt.date(2021, 2, 28)
    assert q1(s, "SELECT DATEDIFF('2020-03-01', '2020-02-01') FROM b "
                 "LIMIT 1") == 29
    assert q1(s, "SELECT DAYOFWEEK('2026-07-26') FROM b LIMIT 1") == 1
    assert q1(s, "SELECT LAST_DAY('2024-02-10') FROM b LIMIT 1") == \
        dt.date(2024, 2, 29)
    assert q1(s, "SELECT HOUR('2020-01-01 13:45:59') FROM b LIMIT 1") == 13
    assert q1(s, "SELECT MINUTE('2020-01-01 13:45:59') FROM b LIMIT 1") == 45
    assert q1(s, "SELECT SECOND('2020-01-01 13:45:59') FROM b LIMIT 1") == 59
    assert q1(s, "SELECT DATE_ADD('2020-01-01', INTERVAL 25 HOUR) FROM b "
                 "LIMIT 1") == dt.datetime(2020, 1, 2, 1, 0, 0)


def test_date_parts_vs_python(session):
    rows = session.query(
        "SELECT d, DAYOFWEEK(d), WEEKDAY(d), DAYOFYEAR(d), QUARTER(d), "
        "LAST_DAY(d) FROM b WHERE d IS NOT NULL").rows
    import calendar
    for d, dow, wd, doy, qtr, last in rows[:500]:
        assert dow == (d.weekday() + 1) % 7 + 1
        assert wd == d.weekday()
        assert doy == d.timetuple().tm_yday
        assert qtr == (d.month + 2) // 3
        assert last == d.replace(
            day=calendar.monthrange(d.year, d.month)[1])


def test_math_oracle(session):
    s = session
    assert abs(q1(s, "SELECT EXP(1) FROM b LIMIT 1") - np.e) < 1e-12
    assert abs(q1(s, "SELECT LOG(2, 1024) FROM b LIMIT 1") - 10.0) < 1e-9
    assert q1(s, "SELECT LN(0) FROM b LIMIT 1") is None   # domain → NULL
    assert q1(s, "SELECT SIGN(-7) FROM b LIMIT 1") == -1
    assert float(q1(s, "SELECT TRUNCATE(3.7777, 2) FROM b LIMIT 1")) == \
        pytest.approx(3.77)
    assert q1(s, "SELECT TRUNCATE(dec, 1) FROM b WHERE dec IS NOT NULL "
                 "LIMIT 1") is not None
    assert q1(s, "SELECT GREATEST(1, 5, 3) FROM b LIMIT 1") == 5
    assert q1(s, "SELECT LEAST(1, NULL, 3) FROM b LIMIT 1") is None


def test_string_oracle(session):
    s = session
    assert q1(s, "SELECT SUBSTR('quadratic', 5) FROM b LIMIT 1") == "ratic"
    assert q1(s, "SELECT SUBSTR('quadratic', -3, 2) FROM b LIMIT 1") == "ti"
    assert q1(s, "SELECT CONCAT('a', NULL, 'c') FROM b LIMIT 1") is None
    assert q1(s, "SELECT CONCAT(1.5, ' x') FROM b LIMIT 1") == "1.5 x"
    assert q1(s, "SELECT LOCATE('bar', 'foobarbar', 5) FROM b LIMIT 1") == 7
    assert q1(s, "SELECT SUBSTRING_INDEX('a.b.c', '.', -1) FROM b LIMIT 1") \
        == "c"
    assert q1(s, "SELECT LPAD('hi', 5, '??') FROM b LIMIT 1") == "???hi"
    assert q1(s, "SELECT STRCMP('a', 'b') FROM b LIMIT 1") == -1


def test_distinct_aggregates_cpu(session):
    rows = session.query(
        "SELECT n, COUNT(DISTINCT s), SUM(DISTINCT n) FROM b "
        "WHERE n IS NOT NULL GROUP BY n").rows
    for n, cd, sd in rows:
        assert 1 <= cd <= 4
        assert sd == n          # SUM(DISTINCT n) grouped by n is n


# ---- device differential ---------------------------------------------------

def run_device(s, sql):
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
        assert frags, f"no fragment extracted: {sql}"
        for f in frags:
            assert f.used_device, f"fell back ({f.fallback_reason}): {sql}"
        return [r for ch in chunks for r in ch.rows()]
    finally:
        s.vars["tidb_tpu_engine"] = "off"


def assert_same(rows1, rows2):
    assert len(rows1) == len(rows2)
    for r1, r2 in zip(sorted(rows1, key=str), sorted(rows2, key=str)):
        for v1, v2 in zip(r1, r2):
            if isinstance(v1, float) and v2 is not None:
                assert abs(v1 - v2) <= 1e-5 * max(1.0, abs(v2)), (r1, r2)
            else:
                assert v1 == v2, (r1, r2)


DEVICE_QUERIES = [
    # date builtins trace on device (civil-date int ops)
    "SELECT QUARTER(d), COUNT(*) FROM b GROUP BY QUARTER(d)",
    "SELECT DAYOFWEEK(d), COUNT(*), SUM(n) FROM b GROUP BY DAYOFWEEK(d)",
    "SELECT COUNT(*) FROM b WHERE DATEDIFF(d, '2000-01-01') > 0",
    "SELECT COUNT(*) FROM b WHERE d + INTERVAL 1 MONTH > '2020-06-15'",
    # math on device
    "SELECT SIGN(n), COUNT(*) FROM b GROUP BY SIGN(n)",
    "SELECT COUNT(*), SUM(GREATEST(n, 0)) FROM b",
    # distinct aggregates on device (factorize-dedup)
    "SELECT n, COUNT(DISTINCT s) FROM b GROUP BY n",
    "SELECT QUARTER(d), COUNT(DISTINCT n), SUM(DISTINCT n) FROM b "
    "GROUP BY QUARTER(d)",
    "SELECT COUNT(DISTINCT n) FROM b",
]


@pytest.mark.parametrize("sql", DEVICE_QUERIES)
def test_device_matches_cpu(session, sql):
    assert_same(run_device(session, sql), session.query(sql).rows)


def test_epoch_digest_radix_builtins():
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    s.execute("CREATE TABLE bt (d DATETIME, x BIGINT, t VARCHAR(16))")
    s.execute("INSERT INTO bt VALUES ('2024-03-05 14:30:45', 255, 'abc')")
    r = s.query("SELECT UNIX_TIMESTAMP(d), "
                "FROM_UNIXTIME(UNIX_TIMESTAMP(d)) FROM bt").rows[0]
    assert r[0] == 1709649045
    assert str(r[1]) == "2024-03-05 14:30:45"
    r = s.query("SELECT MD5(t), SHA1(t), SHA2(t, 256), CRC32(t), BIN(x), "
                "OCT(x), UNHEX('414243') FROM bt").rows[0]
    assert r[0] == "900150983cd24fb0d6963f7d28e17f72"
    assert r[1] == "a9993e364706816aba3e25717850c26c9cd0d89d"
    assert r[2].startswith("ba7816bf8f01cfea")
    assert r[3] == 891568578
    assert (r[4], r[5], r[6]) == ("11111111", "377", "ABC")
    r = s.query("SELECT DATE_FORMAT(d, '%Y/%c/%e %T %M %a %p %%') "
                "FROM bt").rows[0][0]
    assert r == "2024/3/5 14:30:45 March Tue PM %"


def test_env_functions():
    from tidb_tpu.session import Engine
    eng = Engine()
    s = eng.new_session()
    assert s.query("SELECT VERSION()").rows[0][0] == "8.0.11-tidb-tpu"
    assert s.query("SELECT USER()").rows[0][0] == "root@%"
    assert s.query("SELECT DATABASE()").rows[0][0] == "test"
    assert s.query("SELECT CONNECTION_ID()").rows[0][0] == s.conn_id
    y = s.query("SELECT YEAR(NOW()), YEAR(CURDATE())").rows[0]
    assert y[0] >= 2026 and y[1] >= 2026
    assert s.query("SELECT UNIX_TIMESTAMP()").rows[0][0] > 1_700_000_000


# ---- round-4 breadth builtins ----------------------------------------------

def test_breadth_string_builtins():
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    s.execute("CREATE TABLE bb (v VARCHAR(20))")
    s.execute("INSERT INTO bb VALUES ('Hello')")
    r = s.query(
        "SELECT BIT_LENGTH(v), ORD(v), QUOTE(v), SOUNDEX(v), "
        "TO_BASE64(v), FROM_BASE64(TO_BASE64(v)), "
        "INSERT(v, 2, 3, 'XX'), FIELD(v, 'x', 'Hello', 'y'), "
        "ELT(2, 'a', 'b'), CHAR(72, 105) FROM bb").rows[0]
    assert r == (40, 72, "'Hello'", "H400", "SGVsbG8=", "Hello",
                 "HXXo", 2, "b", "Hi")


def test_round_scale_exact_half_away_from_zero():
    """ROUND with a scale argument is EXACT decimal half-away-from-zero
    (the reference's types.Round): float arithmetic would turn 1.005
    into 1.00499…  and round it DOWN."""
    from decimal import Decimal
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    q = lambda sql: s.query(sql).rows[0][0]    # noqa: E731
    assert q("SELECT ROUND(1.005, 2)") == Decimal("1.01")
    assert q("SELECT ROUND(1.25, 1)") == Decimal("1.3")
    assert q("SELECT ROUND(-1.25, 1)") == Decimal("-1.3")
    assert q("SELECT ROUND(2.567, 10)") == Decimal("2.567")
    # half-away-from-zero at scale 0 (Python's round() would give 2/-2)
    assert q("SELECT ROUND(2.5)") == 3
    assert q("SELECT ROUND(-2.5)") == -3
    # negative scale zeroes digits LEFT of the point, on ints too
    assert q("SELECT ROUND(123.456, -2)") == 100
    assert q("SELECT ROUND(12345, -2)") == 12300


def test_cast_decimal_downscale_rounds_half_away():
    """CAST to a SMALLER scale rounds half away from zero (the same
    types.Round rule as ROUND) — it must never reinterpret the scaled
    int at the new scale (1.005 → 10.05)."""
    from decimal import Decimal
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    q = lambda sql: s.query(sql).rows[0][0]    # noqa: E731
    assert q("SELECT CAST(1.005 AS DECIMAL(10,2))") == Decimal("1.01")
    assert q("SELECT CAST(-1.005 AS DECIMAL(10,2))") == Decimal("-1.01")
    assert q("SELECT CAST(1.004 AS DECIMAL(10,2))") == Decimal("1.00")
    assert q("SELECT CAST(2.5 AS DECIMAL(10,0))") == 3
    assert q("SELECT CAST(-2.5 AS DECIMAL(10,0))") == -3
    # up-scale and same-scale stay exact
    assert q("SELECT CAST(1.005 AS DECIMAL(10,4))") == Decimal("1.0050")
    assert q("SELECT CAST(3 AS DECIMAL(10,2))") == Decimal("3.00")
    # column path (not constant-folded), host vs device
    s.execute("CREATE TABLE bdc (d DECIMAL(6,3))")
    s.execute("INSERT INTO bdc VALUES (1.005), (-1.005), (2.499), (NULL)")
    sql = "SELECT CAST(d AS DECIMAL(10,2)) FROM bdc"
    host = [r[0] for r in s.query(sql).rows]
    assert host == [Decimal("1.01"), Decimal("-1.01"),
                    Decimal("2.50"), None]
    s.vars.update({"tidb_tpu_engine": "on", "tidb_tpu_row_threshold": 1})
    assert [r[0] for r in s.query(sql).rows] == host


def test_breadth_math_misc_builtins():
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    s.execute("CREATE TABLE bm (n BIGINT)")
    s.execute("INSERT INTO bm VALUES (255)")
    r = s.query(
        "SELECT CONV(n, 10, 16), CONV('ff', 16, 10), "
        "FORMAT(1234567.891, 2), INET_ATON('192.168.0.1'), "
        "INET_NTOA(3232235521), ATAN2(1, 1) FROM bm").rows[0]
    assert r[:5] == ("FF", "255", "1,234,567.89", 3232235521,
                     "192.168.0.1")
    assert abs(r[5] - 0.7853981634) < 1e-9
    u = s.query("SELECT UUID() FROM bm").rows[0][0]
    assert len(u) == 36 and u.count("-") == 4


def test_breadth_temporal_builtins():
    import datetime as dt
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    s.execute("CREATE TABLE bt (d DATE, t DATETIME)")
    s.execute("INSERT INTO bt VALUES ('2024-03-15', "
              "'2024-03-15 10:30:45.123456')")
    r = s.query(
        "SELECT TO_DAYS(d), FROM_DAYS(TO_DAYS(d)), YEARWEEK(d), "
        "MAKEDATE(2024, 75), TIME_TO_SEC(t), MICROSECOND(t), "
        "STR_TO_DATE('15,3,2024', '%d,%m,%Y') FROM bt").rows[0]
    assert r[0] == 739325                      # MySQL TO_DAYS value
    assert r[1] == dt.date(2024, 3, 15)
    assert r[2] == 202411
    assert r[3] == dt.date(2024, 3, 15)
    assert r[4] == 10 * 3600 + 30 * 60 + 45
    assert r[5] == 123456
    assert r[6] == dt.datetime(2024, 3, 15)
    r = s.query(
        "SELECT TIMESTAMPDIFF(day, d, '2024-04-15'), "
        "TIMESTAMPDIFF(month, '2023-01-31', '2024-03-01'), "
        "TIMESTAMPDIFF(year, '2020-06-01', '2024-05-31'), "
        "TIMESTAMPADD(hour, 5, t) FROM bt").rows[0]
    assert r[0] == 31 and r[1] == 13 and r[2] == 3
    assert r[3] == dt.datetime(2024, 3, 15, 15, 30, 45, 123456)


def test_breadth_error_codes():
    import pytest
    from tidb_tpu.errors import (NotNullViolation, SubqueryRowError,
                                 UnsupportedFunctionError)
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    s.execute("CREATE TABLE ec (a BIGINT NOT NULL, b BIGINT)")
    s.execute("INSERT INTO ec VALUES (1, 2), (2, 3)")
    with pytest.raises(NotNullViolation) as e:
        s.execute("INSERT INTO ec VALUES (NULL, 4)")
    assert e.value.code == 1048
    with pytest.raises(UnsupportedFunctionError) as e:
        s.query("SELECT NO_SUCH_FN(a) FROM ec")
    assert e.value.code == 1305
    with pytest.raises(SubqueryRowError) as e:
        s.query("SELECT * FROM ec WHERE b = (SELECT a FROM ec)")
    assert e.value.code == 1242


def test_set_global_persists_via_backup(tmp_path):
    from tidb_tpu.session import Engine
    from tidb_tpu.tools import backup, restore
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE TABLE gp (a BIGINT)")
    s.execute("SET GLOBAL tidb_tpu_row_threshold = 777")
    s.execute("CREATE USER alice IDENTIFIED BY 'pw'")
    s.execute("GRANT SELECT ON gp TO alice")
    # SET GLOBAL must NOT touch the CURRENT session (MySQL scoping)
    assert s.vars.get("tidb_tpu_row_threshold") != 777
    assert eng.new_session().vars["tidb_tpu_row_threshold"] == 777
    backup(eng, str(tmp_path))
    # "restart": a fresh engine restored from the image
    eng2 = Engine()
    restore(eng2, str(tmp_path))
    assert eng2.new_session().vars["tidb_tpu_row_threshold"] == 777
    assert "alice" in eng2.auth.users      # grant tables survived too
    eng2.auth.require("alice", "SELECT", "gp")


def test_show_grants_requires_privilege():
    import pytest
    from tidb_tpu.errors import SpecificAccessDeniedError
    from tidb_tpu.session import Engine
    eng = Engine()
    s = eng.new_session()
    s.execute("CREATE USER bob IDENTIFIED BY 'x'")
    s2 = eng.new_session()
    s2.user = "bob"
    s2.query("SHOW GRANTS")                 # own grants: fine
    with pytest.raises(SpecificAccessDeniedError) as ei:
        s2.query("SHOW GRANTS FOR root")    # other users: SUPER only
    assert ei.value.code == 1227


def test_regexp_rlike():
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    s.execute("CREATE TABLE rx (v VARCHAR(20))")
    s.execute("INSERT INTO rx VALUES ('hello42'), ('WORLD'), ('h2o')")
    assert s.query("SELECT COUNT(*) FROM rx WHERE v REGEXP '[0-9]+'"
                   ).rows[0][0] == 2
    assert s.query("SELECT COUNT(*) FROM rx WHERE v RLIKE '^h'"
                   ).rows[0][0] == 2
    assert s.query("SELECT COUNT(*) FROM rx WHERE v NOT REGEXP '[0-9]'"
                   ).rows[0][0] == 1
    # device path: prepared per-dictionary LUT (like LIKE)
    import numpy as np
    rng = np.random.default_rng(2)
    s.execute("INSERT INTO rx VALUES " + ",".join(
        f"('w{int(rng.integers(0, 100))}')" for _ in range(50000)))
    s.execute("ANALYZE TABLE rx")
    sql = "SELECT COUNT(*) FROM rx WHERE v REGEXP '^w[0-4]'"
    want = s.query(sql).rows
    s.vars.update(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
                  tidb_tpu_strict="on")
    try:
        got = s.query(sql).rows
    finally:
        s.vars.update(tidb_tpu_engine="off", tidb_tpu_strict="off")
    assert got == want


def test_batch2_temporal_builtins():
    import datetime as dt
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    s.execute("CREATE TABLE b2 (t DATETIME)")
    s.execute("INSERT INTO b2 VALUES ('2024-03-15 10:00:00')")
    r = s.query(
        "SELECT WEEKOFYEAR(t), PERIOD_ADD(202411, 3), "
        "PERIOD_DIFF(202403, 202311), MAKETIME(10, 30, 15), "
        "ADDTIME(t, MAKETIME(1, 0, 0)), SUBTIME(t, MAKETIME(0, 30, 0)) "
        "FROM b2").rows[0]
    assert r[0] == 11 and r[1] == 202502 and r[2] == 4
    assert r[3] == dt.timedelta(hours=10, minutes=30, seconds=15)
    assert r[4] == dt.datetime(2024, 3, 15, 11, 0)
    assert r[5] == dt.datetime(2024, 3, 15, 9, 30)
    r = s.query("SELECT MAKE_SET(5, 'a', 'b', 'c'), "
                "EXPORT_SET(5, 'Y', 'N', ',', 4) FROM b2").rows[0]
    assert r == ("a,c", "Y,N,Y,N")
    # NULL propagation through the row-loop helpers
    s.execute("INSERT INTO b2 VALUES (NULL)")
    rows = s.query("SELECT WEEKOFYEAR(t), MAKETIME(25, 99, 0) FROM b2"
                   ).rows
    assert (None, None) in [(r[0], r[1]) for r in rows]  # NULL row + bad
    assert all(r[1] is None for r in rows)   # invalid maketime everywhere


def test_extract():
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    s.execute("CREATE TABLE ex (d DATETIME)")
    s.execute("INSERT INTO ex VALUES ('2024-03-15 10:30:45.123456')")
    r = s.query("SELECT EXTRACT(year FROM d), EXTRACT(quarter FROM d), "
                "EXTRACT(day FROM d), EXTRACT(minute FROM d), "
                "EXTRACT(microsecond FROM d) FROM ex").rows[0]
    assert r == (2024, 1, 15, 30, 123456)


def test_advisor_r4_fixes():
    """Round-4 advisor findings: UUID() not constant-folded (distinct per
    row), INET_ATON malformed → NULL (builtin_miscellaneous.go)."""
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    s.execute("CREATE TABLE adv (a BIGINT)")
    s.execute("INSERT INTO adv VALUES (1),(2),(3)")
    uuids = [r[0] for r in s.query("SELECT UUID() FROM adv").rows]
    assert len(set(uuids)) == 3
    # and a second execution (cached plan) yields fresh values
    uuids2 = [r[0] for r in s.query("SELECT UUID() FROM adv").rows]
    assert not set(uuids) & set(uuids2)
    r = s.query("SELECT INET_ATON('256.1.1.1'), INET_ATON('abc'), "
                "INET_ATON('1.2.3.4') FROM adv LIMIT 1").rows[0]
    assert r == (None, None, 16909060)


def test_nondeterministic_fold_propagates():
    # wrapping UUID() must not re-enable constant folding (UPPER(UUID()))
    from tidb_tpu.session import Engine
    s = Engine().new_session()
    s.execute("CREATE TABLE nf (a BIGINT)")
    s.execute("INSERT INTO nf VALUES (1),(2),(3)")
    got = [r[0] for r in s.query("SELECT UPPER(UUID()) FROM nf").rows]
    assert len(set(got)) == 3
