"""Random DML over a fact and a build table: the device engine answers as
the CPU engine does after every step.

What `tests/test_delta_slabs.py`, `test_aligned_join.py` and
`test_dml_match.py` pin one shape at a time, mixed by a seeded generator:
two-table transactions as the refresh functions make them (committed and
rolled back), DELETE by key range, by DATE and by a DECIMAL column
against an integer literal, UPDATE of fact rows and of build rows, build
rows deleted under live fact rows, rows replaced under their key, facts
whose key no build row has — with compactions falling due every few steps
— read back by a grouped aggregate over a `delta`-layout date, a PK-FK
join, a filtered global sum, MIN/MAX, an outer join, an ORDER BY … LIMIT
root and an anti-join."""

import datetime as dt
import random

import pytest

from tidb_tpu.executor import delta
from tidb_tpu.session import Engine

ORDERS = 1000
STEPS = 18
ON = dict(tidb_tpu_engine="on", tidb_tpu_row_threshold=1,
          tidb_tpu_max_slab_rows=2048, tidb_tpu_compaction="off")
QUERIES = [
    "SELECT rf, COUNT(*), SUM(qty), SUM(price*(1-disc)), AVG(disc), SUM(n) "
    "FROM l WHERE sd <= '1998-09-02' GROUP BY rf ORDER BY rf",
    "SELECT prio, COUNT(*), SUM(price*(1-disc)) FROM l JOIN o ON lk = ok "
    "WHERE od < '1995-03-15' AND sd > '1995-03-15' GROUP BY prio "
    "ORDER BY prio",
    "SELECT SUM(price*disc), COUNT(*) FROM l WHERE sd >= '1994-01-01' "
    "AND sd < '1995-01-01' AND disc BETWEEN 0.05 AND 0.07 AND qty < 24",
    "SELECT COUNT(*), SUM(tp), MIN(od), MAX(ok) FROM o",
    "SELECT COUNT(*), MIN(ts), MAX(ts), MIN(n), MAX(sd) FROM l",
    "SELECT COUNT(*), SUM(tp) FROM l LEFT JOIN o ON lk = ok",
    "SELECT lk, price FROM l WHERE qty = 7 AND n > 20 "
    "ORDER BY lk, price LIMIT 20",
    "SELECT COUNT(*) FROM l WHERE lk NOT IN (SELECT ok FROM o)",
]


def _date(day: int) -> str:
    return (dt.date(1992, 1, 1) + dt.timedelta(days=day)).isoformat()


class _Stream:
    """The tables, the generator and the key range still present."""

    def __init__(self, seed: int):
        self.rnd = rnd = random.Random(seed)
        self.lo, self.hi = 0, ORDERS
        eng = Engine()
        eng.global_vars["tidb_enable_auto_analyze"] = False
        self.s = s = eng.new_session()
        s.execute("CREATE TABLE o (ok BIGINT PRIMARY KEY, ck BIGINT, "
                  "od DATE, prio VARCHAR(4), tp DECIMAL(15,2))")
        s.execute("CREATE TABLE l (lk BIGINT, qty DECIMAL(15,2), "
                  "price DECIMAL(15,2), disc DECIMAL(15,2), sd DATE, "
                  "rf CHAR(1), ts DATETIME, n INT)")
        s.execute("INSERT INTO o VALUES " + ",".join(
            self.order(k) for k in range(ORDERS)))
        # loaded in ship-date order, so `sd` takes the `delta` layout
        facts = sorted((rnd.randrange(2500), k)
                       for k in range(ORDERS) for _ in range(4))
        s.execute("INSERT INTO l VALUES " + ",".join(
            self.fact(k, day) for day, k in facts))
        for t in "ol":
            s.execute(f"ANALYZE TABLE {t}")

    def order(self, k: int) -> str:
        r = self.rnd
        return (f"({k},{r.randrange(300)},'{_date(r.randrange(2400))}',"
                f"'p{r.randrange(4)}',{r.randrange(100000) / 100:.2f})")

    def fact(self, k: int, day=None) -> str:
        r = self.rnd
        day = r.randrange(2500) if day is None else day
        return (f"({k},{r.randrange(1, 51)}.00,"
                f"{r.randrange(90000, 10000000) / 100:.2f},"
                f"0.{r.randrange(11):02d},'{_date(day)}',"
                f"'{r.choice('ARN')}','{_date(day)} 0{r.randrange(10)}:00:00',"
                f"{r.randrange(-50, 50)})")

    def write(self) -> str:
        r, x = self.rnd, self.s.execute
        lo, hi = self.lo, self.hi
        c = r.randrange(12)
        if c == 0:
            k = r.randrange(1, 40)
            x("BEGIN")
            x("INSERT INTO o VALUES " + ",".join(
                self.order(i) for i in range(hi, hi + k)))
            x("INSERT INTO l VALUES " + ",".join(
                self.fact(i) for i in range(hi, hi + k)
                for _ in range(r.randrange(1, 6))))
            if r.random() < 0.15:
                x("ROLLBACK")
                return "new sales, rolled back"
            x("COMMIT")
            self.hi += k
            return f"new sales {k}"
        if c == 1:
            k = r.randrange(1, 40)
            x("BEGIN")
            x(f"DELETE FROM l WHERE lk >= {lo} AND lk < {lo + k}")
            x(f"DELETE FROM o WHERE ok >= {lo} AND ok < {lo + k}")
            x("COMMIT")
            self.lo += k
            return f"old sales {k}"
        if c == 2:
            x(f"DELETE FROM l WHERE sd = '{_date(r.randrange(2500))}'")
            return "delete by date"
        if c == 3:
            x(f"DELETE FROM l WHERE qty < {r.randrange(2, 4)} "
              f"AND n > {r.randrange(30, 48)}")
            return "delete by decimal against an integer literal"
        if c == 4:
            x(f"UPDATE o SET prio = 'p{r.randrange(4)}' "
              f"WHERE ok = {r.randrange(lo, hi)}")
            return "update of a build row"
        if c == 5:
            x(f"UPDATE l SET qty = qty + 1, n = n - 1 "
              f"WHERE lk = {r.randrange(lo, hi)}")
            return "update of fact rows"
        if c == 6:
            keys = ",".join(map(str, r.sample(range(lo, hi), 5)))
            x(f"DELETE FROM o WHERE ok IN ({keys})")
            return "build rows deleted under live fact rows"
        if c == 7:
            k = r.randrange(lo, hi)
            x(f"DELETE FROM l WHERE lk = {k}")
            x(f"INSERT INTO l VALUES {self.fact(k)},{self.fact(k)}")
            return "fact rows replaced"
        if c == 8:
            k = hi + r.randrange(1000, 100000)
            x(f"INSERT INTO l VALUES {self.fact(k)}")
            return "a fact row far outside the build keys"
        if c == 9:
            x(f"UPDATE l SET n = n + 1, price = price - 1 WHERE "
              f"sd >= '{_date(r.randrange(2500))}' AND qty = 9 AND n > 40")
            return "update by date"
        if c == 10:
            k = r.randrange(lo, hi)
            x(f"DELETE FROM o WHERE ok = {k}")
            x(f"INSERT INTO o VALUES {self.order(k)}")
            return "a build row replaced"
        return f"compactions: {delta.run_pending_compactions()}"

    def read(self, sql: str, on: bool):
        self.s.vars.update(ON if on else {"tidb_tpu_engine": "off"})
        try:
            return [tuple(map(str, row)) for row in self.s.query(sql).rows]
        finally:
            self.s.vars["tidb_tpu_engine"] = "off"


@pytest.mark.parametrize("seed", [3, 4, 7])
def test_the_device_answers_as_the_cpu_does_after_any_write(seed, monkeypatch):
    # due after some sixty appended rows or a hundredth of the base dead
    monkeypatch.setattr(delta, "COMPACT_FILL", 60 / delta.MIN_DELTA_CAP)
    monkeypatch.setattr(delta, "COMPACT_DEAD", 0.01)
    st = _Stream(seed)
    for step in range(STEPS):
        did = st.write()
        if st.rnd.random() < 0.3:
            did += " + " + st.write()
        if step % 5 == 4:
            did += f" + compactions: {delta.run_pending_compactions()}"
        for sql in st.rnd.sample(QUERIES, st.rnd.randrange(2, 9)):
            assert st.read(sql, on=True) == st.read(sql, on=False), \
                (seed, step, did, sql)
