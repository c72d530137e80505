"""TPC-H-shaped data set: schema, seeded generator, loader, statements, reference.

A COPY of `tidb_tpu/tools/tpch_shaped.py` (generator, schema, Q1/Q3/Q5/Q6,
loader) and of `chip_smoke.py`'s `Reference`, kept here so that the program
may change later and the yardstick may not. It imports nothing of the program
except, inside `load`, the bulk-append API the rows go in through.

Departures from TPC-H (listed in every configuration's `reduced`/`assumed`):
`lineitem` carries 8 of its 16 columns, `orders` 4 of 9, `customer` 2 of 8;
values are uniform, not dbgen's; `lineitem` is sorted by `l_shipdate` (TPC-H
lineitem arrives in orderdate order, so shipdate is nearly clustered — that is
what gives per-slab zone maps their pruning power on Q6); order and customer
keys are dense 0..n-1 in row order; Q3 groups by `o_orderpriority` without the
top-10, Q5 joins 3 tables, not 6. Scale 1 is 6,001,215 lineitem rows, one
`orders` row per four of them and one `customer` row per forty.

The harness's view of a data set module:

    generate(scale, seed)      -> data ({table: {column: ndarray}})
    load(engine, data)         -> None (tables created, appended, ANALYZEd)
    STATEMENTS                 -> {name: SQL}
    COLUMNS                    -> {name: {table: [column, ...]}} each statement reads
    PRUNED_TABLE               -> the table whose slabs zone maps can skip
    reference(data, arithmetic="exact") -> {name: expected wire rows}
"""

from __future__ import annotations

import datetime
from concurrent.futures import ThreadPoolExecutor

import numpy as np

LINEITEM_ROWS_SF1 = 6_001_215

RETURNFLAGS = ("A", "N", "R")
LINESTATUSES = ("F", "O")
PRIORITIES = ("1", "2", "3", "4", "5")
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
# l_shipdate / o_orderdate are uniform over [DATE_LO, DATE_HI) days since
# the epoch: 1992-01-01 .. 1998-12-29
DATE_LO, DATE_HI = 8036, 10590

Q1 = """SELECT l_returnflag, l_linestatus, SUM(l_quantity),
 SUM(l_extendedprice), SUM(l_extendedprice * (1 - l_discount)),
 SUM(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
 AVG(l_quantity), AVG(l_extendedprice), AVG(l_discount), COUNT(*)
 FROM lineitem WHERE l_shipdate <= '1998-09-02'
 GROUP BY l_returnflag, l_linestatus ORDER BY l_returnflag, l_linestatus"""

Q3 = """SELECT o_orderpriority, COUNT(*),
 SUM(l_extendedprice * (1 - l_discount))
 FROM lineitem JOIN orders ON l_orderkey = o_orderkey
 WHERE l_shipdate <= '1998-09-02' AND o_orderdate < '1998-01-01'
 GROUP BY o_orderpriority ORDER BY o_orderpriority"""

Q5 = """SELECT c_mktsegment, COUNT(*),
 SUM(l_extendedprice * (1 - l_discount))
 FROM lineitem JOIN orders ON l_orderkey = o_orderkey
 JOIN customer ON o_custkey = c_custkey
 WHERE l_shipdate <= '1998-09-02'
 GROUP BY c_mktsegment ORDER BY c_mktsegment"""

# the selective forecasting-revenue scan: one date-year window over a
# shipdate-clustered table, the canonical zone-map pruning shape — most
# slabs are provably outside the window and never dispatch
Q6 = """SELECT COUNT(*), SUM(l_extendedprice * l_discount)
 FROM lineitem WHERE l_shipdate >= '1994-01-01'
 AND l_shipdate < '1995-01-01'
 AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24"""

SCHEMA = (
    "CREATE TABLE lineitem (l_quantity DECIMAL(15,2), "
    "l_extendedprice DECIMAL(15,2), l_discount DECIMAL(15,2), "
    "l_tax DECIMAL(15,2), l_returnflag CHAR(1), l_linestatus CHAR(1), "
    "l_shipdate DATE, l_orderkey BIGINT)",
    "CREATE TABLE orders (o_orderkey BIGINT PRIMARY KEY, "
    "o_orderdate DATE, o_orderpriority CHAR(1), o_custkey BIGINT)",
    "CREATE TABLE customer (c_custkey BIGINT PRIMARY KEY, "
    "c_mktsegment CHAR(10))",
)


def table_rows(n_rows: int) -> dict:
    """Row count of each table for a lineitem of `n_rows`."""
    return {"lineitem": n_rows, "orders": max(n_rows // 4, 1),
            "customer": max(n_rows // 40, 1)}


def generate(scale: float, seed: int) -> dict:
    """→ {table: {column: ndarray}} in schema column order, `scale` × SF=1. DECIMAL(15,2)
    columns are scaled int64 (1.00 ↔ 100), DATE int32 days, string
    columns int8 codes into the module's *_VALUES tuples."""
    sizes = table_rows(int(LINEITEM_ROWS_SF1 * scale))
    n, n_orders, n_cust = (sizes["lineitem"], sizes["orders"],
                           sizes["customer"])
    rng = np.random.default_rng([seed, 0])

    def draw(lo, hi, size, dtype=np.int64):
        """Uniform over [lo, hi) with both ends of the domain planted (rows
        0 and 1, before any sort). The engine's frame-of-reference layouts
        take each column's least value, and its compiled programs hold that
        value as a constant: with the extremes left to chance every seed
        would compile programs of its own, and set-up would measure the
        seed. The program's generator does not plant them."""
        col = rng.integers(lo, hi, size, dtype=dtype)
        col[0], col[1 % size] = lo, hi - 1
        return col

    shipdate = draw(DATE_LO, DATE_HI, n).astype(np.int32)
    order = np.argsort(shipdate)
    drawn = {
        "l_quantity": draw(100, 5001, n),                      # 1.00..50.00
        "l_extendedprice": draw(90_000, 10_500_001, n),
        "l_discount": draw(0, 11, n),                          # 0.00..0.10
        "l_tax": draw(0, 9, n),                                # 0.00..0.08
        # returnflag correlates with shipdate in TPC-H; uniform is fine
        "l_returnflag": draw(0, len(RETURNFLAGS), n, np.int8),
        "l_linestatus": draw(0, len(LINESTATUSES), n, np.int8),
        "l_shipdate": shipdate,
    }
    # the seven permutations are most of generate's time at SF=10 and numpy
    # runs them without the interpreter lock: one thread each
    with ThreadPoolExecutor(max_workers=len(drawn)) as pool:
        lineitem = dict(zip(drawn, pool.map(lambda c: c[order],
                                            drawn.values())))
    del drawn
    del order
    rng = np.random.default_rng([seed, 1])
    lineitem["l_orderkey"] = draw(0, n_orders, n)
    orders = {
        "o_orderkey": np.arange(n_orders, dtype=np.int64),
        "o_orderdate": draw(DATE_LO, DATE_HI, n_orders).astype(np.int32),
        "o_orderpriority": draw(0, len(PRIORITIES), n_orders, np.int8),
        "o_custkey": draw(0, n_cust, n_orders),
    }
    customer = {
        "c_custkey": np.arange(n_cust, dtype=np.int64),
        "c_mktsegment": draw(0, len(SEGMENTS), n_cust, np.int8),
    }
    return {"lineitem": lineitem, "orders": orders, "customer": customer}


_CODED = {"l_returnflag": RETURNFLAGS, "l_linestatus": LINESTATUSES,
          "o_orderpriority": PRIORITIES, "c_mktsegment": SEGMENTS}


def load(eng, data: dict) -> None:
    """Bulk-append each generated table into the engine's store (one
    transaction per table) and ANALYZE it."""
    from tidb_tpu.chunk import Chunk, Column

    s = eng.new_session()
    for ddl in SCHEMA:
        s.execute(ddl)
    for name, cols in data.items():
        info = eng.catalog.info_schema.table(name)
        assert [c.name for c in info.columns] == list(cols), name
        chunk = Chunk([
            Column(c.ftype,
                   np.array(_CODED[c.name], dtype=object)[cols[c.name]]
                   if c.name in _CODED else cols[c.name], None)
            for c in info.columns])
        txn = eng.store.begin()
        txn.append(info.id, chunk)
        txn.commit()
        del chunk
    for name in data:
        s.execute(f"ANALYZE TABLE {name}")


STATEMENTS = {"Q1": Q1, "Q3": Q3, "Q5": Q5, "Q6": Q6}

# what each statement reads, for the bytes function behind scan_hbm_share
COLUMNS = {
    "Q1": {"lineitem": ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
                        "l_returnflag", "l_linestatus", "l_shipdate"]},
    "Q3": {"lineitem": ["l_extendedprice", "l_discount", "l_shipdate",
                        "l_orderkey"],
           "orders": ["o_orderkey", "o_orderdate", "o_orderpriority"]},
    "Q5": {"lineitem": ["l_extendedprice", "l_discount", "l_shipdate",
                        "l_orderkey"],
           "orders": ["o_orderkey", "o_custkey"],
           "customer": ["c_custkey", "c_mktsegment"]},
    "Q6": {"lineitem": ["l_quantity", "l_extendedprice", "l_discount",
                        "l_shipdate"]},
}
PRUNED_TABLE = "lineitem"


# ---------------------------------------------------------------------------
# the plain reference: the statements answered from the raw columns in numpy,
# as the text rows the wire carries
# ---------------------------------------------------------------------------

def days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - datetime.date(1970, 1, 1)).days


def fmt_dec(v: int, scale: int) -> str:
    """Scaled integer → the decimal text the wire carries."""
    v = int(v)
    sign, v = ("-", -v) if v < 0 else ("", v)
    if scale == 0:
        return f"{sign}{v}"
    return f"{sign}{v // 10 ** scale}.{v % 10 ** scale:0{scale}d}"


def avg_dec(total: int, count: int, scale: int) -> str:
    """AVG of a DECIMAL(.., scale): the sum carried at scale+4 and divided by
    the count, rounding half away from zero — in Python integers, so exact at
    any size."""
    total, count = int(total) * 10 ** 4, int(count)
    q, r = divmod(abs(total), count)
    q += 2 * r >= count
    return fmt_dec(-q if total < 0 else q, scale + 4)


def _sum_exact(col: np.ndarray) -> int:
    # int64 partial sums are exact here: |sum| < 2^63 for every column up to
    # SF=10 (Q1's charge sum is about 7e17 at scale 6)
    return int(col.sum(dtype=np.int64))


def _sum_float64(col: np.ndarray) -> int:
    # the control: what an accumulator in floating point would answer
    return int(round(float(col.astype(np.float64).sum())))


_SUMS = {"exact": _sum_exact, "float64": _sum_float64}


class Reference:
    """Q1/Q3/Q5/Q6 from the raw columns. `arithmetic="exact"` is the
    reference; `"float64"` is the control of the `correct` comparison: the
    same answers with every SUM accumulated in a float64, the step below the
    exact DECIMAL arithmetic the configurations guarantee."""

    def __init__(self, data: dict, arithmetic: str = "exact"):
        self.sum = _SUMS[arithmetic]
        li, self.orders, self.customer = (data["lineitem"], data["orders"],
                                          data["customer"])
        self.li = li
        # l_extendedprice * (1 - l_discount): scale 2+2; * (1 + l_tax): +2
        self.disc_price = li["l_extendedprice"] * (100 - li["l_discount"])
        self.q1_mask = li["l_shipdate"] <= days("1998-09-02")

    def group_sums(self, codes: np.ndarray, n_groups: int, mask: np.ndarray,
                   cols):
        """→ per group: (count, [sum of each col]) over mask."""
        out = []
        for g in range(n_groups):
            m = mask & (codes == g)
            out.append((int(m.sum()), [self.sum(c[m]) for c in cols]))
        return out

    def q1(self):
        li = self.li
        charge = self.disc_price * (100 + li["l_tax"])
        codes = li["l_returnflag"].astype(np.int16) * len(LINESTATUSES) \
            + li["l_linestatus"]
        groups = self.group_sums(
            codes, len(RETURNFLAGS) * len(LINESTATUSES), self.q1_mask,
            [li["l_quantity"], li["l_extendedprice"], self.disc_price,
             charge, li["l_discount"]])
        rows = []
        for g, (cnt, (qty, price, dp, ch, disc)) in enumerate(groups):
            if not cnt:
                continue
            rows.append((RETURNFLAGS[g // len(LINESTATUSES)],
                         LINESTATUSES[g % len(LINESTATUSES)],
                         fmt_dec(qty, 2), fmt_dec(price, 2), fmt_dec(dp, 4),
                         fmt_dec(ch, 6), avg_dec(qty, cnt, 2),
                         avg_dec(price, cnt, 2), avg_dec(disc, cnt, 2),
                         str(cnt)))
        return sorted(rows)

    def _by_name(self, names, groups):
        return sorted((names[g], str(cnt), fmt_dec(s[0], 4))
                      for g, (cnt, s) in enumerate(groups) if cnt)

    def q3(self):
        # orders is keyed 0..n-1 in row order: the join is an index
        okey = self.li["l_orderkey"]
        mask = self.q1_mask & \
            (self.orders["o_orderdate"][okey] < days("1998-01-01"))
        return self._by_name(PRIORITIES, self.group_sums(
            self.orders["o_orderpriority"][okey], len(PRIORITIES),
            mask, [self.disc_price]))

    def q5(self):
        seg = self.customer["c_mktsegment"][
            self.orders["o_custkey"][self.li["l_orderkey"]]]
        return self._by_name(SEGMENTS, self.group_sums(
            seg, len(SEGMENTS), self.q1_mask, [self.disc_price]))

    def q6(self):
        li = self.li
        lo, hi = days("1994-01-01"), days("1995-01-01")
        m = (li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi) \
            & (li["l_discount"] >= 5) & (li["l_discount"] <= 7) \
            & (li["l_quantity"] < 2400)
        rev = self.sum(li["l_extendedprice"][m] * li["l_discount"][m])
        return [(str(int(m.sum())), fmt_dec(rev, 4))]


def reference(data: dict, arithmetic: str = "exact") -> dict:
    """→ {statement name: the rows the wire must carry, in order}."""
    ref = Reference(data, arithmetic)
    return {"Q1": ref.q1(), "Q3": ref.q3(), "Q5": ref.q5(), "Q6": ref.q6()}
