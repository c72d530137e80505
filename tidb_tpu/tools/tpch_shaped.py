"""The repo's TPC-H-shaped deployment: schema, generator, loader, queries.

`chip_smoke.py`'s definition; the benchmark keeps its own copy under
`benchmarks/datasets/` (the mockDataSource pattern of the reference's executor/benchmark_test.go — the
generated columns go straight into the columnar region store through its
bulk append, no SQL INSERT round trip).

Shapes: `lineitem` carries the Q1/Q3/Q5/Q6 columns with TPC-H-like value
distributions, sorted by `l_shipdate` (TPC-H lineitem arrives in orderdate
order, so shipdate is nearly clustered on disk — that is what gives per-slab
zone maps their pruning power on Q6); `orders` has one row per four lineitem
rows and `customer` one per forty, both keyed 0..n-1 in row order. SF=1 is
6,001,215 lineitem rows.

`generate()` returns the raw columns so a caller can compute a reference
answer from them outside the engine; string columns are kept as small-int
codes into the *_VALUES tuples and only expanded at load.
"""

from __future__ import annotations

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


def generate(n_rows: int, seed: int = 42) -> dict:
    """→ {table: {column: ndarray}} in schema column order. DECIMAL(15,2)
    columns are scaled int64 (1.00 ↔ 100), DATE int32 days, string
    columns int8 codes into the module's *_VALUES tuples."""
    sizes = table_rows(n_rows)
    n, n_orders, n_cust = (sizes["lineitem"], sizes["orders"],
                           sizes["customer"])
    rng = np.random.default_rng([seed, 0])
    shipdate = rng.integers(DATE_LO, DATE_HI, n).astype(np.int32)
    order = np.argsort(shipdate)

    def li(col):
        return col[order]

    lineitem = {
        "l_quantity": li(rng.integers(100, 5001, n)),          # 1.00..50.00
        "l_extendedprice": li(rng.integers(90_000, 10_500_001, n)),
        "l_discount": li(rng.integers(0, 11, n)),              # 0.00..0.10
        "l_tax": li(rng.integers(0, 9, n)),                    # 0.00..0.08
        # returnflag correlates with shipdate in TPC-H; uniform is fine
        "l_returnflag": li(rng.integers(0, len(RETURNFLAGS), n,
                                        dtype=np.int8)),
        "l_linestatus": li(rng.integers(0, len(LINESTATUSES), n,
                                        dtype=np.int8)),
        "l_shipdate": shipdate[order],
    }
    del order
    rng = np.random.default_rng([seed, 1])
    lineitem["l_orderkey"] = rng.integers(0, n_orders, n)
    orders = {
        "o_orderkey": np.arange(n_orders, dtype=np.int64),
        "o_orderdate": rng.integers(DATE_LO, DATE_HI,
                                    n_orders).astype(np.int32),
        "o_orderpriority": rng.integers(0, len(PRIORITIES), n_orders,
                                        dtype=np.int8),
        "o_custkey": rng.integers(0, n_cust, n_orders),
    }
    customer = {
        "c_custkey": np.arange(n_cust, dtype=np.int64),
        "c_mktsegment": rng.integers(0, len(SEGMENTS), n_cust,
                                     dtype=np.int8),
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


def build_engine(n_rows: int, seed: int = 42):
    """→ (engine, session) with the three tables loaded and analyzed, plus
    the small point-read table `pr` (same-digest `WHERE k = ?` probes are
    the interactive class and the micro-batch coalescing substrate)."""
    from tidb_tpu.session import Engine

    eng = Engine()
    load(eng, generate(n_rows, seed))
    s = eng.new_session()
    s.execute("CREATE TABLE pr (k BIGINT, v BIGINT)")
    s.execute("INSERT INTO pr VALUES " +
              ", ".join(f"({i}, {i * i})" for i in range(1024)))
    s.execute("ANALYZE TABLE pr")
    return eng, s
