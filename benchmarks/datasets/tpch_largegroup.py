"""TPC-H's large-group statements over the TPC-H-shaped data: Q3 (clause
2.4.3), Q10 (2.4.10) and Q18 (2.4.18) with their specified GROUP BY, ORDER BY
an aggregate, LIMIT n and validation parameters.

The data is `tpch_shaped`'s, made by that module loaded BY PATH (the same
seed gives the same lineitem and customer bytes, and the same four `orders`
columns, as `tpch-shaped-*`), plus `o_totalprice DECIMAL(15,2)` drawn from the
seed with its domain's extremes planted like every other drawn column.

Cuts, all listed in the configuration's `reduced`: the select and group lists
hold only columns the data set carries (no `o_shippriority`, `c_name`,
`c_acctbal`, `c_phone`, `n_name`, `c_address`, `c_comment` — each a function
of the kept key, so the groups are the specification's). Assumed: the group
key as the last ORDER BY term, because the specification leaves the order of
ties open and the comparison here is text-equal in order.

`reference` is plain numpy over the raw columns: dense keys make every join
an index and every GROUP BY a `bincount`. It imports nothing of the program.
`arithmetic="float32"` is the control: the same answers with every SUM
accumulated in float32, the nearest precision below the exact DECIMAL the
configuration guarantees in which the answers change (a float64 holds every
per-group sum here exactly: all stay under 2^53).
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "benchmarks_datasets_tpch_shaped_base",
    Path(__file__).resolve().parent / "tpch_shaped.py")
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

# o_totalprice is uniform over [PRICE_LO, PRICE_HI) cents: 857.71 .. 555,285.16
# are the least and greatest order totals dbgen's formulas allow (clause 4.2.3)
PRICE_LO, PRICE_HI = 85_771, 55_528_517

# The joins are written JOIN ... ON from the fact table down, as every
# statement of `tpch_shaped` is: the same relational expression as the
# specification's comma list with its equalities in WHERE.
Q3 = """SELECT l_orderkey, SUM(l_extendedprice * (1 - l_discount)) AS revenue,
 o_orderdate
 FROM lineitem JOIN orders ON l_orderkey = o_orderkey
 JOIN customer ON o_custkey = c_custkey
 WHERE c_mktsegment = 'BUILDING' AND o_orderdate < '1995-03-15'
 AND l_shipdate > '1995-03-15'
 GROUP BY l_orderkey, o_orderdate
 ORDER BY revenue DESC, o_orderdate, l_orderkey LIMIT 10"""

Q10 = """SELECT c_custkey, SUM(l_extendedprice * (1 - l_discount)) AS revenue
 FROM lineitem JOIN orders ON l_orderkey = o_orderkey
 JOIN customer ON o_custkey = c_custkey
 WHERE o_orderdate >= '1993-10-01' AND o_orderdate < '1994-01-01'
 AND l_returnflag = 'R'
 GROUP BY c_custkey
 ORDER BY revenue DESC, c_custkey LIMIT 20"""

Q18 = """SELECT c_custkey, o_orderkey, o_orderdate, o_totalprice,
 SUM(l_quantity)
 FROM lineitem JOIN orders ON l_orderkey = o_orderkey
 JOIN customer ON o_custkey = c_custkey
 WHERE o_orderkey IN (SELECT l_orderkey FROM lineitem
 GROUP BY l_orderkey HAVING SUM(l_quantity) > 300)
 GROUP BY c_custkey, o_orderkey, o_orderdate, o_totalprice
 ORDER BY o_totalprice DESC, o_orderdate, o_orderkey LIMIT 100"""

LIMITS = {"Q3": 10, "Q10": 20, "Q18": 100}
Q18_QUANTITY = 300_00            # HAVING SUM(l_quantity) > 300, scale 2

SCHEMA = base.SCHEMA[:1] + (
    "CREATE TABLE orders (o_orderkey BIGINT PRIMARY KEY, "
    "o_orderdate DATE, o_orderpriority CHAR(1), o_custkey BIGINT, "
    "o_totalprice DECIMAL(15,2))",
) + base.SCHEMA[2:]


def generate(scale: float, seed: int) -> dict:
    """`tpch_shaped.generate(scale, seed)` and, on `orders`, `o_totalprice`
    from a stream of its own ([seed, 3]), extremes planted in rows 0 and 1."""
    data = base.generate(scale, seed)
    n_orders = len(data["orders"]["o_orderkey"])
    rng = np.random.default_rng([seed, 3])
    price = rng.integers(PRICE_LO, PRICE_HI, n_orders, dtype=np.int64)
    price[0], price[1 % n_orders] = PRICE_LO, PRICE_HI - 1
    data["orders"]["o_totalprice"] = price
    return data


def load(eng, data: dict) -> None:
    """Bulk-append each table (one transaction a table) and ANALYZE it: as
    `tpch_shaped.load`, with this module's schema."""
    from tidb_tpu.chunk import Chunk, Column

    s = eng.new_session()
    for ddl in SCHEMA:
        s.execute(ddl)
    coded = {"l_returnflag": base.RETURNFLAGS,
             "l_linestatus": base.LINESTATUSES,
             "o_orderpriority": base.PRIORITIES,
             "c_mktsegment": base.SEGMENTS}
    for name, cols in data.items():
        info = eng.catalog.info_schema.table(name)
        assert [c.name for c in info.columns] == list(cols), name
        chunk = Chunk([
            Column(c.ftype,
                   np.array(coded[c.name], dtype=object)[cols[c.name]]
                   if c.name in coded else cols[c.name], None)
            for c in info.columns])
        txn = eng.store.begin()
        txn.append(info.id, chunk)
        txn.commit()
        del chunk
    for name in data:
        s.execute(f"ANALYZE TABLE {name}")
    require_device_plans(eng)


def require_device_plans(eng) -> None:
    """Can this program run the deployment at all? Asked of the PLANS,
    before a run spends its time: every statement must plan as one device
    fragment under at most a projection of its result rows. A program
    whose `tidb_tpu_strict` says nothing about host plans (any before this
    data set existed) answers the top-n from the host over every group and
    Q18's joins over every row — the `device_path` guarantee broken, which
    `correct` would only say after tens of minutes; the benchmark's
    contract wants a program that cannot run a configuration to fail
    soon. Raises. EXPLAIN only plans; the device path is forced so that
    the check reads the same at a rehearsal's scale (Q3 is asked first: it
    has no subquery a planner could run)."""
    s = eng.new_session()
    s.execute("SET tidb_tpu_engine = 'on'")
    s.execute("SET tidb_tpu_row_threshold = 1")
    for name in ("Q3", "Q10", "Q18"):
        ops = [str(r[0]).lstrip(" └─") for r in
               s.execute("EXPLAIN " + STATEMENTS[name])[0].rows]
        above = ops[:ops.index("TpuFragment")] if "TpuFragment" in ops \
            else ops
        host = [o for o in above if o != "Projection"]
        if host:
            raise RuntimeError(
                f"tpch_largegroup: {name} does not plan as one device "
                f"fragment on this program (host operators: {host[:3]}); "
                "the configuration's device_path guarantee cannot hold")


STATEMENTS = {"Q3": Q3, "Q10": Q10, "Q18": Q18}

COLUMNS = {
    "Q3": {"lineitem": ["l_extendedprice", "l_discount", "l_shipdate",
                        "l_orderkey"],
           "orders": ["o_orderkey", "o_orderdate", "o_custkey"],
           "customer": ["c_custkey", "c_mktsegment"]},
    "Q10": {"lineitem": ["l_extendedprice", "l_discount", "l_returnflag",
                         "l_orderkey"],
            "orders": ["o_orderkey", "o_orderdate", "o_custkey"],
            "customer": ["c_custkey"]},
    # lineitem's two columns are read twice (subquery and outer join), so
    # they are named twice: the bytes function counts every name
    "Q18": {"lineitem": ["l_quantity", "l_orderkey",
                         "l_quantity", "l_orderkey"],
            "orders": ["o_orderkey", "o_orderdate", "o_custkey",
                       "o_totalprice"],
            "customer": ["c_custkey"]},
}
PRUNED_TABLE = "lineitem"


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _sums_exact(keys, weights, n):
    """Per-key integer sums. bincount accumulates in float64: exact while
    every sum stays under 2^53 (checked)."""
    out = np.bincount(keys, weights=weights, minlength=n)
    assert out.size == 0 or np.abs(out).max() < 2.0 ** 53
    return out.astype(np.int64)


def _sums_float32(keys, weights, n):
    """The control: each group's SUM accumulated in a float32, in row
    order."""
    acc = np.zeros(n, np.float32)
    np.add.at(acc, keys, weights.astype(np.float32))
    return np.rint(acc.astype(np.float64)).astype(np.int64)


_SUMS = {"exact": _sums_exact, "float64": _sums_exact,
         "float32": _sums_float32}


def _date(d: int) -> str:
    return (base.datetime.date(1970, 1, 1)
            + base.datetime.timedelta(days=int(d))).isoformat()


def _top(n, *keys_last_is_primary):
    """Indices of the first `n` rows in the order `np.lexsort` gives."""
    return np.lexsort(keys_last_is_primary)[:n]


def reference(data: dict, arithmetic: str = "exact") -> dict:
    """→ {statement name: the rows the wire must carry, in order}."""
    sums = _SUMS[arithmetic]
    li, orders, cust = data["lineitem"], data["orders"], data["customer"]
    okey = li["l_orderkey"]
    n_orders, n_cust = len(orders["o_orderkey"]), len(cust["c_custkey"])
    odate, ocust = orders["o_orderdate"], orders["o_custkey"]
    disc_price = li["l_extendedprice"] * (100 - li["l_discount"])  # scale 4
    out = {}

    # Q3: orders before the date whose customer is in the segment, their
    # lineitems shipped after it; one group per order (o_orderdate is a
    # function of the key)
    cut = base.days("1995-03-15")
    building = base.SEGMENTS.index("BUILDING")
    order_ok = (odate < cut) & (cust["c_mktsegment"][ocust] == building)
    m = (li["l_shipdate"] > cut) & order_ok[okey]
    rev = sums(okey[m], disc_price[m], n_orders)
    live = np.flatnonzero(np.bincount(okey[m], minlength=n_orders))
    top = live[_top(LIMITS["Q3"], live, odate[live], -rev[live])]
    out["Q3"] = [(str(k), base.fmt_dec(rev[k], 4), _date(odate[k]))
                 for k in top]

    # Q10: returned items of the quarter's orders, by customer
    lo, hi = base.days("1993-10-01"), base.days("1994-01-01")
    order_ok = (odate >= lo) & (odate < hi)
    m = (li["l_returnflag"] == base.RETURNFLAGS.index("R")) & order_ok[okey]
    ckey = ocust[okey[m]]
    rev = sums(ckey, disc_price[m], n_cust)
    live = np.flatnonzero(np.bincount(ckey, minlength=n_cust))
    top = live[_top(LIMITS["Q10"], live, -rev[live])]
    out["Q10"] = [(str(k), base.fmt_dec(rev[k], 4)) for k in top]

    # Q18: orders whose quantities sum over 300; the outer SUM is the same
    # sum (the join multiplies nothing: order and customer keys are unique)
    qty = sums(okey, li["l_quantity"], n_orders)
    big = np.flatnonzero(qty > Q18_QUANTITY)
    price = orders["o_totalprice"]
    top = big[_top(LIMITS["Q18"], big, odate[big], -price[big])]
    out["Q18"] = [(str(ocust[k]), str(k), _date(odate[k]),
                   base.fmt_dec(price[k], 2), base.fmt_dec(qty[k], 2))
                  for k in top]
    return out
