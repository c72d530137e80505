"""TPC-H's refresh functions over the TPC-H-shaped data: RF1 "new sales"
(clause 2.6: insert new orders with their lineitems) and RF2 "old sales"
(clause 2.7: delete old orders with their lineitems), each cut into small
two-table transactions, each read back by Q1, Q3 and Q6.

The data is `tpch_shaped`'s, made by that module loaded BY PATH (the same
seed gives the same base bytes as `tpch-shaped-*`), and the three reads are
its statements. This module adds

    refresh_set(data, seed, n) -> the n-th refresh pair's rows and key range
    reference(data, arithmetic) -> base answers + what `check` needs to give
                                   the exact rows after any number of pairs
                                   (a `RefreshState` under key `STATE`)
    load(engine, data)         -> as `tpch_shaped.load`, after a PROBE that
                                   raises on a program that cannot run this
                                   deployment (below)

The n-th RF1 transaction inserts K = `ORDERS_PER_TRANSACTION` orders with
keys `n_orders + n*K ...` (above every key loaded: dbgen would use the gaps
of its sparse keys, these keys are dense) and four lineitems each (the
generator's ratio), every column drawn from the generator's own domains by
a numpy generator seeded from (seed, n): ship and order dates over the whole
range, in no order. The n-th RF2 transaction deletes the K lowest order keys
still present, `[n*K, (n+1)*K)`, and their lineitems. So after n pairs the
tables hold 5*K*n rows that were not loaded and have lost as many that were.

All three statements are sums and counts per group, so the reference keeps
integer partial sums per group for the base and adds or subtracts each
refresh set's: plain numpy on the scaled integers, AVG divided at the end as
`tpch_shaped.Reference` does. It imports nothing of the program and takes
nothing it made. `arithmetic="float64"` is the control: the same partial
sums accumulated in float64; it fails Q1 (`benchmarks/control.py`).

The probe. A program whose reads after a write rebuild the table (≈ 22 s of
host encode per unit of scale factor and table) would spend hours in this
cell's warm-up. `load` therefore runs, BEFORE the bulk load and on a table
of the same schema and `PROBE_ROWS` lineitems, one RF1 and one RF2
transaction of the configuration's shape, each followed by Q6, and raises
unless both reads extended the cached table: it reads the program's
`tidb_tpu_delta_extensions_total` and `tidb_tpu_delta_declines_total{gate=}`
through `information_schema.engine_metrics`; a program without the decline
counter, or with a decline counted, or that extended nothing, cannot run
this deployment.
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

ORDERS_PER_TRANSACTION = 150
LINEITEMS_PER_ORDER = 4
READS = ("Q1", "Q3", "Q6")
PROBE_ROWS = 262_144

SCHEMA = base.SCHEMA
STATEMENTS = {q: base.STATEMENTS[q] for q in READS}
COLUMNS = {q: base.COLUMNS[q] for q in READS}
PRUNED_TABLE = base.PRUNED_TABLE

# the run's data and seed, kept for the operation kind: the harness hands
# `bind` this module, not what `generate` returned
CURRENT: dict = {}


def generate(scale: float, seed: int) -> dict:
    data = base.generate(scale, seed)
    CURRENT.clear()
    CURRENT.update(data=data, seed=seed)
    return data


# ---------------------------------------------------------------------------
# the refresh sets
# ---------------------------------------------------------------------------

def refresh_set(data: dict, seed: int, n: int,
                k: int = ORDERS_PER_TRANSACTION) -> dict:
    """→ {"orders": {column: ndarray}, "lineitem": {column: ndarray}} of
    the n-th RF1 transaction, in schema column order and the generator's
    encodings (DECIMAL(15,2) as scaled int64, DATE as days, coded strings
    as int8), and "delete": (a, b), the n-th RF2's order key range."""
    n_orders = len(data["orders"]["o_orderkey"])
    n_cust = len(data["customer"]["c_custkey"])
    rng = np.random.default_rng([seed, 5, n])
    keys = n_orders + n * k + np.arange(k, dtype=np.int64)
    m = k * LINEITEMS_PER_ORDER

    def draw(lo, hi, size, dtype=np.int64):
        return rng.integers(lo, hi, size, dtype=dtype)

    orders = {
        "o_orderkey": keys,
        "o_orderdate": draw(base.DATE_LO, base.DATE_HI, k).astype(np.int32),
        "o_orderpriority": draw(0, len(base.PRIORITIES), k, np.int8),
        "o_custkey": draw(0, n_cust, k),
    }
    lineitem = {
        "l_quantity": draw(100, 5001, m),
        "l_extendedprice": draw(90_000, 10_500_001, m),
        "l_discount": draw(0, 11, m),
        "l_tax": draw(0, 9, m),
        "l_returnflag": draw(0, len(base.RETURNFLAGS), m, np.int8),
        "l_linestatus": draw(0, len(base.LINESTATUSES), m, np.int8),
        "l_shipdate": draw(base.DATE_LO, base.DATE_HI, m).astype(np.int32),
        "l_orderkey": np.repeat(keys, LINEITEMS_PER_ORDER),
    }
    return {"orders": orders, "lineitem": lineitem,
            "delete": (n * k, (n + 1) * k)}


def _date(d: int) -> str:
    return (base.datetime.date(1970, 1, 1)
            + base.datetime.timedelta(days=int(d))).isoformat()


_CODED = {"l_returnflag": base.RETURNFLAGS, "l_linestatus": base.LINESTATUSES,
          "o_orderpriority": base.PRIORITIES}
_DATES = ("l_shipdate", "o_orderdate")
_DECIMALS = ("l_quantity", "l_extendedprice", "l_discount", "l_tax")


def values_sql(cols: dict) -> str:
    """Generated rows → the VALUES list of an INSERT, column order as in
    the schema."""
    texts = []
    for name, col in cols.items():
        if name in _CODED:
            texts.append([f"'{_CODED[name][c]}'" for c in col])
        elif name in _DATES:
            texts.append([f"'{_date(c)}'" for c in col])
        elif name in _DECIMALS:
            texts.append([base.fmt_dec(c, 2) for c in col])
        else:
            texts.append([str(int(c)) for c in col])
    return ",".join("(" + ",".join(row) + ")" for row in zip(*texts))


def refresh_sql(rs: dict) -> dict:
    """The statements of one refresh pair, as the operation sends them."""
    a, b = rs["delete"]
    return {
        "rf1": ["BEGIN",
                "INSERT INTO orders VALUES " + values_sql(rs["orders"]),
                "INSERT INTO lineitem VALUES " + values_sql(rs["lineitem"]),
                "COMMIT"],
        "rf2": ["BEGIN",
                f"DELETE FROM lineitem WHERE l_orderkey >= {a} "
                f"AND l_orderkey < {b}",
                f"DELETE FROM orders WHERE o_orderkey >= {a} "
                f"AND o_orderkey < {b}",
                "COMMIT"]}


# ---------------------------------------------------------------------------
# the plain reference: integer partial sums per group, added and subtracted
# ---------------------------------------------------------------------------

N_Q1 = len(base.RETURNFLAGS) * len(base.LINESTATUSES)
N_Q3 = len(base.PRIORITIES)


def _sum_exact(col):
    return int(col.sum(dtype=np.int64))


def _sum_float64(col):
    return float(col.astype(np.float64).sum())


_SUMS = {"exact": _sum_exact, "float64": _sum_float64}


def partial_sums(lineitem: dict, order_date, order_priority,
                 arithmetic: str = "exact") -> dict:
    """The three statements' per-group counts and sums over `lineitem`'s
    rows, each row's order given by its date and priority (aligned with
    the rows). Counts are integers; the sums integers, or float64 in the
    control."""
    total = _SUMS[arithmetic]
    li = lineitem
    disc_price = li["l_extendedprice"] * (100 - li["l_discount"])
    charge = disc_price * (100 + li["l_tax"])
    q1_mask = li["l_shipdate"] <= base.days("1998-09-02")
    code = li["l_returnflag"].astype(np.int16) * len(base.LINESTATUSES) \
        + li["l_linestatus"]
    q1 = []
    for g in range(N_Q1):
        m = q1_mask & (code == g)
        q1.append([int(m.sum())] + [total(c[m]) for c in (
            li["l_quantity"], li["l_extendedprice"], disc_price, charge,
            li["l_discount"])])
    q3_mask = q1_mask & (order_date < base.days("1998-01-01"))
    q3 = []
    for g in range(N_Q3):
        m = q3_mask & (order_priority == g)
        q3.append([int(m.sum()), total(disc_price[m])])
    lo, hi = base.days("1994-01-01"), base.days("1995-01-01")
    m = (li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi) \
        & (li["l_discount"] >= 5) & (li["l_discount"] <= 7) \
        & (li["l_quantity"] < 2400)
    q6 = [int(m.sum()), total(li["l_extendedprice"][m] * li["l_discount"][m])]
    return {"Q1": q1, "Q3": q3, "Q6": q6}


def combine(a: dict, b: dict, sign: int) -> dict:
    """a + sign * b, group by group."""
    return {"Q1": [[x + sign * y for x, y in zip(ga, gb)]
                   for ga, gb in zip(a["Q1"], b["Q1"])],
            "Q3": [[x + sign * y for x, y in zip(ga, gb)]
                   for ga, gb in zip(a["Q3"], b["Q3"])],
            "Q6": [x + sign * y for x, y in zip(a["Q6"], b["Q6"])]}


def _int(v) -> int:
    return int(round(v)) if isinstance(v, float) else int(v)


def rows_of(state: dict) -> dict:
    """Partial sums → the rows the wire must carry, in order."""
    q1 = []
    for g, (cnt, qty, price, dp, ch, disc) in enumerate(state["Q1"]):
        if not cnt:
            continue
        qty, price, dp, ch, disc = map(_int, (qty, price, dp, ch, disc))
        q1.append((base.RETURNFLAGS[g // len(base.LINESTATUSES)],
                   base.LINESTATUSES[g % len(base.LINESTATUSES)],
                   base.fmt_dec(qty, 2), base.fmt_dec(price, 2),
                   base.fmt_dec(dp, 4), base.fmt_dec(ch, 6),
                   base.avg_dec(qty, cnt, 2), base.avg_dec(price, cnt, 2),
                   base.avg_dec(disc, cnt, 2), str(cnt)))
    q3 = sorted((base.PRIORITIES[g], str(cnt), base.fmt_dec(_int(rev), 4))
                for g, (cnt, rev) in enumerate(state["Q3"]) if cnt)
    cnt, rev = state["Q6"]
    return {"Q1": sorted(q1), "Q3": q3,
            "Q6": [(str(cnt), base.fmt_dec(_int(rev), 4))]}


class RefreshState:
    """The state after any number of refresh pairs: `after(n, "rf1")` is
    the rows once RF1 0..n and RF2 0..n-1 are committed, `after(n, "rf2")`
    once RF2 n is too; `base` the rows before any."""

    def __init__(self, data: dict, seed: int, arithmetic: str = "exact",
                 k: int = ORDERS_PER_TRANSACTION):
        self.data, self.seed, self.k = data, seed, k
        self.arithmetic = arithmetic
        okey = data["lineitem"]["l_orderkey"]
        self.base_state = partial_sums(
            data["lineitem"], data["orders"]["o_orderdate"][okey],
            data["orders"]["o_orderpriority"][okey], arithmetic)
        self.base = rows_of(self.base_state)
        # RF2 n deletes keys [n*k, (n+1)*k): the lineitems of each range,
        # found through one sort of the keys
        self._by_key = np.argsort(okey, kind="stable")
        self._key_sorted = okey[self._by_key]
        self._states: list = []    # n → (after rf1, after rf2, rows gone)

    # it rides in the reference's dict beside the answers and is none:
    # two references differ by their answers alone
    def __eq__(self, other):
        return isinstance(other, RefreshState)

    __hash__ = None

    def deltas(self, n: int):
        """→ (what RF1 n adds, what RF2 n takes away, lineitems gone)."""
        rs = refresh_set(self.data, self.seed, n, self.k)
        rep = np.repeat(np.arange(self.k), LINEITEMS_PER_ORDER)
        added = partial_sums(rs["lineitem"],
                             rs["orders"]["o_orderdate"][rep],
                             rs["orders"]["o_orderpriority"][rep],
                             self.arithmetic)
        a, b = rs["delete"]
        lo = np.searchsorted(self._key_sorted, a, side="left")
        hi = np.searchsorted(self._key_sorted, b, side="left")
        rows = np.sort(self._by_key[lo:hi])
        gone = {c: v[rows] for c, v in self.data["lineitem"].items()}
        okey = gone["l_orderkey"]
        removed = partial_sums(gone, self.data["orders"]["o_orderdate"][okey],
                               self.data["orders"]["o_orderpriority"][okey],
                               self.arithmetic)
        return added, removed, int(rows.size)

    def _upto(self, n: int):
        while len(self._states) <= n:
            prev = self._states[-1][1] if self._states else self.base_state
            added, removed, n_gone = self.deltas(len(self._states))
            s1 = combine(prev, added, +1)
            self._states.append((s1, combine(s1, removed, -1), n_gone))
        return self._states[n]

    def after(self, n: int, which: str) -> dict:
        s1, s2, _gone = self._upto(n)
        return rows_of(s1 if which == "rf1" else s2)

    def deleted_rows(self, n: int) -> int:
        """Lineitem rows RF2 n deletes."""
        return self._upto(n)[2]


STATE = "refresh_state"


def reference(data: dict, arithmetic: str = "exact") -> dict:
    """→ {statement name: the rows the wire must carry before any refresh,
    in order} and, under `STATE`, the `RefreshState` that gives them after
    any number of refresh pairs (the harness copies the dict, so what
    `check` needs rides in it)."""
    seed = CURRENT["seed"] if CURRENT.get("data") is data else 0
    state = RefreshState(data, seed, arithmetic)
    return dict(state.base, **{STATE: state})


# ---------------------------------------------------------------------------
# load, behind the probe
# ---------------------------------------------------------------------------

def _bulk_load(eng, data: dict, suffix: str = "") -> None:
    from tidb_tpu.chunk import Chunk, Column

    s = eng.new_session()
    names = {}
    for ddl in SCHEMA:
        table = ddl.split()[2]
        names[table] = table + suffix
        s.execute(ddl.replace(f"CREATE TABLE {table} ",
                              f"CREATE TABLE {table}{suffix} ", 1))
    coded = dict(_CODED, c_mktsegment=base.SEGMENTS)
    for name, cols in data.items():
        info = eng.catalog.info_schema.table(names[name])
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
        s.execute(f"ANALYZE TABLE {names[name]}")


def _counter(session, metric: str) -> dict:
    rs = session.execute(
        "SELECT LABELS, VALUE FROM information_schema.engine_metrics "
        f"WHERE METRIC = '{metric}'")[0]
    return {str(r[0]): float(r[1]) for r in rs.rows}


def require_extension(eng, seed: int = 0) -> dict:
    """Can this program run the deployment at all? One RF1 and one RF2
    transaction of the configuration's shape over a probe table, each
    followed by Q6: both reads must extend the cached tables, none may
    fall to a rebuild. Raises; → the counters read."""
    small = base.generate(PROBE_ROWS / base.LINEITEM_ROWS_SF1, seed)
    suffix = "_probe"
    _bulk_load(eng, small, suffix)
    s = eng.new_session()
    s.execute("SET tidb_tpu_engine = 'on'")
    s.execute("SET tidb_tpu_row_threshold = 1")
    q6 = STATEMENTS["Q6"].replace("lineitem", "lineitem" + suffix)
    ref = RefreshState(small, seed)
    try:
        got = [s.execute(q6)[0].rows]
        seen = []
        sql = refresh_sql(refresh_set(small, seed, 0))
        for which in ("rf1", "rf2"):
            before = (_counter(s, "tidb_tpu_delta_extensions_total"),
                      _counter(s, "tidb_tpu_delta_declines_total"))
            for stmt in sql[which]:
                s.execute(stmt.replace("orders", "orders" + suffix)
                          .replace("lineitem", "lineitem" + suffix))
            got.append(s.execute(q6)[0].rows)
            ext = sum(_counter(s, "tidb_tpu_delta_extensions_total")
                      .values()) - sum(before[0].values())
            dec = _counter(s, "tidb_tpu_delta_declines_total")
            fell = {g: v - before[1].get(g, 0.0) for g, v in dec.items()
                    if v - before[1].get(g, 0.0)}
            seen.append({"after": which, "extensions": ext,
                         "declines": fell})
            if ext < 1 or fell:
                raise RuntimeError(
                    f"tpch_refresh: the read after {which.upper()} did not "
                    f"extend the cached table on this program (extensions "
                    f"{ext:g}, declines {fell or 'not counted'}): every "
                    "read after a write would rebuild the table, and the "
                    "cell's warm-up alone would outlast a run")
        want = [ref.base["Q6"], ref.after(0, "rf1")["Q6"],
                ref.after(0, "rf2")["Q6"]]
        text = [[tuple(str(v) for v in r) for r in rows] for rows in got]
        if text != [[tuple(r) for r in w] for w in want]:
            raise RuntimeError(f"tpch_refresh: the probe's Q6 answered "
                               f"{text}, the reference {want}")
        return {"probe": seen}
    finally:
        for table in ("lineitem", "orders", "customer"):
            s.execute(f"DROP TABLE IF EXISTS {table}{suffix}")


def load(eng, data: dict) -> None:
    """The probe, then bulk-append each table (one transaction a table)
    and ANALYZE it, as `tpch_shaped.load` does."""
    require_extension(eng)
    _bulk_load(eng, data)
