"""22-query TPC-H device-coverage sweep (the whole-query compilation
ratchet).

Flare's argument is that query compilation pays off only when it covers
whole workloads, not showcase queries — so the tracked metric here is the
fraction of the full TPC-H suite whose ANALYTIC CORE runs as fused device
fragments with zero CPU fallback.  Every query is the TPC-H shape adapted
to this engine's SQL surface (same joins, aggregates, subquery and
ordering structure; synthetic column distributions) over a generated
schema of all eight tables.

Per query the sweep reports:

  fused              every extracted fragment ran on device (and at
                     least one fragment was extracted)
  n_fragments        device fragments extracted from the plan
  fallback           normalized reason code (eligibility.FALLBACK_REASONS)
                     of the first fragment that fell back, else None
  programs_per_slab  warm-run device launches / data slabs — the
                     slabs+1 fused-pipeline model shows up as ~1.x
  speedup            CPU wall / device wall on this host (small SF:
                     indicative only, the ratchet keys on `fused`)

`tools/check_coverage.py` compares a fresh sweep against the committed
COVERAGE.json baseline and fails when a query that was fused regresses
to fallback.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np
from tidb_tpu.executor import run_to_completion
from tidb_tpu.executor.builder import build
from tidb_tpu.executor.fragment import TpuFragmentExec

# ---------------------------------------------------------------------------
# Queries: TPC-H 1-22, adapted to the engine's SQL surface.
# ---------------------------------------------------------------------------

QUERIES: Dict[str, str] = {
    # pricing summary report: the headline fused agg+sort chain
    "q1": """SELECT l_returnflag, l_linestatus, SUM(l_quantity),
        SUM(l_extendedprice), SUM(l_extendedprice * (1 - l_discount)),
        AVG(l_quantity), COUNT(*) FROM lineitem
        WHERE l_shipdate <= '1998-09-02'
        GROUP BY l_returnflag, l_linestatus
        ORDER BY l_returnflag, l_linestatus""",
    # minimum-cost supplier: join chain + grouped MIN, TopN root
    "q2": """SELECT n_name, MIN(ps_supplycost), COUNT(*)
        FROM partsupp JOIN supplier ON ps_suppkey = s_suppkey
        JOIN nation ON s_nationkey = n_nationkey
        JOIN region ON n_regionkey = r_regionkey
        WHERE r_name = 'EUROPE'
        GROUP BY n_name ORDER BY 2 LIMIT 10""",
    # shipping priority: join + agg + TopN over revenue
    "q3": """SELECT l_orderkey, SUM(l_extendedprice * (1 - l_discount)),
        MIN(o_orderdate)
        FROM lineitem JOIN orders ON l_orderkey = o_orderkey
        JOIN customer ON o_custkey = c_custkey
        WHERE c_mktsegment = 'BUILDING' AND o_orderdate < '1995-03-15'
          AND l_shipdate > '1995-03-15'
        GROUP BY l_orderkey ORDER BY 2 DESC LIMIT 10""",
    # order priority checking: EXISTS semijoin + grouped count
    "q4": """SELECT o_orderpriority, COUNT(*) FROM orders
        WHERE o_orderdate >= '1993-07-01' AND o_orderdate < '1993-10-01'
          AND EXISTS (SELECT 1 FROM lineitem
                      WHERE l_orderkey = o_orderkey
                        AND l_commitdate < l_receiptdate)
        GROUP BY o_orderpriority ORDER BY o_orderpriority""",
    # local supplier volume: 5-way join + grouped revenue
    "q5": """SELECT n_name, SUM(l_extendedprice * (1 - l_discount))
        FROM lineitem JOIN orders ON l_orderkey = o_orderkey
        JOIN customer ON o_custkey = c_custkey
        JOIN nation ON c_nationkey = n_nationkey
        JOIN region ON n_regionkey = r_regionkey
        WHERE r_name = 'ASIA' AND o_orderdate >= '1994-01-01'
          AND o_orderdate < '1995-01-01'
        GROUP BY n_name ORDER BY 2 DESC""",
    # forecasting revenue change: the selective zone-map scan
    "q6": """SELECT COUNT(*), SUM(l_extendedprice * l_discount)
        FROM lineitem WHERE l_shipdate >= '1994-01-01'
          AND l_shipdate < '1995-01-01'
          AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24""",
    # volume shipping: join + YEAR() group keys
    "q7": """SELECT n_name, YEAR(l_shipdate), SUM(l_extendedprice)
        FROM lineitem JOIN supplier ON l_suppkey = s_suppkey
        JOIN nation ON s_nationkey = n_nationkey
        WHERE l_shipdate >= '1995-01-01' AND l_shipdate <= '1996-12-31'
        GROUP BY n_name, YEAR(l_shipdate)
        ORDER BY n_name, 2""",
    # national market share: CASE share aggregation over a join chain
    "q8": """SELECT YEAR(o_orderdate),
        SUM(CASE WHEN n_name = 'BRAZIL'
            THEN l_extendedprice * (1 - l_discount) ELSE 0 END),
        SUM(l_extendedprice * (1 - l_discount))
        FROM lineitem JOIN orders ON l_orderkey = o_orderkey
        JOIN supplier ON l_suppkey = s_suppkey
        JOIN nation ON s_nationkey = n_nationkey
        WHERE o_orderdate >= '1995-01-01' AND o_orderdate <= '1996-12-31'
        GROUP BY YEAR(o_orderdate) ORDER BY 1""",
    # product type profit: LIKE filter + multi-join grouped profit
    "q9": """SELECT n_name, YEAR(o_orderdate),
        SUM(l_extendedprice * (1 - l_discount) - ps_supplycost * l_quantity)
        FROM lineitem JOIN orders ON l_orderkey = o_orderkey
        JOIN part ON l_partkey = p_partkey
        JOIN partsupp ON l_partkey = ps_partkey AND l_suppkey = ps_suppkey
        JOIN supplier ON l_suppkey = s_suppkey
        JOIN nation ON s_nationkey = n_nationkey
        WHERE p_name LIKE '%green%'
        GROUP BY n_name, YEAR(o_orderdate) ORDER BY n_name, 2 DESC""",
    # returned item reporting: join + agg + TopN 20
    "q10": """SELECT c_custkey, c_name,
        SUM(l_extendedprice * (1 - l_discount)), MIN(c_acctbal)
        FROM lineitem JOIN orders ON l_orderkey = o_orderkey
        JOIN customer ON o_custkey = c_custkey
        WHERE o_orderdate >= '1993-10-01' AND o_orderdate < '1994-01-01'
          AND l_returnflag = 'R'
        GROUP BY c_custkey, c_name ORDER BY 3 DESC LIMIT 20""",
    # important stock identification: value threshold via uncorrelated
    # scalar subquery over the same aggregation
    "q11": """SELECT ps_partkey, SUM(ps_supplycost * ps_availqty)
        FROM partsupp JOIN supplier ON ps_suppkey = s_suppkey
        JOIN nation ON s_nationkey = n_nationkey
        WHERE n_name = 'GERMANY'
        GROUP BY ps_partkey
        HAVING SUM(ps_supplycost * ps_availqty) >
            (SELECT SUM(ps_supplycost * ps_availqty) * 0.0005
             FROM partsupp JOIN supplier ON ps_suppkey = s_suppkey
             JOIN nation ON s_nationkey = n_nationkey
             WHERE n_name = 'GERMANY')
        ORDER BY 2 DESC LIMIT 20""",
    # shipping modes and order priority: CASE tallies over a join
    "q12": """SELECT l_shipmode,
        SUM(CASE WHEN o_orderpriority = '1' OR o_orderpriority = '2'
            THEN 1 ELSE 0 END),
        SUM(CASE WHEN o_orderpriority <> '1' AND o_orderpriority <> '2'
            THEN 1 ELSE 0 END)
        FROM lineitem JOIN orders ON l_orderkey = o_orderkey
        WHERE l_shipmode IN ('MAIL', 'SHIP')
          AND l_commitdate < l_receiptdate
          AND l_receiptdate >= '1994-01-01' AND l_receiptdate < '1995-01-01'
        GROUP BY l_shipmode ORDER BY l_shipmode""",
    # customer distribution: two-level aggregation (count per customer,
    # then histogram of the counts) — the agg-over-agg shape
    "q13": """SELECT cnt, COUNT(*) FROM
        (SELECT o_custkey, COUNT(*) AS cnt FROM orders
         WHERE o_orderpriority <> '5' GROUP BY o_custkey) t
        GROUP BY cnt ORDER BY 2 DESC, cnt DESC LIMIT 20""",
    # promotion effect: CASE revenue share over a join
    "q14": """SELECT SUM(CASE WHEN p_type LIKE 'PROMO%'
            THEN l_extendedprice * (1 - l_discount) ELSE 0 END),
        SUM(l_extendedprice * (1 - l_discount))
        FROM lineitem JOIN part ON l_partkey = p_partkey
        WHERE l_shipdate >= '1995-09-01' AND l_shipdate < '1995-10-01'""",
    # top supplier: revenue per supplier ranked by a window function
    "q15": """SELECT s_suppkey, total,
        RANK() OVER (ORDER BY total DESC) AS rnk FROM
        (SELECT l_suppkey AS s_suppkey,
                SUM(l_extendedprice * (1 - l_discount)) AS total
         FROM lineitem
         WHERE l_shipdate >= '1996-01-01' AND l_shipdate < '1996-04-01'
         GROUP BY l_suppkey) rev
        ORDER BY rnk, s_suppkey LIMIT 10""",
    # parts/supplier relationship: grouped COUNT(DISTINCT) — the
    # cross-slab pair-dedup path
    "q16": """SELECT p_brand, p_size, COUNT(DISTINCT ps_suppkey)
        FROM partsupp JOIN part ON ps_partkey = p_partkey
        WHERE p_brand <> 'Brand#45' AND p_size < 20
        GROUP BY p_brand, p_size ORDER BY 3 DESC, p_brand LIMIT 20""",
    # small-quantity-order revenue: uncorrelated scalar AVG threshold
    "q17": """SELECT COUNT(*), SUM(l_extendedprice)
        FROM lineitem JOIN part ON l_partkey = p_partkey
        WHERE p_container = 'MED BOX' AND
          l_quantity < (SELECT AVG(l_quantity) * 0.5 FROM lineitem)""",
    # large volume customer: IN semijoin over a grouped HAVING subquery
    "q18": """SELECT c_custkey, o_orderkey, MIN(o_totalprice), SUM(l_quantity)
        FROM lineitem JOIN orders ON l_orderkey = o_orderkey
        JOIN customer ON o_custkey = c_custkey
        WHERE o_orderkey IN (SELECT l_orderkey FROM lineitem
                             GROUP BY l_orderkey HAVING SUM(l_quantity) > 150)
        GROUP BY c_custkey, o_orderkey ORDER BY 3 DESC, o_orderkey LIMIT 20""",
    # discounted revenue: the OR-of-ANDs disjunctive filter join
    "q19": """SELECT COUNT(*), SUM(l_extendedprice * (1 - l_discount))
        FROM lineitem JOIN part ON l_partkey = p_partkey
        WHERE (p_container = 'SM CASE' AND l_quantity <= 11)
           OR (p_container = 'MED BOX' AND l_quantity >= 10
               AND l_quantity <= 20)
           OR (p_container = 'LG BOX' AND l_quantity >= 20
               AND l_quantity <= 30)""",
    # potential part promotion: nested IN semijoins
    "q20": """SELECT s_suppkey, COUNT(*) FROM supplier
        JOIN nation ON s_nationkey = n_nationkey
        WHERE n_name = 'CANADA'
          AND s_suppkey IN (SELECT ps_suppkey FROM partsupp
                            WHERE ps_partkey IN
                                (SELECT p_partkey FROM part
                                 WHERE p_name LIKE 'forest%')
                              AND ps_availqty > 100)
        GROUP BY s_suppkey ORDER BY s_suppkey LIMIT 20""",
    # suppliers who kept orders waiting: semijoin + late-line filter
    "q21": """SELECT s_name, COUNT(*) FROM lineitem
        JOIN orders ON l_orderkey = o_orderkey
        JOIN supplier ON l_suppkey = s_suppkey
        WHERE o_orderstatus = 'F' AND l_receiptdate > l_commitdate
          AND l_orderkey IN (SELECT l_orderkey FROM lineitem
                             GROUP BY l_orderkey HAVING COUNT(*) > 1)
        GROUP BY s_name ORDER BY 2 DESC, s_name LIMIT 20""",
    # global sales opportunity: SUBSTRING group key + NOT EXISTS
    # anti-join against orders
    "q22": """SELECT SUBSTRING(c_phone, 1, 2), COUNT(*), SUM(c_acctbal)
        FROM customer
        WHERE SUBSTRING(c_phone, 1, 2) IN ('13', '31', '23', '29')
          AND c_acctbal > 0
          AND NOT EXISTS (SELECT 1 FROM orders WHERE o_custkey = c_custkey)
        GROUP BY SUBSTRING(c_phone, 1, 2) ORDER BY 1""",
}

# queries whose analytic core is NOT expected to fuse yet, with the
# taxonomy code the fragment layer reports — the ratchet allows these to
# stay fallback but fails if a FUSED query joins them
EXPECTED_FALLBACK: Dict[str, str] = {
    # (q18 left this list in PR 28: its IN over a grouped-HAVING subquery
    # plans as a semijoin whose build side is a nested device-rows
    # fragment — eligibility.nest_build_aggregates)
    # the SUBSTRING(c_phone, ...) group key / IN-list is a COMPUTED
    # string: no dictionary to prepare codes against, host executes
    "q22": "shape",
}


# ---------------------------------------------------------------------------
# Schema + data
# ---------------------------------------------------------------------------

def build_schema(s, n_lineitem: int = 6000, seed: int = 42) -> None:
    """Create and populate all eight TPC-H tables at a size proportional
    to `n_lineitem` (SF≈n/6M), via direct chunk appends."""
    from tidb_tpu.chunk import Chunk, Column

    eng = s.engine if hasattr(s, "engine") else s._engine
    rng = np.random.default_rng(seed)
    n = n_lineitem
    n_ord = max(n // 4, 8)
    n_cust = max(n // 15, 8)
    n_part = max(n // 20, 8)
    n_supp = max(n // 100, 4)
    n_ps = max(n // 10, 16)

    s.execute(
        "CREATE TABLE lineitem (l_orderkey BIGINT, l_partkey BIGINT, "
        "l_suppkey BIGINT, l_quantity DECIMAL(15,2), "
        "l_extendedprice DECIMAL(15,2), l_discount DECIMAL(15,2), "
        "l_tax DECIMAL(15,2), l_returnflag CHAR(1), l_linestatus CHAR(1), "
        "l_shipdate DATE, l_commitdate DATE, l_receiptdate DATE, "
        "l_shipmode CHAR(10))")
    s.execute(
        "CREATE TABLE orders (o_orderkey BIGINT PRIMARY KEY, "
        "o_custkey BIGINT, o_orderstatus CHAR(1), "
        "o_totalprice DECIMAL(15,2), o_orderdate DATE, "
        "o_orderpriority CHAR(1))")
    s.execute(
        "CREATE TABLE customer (c_custkey BIGINT PRIMARY KEY, "
        "c_name CHAR(18), c_nationkey BIGINT, c_acctbal DECIMAL(15,2), "
        "c_mktsegment CHAR(10), c_phone CHAR(15))")
    s.execute(
        "CREATE TABLE part (p_partkey BIGINT PRIMARY KEY, p_name CHAR(32), "
        "p_brand CHAR(10), p_type CHAR(16), p_size BIGINT, "
        "p_container CHAR(10))")
    s.execute(
        "CREATE TABLE supplier (s_suppkey BIGINT PRIMARY KEY, "
        "s_name CHAR(18), s_nationkey BIGINT, s_acctbal DECIMAL(15,2))")
    s.execute(
        "CREATE TABLE partsupp (ps_partkey BIGINT, ps_suppkey BIGINT, "
        "ps_availqty BIGINT, ps_supplycost DECIMAL(15,2))")
    s.execute(
        "CREATE TABLE nation (n_nationkey BIGINT PRIMARY KEY, "
        "n_name CHAR(16), n_regionkey BIGINT)")
    s.execute(
        "CREATE TABLE region (r_regionkey BIGINT PRIMARY KEY, "
        "r_name CHAR(12))")

    def append(table: str, arrays) -> None:
        info = eng.catalog.info_schema.table(table)
        fts = [c.ftype for c in info.columns]
        chunk = Chunk([Column(ft, a, None) for ft, a in zip(fts, arrays)])
        txn = eng.store.begin()
        txn.append(info.id, chunk)
        txn.commit()

    def pick(options, count):
        arr = np.array(options, dtype=object)
        return arr[rng.integers(0, len(arr), count)]

    # dates as day numbers, 1992-01-01..1998-12-01 ≈ 8036..10560
    ship = rng.integers(8036, 10560, n).astype(np.int32)
    ship.sort()      # shipdate-clustered storage, as in TPC-H loads
    commit = ship + rng.integers(-10, 40, n).astype(np.int32)
    receipt = commit + rng.integers(-5, 30, n).astype(np.int32)
    append("lineitem", [
        rng.integers(0, n_ord, n).astype(np.int64),
        rng.integers(0, n_part, n).astype(np.int64),
        rng.integers(0, n_supp, n).astype(np.int64),
        rng.integers(100, 5001, n).astype(np.int64),
        rng.integers(90_000, 10_500_001, n).astype(np.int64),
        rng.integers(0, 11, n).astype(np.int64),
        rng.integers(0, 9, n).astype(np.int64),
        pick(["A", "N", "R"], n), pick(["F", "O"], n),
        ship, commit, receipt,
        pick(["MAIL", "SHIP", "AIR", "TRUCK", "RAIL"], n)])
    append("orders", [
        np.arange(n_ord, dtype=np.int64),
        rng.integers(0, n_cust, n_ord).astype(np.int64),
        pick(["F", "O", "P"], n_ord),
        rng.integers(1_000, 50_000_000, n_ord).astype(np.int64),
        rng.integers(8036, 10560, n_ord).astype(np.int32),
        pick(["1", "2", "3", "4", "5"], n_ord)])
    append("customer", [
        np.arange(n_cust, dtype=np.int64),
        np.array([f"Customer#{i:09d}" for i in range(n_cust)],
                 dtype=object),
        rng.integers(0, 25, n_cust).astype(np.int64),
        rng.integers(-99_999, 999_999, n_cust).astype(np.int64),
        pick(["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY",
              "HOUSEHOLD"], n_cust),
        np.array([f"{c}-{i % 900 + 100}-{i % 9000 + 1000}"
                  for i, c in enumerate(
                      rng.integers(10, 35, n_cust))], dtype=object)])
    adjectives = ["green", "blue", "red", "ivory", "forest", "navy",
                  "plum", "puff"]
    nouns = ["almond", "steel", "linen", "cream", "misty", "tomato"]
    append("part", [
        np.arange(n_part, dtype=np.int64),
        np.array([f"{adjectives[i % 8]} {nouns[i % 6]} part{i}"
                  for i in range(n_part)], dtype=object),
        np.array([f"Brand#{i % 5 + 1}{i % 5 + 1}" for i in range(n_part)],
                 dtype=object),
        pick(["PROMO BOX", "PROMO CASE", "STANDARD TIN", "SMALL PLATED",
              "MEDIUM BAG"], n_part),
        rng.integers(1, 50, n_part).astype(np.int64),
        pick(["SM CASE", "MED BOX", "LG BOX", "JUMBO JAR", "WRAP BAG"],
             n_part)])
    append("supplier", [
        np.arange(n_supp, dtype=np.int64),
        np.array([f"Supplier#{i:09d}" for i in range(n_supp)],
                 dtype=object),
        rng.integers(0, 25, n_supp).astype(np.int64),
        rng.integers(-99_999, 999_999, n_supp).astype(np.int64)])
    append("partsupp", [
        rng.integers(0, n_part, n_ps).astype(np.int64),
        rng.integers(0, n_supp, n_ps).astype(np.int64),
        rng.integers(1, 10_000, n_ps).astype(np.int64),
        rng.integers(100, 100_000, n_ps).astype(np.int64)])
    nations = ["ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT",
               "ETHIOPIA", "FRANCE", "GERMANY", "INDIA", "INDONESIA",
               "IRAN", "IRAQ", "JAPAN", "JORDAN", "KENYA", "MOROCCO",
               "MOZAMBIQUE", "PERU", "CHINA", "ROMANIA", "SAUDI ARABIA",
               "VIETNAM", "RUSSIA", "UNITED KINGDOM", "UNITED STATES"]
    append("nation", [
        np.arange(25, dtype=np.int64),
        np.array(nations, dtype=object),
        (np.arange(25, dtype=np.int64) % 5)])
    append("region", [
        np.arange(5, dtype=np.int64),
        np.array(["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"],
                 dtype=object)])
    for t in ("lineitem", "orders", "customer", "part", "supplier",
              "partsupp", "nation", "region"):
        s.execute(f"ANALYZE TABLE {t}")


# ---------------------------------------------------------------------------
# Sweep
# ---------------------------------------------------------------------------

def _fragments(root) -> list:
    out = []

    def walk(e):
        if isinstance(e, TpuFragmentExec):
            out.append(e)
        for c in getattr(e, "children", []):
            walk(c)

    walk(root)
    return out


def run_one(s, name: str, time_cpu: bool = True) -> dict:
    """Run one coverage query (device on, forced threshold) and report
    fused status, fallback code, warm launches-per-slab, and speedup."""
    from tidb_tpu.parser import parse

    sql = QUERIES[name]
    cpu_s = None
    if time_cpu:
        s.vars["tidb_tpu_engine"] = "off"
        t0 = time.perf_counter()
        s.query(sql)
        cpu_s = time.perf_counter() - t0
    s.vars["tidb_tpu_engine"] = "on"
    s.vars["tidb_tpu_row_threshold"] = 1
    try:
        plan = s._plan(parse(sql)[0])
        root = build(plan)
        run_to_completion(root, s._exec_ctx())     # cold: compile + upload
        frags = _fragments(root)
        fused = bool(frags) and all(f.used_device for f in frags)
        fallback = None
        for f in frags:
            if not f.used_device:
                fallback = getattr(f, "fallback_code", None) or "device-error"
                break
        if not frags:
            fallback = "shape"
        t0 = time.perf_counter()
        s.query(sql)                               # warm, for launch count
        dev_s = time.perf_counter() - t0
        ph = s.last_guard.phases if s.last_guard is not None else None
        launches = getattr(ph, "programs_launched", 0) if ph else 0
        # slab count: fused-pipeline launches when the pipeline ran,
        # else partial launches (everything but the one merge/finalize) —
        # the slabs+1 model reads as programs_per_slab → 1.0 at scale
        fused_l = getattr(ph, "fused_pipelines", 0) if ph else 0
        slabs = max(fused_l or launches - 1, 1)
        pps = round(launches / slabs, 2) if launches else None
    finally:
        s.vars["tidb_tpu_engine"] = "off"
        s.vars.pop("tidb_tpu_row_threshold", None)
    return {
        "query": name,
        "fused": fused,
        "n_fragments": len(frags),
        "fallback": fallback,
        "launches": launches,
        "programs_per_slab": pps,
        "device_s": round(dev_s, 4),
        "cpu_s": round(cpu_s, 4) if cpu_s is not None else None,
        "speedup": round(cpu_s / dev_s, 2)
        if cpu_s is not None and dev_s > 0 else None,
    }


def run_coverage(s, time_cpu: bool = True,
                 queries: Optional[List[str]] = None) -> List[dict]:
    rows = []
    for name in queries or sorted(QUERIES, key=lambda q: int(q[1:])):
        rows.append(run_one(s, name, time_cpu=time_cpu))
    return rows


def coverage_table(rows: List[dict]) -> str:
    """Render the per-query table (`python -m tidb_tpu.tools.coverage`)."""
    hdr = (f"{'query':<6}{'fused':<7}{'frags':<7}{'fallback':<15}"
           f"{'prog/slab':<11}{'speedup':<8}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r['query']:<6}{str(r['fused']):<7}{r['n_fragments']:<7}"
            f"{str(r['fallback'] or '-'):<15}"
            f"{str(r['programs_per_slab'] or '-'):<11}"
            f"{str(r['speedup'] or '-'):<8}")
    fused = sum(1 for r in rows if r["fused"])
    lines.append(f"fused: {fused}/{len(rows)}")
    return "\n".join(lines)


def fresh_session(n_lineitem: int = 6000):
    from tidb_tpu.session import Engine
    eng = Engine()
    eng.global_vars["tidb_enable_auto_analyze"] = False
    s = eng.new_session()
    build_schema(s, n_lineitem)
    return eng, s
