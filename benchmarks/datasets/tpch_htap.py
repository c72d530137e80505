"""The HTAP deployment's data set: the TPC-H-shaped tables an analyst scans
(Q1) while an order-status service reads single orders by primary key.

The data is `tpch_shaped`'s, made by that module loaded BY PATH (the same
seed gives the same bytes as `tpch-shaped-*`), and the scan is its Q1. This
module adds

    POINT_STATEMENT            the prepared point read, one `?` for the key
    reference(data, arithmetic) -> Q1's exact rows and, under `POINT`, a
                                   `PointLookup`: the row of any order key
                                   by a plain position lookup in the raw
                                   generated columns, rendered as the
                                   program's client returns a binary row
    load(engine, data)         -> `tpch_shaped.load`, after a PROBE that
                                   raises on a program that cannot run this
                                   deployment (below)

The statement is sysbench's `oltp_point_select` (`SELECT c FROM sbtest WHERE
id = ?`, prepared once and executed through the binary protocol) on the
TPC-H schema: three columns of one `orders` row by its primary key.

The reference imports nothing of the program and takes nothing it made: a
key's position is found by binary search in the generated key column (which
is sorted: keys are dense in row order), never through the engine's index.
`arithmetic="float64"` is the control of Q1's sums (`benchmarks/control.py`);
a point row holds no arithmetic, so the lookup is the same in both.

The probe. A program whose planner answers the point statement by a table
scan would read 12M rows a point read, seven connections wide, and one whose
client cannot prepare cannot send the source's protocol at all. `load`
therefore asks, BEFORE the bulk load and on the empty tables of the schema,
`EXPLAIN` for the plan of the point statement and the program's client for
`prepare` / `execute_prepared`, and raises unless the plan is an index read
on `PRIMARY` with the key as its one range: such a program fails in seconds.
"""

from __future__ import annotations

import datetime
import importlib.util
from pathlib import Path

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "benchmarks_datasets_tpch_shaped_base",
    Path(__file__).resolve().parent / "tpch_shaped.py")
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

SCAN = "Q1"
POINT = "point"
POINT_COLUMNS = ("o_orderdate", "o_orderpriority", "o_custkey")
POINT_STATEMENT = (f"SELECT {', '.join(POINT_COLUMNS)} FROM orders "
                   "WHERE o_orderkey = ?")

SCHEMA = base.SCHEMA
# only what runs on the device: the harness asks `engine = tpu` of every
# listed statement and reckons `scan_hbm_share` from them
STATEMENTS = {SCAN: base.STATEMENTS[SCAN]}
COLUMNS = {SCAN: base.COLUMNS[SCAN]}
PRUNED_TABLE = base.PRUNED_TABLE

# the run's data, kept for the operation kind: the harness hands `bind`
# this module, not what `generate` returned
CURRENT: dict = {}


def generate(scale: float, seed: int) -> dict:
    data = base.generate(scale, seed)
    CURRENT.clear()
    CURRENT.update(data=data, seed=seed)
    return data


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

_EPOCH = datetime.date(1970, 1, 1)


class PointLookup:
    """`row(key)` → the rows the point statement must answer for `key`:
    one tuple of (order date as ISO text, priority as text, customer key
    as int), or none for a key no order has."""

    def __init__(self, orders: dict):
        self.orders = orders
        self.keys = orders["o_orderkey"]

    # it rides in the reference's dict beside Q1's rows and is none: two
    # references differ by their answers alone
    def __eq__(self, other):
        return isinstance(other, PointLookup)

    __hash__ = None

    def row(self, key: int) -> list:
        pos = int(np.searchsorted(self.keys, key))
        if pos >= len(self.keys) or int(self.keys[pos]) != int(key):
            return []
        o = self.orders
        day = _EPOCH + datetime.timedelta(days=int(o["o_orderdate"][pos]))
        return [(day.isoformat(),
                 base.PRIORITIES[int(o["o_orderpriority"][pos])],
                 int(o["o_custkey"][pos]))]


def reference(data: dict, arithmetic: str = "exact") -> dict:
    """→ {"Q1": the rows the wire must carry, in order; "point": the
    lookup `check` asks for the row of the key an answer carries}."""
    return {SCAN: base.Reference(data, arithmetic).q1(),
            POINT: PointLookup(data["orders"])}


# ---------------------------------------------------------------------------
# load, behind the probe
# ---------------------------------------------------------------------------

def require_point_path(eng) -> dict:
    """Can this program run the deployment at all? The schema's tables
    must exist (empty is enough). Raises; → what was read."""
    from tidb_tpu.client import Client
    missing = [m for m in ("prepare", "execute_prepared")
               if not hasattr(Client, m)]
    if missing:
        raise RuntimeError(
            f"tpch_htap: tidb_tpu.client.Client has no {missing}: the "
            "deployment's point reads are prepared statements executed "
            "through the binary protocol (sysbench oltp_point_select), "
            "and this program's client cannot send them")
    s = eng.new_session()
    plan = [tuple(str(v) for v in r) for r in s.execute(
        "EXPLAIN " + POINT_STATEMENT.replace("?", "7"))[0].rows]
    reads = [r for r in plan if "IndexScan" in r[0]]
    if len(reads) != 1 or "index:PRIMARY" not in reads[0][-1] \
            or "ranges:[[7,7]]" not in reads[0][-1]:
        raise RuntimeError(
            "tpch_htap: the point statement does not plan as ONE index "
            f"read on PRIMARY with the key as its range: EXPLAIN gave "
            f"{plan}; every point read would scan the table")
    return {"probe": {"plan": plan, "client": "prepare, execute_prepared"}}


def load(eng, data: dict) -> None:
    """PROBE on the empty tables of the schema (in an engine of its own,
    thrown away), then load as `tpch_shaped.load` does."""
    from tidb_tpu.session import Engine

    probe = Engine()
    try:
        s = probe.new_session()
        for ddl in SCHEMA:
            s.execute(ddl)
        require_point_path(probe)
    finally:
        probe.close()
    base.load(eng, data)
