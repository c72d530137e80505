"""TPC-H's throughput test over the TPC-H-shaped data: S query streams, each
on a connection of its own, with ONE refresh stream running RF1/RF2 pairs
beside them (clause 5.3.4; the refresh stream 5.3.7; RF1/RF2 2.5-2.7).

The data, the refresh sets, the statements and the incremental exact
reference are `tpch_refresh`'s, made by that module loaded BY PATH (the same
seed gives the same bytes as `tpch-refresh-*`). This module adds

    at(state, s)         -> the three answers in committed state s: after s
                            refresh transactions (0 = as loaded, 2n+1 =
                            after RF1 n, 2n+2 = after RF2 n)
    reference(data, ...) -> `tpch_refresh.reference`: the answers as loaded
                            and, under `STATE`, the `RefreshState`
    load(engine, data)   -> `tpch_refresh`'s extension probe, then the
                            OLDER-SNAPSHOT probe (below), then its bulk load

With a writer beside the readers an answer has a SET of correct values: a
statement sent after the acknowledgement of transaction lo - 1 and answered
after the COMMIT of transaction hi - 1 was sent may have read any state lo
<= s <= hi — but ONE of them, whole (`ops/throughput_streams.py` holds the
comparison). The reference gives the state; it imports nothing of the
program and takes nothing it made.

The older-snapshot probe. In this deployment a statement can reach the
device cache one commit BEHIND it: it took its snapshot, the refresher's
COMMIT was acknowledged, another stream's statement extended the cached
table, and only then does it open the table. A program whose cache answers
that by rebuilding the table inside the statement (about a minute for
lineitem at SF=4) spends the run's time limit in rebuilds. `load` therefore
asks, BEFORE the bulk load and on a table of the same schema and
`PROBE_ROWS` lineitems, exactly that read through SQL — Q6, one RF1
transaction, Q6 (the cache moves on), then Q6 `AS OF TIMESTAMP` a moment
before the transaction — and raises unless the older read got the older
state's exact rows from a generation the cache KEPT: the program's
`tidb_tpu_delta_generation_reads_total{age=kept}` moved, `{age=rebuilt}` and
`tidb_tpu_delta_declines_total` did not, and the plain Q6 after it extended
nothing again (the newest generation was still installed). Such a program
fails in seconds.
"""

from __future__ import annotations

import datetime
import importlib.util
import time
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "benchmarks_datasets_tpch_refresh_base",
    Path(__file__).resolve().parent / "tpch_refresh.py")
rf = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(rf)

ORDERS_PER_TRANSACTION = rf.ORDERS_PER_TRANSACTION
LINEITEMS_PER_ORDER = rf.LINEITEMS_PER_ORDER
READS = rf.READS
PROBE_ROWS = rf.PROBE_ROWS
SCHEMA = rf.SCHEMA
STATEMENTS = rf.STATEMENTS
COLUMNS = rf.COLUMNS
PRUNED_TABLE = rf.PRUNED_TABLE
STATE = rf.STATE
RefreshState = rf.RefreshState
refresh_set = rf.refresh_set
refresh_sql = rf.refresh_sql

# the run's data and seed, kept for the operation kind (`tpch_refresh`'s
# own: its `reference` reads the seed there)
CURRENT = rf.CURRENT
generate = rf.generate
reference = rf.reference


def at(state, s: int) -> dict:
    """The reads' exact rows once `s` refresh transactions are committed:
    RF1 0, RF2 0, RF1 1, ... in the refresh stream's order."""
    if s <= 0:
        return state.base
    return state.after((s - 1) // 2, "rf1" if s % 2 else "rf2")


def which(t: int) -> tuple:
    """Transaction number t (0-based) of the refresh stream → (its refresh
    pair n, "rf1" | "rf2")."""
    return t // 2, "rf1" if t % 2 == 0 else "rf2"


# ---------------------------------------------------------------------------
# load, behind the probes
# ---------------------------------------------------------------------------

_READS_BY_AGE = "tidb_tpu_delta_generation_reads_total"


def _total(counter: dict, label: str = "") -> float:
    return sum(v for k, v in counter.items() if label in k)


def require_older_snapshot(eng, seed: int = 0) -> dict:
    """Can this program answer a statement whose snapshot is one commit
    behind the device cache without rebuilding the table? Raises; → what
    was read."""
    small = rf.base.generate(PROBE_ROWS / rf.base.LINEITEM_ROWS_SF1, seed)
    suffix = "_older"
    rf._bulk_load(eng, small, suffix)
    s = eng.new_session()
    s.execute("SET tidb_tpu_engine = 'on'")
    s.execute("SET tidb_tpu_row_threshold = 1")
    q6 = STATEMENTS["Q6"].replace("lineitem", "lineitem" + suffix)
    ref = RefreshState(small, seed)

    def rows(sql):
        return [tuple(str(v) for v in r) for r in s.execute(sql)[0].rows]

    def counters():
        return {m: rf._counter(s, m) for m in (
            _READS_BY_AGE, "tidb_tpu_delta_declines_total",
            "tidb_tpu_delta_extensions_total")}

    try:
        got = [rows(q6)]
        # a wall-clock instant strictly between the load's commits and the
        # transaction's: the store's history is kept by wall time
        time.sleep(0.02)
        before = datetime.datetime.fromtimestamp(time.time()).isoformat(
            sep=" ")
        time.sleep(0.02)
        for stmt in refresh_sql(refresh_set(small, seed, 0))["rf1"]:
            s.execute(stmt.replace("orders", "orders" + suffix)
                      .replace("lineitem", "lineitem" + suffix))
        got.append(rows(q6))            # the cache moves on
        c0 = counters()
        got.append(rows(q6.replace(
            "FROM lineitem" + suffix,
            f"FROM lineitem{suffix} AS OF TIMESTAMP '{before}'")))
        c1 = counters()
        got.append(rows(q6))            # the newest is still installed
        c2 = counters()
        want = [ref.base["Q6"], ref.after(0, "rf1")["Q6"], ref.base["Q6"],
                ref.after(0, "rf1")["Q6"]]
        if got != [[tuple(r) for r in w] for w in want]:
            raise RuntimeError(f"tpch_throughput: the probe's Q6 answered "
                               f"{got}, the reference {want}")
        kept = _total(c1[_READS_BY_AGE], "age=kept") \
            - _total(c0[_READS_BY_AGE], "age=kept")
        rebuilt = _total(c1[_READS_BY_AGE], "age=rebuilt") \
            - _total(c0[_READS_BY_AGE], "age=rebuilt")
        declined = {g: v - c0["tidb_tpu_delta_declines_total"].get(g, 0.0)
                    for g, v in c2["tidb_tpu_delta_declines_total"].items()
                    if v - c0["tidb_tpu_delta_declines_total"].get(g, 0.0)}
        again = _total(c2["tidb_tpu_delta_extensions_total"]) \
            - _total(c1["tidb_tpu_delta_extensions_total"])
        seen = {"kept_reads": kept, "rebuilt_reads": rebuilt,
                "declines": declined, "extensions_after": again}
        if kept < 1 or rebuilt or declined or again:
            raise RuntimeError(
                "tpch_throughput: a read one commit BEHIND the device "
                "cache's newest generation was not served from a "
                f"generation the cache kept ({seen}; a program without "
                f"`{_READS_BY_AGE}` reads 0 kept): every stream statement "
                "that a commit and another stream's read overtake would "
                "rebuild its table inside the statement and take the "
                "newest generation's place, and a run would spend its "
                "time limit in rebuilds")
        return {"probe": seen}
    finally:
        for table in ("lineitem", "orders", "customer"):
            s.execute(f"DROP TABLE IF EXISTS {table}{suffix}")


def load(eng, data: dict) -> None:
    """Both probes, then bulk-append each table (one transaction a table)
    and ANALYZE it, as `tpch_refresh.load` does."""
    rf.require_extension(eng)
    require_older_snapshot(eng)
    rf._bulk_load(eng, data)
