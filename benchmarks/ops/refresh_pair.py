"""Operation kind `refresh_pair`: one pair of TPC-H's refresh functions cut
to one transaction each, every transaction read back at once.

    {"kind": "refresh_pair", "orders": 150, "reads": ["Q1", "Q3", "Q6"]}

One operation, numbered n from 0 through first touch, warm cycles and window
alike, is, on one connection:

    BEGIN · INSERT INTO orders VALUES (150 rows) ·
    INSERT INTO lineitem VALUES (600 rows) · COMMIT      → Q1, Q3, Q6
    BEGIN · DELETE FROM lineitem WHERE l_orderkey in [a, b) ·
    DELETE FROM orders WHERE o_orderkey in [a, b) · COMMIT → Q1, Q3, Q6

It is correct only if all six answers are text-equal to the reference's in
the state after RF1 n, respectively after RF2 n (the freshness and the
atomicity the configuration guarantees: a read sent after COMMIT's
acknowledgement sees both tables' rows, and nothing of a later
transaction), and both transactions reported the row counts written. So
the tables MOVE through the run, as the specification's do.

The rows of refresh set n come from the data set (`refresh_set`, seeded
from (--seed, n)); the statements are rendered when the operation runs,
which is the client's work and inside the operation's time as it would be
in an order feed. `statements` lists the three reads: the harness warms
and reads the ledger of every listed statement, and asks each for
`engine = tpu`.

Beside each answer the operation keeps two of the program's always-on
counters (`tidb_tpu_delta_declines_total`, `tidb_tpu_compactions_total`,
read in-process as `meters.pool_stats` reads the scheduler's): the
per-layer readers `rebuilds_per_op` and `compact_ms_per_op` take the
window's deltas from `refresh_counters.SAMPLES`.
"""

from __future__ import annotations

import refresh_counters


def bind(spec: dict, dataset, rng) -> dict:
    cur = dataset.CURRENT
    reads = list(spec["reads"])
    return {"name": "refresh_pair", "orders": int(spec["orders"]),
            "reads": reads, "dataset": dataset, "data": cur["data"],
            "seed": cur["seed"], "next": [0],
            "statements": {q: dataset.STATEMENTS[q] for q in reads}}


def _transaction(client, statements) -> list:
    """Send one transaction → the affected-row count of each of its DML
    statements."""
    counts = []
    for sql in statements:
        got = client.execute(sql)
        if sql not in ("BEGIN", "COMMIT"):
            counts.append(int(got))
    return counts


def run(client, op: dict):
    n = op["next"][0]
    op["next"][0] = n + 1
    ds = op["dataset"]
    sql = ds.refresh_sql(ds.refresh_set(op["data"], op["seed"], n,
                                        op["orders"]))
    answer = {"n": n}
    for which in ("rf1", "rf2"):
        answer[which + "_rows"] = _transaction(client, sql[which])
        answer[which] = {q: client.query(op["statements"][q])[1]
                         for q in op["reads"]}
    refresh_counters.sample()
    return answer


def check(op: dict, answer, reference) -> bool:
    reference = reference[op["dataset"].STATE]
    n, k = answer["n"], op["orders"]
    per_order = op["dataset"].LINEITEMS_PER_ORDER
    if answer["rf1_rows"] != [k, k * per_order]:
        return False
    if answer["rf2_rows"] != [reference.deleted_rows(n), k]:
        return False
    for which in ("rf1", "rf2"):
        want = reference.after(n, which)
        for q in op["reads"]:
            if [tuple(r) for r in answer[which][q]] != \
                    [tuple(r) for r in want[q]]:
                return False
    return True
