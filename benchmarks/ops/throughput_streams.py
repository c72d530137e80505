"""Operation kind `throughput_streams`: TPC-H's throughput test — query
streams and ONE refresh stream, each on a connection of its own. Which a
connection is depends on when the kind first sees it; a role, once pinned,
never changes.

    {"kind": "throughput_streams", "streams": 3, "orders": 150,
     "reads": ["Q1", "Q3", "Q6"]}

*Stream k* (the first `streams` connections seen; the harness's first touch
runs on `clients[0]` alone, so that is stream 0): the reads as COM_QUERY
text, back to back, ONE STATEMENT AN OPERATION, statement i of stream k
being `reads[(i + k) mod len(reads)]`. The run's very first call (first
touch) sends all the reads once, so that every table's first touch lies in
set-up and is compared.

*Refresher* (the next connection): the refresh stream RF1 0, RF2 0, RF1 1,
RF2 1, ... of `orders` orders + 4 lineitems each, ONE TRANSACTION AN
OPERATION (BEGIN, two DML statements, COMMIT), the rows of pair n from
(`--seed`, n) as `refresh_pair` draws them (`tpch_refresh.refresh_set`).
It runs back to back in the closed loop: a pacing wait would lie inside an
operation's latency.

A cold stream keeps step with the refresher. A stream statement takes a
seventh of a transaction's time, so in a warm-up counted in operations the
streams would be through their statements before the refresher's second
COMMIT, would never run the programs a read after RF2 needs, and the window
would open with the device cache ten commits behind. So a stream is COLD
until each of its reads has run `RUNS` times beside the refresher (a digest
compiles on its first and on its second execution, over a table with and
without delta rows and masks: four), and while cold its statement i is sent
only once the refresher's transaction i is acknowledged — by the `acked`
count every statement reads anyway (at most `PACE_TIMEOUT_S`; no transaction
by then: the refresher is gone and the stream stops waiting for good). Three
reads x `RUNS` = 12 statements a stream beside 12 transactions, which is why
the mix's `warmup_cycles` is 12; a warm stream never waits, and one still
cold when the window opens shows as operations a transaction long. (Waiting
for a transaction acknowledged since the stream's LAST statement, until each
read had run after both refresh functions, was read on the chip first: a
statement that loads programs spans several commits, the stream then needs
more commits than the refresher's twelve, and every run's warm-up ended in
the 60 s limit.)

Isolation, as the configuration guarantees it and `check` holds it. The
refresher counts its COMMITs twice, in the bound operation: `sent` just
before a COMMIT goes out, `acked` when its acknowledgement has come back. A
stream reads `acked` before it sends a statement (lo) and `sent` when the
answer has arrived (hi). The answer is correct only if its rows are
text-equal to the reference's rows in ONE committed state s — all three
tables' rows of s transactions and nothing of a later one (Q3 joins two
tables that every transaction writes: lineitem of state s with orders of
s - 1 equals no state's rows) — with lo <= s <= hi, and if s never
decreases along one connection (where a statement's rows fit several
states, the least one not below the connection's last is taken, which
decides existence). A transaction is correct if it reported the row counts
written. `check` reckons every connection's whole history once, in the
order it ran, on its first call: the harness judges the window's records
before the set-up's.

Beside each answer the kind keeps (role, sent, done) in
`point_roles.SAMPLES` for the per-role `roles` line, and samples the
program's always-on counters (`refresh_counters`, `generation_counters`)
for the readers that take the window's deltas.
"""

from __future__ import annotations

import threading
import time

import generation_counters
import point_roles
import refresh_counters

PACE_TIMEOUT_S = 60.0       # a cold stream's wait for the next transaction
RUNS = 4                    # executions of a read before its stream is warm


def bind(spec: dict, dataset, rng) -> dict:
    cur = dataset.CURRENT
    reads = list(spec["reads"])
    return {"name": "throughput_streams", "dataset": dataset,
            "data": cur["data"], "seed": cur["seed"],
            "streams": int(spec["streams"]), "orders": int(spec["orders"]),
            "reads": reads, "roles": {}, "lock": threading.Lock(),
            # the refresh stream's COMMITs, sent and acknowledged
            "sent": [0], "acked": [0],
            # connection → its answers in the order it ran them
            "history": {}, "verdicts": None,
            "statements": {q: dataset.STATEMENTS[q] for q in reads}}


def role_of(client, op: dict) -> dict:
    """The connection's pinned role, pinned on first sight: the first
    `streams` connections are streams 0, 1, ..., the next the refresher."""
    state = op["roles"].get(id(client))
    if state is None:
        with op["lock"]:
            n = len(op["roles"])
            role = "stream" if n < op["streams"] else "refresh"
            if n > op["streams"]:
                raise RuntimeError("throughput_streams: one refresh stream, "
                                   f"and this is connection {n + 1}")
            # `first`: the run's very first call sends every read once
            state = {"role": role, "stream": n, "first": n == 0, "i": 0,
                     # executions a cold stream's reads have yet to run
                     "cold": dict.fromkeys(op["reads"], RUNS)}
            op["roles"][id(client)] = state
            op["history"][n] = []
    return state


def _read(client, op: dict, name: str) -> dict:
    lo = op["acked"][0]
    rows = client.query(op["statements"][name])[1]
    return {"q": name, "rows": rows, "lo": lo, "hi": op["sent"][0]}


def _transaction(client, op: dict, t: int) -> dict:
    ds = op["dataset"]
    n, which = ds.which(t)
    counts = []
    for sql in ds.refresh_sql(ds.refresh_set(
            op["data"], op["seed"], n, op["orders"]))[which]:
        if sql == "COMMIT":
            op["sent"][0] = t + 1
        got = client.execute(sql)
        if sql not in ("BEGIN", "COMMIT"):
            counts.append(int(got))
    op["acked"][0] = t + 1
    return {"t": t, "counts": counts}


def _await_its_transaction(op: dict, state: dict, sent: float) -> float:
    """A cold stream's statement i waits for the refresher's transaction i
    → when the statement is sent."""
    limit = time.monotonic() + PACE_TIMEOUT_S
    while op["acked"][0] <= state["i"]:
        if time.monotonic() >= limit:
            state["cold"] = None
            return sent
        time.sleep(0.001)
    return time.perf_counter()


def _warmed(state: dict, name: str) -> None:
    """One execution less to run of the read `name`."""
    if state["cold"]:
        state["cold"][name] -= 1
        if all(n <= 0 for n in state["cold"].values()):
            state["cold"] = None


def run(client, op: dict):
    state = role_of(client, op)
    sent = time.perf_counter()
    answer = {"role": state["role"], "conn": state["stream"],
              "seq": len(op["history"][state["stream"]])}
    if state["role"] == "refresh":
        answer["txn"] = _transaction(client, op, state["i"])
        state["i"] += 1
    elif state["first"]:
        state["first"] = False
        answer["role"] = "first"
        answer["reads"] = [_read(client, op, q) for q in op["reads"]]
    else:
        if state["cold"]:
            sent = _await_its_transaction(op, state, sent)
        name = op["reads"][(state["i"] + state["stream"]) % len(op["reads"])]
        state["i"] += 1
        answer["reads"] = [_read(client, op, name)]
        _warmed(state, name)
    point_roles.sample(answer["role"], sent, time.perf_counter())
    refresh_counters.sample()
    generation_counters.sample()
    op["history"][state["stream"]].append(answer)
    return answer


def _as_rows(rows) -> list:
    return [tuple(r) for r in rows]


def states_of(op: dict, reference, read: dict) -> list:
    """The committed states within the read's [lo, hi] whose exact rows
    are the read's."""
    got = _as_rows(read["rows"])
    return [s for s in range(read["lo"], read["hi"] + 1)
            if got == _rows_at(op, reference, s)[read["q"]]]


def _rows_at(op: dict, reference, s: int) -> dict:
    """The reference's rows in state `s`, rendered once a state."""
    known = op.setdefault("rows_at", {})
    if s not in known:
        known[s] = {q: _as_rows(rows) for q, rows in
                    op["dataset"].at(reference, s).items()}
    return known[s]


def verdicts(op: dict, reference) -> dict:
    """(connection, sequence number) → is that answer correct, over every
    connection's whole history in the order it ran."""
    ds = op["dataset"]
    per_order = ds.LINEITEMS_PER_ORDER
    out = {}
    for conn, answers in op["history"].items():
        last = 0
        for a in answers:
            ok = True
            for read in a.get("reads", ()):
                fits = [s for s in states_of(op, reference, read)
                        if s >= last]
                if not fits:
                    ok = False
                    continue
                last = fits[0]
            if "txn" in a:
                n, which = ds.which(a["txn"]["t"])
                k = op["orders"]
                want = [k, k * per_order] if which == "rf1" else \
                    [reference.deleted_rows(n), k]
                ok = a["txn"]["counts"] == want
            out[(conn, a["seq"])] = ok
    return out


def check(op: dict, answer, reference) -> bool:
    if op["verdicts"] is None:
        op["verdicts"] = verdicts(op, reference[op["dataset"].STATE])
    return bool(op["verdicts"].get((answer["conn"], answer["seq"]), False))
