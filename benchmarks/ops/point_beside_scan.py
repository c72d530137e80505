"""Operation kind `point_beside_scan`: ONE statement an operation, and which
statement depends on the connection — the first connection the kind sees is
an analyst's report cycling, every other one an order-status service reading
single orders by primary key.

    {"kind": "point_beside_scan", "scanners": 1, "readers": 7, "zipf": 0.99}

*Scanner* (the first `scanners` connections seen: the harness's first touch
runs on `clients[0]` alone, so that is one of them): the data set's `SCAN`
statement (Q1) as COM_QUERY text, back to back. *Point reader* (the rest):
prepares the data set's `POINT_STATEMENT` once, then sends COM_STMT_EXECUTE
back to back through the program's client (sysbench `oltp_point_select`,
`--db-ps-mode=auto`). A role, once pinned to a connection, never changes.

Keys (YCSB core workload C: `requestdistribution=zipfian`, the generator's
`ZIPFIAN_CONSTANT` 0.99, scrambled): read n of stream c has key
`perm[rank]`, `rank` drawn Zipf(0.99) over [0, number of orders) by
inversion of the exact cumulative weights, `perm` ONE seeded permutation of
the order keys shared by every stream (YCSB scrambles by one hash, so all
clients share the hot keys while the hot keys lie all over the table), the
ranks from (`rng`, c): the same `--seed` gives the same streams, another
connection another stream. `bind` draws the first `BLOCK` keys of every
stream ahead; a stream that ran out would draw its next block (no window
gets there). Every drawn key exists: hits only.

The very first call of a run (first touch) is the scanner's: it sends Q1
and then prepares and sends ONE point read on the same connection, so that
the engine's index view of `orders` is built during set-up, inside
`setup_s`, and both statements' first answers are compared.

An answer carries what `check` needs: `{"role", "scan": rows or None,
"points": [(key, rows)]}`; the scan's rows are compared with the
reference's Q1 and each point answer with the row the reference's plain
lookup gives for its key. `statements` lists ONLY the scan: the harness asks
`engine = tpu` of every listed statement and reckons `scan_hbm_share` from
them, and the point statement is on the host by design.

Beside each answer the kind keeps (role, sent, done) on the client's clock
in `point_roles.SAMPLES`, for the per-role `roles` line of a traced run.
"""

from __future__ import annotations

import threading
import time

import numpy as np

import point_roles

BLOCK = 1 << 18             # keys drawn at a time for one stream


def zipf_cdf(n: int, s: float) -> np.ndarray:
    """Cumulative probability of ranks 0..n-1 under weight 1/(rank+1)^s."""
    w = np.arange(1, n + 1, dtype=np.float64) ** -s
    np.cumsum(w, out=w)
    w /= w[-1]
    return w


class KeyStreams:
    """The point readers' key streams: `next(c)` → the next key of stream
    c. Everything comes from the seed words handed in."""

    def __init__(self, keys: np.ndarray, s: float, base_seed: int):
        self.cdf = zipf_cdf(len(keys), s)
        self.perm = np.random.default_rng([base_seed, 0]).permutation(keys)
        self.base_seed = base_seed
        self._drawn: dict = {}      # stream → [block index, keys, cursor]

    def block(self, stream: int, index: int) -> np.ndarray:
        rng = np.random.default_rng([self.base_seed, 1, stream, index])
        ranks = np.searchsorted(self.cdf, rng.random(BLOCK), side="right")
        return self.perm[np.minimum(ranks, len(self.perm) - 1)]

    def ahead(self, stream: int) -> None:
        self._drawn[stream] = [0, self.block(stream, 0), 0]

    def next(self, stream: int) -> int:
        if stream not in self._drawn:
            self.ahead(stream)
        st = self._drawn[stream]
        if st[2] >= BLOCK:
            st[0] += 1
            st[1], st[2] = self.block(stream, st[0]), 0
        st[2] += 1
        return int(st[1][st[2] - 1])


def bind(spec: dict, dataset, rng) -> dict:
    keys = dataset.CURRENT["data"]["orders"]["o_orderkey"]
    streams = KeyStreams(keys, float(spec["zipf"]),
                         int(rng.integers(0, 1 << 62)))
    # stream 0 is the scanner's one first-touch read; 1.. the readers'
    for c in range(int(spec["scanners"]) + int(spec["readers"])):
        streams.ahead(c)
    return {"name": "point_beside_scan", "dataset": dataset,
            "scanners": int(spec["scanners"]), "streams": streams,
            "scan_sql": dataset.STATEMENTS[dataset.SCAN],
            "point_sql": dataset.POINT_STATEMENT,
            "roles": {}, "lock": threading.Lock(),
            "statements": {dataset.SCAN: dataset.STATEMENTS[dataset.SCAN]}}


def role_of(client, op: dict) -> dict:
    """The connection's pinned role, pinned on first sight: the first
    `scanners` connections scan, the rest read points, stream 1, 2, …"""
    state = op["roles"].get(id(client))
    if state is None:
        with op["lock"]:
            n = len(op["roles"])
            scans = n < op["scanners"]
            # `first`: the run's very first call also builds the index
            state = {"role": "scan" if scans else "point", "stream": n,
                     "first": n == 0, "stmt": None}
            op["roles"][id(client)] = state
    return state


def _point(client, op: dict, state: dict) -> tuple:
    if state["stmt"] is None:
        state["stmt"] = client.prepare(op["point_sql"])
    key = op["streams"].next(state["stream"])
    return key, client.execute_prepared(state["stmt"], [key])


def run(client, op: dict):
    state = role_of(client, op)
    sent = time.perf_counter()
    answer = {"role": state["role"], "scan": None, "points": []}
    if state["role"] == "scan":
        answer["scan"] = client.query(op["scan_sql"])[1]
        if state["first"]:
            state["first"] = False
            answer["points"].append(_point(client, op, state))
            answer["role"] = "first"
    else:
        answer["points"].append(_point(client, op, state))
    point_roles.sample(answer["role"], sent, time.perf_counter())
    return answer


def check(op: dict, answer, reference: dict) -> bool:
    ds = op["dataset"]
    if answer["scan"] is None and not answer["points"]:
        return False
    if answer["scan"] is not None and \
            [tuple(r) for r in answer["scan"]] != \
            [tuple(r) for r in reference[ds.SCAN]]:
        return False
    lookup = reference[ds.POINT]
    return all([tuple(r) for r in rows] == lookup.row(key)
               for key, rows in answer["points"])
