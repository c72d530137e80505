"""The traced window's requests split by the role that sent them, for the
readers of the throughput cell (`layer_metrics/stream_stmt_ms`,
`refresh_txn_ms`, `commit_wait_ms_per_txn`), as `point_spans.py` splits the
HTAP cell's.

  stream statements   requests whose `stmt` root ran a span of lane `frag`
                      (a device fragment): the query streams' reads;
  transactions        the refresh stream's requests — roots whose `sql` tag
                      starts with BEGIN, INSERT, DELETE or COMMIT and that
                      ran no fragment. A transaction is four requests; the
                      COMMIT roots are counted, and the four roots' time is
                      reckoned per COMMIT;
  commit gate         `commit.gate` spans (lane `lock`, `wait=lock`): a
                      COMMIT's contended acquire of the store's lock.

`window(ctx)` → {"statements", "stmt_s", "txns", "txn_s", "gate_s",
"gates"}, reduced once and printed once a run as a `stream_spans` line with
the `roles` line of `point_roles` (per-role operations and percentiles on
the client's clock) after it; None on a program that records no `stmt`
root.
"""

from __future__ import annotations

import json

import point_roles
import span_events
import span_reduce

WRITES = ("BEGIN", "INSERT", "DELETE", "COMMIT")


def reduce(events) -> dict | None:
    args = lambda e: e.get("args") or {}  # noqa: E731
    on_device = {args(e).get("req", 0) for e in events
                 if e.get("cat") == span_reduce.FRAGMENT_LANE}
    roots = [e for e in events if e.get("cat") == span_reduce.ROOT_LANE]
    if not roots:
        return None
    reads = [e for e in roots if args(e).get("req", 0) in on_device]
    writes = [e for e in roots if args(e).get("req", 0) not in on_device
              and str(args(e).get("sql", "")).lstrip().upper()
              .startswith(WRITES)]
    gates = [e for e in events if e.get("name") == "commit.gate"]
    dur = lambda es: sum(e.get("dur", 0.0) for e in es) * 1e-6  # noqa: E731
    return {"statements": len(reads), "stmt_s": dur(reads),
            "txns": sum(str(args(e).get("sql", "")).lstrip().upper()
                        .startswith("COMMIT") for e in writes),
            "txn_s": dur(writes), "gates": len(gates),
            "gate_s": dur(gates)}


def window(ctx):
    if "_stream_spans" in ctx:
        return ctx["_stream_spans"]
    got = None
    try:
        got = reduce(span_events.events(ctx))
    except Exception as e:  # noqa: BLE001 — a reader never sinks the run
        print(json.dumps({"phase": "stream_spans", "error": repr(e)}),
              flush=True)
    if got is not None:
        print(json.dumps({
            "phase": "stream_spans", **got,
            "stream_stmt_ms": (got["stmt_s"] / got["statements"] * 1e3
                               if got["statements"] else None),
            "refresh_txn_ms": (got["txn_s"] / got["txns"] * 1e3
                               if got["txns"] else None)}), flush=True)
        point_roles.window(ctx)
    ctx["_stream_spans"] = got
    return got
