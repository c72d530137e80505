"""Per-role latencies of a cell whose connections do different work
(`ops/point_beside_scan.py`): the kind appends (role, sent, done) on the
client's clock as each operation ends, and a traced run's readers print ONE
`roles` line — operations, p50 and p95 by role over the window — so that a
change which buys point reads by starving the scan, or the reverse, is read
beside the means. The harness hands a reader the window's size and no
per-connection record: the window's operations are the last
`ctx["attempted"]` samples (every warm-up operation has ended before the
window's first is sent).
"""

from __future__ import annotations

import json

import stats

SAMPLES: list = []          # (role, sent, done), `time.perf_counter` seconds


def sample(role: str, sent: float, done: float) -> None:
    SAMPLES.append((role, sent, done))


def by_role(samples) -> dict:
    out = {}
    for role in sorted({r for r, _s, _d in samples}):
        ms = [(d - s) * 1e3 for r, s, d in samples if r == role]
        out[role] = {"operations": len(ms),
                     "p50_ms": stats.percentile(ms, 50),
                     "p95_ms": stats.percentile(ms, 95)}
    return out


def window(ctx) -> dict | None:
    """The window's operations by role, reduced and printed once a run."""
    if "_roles" not in ctx:
        n = ctx.get("attempted") or 0
        got = by_role(SAMPLES[-n:]) if 0 < n <= len(SAMPLES) else None
        if got is not None:
            print(json.dumps({"phase": "roles", **got}), flush=True)
        ctx["_roles"] = got
    return ctx["_roles"]
