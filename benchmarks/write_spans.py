"""Self seconds of the write path's spans over the traced window, for the
readers in `layer_metrics/` (`write_ms_per_op`, `delta_extend_ms_per_op`,
`compact_ms_per_op`).

`span_reduce.window` keeps the requests that ran a device fragment: an
operation's reads. A refresh operation's INSERT, DELETE and COMMIT
statements run none, and the compactor runs under no request at all, so
their spans are summed here, over every event the recorder kept, and
divided by the window's OPERATIONS (`ctx["attempted"]`), not by its
device statements. `window(ctx)` → {"write_s", "delta_s", "compact_s",
"by_name": {lane/name: self seconds}}, reduced once and printed once a run
as a `write_spans` line; None on a program that records no span of lane
`write` or `delta`.
"""

from __future__ import annotations

import json

import span_events
import span_reduce


def window(ctx):
    if "_write_spans" in ctx:
        return ctx["_write_spans"]
    got = None
    events = span_events.events(ctx)
    if any(e.get("cat") in ("write", "delta") for e in events):
        by_name: dict = {}
        for e, s in span_reduce.self_times(events):
            if e.get("cat") in ("write", "delta"):
                k = f"{e['cat']}/{e['name']}"
                by_name[k] = by_name.get(k, 0.0) + s
        got = {
            "write_s": sum(s for k, s in by_name.items()
                           if k.startswith("write/")),
            "delta_s": sum(s for k, s in by_name.items()
                           if k.startswith("delta/delta.")),
            "compact_s": sum(s for k, s in by_name.items()
                             if k.startswith("delta/compact.")),
            "by_name": by_name}
        n = ctx.get("attempted") or 0
        print(json.dumps({
            "phase": "write_spans", "operations": n,
            "self_ms_per_operation_by_name": {
                k: v / n * 1e3 for k, v in sorted(
                    by_name.items(), key=lambda kv: -kv[1])} if n else {}}),
            flush=True)
    ctx["_write_spans"] = got
    return got


def ms_per_operation(ctx, key: str):
    got = window(ctx)
    n = ctx.get("attempted") or 0
    if got is None or not n:
        return None
    return got[key] / n * 1e3
