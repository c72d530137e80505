"""From the program's own spans (`tidb_tpu/util/timeline.py`) to self times
per statement. The benchmark's reduction: arithmetic on plain event dicts, so
that it can be checked on a synthetic nest (tests/test_span_reduce.py).

An event is a Chrome-trace "X" event as the timeline records it: `cat` (the
lane), `name`, `ts` and `dur` in microseconds, and under `args` the request
id `req`, the span's own `id` and its `parent` (the enclosing span on the
same thread; 0 for a root). Durations measured elsewhere (`timeline.record`)
have a `parent` and no `id`: they are leaves.

A span's *self time* is its duration less what its children cover (guide §4):
children may overlap or touch, so it is the duration less the union of their
intervals clipped to the span.

`window(ctx)` reads `timeline.last_events()` — every span between the switch
on, just before the window, and the stop after it — keeps the requests that
ran a device fragment (the operations; the benchmark's own ledger reads over
the admin connection run none), prints ONE `{"phase": "span_self", ...}`
line and hands the numbers to the readers in `layer_metrics/`. With a
program that has no such spans (no `last_events`, no `stmt` root) it returns
None and every reader that depends on it finds nothing to read.
"""

from __future__ import annotations

import json

import trace_reduce

ROOT_LANE = "stmt"          # one root span per request
FRAGMENT_LANE = "frag"      # a request that ran on the device has one
LAUNCH_LANE = "launch"      # one span per jitted call


def self_times(events) -> list:
    """→ [(event, self seconds)] for every "X" event. Children are found
    through `args.parent`; an event nobody names as parent is a leaf."""
    spans = [e for e in events if e.get("ph") == "X"]
    children: dict = {}
    for e in spans:
        up = (e.get("args") or {}).get("parent")
        if up:
            children.setdefault(up, []).append(e)
    out = []
    for e in spans:
        lo, hi = e["ts"], e["ts"] + e.get("dur", 0.0)
        kids = children.get((e.get("args") or {}).get("id"), ())
        covered = trace_reduce.total(trace_reduce.union(trace_reduce.clip(
            [(k["ts"], k["ts"] + k.get("dur", 0.0)) for k in kids], lo, hi)))
        out.append((e, max(hi - lo - covered, 0.0) * 1e-6))
    return out


def reduce(events) -> dict | None:
    """→ the operations' spans by lane: `ops` (root spans of requests that
    ran a device fragment), `launches`, `stmt_s` (their roots' durations
    summed), `self_s` {lane: self seconds}, `self_s_by_name` {lane/name:
    self seconds} without the launches, `programs` {program: launches and
    the statements (the root's `sql` tag) that launched it}, `spans` {lane:
    count}; `client_s` is the server's wait for the next command on the
    connections those requests came over. None when there is no such
    root."""
    spans = [e for e in events if e.get("ph") == "X"]
    req_of = lambda e: (e.get("args") or {}).get("req", 0)  # noqa: E731
    on_device = {req_of(e) for e in spans if e["cat"] == FRAGMENT_LANE}
    on_device.discard(0)
    roots = [e for e in spans
             if e["cat"] == ROOT_LANE and req_of(e) in on_device]
    if not roots:
        return None
    conns = {e["pid"] for e in roots}
    self_s: dict = {}
    count: dict = {}
    by_name: dict = {}
    sql_of = {req_of(e): (e.get("args") or {}).get("sql", "") for e in roots}
    programs: dict = {}
    for e, s in self_times(spans):
        if req_of(e) not in on_device:
            continue
        self_s[e["cat"]] = self_s.get(e["cat"], 0.0) + s
        count[e["cat"]] = count.get(e["cat"], 0) + 1
        if e["cat"] == LAUNCH_LANE:
            # which statement launches which program, and how often
            p = programs.setdefault(e["name"], {"launches": 0, "sql": set()})
            p["launches"] += 1
            p["sql"].add(sql_of.get(req_of(e), ""))
        else:
            k = f"{e['cat']}/{e['name']}"
            by_name[k] = by_name.get(k, 0.0) + s
    return {"ops": len(roots), "launches": count.get(LAUNCH_LANE, 0),
            "self_s_by_name": by_name,
            "programs": {k: {"launches": v["launches"],
                             "sql": sorted(v["sql"])}
                         for k, v in programs.items()},
            "stmt_s": sum(e.get("dur", 0.0) for e in roots) * 1e-6,
            "client_s": sum(e.get("dur", 0.0) for e in spans
                            if e["cat"] == "client"
                            and e["pid"] in conns) * 1e-6,
            "self_s": self_s, "spans": count}


def window(ctx) -> dict | None:
    """The traced run's spans, reduced once and printed once a run (kept
    on the run's own `ctx`, which every reader is handed)."""
    if "_span_self" in ctx:
        return ctx["_span_self"]
    got = None
    try:
        from tidb_tpu.util import timeline
        last = getattr(timeline, "last_events", None)
        got = reduce(last()) if last is not None else None
    except Exception as e:  # noqa: BLE001 — a reader never sinks the run
        print(json.dumps({"phase": "span_self", "error": repr(e)}),
              flush=True)
    if got is not None:
        n = got["ops"]
        print(json.dumps({
            "phase": "span_self", "ops": n, "launches": got["launches"],
            "stmt_ms_per_op": got["stmt_s"] / n * 1e3,
            "client_wait_ms_per_op": got["client_s"] / n * 1e3,
            "client_op_mean_ms": (sum(ctx["latencies_s"])
                                  / len(ctx["latencies_s"]) * 1e3
                                  if ctx.get("latencies_s") else None),
            "self_ms_per_op_by_lane": {
                k: v / n * 1e3 for k, v in sorted(got["self_s"].items())},
            "self_ms_per_op_by_name": {
                k: v / n * 1e3 for k, v in sorted(
                    got["self_s_by_name"].items(),
                    key=lambda kv: -kv[1])[:16]},
            "spans_by_lane": dict(sorted(got["spans"].items())),
            "programs": got["programs"]}),
            flush=True)
    ctx["_span_self"] = got
    return got


def lanes_ms(ctx, lanes, per: str = "ops"):
    """Self milliseconds of `lanes` per operation (or per launch)."""
    got = window(ctx)
    if got is None or not got[per]:
        return None
    return sum(got["self_s"].get(k, 0.0) for k in lanes) / got[per] * 1e3
