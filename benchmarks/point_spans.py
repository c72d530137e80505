"""The traced window's requests split by what they were, for the readers of
a cell whose connections do different work (`layer_metrics/point_stmt_ms`,
`index_ms_per_point`, `plan_miss_share`, `point_parse_ms`, `scan_stmt_ms`).

`span_reduce.window` keeps the requests that ran a device fragment — here
the scanner's — and divides by them. A point read runs none. This module
takes the other side from the same events (`span_events.events`):

  point reads   requests whose `stmt` root is tagged `class=interactive`
                (what `session._classify_admission` decided) and that ran
                no span of lane `frag`;
  scans         requests that ran one.

`window(ctx)` → {"points", "point_stmt_s", "point_self_s" {lane: self
seconds}, "point_misses", "scans", "scan_stmt_s"}, reduced once and printed
once a run as a `point_spans` line (self ms per point read by lane and by
span), with the `roles` line of `point_roles` after it. None on a program
whose `stmt` roots carry no `class` tag: every reader built on this then
finds nothing to read.
"""

from __future__ import annotations

import json

import point_roles
import span_events
import span_reduce


def reduce(events) -> dict | None:
    args = lambda e: e.get("args") or {}  # noqa: E731
    on_device = {args(e).get("req", 0) for e in events
                 if e.get("cat") == span_reduce.FRAGMENT_LANE}
    roots = [e for e in events if e.get("cat") == span_reduce.ROOT_LANE]
    if not any("class" in args(e) for e in roots):
        return None
    points = {args(e)["req"]: e for e in roots
              if args(e).get("class") == "interactive"
              and args(e).get("req", 0) not in on_device}
    scans = [e for e in roots if args(e).get("req", 0) in on_device]
    self_s: dict = {}
    by_name: dict = {}
    misses = 0
    for e, s in span_reduce.self_times(
            [e for e in events if args(e).get("req", 0) in points]):
        self_s[e["cat"]] = self_s.get(e["cat"], 0.0) + s
        k = f"{e['cat']}/{e['name']}"
        by_name[k] = by_name.get(k, 0.0) + s
        misses += e["name"] == "planner.optimize" \
            and args(e).get("cache") == "miss"
    return {"points": len(points),
            "point_stmt_s": sum(e.get("dur", 0.0)
                                for e in points.values()) * 1e-6,
            "point_self_s": self_s, "point_self_s_by_name": by_name,
            "point_misses": misses, "scans": len(scans),
            "scan_stmt_s": sum(e.get("dur", 0.0) for e in scans) * 1e-6}


def window(ctx):
    if "_point_spans" in ctx:
        return ctx["_point_spans"]
    got = None
    try:
        got = reduce(span_events.events(ctx))
    except Exception as e:  # noqa: BLE001 — a reader never sinks the run
        print(json.dumps({"phase": "point_spans", "error": repr(e)}),
              flush=True)
    if got is not None:
        n = got["points"] or 1
        print(json.dumps({
            "phase": "point_spans", "point_reads": got["points"],
            "scans": got["scans"],
            "plan_cache_misses": got["point_misses"],
            "point_stmt_ms": got["point_stmt_s"] / n * 1e3,
            "scan_stmt_ms": (got["scan_stmt_s"] / got["scans"] * 1e3
                             if got["scans"] else None),
            "self_ms_per_point_by_lane": {
                k: v / n * 1e3 for k, v in sorted(
                    got["point_self_s"].items())},
            "self_ms_per_point_by_name": {
                k: v / n * 1e3 for k, v in sorted(
                    got["point_self_s_by_name"].items(),
                    key=lambda kv: -kv[1])[:16]}}), flush=True)
        point_roles.window(ctx)
    ctx["_point_spans"] = got
    return got


def per_point(ctx, value):
    """`value(got)` ÷ the window's point reads; None without any."""
    got = window(ctx)
    if got is None or not got["points"]:
        return None
    return value(got) / got["points"]
