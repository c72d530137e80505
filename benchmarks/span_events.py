"""The traced window's raw spans (`tidb_tpu/util/timeline.py`), for the
readers that count tags rather than self times. `spans(ctx, lane, prefix)`
→ the "X" events of one lane whose name starts with `prefix`, read once a
run and kept on the run's `ctx`. A program without the recorder gives
none, and every reader built on this then finds nothing to read."""

from __future__ import annotations


def events(ctx) -> list:
    if "_span_events" not in ctx:
        got = []
        try:
            from tidb_tpu.util import timeline
            last = getattr(timeline, "last_events", None)
            got = [e for e in (last() if last is not None else ())
                   if e.get("ph") == "X"]
        except Exception:  # noqa: BLE001 — a reader never sinks the run
            got = []
        ctx["_span_events"] = got
    return ctx["_span_events"]


def spans(ctx, lane: str, prefix: str = "") -> list:
    return [e for e in events(ctx)
            if e.get("cat") == lane and e["name"].startswith(prefix)]


def tag(event, name: str, default=0):
    return (event.get("args") or {}).get(name, default)
