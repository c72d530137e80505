"""Session, planner: rows that host executors consumed above or outside the
device fragments, per operation — the `rows` tag of the `exec.<operator>`
spans (lane `exec`) over the traced window. A statement whose joins,
grouping, ordering and limit all ran on the device leaves the host its
result rows alone."""

import span_events
import span_reduce


def read(ctx):
    got = span_reduce.window(ctx)
    rows = span_events.spans(ctx, "exec", "exec.")
    if got is None or not got["ops"] or not rows:
        return None
    return sum(span_events.tag(e, "rows") for e in rows) / got["ops"]
