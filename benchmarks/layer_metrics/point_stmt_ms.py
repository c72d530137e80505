"""Wire, session, planner, index: one point read inside the server — the
mean `stmt` root (command received → last result byte written) of the traced
window's requests tagged `class=interactive` that ran no device fragment
(`point_spans.py`). None on a program whose roots carry no `class` tag."""

import point_spans


def read(ctx):
    return point_spans.per_point(ctx, lambda got: got["point_stmt_s"] * 1e3)
