"""Session, planner: the share of the traced window's point reads whose
`planner.optimize` span is tagged `cache=miss` — a plan built again for a
key the session's plan cache had not kept (`point_spans.py`). Percent."""

import point_spans


def read(ctx):
    return point_spans.per_point(
        ctx, lambda got: got["point_misses"] * 100.0)
