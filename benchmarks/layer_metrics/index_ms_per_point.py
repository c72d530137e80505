"""Index (`executor/index_scan.py`): self time of lane `index` —
`index.probe` (two binary searches in the sorted view, the row gather, the
residual filters) and any `index.build` the window paid for — per point
read of the traced window (`point_spans.py`). None on a program without
the lane."""

import point_spans


def read(ctx):
    got = point_spans.window(ctx)
    if got is None or "index" not in got["point_self_s"]:
        return None
    return got["point_self_s"]["index"] / got["points"] * 1e3
