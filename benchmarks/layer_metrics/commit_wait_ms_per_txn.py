"""Store: what a COMMIT waits for the store's lock — the `commit.gate`
spans (lane `lock`, `wait=lock`, recorded around a CONTENDED acquire only)
summed, per COMMIT of the traced window (`stream_spans.py`). 0 where no
commit waited; None on a program that has no such span."""

import generation_counters
import stream_spans


def read(ctx):
    got = stream_spans.window(ctx)
    if got is None or not got["txns"] or not generation_counters.counted():
        return None
    return got["gate_s"] / got["txns"] * 1e3
