"""Session, store beside readers: one refresh transaction inside the server
— the `stmt` roots of its four requests (BEGIN, two DML statements, COMMIT)
summed, per COMMIT of the traced window (`stream_spans.py`)."""

import stream_spans


def read(ctx):
    got = stream_spans.window(ctx)
    if got is None or not got["txns"]:
        return None
    return got["txn_s"] / got["txns"] * 1e3
