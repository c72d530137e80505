"""Fragments beside a writer: the mean `stmt` root (command received → last
result byte written) of the traced window's requests that ran a device
fragment — a query stream's statement while the refresh stream commits
(`stream_spans.py`)."""

import stream_spans


def read(ctx):
    got = stream_spans.window(ctx)
    if got is None or not got["statements"]:
        return None
    return got["stmt_s"] / got["statements"] * 1e3
