"""Fragments under contention: the mean `stmt` root of the traced window's
requests that ran a device fragment — the scanner's Q1 beside the point
readers (`point_spans.py`): what the analyst gets while the service reads."""

import point_spans


def read(ctx):
    got = point_spans.window(ctx)
    if got is None or not got["scans"]:
        return None
    return got["scan_stmt_s"] / got["scans"] * 1e3
