"""Kernels: percent of the device's busy seconds under the write path's
scopes — `delta_merge` (appended rows scattered into the delta slab, the
FK-aligned joins following a generation) and `tombstone` (liveness masks).
Each is a program of its own (`delta_merge_<sig8>`, `tombstone_<sig8>`),
read from the profile's "XLA Modules" line (`delta_scopes.py`)."""

import delta_scopes


def read(ctx):
    got = delta_scopes.window(ctx)
    if got is None or not got["busy_s"] or not got["programs"]:
        return None
    return 100.0 * (got["delta_merge_s"] + got["tombstone_s"]) \
        / got["busy_s"]
