"""Kernels: how near the grouping comes to the HBM bound. The least bytes
the window's partial aggregates and merges had to move
(`group_bytes.window_bytes`) per second of the window, over the chip's
published HBM bandwidth, over the share of the traced span the device spent
under scopes `agg` and `merge`. Percent."""

import device_scopes
import group_bytes


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    scopes = device_scopes.window(ctx)
    moved = group_bytes.window_bytes(ctx)
    if not tr or not peaks or scopes is None or moved is None:
        return None
    grouping_s = sum(scopes["by_scope"].get(s, 0.0) for s in ("agg", "merge"))
    if grouping_s <= 0:
        return None
    rate = moved / ctx["window_s"]
    return 100.0 * rate / (peaks["hbm_bytes_per_s"]
                           * grouping_s / tr["window_s"])
