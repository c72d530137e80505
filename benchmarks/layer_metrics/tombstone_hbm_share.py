"""Kernels: how near the tombstone rewrites come to the HBM bound. The least
bytes the window's rewrites had to move (`tombstone_bytes.window_bytes`:
each rewritten slab's rows x the bytes a row of what is rewritten holds,
read once and written once) per second of the window, over the chip's
published HBM bandwidth, over the share of the traced span the device spent
in the `tombstone` programs. Percent."""

import delta_scopes
import tombstone_bytes


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    got = delta_scopes.window(ctx)
    moved = tombstone_bytes.window_bytes(ctx)
    if not tr or not peaks or got is None or moved is None \
            or got["tombstone_s"] <= 0:
        return None
    rate = moved / ctx["window_s"]
    return 100.0 * rate / (peaks["hbm_bytes_per_s"]
                           * got["tombstone_s"] / tr["window_s"])
