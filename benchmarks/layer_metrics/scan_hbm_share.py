"""Kernels: how near the fragment programs come to the HBM bound. The bytes
the window's statements had to read (`scan_bytes.needed_bytes`) per second
of the window, over the chip's published HBM bandwidth, over the share of
the traced span in which the device was busy. Percent."""


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if not tr or not peaks or not ctx["scan_needed_bytes"]:
        return None
    busy_share = tr["busy_s"] / tr["window_s"]
    rate = ctx["scan_needed_bytes"] / ctx["window_s"]
    return 100.0 * rate / (peaks["hbm_bytes_per_s"] * busy_share)
