"""Kernels: percent of the device operations' seconds under the ordering
scopes (`sort`: ORDER BY / top-n; `finalize`: finals and what else the
fused finalize does outside merge and sort)."""

import device_scopes


def read(ctx):
    return device_scopes.share(ctx, ("sort", "finalize"))
