"""Kernels: percent of the device operations' seconds under the join
scopes (`join_probe`: build tables and probes; `partition`: exchange)."""

import device_scopes


def read(ctx):
    return device_scopes.share(ctx, ("join_probe", "partition"))
