"""Kernels: percent of the device operations' seconds under the aggregation
scopes (`agg`: partial aggregate; `merge`: the merge of slab partials)."""

import device_scopes


def read(ctx):
    return device_scopes.share(ctx, ("agg", "merge"))
