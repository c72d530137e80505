"""Kernels: percent of the device operations' seconds that the in-trace
decode of compressed columns takes (scope `decode`), from the profile."""

import device_scopes


def read(ctx):
    return device_scopes.share(ctx, ("decode",))
