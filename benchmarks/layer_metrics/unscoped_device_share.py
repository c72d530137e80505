"""Kernels: percent of the device operations' seconds under none of the
stage scopes — what the profile cannot attribute."""

import device_scopes


def read(ctx):
    return device_scopes.share(ctx, (device_scopes.UNSCOPED,))
