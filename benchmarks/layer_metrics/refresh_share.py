"""Harness, traffic: percent of the window's operations that were refresh
transactions (the rest are stream statements), from the per-role samples
the operation kind keeps on the client's clock (`point_roles.py`). The
specification fixes the ratio (S pairs to S x 22 queries); here the
refresher runs back to back and the ratio is whatever the system gives."""

import point_roles


def read(ctx):
    n = ctx.get("attempted") or 0
    if not 0 < n <= len(point_roles.SAMPLES):
        return None
    roles = [r for r, _s, _d in point_roles.SAMPLES[-n:]]
    if "refresh" not in roles:
        return None
    return 100.0 * roles.count("refresh") / n
