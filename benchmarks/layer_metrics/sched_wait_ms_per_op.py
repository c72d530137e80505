"""Scheduler: seconds statements waited for admission (`POOL.stats()`
`wait_s_total` over the window) per operation, milliseconds."""


def read(ctx):
    n = ctx["attempted"]
    return ctx["pool"]["wait_s_total"] / n * 1e3 if n else None
