"""Device cache: bytes uploaded per operation in the window (0 when every
column the mix reads is resident)."""


def read(ctx):
    n = ctx["attempted"]
    return ctx["ledger"]["*"]["H2D_BYTES"] / n if n else None
