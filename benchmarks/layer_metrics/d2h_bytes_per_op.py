"""Fragments: bytes fetched from the device per operation in the window
(`statements_summary` D2H_BYTES): the result rows and the control values a
capacity ladder validates — nothing per group when the ordering and the
limit run on the device."""


def read(ctx):
    n = ctx["attempted"]
    return ctx["ledger"]["*"]["D2H_BYTES"] / n if n else None
