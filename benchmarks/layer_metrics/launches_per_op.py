"""Fragments: device programs launched per operation in the window."""


def read(ctx):
    n = ctx["attempted"]
    return ctx["ledger"]["*"]["PROGRAMS_LAUNCHED"] / n if n else None
