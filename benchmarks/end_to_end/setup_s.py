"""Process start to the first timed operation: imports, device, generate,
load, ANALYZE, first touch and warm cycles. The reference runs beside it on
its own thread."""


def read(ctx):
    return ctx["setup_s"]
