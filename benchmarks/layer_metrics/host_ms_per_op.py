"""Wire, session, planner, fetch, decode: mean client wall per operation
less what the program's ledger says was queue wait and the host's compute
phase (`DEVICE_SECONDS` is a host timer around that phase, used here as
what it is). Host clocks for host layers."""


def read(ctx):
    n = len(ctx["latencies_s"])
    if not n:
        return None
    led = ctx["ledger"]["*"]
    mean = sum(ctx["latencies_s"]) / n
    return (mean - (led["QUEUE_WAIT_S"] + led["DEVICE_SECONDS"]) / n) * 1e3
