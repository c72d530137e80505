"""Median operation latency in the window, client side, milliseconds."""
import stats


def read(ctx):
    return stats.percentile(ctx["latencies_s"], 50) * 1e3
