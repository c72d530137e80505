"""95th percentile of operation latency over every operation sent in the
window, milliseconds. The window line says whether ten samples lie beyond
it (`p95_has_ten_samples_beyond`)."""
import stats


def read(ctx):
    return stats.percentile(ctx["latencies_s"], 95) * 1e3
