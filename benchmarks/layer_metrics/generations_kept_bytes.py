"""Device cache, generations: bytes of HBM the generations kept behind the
newest ones own beyond the arrays the newest hold too (liveness masks, the
delta slabs' arrays, the aligned structures' changed parts) — the gauge
`tidb_tpu_delta_generations_kept_bytes` as the window's last operation left
it (`generation_counters.py`). None on a program that keeps none."""

import generation_counters


def read(ctx):
    return generation_counters.last(ctx, "kept_bytes")
