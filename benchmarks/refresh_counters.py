"""The program's always-on write-path counters, sampled once an operation by
the `refresh_pair` kind and read back as the window's deltas by the
per-layer readers (`layer_metrics/rebuilds_per_op.py`,
`compact_ms_per_op.py`): the harness hands a reader the window's size but
no snapshot of `REGISTRY` at its start.

`sample()` appends the totals of `tidb_tpu_delta_declines_total` (every
gate) and `tidb_tpu_compactions_total` (every cause) as the operation
ends. `window_delta(ctx, name)` is the last sample less the one before the
window's first operation (`ctx["attempted"]` operations back). A program
without the decline counter's module (`executor/delta.decline`) gives
None, and the readers then find nothing to read.
"""

from __future__ import annotations

SAMPLES: list = []          # one {"declines": n, "compactions": n} an op

COUNTERS = {"declines": "tidb_tpu_delta_declines_total",
            "compactions": "tidb_tpu_compactions_total"}


def counted() -> bool:
    """Does this program count declines at all?"""
    try:
        from tidb_tpu.executor import delta
    except Exception:  # noqa: BLE001 — a reader never sinks the run
        return False
    return hasattr(delta, "decline")


def totals() -> dict:
    from tidb_tpu.util.observability import REGISTRY
    out = dict.fromkeys(COUNTERS, 0.0)
    for (name, _labels), value in list(REGISTRY.counters.items()):
        for key, counter in COUNTERS.items():
            if name == counter:
                out[key] += value
    return out


def sample() -> None:
    try:
        SAMPLES.append(totals())
    except Exception:  # noqa: BLE001 — a meter never fails an operation
        pass


def window_delta(ctx, key: str):
    n = ctx.get("attempted") or 0
    if not counted() or not n or len(SAMPLES) <= n:
        return None
    return SAMPLES[-1][key] - SAMPLES[-n - 1][key]
