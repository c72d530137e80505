"""The device cache's always-on generation counters, sampled once an
operation by the `throughput_streams` kind and read back as the window's
deltas by the per-layer readers (`layer_metrics/kept_generation_read_share`,
`stale_rebuilds_per_op`, `generations_kept_bytes`): the harness hands a
reader the window's size but no snapshot of `REGISTRY` at its start.

`sample()` appends, as the operation ends,

    reads      {age: tidb_tpu_delta_generation_reads_total{age=}} — what
               every cached read was served from: the key's `newest`
               generation, one `kept` behind it, a table `rebuilt` beside
    declines   {gate: tidb_tpu_delta_declines_total{gate=}}
    kept, kept_bytes   the gauges tidb_tpu_delta_generations_kept[_bytes]

`window_delta(ctx, key)` is the last sample less the one before the window's
first operation (`ctx["attempted"]` operations back), label by label. A
program that does not count reads by age (`counted()`) gives None, and the
readers then find nothing to read.
"""

from __future__ import annotations

SAMPLES: list = []

READS = "tidb_tpu_delta_generation_reads_total"
DECLINES = "tidb_tpu_delta_declines_total"
GAUGES = {"kept": "tidb_tpu_delta_generations_kept",
          "kept_bytes": "tidb_tpu_delta_generations_kept_bytes"}


def counted() -> bool:
    """Does this program count its cached reads by age? (Every cached read
    bumps the counter, so a program that has it has moved it by the time a
    reader asks.)"""
    try:
        from tidb_tpu.util.observability import REGISTRY
        return any(name == READS for name, _labels in list(REGISTRY.counters))
    except Exception:  # noqa: BLE001 — a reader never sinks the run
        return False


def totals() -> dict:
    from tidb_tpu.util.observability import REGISTRY
    out = {"reads": {}, "declines": {}, "kept": 0.0, "kept_bytes": 0.0}
    for (name, labels), value in list(REGISTRY.counters.items()):
        if name == READS:
            out["reads"][dict(labels).get("age", "")] = value
        elif name == DECLINES:
            out["declines"][dict(labels).get("gate", "")] = value
        else:
            for key, gauge in GAUGES.items():
                if name == gauge:
                    out[key] = value
    return out


def sample() -> None:
    try:
        SAMPLES.append(totals())
    except Exception:  # noqa: BLE001 — a meter never fails an operation
        pass


def window_delta(ctx, key: str):
    """→ {label: the window's count} for `reads` / `declines`."""
    n = ctx.get("attempted") or 0
    if not counted() or not n or len(SAMPLES) <= n:
        return None
    last, first = SAMPLES[-1][key], SAMPLES[-n - 1][key]
    return {k: v - first.get(k, 0.0) for k, v in last.items()}


def last(ctx, key: str):
    """The gauge as the window's last operation left it."""
    if not counted() or not SAMPLES:
        return None
    return SAMPLES[-1][key]
