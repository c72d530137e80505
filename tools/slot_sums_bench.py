"""Microbenchmark of `ops/segment.slot_sums` on the chip: one 8,388,608-row
slab of Q1's states (12 slots) and of Q3/Q5's (6 slots) at the benchmark's
TPC-H domains, contracted knowing no width and knowing each argument's —
milliseconds a call on the host's clock around back-to-back calls (it
carries a call's fixed cost: read differences) and the rows of the piece
matrix, every sum checked against `segment_sum` of the column.

    chiprun -- python3 tools/slot_sums_bench.py          # one JSON line a case
    JAX_PLATFORMS=cpu python3 tools/slot_sums_bench.py --rows 65536 --reps 2
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tidb_tpu import types as T  # noqa: E402
from tidb_tpu.expression import ColumnRef  # noqa: E402
from tidb_tpu.expression.aggfuncs import AggDesc, build_agg  # noqa: E402
from tidb_tpu.ops import segment as seg  # noqa: E402
from tidb_tpu.ops.jax_env import jax, jnp  # noqa: E402

DEC = T.decimal(15, 2, True)
# (aggregate, argument, its greatest value at the benchmark's domains)
Q1 = [("sum", "qty", 5000), ("sum", "price", (1 << 24) - 1),
      ("sum", "disc_price", (1 << 30) - 1), ("sum", "charge", (1 << 37) - 1),
      ("avg", "qty", 5000), ("avg", "price", (1 << 24) - 1),
      ("avg", "disc", 10), ("count", None, 0)]
Q3 = [("sum", "revenue", (1 << 30) - 1)]
CASES = {"q1": (Q1, 12), "q3": (Q3, 6)}


def columns(shape, values, valid, live, ranged: bool):
    """The aggregates' SumColumns as `device_emit._agg_states` gathers
    them: an argument is ONE pair of arrays however many aggregates name
    it."""
    evaluated, out = {}, []
    for name, arg, hi in shape:
        if arg is None:
            agg = build_agg(AggDesc("count", []))
            plan = agg.row_sums(jnp, None, live)
        else:
            if arg not in evaluated:
                evaluated[arg] = (values[arg], valid[arg] & live)
            v, m = evaluated[arg]
            agg = build_agg(AggDesc(name, [ColumnRef(0, DEC)]))
            plan = agg.row_sums(jnp, v, m,
                                hi.bit_length() if ranged else None)
        out += [c for c in plan if c is not None]
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 23)
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--seed", type=int, default=41)
    args = ap.parse_args()
    rng = np.random.default_rng(args.seed)
    n = args.rows
    if jax.default_backend() != "tpu":
        seg.SLOT_SUM_MIN_WORK = 2
    for case, (shape, slots) in CASES.items():
        his = {arg: hi for _, arg, hi in shape if arg}
        host = {a: rng.integers(0, hi + 1, n).astype(np.int64)
                for a, hi in his.items()}
        for a, hi in his.items():
            host[a][:2] = (0, hi)       # the domain's ends are there
        hvalid = {a: rng.random(n) < 0.97 for a in his}
        hlive = rng.random(n) < 0.98
        gid = rng.integers(0, slots, n).astype(np.int32)
        gid = np.where(hlive, gid, slots).astype(np.int32)
        dev = ({a: jnp.asarray(v) for a, v in host.items()},
               {a: jnp.asarray(v) for a, v in hvalid.items()},
               jnp.asarray(hlive), jnp.asarray(gid))
        for label, ranged in [("whole width", False),
                              ("each argument's width", True)]:

            def run(values, valid, live, g):
                return seg.slot_sums(
                    jnp, columns(shape, values, valid, live, ranged), g,
                    slots)

            def ref(values, valid, live, g):
                # the masked reduce a column, on the device itself
                return [seg.segment_sum(jnp, seg._column_data(jnp, c), g,
                                        slots)
                        for c in columns(shape, values, valid, live, ranged)]
            f = jax.jit(run)
            t0 = time.perf_counter()
            got = jax.block_until_ready(f(*dev))
            compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(args.reps):
                out = f(*dev)
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / args.reps * 1e3
            equal = all(bool((a == b).all())
                        for a, b in zip(got, jax.jit(ref)(*dev)))
            print(json.dumps({
                "case": case, "plan": label, "rows": n, "slots": slots,
                "device": jax.devices()[0].device_kind,
                "piece_rows": seg.slot_sum_pieces(
                    columns(shape, dev[0], dev[1], dev[2], ranged)),
                "ms_per_call": ms, "compile_s": compile_s,
                "equal_to_segment_sum": equal}), flush=True)


if __name__ == "__main__":
    main()
