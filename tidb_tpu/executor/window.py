"""Window-function executor (ref: executor/window.go:31).

Blocking operator: drains the child, sorts once per distinct window spec
by (partition, order) keys with MySQL NULL ordering, computes every
window column via the whole-column primitives in ops/window.py, and
scatters results back to the original row order. The reference streams
partition groups through per-function slide states (pipelined_window.go);
the columnar formulation is one sort + cumulative ops — the same code
path the device engine traces.

Two execution paths share the ops/window.py primitives:

* **Device** — when the engine is on, the input clears the row threshold
  and every spec passes the fragment gate (eligibility.window_device_ok),
  the per-spec sort runs as a device lexsort over the HOST-rank-encoded
  keys (executor/sort.rank_keys bakes in direction + MySQL NULL
  ordering, so the device comparison is a plain int compare) and the
  window columns evaluate as jnp segmented scans. This covers windows
  whose CHILD is a host operator — windows over device-eligible scans
  fuse into the fragment programs instead (device_emit.emit_window) and
  never reach this executor.
* **Host** — the numpy twin of the same primitives; also the per-spec
  fallback when a device evaluation raises (object-dtype args, missing
  accelerator), so a device fault degrades to the oracle result.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from tidb_tpu.chunk import Chunk, Column
from tidb_tpu.executor import Executor, MaterializingExec, empty_chunk
from tidb_tpu.executor.eligibility import window_device_ok
from tidb_tpu.executor.sort import rank_keys
from tidb_tpu.expression import EvalContext
from tidb_tpu.expression.runner import host_context
from tidb_tpu.ops import window as W
from tidb_tpu.planner.physical import PhysWindow
from tidb_tpu.sysvars import var_int, var_on
from tidb_tpu.types import TypeKind


class WindowExec(MaterializingExec):
    def __init__(self, plan: PhysWindow, child: Executor):
        super().__init__(plan.schema.field_types, [child])
        self.plan = plan

    # ------------------------------------------------------------------
    def _materialize(self) -> Chunk:
        chunks = []
        while True:
            ch = self.child_next()
            if ch is None:
                break
            if ch.num_rows:
                chunks.append(ch)
        if not chunks:
            return empty_chunk(self.schema)
        inp = Chunk.concat(chunks) if len(chunks) > 1 else chunks[0]
        ctx = host_context(inp)
        n = inp.num_rows

        sort_cache: Dict[str, Tuple] = {}
        device = self._device_eligible(n)
        out_cols = list(inp.columns)
        for d in self.plan.wdescs:
            key = repr((d.partition, d.order, d.descs))
            if device:
                col = self._one_device(d, ctx, inp, n, key, sort_cache)
                if col is not None:
                    out_cols.append(col)
                    continue
            layout = sort_cache.get("host|" + key)
            if layout is None:
                layout = _sorted_layout(inp, n, d)
                sort_cache["host|" + key] = layout
            sidx, pstart, peerstart = layout
            v, m = self._one(d, ctx, n, sidx, pstart, peerstart)
            back_v = np.empty_like(v)
            back_v[sidx] = v
            back_m = np.empty(n, dtype=bool)
            back_m[sidx] = m
            if d.ftype.is_varlen:
                back_v = np.asarray(back_v, dtype=object)
            elif back_v.dtype != d.ftype.np_dtype:
                back_v = back_v.astype(d.ftype.np_dtype)
            out_cols.append(Column(d.ftype, back_v,
                                   None if back_m.all() else back_m))
        return Chunk(out_cols)

    def _device_eligible(self, n: int) -> bool:
        ctx = getattr(self, "ctx", None)
        if ctx is None or not var_on(ctx.vars, "tidb_tpu_engine"):
            return False
        threshold = var_int(ctx.vars, "tidb_tpu_row_threshold")
        return n >= max(threshold, 1) and window_device_ok(self.plan)

    def _one_device(self, d, ctx, inp, n: int, key: str,
                    sort_cache) -> Optional[Column]:
        """One window column on device: device lexsort over host-rank-
        encoded keys + jnp segmented scans (the same ops/window.py
        primitives the fused programs trace). → None to run this spec on
        the host instead (object-dtype args, device fault)."""
        try:
            from tidb_tpu.ops.jax_env import jnp
            layout = sort_cache.get("dev|" + key)
            if layout is None:
                pkeys = rank_keys(list(d.partition),
                                  [False] * len(d.partition), inp)
                okeys = rank_keys(list(d.order), list(d.descs), inp)
                all_keys = pkeys + okeys
                if all_keys:
                    sidx = jnp.lexsort(tuple(jnp.asarray(k) for k in
                                             reversed(all_keys)))
                else:
                    sidx = jnp.arange(n, dtype=jnp.int64)

                def changes(keys):
                    out = jnp.zeros(n, dtype=bool).at[0].set(True)
                    for k in keys:
                        ks = jnp.take(jnp.asarray(k), sidx)
                        out = out | jnp.concatenate(
                            [jnp.zeros(1, dtype=bool), ks[1:] != ks[:-1]])
                    return out

                pstart = changes(pkeys)
                peerstart = changes(all_keys) if okeys else pstart
                layout = (sidx, pstart, peerstart)
                sort_cache["dev|" + key] = layout
            sidx, pstart, peerstart = layout
            vals = valid = fill = None
            if d.args:
                v, m = d.args[0].eval(ctx)
                v = np.asarray(v)
                if v.dtype == object:
                    return None          # string payloads stay host-side
                vals = jnp.take(jnp.asarray(v), sidx)
                valid = jnp.take(jnp.asarray(np.asarray(m, dtype=bool)),
                                 sidx)
            elif d.name not in ("row_number", "rank", "dense_rank"):
                vals = jnp.zeros(n, dtype=jnp.int64)    # COUNT(*)
                valid = jnp.ones(n, dtype=bool)
            if d.name in ("lag", "lead"):
                if d.default is not None and d.default.value is not None:
                    fv = d.args[0].ftype.encode_value(d.default.value)
                    fill = (jnp.full(n, fv, dtype=vals.dtype),
                            jnp.ones(n, dtype=bool))
                else:
                    fill = (jnp.zeros(n, dtype=vals.dtype),
                            jnp.zeros(n, dtype=bool))
            if d.name == "avg" and d.args and \
                    d.args[0].ftype.kind is TypeKind.DECIMAL:
                vals = vals.astype(np.float64) / \
                    d.args[0].ftype.decimal_multiplier
            frame = getattr(d, "frame", None)
            range_key = None
            if frame is not None and frame[0] == "range":
                kv, km = d.order[0].eval(ctx)
                range_key = (jnp.take(jnp.asarray(np.asarray(kv)), sidx),
                             jnp.take(jnp.asarray(
                                 np.asarray(km, dtype=bool)), sidx),
                             bool(d.descs[0]))
            v, m = W.compute(jnp, d.name, vals, valid, pstart, peerstart,
                             bool(d.order), d.offset, fill, frame=frame,
                             range_key=range_key)
            back_v = np.asarray(jnp.zeros(n, dtype=v.dtype)
                                .at[sidx].set(v))
            back_m = np.asarray(jnp.zeros(n, dtype=bool)
                                .at[sidx].set(m))
        except Exception:       # noqa: BLE001 — per-spec host fallback
            return None
        if back_v.dtype != d.ftype.np_dtype and not d.ftype.is_varlen:
            back_v = back_v.astype(d.ftype.np_dtype)
        return Column(d.ftype, back_v,
                      None if back_m.all() else back_m.copy())

    def _one(self, d, ctx, n, sidx, pstart, peerstart):
        vals = valid = fill = None
        if d.args:
            v, m = d.args[0].eval(ctx)
            vals = np.asarray(v)[sidx]
            valid = np.asarray(m, dtype=bool)[sidx]
        elif d.name not in ("row_number", "rank", "dense_rank"):
            vals = np.zeros(n, dtype=np.int64)      # COUNT(*)
            valid = np.ones(n, dtype=bool)
        if d.name in ("lag", "lead"):
            if d.default is not None and d.default.value is not None:
                fv = d.args[0].ftype.encode_value(d.default.value)
                fill = (np.full(n, fv,
                                dtype=object if vals.dtype == object
                                else vals.dtype),
                        np.ones(n, dtype=bool))
            else:
                fill = (np.zeros(n, dtype=vals.dtype)
                        if vals.dtype != object
                        else np.full(n, "", dtype=object),
                        np.zeros(n, dtype=bool))
        if d.name == "avg" and d.args and \
                d.args[0].ftype.kind is TypeKind.DECIMAL:
            vals = vals.astype(np.float64) / \
                d.args[0].ftype.decimal_multiplier
        frame = getattr(d, "frame", None)
        range_key = None
        if frame is not None and frame[0] == "range":
            kv, km = d.order[0].eval(ctx)
            range_key = (np.asarray(kv)[sidx],
                         np.asarray(km, dtype=bool)[sidx],
                         bool(d.descs[0]))
        return W.compute(np, d.name, vals, valid, pstart, peerstart,
                         bool(d.order), d.offset, fill, frame=frame,
                         range_key=range_key)


def _sorted_layout(chunk: Chunk, n: int, d):
    """→ (sidx, pstart, peerstart) for one window spec. Rank-encoded keys
    (executor/sort.rank_keys) bake in direction and MySQL NULL ordering,
    so boundary detection is a plain code comparison."""
    pkeys = rank_keys(list(d.partition), [False] * len(d.partition), chunk)
    okeys = rank_keys(list(d.order), list(d.descs), chunk)
    all_keys = pkeys + okeys
    if all_keys:
        sidx = np.lexsort(tuple(reversed(all_keys)))
    else:
        sidx = np.arange(n, dtype=np.int64)

    def changes(keys) -> np.ndarray:
        out = np.zeros(n, dtype=bool)
        if n:
            out[0] = True
        for k in keys:
            ks = k[sidx]
            out[1:] |= ks[1:] != ks[:-1]
        return out

    pstart = changes(pkeys)
    peerstart = changes(all_keys) if okeys else pstart
    return sidx, pstart, peerstart
