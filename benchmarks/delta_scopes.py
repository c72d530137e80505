"""Device seconds of the write path's programs, from the profile's "XLA
Modules" line. The write path's device work runs as small programs of its
own, named for the `jax.named_scope` they trace under: `delta_merge_<sig8>`
(rows scattered into a delta slab, lookup-table and aligned-column updates)
and `tombstone_<sig8>` (liveness masks). `device_scopes.py` groups
operations by a fixed list of stages and prints its twelve largest
programs, so these are read here: every event of the modules line whose
program name starts with a scope's name, summed.

`window(ctx)` → {"delta_merge_s", "tombstone_s", "busy_s", "programs"} of
the traced span, reduced once and printed once a run as a `delta_scopes`
line; None when the run was not traced, or the profile cannot be read.
"""

from __future__ import annotations

import json

import device_scopes
import trace_reduce

SCOPES = ("delta_merge", "tombstone")


def read(path: str, platform: str) -> dict:
    import xplane_raw
    plane_prefix, _line = trace_reduce.DEVICE_LINES[platform]
    planes = xplane_raw.read_planes(
        path, lambda n: n.startswith(plane_prefix),
        lambda n: n == trace_reduce.PROGRAM_LINE)
    out = {f"{s}_s": 0.0 for s in SCOPES}
    programs: dict = {}
    for plane in planes:
        for ln in plane["lines"]:
            for mid, start, end, _st in ln["events"]:
                prog = device_scopes.program_of(
                    "", plane["metadata"][mid]["name"])
                for s in SCOPES:
                    if prog.startswith(s + "_"):
                        out[f"{s}_s"] += end - start
                        programs[prog] = programs.get(prog, 0) + 1
    out["programs"] = programs
    return out


def window(ctx):
    if "_delta_scopes" in ctx:
        return ctx["_delta_scopes"]
    got = None
    scopes = device_scopes.window(ctx)
    if scopes is not None:
        try:
            from tidb_tpu.ops.jax_env import jax
            path = device_scopes.newest_profile()
            if path is not None:
                got = read(path, jax.devices()[0].platform)
                got["busy_s"] = scopes["busy_s"]
        except Exception as e:  # noqa: BLE001 — a reader never sinks the run
            print(json.dumps({"phase": "delta_scopes", "error": repr(e)}),
                  flush=True)
    if got is not None:
        print(json.dumps({"phase": "delta_scopes", **got}), flush=True)
    ctx["_delta_scopes"] = got
    return got
