"""From a profiler trace (`.xplane.pb`) to device seconds by *stage* and by
*program*. The program traces every stage of a fragment under a
`jax.named_scope` (`tidb_tpu/executor/device_emit.py`: decode, filter,
project, join_probe, agg, merge, finalize, sort, window, partition) and
gives every jitted program a name `<kind>_<sig8>`, so an operation's
`op_name`, `jit(<program>)/.../<stage>/<op>`, says who emitted it.

`read(path, platform)` reads the device lines `trace_reduce.DEVICE_LINES`
names, through `xplane_raw.py` (the `op_name` sits in the statistics of an
event's metadata, which `jax.profiler.ProfileData` does not show: `tf_op` on
the TPU plane). An operation's seconds are its *self* time on its line (a
`while` spans its body's operations on the same line; each second is counted
once), so the shares add up to the line's busy time. `window(ctx)` finds the traced
run's profile — `run.py` keeps it in the newest `bench_xplane_*` directory
under the temporary directory while the readers run, and does not pass its
path — reduces it once, prints ONE `{"phase": "device_by_scope", ...}` line
and hands the tables to the readers in `layer_metrics/`.

Where the profile carries no `op_name` (XLA's CPU thunks do not, so the
rehearsal; or a program without the scopes) every operation is `(unscoped)`.
An operation the compiler made itself has none either: it takes its operand's
(`read`), and `inherited_s` says how many seconds were attributed that way.
"""

from __future__ import annotations

import glob
import json
import os
import re
import tempfile

import trace_reduce

STAGES = ("decode", "filter", "project", "join_probe", "agg", "merge",
          "finalize", "sort", "window", "partition")
UNSCOPED = "(unscoped)"
# the stat of a device operation's metadata that holds its `op_name` on the
# TPU plane (found on the chip, PR 26)
OP_NAME_STAT = "tf_op"
ANNOTATION_PREFIX = "tidb_tpu/"
TOP = 12


def scope_of(op_name: str) -> str:
    """The innermost stage on an `op_name` path: `finalize/merge/reduce` is
    merge's. → a member of `STAGES`, or `UNSCOPED`."""
    for part in reversed(op_name.split("/")[:-1]):
        if part in STAGES:
            return part
    return UNSCOPED


def program_of(op_name: str, module: str = "") -> str:
    """`jit(partial_fused_ab12cd34)/...` → `partial_fused_ab12cd34`; else
    the module's name without `jit_` and a trailing `(id)`."""
    m = re.match(r"(?:jit|pjit)\(([^)]+)\)", op_name)
    if m:
        return m.group(1)
    module = re.sub(r"\(\d+\)$", "", module.strip())
    return module[4:] if module.startswith("jit_") else module


def self_seconds(events) -> list:
    """[(start, end, payload)] of ONE line, properly nested → [(payload,
    self seconds)]: an event's duration less its direct children's."""
    out = []
    stack: list = []            # [end, index into out]
    for s, e, payload in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            up = stack[-1][1]
            out[up][1] -= min(e, stack[-1][0]) - s
        out.append([payload, e - s])
        stack.append([e, len(out) - 1])
    return [(p, max(d, 0.0)) for p, d in out]


def operands(hlo_text: str) -> list:
    """The `%names` an HLO instruction's text mentions after its own: its
    operands first, then what it calls."""
    _head, sep, rest = hlo_text.partition(" = ")
    return re.findall(r"%([\w.\-]+)", rest if sep else "")


def read(path: str, platform: str) -> dict:
    """→ {"busy_s", "by_scope": {stage: s}, "inherited_s": {stage: s} (the
    part of by_scope that came through an operand, see below),
    "by_program": {program: s} (from the operations), "modules": {program:
    s} (the "XLA Modules" line), "by_program_scope": [[program, stage, s]],
    "top_ops": [[op, stage, program, s]], "annotations": host events named
    `tidb_tpu/...`, "op_name_stat": which stat held the op_name}.

    An operation the compiler made itself carries no `op_name` (the TPU
    compiler's 64-bit rewrite of a cumsum's reduce-window, layout copies):
    it takes the scope of the first of its operands' producers, in the same
    program, that has one."""
    import xplane_raw
    plane_prefix, line_prefix = trace_reduce.DEVICE_LINES[platform]
    planes = xplane_raw.read_planes(
        path, lambda n: n.startswith(plane_prefix),
        lambda n: n.startswith(line_prefix) or n == trace_reduce.PROGRAM_LINE)
    modules: dict = {}
    module_of: dict = {}            # program id → program
    for plane in planes:
        for ln in plane["lines"]:
            if ln["name"] != trace_reduce.PROGRAM_LINE:
                continue
            for mid, s, e, _st in ln["events"]:
                name = plane["metadata"][mid]["name"]
                p = program_of("", name)
                modules[p] = modules.get(p, 0.0) + e - s
                m = re.search(r"\((\d+)\)$", name)
                if m:
                    module_of[m.group(1)] = p
    by_scope: dict = {}
    inherited: dict = {}
    by_program: dict = {}
    by_both: dict = {}
    by_op: dict = {}
    named = 0
    for plane in planes:
        # what each distinct operation is: (op, own scope, program, text)
        known: dict = {}
        for mid, md in plane["metadata"].items():
            st = md["stats"]
            stat = OP_NAME_STAT \
                if "/" in str(st.get(OP_NAME_STAT, "")) else None
            op_name = str(st[stat]).rstrip(":") if stat else ""
            pid = str(st.get("program_id", ""))
            program = module_of.get(pid) or program_of(op_name)
            known[mid] = [md["display_name"] or
                          trace_reduce.short_name(md["name"]),
                          scope_of(op_name), program, md["name"], stat, pid]
        # an operation without a scope takes its operand's producer's
        scope_at = {(k[5], k[0]): k[1] for k in known.values()
                    if k[1] != UNSCOPED}
        via: set = set()
        for _round in range(3):         # through producers without a name
            for mid, k in known.items():
                if k[1] != UNSCOPED:
                    continue
                got = next((scope_at[(k[5], o)] for o in operands(k[3])
                            if (k[5], o) in scope_at), None)
                if got is not None:
                    k[1] = got
                    scope_at[(k[5], k[0])] = got
                    via.add(mid)
        for ln in plane["lines"]:
            if ln["name"] == trace_reduce.PROGRAM_LINE:
                continue
            # XLA's CPU thunks name module and operation on the event
            for mid, _s, _e, st in ln["events"]:
                if st.get("hlo_module") and not known[mid][2]:
                    known[mid][2] = program_of("", str(st["hlo_module"]))
            evs = [(s, e, mid) for mid, s, e, _st in ln["events"]]
            for mid, s in self_seconds(evs):
                op, scope, program, _text, stat, _pid = known[mid]
                named += bool(stat)
                by_scope[scope] = by_scope.get(scope, 0.0) + s
                if mid in via:
                    inherited[scope] = inherited.get(scope, 0.0) + s
                by_program[program] = by_program.get(program, 0.0) + s
                k = (program, scope)
                by_both[k] = by_both.get(k, 0.0) + s
                k = (op, scope, program)
                by_op[k] = by_op.get(k, 0.0) + s
    annotations = sum(
        1 for plane in xplane_raw.read_planes(
            path, lambda n: n.startswith("/host:"), lambda n: True)
        for ln in plane["lines"] for mid, _s, _e, _st in ln["events"]
        if plane["metadata"].get(mid, {}).get("name", "").startswith(
            ANNOTATION_PREFIX))

    def top(d):
        return [list(k) + [s] if isinstance(k, tuple) else [k, s]
                for k, s in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": sum(by_scope.values()), "by_scope": by_scope,
            "inherited_s": inherited,
            "by_program": dict(top(by_program)), "modules": dict(top(modules)),
            "by_program_scope": top(by_both), "top_ops": top(by_op),
            "annotations": annotations,
            "op_name_stat": OP_NAME_STAT if named else None}


def newest_profile() -> str | None:
    dirs = sorted(glob.glob(os.path.join(tempfile.gettempdir(),
                                         "bench_xplane_*")),
                  key=os.path.getmtime)
    for d in reversed(dirs):
        try:
            return trace_reduce.find_xplane(d)
        except FileNotFoundError:
            continue
    return None


def window(ctx) -> dict | None:
    """The traced run's profile, reduced once and printed once a run (kept
    on the run's own `ctx`). None when the run was not traced or the
    profile cannot be read."""
    if "_device_by_scope" in ctx:
        return ctx["_device_by_scope"]
    got = None
    if ctx.get("trace"):
        try:
            from tidb_tpu.ops.jax_env import jax
            path = newest_profile()
            if path is not None:
                got = read(path, jax.devices()[0].platform)
        except Exception as e:  # noqa: BLE001 — a reader never sinks the run
            print(json.dumps({"phase": "device_by_scope",
                              "error": repr(e)}), flush=True)
    if got is not None and got["busy_s"] <= 0:
        got = None
    if got is not None:
        print(json.dumps({"phase": "device_by_scope", **got}), flush=True)
    ctx["_device_by_scope"] = got
    return got


def share(ctx, scopes) -> float | None:
    """Percent of the device operations' seconds under `scopes`."""
    got = window(ctx)
    if got is None:
        return None
    return 100.0 * sum(got["by_scope"].get(s, 0.0)
                       for s in scopes) / got["busy_s"]
