#!/usr/bin/env python
"""Metric-name/label lint: walk every `REGISTRY.inc(...)` /
`REGISTRY.observe(...)` call site in the tree and enforce the naming
contract the dashboards and tools/check_metrics assertions depend on:

  * metric names are literal snake_case strings starting with
    `tidb_tpu_` and ending in a unit suffix — `_total` (counters),
    `_seconds` / `_bytes` (histograms/quantities);
  * label KEYS come from a fixed vocabulary, so a new call site cannot
    silently fork cardinality (`stmt` vs `statement` vs `kind`).

Run directly (`python tools/check_metrics.py`) or let the chaos sweep
entry point run it — metric drift fails the sweep fast, before any
scenario executes.  Exit 0 = clean, 1 = violations (printed one per
line as path:lineno: message)."""

import ast
import os
import sys

UNIT_SUFFIXES = ("_total", "_seconds", "_bytes")
LABEL_VOCAB = {"stmt", "engine", "table", "site", "device", "phase",
               "stage", "reason", "class", "le", "grouping", "operator",
               "rung", "scan", "gate", "kind", "cause", "lowering", "plan", "age",
               "range"}
PREFIX = "tidb_tpu_"


def _is_registry_call(node: ast.Call):
    """→ 'inc' | 'observe' | 'set_gauge' when the call is
    REGISTRY.inc/observe/set_gauge, else None."""
    f = node.func
    if not isinstance(f, ast.Attribute) \
            or f.attr not in ("inc", "observe", "set_gauge"):
        return None
    target = f.value
    if isinstance(target, ast.Name) and target.id == "REGISTRY":
        return f.attr
    return None


def _label_keys(node: ast.Call, arg_index: int):
    """Label-dict keys of the call, or None when not statically known."""
    args = list(node.args)
    dict_arg = args[arg_index] if len(args) > arg_index else None
    for kw in node.keywords:
        if kw.arg == "labels":
            dict_arg = kw.value
    if dict_arg is None or (isinstance(dict_arg, ast.Constant)
                            and dict_arg.value is None):
        return []
    if not isinstance(dict_arg, ast.Dict):
        return None
    keys = []
    for k in dict_arg.keys:
        if not (isinstance(k, ast.Constant) and isinstance(k.value, str)):
            return None
        keys.append(k.value)
    return keys


def check_file(path: str):
    with open(path) as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [f"{path}:{e.lineno}: unparseable: {e.msg}"]
    problems = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        kind = _is_registry_call(node)
        if kind is None:
            continue
        where = f"{path}:{node.lineno}"
        if not node.args:
            problems.append(f"{where}: {kind}() without a metric name")
            continue
        name_arg = node.args[0]
        if not (isinstance(name_arg, ast.Constant)
                and isinstance(name_arg.value, str)):
            problems.append(
                f"{where}: metric name must be a string literal "
                f"(dynamic names fork cardinality invisibly)")
            continue
        name = name_arg.value
        if not name.startswith(PREFIX):
            problems.append(
                f"{where}: metric {name!r} must start with '{PREFIX}'")
        if name != name.lower() or not all(
                c.isalnum() or c == "_" for c in name):
            problems.append(f"{where}: metric {name!r} is not snake_case")
        if kind != "set_gauge" and not name.endswith(UNIT_SUFFIXES):
            problems.append(
                f"{where}: metric {name!r} lacks a unit suffix "
                f"({'/'.join(UNIT_SUFFIXES)})")
        if kind == "inc" and not name.endswith("_total"):
            problems.append(
                f"{where}: counter {name!r} must end in '_total'")
        if kind == "observe" and name.endswith("_total"):
            problems.append(
                f"{where}: histogram {name!r} must not end in '_total'")
        if kind == "set_gauge" and name.endswith("_total"):
            problems.append(
                f"{where}: gauge {name!r} must not end in '_total' "
                f"(gauges are set-points, not counters)")
        keys = _label_keys(node, 1 if kind == "inc" else 2)
        if keys is None:
            problems.append(
                f"{where}: labels for {name!r} must be an inline dict "
                f"with string-literal keys")
        else:
            for k in keys:
                if k not in LABEL_VOCAB:
                    problems.append(
                        f"{where}: label key {k!r} on {name!r} not in "
                        f"the fixed vocabulary {sorted(LABEL_VOCAB)}")
    return problems


def run(root: str = None):
    """Lint every .py under the package + tools. → problem list."""
    if root is None:
        root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..")
    root = os.path.abspath(root)
    targets = []
    for sub in ("tidb_tpu", "tools"):
        for dirpath, _dirs, files in os.walk(os.path.join(root, sub)):
            targets.extend(os.path.join(dirpath, f) for f in files
                           if f.endswith(".py"))
    problems = []
    for path in sorted(targets):
        problems.extend(check_file(path))
    return problems


def main(argv=None) -> int:
    problems = run(argv[0] if argv else None)
    for p in problems:
        print(p, file=sys.stderr)
    if problems:
        print(f"check_metrics: {len(problems)} violation(s)",
              file=sys.stderr)
        return 1
    print("check_metrics: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
