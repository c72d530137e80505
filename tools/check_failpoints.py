#!/usr/bin/env python
"""Failpoint-catalog drift lint: the chaos sweep can only gate coverage
over sites it can ENUMERATE, so the site catalog
(tidb_tpu/util/failpoint.py `register(...)` — plus module-local
registrations like executor/zonemap.py's) and the `failpoint.inject(...)`
call sites in the tree must agree both ways:

  * every inject() with a literal site name must name a REGISTERED site
    (an unregistered site is invisible to the sweep's coverage gate —
    a fault path nobody sweeps);
  * every registered site must be REFERENCED in code — as an inject()
    literal or (for the shared-helper sites the distributed path
    dispatches dynamically, e.g. `failpoint.inject(site)`) as a string
    literal passed toward one;
  * inject() must not be called with a dynamic name unless some
    registered site reaches it as a literal elsewhere in the same file
    (otherwise the name can drift from the catalog silently).

Run directly (`python tools/check_failpoints.py`) or let the chaos
sweep entry point run it — like tools/check_metrics.py, drift fails the
sweep before any scenario spends wall time. Exit 0 = clean, 1 =
violations (one per line as path:lineno: message)."""

import ast
import os
import sys


def _is_inject(node: ast.Call) -> bool:
    f = node.func
    return (isinstance(f, ast.Attribute) and f.attr == "inject"
            and isinstance(f.value, ast.Name) and f.value.id == "failpoint")


def _is_register(node: ast.Call) -> bool:
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr == "register" and \
            isinstance(f.value, ast.Name) and f.value.id == "failpoint":
        return True
    # failpoint.py registers its own sites via a bare register() call
    return isinstance(f, ast.Name) and f.id == "register"


def scan_file(path: str):
    """→ (inject_literals [(name, lineno)], dynamic_injects [lineno],
    registered [(name, lineno)], string_constants {str})."""
    with open(path) as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [], [], [], set(), [f"{path}:{e.lineno}: unparseable: {e.msg}"]
    injects, dynamic, registered, strings = [], [], [], set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            strings.add(node.value)
        if not isinstance(node, ast.Call):
            continue
        if _is_inject(node):
            arg = node.args[0] if node.args else None
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                injects.append((arg.value, node.lineno))
            else:
                dynamic.append(node.lineno)
        elif _is_register(node):
            arg = node.args[0] if node.args else None
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                registered.append((arg.value, node.lineno))
            # failpoint.py's bulk loop registers from a tuple literal —
            # those names land in `strings` and the catalog is loaded
            # dynamically below, so nothing is lost here
    return injects, dynamic, registered, strings, []


def _catalog(root: str, register_files):
    """The authoritative registered-site set: import failpoint plus
    every module that calls failpoint.register() at import time."""
    sys.path.insert(0, root)
    try:
        from tidb_tpu.util import failpoint
        for path in register_files:
            rel = os.path.relpath(path, root)
            if not rel.startswith("tidb_tpu") or rel.endswith("__main__.py"):
                continue
            mod = rel[:-3].replace(os.sep, ".")
            try:
                __import__(mod)
            except Exception as e:  # noqa: BLE001 — a module that can't
                # import can't register either; surface it
                print(f"check_failpoints: warning: import {mod}: {e}",
                      file=sys.stderr)
        return failpoint.catalog()
    finally:
        sys.path.remove(root)


def run(root: str = None):
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
    injects, dynamic, register_files = [], [], []
    all_strings = set()
    per_file_strings = {}
    for path in sorted(targets):
        inj, dyn, reg, strings, errs = scan_file(path)
        problems.extend(errs)
        injects.extend((n, path, ln) for n, ln in inj)
        dynamic.extend((path, ln) for ln in dyn)
        if reg or path.endswith(os.path.join("util", "failpoint.py")):
            register_files.append(path)
        all_strings |= strings
        per_file_strings[path] = strings

    catalog = _catalog(root, register_files)

    # direction 0: the sweep's `--list-sites` enumeration must agree
    # with the catalog this lint derives from the tree — that printed
    # "N sites" number is what the docs/README advertise, and a
    # module-scope registration the sweep forgot to import (or a stale
    # import that registers a site nothing sweeps) would silently skew
    # the coverage gate
    sys.path.insert(0, root)
    try:
        from tidb_tpu.tools import chaos_sweep
        listed = set(chaos_sweep.list_sites())
        if listed != set(catalog):
            missing = sorted(set(catalog) - listed)
            extra = sorted(listed - set(catalog))
            problems.append(
                f"catalog: chaos_sweep --list-sites prints {len(listed)} "
                f"sites but the tree registers {len(catalog)}"
                + (f"; not listed: {missing}" if missing else "")
                + (f"; listed but unregistered: {extra}" if extra else ""))
    except Exception as e:  # noqa: BLE001 — an unimportable sweep can't
        # enumerate anything; that IS the drift
        problems.append(
            f"catalog: cannot import tidb_tpu.tools.chaos_sweep to "
            f"cross-check --list-sites: {type(e).__name__}: {e}")
    finally:
        sys.path.remove(root)

    # direction 0b: the README's failpoint catalog table must list
    # exactly the registered sites — a new site that skips the table is
    # undocumented, a removed site that lingers advertises a fault
    # boundary that no longer exists
    readme = os.path.join(root, "README.md")
    if os.path.exists(readme):
        with open(readme) as f:
            lines = f.read().splitlines()
        rows, in_table = set(), False
        for line in lines:
            s = line.strip()
            if s.startswith("| Site |"):
                in_table = True
                continue
            if in_table:
                if not s.startswith("|"):
                    break
                cell = s.split("|")[1].strip()
                if cell.startswith("`") and cell.rstrip("† ").endswith("`"):
                    rows.add(cell.strip("`† "))
        if not in_table:
            problems.append(
                "README.md: failpoint catalog table (header '| Site |') "
                "not found — document the catalog or drop this gate")
        else:
            undocumented = sorted(set(catalog) - rows)
            stale = sorted(rows - set(catalog))
            if undocumented:
                problems.append(
                    f"README.md: failpoint table is missing registered "
                    f"site(s): {undocumented}")
            if stale:
                problems.append(
                    f"README.md: failpoint table lists unregistered "
                    f"site(s): {stale}")

    # direction 1: every literal inject site is registered
    for name, path, ln in injects:
        if name not in catalog:
            problems.append(
                f"{path}:{ln}: inject site {name!r} is not in the "
                f"failpoint catalog — the chaos sweep cannot gate it "
                f"(register it in util/failpoint.py or at module scope)")

    # direction 2: every registered site is referenced somewhere in code
    referenced = {n for n, _p, _l in injects}
    for name in catalog:
        if name in referenced:
            continue
        # dynamically-dispatched sites (inject(site) helpers) still
        # carry the name as a string literal at their call sites
        if any(name in per_file_strings[p] for p, _l in dynamic):
            continue
        problems.append(
            f"catalog: registered site {name!r} has no inject() call "
            f"site in the tree — dead catalog entry (remove it, or the "
            f"sweep's coverage gate chases a site that can never fire)")

    # dynamic injects in a file with no catalog names at all: the name
    # cannot be cross-checked — require at least one registered site
    # to appear as a literal in the same file
    for path, ln in dynamic:
        if not (per_file_strings[path] & set(catalog)):
            problems.append(
                f"{path}:{ln}: inject() with a dynamic site name and no "
                f"registered site literal in the file — the name can "
                f"drift from the catalog silently")
    return problems


def main(argv=None) -> int:
    problems = run(argv[0] if argv else None)
    for p in problems:
        print(p, file=sys.stderr)
    if problems:
        print(f"check_failpoints: {len(problems)} violation(s)",
              file=sys.stderr)
        return 1
    print("check_failpoints: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
