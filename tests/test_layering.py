"""The executor's module graph, read off the source (PR 47).

Every rule walks `tidb_tpu/` with `ast` and imports nothing of it, so no
case needs JAX. A rule that fails prints every offending line: what it
names is a reach-around to repair where it is, not a line to allow-list.
"""

import ast
import os
import re

import pytest

ROOT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "tidb_tpu")
PKG = "tidb_tpu"
EXECUTOR = "tidb_tpu.executor"

# layers BELOW the executor: they import nothing of it
LOWER = ("ops", "chunk", "expression", "types", "util", "storage", "parser")

# pairs of executor modules that may import each other (and do so inside
# functions): one subsystem in two files, or a named debt. Nothing else.
CYCLE_ALLOWED = {
    frozenset(("device_cache", "delta")):
        "one subsystem in two files: the cache extends an entry through "
        "delta.py on a read, delta.py builds and swaps the cache's entries",
    frozenset(("device_cache", "scheduler")):
        "placement and re-homing (PR 18/19): the cache asks the pool where "
        "a statement runs, the pool re-homes a lost device's entries "
        "(ROADMAP.md Queue 3, a named debt)",
}

# imports of tidb_tpu.executor.* inside a function, outside those pairs
FUNCTION_IMPORT_ALLOWED = {
    ("tidb_tpu.planner.physical", "tidb_tpu.executor.eligibility"):
        "planner/physical.py defines the plan nodes the executor is built "
        "on, so its last pass (which subtrees run on the device) calls UP",
}


def _modules():
    mods = {}
    for d, _dirs, files in os.walk(ROOT):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(d, f)
            rel = os.path.relpath(path, os.path.dirname(ROOT))[:-3]
            name = rel.replace(os.sep, ".")
            if name.endswith(".__init__"):
                name = name[:-len(".__init__")]
            mods[name] = path
    return mods


MODS = _modules()
TREES = {m: ast.parse(open(p).read(), p) for m, p in MODS.items()}


def _imports(mod):
    """→ [(target module, imported name or None, inside a function,
    line)] for every import of a `tidb_tpu` module in `mod`."""
    out = []
    is_pkg = MODS[mod].endswith("__init__.py")

    def visit(node, in_func):
        for ch in ast.iter_child_nodes(node):
            inner = in_func or isinstance(
                ch, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda))
            if isinstance(ch, ast.ImportFrom):
                base = ch.module or ""
                if ch.level:
                    pk = mod.split(".")
                    if not is_pkg:
                        pk = pk[:-1]
                    pk = pk[:len(pk) - (ch.level - 1)]
                    base = ".".join(pk + ([ch.module] if ch.module else []))
                for a in ch.names:
                    full = base + "." + a.name
                    if full in MODS:
                        out.append((full, None, in_func, ch.lineno))
                    elif base in MODS:
                        out.append((base, a.name, in_func, ch.lineno))
            elif isinstance(ch, ast.Import):
                for a in ch.names:
                    if a.name in MODS:
                        out.append((a.name, None, in_func, ch.lineno))
            visit(ch, inner)
    visit(TREES[mod], False)
    return out


IMPORTS = {m: _imports(m) for m in MODS}


def _where(mod, line):
    return f"{os.path.relpath(MODS[mod], os.path.dirname(ROOT))}:{line}"


def _short(mod):
    return mod[len(EXECUTOR) + 1:] or "__init__"


def _is_executor(mod):
    return mod == EXECUTOR or mod.startswith(EXECUTOR + ".")


def _private(name):
    return name.startswith("_") and not name.startswith("__")


def lower_layers_import_no_executor():
    bad = []
    for m in MODS:
        parts = m.split(".")
        if len(parts) > 1 and parts[1] in LOWER:
            bad += [f"{_where(m, ln)} imports {t}"
                    for t, _n, _f, ln in IMPORTS[m] if _is_executor(t)]
    return bad


def executor_graph_has_no_cycle():
    """Function-level imports count, and so does the package's own
    `__init__` (Python runs it before any submodule). Modules of an
    allow-listed pair count as one node."""
    nodes = [m for m in MODS if _is_executor(m)]
    group = {m: m for m in nodes}
    for pair in CYCLE_ALLOWED:
        a, b = (EXECUTOR + "." + x for x in sorted(pair))
        ga, gb = group[a], group[b]
        for m in nodes:
            if group[m] == gb:
                group[m] = ga
    edges = {}
    for m in nodes:
        targets = {t for t, _n, _f, _ln in IMPORTS[m] if _is_executor(t)}
        if m != EXECUTOR:
            targets.add(EXECUTOR)
        for t in targets:
            if group[t] != group[m]:
                edges.setdefault(group[m], {})[group[t]] = (m, t)
    bad, state = [], {}

    def dfs(n, path):
        state[n] = 1
        for t, (src, dst) in edges.get(n, {}).items():
            if state.get(t) == 1:
                cyc = path[path.index(t):] + [t] if t in path else [n, t]
                bad.append(" -> ".join(_short(x) for x in cyc)
                           + f"   (closed by {_short(src)} importing "
                             f"{_short(dst)})")
            elif t not in state:
                dfs(t, path + [t])
        state[n] = 2

    for n in sorted(set(group.values())):
        if n not in state:
            dfs(n, [n])
    return bad


def no_private_name_crosses_an_executor_module():
    bad = []
    for m in MODS:
        aliases = {}
        for node in ast.walk(TREES[m]):
            if isinstance(node, ast.ImportFrom):
                for a in node.names:
                    full = (node.module or "") + "." + a.name
                    if _is_executor(full) and full in MODS and full != m:
                        aliases[a.asname or a.name] = full
            elif isinstance(node, ast.Import):
                for a in node.names:
                    if _is_executor(a.name) and a.asname and a.name != m:
                        aliases[a.asname] = a.name
        for t, name, _f, ln in IMPORTS[m]:
            if name and _is_executor(t) and t != m and _private(name):
                bad.append(f"{_where(m, ln)} imports {_short(t)}.{name}")
        for node in ast.walk(TREES[m]):
            if isinstance(node, ast.Attribute) and _private(node.attr) \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id in aliases:
                bad.append(f"{_where(m, node.lineno)} reads "
                           f"{_short(aliases[node.value.id])}.{node.attr}")
    return bad


# what an executor module may still import inside a function: JAX stays
# lazy behind `ops.jax_env`, and the mesh (`parallel`) loads on its branch
LAZY_BELOW = ("tidb_tpu.ops.jax_env", "tidb_tpu.parallel")


def no_executor_import_inside_a_function():
    """Nowhere in `tidb_tpu/` is an executor module imported inside a
    function, and inside the executor nothing of `tidb_tpu` is, but for
    the allow-lists."""
    bad = []
    for m in MODS:
        for t, _n, in_func, ln in IMPORTS[m]:
            if in_func and _is_executor(m) and not _is_executor(t) \
                    and not t.startswith(LAZY_BELOW):
                bad.append(f"{_where(m, ln)} imports {t} inside a function")
            if not in_func or not _is_executor(t) or t == m:
                continue
            if (m, t) in FUNCTION_IMPORT_ALLOWED:
                continue
            if _is_executor(m) and frozenset(
                    (_short(m), _short(t))) in CYCLE_ALLOWED:
                continue
            bad.append(f"{_where(m, ln)} imports {t} inside a function")
    return bad


OPTION = re.compile(r"^tidb_tpu_[a-z0-9_]+$")


def _declared_options():
    """The keys of `DEFAULT_VARS` (tidb_tpu/sysvars.py) → {name: times
    written as a key}."""
    seen = {}
    for node in ast.walk(TREES["tidb_tpu.sysvars"]):
        targets = node.targets if isinstance(node, ast.Assign) else \
            [node.target] if isinstance(node, ast.AnnAssign) else []
        if any(isinstance(t, ast.Name) and t.id == "DEFAULT_VARS"
               for t in targets) and isinstance(node.value, ast.Dict):
            for k in node.value.keys:
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    seen[k.value] = seen.get(k.value, 0) + 1
    return seen


def _option_reads():
    """Every place the source READS a `tidb_tpu_*` name off a variables
    mapping: `x.get("tidb_tpu_…"[, default])`, `x["tidb_tpu_…"]`, and the
    accessors of tidb_tpu/sysvars.py (`var_on`, `var_int`, `var_str`).
    → [(module, line, name, has a default of its own)]"""
    out = []
    for m, tree in TREES.items():
        for node in ast.walk(tree):
            name, own_default = None, False
            if isinstance(node, ast.Call) and node.args:
                f = node.func
                fname = f.attr if isinstance(f, ast.Attribute) else \
                    f.id if isinstance(f, ast.Name) else ""
                if fname == "get":
                    a0 = node.args[0]
                    own_default = len(node.args) > 1
                elif fname in ("var_on", "var_int", "var_str") and \
                        len(node.args) > 1:
                    a0 = node.args[1]
                else:
                    continue
                if isinstance(a0, ast.Constant) and \
                        isinstance(a0.value, str):
                    name = a0.value
            elif isinstance(node, ast.Subscript) and \
                    isinstance(node.ctx, ast.Load) and \
                    isinstance(node.slice, ast.Constant) and \
                    isinstance(node.slice.value, str):
                name = node.slice.value
            if name is not None and OPTION.match(name):
                out.append((m, node.lineno, name, own_default))
    return out


def every_option_is_declared_once_and_read_without_a_default():
    declared = _declared_options()
    bad = [f"{name} is declared {n} times" for name, n in declared.items()
           if n != 1]
    for m, ln, name, own_default in _option_reads():
        if name not in declared:
            bad.append(f"{_where(m, ln)} reads {name}, which "
                       f"DEFAULT_VARS does not declare")
        if own_default:
            bad.append(f"{_where(m, ln)} reads {name} with a default of "
                       f"its own")
    return bad


def tidb_tpu_scheduler_is_no_option():
    bad = []
    for m, path in MODS.items():
        for i, line in enumerate(open(path), 1):
            if re.search(r"tidb_tpu_scheduler\b", line):
                bad.append(f"{_where(m, i)}: {line.strip()}")
    return bad


def delta_imports_nothing_above_the_cache():
    """The cache's write path runs statements through what its readers
    left it (`device_cache.note_reader`), not by importing the executor
    above it."""
    below = {EXECUTOR, EXECUTOR + ".device_cache", EXECUTOR + ".device_emit",
             EXECUTOR + ".scan", EXECUTOR + ".scheduler",
             EXECUTOR + ".zonemap"}
    m = EXECUTOR + ".delta"
    return [f"{_where(m, ln)} imports {t}" for t, _n, _f, ln in IMPORTS[m]
            if _is_executor(t) and t not in below]


RULES = [lower_layers_import_no_executor,
         executor_graph_has_no_cycle,
         no_private_name_crosses_an_executor_module,
         no_executor_import_inside_a_function,
         every_option_is_declared_once_and_read_without_a_default,
         tidb_tpu_scheduler_is_no_option,
         delta_imports_nothing_above_the_cache]


@pytest.mark.parametrize("rule", RULES, ids=lambda r: r.__name__)
def test_layering(rule):
    bad = rule()
    assert not bad, f"{len(bad)} against `{rule.__name__}`:\n  " + \
        "\n  ".join(bad)


def test_the_allow_lists_are_short_and_say_why():
    assert len(CYCLE_ALLOWED) <= 2
    for why in list(CYCLE_ALLOWED.values()) + \
            list(FUNCTION_IMPORT_ALLOWED.values()):
        assert len(why) > 20
