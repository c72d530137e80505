"""The main path's device programs compile for a TPU v5e — asked of the TPU
compiler that is installed here, with no chip attached — and `chip_smoke.py`
keeps its contract on the CPU.

How the programs get here. The engine builds its device programs from a
plan and a slab geometry (`_FragmentProgram`, `TreeProgram`,
`_FusedFinalizeProgram`, `_AggMergeProgram`), and running a statement at a
real slab (8M rows) on the CPU backend costs most of a minute. So the
statements of `chip_smoke.py` run ONCE at a toy size under a recording
`jax.jit`, which keeps, for every program launched, the program object and
the shapes it was called with. Each is then rebuilt through its own
constructor at the real geometry — one full lineitem slab of
`DEFAULT_MAX_SLAB_ROWS` rows with its build sides in proportion (orders a
quarter, customer a fortieth: the deployment at SF≈1.4, one probe slab), and
the stack of SF=10's 8 slab partials for the merge/finalize — and compiled
for a described `v5e:2x2` device. The toy is large enough (256K rows) that
the compressed layouts come out as at SF=10 (pack / dict / delta per column),
except that SF=10's wider join keys pack wider; the aligned-join build
closures (`device_cache._lut/_probe/_gather`) are not rebuilt. The smoke
itself runs those on the chip.

`on_tpu()` sees the CPU here, so it is steered to True IN THE TEST — the
programs compiled are the chip's variants (donated merge inputs, f32 for
DOUBLE) — never through an option of the program.

One file on purpose: only one process may hold the TPU compiler's library,
and the worker that gets this file is the one that loads it. The topology is
described inside a module-scoped fixture (never at import, never autouse),
and nothing here starts a child process.
"""

import json
import os

import numpy as np
import pytest

TOY_ROWS = 1 << 18          # lineitem rows of the recording run (one toy slab)
HBM_BYTES = 16 * 10 ** 9    # one v5e chip
SF10_SLABS = 8              # ceil(60,012,150 / DEFAULT_MAX_SLAB_ROWS)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this image
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


class _Recorder:
    """Stands in for `jax.jit` during the toy run: jits as usual, and
    keeps (owner object, method name, argument shapes) of every distinct
    call signature of a bound-method program."""

    def __init__(self, jax):
        self.jax = jax
        self.real_jit = jax.jit
        self.calls = []     # (owner, method name, args as ShapeDtypeStructs)

    def __call__(self, fn, **kw):
        jitted = self.real_jit(fn, **kw)
        # the program gives jit a thin wrapper that carries its name
        # (jax_env.named_jit): the bound method is behind it
        fn = getattr(fn, "__wrapped__", fn)
        owner = getattr(fn, "__self__", None)
        if owner is None:
            return jitted
        rec, jax, seen = self, self.jax, set()

        def call(*args):
            shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x),
                                               jax.numpy.result_type(x))
                if hasattr(x, "dtype") else x, args)
            if str(shapes) not in seen:
                seen.add(str(shapes))
                rec.calls.append((owner, fn.__name__, shapes))
            return jitted(*args)
        call.lower = jitted.lower
        return call


@pytest.fixture(scope="module")
def recorded():
    """Run the smoke's statements once at toy size with the chip's program
    variants, recording every program launch. → list of calls."""
    from tidb_tpu.executor import device_cache as dc
    from tidb_tpu.executor import agg_slabs, compile_cache
    from tidb_tpu.ops import jax_env
    from tidb_tpu.session import Engine
    from tidb_tpu.tools import tpch_shaped as T
    import chip_smoke

    jax = jax_env.jax
    rec = _Recorder(jax)
    mp = pytest.MonkeyPatch()
    compile_cache._COMPILE_CACHE.clear()
    agg_slabs._SPEC_CACHE.clear()
    try:
        mp.setattr(jax_env, "on_tpu", lambda: True)
        mp.setattr(jax, "jit", rec)
        eng = Engine()
        eng.global_vars["tidb_enable_auto_analyze"] = False
        T.load(eng, T.generate(TOY_ROWS, seed=42))
        s = eng.new_session()
        s.vars.update(tidb_tpu_row_threshold=1, tidb_tpu_strict="on")
        for sql in (T.Q1, T.Q3, T.Q6):
            assert s.query(sql).rows and s.last_engine == "tpu"
        w = chip_smoke.WRITE
        s.execute("INSERT INTO lineitem VALUES (10.00, 12345.67, 0.06, "
                  f"0.02, 'N', 'O', '{w['ship']}', 0)")
        assert s.query(T.Q6).rows and s.last_engine == "tpu"   # delta slab
        # ... and the join tree over a delta generation of both tables: a
        # new order with its lineitem in, the written rows out again
        # (masks, the anchor's delta slab, the aligned join following)
        s.execute("INSERT INTO orders VALUES (65543, '1995-01-01', "
                  "'1', 0)")
        s.execute("INSERT INTO lineitem VALUES (10.00, 12345.67, 0.06, "
                  f"0.02, 'N', 'O', '{w['ship']}', 65543)")
        assert s.query(T.Q3).rows and s.last_engine == "tpu"
        s.execute("DELETE FROM lineitem WHERE l_orderkey = 65543")
        s.execute("DELETE FROM orders WHERE o_orderkey = 65543")
        assert s.query(T.Q3).rows and s.last_engine == "tpu"
        eng.close()
    finally:
        mp.undo()
        # programs built under the steering must not serve later tests
        compile_cache._COMPILE_CACHE.clear()
        agg_slabs._SPEC_CACHE.clear()
        dc.clear()
    return rec.calls


def _scaled(jax, tree, factor, sharding, axis=-1):
    """Every array leaf of `tree`, its `axis` grown by `factor`, placed on
    the described chip."""
    def grow(x):
        if not isinstance(x, jax.ShapeDtypeStruct):
            return x
        shape = list(x.shape)
        if shape:
            shape[axis] *= factor
        return jax.ShapeDtypeStruct(tuple(shape), x.dtype, sharding=sharding)
    return jax.tree.map(grow, tree)


def _scaled_live(jax, rows, factor, sharding):
    """A slab's liveness as a program takes it: the length of a live
    prefix (a count, which does not grow with the slab) or, from a delta
    generation, a mask of the slab's rows (which does)."""
    def grow(x):
        if not isinstance(x, jax.ShapeDtypeStruct):
            return x
        by = factor if x.dtype == np.dtype(bool) and x.shape else 1
        return _scaled(jax, x, by, sharding)
    return jax.tree.map(grow, rows)


def _scaled_cols(jax, cols, factor, sharding):
    """A scan's column dict: each column is (values, validity) or a packed
    (words, mask_words[, dictvals | delta base]) — the first two grow with
    the slab, a dictionary or a base does not."""
    return {i: tuple(_scaled(jax, leaf, factor if k < 2 else 1, sharding)
                     for k, leaf in enumerate(col))
            for i, col in cols.items()}


def _rebuild_tree(owner, factor):
    """A recorded `TreeProgram` again at the real slab geometry."""
    from tidb_tpu.executor.eligibility import walk_joins
    from tidb_tpu.executor.tree_fragment import TreeProgram
    return TreeProgram(
        owner.plan,
        # (slab capacity, slabs[, capacity of a raw delta slab])
        {k: (c[0] * factor, c[1]) + tuple(d * factor for d in c[2:])
         for k, c in owner.caps.items()},
        owner.group_cap,
        [owner.join_cfgs[id(n)] for n in walk_joins(owner.plan)],
        owner.agg_key_bounds, owner.scan_layouts, owner.pairs_out,
        owner.pair_cap)


def _rebuild(owner, factor):
    """The same program through its own constructor at the real slab
    geometry → (program, jitted entry point by method name)."""
    from tidb_tpu.executor import agg_slabs
    from tidb_tpu.executor.tree_fragment import TreeProgram
    if isinstance(owner, agg_slabs._FragmentProgram):
        p = agg_slabs._FragmentProgram(
            owner.chain, owner.used_cols, owner.in_types,
            owner.slab_cap * factor, owner.group_cap, owner.key_bounds,
            owner.has_distinct, owner.layouts, owner.pair_cap)
        return {"_partial": p.partial, "_merge": p.merge}
    if isinstance(owner, TreeProgram):
        return {"_run": _rebuild_tree(owner, factor).run}
    if isinstance(owner, agg_slabs._FusedFinalizeProgram):
        p = agg_slabs._FusedFinalizeProgram(owner.agg_root, owner.order_root,
                                           owner.group_cap)
        return {"_run": p.run}
    if isinstance(owner, agg_slabs._AggMergeProgram):
        p = agg_slabs._AggMergeProgram(owner.root, owner.group_cap)
        return {"_merge": p.merge}
    return None


_COMPILED = {}      # (id(owner), method) → compiled: two tests read Q1's and Q6's


def _compile_all(calls, kinds, one_chip, monkeypatch):
    """Rebuild + compile every recorded call of the given owner kinds.
    → [(label, CompiledMemoryStats)]"""
    return [(label, compiled.memory_analysis()) for _owner, label, compiled
            in _compile_calls(calls, kinds, one_chip, monkeypatch)]


def _compile_calls(calls, kinds, one_chip, monkeypatch):
    """→ [(owner, label, compiled program)]"""
    from tidb_tpu.executor import agg_slabs
    from tidb_tpu import sysvars
    from tidb_tpu.executor.tree_fragment import TreeProgram
    from tidb_tpu.ops import jax_env
    jax = jax_env.jax
    monkeypatch.setattr(jax_env, "on_tpu", lambda: True)
    factor = sysvars.DEFAULT_MAX_SLAB_ROWS // TOY_ROWS
    out = []
    for owner, method, shapes in calls:
        if not isinstance(owner, kinds):
            continue
        label = f"{type(owner).__name__}.{method}"
        if (id(owner), method) in _COMPILED:
            out.append((owner, label, _COMPILED[id(owner), method]))
            continue
        entry = _rebuild(owner, factor)[method]
        if isinstance(owner, agg_slabs._FragmentProgram) \
                and method == "_partial":
            cols, n_rows, preps = shapes
            args = (_scaled_cols(jax, cols, factor, one_chip),
                    _scaled_live(jax, n_rows, factor, one_chip),
                    _scaled(jax, preps, 1, one_chip))
        elif isinstance(owner, TreeProgram):
            scans, rows, preps, *rest = shapes
            args = (tuple(_scaled_cols(jax, c, factor, one_chip)
                          for c in scans),
                    _scaled_live(jax, rows, factor, one_chip),
                    _scaled(jax, preps, 1, one_chip),
                    *(_scaled(jax, r, factor, one_chip) for r in rest))
        else:
            # merge / finalize: SF=10's 8 slab partials stacked on axis 0
            args = _scaled(jax, shapes, SF10_SLABS, one_chip, axis=0)
        compiled = _COMPILED[id(owner), method] = \
            entry.lower(*args).compile()
        out.append((owner, label, compiled))
    return out


def _fits(stats):
    for label, m in stats:
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes + m.generated_code_size_in_bytes)
        assert need < HBM_BYTES, f"{label} needs {need} bytes of HBM"


def test_chain_partials_compile_at_a_full_slab(recorded, one_chip,
                                               monkeypatch):
    """Q1 and Q6: scan → in-trace compressed decode → filter → partial
    aggregate over one 8M-row slab."""
    from tidb_tpu.executor import agg_slabs
    from tidb_tpu.executor import delta
    calls = [c for c in recorded if c[1] == "_partial"]
    # the one raw program is the delta slab's: Q6 again after the INSERT,
    # at the delta slab's own capacity; and that read took the base slab's
    # liveness as a MASK (the same program, a second variant)
    raw = [c for c in calls if not c[0].layouts]
    assert [c[0].slab_cap for c in raw] == [delta.delta_capacity(TOY_ROWS)]
    assert any(np.dtype(bool) == getattr(c[2][1], "dtype", None)
               and c[2][1].shape == (TOY_ROWS,) for c in calls
               if c[0].layouts), "no base slab ran under a liveness mask"
    stats = _compile_all(calls, agg_slabs._FragmentProgram, one_chip,
                         monkeypatch)
    assert len(stats) >= 4     # Q1, Q6, Q6 masked, Q6 over the delta slab
    _fits(stats)


def test_global_aggregate_partial_has_no_slot_axis(recorded, one_chip,
                                                   monkeypatch):
    """Q6 has no GROUP BY: its partial over a full 8M-row slab reduces into
    ONE slot, so the optimized program has no loop (the blocked masked
    reduce was a `while` per state: five of them, 97 of a 106 ms launch on
    the chip, PERF.md §6 PR 27) and nothing of stage `agg` carries a slot
    axis of `_pow2`'s floor. Q1's partial is the counter-case: its 12
    key-bounded slots are there, and its ONE loop is the slot sums'
    contraction over blocks of rows (`ops/segment.slot_sums`), with the
    one convolution of the program in its body."""
    import re
    from tidb_tpu.executor import agg_slabs
    calls = [c for c in recorded if c[1] == "_partial"]
    compiled = _compile_calls(calls, agg_slabs._FragmentProgram, one_chip,
                              monkeypatch)

    def agg_slot_axes(text, slots):
        """Operations of stage `agg` whose result's last dimension is
        `slots`."""
        dim = re.compile(r" = [^ ]*\[(?:\d+,)*%d\]" % slots)
        return sum(1 for line in text.splitlines()
                   if re.search(r'op_name="[^"]*/agg/', line)
                   and dim.search(line))

    glob = [(o, c) for o, _label, c in compiled
            if not o.chain[0].group_exprs]
    keyed = [(o, c) for o, _label, c in compiled if o.chain[0].group_exprs]
    assert glob and keyed, "the toy run launched Q1's and Q6's partials"
    for owner, c in glob:
        text = c.as_text()
        assert owner.group_cap == 1
        assert 'op_name="' in text and "/agg/" in text, \
            "the optimized HLO names no stage: this guard reads nothing"
        assert not re.search(r"\bwhile\(", text)
        assert agg_slot_axes(text, 1024) == 0
    for owner, c in keyed:
        text = c.as_text()
        assert owner.group_cap == 12 and owner.key_bounds
        assert len(re.findall(r"\bwhile\(", text)) == 1
        assert len(re.findall(r" convolution\(", text)) == 1
        assert agg_slot_axes(text, 12) > 0


def test_delta_decode_has_no_slab_wide_scan(recorded, one_chip, monkeypatch):
    """Q1's and Q6's partials read `l_shipdate`, a `delta` column, over a
    full 8M-row slab: its decode scans inside blocks of `DELTA_BLOCK` rows
    by shifted adds, so the optimized program holds no `reduce-window`
    (what a `cumsum` is to the TPU compiler) over as many elements as a
    slab has rows, in any width, and no loop outside stage `agg` (whose one
    is the slot sums' contraction over blocks). The one 64-bit cumsum over
    the slab was `%reduce-window.1`: 8.8 ms of every launched slab, two
    thirds of the device's seconds (PERF.md §6, PR 29). What is left scans
    the blocks' totals."""
    import re
    from tidb_tpu.chunk import compress
    from tidb_tpu.executor import agg_slabs
    from tidb_tpu import sysvars
    calls = [c for c in recorded if c[1] == "_partial"]
    compiled = _compile_calls(calls, agg_slabs._FragmentProgram, one_chip,
                              monkeypatch)
    slab = sysvars.DEFAULT_MAX_SLAB_ROWS
    checked = 0
    for owner, _label, c in compiled:
        deltas = [lay for lay in (owner.layouts or {}).values()
                  if lay is not None and lay.kind == "delta"]
        if not deltas:
            continue
        assert {compress.delta_scan(lay, slab) for lay in deltas} == {"int32"}
        text = c.as_text()
        assert re.search(r'op_name="[^"]*/decode/', text), \
            "the optimized HLO names no decode stage: this guard reads nothing"
        for line in text.splitlines():
            if re.search(r"\bwhile\(", line):
                assert re.search(r'op_name="[^"]*/agg/', line), line[:300]
            m = re.search(r" = (.*?) reduce-window\(", line)
            if m is None:
                continue
            sizes = [int(np.prod([int(d) for d in dims.split(",")]))
                     for dims in re.findall(r"\[([\d,]+)\]", m.group(1))]
            assert max(sizes, default=0) <= slab // compress.DELTA_BLOCK + 1, \
                line[:200]
        checked += 1
    assert checked >= 2, "Q1's and Q6's partials both read l_shipdate"


def test_fused_pipeline_compiles_at_a_full_probe_slab(recorded, one_chip,
                                                      monkeypatch):
    """Q3: scan → filter → FK-aligned join probe → partial aggregate over
    one 8M-row lineitem slab with its orders build side."""
    from tidb_tpu.executor.tree_fragment import TreeProgram
    stats = _compile_all(recorded, TreeProgram, one_chip, monkeypatch)
    assert stats, "Q3 launched no fused pipeline program"
    _fits(stats)


def test_finalize_and_merge_compile_over_sf10_partials(recorded, one_chip,
                                                       monkeypatch):
    """The whole-query tails: fused finalize (merge → finalize → ORDER BY)
    and the delta merge, over 8 stacked slab partials, inputs donated."""
    from tidb_tpu.executor import agg_slabs
    calls = [c for c in recorded if c[1] != "_partial"]
    stats = _compile_all(
        calls, (agg_slabs._FusedFinalizeProgram, agg_slabs._AggMergeProgram,
                agg_slabs._FragmentProgram), one_chip, monkeypatch)
    labels = {label for label, _ in stats}
    assert "_FusedFinalizeProgram._run" in labels, labels
    assert labels & {"_FragmentProgram._merge", "_AggMergeProgram._merge"}, \
        f"no merge program was launched by the delta path: {labels}"
    _fits(stats)


def test_a_statement_program_is_one_body_in_a_loop(recorded, one_chip,
                                                   monkeypatch):
    """Q6 warm at SF=8 after zone-map pruning: three full 8M-row slabs and
    their merge in ONE program (`_StatementProgram`). The slabs' body is
    the body of ONE loop — a copy of it a slab read 113 s of cold compile
    and 20 s of every later run's set-up for Q1 over six slabs on the
    chip's host (PERF.md §6, PR 39) — so the program's code and
    temporaries stay near one slab's, and the stages keep their names
    under the statement program's."""
    import functools
    import re
    from tidb_tpu.executor import agg_slabs
    from tidb_tpu import sysvars
    from tidb_tpu.ops import jax_env
    jax = jax_env.jax
    calls = [c for c in recorded if c[1] == "_partial" and c[0].layouts
             and not c[0].chain[0].group_exprs
             and getattr(c[2][1], "dtype", None) != np.dtype(bool)]
    assert calls, "the toy run launched no Q6 partial over a live prefix"
    (owner, _label, one), = _compile_calls(
        calls[:1], agg_slabs._FragmentProgram, one_chip, monkeypatch)
    factor = sysvars.DEFAULT_MAX_SLAB_ROWS // TOY_ROWS
    p = agg_slabs._FragmentProgram(
        owner.chain, owner.used_cols, owner.in_types,
        owner.slab_cap * factor, owner.group_cap, owner.key_bounds,
        owner.has_distinct, owner.layouts, owner.pair_cap)
    cols, n_rows, preps = calls[0][2]
    slab = (_scaled_cols(jax, cols, factor, one_chip),
            _scaled_live(jax, n_rows, factor, one_chip))
    args = (_scaled(jax, preps, 1, one_chip), _stacked(jax, slab, 6),
            None, (jax.ShapeDtypeStruct((3,), np.int32, sharding=one_chip),))
    sp = agg_slabs._StatementProgram(
        "stmt_chain", functools.partial(agg_slabs.ChainSlabs._slab_body, p),
        None, p._merge, agg_slabs.ChainSlabs.control, True, "toy", args)
    assert set(sp.like) == {"ngs", "ng", "keys", "states"}
    compiled = sp.run.lower(*args).compile()    # the build's own executable
    m, m1 = compiled.memory_analysis(), one.memory_analysis()
    _fits([("_StatementProgram._run", m)])
    assert m.temp_size_in_bytes < 2 * m1.temp_size_in_bytes, (m, m1)
    assert m.generated_code_size_in_bytes < \
        2 * m1.generated_code_size_in_bytes, (m, m1)
    text = compiled.as_text()
    assert "jit_stmt_chain_" in text.splitlines()[0], text[:200]
    assert len(re.findall(r"\bwhile\(", text)) == 1
    for stage in ("decode", "filter", "agg", "merge"):
        assert re_search_stage(text, stage), stage


def _stacked(jax, slab, n_slabs: int):
    """One slab's arguments (shapes) as a statement program takes the base
    slabs': every leaf that a slab has of its own — what grew with the
    slab, and a live-row count — as `device_cache.Stacked` over the
    column's ONE array (`SlabColumn.stack`'s shape), a dictionary or a
    delta base as it is."""
    from tidb_tpu.executor import device_cache as dc, device_emit
    node = dc._node(dc.Stacked)

    def stack(x):
        if not isinstance(x, jax.ShapeDtypeStruct):
            return x
        if x.shape and x.shape[-1] < TOY_ROWS // 64:
            return x            # (a dictionary, a delta base: shared)
        return node(jax.ShapeDtypeStruct(
            (n_slabs,) + device_emit.folded(x.shape), x.dtype,
            sharding=x.sharding), None, 0, tuple(x.shape))
    return jax.tree.map(stack, slab)


def _slab_sized_ops(text: str, op: str, rows: int, rank=None):
    """→ [(enclosing computation, line)] of the `op`s of optimized HLO
    `text` whose result holds at least `rows` elements (in `rank`
    dimensions, if given)."""
    import re
    found, comp = [], ""
    for line in text.splitlines():
        head = re.match(r"\s*(?:ENTRY )?%([^ ]+) \(.*\) -> .* \{$", line)
        if head:
            comp = head.group(1)
        m = re.search(r" = ([^ ]+) %s\(" % re.escape(op), line)
        if m is None:
            continue
        dims = re.search(r"\[([\d,]*)\]", m.group(1))
        dims = [int(d) for d in dims.group(1).split(",") if d] if dims \
            else []
        if int(np.prod(dims)) >= rows and rank in (None, len(dims)):
            found.append((comp, line.strip()[:160]))
    return found


# temporaries of the SAME two programs at the parent (PR 44's tree: the
# slabs a pytree each, a `lax.switch` a turn → one `conditional` whose
# branches copy a slab), compiled there for the same described chip. This
# tree's read 301,810,688 and 131,480,576 (compiled here, PR 46): the copies'
# buffers went, the staged words of the narrow columns came — within 5%
PARENT_TEMP_BYTES = {"stmt_chain": 287_900_160,
                     "stmt_fused": 242_004_992}


def test_a_six_slab_statement_program_indexes_its_slabs_in_place(
        recorded, one_chip, monkeypatch):
    """Q1 (`stmt_chain`) over six full 8M-row base slabs and Q3
    (`stmt_fused`: masks, the FK-aligned match mask and gathered build
    columns in the fact's row space, the raw delta slab behind) as the
    statement programs the chip runs at SF=8: the base slabs are ONE array
    a leaf and the loop indexes it — no `conditional` (PR 39's
    `lax.switch`: a copy of a slab's every array a turn, 12% of
    `qstream8.sf8`'s device time; the parent's Q1 program holds 24 such
    copies of a million elements or more, each a 1-D slab array), no copy
    of a slab's 1-D array (what is left are the contraction's own
    relayouts, rank 4, the same thirteen the parent has), and every
    `dynamic-slice` of a stack INSIDE a fused computation: the 32-bit
    column in the fusion that unpacks it, the narrower packed words each
    in a fusion of their own that hands the slab's words to the fast
    memory space (`S(1)`) its several readers then share. The stack
    folds a 1-D leaf to (slabs, rows / 128, 128): the compiler then keeps
    the slab axis OUTSIDE the tile for every dtype — a bool mask comes out
    `pred[6,65536,128]{2,1,0:T(8,128)(4,1)}`, six slabs take the bytes of
    six — where (slabs, rows) is tiled `T(8,128)` with the slab axis
    inside: padded to eight and read with a stride."""
    import functools
    import re
    from tidb_tpu.executor import agg_slabs
    from tidb_tpu import sysvars
    from tidb_tpu.executor.tree_fragment import TreeProgram
    from tidb_tpu.ops import jax_env
    jax = jax_env.jax
    monkeypatch.setattr(jax_env, "on_tpu", lambda: True)
    factor = sysvars.DEFAULT_MAX_SLAB_ROWS // TOY_ROWS
    slab_rows, n_slabs = sysvars.DEFAULT_MAX_SLAB_ROWS, 6
    picks = (jax.ShapeDtypeStruct((n_slabs,), np.int32, sharding=one_chip),)

    # -- Q1: a chain over six slabs under live-row counts ------------------
    owner, _m, (cols, n_rows, preps) = next(
        c for c in recorded if c[1] == "_partial" and c[0].layouts
        and c[0].chain[0].group_exprs
        and getattr(c[2][1], "dtype", None) != np.dtype(bool))
    p = agg_slabs._FragmentProgram(
        owner.chain, owner.used_cols, owner.in_types,
        owner.slab_cap * factor, owner.group_cap, owner.key_bounds,
        owner.has_distinct, owner.layouts, owner.pair_cap)
    slab = (_scaled_cols(jax, cols, factor, one_chip),
            _scaled_live(jax, n_rows, factor, one_chip))
    chain_args = (_scaled(jax, preps, 1, one_chip),
                  _stacked(jax, slab, n_slabs), None, picks)
    chain = agg_slabs._StatementProgram(
        "stmt_chain", functools.partial(agg_slabs.ChainSlabs._slab_body, p),
        None, p._merge, agg_slabs.ChainSlabs.control, True, "toy-q1",
        chain_args)

    # -- Q3: the join tree's statement program over a delta generation -----
    sp, _m, (shared, base, delta, _picks) = next(
        c for c in recorded if isinstance(c[0], agg_slabs._StatementProgram))
    assert isinstance(sp.body.args[0], TreeProgram) and delta is not None
    bodies = [functools.partial(
        agg_slabs.TreeSlabs._slab_body, _rebuild_tree(b.args[0], factor),
        b.args[1]) for b in (sp.body, sp.dbody)]
    si, sr, pv, ai, nested = shared
    grow = functools.partial(_scaled, jax, factor=factor, sharding=one_chip)
    shared = (tuple(c if c is None else _scaled_cols(jax, c, factor, one_chip)
                    for c in si),
              _scaled_live(jax, sr, factor, one_chip),
              _scaled(jax, pv, 1, one_chip), ai, nested)

    def own(slab_arg):
        cols, live, sliced = slab_arg
        return ({i: [_scaled_cols(jax, {0: t}, factor, one_chip)[0]
                     for t in ts] for i, ts in cols.items()},
                grow(live), grow(sliced))
    fused_args = (shared, _stacked(jax, own(base), n_slabs), own(delta),
                  picks)
    fused = agg_slabs._StatementProgram(
        "stmt_fused", bodies[0], bodies[1], sp.tail, sp.control, sp.small,
        "toy-q3", fused_args)

    for kind, prog, args in (("stmt_chain", chain, chain_args),
                             ("stmt_fused", fused, fused_args)):
        assert prog.said == {"slab_pick": "index"}
        compiled = prog.run.lower(*args).compile()
        text, m = compiled.as_text(), compiled.memory_analysis()
        _fits([(kind, m)])
        assert f"jit_{kind}_" in text.splitlines()[0], text[:200]
        assert not re.search(r"\bconditional\(", text), kind
        assert not _slab_sized_ops(text, "copy", slab_rows // 32, rank=1), \
            kind
        # (a slab's narrowest leaf: its validity words, a bit a row)
        slices = _slab_sized_ops(text, "dynamic-slice", slab_rows // 32)
        assert len(slices) >= 8, f"{kind}: the loop reads no stack"
        outside = [s for s in slices if "fused_computation" not in s[0]]
        assert not outside, (kind, outside[:3])
        assert m.temp_size_in_bytes <= 1.05 * PARENT_TEMP_BYTES[kind], \
            (kind, m.temp_size_in_bytes)
        # the stacked bool masks: the slab axis outside the tile, unpadded
        if kind == "stmt_fused":
            masks = re.findall(
                r"pred\[%d,%d,128\]\{2,1,0:T\(8,128\)\(4,1\)"
                % (n_slabs, slab_rows // 128), text)
            assert masks, "no stacked bool mask among the parameters"
            assert not re.search(r"pred\[%d,%d\]" % (n_slabs, slab_rows),
                                 text)

    # -- Q1's slab program over the SAME stacks (a digest's first execution,
    # the `slabs:*` plans): which slab is a device scalar beside the stack
    # (`SlabColumn.at`), the program indexes the stack in its own fusions —
    # no slab is copied out
    from tidb_tpu.executor import device_cache as dc
    at = dc._node(dc.Stacked)
    row = jax.ShapeDtypeStruct((), np.int32, sharding=one_chip)
    is_stack = lambda x: isinstance(x, dc.Stacked)      # noqa: E731
    cols_at = jax.tree.map(
        lambda x: at(x.a, row, 0, x.shape) if is_stack(x) else x,
        chain_args[1][0], is_leaf=is_stack)
    compiled = p.partial.lower(cols_at, slab[1], chain_args[0]).compile()
    text = compiled.as_text()
    assert not re.search(r"\bconditional\(", text)
    assert not _slab_sized_ops(text, "copy", slab_rows // 32, rank=1)
    slices = _slab_sized_ops(text, "dynamic-slice", slab_rows // 32)
    assert len(slices) >= 8
    assert not [s for s in slices if "fused_computation" not in s[0]]


def test_a_short_leaf_takes_whole_lanes_in_its_stack(one_chip):
    """Slabs whose leaf is no multiple of 128 long (a small table's: 1000
    rows): the stack pads the leaf to whole lanes, (slabs, 8, 128) — the
    slab axis outside the tile, six slabs in the bytes of six folds —
    where (slabs, 1, rows) would put a dimension of ONE inside the tile,
    padded to eight. A per-slab program (the slab's row beside the stack)
    and a statement program's loop read a slab of it inside the consuming
    fusion."""
    import re
    from tidb_tpu.executor import device_cache as dc, device_emit
    from tidb_tpu.ops.jax_env import jax, jnp, lax
    n, n_slabs = 1000, 6
    assert device_emit.folded((n,)) == (8, 128)
    stack = jax.ShapeDtypeStruct((n_slabs, 8, 128), np.int32,
                                 sharding=one_chip)
    mask = jax.ShapeDtypeStruct((n_slabs, 8, 128), np.bool_,
                                sharding=one_chip)
    picks = (jax.ShapeDtypeStruct((n_slabs,), np.int32, sharding=one_chip),)
    row = jax.ShapeDtypeStruct((), np.int32, sharding=one_chip)
    node = dc._node(dc.Stacked)

    def loop(base, picks, one):
        def turn(c, k):
            v, m = dc.in_place(base, picks, k)
            assert v.shape == m.shape == (n,)
            return c + jnp.sum(jnp.where(m, v, 0)), None
        v, m = dc.in_place(one)
        return lax.scan(turn, jnp.sum(jnp.where(m, v, 1)),
                        jnp.arange(n_slabs, dtype=jnp.int32))[0]
    compiled = jax.jit(loop).lower(
        (node(stack, None, 0, (n,)), node(mask, None, 0, (n,))), picks,
        (node(stack, row, 0, (n,)), node(mask, row, 0, (n,)))).compile()
    text, m = compiled.as_text(), compiled.memory_analysis()
    assert re.search(r"s32\[6,8,128\]\{2,1,0:T\(8,128\)", text)
    assert not re.search(r"\bconditional\(|\[6,1,1000\]", text)
    # two int32 stacks and two masks, a byte a row, and the small vectors
    assert m.argument_size_in_bytes <= 2 * 6 * 1024 * (4 + 1) + 4096, m
    outside = [s for s in _slab_sized_ops(text, "dynamic-slice", n)
               if "fused_computation" not in s[0]]
    assert not outside, outside


def test_a_slab_goes_into_its_stack_in_place(one_chip):
    """The fill of a stack (`device_cache._fill`, PR 46): one 8M-row slab
    of a 32-bit leaf written into the DONATED six-slab stack. Compiled for
    the v5e the output IS the argument's buffer (`input_output_alias`), the
    program holds no copy of the stack and no temporaries to speak of: a
    fill never holds two stacks, and the slab's 1-D → folded form costs no
    pass of its own."""
    import re
    from tidb_tpu.executor import device_cache as dc
    from tidb_tpu import sysvars
    from tidb_tpu.ops.jax_env import jax
    rows, n = sysvars.DEFAULT_MAX_SLAB_ROWS, 6
    first, put = dc._stack_programs(n, (rows,), "uint32")
    stack = jax.ShapeDtypeStruct((n, rows // 128, 128), np.uint32,
                                 sharding=one_chip)
    slab = jax.ShapeDtypeStruct((rows,), np.uint32, sharding=one_chip)
    row = jax.ShapeDtypeStruct((), np.int32, sharding=one_chip)
    assert jax.eval_shape(first, slab).shape == stack.shape
    compiled = put.lower(stack, slab, row).compile()
    text, m = compiled.as_text(), compiled.memory_analysis()
    assert "jit_slab_stack_" in text.splitlines()[0]
    assert re.search(r"input_output_alias=\{[^}]*\{\}: \(0,", text), \
        text[:400]
    assert not _slab_sized_ops(text, "copy", rows)
    assert m.alias_size_in_bytes == n * rows * 4, m
    assert m.temp_size_in_bytes <= 1 << 20, m


def test_masks_are_born_in_a_filled_stacks_layout(one_chip):
    """A table's first commit makes its liveness masks stacked with ONE
    program (`device_emit.emit_alive_stack`; no fill). Compiled for the
    v5e at six slabs of 8M rows the result has the layout a filled stack
    of masks has — the slab axis outside the tile, unpadded: a byte a row
    — and the program holds no temporaries to speak of."""
    import re
    from tidb_tpu.executor import device_emit
    from tidb_tpu import sysvars
    from tidb_tpu.ops.jax_env import jax
    rows, n = sysvars.DEFAULT_MAX_SLAB_ROWS, 6
    born = device_emit.emit_alive_stack([rows] * 5 + [17], rows)
    assert born.shape == (n, rows // 128, 128) and born.dtype == bool
    assert int(born[5].sum()) == 17 and bool(born[4].all())
    prog = device_emit._DELTA_PROGRAMS[("tombstone", ("init", rows, n))]
    compiled = prog.lower(jax.ShapeDtypeStruct(
        (n,), np.int32, sharding=one_chip)).compile()
    text, m = compiled.as_text(), compiled.memory_analysis()
    assert re.search(r"pred\[%d,%d,128\]\{2,1,0:T\(8,128\)\(4,1\)"
                     % (n, rows // 128), text)
    assert m.output_size_in_bytes <= n * rows + 4096, m
    assert m.temp_size_in_bytes <= 1 << 20, m


def re_search_stage(text: str, stage: str):
    import re
    return re.search(r'op_name="jit\(stmt_chain_[0-9a-f]{8}\)/[^"]*/?%s/'
                     % stage, text)


def test_sorted_runs_grouping_lowers_for_the_chip_with_two_sorts(one_chip):
    """Grouping by sorted runs at the real geometry — six slabs of 8M rows,
    millions of groups. The shared sort program holds exactly TWO sorts
    (the rows by their packed key word with the aggregate's argument as
    payload; the run ends, one uint32 operand) and no loop; it is only
    LOWERED here, because the TPU compiler takes two to three minutes over
    the int64 comparator (PERF.md §6, PR 28). A statement's finalize over
    the sorted rows — states by blocked cumsums, keys unpacked, top-10 by
    selection — holds NO sort and is compiled: its one loop is the
    selection's."""
    import re

    from tidb_tpu.ops import factorize as F
    from tidb_tpu.ops import jax_env
    from tidb_tpu.ops.segment import SortedRuns
    jax, jnp = jax_env.jax, jax_env.jnp
    n, cap = 6 * (1 << 23), 1 << 21
    bounds = [(0, 12_002_429), (8036, 10589)]

    def arr(rows, dtype):
        return jax.ShapeDtypeStruct((rows,), dtype, sharding=one_chip)

    def sort_rows(k1, k2, live, v, m):
        return F.sort_rows(F.pack_words([(k1, live), (k2, live)], bounds),
                           live, [v, m])

    text = jax.jit(sort_rows).lower(
        arr(n, jnp.int64), arr(n, jnp.int32), arr(n, jnp.bool_),
        arr(n, jnp.int64), arr(n, jnp.bool_)).as_text()
    assert len(re.findall(r"stablehlo\.sort", text)) == 2, text[:2000]
    assert "stablehlo.while" not in text

    def finalize(word, v, m, ends, n_runs):
        runs = SortedRuns(ends, n_runs, cap)
        v = jnp.where(m, v, 0)
        mask = (1 << 30) - 1
        limbs = [runs.sum(v & mask), runs.sum((v >> 30) & mask),
                 runs.sum(v >> 60)]
        seen = runs.sum(m) > 0
        keys = F.unpack_words([runs.at_ends(word)], bounds,
                              [jnp.int64, jnp.int32])
        idx, n_out = F.topn_select(
            [(limbs[2], seen), (limbs[1], seen), keys[1], keys[0]],
            [True, True, False, False], runs.slot_live, 10)
        return [k[idx] for k, _ in keys], [a[idx] for a in limbs], n_out

    compiled = jax.jit(finalize).lower(
        arr(n, jnp.int64), arr(n, jnp.int64), arr(n, jnp.bool_),
        arr(n, jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert not re.search(r"\bsort\(", text)
    assert len(re.findall(r"\bwhile\(", text)) == 1
    _fits([("sorted-runs finalize", compiled.memory_analysis())])


def test_q18s_nested_finalize_gathers_a_word_and_the_key_word(one_chip):
    """`lgstream1.sf2`'s dearest program at its geometry — 2²⁴ sorted rows
    (two slabs at the first's capacity), 3.2M run ends, SUM(l_quantity)
    over a value of 13 bits under a wide result: the three limbs and the
    count are ONE int64 word (`ops/segment.run_sums`), so the program
    holds two int64 gathers at the run ends, the word's and the key
    word's (an int64 is two uint32 planes: four gather instructions; a
    gather a state made ten), and one int64 scan over the rows (its
    blocks' and their totals' `reduce-window`s: four; sixteen before).
    With no width known every non-constant state is a word again."""
    import re

    from tidb_tpu import types as T
    from tidb_tpu.executor import device_emit
    from tidb_tpu.expression import ColumnRef
    from tidb_tpu.expression.aggfuncs import AggDesc, build_agg
    from tidb_tpu.ops import jax_env
    from tidb_tpu.planner.physical import PhysHashAgg
    jax, jnp = jax_env.jax, jax_env.jnp
    n, cap = 1 << 24, 3_200_000
    root = PhysHashAgg.__new__(PhysHashAgg)
    root.group_exprs = [ColumnRef(0, T.bigint(True))]
    root.aggs = [AggDesc("sum", [ColumnRef(1, T.decimal(15, 2, True))])]
    aggs = [build_agg(d) for d in root.aggs]

    def arr(rows, dtype):
        return jax.ShapeDtypeStruct((rows,), dtype, sharding=one_chip)

    rows = {"words": [arr(n, jnp.int64)],
            "payloads": [arr(n, jnp.int64), arr(n, jnp.bool_)],
            "ends": arr(n, jnp.int32),
            "n_runs": jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)}

    def compiled(arg_bits):
        return jax.jit(lambda r: device_emit.emit_runs_finalize(
            root, None, aggs, cap, ((1, 12_000_000),), [jnp.int64], r,
            arg_bits)).lower(rows).compile()

    def count(text):
        return (len(re.findall(r" gather\(", text)),
                len(re.findall(r" reduce-window\(", text)))

    packed = compiled((13,))
    assert count(packed.as_text()) == (4, 4)
    assert not re.search(r"\bsort\(", packed.as_text())
    _fits([("Q18's nested finalize", packed.memory_analysis())])
    # limbs 0 and 1 (30-bit fields), the signed limb 2, the count, the key
    assert count(compiled(()).as_text()) == (10, 16)


def test_shard_map_aggregate_step_compiles_for_four_chips(topo, monkeypatch):
    """The distributed Q3-shaped step (filter → all_to_all exchange of
    both sides → per-shard sort-probe join → two-phase aggregate) on a
    mesh of the described chips. At 4096 probe rows, not a real size: this
    program's TPU compile grows with its row count (12 s here, 170 s at
    64K rows, 206 s at 256K — PERF.md, open questions), so tier-1 only
    asks that the step partitions and lowers for four chips at all."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from tidb_tpu.ops import jax_env
    from tidb_tpu.parallel.dist_query import AXIS, build_agg_join_step
    jax, jnp = jax_env.jax, jax_env.jnp
    monkeypatch.setattr(jax_env, "on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices), (AXIS,))
    assert mesh.devices.size == 4
    n, b = 1 << 12, 1 << 10
    step = build_agg_join_step(mesh, bucket_cap=n // 4, group_cap=32,
                               filter_limit=0.7)
    f = jax_env.device_float_dtype()
    assert f == jnp.float32
    row = NamedSharding(mesh, P(AXIS))

    def arr(rows, dtype):
        return jax.ShapeDtypeStruct((rows,), dtype, sharding=row)

    compiled = step.lower(
        arr(n, jnp.int64), arr(n, f), arr(n, f), arr(n, jnp.bool_),
        arr(b, jnp.int64), arr(b, jnp.int64), arr(b, f),
        arr(b, jnp.bool_)).compile()
    assert "all-to-all" in compiled.as_text()
    _fits([("agg_join_step (per device)", compiled.memory_analysis())])


# ---------------------------------------------------------------------------
# chip_smoke.py's control flow, guarded at no chip time
# ---------------------------------------------------------------------------

def test_chip_smoke_refuses_the_cpu_backend(capsys):
    """On the CPU backend the smoke stops at the device phase, before any
    data is made: it raises (a non-zero exit from `__main__`) and never
    prints a result line."""
    import chip_smoke
    with pytest.raises(chip_smoke.SmokeFailed, match="needs a TPU"):
        chip_smoke.main(["--sf", "0.01"])
    out = capsys.readouterr().out
    phases = [json.loads(line)["phase"] for line in out.splitlines()]
    assert phases == ["device"]
    assert '"ok"' not in out


def test_chip_smoke_phases_pass_on_cpu_with_device_phase_bypassed(
        capsys, monkeypatch):
    """Load → serve → check → memory through the wire server against the
    numpy reference, with the device phase replaced BY THIS TEST and
    `on_tpu()` steered so `tidb_tpu_engine=auto` takes the device path
    (on the CPU backend it would — rightly — fail every device check)."""
    import chip_smoke
    from tidb_tpu.ops import jax_env
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    monkeypatch.setattr(chip_smoke, "phase_device", lambda chips: device)
    monkeypatch.setattr(jax_env, "on_tpu", lambda: True)
    assert chip_smoke.main(["--sf", "0.01", "--seed", "7"]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    recs = [json.loads(line) for line in lines[:-1]]
    served = [r for r in recs if r["phase"] == "serve"]
    assert [r["statement"] for r in served] == ["Q1", "Q3", "Q5", "Q6"]
    for r in served:
        assert "device:yes" in r["explain_analyze"] and r["fallbacks"] == 0
        assert r["cold"]["launches"] > 0 and r["warm"]["compiles"] == 0
        assert r["warm"]["h2d_bytes"] == 0
    writes = [r for r in recs if r["phase"] == "write"]
    assert [r["count"] for r in writes] == ["60013", "60012"]
    assert not [r for r in recs if r["phase"] == "check_failed"]


def test_chip_smoke_fails_when_a_statement_stays_off_the_device(
        capsys, monkeypatch):
    """The device path cannot hide: with the device phase bypassed but
    `on_tpu()` telling the truth, `auto` serves from the CPU engine,
    every answer is still right — and the smoke fails."""
    import chip_smoke
    monkeypatch.setattr(chip_smoke, "phase_device", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    assert chip_smoke.main(["--sf", "0.01"]) == 1
    out = capsys.readouterr().out
    assert '"ok"' not in out
    failed = [json.loads(line)["what"] for line in out.splitlines()
              if json.loads(line)["phase"] == "check_failed"]
    assert any("no device program launched" in w for w in failed)
    assert not any("differ from the numpy reference" in w for w in failed)
