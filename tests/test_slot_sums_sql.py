"""Slot sums on the matrix unit, at the SQL surface: the benchmark's Q1, Q3
and Q5 over several slabs, over a delta slab with tombstones and through
WITH ROLLUP, with the contraction forced onto these small tables (the
threshold and the block size are module constants of `ops/segment.py`) —
text-equal to the benchmark's plain reference and to the host engine; and
the counter and the span tag that say which lowering a traced program took.
"""

import numpy as np
import pytest

from test_refresh_stream import (SCALE, SEED, SETTINGS, _SessionClient,
                                 _drive, _engine, _load)
from tidb_tpu.executor import device_cache as dc
from tidb_tpu.executor import agg_slabs, compile_cache
from tidb_tpu.ops import segment as seg
from tidb_tpu.util import timeline
from tidb_tpu.util.observability import REGISTRY


@pytest.fixture(scope="module")
def ds():
    return _load("datasets", "tpch_refresh")


@pytest.fixture(scope="module")
def shaped():
    return _load("datasets", "tpch_shaped")


@pytest.fixture
def on_the_matrix_unit(monkeypatch):
    """Every grouped partial of these tests contracts: blocks of 4,096
    rows, so a 16,384-row slab is a loop of four and the delta slab and a
    ROLLUP's tiling leave a tail."""
    monkeypatch.setattr(seg, "SLOT_SUM_MIN_WORK", 2)
    monkeypatch.setattr(seg, "SLOT_SUM_BLOCK", 4096)
    compile_cache._COMPILE_CACHE.clear()
    agg_slabs._SPEC_CACHE.clear()
    yield
    compile_cache._COMPILE_CACHE.clear()
    agg_slabs._SPEC_CACHE.clear()
    dc.clear()


def _lowerings() -> dict:
    return {dict(labels)["lowering"]: v
            for (name, labels), v in REGISTRY.counters.items()
            if name == "tidb_tpu_slot_sum_programs_total"}


def _grew(before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in _lowerings().items()
            if v != before.get(k, 0)}


def _text(rows):
    return [tuple(None if v is None else str(v) for v in r) for r in rows]


def test_q1_q3_q5_over_several_slabs(ds, shaped, on_the_matrix_unit,
                                     tmp_path):
    """Four lineitem slabs: every answer text-equal to the benchmark's
    reference (integer arithmetic on the scaled values) and to the host
    engine; Q1, Q3 and Q5 say `mxu`, Q6 (one slot) `flat`, once a TRACED
    program on the counter and on the `launch` span that traced it, and a
    warm statement says nothing."""
    data = shaped.generate(SCALE, SEED)
    want = shaped.reference(data)
    eng = _engine(ds, data)
    s = eng.new_session()
    try:
        s.vars["tidb_tpu_engine"] = "off"
        host = {q: _text(s.query(sql).rows)
                for q, sql in shaped.STATEMENTS.items()}
        s.vars.update(SETTINGS)
        # (rows of the piece matrix / rows at whole width, a group a word)
        expect = {"Q1": "mxu:24/88", "Q3": "mxu:8/24", "Q5": "mxu:8/24",
                  "Q6": "flat:6"}
        for q, sql in shaped.STATEMENTS.items():
            before, traces = _lowerings(), compile_cache.PROGRAM_TRACES
            timeline.start_global(str(tmp_path))
            try:
                rows = _text(s.query(sql).rows)
            finally:
                timeline.stop_global()
            assert s.last_engine == "tpu"
            assert rows == host[q] == [tuple(r) for r in want[q]], q
            tags = [e["args"]["slot_sums"] for e in timeline.last_events()
                    if e["ph"] == "X" and e["cat"] == "launch"
                    and "slot_sums" in e["args"]]
            lowering = expect[q].partition(":")[0]
            # one slab program traced (and the merge, which sums a
            # handful of partial slots and says `masked`)
            assert tags.count(expect[q]) == 1, (q, tags)
            grew = _grew(before)
            assert grew.pop(lowering) == 1, (q, grew)
            assert set(grew) <= {"masked"} and sum(grew.values()) \
                == len(tags) - 1 <= compile_cache.PROGRAM_TRACES - traces
            # the digest's second execution traces ONE statement program,
            # a body a surviving slab: the lowering is said once a body
            before = _lowerings()
            assert _text(s.query(sql).rows) == rows
            grew = _grew(before)
            assert 1 <= grew.pop(lowering) <= 4, (q, grew)
            assert set(grew) <= {"masked"}, (q, grew)
            # warm: nothing traces, so nothing is said
            before = _lowerings()
            timeline.start_global(str(tmp_path))
            try:
                assert _text(s.query(sql).rows) == rows
            finally:
                timeline.stop_global()
            assert _lowerings() == before
            assert not [e for e in timeline.last_events()
                        if "slot_sums" in e.get("args", {})]
    finally:
        eng.close()


def test_refresh_pairs_over_a_delta_slab_with_tombstones(
        ds, on_the_matrix_unit):
    """RF1 and RF2 read back by Q1, Q3 and Q6, six operations: the base
    slabs' masked variants and the delta slab's own shape all contract,
    every answer equal to the reference's state, nothing falls back."""
    data = ds.generate(SCALE, SEED)
    eng = _engine(ds, data)
    s = eng.new_session()
    s.vars.update(SETTINGS)
    before = _lowerings()
    fb0 = sum(v for (n, _l), v in REGISTRY.counters.items()
              if n == "tidb_tpu_device_fallbacks_total")
    try:
        traces, _ref = _drive(ds, _SessionClient(s), data, 6)
    finally:
        eng.close()
    assert not any(traces[2:]), traces
    grew = _grew(before)
    # Q1 and Q3, each plain, masked by liveness and at the delta slab's shape
    assert grew.get("mxu", 0) >= 6, grew
    assert fb0 == sum(v for (n, _l), v in REGISTRY.counters.items()
                      if n == "tidb_tpu_device_fallbacks_total")


ROLLUP = """SELECT l_returnflag, l_linestatus, SUM(l_quantity),
 SUM(l_extendedprice * (1 - l_discount)), AVG(l_discount), COUNT(*),
 COUNT(l_tax), MIN(l_quantity), MAX(l_extendedprice)
 FROM lineitem WHERE l_shipdate <= '1998-09-02'
 GROUP BY l_returnflag, l_linestatus WITH ROLLUP"""


def test_with_rollup_and_a_state_that_is_no_sum(ds, shaped,
                                                on_the_matrix_unit):
    """The ROLLUP tiling (three copies of the batch, sort-factorize ids)
    through the contraction, MIN and MAX beside it on their own path:
    every aggregate's state tuple is whole."""
    data = shaped.generate(SCALE, SEED)
    eng = _engine(ds, data)
    s = eng.new_session()
    try:
        s.vars["tidb_tpu_engine"] = "off"
        host = sorted(_text(s.query(ROLLUP).rows), key=repr)
        s.vars.update(SETTINGS)
        before = _lowerings()
        rows = sorted(_text(s.query(ROLLUP).rows), key=repr)
        assert s.last_engine == "tpu"
        assert rows == host and len(rows) == 6 + 3 + 1
        assert _grew(before).get("mxu", 0) >= 1
    finally:
        eng.close()


def test_the_contraction_under_vmap(on_the_matrix_unit):
    """`emit_batched` vmaps a partial over a member axis: the primitive
    keeps its answers with the slot ids and a validity batched."""
    from tidb_tpu.ops.jax_env import jax, jnp
    n, cap, members = 4096 + 33, 6, 3
    rng = np.random.default_rng(9)
    gid = rng.integers(0, cap + 1, (members, n)).astype(np.int32)
    valid = rng.random((members, n)) < 0.7
    v = rng.integers(-10 ** 15, 10 ** 15, n)

    def one(g, m):
        return seg.slot_sums(jnp, [seg.SumColumn(jnp.asarray(v), m),
                                   seg.SumColumn(None, m)], g, cap)
    sums, counts = jax.jit(jax.vmap(one))(jnp.asarray(gid),
                                          jnp.asarray(valid))
    for k in range(members):
        ok = valid[k] & (gid[k] < cap)
        want = np.zeros(cap, dtype=np.int64)
        np.add.at(want, gid[k][ok], v[ok])
        assert (np.asarray(sums[k]) == want).all()
        assert (np.asarray(counts[k])
                == np.bincount(gid[k][ok], minlength=cap)).all()


# ---------------------------------------------------------------------------
# the contraction cuts what a value can hold (PR 41)
# ---------------------------------------------------------------------------

def _widths() -> dict:
    return {dict(labels)["range"]: v
            for (name, labels), v in REGISTRY.counters.items()
            if name == "tidb_tpu_slot_sum_columns_total"}


WIDENING = {
    "chain": "SELECT a, COUNT(*), SUM(b) FROM t GROUP BY a ORDER BY a",
    "tree": "SELECT d.n, COUNT(*), SUM(t.b) FROM t JOIN d ON t.a = d.k "
            "GROUP BY d.n ORDER BY d.n",
}


@pytest.mark.parametrize("shape", sorted(WIDENING))
def test_an_append_past_a_power_of_two_gets_a_new_program(
        on_the_matrix_unit, shape):
    """`b` holds 13 bits when the programs are first compiled: its sum is
    two pieces. A row of 2⁵⁰ appended through the delta slab widens the
    cached bounds, the digest's next execution compiles a program whose
    piece matrix grew, and the answer is exact — over the base slabs and
    over the delta slab alike."""
    from test_delta_slabs import _engine as _t_engine, _entry, _oracle
    q = WIDENING[shape]
    eng, s = _t_engine()
    try:
        s.execute("CREATE TABLE d (k BIGINT PRIMARY KEY, n BIGINT)")
        s.execute("INSERT INTO d VALUES " + ",".join(
            f"({k}, {k % 4})" for k in range(40)))

        def run():
            with timeline.capture() as cap:
                rows = s.query(q).rows
            assert s.last_engine == "tpu"
            return rows, [e["args"]["slot_sums"] for e in cap.events
                          if e["ph"] == "X" and e["cat"] == "launch"
                          and e["args"].get("slot_sums", "")[:3] == "mxu"]

        def rows_of(tag):
            got, _, whole = tag[4:].partition("/")
            return int(got), int(whole)

        before = _widths()
        rows, tags = run()
        assert rows == _oracle(s, q) and tags, tags
        first = rows_of(tags[0])
        assert first[0] < first[1]
        assert _widths().get("bounded", 0) > before.get("bounded", 0)
        assert _widths().get("whole", 0) == before.get("whole", 0)
        run(), run()                # the statement program; warm
        assert run()[1] == []
        col = 1                     # t.b
        assert _entry(eng).bounds[col] == (0, 4999)
        declines = {k: v for k, v in REGISTRY.counters.items()
                    if k[0] == "tidb_tpu_delta_declines_total"}
        s.execute(f"INSERT INTO t VALUES (3, {2 ** 50}, 'k1')")
        rows, tags = run()
        assert rows == _oracle(s, q)
        ent = _entry(eng)
        assert ent.is_delta and ent.bounds[col][0] == 0 \
            and ent.bounds[col][1] >= 2 ** 50
        assert declines == {k: v for k, v in REGISTRY.counters.items()
                            if k[0] == "tidb_tpu_delta_declines_total"}
        # new programs, base slabs' and delta slab's: seven pieces where
        # two were
        assert tags and all(rows_of(t)[0] > first[0] for t in tags), tags
        assert all(rows_of(t)[1] == first[1] for t in tags)
        # a value inside the widened bounds mints nothing more
        s.execute(f"INSERT INTO t VALUES (4, {2 ** 50 - 7}, 'k2')")
        run(), run()
        s.execute(f"INSERT INTO t VALUES (5, {2 ** 49}, 'k2')")
        rows, tags = run()
        assert rows == _oracle(s, q) and tags == []
    finally:
        eng.close()
