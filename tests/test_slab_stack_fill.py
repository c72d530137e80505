"""The fill of a column's stack (`device_cache.SlabColumn.stack`, PR 46):
it holds a SLAB twice, never a column. The stack's own bytes must be free
before the first slab goes in, so every slab but one waits on the host
while the stack is allocated, and each is written into the DONATED stack
(the installed JAX honours `donate_argnums` on the CPU backend: a donated
`dynamic_update_slice` returns the buffer it was given)."""
import gc
import threading
import weakref

import numpy as np
import pytest

from tidb_tpu.executor import device_cache as dc
from tidb_tpu.ops.jax_env import jax, jnp

N, ROWS = 6, 1 << 15


def _live() -> int:
    return sum(a.nbytes for a in jax.live_arrays())


def _column(n=N, rows=ROWS, holes=()):
    """A packed column of `n` slabs: words, mask words, and ONE dictionary
    every slab's tuple shares."""
    dictvals = jnp.arange(16, dtype=jnp.int64)
    return dc.SlabColumn(
        [None if s in holes else
         (jnp.full(rows, s + 1, dtype=jnp.uint32),
          jnp.full(rows // 32, 100 + s, dtype=jnp.uint32), dictvals)
         for s in range(n)])


def _check(col, holes=()):
    for s in range(col.n_base):
        if s in holes:
            assert col[s] is None
            continue
        words, mask, dictvals = col[s]
        assert words.shape == (ROWS,) and mask.shape == (ROWS // 32,)
        assert np.all(np.asarray(words) == s + 1)
        assert np.all(np.asarray(mask) == 100 + s)
        assert dictvals.shape == (16,)


@pytest.fixture
def writes(monkeypatch):
    """Every write of a slab into a stack, with the live device bytes
    before it (stack and slab both there) and after."""
    seen, real = [], dc._write_slab

    def write(program, *args):
        seen.append(_live())
        out = real(program, *args)
        del args
        seen.append(_live())
        return out
    monkeypatch.setattr(dc, "_write_slab", write)
    return seen


def test_a_fill_holds_a_slab_twice_and_never_a_column(writes):
    gc.collect()
    before = _live()
    col = _column()
    refs = [weakref.ref(a) for s in range(N) for a in col[s][:2]]
    column = _live() - before
    slab = ROWS * 4                       # (the wider leaf's)
    assert column >= N * slab
    col.stack("t")
    # two stacked leaves, a write a slab; the dictionary stays ONE array
    assert len(writes) == 2 * 2 * N
    assert max(writes) - before <= column + slab + 4096, \
        "a list lay beside its stack"
    # (what a whole-column `jnp.stack` holds: the column twice)
    assert max(writes) - before < 2 * column - slab
    assert col.is_stacked
    gc.collect()
    assert all(r() is None for r in refs), \
        "a per-slab array is still reachable"
    assert _live() - before <= column + 4096
    _check(col)
    # one array a leaf, folded: (slabs, rows / 128, 128)
    stacks = [a for _s, a in col.arrays()]
    assert sorted(a.shape for a in stacks) == sorted(
        [(N, ROWS // 128, 128), (N, ROWS // 32 // 128, 128), (16,)])


def test_a_column_with_holes_fills_its_resident_slabs(writes):
    col = _column(holes=(0, 3))
    col.stack("t")
    assert len(writes) == 2 * 2 * (N - 2)
    assert col.holes() == frozenset((0, 3))
    assert col.rows_of((1, 2, 4, 5)) == (0, 1, 2, 3)
    _check(col, holes=(0, 3))


def test_a_reader_waits_a_fill_out(monkeypatch):
    col = _column()
    entered, go, got = threading.Event(), threading.Event(), []
    real = dc._write_slab

    def write(program, *args):
        entered.set()
        assert go.wait(30)
        return real(program, *args)
    monkeypatch.setattr(dc, "_write_slab", write)
    filler = threading.Thread(target=col.stack, args=("t",))
    filler.start()
    assert entered.wait(30)
    reader = threading.Thread(target=lambda: got.append(
        (col.n_base, np.asarray(col[4][0]))))
    reader.start()
    reader.join(0.2)
    assert reader.is_alive() and not got, \
        "a reader took a slab the fill had in transit"
    go.set()
    filler.join(60)
    reader.join(60)
    assert got[0][0] == N and np.all(got[0][1] == 5)


def test_a_fill_that_fails_loses_the_column_and_drops_its_table(monkeypatch):
    """A fill dies between two writes (the device out of memory, an
    interrupt): the arrays went with it — the column reads as nothing
    resident, no reader hangs on it, and `SlabPicks.of`, the one place a
    column is stacked, drops the table's entries so that the next open
    builds them anew."""
    import types
    col = _column()
    calls, dropped, real = [0], [], dc._write_slab

    def write(program, *args):
        calls[0] += 1
        if calls[0] == N + 1:
            raise MemoryError("out of device memory")
        return real(program, *args)
    monkeypatch.setattr(dc, "_write_slab", write)
    monkeypatch.setattr(dc, "invalidate", dropped.append)
    ent = types.SimpleNamespace(owners=None, lost=set())
    with pytest.raises(MemoryError):
        dc.SlabPicks(ent, range(N), 7).of(col)
    assert dropped == [7]
    assert not col.is_stacked and col.n_base == N
    assert col.holes() == frozenset(range(N)) and col[0] is None
    col.stack("t")                      # (nothing to stack: no-op)
    assert not col.is_stacked


def test_a_stack_lies_on_its_slabs_device():
    """An entry pinned to another pool device than the default: the stack
    is allocated where its first slab lies, the others follow it there."""
    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("one device")
    there = devices[-1]
    col = dc.SlabColumn([(jax.device_put(np.full(ROWS, s, np.int32), there),)
                         for s in range(3)])
    col.stack("t")
    (stack,) = [a for _s, a in col.arrays()]
    assert stack.devices() == {there}
    assert [int(np.asarray(col[s][0])[0]) for s in range(3)] == [0, 1, 2]


def test_a_stack_is_committed_iff_its_slabs_were():
    """On one device the cache uploads uncommitted arrays, and every
    program compiles once for them: a stack filled from such slabs stays
    uncommitted (the fill's uploads name no device), so no program sees
    the same column under two commitments — which would compile it twice,
    the second time inside some later window."""
    col = _column()
    assert not col[0][0].committed
    col.stack("t")
    assert not any(a.committed for _s, a in col.arrays())
    _check(col)
    there = jax.devices()[0]
    pinned = dc.SlabColumn(
        [(jax.device_put(np.full(ROWS, s, np.int32), there),)
         for s in range(3)])
    pinned.stack("t")
    assert all(a.committed for _s, a in pinned.arrays())
