"""Central JAX import point.

Everything in tidb_tpu that touches jax must import it from here so that
configuration is applied before the first trace:

* x64 — int64 is the physical type of DECIMAL columns (types/__init__.py),
  so x64 is a correctness requirement, not a preference; on TPU int64 lanes
  are emulated as 2×int32 which is fine for the bandwidth-bound relational
  kernels.

* the persistent compile cache — a statement's device programs are compiled
  per (plan shape, slab shape), and a process that restarts would otherwise
  compile every one of them again. JAX decides ONCE, at the first compile of
  the process, whether a cache is in use, so the directory is placed here,
  at import, before any jit or jnp call can run. Where
  `JAX_COMPILATION_CACHE_DIR` is set, JAX's own handling of it is left alone
  and nothing is set in code; otherwise the cache lives in
  `<checkout>/.jax_cache` (git-ignored) — a fixed path, because the path is
  part of what a later process must find again. Nothing here initialises a
  backend to decide. A process that must not write there (the test suite)
  opts out with `JAX_ENABLE_COMPILATION_CACHE=0`, as tests/conftest.py does.

  The program runs with `jax_persistent_cache_min_compile_time_secs = 0`
  (JAX's default is 1 s; an explicit `JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`
  wins). A cold statement launches dozens of small programs — decode, gather,
  finalize, the eager glue between fragments — each under a second to
  compile and together most of a cold statement; with the default floor only
  the few large fragment programs would be kept and a restarted server would
  still pay for all the rest.
"""

from __future__ import annotations

import functools
import hashlib
import os
from pathlib import Path

# Harmless if already set; tests additionally force a CPU mesh via conftest.
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

jax.config.update("jax_enable_x64", True)

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir",
                      str(Path(__file__).resolve().parents[2] / ".jax_cache"))
if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def compile_cache_dir():
    """Where this process keeps compiled programs, or None when the cache
    is switched off (`JAX_ENABLE_COMPILATION_CACHE=0`)."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    return jax.config.jax_compilation_cache_dir


def program_name(kind: str, sig: str) -> str:
    """`<kind>_<sig8>`: what a jitted program is called in the profile's
    "XLA Modules" line, in HLO `op_name`s and in `launch` spans — its kind
    (partial_fused, merge, finalize, ...) and eight hex digits of its
    compile-cache signature."""
    return f"{kind}_{hashlib.sha1(sig.encode()).hexdigest()[:8]}"


def named_jit(fn, name: str, **jit_kwargs):
    """`jax.jit(fn)` under `name` instead of `fn.__name__`: JAX names the
    XLA module after the function it is given (`jit__partial` for every
    fragment program alike), so the function it is given is a thin wrapper
    that carries the program's own name. Trace-time only."""
    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = name
    return jax.jit(program, **jit_kwargs)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def backend() -> str:
    return jax.default_backend()


def on_tpu() -> bool:
    return backend() == "tpu"


# Device float dtype policy: TPU has no native f64. DOUBLE columns compute in
# f32 on TPU; SUM/AVG accumulate through the exact fixed-point two-float
# path (ops/segment.segment_sum_accurate — ~48-bit sums, ~1e-12 relative at
# SF=10); exact aggregates ride DECIMAL/int64 which is unaffected.
def device_float_dtype():
    return jnp.float32 if on_tpu() else jnp.float64


__all__ = ["jax", "jnp", "lax", "backend", "on_tpu", "device_float_dtype",
           "shard_map", "compile_cache_dir", "named_jit", "program_name"]
