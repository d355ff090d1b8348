"""Real-width TPU compiles of the main-path kernels, for a described v5e.

Nothing runs here. Each test lowers and compiles one kernel for a TPU v5e
that is described (`jax.experimental.topologies`), not attached, so that a
Mosaic lowering the chip's compiler refuses, or a block that overflows
VMEM, fails here instead of on the chip. Widths are the served path's:
B=16 lanes, R=32, M=128, K=10, d=128, the visited bitset of N=2^20 rows
and a scan block of V=4096 gathered rows.

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler, and every test worker imports this
file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

B, R, M, K, D = 16, 32, 128, 10, 128
S, T, W, V = 2, 2, 2, 2            # clause slots, terms, label words, values
PQ_SLOTS = 96                      # d=128 PQ: 32 subspaces x 3 levels
N = 1 << 20


@pytest.fixture(scope="module")
def sds():
    """ShapeDtypeStruct factory on one chip of a described v5e:2x2, with
    the persistent compile cache off (a compile for a described chip is
    written to it but cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None
    return compiled


def _program(sds):
    from repro.filters.compile import FilterProgram

    i32, f32, b = jnp.int32, jnp.float32, jnp.bool_
    return FilterProgram(sds((B, S), i32), sds((B, S, W), jnp.uint32),
                         sds((B, S), f32), sds((B, S), f32),
                         sds((B, S), i32), sds((B, S), b), sds((B, S), i32),
                         sds((B, S), b), sds((B, T), b))


def _prep(sds, precision):
    from repro.quant.codecs import Int8Prep, PQPrep

    if precision == "int8":
        return Int8Prep(sds((B, D), jnp.int8), sds((B,), jnp.float32),
                        sds((B,), jnp.float32))
    return PQPrep(sds((B, PQ_SLOTS, 256), jnp.float32),
                  sds((B,), jnp.float32))


@pytest.mark.parametrize("precision", ["float32", "int8", "pq"])
def test_fused_step_compiles(sds, precision):
    from repro.kernels.fused_step import fused_step
    from repro.quant.codecs import QuantGather

    tail = [sds((B, R), jnp.int32), sds((B, R), jnp.bool_), _program(sds),
            sds((B, R, W), jnp.uint32), sds((B, R, V), jnp.float32),
            sds((B, M), jnp.float32), sds((B, M), jnp.int32),
            sds((B, K), jnp.float32), sds((B, K), jnp.int32)]
    q = sds((B, D), jnp.float32)
    if precision == "float32":
        _compile(lambda q, x, *a: fused_step(q, x, *a),
                 q, sds((B, R, D), jnp.float32), *tail)
        return
    width, dt = (D, jnp.int8) if precision == "int8" else (PQ_SLOTS,
                                                          jnp.int32)
    quant = QuantGather(_prep(sds, precision), sds((B, R, width), dt),
                        sds((B, R), jnp.float32))
    _compile(lambda q, qt, *a: fused_step(q, None, *a, quant=qt,
                                          precision=precision),
             q, quant, *tail)


@pytest.mark.parametrize("precision", ["float32", "int8"])
def test_persistent_multi_step_compiles(sds, precision):
    from repro.core.state import SearchConfig, SearchState
    from repro.kernels.persistent_step import persistent_multi_step

    i32, f32, b = jnp.int32, jnp.float32, jnp.bool_
    cfg = SearchConfig(k=K, queue_size=M, degree=R,
                       precision=None if precision == "float32" else precision)
    rows = sds((N, 128), f32 if precision == "float32" else i32)
    qprep = None if precision == "float32" else _prep(sds, precision)
    lane = sds((B,), i32)
    state = SearchState(
        sds((B, M), f32), sds((B, M), i32), sds((B, M), b), sds((B, M), b),
        sds((B, K), f32), sds((B, K), i32), sds((B, (N + 31) // 32),
                                                jnp.uint32),
        lane, lane, lane, sds((B, 4), i32), lane, sds((B,), f32), lane,
        sds((B,), b), sds((B,), f32), lane, lane)
    _compile(lambda q, p, rw, ax, nb, bud, st, rem, qp: persistent_multi_step(
        cfg, q, p, rw, ax, nb, bud, st, rem, None, qp, steps=8, n_values=V,
        has_gt=False),
        sds((B, D), f32), _program(sds), rows, sds((N, 128), jnp.uint32),
        sds((N, 128), i32), lane, state, sds((), i32), qprep)


def test_scan_distance_compiles_at_scan_width(sds):
    from repro.kernels.distance import sqdist_masked

    v = 4096
    _compile(lambda q, x, m: sqdist_masked(q, x, m),
             sds((B, D), jnp.float32), sds((B, v, D), jnp.float32),
             sds((B, v), jnp.bool_))
