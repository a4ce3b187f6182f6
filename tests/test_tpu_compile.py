"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The Pallas kernels and the fused batched engine are lowered and compiled
by the TPU compiler for a ``v5e:2x2`` topology description, at the widths
the engine runs.  This catches what interpret mode cannot: block shapes
Mosaic refuses, and kernels that need more VMEM than the scoped limit.
Nothing runs; a passing compile says nothing about results or times.

The topology is described inside a module fixture (never at import), so
only the worker that runs this file loads the TPU library.  The
persistent compilation cache is off here: entries written for a
described chip cannot be read back without one.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine
from repro.graphs.layouts import lattice_graph
from repro.kernels.crossing_angle_sum import crossing_angle_stats
from repro.kernels.occlusion_pairs import occlusion_count
from repro.kernels.segment_crossing import crossing_count
from repro.kernels.strip_reversal import strip_reversal_stats

N_STRIPS = 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cap", [512, 2560])
@pytest.mark.parametrize("with_angle", [False, True])
def test_strip_reversal_compiles(one_chip, cap, with_angle):
    f32 = _sds(one_chip, (N_STRIPS, cap), jnp.float32)
    i32 = _sds(one_chip, (N_STRIPS, cap), jnp.int32)
    compiled = jax.jit(lambda *a: strip_reversal_stats(
        *a, ideal=1.2217305, with_angle=with_angle, interpret=False)).lower(
            f32, f32, f32, i32, i32, i32).compile()
    _assert_kernel(compiled)


def test_occlusion_compiles(one_chip):
    n = 10_240                       # |V| = 1e4 padded to the 512 tile
    f32 = _sds(one_chip, (n,), jnp.float32)
    i32 = _sds(one_chip, (n,), jnp.int32)
    compiled = jax.jit(lambda *a: occlusion_count(
        *a, radius=0.5, interpret=False)).lower(f32, f32, i32).compile()
    _assert_kernel(compiled)


def test_segment_crossing_compiles(one_chip):
    n = 20_480                       # E ~ 2e4 padded to the 256 tile
    f32 = _sds(one_chip, (n,), jnp.float32)
    i32 = _sds(one_chip, (n,), jnp.int32)
    compiled = jax.jit(lambda *a: crossing_count(*a, interpret=False)).lower(
        f32, f32, f32, f32, i32, i32, i32).compile()
    _assert_kernel(compiled)


def test_crossing_angle_compiles(one_chip):
    n = 20_480
    f32 = _sds(one_chip, (n,), jnp.float32)
    i32 = _sds(one_chip, (n,), jnp.int32)
    compiled = jax.jit(lambda *a: crossing_angle_stats(
        *a, ideal=1.2217305, interpret=False)).lower(
            f32, f32, f32, f32, f32, i32, i32, i32).compile()
    _assert_kernel(compiled)


def test_evaluate_layouts_compiles(one_chip):
    """The fused batched engine at B=32, |V|=1e4 (the batch-scoring
    shape), planned from real lattice layouts."""
    n_v, batch = 10_000, 32
    pos, edges = lattice_graph(n_v)
    rng = np.random.default_rng(1)
    sigma = 0.3 * 100.0 / np.sqrt(n_v)
    layouts = pos + rng.normal(0, sigma, (batch,) + pos.shape).astype(
        np.float32)
    plan = engine.plan_readability(layouts, edges, n_strips=N_STRIPS)
    compiled = engine.evaluate_layouts.lower(
        plan, _sds(one_chip, layouts.shape, jnp.float32),
        _sds(one_chip, edges.shape, jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2 ** 30
