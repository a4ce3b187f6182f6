"""Equivalence tests for the SPerf optimization variants: every beyond-
paper perf knob must be output-identical to the baseline it replaces."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.geometry import segments_cross, segments_cross_bool


@settings(max_examples=50, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_bool_predicate_equivalent(seed):
    rng = np.random.default_rng(seed)
    pts = rng.uniform(-5, 5, size=(200, 8)).astype(np.float32)
    # inject degenerate cases: shared endpoints, collinear
    pts[0] = [0, 0, 1, 1, 0, 0, 1, 1]
    pts[1] = [0, 0, 1, 0, 1, 0, 2, 0]
    pts[2] = [0, 0, 2, 2, 1, 1, 3, 3]
    args = [jnp.asarray(pts[:, i]) for i in range(8)]
    a = segments_cross(*args)
    b = segments_cross_bool(*args)
    assert bool(jnp.all(a == b))


def test_compact_escn_equivalent():
    from repro.models.equivariant import (EquiformerConfig,
                                          equiformer_forward,
                                          init_equiformer_params)
    rng = np.random.default_rng(3)
    n, e = 20, 48
    batch = {
        "positions": jnp.asarray(rng.normal(size=(n, 3)), jnp.float32),
        "species": jnp.asarray(rng.integers(0, 4, n), jnp.int32),
        "edge_src": jnp.asarray(rng.integers(0, n, e), jnp.int32),
        "edge_dst": jnp.asarray(rng.integers(0, n, e), jnp.int32),
        "edge_mask": jnp.asarray(rng.random(e) > 0.1),
        "node_mask": jnp.ones(n, bool),
        "graph_id": jnp.zeros(n, jnp.int32),
    }
    cfg = EquiformerConfig(name="t", n_layers=2, d_hidden=16, l_max=4,
                           m_max=2, n_heads=4, edge_chunk=16)
    params = init_equiformer_params(cfg, jax.random.PRNGKey(0))
    base = equiformer_forward(params, batch, cfg)
    comp = equiformer_forward(
        params, batch, dataclasses.replace(cfg, compact_escn=True))
    np.testing.assert_allclose(np.asarray(base), np.asarray(comp),
                               rtol=1e-4)


@pytest.mark.xfail(reason="pre-existing (seed never ran this: module used "
                   "to error at collection on missing hypothesis): "
                   "jax.sharding.AxisType is absent from this jax version",
                   strict=False)
def test_sp_and_moe_hints_noop_on_single_device():
    # the sharding hints change layout, never values
    from repro.configs import get_arch
    from repro.models import transformer as tflib
    cfg = get_arch("llama4-scout-17b-a16e").smoke_config.with_mesh(1)
    params = tflib.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((2, 16), jnp.int32),
             "labels": jnp.ones((2, 16), jnp.int32)}
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with jax.set_mesh(mesh):
        base, _ = tflib.loss_fn(params, batch, cfg)
        hinted, _ = tflib.loss_fn(
            params, batch, dataclasses.replace(cfg, sp_activations=True,
                                               moe_hints=True))
    np.testing.assert_allclose(float(base), float(hinted), rtol=1e-6)


def test_scan_layers_off_matches_scan():
    """scan_layers=False must be the same *math* as the scan path.

    Root cause of the historical ~1.3e-3 divergence (this test used to be
    xfail'd): it is bf16 intermediate rounding at different XLA fusion
    boundaries, not an algorithmic difference.  ``lax.scan`` compiles its
    body as one XLA computation whose fused elementwise chains keep f32
    intermediates, while the unrolled Python loop materializes (rounds)
    every primitive's bf16 output; under jit the unrolled graph still
    fuses across layers where the scan body cannot.  Measured on this
    container: fp32 scan-vs-unrolled is bit-identical (diff exactly 0.0,
    eager and jit), bf16 diverges 1.3e-3 eager / 6e-4 jit, and
    ``remat`` on/off does not change the result.

    So the contract is split: fp32 asserts *exact* equality (the variants
    are op-for-op the same program), bf16 asserts a tolerance sized to a
    couple of bf16-rounding accumulation steps (ulp(6.0) in bf16 is
    ~3e-2; 4e-3 relative is well under one output ulp and ~3x the
    observed divergence).
    """
    from repro.configs import get_arch
    from repro.models import transformer as tflib
    cfg = get_arch("qwen3-4b").smoke_config.with_mesh(1)
    params = tflib.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((2, 16), jnp.int32),
             "labels": jnp.ones((2, 16), jnp.int32)}

    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    a32, _ = tflib.loss_fn(params, batch, cfg32)
    b32, _ = tflib.loss_fn(params, batch,
                           dataclasses.replace(cfg32, scan_layers=False))
    assert float(a32) == float(b32)

    a, _ = tflib.loss_fn(params, batch, cfg)
    b, _ = tflib.loss_fn(params, batch,
                         dataclasses.replace(cfg, scan_layers=False))
    np.testing.assert_allclose(float(a), float(b), rtol=4e-3)
