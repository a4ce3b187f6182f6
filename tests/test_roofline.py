"""Roofline extractor: HLO collective parser + the cost_analysis loop
semantics the extrapolation relies on."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.roofline.analysis import collective_bytes, _shape_bytes


def test_shape_bytes():
    assert _shape_bytes("f32[128,256]") == 128 * 256 * 4
    assert _shape_bytes("bf16[8]") == 16
    assert _shape_bytes("(f32[4,4], s32[2])") == 64 + 8
    assert _shape_bytes("pred[16]") == 16


def test_collective_parser():
    hlo = """
  %ag = f32[64,128]{1,0} all-gather(f32[4,128] %x), replica_groups={{0,1,2,3}}, dimensions={0}
  %ar = bf16[1024]{0} all-reduce(bf16[1024] %y), replica_groups=[16,32]<=[512], to_apply=%add
  %cp = f32[256]{0} collective-permute(f32[256] %z), source_target_pairs={{0,1}}
  %other = f32[8] add(f32[8] %a, f32[8] %b)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == (3 / 4) * 64 * 128 * 4
    assert out["all-reduce"] == 2 * (31 / 32) * 1024 * 2
    assert out["collective-permute"] == 256 * 4
    assert out["total"] == (out["all-gather"] + out["all-reduce"]
                            + out["collective-permute"])


def test_cost_analysis_loop_semantics():
    """The fact the roofline extrapolation is built on: while-loop bodies
    are counted ONCE, independent of trip count (so a scanned L-layer
    stack under-reports by ~L, and the Python-loop / single-trip twins in
    the roofline variants are required). Straight-line code is exact
    (2mnk per dot)."""
    m = k = n = 256
    a = jax.ShapeDtypeStruct((m, k), jnp.float32)
    b = jax.ShapeDtypeStruct((k, n), jnp.float32)

    def inline(x, y):
        return ((x @ y) @ y.T) @ y                    # 3 dots

    flops_inline = jax.jit(inline).lower(a, b).compile().cost_analysis()[
        "flops"]
    assert abs(flops_inline - 3 * 2 * m * k * n) / flops_inline < 0.05

    def with_scan(x, y, length):
        def body(c, _):
            return jnp.tanh(c @ y), None
        out, _ = jax.lax.scan(body, x, None, length=length)
        return out

    f1 = jax.jit(lambda x, y: with_scan(x, y, 1)).lower(
        a, b).compile().cost_analysis()["flops"]
    f8 = jax.jit(lambda x, y: with_scan(x, y, 8)).lower(
        a, b).compile().cost_analysis()["flops"]
    # body counted once regardless of trip count
    assert f1 >= 2 * m * k * n
    assert abs(f8 - f1) / f1 < 0.05


def test_analyze_cell_small_mesh():
    from jax.sharding import AxisType

    from repro.roofline.analysis import analyze_cell

    if len(jax.devices()) < 2:
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
    else:
        mesh = jax.make_mesh((1, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
    terms = analyze_cell("xdeepfm", "serve_p99", mesh, "test")
    assert terms.compute_s > 0
    assert terms.memory_s > 0
    assert terms.dominant in ("compute", "memory", "collective")
    assert terms.flops_global > terms.model_flops * 0.2
