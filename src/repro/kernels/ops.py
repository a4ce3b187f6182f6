"""Jit'd public wrappers around the Pallas kernels.

Handle padding to tile multiples, layout (SoA coordinate vectors), the
interpret-mode switch, and the layout->kernel-argument plumbing so callers
pass ``(pos, edges)`` like the pure-jnp API.

:func:`_interpret` is the ONE place that chooses interpret mode: the
Pallas interpreter on the CPU backend (validation), the compiled Mosaic
kernels on TPU, and an error anywhere else — a kernel route never runs
the interpreter silently on an accelerator.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.geometry import edge_endpoints, segment_theta
from repro.core.validate import BackendUnavailableError
from repro.kernels.crossing_angle_sum import crossing_angle_stats
from repro.kernels.occlusion_pairs import occlusion_count
from repro.kernels.segment_crossing import crossing_count
from repro.kernels.strip_reversal import strip_reversal_stats


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise BackendUnavailableError(
        f"the Pallas kernels run compiled on TPU or interpreted on CPU; "
        f"the default backend is {backend!r}")


def _pad1(a, n, fill):
    pad = n - a.shape[0]
    if pad <= 0:
        return a
    return jnp.concatenate([a, jnp.full((pad,), fill, a.dtype)])


def occlusion_count_op(pos, radius, *, valid=None, tile: int = 512):
    """N_c via the Pallas pairwise kernel."""
    pos = jnp.asarray(pos, jnp.float32)
    n = pos.shape[0]
    if valid is None:
        valid = jnp.ones(n, dtype=jnp.int32)
    else:
        valid = jnp.asarray(valid).astype(jnp.int32)
    n_pad = -(-n // tile) * tile
    x = _pad1(pos[:, 0], n_pad, 0.0)
    y = _pad1(pos[:, 1], n_pad, 0.0)
    ok = _pad1(valid, n_pad, 0)
    return occlusion_count(x, y, ok, radius=float(radius), tile_i=tile,
                           tile_j=tile, interpret=_interpret())


def _edge_arrays(pos, edges, valid, tile):
    pos = jnp.asarray(pos, jnp.float32)
    edges = jnp.asarray(edges, jnp.int32)
    e = edges.shape[0]
    if valid is None:
        valid = jnp.ones(e, dtype=jnp.int32)
    else:
        valid = jnp.asarray(valid).astype(jnp.int32)
    x1, y1, x2, y2 = edge_endpoints(pos, edges)
    theta = segment_theta(x1, y1, x2, y2)
    e_pad = -(-e // tile) * tile
    return (_pad1(x1, e_pad, 0.0), _pad1(y1, e_pad, 0.0),
            _pad1(x2, e_pad, 0.0), _pad1(y2, e_pad, 0.0),
            _pad1(theta, e_pad, 0.0),
            _pad1(edges[:, 0], e_pad, -1), _pad1(edges[:, 1], e_pad, -2),
            _pad1(valid, e_pad, 0))


def crossing_count_op(pos, edges, *, valid=None, tile: int = 256):
    """E_c via the Pallas CCW kernel."""
    x1, y1, x2, y2, _, v, u, ok = _edge_arrays(pos, edges, valid, tile)
    return crossing_count(x1, y1, x2, y2, v, u, ok, tile_i=tile, tile_j=tile,
                          interpret=_interpret())


def crossing_angle_op(pos, edges, *, ideal, valid=None, tile: int = 256):
    """(count, deviation sum) via the fused Pallas kernel."""
    x1, y1, x2, y2, theta, v, u, ok = _edge_arrays(pos, edges, valid, tile)
    return crossing_angle_stats(x1, y1, x2, y2, theta, v, u, ok,
                                ideal=float(ideal), tile_i=tile, tile_j=tile,
                                interpret=_interpret())


def strip_reversal_op(buckets, *, ideal: float = 1.0, with_angle=False):
    """Enhanced-crossing inner loop via the bucketed Pallas kernel.

    ``buckets`` is a :class:`repro.core.grid.SegmentBuckets`.
    """
    cap = buckets.yl.shape[1]
    cap_pad = max(-(-cap // 128) * 128, 128)

    def pad(a, fill):
        if cap_pad == cap:
            return a
        extra = jnp.full(a.shape[:-1] + (cap_pad - cap,), fill, a.dtype)
        return jnp.concatenate([a, extra], axis=-1)

    return strip_reversal_stats(
        pad(buckets.yl.astype(jnp.float32), 0.0),
        pad(buckets.yr.astype(jnp.float32), 0.0),
        pad(buckets.theta.astype(jnp.float32), 0.0),
        pad(buckets.v.astype(jnp.int32), -1),
        pad(buckets.u.astype(jnp.int32), -2),
        pad(buckets.valid.astype(jnp.int32), 0),
        ideal=float(ideal), with_angle=with_angle,
        interpret=_interpret())
