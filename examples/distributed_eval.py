"""Distributed readability evaluation on a multi-device mesh.

Runs the paper's exact and enhanced algorithms through the shard_map
drivers on 8 simulated devices (the same code path the 256/512-chip
dry-run lowers).

  PYTHONPATH=src python examples/distributed_eval.py
"""

import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import AxisType  # noqa: E402

from repro.core import grid as gridlib  # noqa: E402
from repro.core import count_crossings_exact  # noqa: E402
from repro.distributed.gridded import sharded_reversal_stats  # noqa: E402
from repro.distributed.pairwise import (ring_occlusion_count,  # noqa: E402
                                        sharded_crossing_count,
                                        sharded_occlusion_count)
from repro.graphs.datasets import random_edges  # noqa: E402
from repro.graphs.layouts import random_layout  # noqa: E402
from repro.kernels import ref  # noqa: E402

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))
print(f"mesh: {mesh}")

n_v, n_e = 1500, 3000
edges = jnp.asarray(random_edges(n_v, n_e, seed=0))
pos = jnp.asarray(random_layout(n_v, seed=0))

# exact occlusion: replicated-columns strategy vs streaming ring
t0 = time.time()
occ = int(sharded_occlusion_count(mesh, pos, 1.0))
print(f"sharded exact N_c = {occ}  ({time.time() - t0:.2f}s)")
occ_ring = int(ring_occlusion_count(mesh, pos, 1.0))
assert occ_ring == occ
print(f"ring-streamed N_c  = {occ_ring}  (collective_permute pipeline)")

# exact crossing, row-sharded over the full mesh
t0 = time.time()
cross = int(sharded_crossing_count(mesh, pos, edges))
want = int(ref.crossing_count_ref(
    pos[edges[:, 0], 0], pos[edges[:, 0], 1],
    pos[edges[:, 1], 0], pos[edges[:, 1], 1], edges[:, 0], edges[:, 1]))
assert cross == want
print(f"sharded exact E_c = {cross}  ({time.time() - t0:.2f}s)")

# enhanced crossing: strips sharded over all 8 devices (capacities from
# the planner — undersized budgets silently drop segments)
n_strips = 256
max_segments, cap = gridlib.plan_strips(pos, edges, n_strips)
segs = gridlib.build_strip_segments_batched(jnp.asarray(pos)[None],
                                           jnp.asarray(edges), n_strips,
                                           max_segments)
buckets = gridlib.bucketize_segments(
    jax.tree_util.tree_map(lambda a: a[0], segs), n_strips, cap=cap)
(enh,) = sharded_reversal_stats(mesh, buckets)
assert int(buckets.overflow) == 0, "segment budget overflow"
err = abs(int(enh) - cross) / max(cross, 1)
print(f"sharded enhanced E_c = {int(enh)}  (err {100 * err:.2f}% vs exact)")
