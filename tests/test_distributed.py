"""Multi-device (fake CPU devices) tests for the distributed drivers.

Runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count
so the main test process keeps its single-device view.
"""

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

assert len(jax.devices()) == 8

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))

rng = np.random.default_rng(0)
n_v, n_e = 300, 600
pos = jnp.asarray(rng.uniform(0, 100, (n_v, 2)).astype(np.float32))
edges = set()
while len(edges) < n_e:
    v, u = rng.integers(0, n_v, 2)
    if v != u:
        edges.add((min(v, u), max(v, u)))
edges = jnp.asarray(np.array(sorted(edges), np.int32))

from repro.kernels import ref
from repro.distributed.pairwise import (sharded_occlusion_count,
                                        ring_occlusion_count,
                                        sharded_crossing_count)
r = 2.0
want_occ = int(ref.occlusion_count_ref(pos[:, 0], pos[:, 1], r))
got = int(sharded_occlusion_count(mesh, pos, r, block=128))
assert got == want_occ, ("sharded occ", got, want_occ)
got_ring = int(ring_occlusion_count(mesh, pos, r))
assert got_ring == want_occ, ("ring occ", got_ring, want_occ)

x1, y1 = pos[edges[:, 0], 0], pos[edges[:, 0], 1]
x2, y2 = pos[edges[:, 1], 0], pos[edges[:, 1], 1]
want_cross = int(ref.crossing_count_ref(x1, y1, x2, y2,
                                        edges[:, 0], edges[:, 1]))
got_cross = int(sharded_crossing_count(mesh, pos, edges, block=128))
assert got_cross == want_cross, ("sharded cross", got_cross, want_cross)

# strip-sharded enhanced crossing matches the single-device enhanced path
from repro.core import grid as gridlib
from repro.core.crossing import bucket_reversal_stats
from repro.distributed.gridded import sharded_reversal_stats
segs = gridlib.build_strip_segments_batched(jnp.asarray(pos)[None],
                                           jnp.asarray(edges), 64, 16384)
buckets = gridlib.bucketize_segments(
    jax.tree_util.tree_map(lambda a: a[0], segs), 64, cap=128)
(want_enh,) = bucket_reversal_stats(buckets)
(got_enh,) = sharded_reversal_stats(mesh, buckets)
assert int(got_enh) == int(want_enh), (int(got_enh), int(want_enh))

# softmax-merge decode attention == plain attention
from repro.distributed.collectives import merge_decode_attention
B, S, H, dh = 2, 64, 4, 16
q = jnp.asarray(rng.normal(size=(B, H, dh)).astype(np.float32))
k = jnp.asarray(rng.normal(size=(B, S, H, dh)).astype(np.float32))
v = jnp.asarray(rng.normal(size=(B, S, H, dh)).astype(np.float32))
pos_t = jnp.asarray(37, jnp.int32)
got = merge_decode_attention(mesh, q, k, v, pos_t)
s = jnp.einsum("bhd,bthd->bht", q, k) * (dh ** -0.5)
t = jnp.arange(S)
s = jnp.where((t <= pos_t)[None, None, :], s, -1e30)
p = jax.nn.softmax(s, axis=-1)
want = jnp.einsum("bht,bthd->bhd", p, v)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

# range-partitioned embedding lookup == take
from repro.distributed.collectives import sharded_embedding_lookup
table = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
ids = jnp.asarray(rng.integers(0, 64, (5, 3)).astype(np.int32))
got = sharded_embedding_lookup(mesh, table, ids)
np.testing.assert_allclose(np.asarray(got),
                           np.asarray(jnp.take(table, ids, axis=0)),
                           atol=1e-6)
print("DISTRIBUTED_OK")
"""


def test_distributed_drivers():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    result = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                            capture_output=True, text=True, timeout=900)
    assert result.returncode == 0, result.stdout + "\n" + result.stderr
    assert "DISTRIBUTED_OK" in result.stdout
