"""Shard-count invariance of the mesh-sharded batched evaluation.

The same candidate batch evaluated on 1, 2, and 4 forced-host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count``) must yield
bit-identical integer metrics and rtol-equal floats — including the
bucket-padded ``n_valid_*`` path and the replan-on-overflow path under
sharding.  Each device count runs in a subprocess (the forced device
count must be set before jax initializes); the parent diffs the JSON
results across counts.
"""

import json
import os
import subprocess
import sys

import numpy as np

SCRIPT = r"""
import os, sys, json, dataclasses
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + sys.argv[1])
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core.keys import pow2_bucket
from repro.distributed.batched import evaluate_layouts_sharded
from jax.sharding import AxisType

ndev = int(sys.argv[1])
assert len(jax.devices()) == ndev

rng = np.random.default_rng(3)
n_v, B = 150, 6                       # 6 % 4 != 0: exercises batch padding
pos = rng.uniform(0, 80, (n_v, 2)).astype(np.float32)
edges = set()
while len(edges) < 2 * n_v:
    v, u = rng.integers(0, n_v, 2)
    if v != u:
        edges.add((min(v, u), max(v, u)))
edges = np.array(sorted(edges), np.int32)
n_e = edges.shape[0]
batch = np.stack([pos + rng.normal(0, 1.0, pos.shape).astype(np.float32)
                  for _ in range(B)])

plan = engine.plan_readability(batch, edges, radius=2.0, n_strips=48)
mesh = jax.make_mesh((ndev,), ("batch",), axis_types=(AxisType.Auto,))

def fetch(res):
    res = jax.device_get(res)
    return {
        "node_occlusion": np.asarray(res.node_occlusion).tolist(),
        "edge_crossing": np.asarray(res.edge_crossing).tolist(),
        "crossing_count_for_angle":
            np.asarray(res.crossing_count_for_angle).tolist(),
        "overflow": np.asarray(res.overflow).tolist(),
        "edge_crossing_angle":
            np.asarray(res.edge_crossing_angle).tolist(),
        "minimum_angle": np.asarray(res.minimum_angle).tolist(),
        "edge_length_variation":
            np.asarray(res.edge_length_variation).tolist(),
    }

out = {"natural": fetch(evaluate_layouts_sharded(mesh, plan, batch, edges))}

# bucket-padded path: padded tails masked via the traced n_valid scalars
vb, eb = pow2_bucket(n_v + 1), pow2_bucket(n_e + 1)
batch_p = np.full((B, vb, 2), -1.0e6, np.float32)
batch_p[:, :n_v] = batch
edges_p = np.zeros((eb, 2), np.int32)
edges_p[:n_e] = edges
out["padded"] = fetch(evaluate_layouts_sharded(
    mesh, plan, batch_p, edges_p,
    n_valid_vertices=np.int32(n_v), n_valid_edges=np.int32(n_e)))

# replan-on-overflow under sharding: starve the strip capacities, watch
# the sharded result report per-layout overflow, grow via the engine's
# replan, and converge to the healthy plan's metrics
starved = dataclasses.replace(
    plan, strip_plans=tuple((ms, 8) for ms, _ in plan.strip_plans),
    strip_tiers=())
r1 = jax.device_get(evaluate_layouts_sharded(mesh, starved, batch, edges))
ov = np.asarray(r1.overflow)
assert ov.max() > 0, "starved plan must overflow"
worst = int(ov.argmax())
grown = engine.replan_on_overflow(starved, batch[worst], edges, r1)
out["replan"] = fetch(evaluate_layouts_sharded(mesh, grown, batch, edges))
assert max(out["replan"]["overflow"]) == 0, "grown plan must not overflow"

# serving session scale-out: a mesh-bearing EvalSession shards coalesced
# batches transparently — per-request integer scores must not depend on
# the mesh size (ndev=1 takes the single-host path, >1 the sharded one)
from repro.core.keys import EvalConfig
from repro.launch.session import EvalSession
sess = EvalSession(EvalConfig(radius=2.0, n_strips=48), mesh=mesh)
scores = sess.evaluate_batch([(batch[i], edges) for i in range(B)])
out["session"] = {
    "edge_crossing": [s.edge_crossing for s in scores],
    "node_occlusion": [s.node_occlusion for s in scores],
    "overflow": [s.overflow for s in scores],
}
sharded_dispatches = sess.stats["sharded_dispatches"]
assert (sharded_dispatches > 0) == (ndev > 1), \
    (ndev, sess.stats)

print("RESULT " + json.dumps(out))
"""

INT_KEYS = ("node_occlusion", "edge_crossing", "crossing_count_for_angle",
            "overflow")
FLOAT_KEYS = ("edge_crossing_angle", "minimum_angle",
              "edge_length_variation")


def run_with_devices(ndev: int) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    result = subprocess.run([sys.executable, "-c", SCRIPT, str(ndev)],
                            env=env, capture_output=True, text=True,
                            timeout=900)
    assert result.returncode == 0, result.stdout + "\n" + result.stderr
    line = [l for l in result.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_shard_count_invariance():
    outs = {ndev: run_with_devices(ndev) for ndev in (1, 2, 4)}
    base = outs[1]
    for ndev in (2, 4):
        for path in ("natural", "padded", "replan"):
            for k in INT_KEYS:
                assert outs[ndev][path][k] == base[path][k], \
                    (ndev, path, k, outs[ndev][path][k], base[path][k])
            for k in FLOAT_KEYS:
                np.testing.assert_allclose(
                    outs[ndev][path][k], base[path][k], rtol=1e-6,
                    err_msg=f"{ndev}/{path}/{k}")
    # the padded path must also match the natural path bit-for-bit on
    # integer metrics (the engine's padding contract, now under sharding)
    for ndev, out in outs.items():
        for k in ("node_occlusion", "edge_crossing"):
            assert out["padded"][k] == out["natural"][k], (ndev, k)
    # session scale-out transparency: per-request integer scores from a
    # mesh-bearing EvalSession are mesh-size independent AND equal to
    # the raw batched program's (flat serving plan + pow2 padding
    # included)
    for ndev, out in outs.items():
        assert out["session"] == base["session"], (ndev, "session")
        for k in ("node_occlusion", "edge_crossing"):
            assert out["session"][k] == out["natural"][k], (ndev, k)
