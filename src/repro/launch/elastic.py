"""Elastic scaling: mesh bring-up policy and checkpoint re-sharding.

One module owns "how many devices, in what shape" for both altitudes:

* **Serving** (:func:`serving_mesh`) — the 1-D mesh
  :class:`~repro.launch.session.EvalSession` brings up for
  ``backend="graph_sharded"`` (and any caller that wants the default
  batch-sharding mesh): every visible device, capped by
  ``EvalConfig.shards``, trimmed to a power of two so the pow2 shape
  buckets divide evenly across shards.
* **Training/recovery** (:func:`make_elastic_mesh` /
  :func:`elastic_restore`) — the recovery story for node failures at
  scale:

  1. checkpoints store *logical* (unsharded) arrays
     (checkpoint/manager);
  2. on restart, the launcher rebuilds the mesh from the live device
     list (:func:`choose_mesh_shape`) — fewer/more hosts just produce a
     different mesh shape;
  3. ``elastic_restore`` re-computes shardings for the new mesh and
     ``device_put``s the restored pytree onto them.

Straggler mitigation at this layer: the readability workloads
over-decompose (strips >> devices) so re-balancing after a shrink is just
a different strip->device round-robin; training workloads re-enter the
standard SPMD step where per-step synchronization is the compiled
collectives only.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def choose_mesh_shape(n_devices: int, *, max_model: int = 16, axes: int = 2):
    """Mesh shape for the available device count.

    ``axes=2`` (the default): ``(data, model)`` with the largest
    power-of-two model axis <= ``max_model`` that divides ``n_devices``
    (the training layout).  ``axes=1``: ``(shards,)`` with the largest
    power of two <= ``n_devices`` (the serving layout — pow2 so the
    session's pow2-bucketed shapes divide evenly; leftover devices are
    idled rather than forcing a ragged partition)."""
    n_devices = max(int(n_devices), 1)
    if axes == 1:
        shards = 1
        while shards * 2 <= n_devices:
            shards *= 2
        return (shards,)
    if axes != 2:
        raise ValueError(f"axes must be 1 or 2, got {axes}")
    model = 1
    while model * 2 <= max_model and n_devices % (model * 2) == 0:
        model *= 2
    return (n_devices // model, model)


def serving_mesh(axis: str = "eval", *, shards=None, devices=None):
    """The serving-side default mesh: a 1-D mesh over the visible
    devices (capped by ``shards`` — the ``EvalConfig.shards`` knob —
    and trimmed to a power of two by :func:`choose_mesh_shape`).

    This is the ONE bring-up policy shared by
    ``EvalSession(backend="graph_sharded")`` (axis ``"graph"``) and
    ``repro.api.Evaluator`` sharded batching (axis ``"eval"``) — ad-hoc
    visible-device counting at call sites is exactly what it replaces.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if shards is not None:
        n = min(n, int(shards))
    (n,) = choose_mesh_shape(n, axes=1)
    return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,),
                         devices=list(devices)[:n])


def make_elastic_mesh():
    n = len(jax.devices())
    shape = choose_mesh_shape(n)
    return jax.make_mesh(shape, ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def elastic_restore(directory: str, template, sharding_fn):
    """Restore the newest valid checkpoint onto a freshly-built mesh.

    ``sharding_fn(mesh, template) -> shardings pytree``; returns
    (tree, step, mesh)."""
    # imported here so the serving path (EvalSession -> serving_mesh)
    # never pays for the checkpoint stack
    from repro.checkpoint.manager import CheckpointManager

    mesh = make_elastic_mesh()
    mgr = CheckpointManager(directory)
    shardings = sharding_fn(mesh, template)
    tree, step = mgr.restore(template, shardings=shardings)
    return tree, step, mesh
