"""Cell construction: one (architecture x input-shape) dry-run unit.

A ``Cell`` bundles the jittable step function, abstract (ShapeDtypeStruct)
arguments, and in/out shardings for the production mesh — everything
``dryrun.py`` needs to ``.lower().compile()`` without allocating a byte,
and everything ``roofline/analysis.py`` needs to derive the three roofline
terms (including the scan-trip metadata for while-body cost correction).

``roofline_variant=True`` builds the cost-extraction twin: single-trip
inner loops (q_chunk = S, loss_chunks = 1, edge_chunk = E) and
``layer_override`` for the L=1/L=2 extrapolation of scanned layers
(XLA cost_analysis counts while bodies once; see EXPERIMENTS.md).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_arch
from repro.distributed.sharding import batch_axes
from repro.models import equivariant as eqv
from repro.models import gnn as gnnlib
from repro.models import recsys as rslib
from repro.models import transformer as tflib
from repro.optim import adamw

OPT_CFG = adamw.AdamWConfig()


@dataclasses.dataclass
class Cell:
    arch_id: str
    shape_id: str
    kind: str                       # train | prefill | decode | serve
    fn: Callable
    abstract_args: tuple
    in_shardings: Any
    out_shardings: Any
    meta: dict


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _ns(mesh, *spec):
    return NamedSharding(mesh, P(*spec))


def _round_up(n, m):
    return -(-n // m) * m


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------

LM_SHAPE_DEFS = {
    "train_4k": dict(seq_len=4096, global_batch=256, kind="train"),
    "prefill_32k": dict(seq_len=32768, global_batch=32, kind="prefill"),
    "decode_32k": dict(seq_len=32768, global_batch=128, kind="decode"),
    "long_500k": dict(seq_len=524288, global_batch=1, kind="decode"),
}


def _lm_param_spec(path, leaf):
    key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                   for p in path)
    last = key.split("/")[-1]
    if last == "embed":
        return P("model", None)
    if last == "unembed":
        return P(None, "model")
    if last in ("wq", "w_gate", "w_up", "ws_gate", "ws_up"):
        return P(None, None, "model")
    if last in ("wo", "w_down", "ws_down"):
        return P(None, "model", None)
    if last == "bq":
        return P(None, "model")
    if last in ("we_gate", "we_up", "we_down"):
        return P(None, "model", None, None)      # expert-parallel
    return P()       # ln/bias/kv (replicated kv: Megatron GQA convention)


def _lm_shardings(mesh, params_shape):
    pspecs = jax.tree_util.tree_map_with_path(_lm_param_spec, params_shape)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def _lm_cell(arch_id: str, shape_id: str, mesh: Mesh, *,
             roofline_variant: bool, layer_override: Optional[int],
             config_patch: Optional[dict] = None) -> Cell:
    spec = get_arch(arch_id)
    cfg = spec.config.with_mesh(mesh.shape["model"])
    if config_patch:
        cfg = dataclasses.replace(cfg, **config_patch)
    sd = LM_SHAPE_DEFS[shape_id]
    seq, gb, kind = sd["seq_len"], sd["global_batch"], sd["kind"]
    if roofline_variant:
        tokens_total = gb * (seq if kind in ("train", "prefill") else 1)
        cfg = dataclasses.replace(
            cfg, q_chunk=seq, loss_chunks=1, scan_layers=False,
            moe_group=min(cfg.moe_group, tokens_total))
    if layer_override is not None:
        cfg = dataclasses.replace(cfg, n_layers=layer_override)
    bax = batch_axes(mesh)

    params_shape = jax.eval_shape(
        lambda k: tflib.init_params(cfg, k), jax.random.PRNGKey(0))
    pshard = _lm_shardings(mesh, params_shape)

    n_active = cfg.active_param_count()
    meta = dict(model_params=cfg.param_count(), active_params=n_active,
                scan_axis="layers", n_layers=cfg.n_layers)

    if kind == "train":
        opt_shape = jax.eval_shape(adamw.init_state, params_shape)
        oshard = {"m": pshard, "v": pshard, "step": _ns(mesh)}
        batch_shape = {"tokens": _sds((gb, seq), jnp.int32),
                       "labels": _sds((gb, seq), jnp.int32)}
        bshard = {"tokens": _ns(mesh, bax, None),
                  "labels": _ns(mesh, bax, None)}

        def train_step(params, opt_state, batch):
            (loss, mets), grads = jax.value_and_grad(
                lambda p: tflib.loss_fn(p, batch, cfg), has_aux=True)(params)
            params, opt_state, om = adamw.apply_updates(
                params, grads, opt_state, OPT_CFG)
            return params, opt_state, {"loss": loss, **om}

        meta["model_flops"] = 6.0 * n_active * gb * seq
        meta["tokens"] = gb * seq
        return Cell(arch_id, shape_id, kind, train_step,
                    (params_shape, opt_shape, batch_shape),
                    (pshard, oshard, bshard),
                    (pshard, oshard, None), meta)

    # serving cells share the cache layout: batch->data, seq->model
    Smax = seq
    cache_shape = {
        "k": _sds((cfg.n_layers, gb, Smax, cfg.n_kv_heads, cfg.d_head),
                  cfg.dtype),
        "v": _sds((cfg.n_layers, gb, Smax, cfg.n_kv_heads, cfg.d_head),
                  cfg.dtype),
        "pos": _sds((), jnp.int32),
    }
    if gb == 1:
        # long-context: sequence shards over every data-like axis + model
        seq_axes = tuple(a for a in mesh.axis_names)
        cshard_kv = _ns(mesh, None, None, seq_axes, None, None)
    else:
        cshard_kv = _ns(mesh, None, bax, "model", None, None)
    cshard = {"k": cshard_kv, "v": cshard_kv, "pos": _ns(mesh)}

    if kind == "prefill":
        tokens_shape = _sds((gb, seq), jnp.int32)
        tshard = _ns(mesh, bax, None)

        def prefill_step(params, tokens, cache):
            return tflib.prefill(params, tokens, cache, cfg)

        meta["model_flops"] = 2.0 * n_active * gb * seq
        meta["tokens"] = gb * seq
        return Cell(arch_id, shape_id, kind, prefill_step,
                    (params_shape, tokens_shape, cache_shape),
                    (pshard, tshard, cshard),
                    (cshard, None), meta)

    # decode
    tokens_shape = _sds((gb,), jnp.int32)
    tshard = _ns(mesh, bax) if gb > 1 else _ns(mesh)

    def decode(params, tokens, cache):
        return tflib.decode_step(params, tokens, cache, cfg)

    meta["model_flops"] = 2.0 * n_active * gb \
        + 2.0 * gb * seq * cfg.n_heads * cfg.d_head * 2  # attn vs cache
    meta["tokens"] = gb
    return Cell(arch_id, shape_id, kind, decode,
                (params_shape, tokens_shape, cache_shape),
                (pshard, tshard, cshard),
                (tshard, None, cshard), meta)


# ---------------------------------------------------------------------------
# GNN cells
# ---------------------------------------------------------------------------

GNN_SHAPE_DEFS = {
    # n_nodes/n_edges padded to multiples of 512 (shards over 32 and 128)
    "full_graph_sm": dict(n_nodes=2708, n_edges=10556, d_feat=1433,
                          n_classes=7, kind="train"),
    "minibatch_lg": dict(batch_nodes=1024, fanout=(15, 10), d_feat=602,
                         n_classes=41, kind="train"),
    "ogb_products": dict(n_nodes=2_449_029, n_edges=61_859_140, d_feat=100,
                         n_classes=47, kind="train"),
    "molecule": dict(n_graphs=128, nodes_per=30, edges_per=64, d_feat=16,
                     n_classes=8, kind="train"),
}


def _gnn_graph_dims(shape_id):
    sd = GNN_SHAPE_DEFS[shape_id]
    if shape_id == "minibatch_lg":
        b = sd["batch_nodes"]
        f1, f2 = sd["fanout"]
        n_nodes = b * (1 + f1 + f1 * f2)
        n_edges = b * f1 + b * f1 * f2
    elif shape_id == "molecule":
        n_nodes = sd["n_graphs"] * sd["nodes_per"]
        n_edges = sd["n_graphs"] * sd["edges_per"]
    else:
        n_nodes, n_edges = sd["n_nodes"], sd["n_edges"]
    return _round_up(n_nodes, 512), _round_up(n_edges, 512), sd


def _gnn_train_wrap(forward, loss_of_logits, params_shape):
    def train_step(params, opt_state, batch):
        def loss_fn(p):
            out = forward(p, batch)
            return loss_of_logits(out, batch)
        (loss, grads) = jax.value_and_grad(loss_fn)(params)
        params, opt_state, om = adamw.apply_updates(params, grads,
                                                    opt_state, OPT_CFG)
        return params, opt_state, {"loss": loss, **om}
    return train_step


def _gnn_cell(arch_id: str, shape_id: str, mesh: Mesh, *,
              roofline_variant: bool, layer_override: Optional[int],
              edge_chunk_override: Optional[int] = None,
              edges_override: Optional[int] = None,
              config_patch: Optional[dict] = None) -> Cell:
    spec = get_arch(arch_id)
    if config_patch:
        spec = dataclasses.replace(
            spec, config=dataclasses.replace(spec.config, **config_patch))
    n_nodes, n_edges, sd = _gnn_graph_dims(shape_id)
    bax = batch_axes(mesh)
    equivariant = arch_id in ("nequip", "equiformer-v2")
    sage_sampled = (arch_id == "graphsage-reddit"
                    and shape_id == "minibatch_lg")

    if equivariant:
        cfg = spec.config
        # edge buffers rounded to the chunk size so chunking divides evenly
        n_edges = _round_up(n_edges, 16384)
        if edges_override is not None:
            n_edges = edges_override
        if edge_chunk_override is not None:
            cfg = dataclasses.replace(cfg, edge_chunk=edge_chunk_override)
        elif roofline_variant:
            cfg = dataclasses.replace(cfg, edge_chunk=n_edges)
        if layer_override is not None:
            cfg = dataclasses.replace(cfg, n_layers=layer_override)
        n_graphs = sd.get("n_graphs", 1)
        batch_shape = {
            "positions": _sds((n_nodes, 3), jnp.float32),
            "species": _sds((n_nodes,), jnp.int32),
            "edge_src": _sds((n_edges,), jnp.int32),
            "edge_dst": _sds((n_edges,), jnp.int32),
            "edge_mask": _sds((n_edges,), jnp.bool_),
            "node_mask": _sds((n_nodes,), jnp.bool_),
            "graph_id": _sds((n_nodes,), jnp.int32),
            "targets": _sds((n_graphs,), jnp.float32),
        }
        bshard = {k: _ns(mesh, bax) if v.shape and v.shape[0] in
                  (n_nodes, n_edges) else _ns(mesh)
                  for k, v in batch_shape.items()}
        init = (eqv.init_nequip_params if arch_id == "nequip"
                else eqv.init_equiformer_params)
        fwd = (eqv.nequip_forward if arch_id == "nequip"
               else eqv.equiformer_forward)
        params_shape = jax.eval_shape(lambda k: init(cfg, k),
                                      jax.random.PRNGKey(0))
        pshard = jax.tree.map(lambda _: _ns(mesh), params_shape)
        opt_shape = jax.eval_shape(adamw.init_state, params_shape)
        oshard = {"m": pshard, "v": pshard, "step": _ns(mesh)}

        def forward(p, batch):
            return fwd(p, batch, cfg, n_graphs=n_graphs)

        def loss_of(out, batch):
            return eqv.energy_loss(out, batch["targets"])

        train_step = _gnn_train_wrap(forward, loss_of, params_shape)
        meta = dict(n_layers=cfg.n_layers, scan_axis=None,
                    model_flops=_equivariant_flops(arch_id, cfg, n_edges,
                                                   n_nodes),
                    tokens=n_nodes)
        return Cell(arch_id, shape_id, "train", train_step,
                    (params_shape, opt_shape, batch_shape),
                    (pshard, oshard, bshard), (pshard, oshard, None), meta)

    # gcn / graphsage
    cfg = dataclasses.replace(spec.config, d_in=sd["d_feat"],
                              n_classes=sd["n_classes"])
    if layer_override is not None:
        cfg = dataclasses.replace(cfg, n_layers=max(layer_override, 2))

    if sage_sampled:
        b = sd["batch_nodes"]
        f1, f2 = sd["fanout"]
        d = sd["d_feat"]
        batch_shape = {
            "x0": _sds((b, d), jnp.float32),
            "x1": _sds((b, f1, d), jnp.float32),
            "x2": _sds((b, f1, f2, d), jnp.float32),
            "m1": _sds((b, f1), jnp.bool_),
            "m2": _sds((b, f1, f2), jnp.bool_),
            "labels": _sds((b,), jnp.int32),
        }
        bshard = {k: _ns(mesh, bax, *((None,) * (len(v.shape) - 1)))
                  for k, v in batch_shape.items()}
        params_shape = jax.eval_shape(
            lambda k: gnnlib.init_sage_params(cfg, k), jax.random.PRNGKey(0))

        def forward(p, batch):
            return gnnlib.sage_forward_sampled(p, batch, cfg)

        def loss_of(out, batch):
            loss, _ = gnnlib.node_classification_loss(
                out, batch["labels"], jnp.ones_like(batch["labels"],
                                                    dtype=bool))
            return loss

        flops = 6.0 * (b * (1 + f1) * 2 * d * cfg.d_hidden
                       + b * 2 * cfg.d_hidden * cfg.n_classes)
    else:
        batch_shape = {
            "node_feat": _sds((n_nodes, sd["d_feat"]), jnp.float32),
            "edge_src": _sds((n_edges,), jnp.int32),
            "edge_dst": _sds((n_edges,), jnp.int32),
            "edge_mask": _sds((n_edges,), jnp.bool_),
            "node_mask": _sds((n_nodes,), jnp.bool_),
            "labels": _sds((n_nodes,), jnp.int32),
        }
        bshard = {k: _ns(mesh, bax, *((None,) * (len(v.shape) - 1)))
                  for k, v in batch_shape.items()}
        if arch_id == "gcn-cora":
            params_shape = jax.eval_shape(
                lambda k: gnnlib.init_gcn_params(cfg, k),
                jax.random.PRNGKey(0))

            def forward(p, batch):
                return gnnlib.gcn_forward(p, batch, cfg)
        else:
            params_shape = jax.eval_shape(
                lambda k: gnnlib.init_sage_params(cfg, k),
                jax.random.PRNGKey(0))

            def forward(p, batch):
                return gnnlib.sage_forward_full(p, batch, cfg)

        def loss_of(out, batch):
            loss, _ = gnnlib.node_classification_loss(out, batch["labels"],
                                                      batch["node_mask"])
            return loss

        dims = [sd["d_feat"]] + [cfg.d_hidden] * (cfg.n_layers - 1) \
            + [sd["n_classes"]]
        flops = 6.0 * sum(n_nodes * dims[i] * dims[i + 1]
                          for i in range(cfg.n_layers)) \
            + 6.0 * sum(2 * n_edges * dims[i + 1]
                        for i in range(cfg.n_layers))

    pshard = jax.tree.map(lambda _: _ns(mesh), params_shape)
    opt_shape = jax.eval_shape(adamw.init_state, params_shape)
    oshard = {"m": pshard, "v": pshard, "step": _ns(mesh)}
    train_step = _gnn_train_wrap(forward, loss_of, params_shape)
    meta = dict(n_layers=cfg.n_layers, scan_axis=None, model_flops=flops,
                tokens=n_nodes)
    return Cell(arch_id, shape_id, "train", train_step,
                (params_shape, opt_shape, batch_shape),
                (pshard, oshard, bshard), (pshard, oshard, None), meta)


def _equivariant_flops(arch_id, cfg, n_edges, n_nodes):
    C = cfg.d_hidden
    if arch_id == "nequip":
        paths = len(cfg.paths)
        per_edge = sum(2 * (2 * l1 + 1) * (2 * l2 + 1) * (2 * l3 + 1) * C
                       for (l1, l2, l3) in cfg.paths) \
            + 2 * cfg.n_rbf * cfg.radial_hidden \
            + 2 * cfg.radial_hidden * paths * C
        per_node = 2 * ((cfg.l_max + 1) ** 2) * C * C * 2
        return 3.0 * cfg.n_layers * (n_edges * per_edge + n_nodes * per_node)
    # equiformer: wigner rotate (2x block-diag matmuls) + SO(2) mixes
    rot = 2 * sum((2 * l + 1) ** 2 for l in range(cfg.l_max + 1)) * C * 2
    so2 = 2 * ((cfg.l_max + 1) * C) ** 2 \
        + sum(4 * 2 * ((cfg.l_max + 1 - m) * C) ** 2
              for m in range(1, cfg.m_max + 1))
    per_node = 2 * ((cfg.l_max + 1) ** 2) * C * C * 6
    return 3.0 * cfg.n_layers * (n_edges * (rot + so2) + n_nodes * per_node)


# ---------------------------------------------------------------------------
# recsys cells
# ---------------------------------------------------------------------------

RECSYS_SHAPE_DEFS = {
    "train_batch": dict(batch=65536, kind="train"),
    "serve_p99": dict(batch=512, kind="serve"),
    "serve_bulk": dict(batch=262144, kind="serve"),
    "retrieval_cand": dict(batch=1, n_candidates=1_000_000,
                           kind="retrieval"),
}


def _recsys_cell(arch_id: str, shape_id: str, mesh: Mesh, *,
                 roofline_variant: bool,
                 layer_override: Optional[int],
                 config_patch: Optional[dict] = None) -> Cell:
    spec = get_arch(arch_id)
    cfg = spec.config
    if config_patch:
        cfg = dataclasses.replace(cfg, **config_patch)
    sd = RECSYS_SHAPE_DEFS[shape_id]
    b = sd["batch"]
    bax = batch_axes(mesh)
    params_shape = jax.eval_shape(
        lambda k: rslib.init_xdeepfm_params(cfg, k), jax.random.PRNGKey(0))

    def pspec(path, leaf):
        last = str(getattr(path[-1], "key", ""))
        if last in ("embed", "item_embed"):
            return P("model", None)
        if last == "linear":
            return P("model")
        return P()

    pspecs = jax.tree_util.tree_map_with_path(pspec, params_shape)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda x: isinstance(x, P))
    m, D = cfg.n_fields, cfg.embed_dim
    cin_flops = 0
    h_prev = m
    for h in cfg.cin_layers:
        cin_flops += 2 * b * h * h_prev * m * D
        h_prev = h
    mlp_flops = 2 * b * m * D * cfg.mlp_dims[0] \
        + 2 * b * cfg.mlp_dims[0] * cfg.mlp_dims[1]
    fwd_flops = cin_flops + mlp_flops

    ids_shape = _sds((b, cfg.n_fields), jnp.int32)
    ids_shard = _ns(mesh, bax, None) if b > 1 else _ns(mesh)

    if sd["kind"] == "train":
        opt_shape = jax.eval_shape(adamw.init_state, params_shape)
        oshard = {"m": pshard, "v": pshard, "step": _ns(mesh)}
        batch_shape = {"ids": ids_shape, "labels": _sds((b,), jnp.float32)}
        bshard = {"ids": ids_shard, "labels": _ns(mesh, bax)}

        def train_step(params, opt_state, batch):
            def loss_fn(p):
                logits = rslib.xdeepfm_logits(p, batch["ids"], cfg)
                return rslib.bce_loss(logits, batch["labels"])
            loss, grads = jax.value_and_grad(loss_fn)(params)
            params, opt_state, om = adamw.apply_updates(
                params, grads, opt_state, OPT_CFG)
            return params, opt_state, {"loss": loss, **om}

        meta = dict(model_flops=3.0 * fwd_flops, tokens=b, scan_axis=None,
                    n_layers=len(cfg.cin_layers))
        return Cell(arch_id, shape_id, "train", train_step,
                    (params_shape, opt_shape, batch_shape),
                    (pshard, oshard, bshard), (pshard, oshard, None), meta)

    if sd["kind"] == "retrieval":
        def retrieve(params, ids):
            return rslib.retrieval_scores(params, ids, cfg)

        meta = dict(model_flops=fwd_flops + 2.0 * b * sd["n_candidates"]
                    * cfg.retrieval_dim,
                    tokens=b * sd["n_candidates"], scan_axis=None,
                    n_layers=len(cfg.cin_layers))
        return Cell(arch_id, shape_id, "retrieval", retrieve,
                    (params_shape, ids_shape), (pshard, ids_shard),
                    _ns(mesh, None, "model"), meta)

    def serve(params, ids):
        return rslib.xdeepfm_logits(params, ids, cfg)

    meta = dict(model_flops=fwd_flops, tokens=b, scan_axis=None,
                n_layers=len(cfg.cin_layers))
    return Cell(arch_id, shape_id, "serve", serve,
                (params_shape, ids_shape), (pshard, ids_shard),
                _ns(mesh, bax) if b > 1 else _ns(mesh), meta)


# ---------------------------------------------------------------------------
# readability (the paper's own workload) cells
# ---------------------------------------------------------------------------

def readability_cell(shape_id: str, mesh: Mesh,
                     dataset: str = "soc-Epinions1", *,
                     roofline_variant: bool = False,
                     predicate: str = "sign"):
    """Lowerable cells for the paper's technique on the production mesh.

    ``roofline_variant`` sizes the row blocks so the per-device sweep is a
    single (inlined, hence cost-counted) loop trip."""
    from repro.configs.readability import dataset_dims
    from repro.distributed.gridded import lower_sharded_reversal
    from repro.distributed.pairwise import (lower_sharded_crossing,
                                            lower_sharded_occlusion)
    n_v, n_e = dataset_dims(dataset)
    n_dev = mesh.size
    if shape_id == "exact_occlusion":
        block = _round_up(-(-n_v // n_dev), 8) if roofline_variant else 1024
        fn, args = lower_sharded_occlusion(mesh, n_v, 0.5, block=block)
        flops = 4.0 * n_v * n_v        # dx,dy,squares,cmp per pair
        tokens = n_v
    elif shape_id == "exact_crossing":
        block = _round_up(-(-n_e // n_dev), 8) if roofline_variant else 256
        fn, args = lower_sharded_crossing(mesh, n_e, block=block,
                                          predicate=predicate)
        flops = 30.0 * n_e * n_e       # 4 CCW x ~7 flops + predicates
        tokens = n_e
    elif shape_id == "enhanced_crossing":
        # paper-scale strips: width ~0.05 on [0,100] -> 2048 strips;
        # segments ~ E x mean-span; cap ~ max per-strip occupancy
        n_strips, cap = 2048, _round_up(int(3.0 * n_e / 2048) + 64, 128)
        per = _round_up(n_strips, n_dev) // n_dev
        strip_block = per if roofline_variant else min(64, per)
        fn, args = lower_sharded_reversal(mesh, n_strips, cap,
                                          strip_block=strip_block)
        flops = 6.0 * n_strips * cap * cap
        tokens = n_e
    else:
        raise KeyError(shape_id)
    meta = dict(model_flops=flops, tokens=tokens, scan_axis=None,
                n_layers=1, dataset=dataset)
    return Cell("readability", shape_id, "eval", fn, args, None, None, meta)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def make_cell(arch_id: str, shape_id: str, mesh: Mesh, *,
              roofline_variant: bool = False,
              layer_override: Optional[int] = None,
              edge_chunk_override: Optional[int] = None,
              edges_override: Optional[int] = None,
              config_patch: Optional[dict] = None) -> Cell:
    if arch_id == "readability":
        kw = dict(config_patch or {})
        return readability_cell(shape_id, mesh,
                                roofline_variant=roofline_variant, **kw)
    family = get_arch(arch_id).family
    maker = {"lm": _lm_cell, "gnn": _gnn_cell, "recsys": _recsys_cell}[family]
    kw = dict(roofline_variant=roofline_variant,
              layer_override=layer_override, config_patch=config_patch)
    if family == "gnn":
        kw["edge_chunk_override"] = edge_chunk_override
        kw["edges_override"] = edges_override
    return maker(arch_id, shape_id, mesh, **kw)


def lower_cell(cell: Cell, mesh: Mesh):
    """AOT-lower a cell on its mesh (no allocation)."""
    with jax.set_mesh(mesh):
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings) \
            if cell.in_shardings is not None else cell.fn
        return jitted.lower(*cell.abstract_args)
