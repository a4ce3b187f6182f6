"""Roofline-term extraction from compiled dry-run artifacts.

Per (arch x shape x mesh):

    compute_s    = HLO_FLOPs_global   / (chips * PEAK_FLOPS)
    memory_s     = HLO_bytes_global   / (chips * HBM_BW)
    collective_s = coll_bytes_per_dev / LINK_BW

Sources + corrections (all validated in tests/test_roofline.py):

* ``compiled.cost_analysis()`` reports the *per-device* SPMD program and
  counts ``while``-loop bodies ONCE, independent of trip count (verified
  empirically: tests/test_roofline.py) — a scanned 36-layer stack
  under-reports by ~36x. Consequences:
    - scanned-layer LMs are measured via Python-loop twins
      (``scan_layers=False``) at L=1 / L=2 and extrapolated
      ``C(L) = C(1) + (L-1) * (C(2) - C(1))`` — exact for
      depth-homogeneous stacks;
    - every inner loop in a roofline twin is forced to a single trip
      (q_chunk = S, loss_chunks = 1, edge_chunk = E, full-width row
      blocks for the readability sweeps) so it inlines;
    - the big-edge equivariant cells are measured at two reduced edge
      counts (single-trip) and extrapolated linearly in E.

* collective bytes are parsed from ``compiled.as_text()`` (post-SPMD HLO):
  every all-reduce / all-gather / reduce-scatter / all-to-all /
  collective-permute, weighted by ring-algorithm traffic factors with the
  participant count from ``replica_groups``. The same L-extrapolation
  applies (loop-body collectives appear once in the text).

Hardware model: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s/link
ICI (one link direction as the serialization bound).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
LINK_BW = 50e9               # bytes/s / ICI link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0,
}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\]))\S*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.MULTILINE)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_V2_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 2


def collective_bytes(hlo_text: str) -> dict:
    """Per-device link traffic by collective kind (ring-algorithm model):
    all-gather/reduce-scatter move (g-1)/g of the full buffer, all-reduce
    2x that, all-to-all (g-1)/g, collective-permute the full buffer."""
    out = {"all-reduce": 0.0, "all-gather": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        nbytes = _shape_bytes(shape_str)
        g = _group_size(line)
        ring = (g - 1) / max(g, 1)
        if kind == "all-reduce":
            out[kind] += 2.0 * ring * nbytes
        elif kind == "collective-permute":
            out[kind] += float(nbytes)
        else:
            out[kind] += ring * nbytes
    out["total"] = sum(v for k, v in out.items())
    return out


@dataclasses.dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_global: float
    bytes_global: float
    coll_bytes_per_dev: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    useful_ratio: float
    note: str = ""

    def row(self):
        return (f"| {self.arch} | {self.shape} | {self.mesh} "
                f"| {self.compute_s:.3e} | {self.memory_s:.3e} "
                f"| {self.collective_s:.3e} | {self.dominant} "
                f"| {self.model_flops:.3e} | {self.useful_ratio:.2f} "
                f"| {self.note} |")


def _measure(cell, mesh):
    """Lower+compile one cell; return (flops, bytes, coll_bytes) per-dev."""
    from repro.launch.cells import lower_cell
    lowered = lower_cell(cell, mesh)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return (float(cost.get("flops", 0.0) or 0.0),
            float(cost.get("bytes accessed", 0.0) or 0.0),
            float(coll["total"]), coll)


def analyze_cell(arch_id: str, shape_id: str, mesh, mesh_name: str,
                 *, note: str = "", config_patch=None) -> RooflineTerms:
    """Derive the three roofline terms for one cell on one mesh.
    ``config_patch``: dataclasses.replace overrides for SPerf variants."""
    import functools
    from repro.launch.cells import make_cell as _mk
    make_cell = functools.partial(_mk, config_patch=config_patch)

    chips = mesh.size
    if arch_id == "readability":
        # single-trip row blocks (XLA inlines trip-1 loops -> counted)
        cell = make_cell(arch_id, shape_id, mesh, roofline_variant=True)
        flops, bytes_, coll, _ = _measure(cell, mesh)
        meta = cell.meta
    else:
        from repro.configs import get_arch
        family = get_arch(arch_id).family
        scanned = family == "lm"
        if scanned:
            cell1 = make_cell(arch_id, shape_id, mesh, roofline_variant=True,
                              layer_override=1)
            cell2 = make_cell(arch_id, shape_id, mesh, roofline_variant=True,
                              layer_override=2)
            L = make_cell(arch_id, shape_id, mesh).meta["n_layers"]
            f1, b1, c1, _ = _measure(cell1, mesh)
            f2, b2, c2, _ = _measure(cell2, mesh)
            flops = f1 + (L - 1) * (f2 - f1)
            bytes_ = b1 + (L - 1) * (b2 - b1)
            coll = c1 + (L - 1) * (c2 - c1)
            meta = make_cell(arch_id, shape_id, mesh).meta
        elif (arch_id in ("nequip", "equiformer-v2")
              and shape_id in ("ogb_products", "minibatch_lg")):
            # big edge sets: the unchunked single-trip buffer would be
            # astronomically large, so measure two *reduced edge counts*
            # with single-trip (inlined) loops and extrapolate the exact
            # linear-in-E cost model C(E) = alpha_N + beta*E to E_full
            # (node terms sit at full size inside alpha_N).
            from repro.launch.cells import _gnn_graph_dims
            _, n_edges_full, _ = _gnn_graph_dims(shape_id)
            n_edges_full = -(-n_edges_full // 16384) * 16384
            e1, e2 = 16384, 32768
            ca_cell = make_cell(arch_id, shape_id, mesh, edges_override=e1,
                                edge_chunk_override=e1)
            cb_cell = make_cell(arch_id, shape_id, mesh, edges_override=e2,
                                edge_chunk_override=e2)
            fa, ba, cca, _ = _measure(ca_cell, mesh)
            fb, bb, ccb, _ = _measure(cb_cell, mesh)

            def _extrap(a, b):
                beta = (b - a) / (e2 - e1)
                alpha = a - beta * e1
                return alpha + beta * n_edges_full

            flops = _extrap(fa, fb)
            bytes_ = _extrap(ba, bb)
            coll = _extrap(cca, ccb)
            meta = make_cell(arch_id, shape_id, mesh).meta
        else:
            cell = make_cell(arch_id, shape_id, mesh, roofline_variant=True)
            flops, bytes_, coll, _ = _measure(cell, mesh)
            meta = cell.meta

    flops_global = flops * chips
    bytes_global = bytes_ * chips
    compute_s = flops_global / (chips * PEAK_FLOPS)
    memory_s = bytes_global / (chips * HBM_BW)
    collective_s = coll / LINK_BW
    dominant = max(
        (("compute", compute_s), ("memory", memory_s),
         ("collective", collective_s)), key=lambda kv: kv[1])[0]
    model_flops = float(meta.get("model_flops", 0.0))
    ratio = model_flops / flops_global if flops_global else 0.0
    return RooflineTerms(
        arch=arch_id, shape=shape_id, mesh=mesh_name, chips=chips,
        flops_global=flops_global, bytes_global=bytes_global,
        coll_bytes_per_dev=coll, compute_s=compute_s, memory_s=memory_s,
        collective_s=collective_s, dominant=dominant,
        model_flops=model_flops, useful_ratio=ratio, note=note)


HEADER = ("| arch | shape | mesh | compute_s | memory_s | collective_s "
          "| dominant | model_flops | useful | note |\n"
          "|---|---|---|---|---|---|---|---|---|---|")
