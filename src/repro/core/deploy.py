"""Deployment: trained QAT weights -> CIM-packed serving artifacts.

The paper's inference flow (§III): after QAT + pruning, only nonzero
group-sets are stored (with index codes) and computed. Here the LM
equivalent: every CIM-mapped projection is quantized to int levels
(eqs. 6-8), pruned at the TPU tile granularity, and packed for the
``cim_bsr_matmul`` kernel. ``deployed_matmul`` is the drop-in serving
replacement for ``cim_matmul``.

Uniform envelope: :func:`stack_deployed` folds L per-layer
:class:`DeployedWeight` packings of one projection into a single
:class:`StackedWeight` whose slot axis is padded to the per-projection
``nnz_max`` maximum (zero blocks AND zero scales, so padding is inert even
past a truncated layer's guard) while the per-layer ``nnz``/``row_idx``
stay exact - padding blocks are never computed. ``stacked_matmul`` then
serves any layer of the stack through ONE compiled layer-indexed kernel,
which is what lets the serving runtime ``lax.scan`` over layers instead of
dispatching L separate kernels per decode step.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import ops
from ..kernels.cim_bsr_matmul import MACRO_AXIS
from . import quant as Q
from . import sparsity as S
from .cim_layer import CIMConfig


@dataclasses.dataclass
class DeployedWeight:
    """One projection packed for the kernel (per layer of a stack).

    Registered as a jax pytree so a whole model of packed projections can be
    passed through ``jit`` (the serving engines do exactly that); the block
    arrays are the leaves, the geometry is static aux data.

    ``mesh`` is None for single-device serving. After ``shard_weight`` it
    holds the macro-cluster mesh: each packed dict's block-column axis is
    then permuted into device order (equal-cardinality LPT shards), laid
    out over the mesh's ``macro`` axis, and carries a ``col_inv`` index
    that restores the original column order after the sharded kernel's
    all-gather. After ``replicate_weight`` it holds the mesh too, with
    every device holding the whole packing and no ``col_inv``.
    """

    packed: List[dict]  # one kernel dict per stacked layer
    d_in: int
    d_out: int
    bits: int
    mesh: Optional[Mesh] = None

    @property
    def density(self) -> float:
        return float(np.mean([p["density"] for p in self.packed]))

    @property
    def sharded(self) -> bool:
        """Block columns split over the mesh (not replicated on it)."""
        return self.mesh is not None and "col_inv" in self.packed[0]

    @property
    def tile(self) -> tuple:
        """(bk, bn) block shape the projection was packed with."""
        b = self.packed[0]["blocks"]
        return (int(b.shape[2]), int(b.shape[3]))

    def astype(self, dtype):
        """No-op for call-site compatibility with raw weight arrays (the
        model code writes ``p["wq"].astype(x.dtype)``); the kernel's int8
        blocks + f32 scales are the only at-rest representation."""
        return self


jax.tree_util.register_pytree_node(
    DeployedWeight,
    lambda dw: ((dw.packed,), (dw.d_in, dw.d_out, dw.bits, dw.mesh)),
    lambda aux, ch: DeployedWeight(ch[0], *aux),
)


@dataclasses.dataclass
class StackedWeight:
    """L layers of one projection in a single uniform packing envelope.

    Every layer shares the (go, bk, bn) geometry; the slot axis is padded to
    the per-projection ``nnz_max`` maximum with zero blocks and zero scales,
    and the per-layer ``nnz``/``row_idx`` stay exact, so a padded slot is
    never a numeric participant. One layer-indexed kernel serves the whole
    stack - the compiled decode step never dispatches per layer.

    ``col_inv`` is None for single-device stacks. After stacking macro-
    sharded layers it holds the per-layer un-permute index ((L, go),
    replicated) that restores logical column order after the sharded
    kernel's all-gather - each layer keeps its own LPT column placement.
    """

    blocks: jnp.ndarray   # (L, go, nnz_max, bk, bn) int8
    scales: jnp.ndarray   # (L, go, nnz_max) f32 (0 in padding slots)
    row_idx: jnp.ndarray  # (L, go, nnz_max) int32
    nnz: jnp.ndarray      # (L, go) int32 true per-layer slot counts
    d_in: int
    d_out: int
    bits: int
    col_inv: Optional[jnp.ndarray] = None  # (L, go) int32 when sharded
    mesh: Optional[Mesh] = None

    @property
    def n_layers(self) -> int:
        return int(self.blocks.shape[0])

    @property
    def tile(self) -> tuple:
        b = self.blocks
        return (int(b.shape[3]), int(b.shape[4]))

    @property
    def density(self) -> float:
        total = (self.d_in // self.tile[0]) * (self.d_out // self.tile[1])
        return float(np.asarray(self.nnz).sum()) / max(
            total * self.n_layers, 1)

    def layer(self, i: int) -> DeployedWeight:
        """Materialize layer ``i`` as a standalone single-layer
        DeployedWeight (host-side; for tests and storage accounting)."""
        go = int(self.nnz.shape[1])
        p = {k: np.asarray(getattr(self, k)[i])
             for k in ("blocks", "scales", "row_idx", "nnz")}
        gi = self.d_in // self.tile[0]
        p["density"] = float(p["nnz"].sum()) / max(gi * go, 1)
        if self.col_inv is not None:
            p["col_inv"] = np.asarray(self.col_inv[i])
        return DeployedWeight([p], self.d_in, self.d_out, self.bits,
                              mesh=self.mesh)

    def astype(self, dtype):
        """No-op (call-site compatibility with raw weight arrays)."""
        return self


jax.tree_util.register_pytree_node(
    StackedWeight,
    lambda sw: ((sw.blocks, sw.scales, sw.row_idx, sw.nnz, sw.col_inv),
                (sw.d_in, sw.d_out, sw.bits, sw.mesh)),
    lambda aux, ch: StackedWeight(*ch[:4], aux[0], aux[1], aux[2],
                                  col_inv=ch[4], mesh=aux[3]),
)


class StackedLayerView:
    """One layer of a :class:`StackedWeight`, as seen from inside a traced
    scan body: ``layer`` is the (traced) scan index. ``cim_matmul``
    dispatches this to :func:`stacked_matmul`, so the standard model code
    (attention / MLP bodies) runs over a layer stack unchanged. Never
    crosses a jit boundary - it is built fresh each scan step."""

    __slots__ = ("sw", "layer")

    def __init__(self, sw: StackedWeight, layer):
        self.sw = sw
        self.layer = layer

    def astype(self, dtype):
        return self


def stack_deployed(dws: Sequence[DeployedWeight]) -> StackedWeight:
    """Stack per-layer packings of ONE projection into a uniform envelope.

    Every entry must share (d_in, d_out, bits, tile, go) - the uniform-tile
    contract; only ``nnz_max`` may differ, and it is padded up to the
    per-projection maximum with zero blocks/scales (``nnz`` keeps the exact
    per-layer counts, so padding is never fetched by the guard). Accepts
    single-layer weights or multi-layer ones (their packed lists are
    concatenated in order). Macro-sharded inputs must all carry the same
    mesh; their per-layer ``col_inv`` indices stack alongside.
    """
    if isinstance(dws, DeployedWeight):
        dws = [dws]
    dws = list(dws)
    if not dws:
        raise ValueError("stack_deployed needs at least one DeployedWeight")
    ref = dws[0]
    for dw in dws[1:]:
        if (dw.d_in, dw.d_out, dw.bits) != (ref.d_in, ref.d_out, ref.bits):
            raise ValueError(
                "stack_deployed: mixed projection geometry "
                f"{(dw.d_in, dw.d_out, dw.bits)} vs "
                f"{(ref.d_in, ref.d_out, ref.bits)} - stack one projection "
                "at a time")
        if dw.mesh is not ref.mesh:
            raise ValueError("stack_deployed: mixed meshes across layers")
    packed = [p for dw in dws for p in dw.packed]
    shapes = {tuple(np.asarray(p["blocks"]).shape[i] for i in (0, 2, 3))
              for p in packed}
    if len(shapes) != 1:
        raise ValueError(
            f"stack_deployed: non-uniform (go, bk, bn) across layers "
            f"{sorted(shapes)} - repack with a uniform tile "
            "(sched.search uniform mode / compress(uniform=True))")
    sharded = ref.mesh is not None and "col_inv" in packed[0]
    if sharded != all("col_inv" in p for p in packed):
        raise ValueError("stack_deployed: sharded stack missing col_inv")
    nnz_max = max(int(np.asarray(p["row_idx"]).shape[1]) for p in packed)

    def pad(a, width):
        a = np.asarray(a)
        if a.shape[1] == width:
            return a
        pads = [(0, 0)] * a.ndim
        pads[1] = (0, width - a.shape[1])
        return np.pad(a, pads)  # zero blocks, zero scales, row_idx 0

    blocks = np.stack([pad(p["blocks"], nnz_max) for p in packed])
    scales = np.stack([pad(p["scales"], nnz_max) for p in packed])
    row_idx = np.stack([pad(p["row_idx"], nnz_max) for p in packed])
    nnz = np.stack([np.asarray(p["nnz"]) for p in packed])
    col_inv = (np.stack([np.asarray(p["col_inv"]) for p in packed])
               if sharded else None)
    if sharded:
        specs = deployed_weight_specs()
        stacked_specs = {
            k: P(*((None,) + tuple(specs[k])))
            for k in ("blocks", "scales", "row_idx", "nnz", "col_inv")}
        put = lambda k, v: jax.device_put(
            jnp.asarray(v), NamedSharding(ref.mesh, stacked_specs[k]))
        return StackedWeight(put("blocks", blocks), put("scales", scales),
                             put("row_idx", row_idx), put("nnz", nnz),
                             ref.d_in, ref.d_out, ref.bits,
                             col_inv=put("col_inv", col_inv), mesh=ref.mesh)
    if ref.mesh is not None:  # replicated on every device of the mesh
        put = lambda v: jax.device_put(jnp.asarray(v),
                                       NamedSharding(ref.mesh, P()))
        return StackedWeight(put(blocks), put(scales), put(row_idx),
                             put(nnz), ref.d_in, ref.d_out, ref.bits,
                             mesh=ref.mesh)
    return StackedWeight(jnp.asarray(blocks), jnp.asarray(scales),
                         jnp.asarray(row_idx), jnp.asarray(nnz),
                         ref.d_in, ref.d_out, ref.bits)


def fit_tile(d_in: int, d_out: int, bk: int, bn: int) -> tuple:
    """Largest (bk, bn) at most the requested tile that exactly divides
    (d_in, d_out) - ``pack_bsr`` requires exact tiling - with both edges
    multiples of the backend's tile quantum (``ops.tile_quantum``: the
    compiled TPU kernel needs lane-aligned blocks). Raises, naming the
    tile, when no such tile exists."""
    q = ops.tile_quantum()
    fit = (_largest_divisor(d_in, bk, q), _largest_divisor(d_out, bn, q))
    if None in fit:
        raise ValueError(
            f"tile {bk}x{bn}: no {q}-aligned tile at most that size divides "
            f"({d_in}, {d_out}) - the BSR kernel cannot run it here")
    return fit


def _largest_divisor(n: int, at_most: int, quantum: int = 1):
    """Largest multiple of ``quantum`` that divides ``n`` and is at most
    ``at_most``; None when there is none."""
    for d in range(min(at_most, n), 0, -1):
        if n % d == 0 and d % quantum == 0:
            return d
    return None


def deploy_weight(w, cim: CIMConfig, bk: int = 128, bn: int = 128,
                  target_sparsity: Optional[float] = None) -> DeployedWeight:
    """Quantize + prune + pack a (d_in, d_out) or stacked (L, d_in, d_out)
    master weight for serving.

    Pruning and quantization run in float32 on the weight's device; only
    the int8 levels cross to the host, where ``pack_bsr`` builds the packing.
    The packed arrays stay in host memory (numpy): the serving runtimes put
    on the device the form they run (``serve.stacked.stack`` the layer
    stack), so a packing is never held on the device twice."""
    w = jnp.asarray(w)
    stacked = w if w.ndim == 3 else w[None]
    bits = cim.quant.w_bits
    ts = (cim.sparsity.target_sparsity if target_sparsity is None
          else target_sparsity)
    packed = []
    for wl in stacked:
        wl = wl.astype(jnp.float32)
        mask = S.prune_mask_2d(wl, bk, bn, ts)
        wq = Q.mars_weight_quant(wl * mask, bits, cim.quant.group_size)
        packed.append(ops.pack_for_kernel(wq, bits=bits, bk=bk, bn=bn))
    return DeployedWeight(packed, stacked.shape[-2], stacked.shape[-1], bits)


def shardable_columns(dw: DeployedWeight, n_devices: int) -> bool:
    """True when every stacked layer's block-column count splits evenly
    over ``n_devices`` - the precondition for equal-shaped macro shards."""
    return all(int(p["blocks"].shape[0]) % n_devices == 0 for p in dw.packed)


def deployed_weight_specs(axis: str = MACRO_AXIS) -> Dict[str, P]:
    """PartitionSpecs for one macro-sharded packed projection dict - the
    layout contract ``shard_weight`` applies: block-column axis over the
    macro cluster, the un-permute index replicated."""
    return {
        "blocks": P(axis, None, None, None),
        "scales": P(axis, None),
        "row_idx": P(axis, None),
        "nnz": P(axis),
        "col_inv": P(),
        "density": P(),
    }


def shard_weight(dw: DeployedWeight, mesh: Mesh, axis: str = MACRO_AXIS,
                 assign: Optional[Callable] = None) -> DeployedWeight:
    """Column-shard a packed projection over the serving macro cluster.

    ``assign(nnz_counts, n_devices) -> (go,) device ids`` chooses which
    block columns live on which device (``sched.allocate.device_assignment``
    is the LPT policy; None = contiguous split). The packed arrays are
    permuted into device order on the column axis, ``device_put`` with the
    leading axis over ``mesh[axis]``, and a replicated ``col_inv`` records
    how to restore the original column order after the kernel's all-gather.
    Non-divisible projections are returned unchanged (served replicated) -
    sharding must never change which weights exist, only where they live.
    """
    n_dev = int(mesh.shape[axis])
    if dw.mesh is not None or n_dev <= 1 or not shardable_columns(dw, n_dev):
        return dw
    specs = deployed_weight_specs(axis)
    packed = []
    for p in dw.packed:
        counts = np.asarray(p["nnz"])
        go = counts.shape[0]
        if assign is None:
            dev = np.repeat(np.arange(n_dev), go // n_dev)
        else:
            dev = np.asarray(assign(counts, n_dev))
        perm = np.concatenate([np.flatnonzero(dev == d) for d in range(n_dev)])
        inv = np.argsort(perm)
        q = {k: np.asarray(p[k])[perm]
             for k in ("blocks", "scales", "row_idx", "nnz")}
        q["col_inv"] = np.asarray(inv, np.int32)
        packed.append({
            **{k: jax.device_put(jnp.asarray(v),
                                 NamedSharding(mesh, specs[k]))
               for k, v in q.items()},
            "density": p["density"],
        })
    return DeployedWeight(packed, dw.d_in, dw.d_out, dw.bits, mesh=mesh)


def replicate_weight(dw: DeployedWeight, mesh: Mesh) -> DeployedWeight:
    """Place a whole packing on every device of ``mesh`` - the serving form
    of a projection ``shard_weight`` cannot split. Its matmul then runs the
    kernel on every device under ``shard_map`` (a Mosaic kernel cannot be
    partitioned by the compiler inside a mesh program)."""
    if dw.mesh is not None or mesh.size <= 1:
        return dw
    rep = NamedSharding(mesh, P())
    packed = [{**{k: jax.device_put(jnp.asarray(p[k]), rep)
                  for k in ("blocks", "scales", "row_idx", "nnz")},
               "density": p["density"]} for p in dw.packed]
    return DeployedWeight(packed, dw.d_in, dw.d_out, dw.bits, mesh=mesh)


def bm_for_rows(rows: int) -> int:
    """Kernel row-tile for an activation row count: the next power of two in
    [8, 128]. A fixed bucket ladder instead of the raw row count means a
    changing active-batch / padded-prompt size maps to O(log) compiled
    kernels, not one per size - batch-server admission can't trigger a
    recompile cascade - and every tile is MXU-aligned."""
    b = 8
    while b < rows and b < 128:
        b *= 2
    return b


def deployed_matmul(x: jnp.ndarray, dw: DeployedWeight, layer: int = 0,
                    a_bits: int = 0, interpret: Optional[bool] = None
                    ) -> jnp.ndarray:
    """Serving-path matmul: eq.5 activation quant + BSR kernel.

    x: (..., d_in). The zero blocks dropped at packing are never fetched
    or multiplied - MARS §III.B on the MXU. When ``dw`` is macro-sharded,
    each device runs the kernel on its resident block columns only and the
    all-gathered output is un-permuted back to the logical column order.
    """
    dtype = x.dtype  # the output keeps the caller's activation dtype
    if a_bits:
        x = Q.quantize_activation(x.astype(jnp.float32), a_bits, signed=True)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, dw.d_in)
    bm = bm_for_rows(x2.shape[0])
    if dw.sharded:
        p = dw.packed[layer]
        go, _, _, bn = p["blocks"].shape
        y = ops.bsr_matmul_sharded(x2, p, dw.mesh, bm=bm, interpret=interpret)
        y = jnp.take(y.reshape(-1, go, bn), p["col_inv"], axis=1)
        y = y.reshape(-1, dw.d_out)
    elif dw.mesh is not None:
        y = ops.bsr_matmul_sharded(x2, dw.packed[layer], dw.mesh, bm=bm,
                                   interpret=interpret, axis=None)
    else:
        y = ops.bsr_matmul(x2, dw.packed[layer], bm=bm, interpret=interpret)
    return y.reshape(*lead, dw.d_out).astype(dtype)


def stacked_matmul(x: jnp.ndarray, sw: StackedWeight, layer,
                   a_bits: int = 0, interpret: Optional[bool] = None
                   ) -> jnp.ndarray:
    """Serving-path matmul against layer ``layer`` of a uniform envelope.

    ``layer`` may be a traced int32 (the scan index): the kernel is layer-
    indexed through the scalar-prefetch channel, so every layer runs the
    same compiled program. Numerics are bit-identical to
    ``deployed_matmul(x, dw_layer)`` - envelope padding contributes nothing.
    """
    dtype = x.dtype  # the output keeps the caller's activation dtype
    if a_bits:
        x = Q.quantize_activation(x.astype(jnp.float32), a_bits, signed=True)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, sw.d_in)
    bm = bm_for_rows(x2.shape[0])
    if sw.mesh is not None and sw.col_inv is None:
        y = ops.bsr_matmul_stacked_sharded(
            x2, sw.blocks, sw.scales, sw.row_idx, sw.nnz, layer, sw.mesh,
            bm=bm, interpret=interpret, axis=None)
    elif sw.mesh is not None:
        go, bn = int(sw.nnz.shape[1]), sw.tile[1]
        y = ops.bsr_matmul_stacked_sharded(
            x2, sw.blocks, sw.scales, sw.row_idx, sw.nnz, layer, sw.mesh,
            bm=bm, interpret=interpret)
        inv = jax.lax.dynamic_index_in_dim(
            sw.col_inv, jnp.asarray(layer, jnp.int32), axis=0, keepdims=False)
        y = jnp.take(y.reshape(-1, go, bn), inv, axis=1)
        y = y.reshape(-1, sw.d_out)
    else:
        y = ops.bsr_matmul_stacked(x2, sw.blocks, sw.scales, sw.row_idx,
                                   sw.nnz, layer, bm=bm, interpret=interpret)
    return y.reshape(*lead, sw.d_out).astype(dtype)


def unshard_weight(dw: DeployedWeight) -> DeployedWeight:
    """Undo ``shard_weight``: restore logical column order (via ``col_inv``)
    and drop the placement. This is the serialization form - artifacts store
    placement-free packings and are re-sharded at load onto whatever mesh
    the serving host has (host-side, like all packing)."""
    if dw.mesh is None:
        return dw
    packed = []
    for p in dw.packed:
        go = np.asarray(p["nnz"]).shape[0]
        inv = np.asarray(p["col_inv"]) if "col_inv" in p else np.arange(go)
        packed.append({
            **{k: np.asarray(p[k])[inv]
               for k in ("blocks", "scales", "row_idx", "nnz")},
            "density": p["density"],
        })
    return DeployedWeight(packed, dw.d_in, dw.d_out, dw.bits)


def uniform_fit_tile(shapes: Sequence[tuple], bk: int, bn: int) -> tuple:
    """One (bk, bn) for a whole network: the largest tile at most the
    requested one that exactly divides EVERY (d_in, d_out) in ``shapes`` -
    the CIM-Tuner-style network-wide mapping constraint that makes every
    projection's packing share a hardware-feasible envelope (and, like
    :func:`fit_tile`, a multiple of the backend's tile quantum)."""
    if not shapes:
        return (bk, bn)
    gk = 0
    gn = 0
    for d_in, d_out in shapes:
        gk = int(np.gcd(gk, int(d_in)))
        gn = int(np.gcd(gn, int(d_out)))
    return fit_tile(gk, gn, bk, bn)


def reference_matmul(x: jnp.ndarray, w, cim: CIMConfig,
                     target_sparsity: Optional[float] = None,
                     bk: int = 128, bn: int = 128) -> jnp.ndarray:
    """QAT-simulation oracle for deployed_matmul (same quant + mask path,
    dense math)."""
    ts = (cim.sparsity.target_sparsity if target_sparsity is None
          else target_sparsity)
    mask = S.prune_mask_2d(w, bk, bn, ts)
    wq = Q.mars_weight_quant(w * mask, cim.quant.w_bits, cim.quant.group_size)
    xq = Q.quantize_activation(x.astype(jnp.float32), cim.quant.a_bits,
                               signed=True)
    return (xq @ wq.astype(jnp.float32)).astype(x.dtype)


def deployment_report(deployed: Dict[str, DeployedWeight]) -> dict:
    """Storage accounting across all deployed projections (Table IV-style)."""
    total_dense_bits = total_weight_bits = total_index_bits = 0
    for name, dw in deployed.items():
        for p in dw.packed:
            nnz_blocks = int(np.asarray(p["nnz"]).sum())
            bk, bn = p["blocks"].shape[2], p["blocks"].shape[3]
            total_weight_bits += nnz_blocks * bk * bn * dw.bits
            total_index_bits += nnz_blocks * 32  # int32 row index per block
        total_dense_bits += dw.d_in * dw.d_out * len(dw.packed) * 32
    return {
        "dense_Mb": total_dense_bits / 2**20,
        "weight_Mb": total_weight_bits / 2**20,
        "index_Kb": total_index_bits / 2**10,
        "compression_x": total_dense_bits / max(total_weight_bits
                                                + total_index_bits, 1),
    }
