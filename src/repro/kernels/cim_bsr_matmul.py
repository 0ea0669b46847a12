"""Block-sparse int8 matmul - the MARS zero-group-set skip, TPU-native.

This is the paper's central hardware mechanism (§III.B) re-expressed for
the TPU memory hierarchy:

  SRAM-CIM macro                      TPU kernel
  ------------------------------      -----------------------------------
  nonzero group-sets packed in        nonzero (bk x bn) weight blocks
  the 64 Kb macro (Fig. 5b)           packed densely in HBM
  16-bit index codes in Index SRAM    row_idx (SMEM, scalar-prefetched)
  SAS generates IFM addresses         BlockSpec index_map steers the x DMA
  zero group-sets never computed      padding slots masked from the MXU
  ping-pong FM SRAMs                  Pallas double-buffered VMEM pipeline

Weights are stored as int8 levels (eq. 8 output x 2^{b-1}) with one f32
scale per block; dequantization rides the VPU before the MXU matmul, so -
exactly as in MARS - no high-precision weight path exists at rest.

Layout (column-major ELL, from core.mapping.pack_bsr):
  x:       (M, K)                activations
  blocks:  (go, nnz_max, bk, bn) int8 packed nonzero blocks
  scales:  (go, nnz_max)         f32 per-block scale
  row_idx: (go, nnz_max)         int32 k-block index per slot (pad -> 0)
  nnz:     (go,)                 int32 true slot counts
  out:     (M, N=go*bn)

Grid = (M/bm, go, nnz_max); the slot axis is innermost so each output tile
stays resident in VMEM across its accumulation.

``bsr_matmul_stacked`` is the uniform-envelope form and the one kernel
both entry points run: L layers of one projection, all packed to the SAME
(go, nnz_max, bk, bn) geometry, stacked along a leading layer axis. The
layer id rides the scalar-prefetch channel, so the BlockSpec index maps
steer every block DMA into the selected layer's slice - ONE compiled
kernel serves all L layers, and a ``lax.scan`` over the layer index never
re-traces or re-dispatches per layer. Only the selected layer's index
arrays (row_idx, nnz, scales) are prefetched into SMEM, so the kernel's
scalar memory does not grow with L. Envelope padding slots carry zero
blocks AND zero scales, so even a slot the per-layer ``nnz`` guard does not
skip contributes exactly 0 - stacking can never change numerics.
``bsr_matmul`` is the one-layer stack.

The ``*_sharded`` forms are the multi-macro layout: the ``go`` block-column
axis is split over a ``macro`` mesh axis (one shard per device, the way one
MARS layer spans several SRAM macros), each device runs the SAME kernel on
only its resident columns, and a single tiled all-gather at the projection
boundary reassembles the (M, N) output - no cross-device weight traffic.

Tiles: interpret mode runs any (bk, bn). Compiled for the TPU, Mosaic needs
the x (bm, bk) and out (bm, bn) blocks lane-aligned, so bk and bn must be
multiples of ``LANE``; :func:`tile_quantum` / :func:`tile_sizes` are the one
statement of that rule the tile search and the packers read.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P


DEFAULT_BM = 128

MACRO_AXIS = "macro"  # mesh axis name for the serving macro cluster

LANE = 128  # TPU lane width: compiled tiles are multiples of it
# tile edges the serving search offers: interpret mode takes any size (the
# small ones fit the smoke models), the compiled kernel only lane multiples
INTERPRET_TILES = (16, 32, 64)
COMPILED_TILES = (128, 256)


def tile_quantum(interpret: bool) -> int:
    """Granularity every bk and bn must be a multiple of for the kernel to
    run: 1 in interpret mode, ``LANE`` when compiled for the TPU."""
    return 1 if interpret else LANE


def tile_sizes(interpret: bool) -> tuple:
    """Tile edges (bk or bn) the serving tile search offers."""
    return INTERPRET_TILES if interpret else COMPILED_TILES


def check_tile(bk: int, bn: int, *, interpret: bool) -> None:
    """Raise, naming the tile, when (bk, bn) cannot compile."""
    q = tile_quantum(interpret)
    if bk % q or bn % q:
        raise ValueError(
            f"BSR tile {bk}x{bn} cannot compile for the TPU: bk and bn must "
            f"be multiples of {q} (Mosaic lane-tiles the x (bm, bk) and out "
            f"(bm, bn) blocks) - re-pack with a {q}-aligned tile")


def _kernel(layer_ref, row_idx_ref, nnz_ref, scales_ref, x_ref, blocks_ref,
            out_ref, *, nnz_max, acc_dtype):
    j, s = pl.program_id(1), pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # the guard uses the selected layer's true slot count; slots past it are
    # envelope padding (zero block, zero scale) and are skipped, and a
    # truncated layer (nnz > stored slots) accumulates only inert zeros
    @pl.when(s < nnz_ref[j])
    def _accum():
        scale = scales_ref[j * nnz_max + s].astype(acc_dtype)
        w = blocks_ref[0, 0, 0].astype(acc_dtype) * scale
        out_ref[...] += jnp.dot(
            x_ref[...].astype(acc_dtype), w, preferred_element_type=acc_dtype
        )


@functools.partial(
    jax.jit, static_argnames=("bm", "interpret", "acc_dtype")
)
def bsr_matmul_stacked(x: jnp.ndarray, blocks: jnp.ndarray,
                       scales: jnp.ndarray, row_idx: jnp.ndarray,
                       nnz: jnp.ndarray, layer: jnp.ndarray, *,
                       interpret: bool, bm: int = DEFAULT_BM,
                       acc_dtype=jnp.float32) -> jnp.ndarray:
    """y = x @ W[layer] for a layer-stacked BSR packing.

    blocks: (L, go, nnz_max, bk, bn); scales/row_idx: (L, go, nnz_max);
    nnz: (L, go); layer: scalar (or (1,)) int32 selecting the layer. The
    layer id is a traced value - the compiled kernel is layer-agnostic and
    the grid never grows with L, so a scan over layers is one dispatch per
    step, not one per (layer, projection). Returns (M, go*bn) in acc_dtype.
    """
    m, k = x.shape
    _, go, nnz_max, bk, bn = blocks.shape
    assert k % bk == 0, (k, bk)
    assert row_idx.shape == blocks.shape[:3]
    check_tile(bk, bn, interpret=interpret)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def pick(a):  # the selected layer's slice, flattened for SMEM
        return jax.lax.dynamic_index_in_dim(a, layer[0], 0,
                                            keepdims=False).reshape(-1)

    pad_m = (-m) % bm
    if pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
    mt = x.shape[0] // bm

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(mt, go, nnz_max),
        in_specs=[
            pl.BlockSpec((bm, bk),
                         lambda i, j, s, l, ri, nz, sc: (i, ri[j * nnz_max + s])),
            pl.BlockSpec((1, 1, 1, bk, bn),
                         lambda i, j, s, l, ri, nz, sc: (l[0], j, s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn),
                               lambda i, j, s, l, ri, nz, sc: (i, j)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, nnz_max=nnz_max, acc_dtype=acc_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((x.shape[0], go * bn), acc_dtype),
        interpret=interpret,
        # the name a device trace shows the kernel's calls under
        name="bsr_matmul_stacked",
    )(layer, pick(row_idx).astype(jnp.int32), pick(nnz).astype(jnp.int32),
      pick(scales).astype(jnp.float32), x, blocks)
    return out[:m]


def bsr_matmul(x: jnp.ndarray, blocks: jnp.ndarray, scales: jnp.ndarray,
               row_idx: jnp.ndarray, nnz: jnp.ndarray, *, interpret: bool,
               bm: int = DEFAULT_BM, acc_dtype=jnp.float32) -> jnp.ndarray:
    """y = x @ W for one BSR-packed W (the one-layer stack). Returns
    (M, go*bn) in acc_dtype."""
    return bsr_matmul_stacked(x, blocks[None], scales[None], row_idx[None],
                              nnz[None], 0, interpret=interpret, bm=bm,
                              acc_dtype=acc_dtype)


def bsr_matmul_stacked_sharded(x: jnp.ndarray, blocks: jnp.ndarray,
                               scales: jnp.ndarray, row_idx: jnp.ndarray,
                               nnz: jnp.ndarray, layer: jnp.ndarray, *,
                               mesh: Mesh, interpret: bool,
                               axis: str = MACRO_AXIS, bm: int = DEFAULT_BM,
                               acc_dtype=jnp.float32) -> jnp.ndarray:
    """Tensor-parallel ``bsr_matmul_stacked`` over the ``axis`` mesh
    dimension.

    The block-column axis (``go``, dim 1 of the stacked arrays) is sharded
    over the mesh; the layer axis and ``x`` are replicated (every device
    holds the full K, so ``row_idx`` needs no translation). Each device
    accumulates only its resident columns' slots - the per-device ``nnz``
    is its own macro occupancy - and one tiled all-gather on the N axis is
    the only collective. Output is the replicated (M, go*bn), columns in
    DEVICE order: callers that column-permuted the packing (LPT balancing)
    un-permute with their per-layer ``col_inv``.

    ``axis=None`` serves a packing replicated on every device of the mesh
    (a projection whose block columns do not split evenly): each device
    runs the whole projection and no collective runs. It still goes
    through ``shard_map``, because the compiler cannot partition a Mosaic
    kernel inside a mesh program on its own."""
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def _local(xl, b, s, ri, nz, l):
        y = bsr_matmul_stacked(xl, b, s, ri, nz, l, bm=bm,
                               interpret=interpret, acc_dtype=acc_dtype)
        if axis is None:
            return y
        return jax.lax.all_gather(y, axis, axis=1, tiled=True)

    f = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(), P(None, axis, None, None, None), P(None, axis, None),
                  P(None, axis, None), P(None, axis), P()),
        out_specs=P(), check_vma=False)
    return f(x, blocks, scales, row_idx, nnz, layer)


def bsr_matmul_sharded(x: jnp.ndarray, blocks: jnp.ndarray,
                       scales: jnp.ndarray, row_idx: jnp.ndarray,
                       nnz: jnp.ndarray, *, mesh: Mesh, interpret: bool,
                       axis: str = MACRO_AXIS, bm: int = DEFAULT_BM,
                       acc_dtype=jnp.float32) -> jnp.ndarray:
    """Tensor-parallel ``bsr_matmul``: the one-layer sharded stack."""
    return bsr_matmul_stacked_sharded(
        x, blocks[None], scales[None], row_idx[None], nnz[None], 0,
        mesh=mesh, interpret=interpret, axis=axis, bm=bm, acc_dtype=acc_dtype)
