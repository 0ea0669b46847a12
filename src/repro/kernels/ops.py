"""Public jit'd wrappers for the Pallas kernels.

The kernel modules take ``interpret`` as a required argument. These
wrappers resolve ``interpret=None`` from the backend, in one place: the
kernels compile for the TPU when JAX runs on one, and run in Pallas
interpret mode (slow, exact) on any other backend, as in CPU tests.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.mapping import BsrWeight, pack_bsr
from ..core.quant import weight_int_levels
from . import cim_bsr_matmul, fake_quant as _fq, quant_matmul as _qm, ssd_intra as _ssd


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Interpret mode exactly when the backend is not a TPU."""
    return not on_tpu()


def tile_quantum() -> int:
    """Multiple every BSR tile edge must be on this backend."""
    return cim_bsr_matmul.tile_quantum(default_interpret())


def tile_sizes() -> tuple:
    """Tile edges the serving tile search offers on this backend."""
    return cim_bsr_matmul.tile_sizes(default_interpret())


# ---------------------------------------------------------------------------
# Deployment packing: quantized dense weight -> device arrays for the kernel
# ---------------------------------------------------------------------------


def pack_for_kernel(w_q, bits: int, bk: int = 128, bn: int = 128) -> dict:
    """Take eq.8 output (float levels/2^{b-1}) and produce the kernel's
    int8-blocks + scales + index arrays. Zero blocks are dropped (the CIM
    skip). The levels are rounded where ``w_q`` lives and only int8 crosses
    to the host; returns a dict of host (numpy) arrays."""
    levels = np.asarray(weight_int_levels(jnp.asarray(w_q), bits))
    bsr = pack_bsr(levels, bk, bn)
    go, nnz_max = bsr.row_idx.shape
    scales = np.full((go, nnz_max), 1.0 / (2.0 ** (bits - 1)), np.float32)
    return {
        "blocks": bsr.blocks,
        "scales": scales,
        "row_idx": bsr.row_idx,
        "nnz": bsr.nnz,
        "density": bsr.density,
    }


def bsr_matmul(x, packed: dict, bm: int = 128, interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    return cim_bsr_matmul.bsr_matmul(
        x, packed["blocks"], packed["scales"], packed["row_idx"], packed["nnz"],
        bm=bm, interpret=interpret,
    )


def bsr_matmul_sharded(x, packed: dict, mesh, bm: int = 128,
                       interpret: bool | None = None,
                       axis: str | None = cim_bsr_matmul.MACRO_AXIS):
    """Macro-cluster tensor-parallel bsr_matmul over a column-sharded
    packed dict (see ``core.deploy.shard_weight``). Output columns are in
    device order - the caller un-permutes with ``packed['col_inv']``.
    ``axis=None``: the packing is replicated on the mesh
    (``core.deploy.replicate_weight``) and the output is in logical order."""
    if interpret is None:
        interpret = default_interpret()
    return cim_bsr_matmul.bsr_matmul_sharded(
        x, packed["blocks"], packed["scales"], packed["row_idx"], packed["nnz"],
        mesh=mesh, axis=axis, bm=bm, interpret=interpret,
    )


def bsr_matmul_stacked(x, blocks, scales, row_idx, nnz, layer,
                       bm: int = 128, interpret: bool | None = None):
    """Layer-indexed matmul over a uniform-envelope layer stack (see
    ``core.deploy.stack_deployed``). ``layer`` is a traced int32 scalar -
    one compiled kernel serves every layer of the stack."""
    if interpret is None:
        interpret = default_interpret()
    return cim_bsr_matmul.bsr_matmul_stacked(
        x, blocks, scales, row_idx, nnz, layer, bm=bm, interpret=interpret,
    )


def bsr_matmul_stacked_sharded(x, blocks, scales, row_idx, nnz, layer, mesh,
                               bm: int = 128, interpret: bool | None = None,
                               axis: str | None = cim_bsr_matmul.MACRO_AXIS):
    """Macro-cluster tensor-parallel ``bsr_matmul_stacked``. Output columns
    are in device order - the caller un-permutes with the stack's per-layer
    ``col_inv`` row. ``axis=None``: a stack replicated on the mesh."""
    if interpret is None:
        interpret = default_interpret()
    return cim_bsr_matmul.bsr_matmul_stacked_sharded(
        x, blocks, scales, row_idx, nnz, layer, mesh=mesh, axis=axis, bm=bm,
        interpret=interpret,
    )


def quant_matmul(x, w_int8, scale, interpret: bool | None = None, **kw):
    if interpret is None:
        interpret = default_interpret()
    return _qm.quant_matmul(x, w_int8, scale, interpret=interpret, **kw)


def fake_quant(x, bits: int, signed: bool = False, interpret: bool | None = None, **kw):
    if interpret is None:
        interpret = default_interpret()
    return _fq.fake_quant(x, bits, signed=signed, interpret=interpret, **kw)


def ssd_intra(a, b, c, x, interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    return _ssd.ssd_intra_chunk(a, b, c, x, interpret=interpret)
