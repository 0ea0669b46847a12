"""Fused fake-quantization kernel (eq. 5 activations / eq. 8 weights).

QAT spends a large fraction of its elementwise budget on clamp+round+scale;
fusing it into one VMEM-tiled pass keeps the data in registers instead of
three HBM round-trips.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, o_ref, *, bits: int, signed: bool):
    x = x_ref[...].astype(jnp.float32)
    if signed:
        qmax = 2.0 ** (bits - 1) - 1.0
        y = jnp.round(jnp.clip(x, -1.0, 1.0) * qmax) / (2.0 ** (bits - 1))
    else:
        levels = 2.0**bits - 1.0
        y = jnp.round(jnp.clip(x, 0.0, 1.0) * levels) / (2.0**bits)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "signed", "bm", "bn", "interpret"))
def fake_quant(x: jnp.ndarray, bits: int, signed: bool = False,
               bm: int = 256, bn: int = 256, *,
               interpret: bool) -> jnp.ndarray:
    """Tiled fake-quant; arbitrary leading shape, last dim tiled."""
    shape = x.shape
    flat = x.reshape(-1, shape[-1])
    m, n = flat.shape
    pm, pn = (-m) % bm, (-n) % bn
    if pm or pn:
        flat = jnp.pad(flat, ((0, pm), (0, pn)))
    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, signed=signed),
        grid=(flat.shape[0] // bm, flat.shape[1] // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(flat.shape, x.dtype),
        interpret=interpret,
    )(flat)
    return out[:m, :n].reshape(shape)
