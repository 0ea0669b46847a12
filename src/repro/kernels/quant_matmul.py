"""Dense int8-weight matmul with per-output-channel scales.

The non-sparse CIM macro (the paper's "baseline" accelerator): weights
live as int8 levels, activations stream through, dequantization happens
once per output tile after K-accumulation (scale factors out of the K
sum because MARS scales are per output group - eq. 8).

  x: (M, K)  float
  w: (K, N)  int8 levels
  scale: (N,) f32      y = (x @ w) * scale
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, s_ref, out_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _finish():
        out_ref[...] = (acc_ref[...] * s_ref[...]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def quant_matmul(x: jnp.ndarray, w: jnp.ndarray, scale: jnp.ndarray,
                 bm: int = 128, bn: int = 128, bk: int = 128, *,
                 interpret: bool) -> jnp.ndarray:
    m, k = x.shape
    k2, n = w.shape
    assert k == k2 and scale.shape == (n,)
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pk or pn:
        w = jnp.pad(w, ((0, pk), (0, pn)))
    if pn:
        scale = jnp.pad(scale, (0, pn))
    mt, nt, kt = x.shape[0] // bm, w.shape[1] // bn, x.shape[1] // bk

    out = pl.pallas_call(
        functools.partial(_kernel, nk=kt),
        grid=(mt, nt, kt),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], w.shape[1]), jnp.float32),
        scratch_shapes=[pltpu_vmem((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w, scale[None, :].astype(jnp.float32))
    return out[:m, :n]


def pltpu_vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)
