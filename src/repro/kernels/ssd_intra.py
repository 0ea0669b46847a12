"""Fused SSD intra-chunk kernel (Mamba2's quadratic block, VMEM-resident).

EXPERIMENTS.md §Perf cell 2 identified the SSD intra-chunk computation as
mamba2's dominant memory term: in pure JAX the (B, nc, H, l, l) decay/score
product materializes in HBM three times (s, s*L, backward). This kernel is
the Pallas fix: for one (batch*chunk, head) grid cell the whole chain

    s   = C @ B^T                  (l, l)
    L   = exp(segsum(a))           (l, l)  causal decay
    y   = (s * L) @ (x * dt)       (l, P)

stays in VMEM - HBM traffic drops from O(l^2) to O(l*(N+P)) per tile,
the same insight as flash attention (and as MARS's ping-pong FM SRAMs:
intermediates live in the near-compute memory, never the big one).

Shapes per grid cell (c = flattened batch*chunk index, h = head):
  a:  (l,)  post-discretization decay logits (dt * A, negative)
  b:  (l, N), c_in: (l, N)  shared across heads (single group)
  x:  (l, P)  head slice of (x * dt)
  y:  (l, P)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(a_ref, b_ref, c_ref, x_ref, y_ref):
    l = a_ref.shape[-1]
    a = a_ref[0, 0].astype(jnp.float32)  # (l,)
    cum = jnp.cumsum(a)
    diff = cum[:, None] - cum[None, :]  # segsum: sum a[(j, i]]
    causal = jnp.arange(l)[:, None] >= jnp.arange(l)[None, :]
    ldecay = jnp.where(causal, jnp.exp(diff), 0.0)  # (l, l)
    s = jnp.dot(c_ref[0].astype(jnp.float32),
                b_ref[0].astype(jnp.float32).T,
                preferred_element_type=jnp.float32)  # (l, l)
    y = jnp.dot(s * ldecay, x_ref[0, 0].astype(jnp.float32),
                preferred_element_type=jnp.float32)  # (l, P)
    y_ref[0, 0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra_chunk(a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray,
                    x: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    """Batched intra-chunk SSD.

    a: (C, H, l); b, c: (C, l, N); x: (C, l, H, P)  ->  y: (C, l, H, P)
    where C = batch*num_chunks flattened. Grid = (C, H): one chunk-head
    tile per step; b/c re-read per head (they are small: l x N).
    """
    C, H, l = a.shape
    N = b.shape[-1]
    P = x.shape[-1]
    xt = x.transpose(0, 2, 1, 3)  # (C, H, l, P)

    y = pl.pallas_call(
        _kernel,
        grid=(C, H),
        in_specs=[
            pl.BlockSpec((1, 1, l), lambda i, h: (i, h, 0)),
            pl.BlockSpec((1, l, N), lambda i, h: (i, 0, 0)),
            pl.BlockSpec((1, l, N), lambda i, h: (i, 0, 0)),
            pl.BlockSpec((1, 1, l, P), lambda i, h: (i, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, l, P), lambda i, h: (i, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((C, H, l, P), x.dtype),
        interpret=interpret,
    )(a.transpose(0, 1, 2), b, c, xt)
    return y.transpose(0, 2, 1, 3)  # (C, l, H, P)
