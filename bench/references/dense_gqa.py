"""Plain float32 reference of a dense GQA decoder served compressed.

This is the model the program serves, written out in ``jax.numpy`` with no
kernel, cache, batching or scan, and nothing imported from the program:

  * weights: the benchmark's own draw (``bench.weights``), pruned by
    ``(bk, bn)`` tile norm to ``target_sparsity`` and quantized to
    ``w_bits`` levels per group of output columns (MARS eqs. 6-8, with
    the tile and group the configuration states), then dequantized;
  * activations entering every projection quantized to ``a_bits``
    signed levels (MARS eq. 5, the LM form);
  * RMSNorm with gain ``1 + g``, rotary embedding on two halves, causal
    GQA softmax attention, SiLU-gated MLP, untied output head.

Every sum runs in float32 at ``highest`` matmul precision. ``rnd`` rounds
each value where the program stores it in its serving dtype: the identity
for the reference, a lower precision for the control.
"""
from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def identity(x):
    return x


def fp8(x):
    """Round to float8 e4m3 (saturating) and back: the control's
    precision, one step below the configuration's bfloat16."""
    return jnp.clip(x, -448.0, 448.0).astype(jnp.float8_e4m3fn).astype(F32)


ROUNDINGS = {"float32": identity, "float8_e4m3fn": fp8}


# -- compression: the configuration's weights as the program must serve them

def _tile_norms(w, bk, bn):
    di, do = w.shape
    t = w.reshape(di // bk, bk, do // bn, bn).transpose(0, 2, 1, 3)
    return jnp.sqrt(jnp.sum(t * t, axis=(-2, -1)) + 1e-24)


def dequantized(w, bk: int, bn: int, sparsity: float, bits: int,
                group: int):
    """Pruned, quantized and dequantized (d_in, d_out) float32 weight.

    Tiles whose L2 norm is not above the ``sparsity`` quantile of all tile
    norms are zeroed; the survivors go through tanh, are normalized by the
    largest magnitude in their group of ``group`` output columns, and are
    rounded to ``2**(bits-1) - 1`` levels over ``2**(bits-1)``. The
    operations are eager, in the order the compression defines them, so
    the same weights give the same levels bit for bit."""
    w = w.astype(F32)
    if sparsity > 0.0:
        norms = _tile_norms(w, bk, bn)
        thresh = jnp.quantile(norms.reshape(-1), sparsity)
        keep = norms > thresh
        mask = jnp.repeat(jnp.repeat(keep, bk, axis=0), bn, axis=1)
        w = w * mask.astype(F32)
    t = jnp.tanh(w)
    do = w.shape[-1]
    if group <= 0 or do % group or do == group:
        t = t / (jnp.max(jnp.abs(t)) + 1e-12)
    else:
        tg = t.reshape(w.shape[0], do // group, group)
        t = (tg / (jnp.max(jnp.abs(tg), axis=(0, 2), keepdims=True)
                   + 1e-12)).reshape(w.shape)
    t = jnp.clip(t, -1.0, 1.0)
    qmax = 2.0 ** (bits - 1) - 1.0
    levels = jnp.round(jnp.round(t * qmax) / 2.0 ** (bits - 1)
                       * 2.0 ** (bits - 1))
    return levels / 2.0 ** (bits - 1)


def _deq(conf: dict, w):
    bk, bn = conf["tile"]
    return dequantized(w, bk, bn, float(conf["target_sparsity"]),
                       int(conf["w_bits"]), int(conf["quant_group"]))


# -- the forward -------------------------------------------------------------

def _qa(x, bits):
    if bits >= 32:  # activations left in float
        return x
    qmax = 2.0 ** (bits - 1) - 1.0
    return jnp.round(jnp.clip(x, -1.0, 1.0) * qmax) / 2.0 ** (bits - 1)


def _mm(x, w, bits):
    return jnp.matmul(_qa(x, bits), w, precision=HIGHEST)


def _rms(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g)


def _rope(x, theta):
    s, dh = x.shape[-3], x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(0, half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("conf_items", "rnd_name"))
def _layer(x, p, conf_items, rnd_name):
    """One block over (B, S, D) float32 hidden states."""
    conf = dict(conf_items)
    r = ROUNDINGS[rnd_name]
    bits = int(conf["a_bits"])
    b, s, _ = x.shape
    nh, nkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    dh = conf["head_dim"]
    eps = float(conf["rms_norm_eps"])
    h = r(_rms(x, p["ln1"], eps))
    q = r(_mm(h, p["wq"], bits)).reshape(b, s, nh, dh)
    k = r(_mm(h, p["wk"], bits)).reshape(b, s, nkv, dh)
    v = r(_mm(h, p["wv"], bits)).reshape(b, s, nkv, dh)
    q = r(_rope(q, float(conf["rope_theta"])))
    k = r(_rope(k, float(conf["rope_theta"])))
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / np.sqrt(dh)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    pr = r(jax.nn.softmax(sc, axis=-1))
    o = r(jnp.einsum("bhqk,bkhd->bqhd", pr, v, precision=HIGHEST))
    x = r(x + r(_mm(o.reshape(b, s, nh * dh), p["wo"], bits)))
    h = r(_rms(x, p["ln2"], eps))
    g = r(_mm(h, p["w_gate"], bits))
    u = r(_mm(h, p["w_up"], bits))
    m = r(r(jax.nn.silu(g)) * u)
    return r(x + r(_mm(m, p["w_down"], bits)))


@functools.partial(jax.jit, static_argnames=("conf_items", "rnd_name"))
def _head(x, g, w, conf_items, rnd_name):
    conf = dict(conf_items)
    r = ROUNDINGS[rnd_name]
    h = r(_rms(x, g, float(conf["rms_norm_eps"])))
    return r(_mm(h, w, int(conf["a_bits"])))


def _items(conf: dict):
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "a_bits")
    return tuple((k, conf[k]) for k in keys)


def logits(conf: dict, seed: int, seqs: List[np.ndarray],
           rows: List[np.ndarray], roundings=("float32",), pad_to: int = 0):
    """Logits of each sequence at its listed positions, for each rounding.

    ``seqs[i]`` is a token sequence, ``rows[i]`` the positions whose next-
    token logits are wanted. Sequences are padded at the end to a common
    length (causal attention keeps the pad out of every real position) and
    run together, one layer at a time, so only one layer's float32 weights
    are on the device at once; ``pad_to`` sets the padded length when it
    is longer. Returns {rounding: [(len(rows[i]), vocab) float32 numpy
    arrays]}."""
    s = max(max(len(t) for t in seqs), pad_to)
    s = -(-s // 128) * 128
    toks = np.zeros((len(seqs), s), np.int32)
    for i, t in enumerate(seqs):
        toks[i, :len(t)] = t
    glob = W.globals_(conf, seed)
    x0 = jnp.take(glob["embed"], jnp.asarray(toks), axis=0).astype(F32)
    xs = {r: ROUNDINGS[r](x0) for r in roundings}
    items = _items(conf)
    for li in range(conf["num_hidden_layers"]):
        lw = W.layer(conf, seed, li)
        p = {k: (_deq(conf, v) if k in W.PROJECTIONS else v)
             for k, v in lw.items()}
        del lw
        for r in roundings:
            xs[r] = _layer(xs[r], p, items, r)
        del p
    head = _deq(conf, glob["head"])
    out = {}
    for r in roundings:
        res = []
        for i, pos in enumerate(rows):
            xi = xs[r][i, np.asarray(pos)][None]
            res.append(np.asarray(_head(xi, glob["final_ln"], head, items,
                                        r))[0])
        out[r] = res
    return out
