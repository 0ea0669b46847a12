"""Decoder-only LM covering the dense / moe / vlm / ssm / hybrid families.

Structure is scan-over-layers with stacked parameters (compile time O(1) in
depth - essential for 512-device dry-runs on this CPU container). Family
quirks:

  * gemma3: per-layer sliding window + RoPE theta via stacked (L,) arrays.
  * moe (phi3.5 / grok): MoE MLP with grouped capacity dispatch.
  * vlm (llava): precomputed patch embeddings (frontend stub per spec)
    prepended to text embeddings through a projector.
  * ssm (mamba2): stacked Mamba2 blocks, no attention anywhere.
  * hybrid (zamba2): scan over super-layers of ``attn_every`` mamba blocks
    followed by one invocation of a SHARED attention+MLP block (weights
    shared across invocations, per-invocation gate) - plus a mamba tail.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from . import ssm as SSM
from .config import ModelConfig


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def _attn_layer_init(key, cfg: ModelConfig, dtype):
    d, dh = cfg.d_model, cfg.dh
    nh, nkv = cfg.n_heads_eff, cfg.n_kv_heads_eff
    ks = jax.random.split(key, 8)
    s = 1.0 / d**0.5

    def _padded(key, shape, pad_axis, true_n, eff_n):
        """Zero-init the TP-padding head slices (forward-identical)."""
        w = jax.random.normal(key, shape, dtype) * s
        if eff_n == true_n:
            return w
        m = (jnp.arange(eff_n * dh) < true_n * dh).astype(dtype)
        return w * (m[None, :] if pad_axis == 1 else m[:, None])

    p = {
        "ln1": jnp.zeros((d,), jnp.float32),
        "wq": _padded(ks[0], (d, nh * dh), 1, cfg.n_heads, nh),
        "wk": _padded(ks[1], (d, nkv * dh), 1, cfg.n_kv_heads, nkv),
        "wv": _padded(ks[2], (d, nkv * dh), 1, cfg.n_kv_heads, nkv),
        "wo": _padded(ks[3], (nh * dh, d), 0, cfg.n_heads, nh)
        * (d**0.5 / (nh * dh) ** 0.5),
        "ln2": jnp.zeros((d,), jnp.float32),
    }
    if cfg.family == "moe":
        e = cfg.n_experts
        p["router"] = jax.random.normal(ks[4], (d, e), jnp.float32) * s
        e_eff = e * cfg.expert_split
        ff = cfg.d_ff // cfg.expert_split
        p["w_gate"] = jax.random.normal(ks[5], (e_eff, d, ff), dtype) * s
        p["w_up"] = jax.random.normal(ks[6], (e_eff, d, ff), dtype) * s
        p["w_down"] = jax.random.normal(ks[7], (e_eff, ff, d), dtype) * (
            1.0 / cfg.d_ff**0.5
        )
    else:
        p["w_gate"] = jax.random.normal(ks[5], (d, cfg.d_ff), dtype) * s
        p["w_up"] = jax.random.normal(ks[6], (d, cfg.d_ff), dtype) * s
        p["w_down"] = jax.random.normal(ks[7], (cfg.d_ff, d), dtype) * (
            1.0 / cfg.d_ff**0.5
        )
    return p


def _stack(layer_fn, keys):
    return jax.vmap(layer_fn)(keys)


def _init_globals(cfg: ModelConfig, keys) -> dict:
    dtype = cfg.param_dtype
    d = cfg.d_model
    params = {
        "embed": jax.random.normal(keys[0], (cfg.vocab_eff, d), dtype) * 0.02,
        "final_ln": jnp.zeros((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["head"] = jax.random.normal(keys[1], (d, cfg.vocab_eff), dtype) * 0.02
    if cfg.family == "vlm":
        params["mm_proj"] = jax.random.normal(keys[5], (d, d), dtype) * (1.0 / d**0.5)
    return params


def init_layerwise(cfg: ModelConfig, key):
    """``init_params`` for the dense / moe / vlm families, one layer at a
    time: returns (params without ``"layers"``, ``layer``) where
    ``layer(i)`` builds layer i's dict - the values of
    ``init_params(cfg, key)["layers"][i]`` - on demand. A caller that
    packs or serves layer by layer never holds the whole layer stack."""
    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(f"init_layerwise: no per-layer form for {cfg.family!r}")
    keys = jax.random.split(key, 8)
    lkeys = jax.random.split(keys[2], cfg.n_layers)
    # eager like init_params: a jit may fold the scale constants and
    # round differently in the last place
    return (_init_globals(cfg, keys),
            lambda i: _attn_layer_init(lkeys[i], cfg, cfg.param_dtype))


def init_params(cfg: ModelConfig, key) -> dict:
    dtype = cfg.param_dtype
    keys = jax.random.split(key, 8)
    d = cfg.d_model
    params = _init_globals(cfg, keys)

    if cfg.family in ("dense", "moe", "vlm"):
        lkeys = jax.random.split(keys[2], cfg.n_layers)
        params["layers"] = _stack(
            functools.partial(_attn_layer_init, cfg=cfg, dtype=dtype), lkeys
        )
    elif cfg.family == "ssm":
        lkeys = jax.random.split(keys[2], cfg.n_layers)
        params["layers"] = _stack(
            lambda k: {"ln": jnp.zeros((d,), jnp.float32), **SSM.mamba_init(k, cfg, dtype)},
            lkeys,
        )
    elif cfg.family == "hybrid":
        n_super = cfg.n_layers // cfg.attn_every
        n_body = n_super * cfg.attn_every
        bkeys = jax.random.split(keys[2], n_body).reshape(n_super, cfg.attn_every, 2)
        params["layers_body"] = jax.vmap(
            jax.vmap(lambda k: {"ln": jnp.zeros((d,), jnp.float32),
                                **SSM.mamba_init(k, cfg, dtype)})
        )(bkeys)
        n_tail = cfg.n_layers - n_body
        if n_tail:
            tkeys = jax.random.split(keys[3], n_tail)
            params["layers_tail"] = _stack(
                lambda k: {"ln": jnp.zeros((d,), jnp.float32), **SSM.mamba_init(k, cfg, dtype)},
                tkeys,
            )
        params["shared_attn"] = _attn_layer_init(keys[4], cfg, dtype)
        params["attn_gate"] = jnp.ones((n_super,), jnp.float32)
    else:
        raise ValueError(cfg.family)
    return params


# ---------------------------------------------------------------------------
# Per-layer bodies
# ---------------------------------------------------------------------------


def _attn_mlp_body(p, x, cfg: ModelConfig, window, theta, positions):
    """One transformer block (full-seq). Returns (x, aux, (k, v))."""
    cfg_l = cfg if theta is None else _with_theta(cfg, theta)
    h = L.rmsnorm(x, p["ln1"])
    attn, kv = L.self_attention(p, h, cfg_l, window=window, positions=positions)
    x = x + attn
    h = L.rmsnorm(x, p["ln2"])
    if cfg.family == "moe":
        y, aux = L.moe_block(p, h, cfg)
    else:
        y, aux = L.gated_mlp(p, h, cfg.cim), jnp.zeros((), jnp.float32)
    x = x + y
    if cfg.seq_shard_residual:
        from jax.sharding import PartitionSpec as _PS
        x = jax.lax.with_sharding_constraint(x, _PS("data", "model", None))
    return x, aux, kv


class _ThetaCfg:
    """Tiny proxy so a traced per-layer rope theta can override the config."""

    def __init__(self, cfg, theta):
        object.__setattr__(self, "_cfg", cfg)
        object.__setattr__(self, "rope_theta", theta)

    def __getattr__(self, k):
        return getattr(self._cfg, k)


def _with_theta(cfg, theta):
    return _ThetaCfg(cfg, theta)


def _layer_kind_arrays(cfg: ModelConfig):
    kinds = cfg.layer_kinds()
    window = jnp.asarray(
        [cfg.window if k == 1 else 0 for k in kinds], jnp.int32
    )
    if cfg.local_global_ratio > 0:
        theta = jnp.asarray(
            [cfg.rope_theta if k == 1 else 1e6 for k in kinds], jnp.float32
        )
    else:
        theta = jnp.full((cfg.n_layers,), cfg.rope_theta, jnp.float32)
    return window, theta


def _maybe_remat(fn, cfg):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        # selective: keep matmul/einsum outputs, recompute elementwise only
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


def _scan(body, init, xs, cfg):
    return jax.lax.scan(body, init, xs, unroll=True if cfg.scan_unroll else 1)


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------


def _embed_inputs(params, batch, cfg: ModelConfig):
    x = L.embed(params["embed"], batch["tokens"], cfg.param_dtype)
    if cfg.family == "vlm":
        patches = batch["patch_embeds"].astype(cfg.param_dtype)
        patches = patches @ params["mm_proj"].astype(patches.dtype)
        x = jnp.concatenate([patches, x], axis=1)
    return x


def forward_hidden(params, batch, cfg: ModelConfig, collect_cache: bool = False):
    """Returns (hidden (B,S,D), aux_loss, cache-or-None)."""
    x = _embed_inputs(params, batch, cfg)
    Bsz, S, _ = x.shape
    positions = jnp.arange(S)[None, :]

    if cfg.family in ("dense", "moe", "vlm"):
        window_arr, theta_arr = _layer_kind_arrays(cfg)

        def body(carry, xs):
            x, aux = carry
            p, w, t = xs
            x, a, kv = _attn_mlp_body(p, x, cfg, w, t, positions)
            return (x, aux + a), kv if collect_cache else None

        body = _maybe_remat(body, cfg)
        (x, aux), kvs = _scan(
            body, (x, jnp.zeros((), jnp.float32)),
            (params["layers"], window_arr, theta_arr), cfg,
        )
        cache = None
        if collect_cache:
            cache = {"k": kvs[0], "v": kvs[1]}  # (L,B,S,KV,dh)

    elif cfg.family == "ssm":

        def body(carry, p):
            x, aux = carry
            h = L.rmsnorm(x, p["ln"])
            y, (conv_tail, h_last) = SSM.mamba_block(p, h, cfg)
            return (x + y, aux), (conv_tail, h_last) if collect_cache else None

        body = _maybe_remat(body, cfg)
        (x, aux), tails = _scan(
            body, (x, jnp.zeros((), jnp.float32)), params["layers"], cfg
        )
        cache = None
        if collect_cache:
            cache = {"conv": tails[0], "ssm": tails[1]}

    elif cfg.family == "hybrid":
        x, aux, cache = _hybrid_forward(params, x, cfg, positions, collect_cache)
    else:
        raise ValueError(cfg.family)

    x = L.rmsnorm(x, params["final_ln"])
    return x, aux, cache


def _hybrid_forward(params, x, cfg: ModelConfig, positions, collect_cache):
    shared = params["shared_attn"]
    aux0 = jnp.zeros((), jnp.float32)

    def mamba_one(x, p):
        h = L.rmsnorm(x, p["ln"])
        y, tail = SSM.mamba_block(p, h, cfg)
        return x + y, tail

    def super_body(carry, xs):
        x, aux = carry
        p_stack, gate = xs

        def inner(x, p):
            x, tail = mamba_one(x, p)
            return x, tail

        x, tails = jax.lax.scan(inner, x, p_stack)
        h = L.rmsnorm(x, shared["ln1"])
        attn, kv = L.self_attention(shared, h, cfg, window=cfg.window,
                                    positions=positions)
        x = x + gate.astype(x.dtype) * attn
        h = L.rmsnorm(x, shared["ln2"])
        x = x + gate.astype(x.dtype) * L.gated_mlp(shared, h, cfg.cim)
        out = (tails, kv) if collect_cache else None
        return (x, aux), out

    super_body = _maybe_remat(super_body, cfg)
    (x, aux), outs = _scan(
        super_body, (x, aux0), (params["layers_body"], params["attn_gate"]), cfg
    )
    cache = None
    if collect_cache:
        tails, kvs = outs
        cache = {
            "conv": tails[0],  # (n_super, attn_every, B, W-1, C)
            "ssm": tails[1],
            "k": kvs[0],  # (n_super, B, S, KV, dh)
            "v": kvs[1],
        }

    if "layers_tail" in params:

        def tail_body(carry, p):
            x, aux = carry
            x, tail = mamba_one(x, p)
            return (x, aux), tail if collect_cache else None

        tail_body = _maybe_remat(tail_body, cfg)
        (x, aux), tails = _scan(tail_body, (x, aux), params["layers_tail"], cfg)
        if collect_cache:
            cache["conv_tail"] = tails[0]
            cache["ssm_tail"] = tails[1]
    return x, aux, cache


# ---------------------------------------------------------------------------
# Losses / serving entry points
# ---------------------------------------------------------------------------


def train_loss(params, batch, cfg: ModelConfig) -> jnp.ndarray:
    """Next-token CE (+ MoE aux). batch: tokens (B,S) [, patch_embeds,
    loss_mask]. For vlm, patches prepend - labels cover text only."""
    hidden, aux, _ = forward_hidden(params, batch, cfg)
    head = params["head"] if "head" in params else params["embed"].T
    if cfg.family == "vlm":
        npatch = batch["patch_embeds"].shape[1]
        hidden = hidden[:, npatch:, :]
    logits = L.logits_out(head, hidden, cfg.cim)
    if cfg.vocab_eff != cfg.vocab:
        # padded vocab columns never win: mask before the softmax
        pad_mask = jnp.arange(cfg.vocab_eff) >= cfg.vocab
        logits = jnp.where(pad_mask[None, None, :], L.NEG_INF, logits.astype(jnp.float32))
    labels = batch["tokens"][:, 1:]
    logits = logits[:, :-1, :]
    mask = batch.get("loss_mask")
    if mask is not None:
        mask = mask[:, 1:]
    loss = L.cross_entropy(logits, labels, mask)
    return loss + 0.01 * aux


def prefill(params, batch, cfg: ModelConfig):
    """Returns (last-position logits (B,V), cache dict with 'pos')."""
    hidden, _, cache = forward_hidden(params, batch, cfg, collect_cache=True)
    head = params["head"] if "head" in params else params["embed"].T
    logits = L.logits_out(head, hidden[:, -1:, :], cfg.cim)[:, 0, : cfg.vocab]
    cache = dict(cache)
    total = batch["tokens"].shape[1]
    if cfg.family == "vlm":
        total += batch["patch_embeds"].shape[1]
    cache["pos"] = jnp.asarray(total, jnp.int32)
    return logits, cache


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int, dtype=None) -> dict:
    """Empty decode cache (for decode-only dry-runs and serving)."""
    dtype = dtype or cfg.param_dtype
    if cfg.family in ("dense", "moe", "vlm"):
        shape = (cfg.n_layers, batch_size, max_len, cfg.n_kv_heads_eff, cfg.dh)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
                "pos": jnp.zeros((), jnp.int32)}
    if cfg.family == "ssm":
        di, N, H, W = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads, cfg.conv_width
        conv_dim = di + 2 * N
        return {
            "conv": jnp.zeros((cfg.n_layers, batch_size, W - 1, conv_dim), dtype),
            "ssm": jnp.zeros((cfg.n_layers, batch_size, H, di // H, N), dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
    if cfg.family == "hybrid":
        n_super = cfg.n_layers // cfg.attn_every
        n_tail = cfg.n_layers - n_super * cfg.attn_every
        di, N, H, W = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads, cfg.conv_width
        conv_dim = di + 2 * N
        kv_len = min(max_len, cfg.window) if cfg.window else max_len
        c = {
            "conv": jnp.zeros((n_super, cfg.attn_every, batch_size, W - 1, conv_dim), dtype),
            "ssm": jnp.zeros((n_super, cfg.attn_every, batch_size, H, di // H, N), dtype),
            "k": jnp.zeros((n_super, batch_size, kv_len, cfg.n_kv_heads_eff, cfg.dh), dtype),
            "v": jnp.zeros((n_super, batch_size, kv_len, cfg.n_kv_heads_eff, cfg.dh), dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
        if n_tail:
            c["conv_tail"] = jnp.zeros((n_tail, batch_size, W - 1, conv_dim), dtype)
            c["ssm_tail"] = jnp.zeros((n_tail, batch_size, H, di // H, N), dtype)
        return c
    raise ValueError(cfg.family)


def pad_cache(cache: dict, max_len: int) -> dict:
    """Grow a prefill cache's seq axis to ``max_len`` for decoding."""
    out = dict(cache)
    for key in ("k", "v"):
        if key in cache:
            c = cache[key]
            pad = max_len - c.shape[2]
            if pad > 0:
                cfgpad = [(0, 0)] * c.ndim
                cfgpad[2] = (0, pad)
                out[key] = jnp.pad(c, cfgpad)
    return out


def decode_step(params, cache: dict, tokens: jnp.ndarray, cfg: ModelConfig):
    """One decode step. tokens: (B, 1). Returns (logits (B,V), new cache)."""
    x = L.embed(params["embed"], tokens, cfg.param_dtype)
    pos = cache["pos"]

    if cfg.family in ("dense", "moe", "vlm"):
        window_arr, theta_arr = _layer_kind_arrays(cfg)

        def body(x, xs):
            p, w, t, kc, vc = xs
            cfg_l = _with_theta(cfg, t)
            h = L.rmsnorm(x, p["ln1"])
            attn, kc, vc = L.decode_attention(p, h, kc, vc, pos, cfg_l, window=w)
            x = x + attn
            h = L.rmsnorm(x, p["ln2"])
            if cfg.family == "moe":
                y, _ = L.moe_block(p, h, cfg)
            else:
                y = L.gated_mlp(p, h, cfg.cim)
            return x + y, (kc, vc)

        x, (k, v) = _scan(
            body, x, (params["layers"], window_arr, theta_arr, cache["k"], cache["v"]), cfg
        )
        new_cache = {"k": k, "v": v, "pos": pos + 1}

    elif cfg.family == "ssm":

        def body(x, xs):
            p, conv, h = xs
            hin = L.rmsnorm(x, p["ln"])
            y, conv, h = SSM.mamba_decode_step(p, hin, conv, h, cfg)
            return x + y, (conv, h)

        x, (conv, h) = _scan(body, x, (params["layers"], cache["conv"], cache["ssm"]), cfg)
        new_cache = {"conv": conv, "ssm": h, "pos": pos + 1}

    elif cfg.family == "hybrid":
        x, new_cache = _hybrid_decode(params, cache, x, cfg)
        new_cache["pos"] = pos + 1
    else:
        raise ValueError(cfg.family)

    x = L.rmsnorm(x, params["final_ln"])
    head = params["head"] if "head" in params else params["embed"].T
    logits = L.logits_out(head, x, cfg.cim)[:, 0, : cfg.vocab]
    return logits, new_cache


def _hybrid_decode(params, cache, x, cfg: ModelConfig):
    shared = params["shared_attn"]
    pos = cache["pos"]
    kv_len = cache["k"].shape[2]
    ring = bool(cfg.window) and kv_len == min(cfg.window, kv_len)

    def super_body(x, xs):
        p_stack, gate, conv, h, kc, vc = xs

        def inner(x, ys):
            p, cv, hh = ys
            hin = L.rmsnorm(x, p["ln"])
            y, cv, hh = SSM.mamba_decode_step(p, hin, cv, hh, cfg)
            return x + y, (cv, hh)

        x, (conv, h) = jax.lax.scan(inner, x, (p_stack, conv, h))
        hin = L.rmsnorm(x, shared["ln1"])
        attn, kc, vc = L.decode_attention(shared, hin, kc, vc, pos, cfg,
                                          window=0, use_rope=True, ring=ring)
        x = x + gate.astype(x.dtype) * attn
        hin = L.rmsnorm(x, shared["ln2"])
        x = x + gate.astype(x.dtype) * L.gated_mlp(shared, hin, cfg.cim)
        return x, (conv, h, kc, vc)

    x, (conv, h, k, v) = _scan(
        super_body, x,
        (params["layers_body"], params["attn_gate"], cache["conv"], cache["ssm"],
         cache["k"], cache["v"]), cfg,
    )
    new_cache = {"conv": conv, "ssm": h, "k": k, "v": v}

    if "layers_tail" in params:

        def tail(x, ys):
            p, cv, hh = ys
            hin = L.rmsnorm(x, p["ln"])
            y, cv, hh = SSM.mamba_decode_step(p, hin, cv, hh, cfg)
            return x + y, (cv, hh)

        x, (cv, hh) = _scan(
            tail, x, (params["layers_tail"], cache["conv_tail"], cache["ssm_tail"]), cfg
        )
        new_cache["conv_tail"] = cv
        new_cache["ssm_tail"] = hh
    return x, new_cache
