"""Compiled serving runtime: one jitted ``lax.scan`` over a layer stack.

The loop runtime (``serve.deployed``) executes a python loop over per-layer
packed weights, so every decode step traces L separate BSR-kernel dispatches
and restacks the whole KV cache - exactly the per-macro-dispatch overhead
the MARS multi-macro fabric exists to amortize. This module is the
compiled form:

  * :func:`stack` folds a :class:`~repro.serve.deployed.ServingParams` into
    a :class:`StackedParams`: dense per-layer leaves are stacked along a
    leading layer axis, and every compressed projection becomes a
    :class:`~repro.core.deploy.StackedWeight` uniform envelope
    (``stack_deployed``: slot axis padded to the per-projection max,
    per-layer ``nnz``/``row_idx`` exact).
  * prefill / decode then run ONE ``lax.scan`` over the layer index: the
    scan body builds a per-layer view where each compressed projection is a
    :class:`~repro.core.deploy.StackedLayerView` dispatching to the
    layer-indexed kernel - a single compiled decode step, no per-layer
    kernel launches, KV written via ``dynamic_update_slice`` into donated
    cache buffers (the scan's ys replace the loop runtime's per-step
    ``jnp.stack(ks)``).

Honesty contract: for the same ServingParams this runtime produces BIT-
IDENTICAL greedy tokens to the loop runtime - dense or compressed, single
device or macro-sharded. ``tests/test_stacked.py`` enforces it.

Timing a scan step is only meaningful at the dispatch boundary (the whole
layer loop is ONE compiled call, so per-layer wall clocks don't exist):
``BatchServer``'s ``step.dispatch`` span fences the decode dispatch with
``block_until_ready`` when observability (``repro.obs``) is enabled.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..core import deploy as D
from ..models import registry, transformer
from ..models import layers as L
from ..models.config import ModelConfig
from . import deployed as DP


@dataclasses.dataclass
class StackedParams:
    """Layer-stacked serving weights (pytree).

    ``dense`` holds the stacked (L, ...) per-layer leaves that stay on the
    float path (norm gains, MoE routers/expert stacks, any un-packed
    projection); ``packed`` maps projection name -> StackedWeight uniform
    envelope. ``head_t`` is the build-time tied-embeddings head."""

    embed: Any
    final_ln: Any
    dense: Dict[str, Any]
    packed: Dict[str, D.StackedWeight]
    head: Any = None
    mm_proj: Any = None
    head_t: Any = None

    @property
    def n_layers(self) -> int:
        if self.dense:
            return int(next(iter(self.dense.values())).shape[0])
        return int(next(iter(self.packed.values())).n_layers)


# By default XLA may keep bf16 intermediates at higher precision wherever
# its fusion choices allow, so the same step compiled for one device and
# for a macro mesh (different fusions) rounds differently: on a TPU v5e the
# greedy tokens of full-width yi-6b diverged. Serving steps compile with
# exactly the precision the program states - every op rounds to its dtype.
SERVING_COMPILER_OPTIONS = {"xla_allow_excess_precision": False}


def serving_jit(fn, **kw):
    """``jax.jit`` for a serving step, with ``SERVING_COMPILER_OPTIONS``:
    the step's bits do not depend on how the compiler fuses it, so they
    are the same on one device and sharded."""
    return jax.jit(fn, compiler_options=SERVING_COMPILER_OPTIONS, **kw)


jax.tree_util.register_pytree_node(
    StackedParams,
    lambda sp: ((sp.embed, sp.final_ln, sp.dense, sp.packed, sp.head,
                 sp.mm_proj, sp.head_t), None),
    lambda aux, ch: StackedParams(*ch),
)


def stack(sp: DP.ServingParams) -> StackedParams:
    """ServingParams (per-layer dicts) -> StackedParams (leading layer axis).

    Every projection key must be uniformly typed across layers (all packed
    or all dense) and, when packed, share the uniform envelope geometry -
    ``stack_deployed`` raises with a pointer at the uniform-tile search
    otherwise. Stacking is placement-preserving: macro-sharded projections
    stack into macro-sharded envelopes.
    """
    if not sp.layers:
        raise ValueError("stack: ServingParams has no layers")
    keys = list(sp.layers[0].keys())
    for i, p in enumerate(sp.layers[1:], 1):
        if list(p.keys()) != keys:
            raise ValueError(
                f"stack: layer {i} keys {sorted(p)} != layer 0 {sorted(keys)}")
    dense: Dict[str, Any] = {}
    packed: Dict[str, D.StackedWeight] = {}
    for k in keys:
        vs = [p[k] for p in sp.layers]
        n_packed = sum(isinstance(v, D.DeployedWeight) for v in vs)
        if n_packed == len(vs):
            packed[k] = D.stack_deployed(vs)
        elif n_packed == 0:
            dense[k] = jnp.stack([jnp.asarray(v) for v in vs])
        else:
            raise ValueError(
                f"stack: projection {k!r} is packed in {n_packed}/{len(vs)} "
                "layers - compress() packs all layers or none")
    # one device copy of what the scan runs: packed leaves still on the
    # host (the head's packing) move once here, not on every call
    return jax.device_put(StackedParams(
        embed=sp.embed, final_ln=sp.final_ln, dense=dense, packed=packed,
        head=sp.head, mm_proj=sp.mm_proj, head_t=sp.head_t))


# StackedParams exposes the same head/head_t/embed fields as ServingParams,
# so the loop runtime's head resolution applies verbatim - one source of
# truth for the tied-head precompute keeps the runtimes in lockstep
_head = DP._head


def _layer_view(sxp: StackedParams, p_dense: dict, li) -> dict:
    """Per-layer param dict for the standard block bodies: dense leaves are
    the scan's sliced xs; packed projections are layer-indexed views into
    the uniform envelopes (``li`` is the traced scan index)."""
    p = dict(p_dense)
    for k, sw in sxp.packed.items():
        p[k] = D.StackedLayerView(sw, li)
    return p


def _scan_xs(sxp: StackedParams, cfg: ModelConfig, *extra):
    window_arr, theta_arr = transformer._layer_kind_arrays(cfg)
    return (jnp.arange(cfg.n_layers), sxp.dense, window_arr, theta_arr,
            *extra)


# ---------------------------------------------------------------------------
# Forward paths: single lax.scan over the stacked layer pytree
# ---------------------------------------------------------------------------


def prefill_hidden(sxp: StackedParams, batch: dict, cfg: ModelConfig):
    """Full-sequence forward, same math as ``deployed.prefill_hidden`` but
    one compiled scan. Returns (hidden (B,S,D), cache k/v (L,B,S,KV,dh))."""
    x = transformer._embed_inputs(
        {"embed": sxp.embed, "mm_proj": sxp.mm_proj}, batch, cfg)
    _, S, _ = x.shape
    positions = jnp.arange(S)[None, :]

    def body(x, xs):
        li, p_dense, w, t = xs
        p = _layer_view(sxp, p_dense, li)
        x, _, kv = transformer._attn_mlp_body(p, x, cfg, w, t, positions)
        return x, kv

    x, (ks, vs) = jax.lax.scan(body, x, _scan_xs(sxp, cfg))
    x = L.rmsnorm(x, sxp.final_ln)
    return x, {"k": ks, "v": vs}


def prefill(sxp: StackedParams, batch: dict, cfg: ModelConfig):
    """Registry-signature prefill: (last-position logits, cache w/ 'pos')."""
    hidden, cache = prefill_hidden(sxp, batch, cfg)
    logits = L.logits_out(_head(sxp), hidden[:, -1:, :], cfg.cim)[:, 0, : cfg.vocab]
    total = batch["tokens"].shape[1]
    if cfg.family == "vlm":
        total += batch["patch_embeds"].shape[1]
    cache["pos"] = jnp.asarray(total, jnp.int32)
    return logits, cache


def prefill_last(sxp: StackedParams, tokens: jnp.ndarray,
                 true_len: jnp.ndarray, cfg: ModelConfig):
    """Batch-server prefill over padded prompts (see
    ``deployed.prefill_last`` for the causality argument)."""
    hidden, cache = prefill_hidden(sxp, {"tokens": tokens}, cfg)
    h_last = jnp.take(hidden, jnp.asarray(true_len - 1, jnp.int32), axis=1)
    logits = L.logits_out(_head(sxp), h_last[:, None, :], cfg.cim)[:, 0, : cfg.vocab]
    return logits, cache["k"], cache["v"]


def decode_step(sxp: StackedParams, cache: dict, tokens: jnp.ndarray,
                cfg: ModelConfig):
    """One decode step, single compiled scan; the per-layer KV write is a
    ``dynamic_update_slice`` into the scanned cache slice and the scan's ys
    ARE the new stacked cache (no per-step restack). Math-identical to
    ``deployed.decode_step``."""
    x = L.embed(sxp.embed, tokens, cfg.param_dtype)
    pos = cache["pos"]

    def body(x, xs):
        li, p_dense, w, t, kc, vc = xs
        p = _layer_view(sxp, p_dense, li)
        cfg_l = transformer._with_theta(cfg, t)
        h = L.rmsnorm(x, p["ln1"])
        attn, kc, vc = L.decode_attention(p, h, kc, vc, pos, cfg_l, window=w)
        x = x + attn
        h = L.rmsnorm(x, p["ln2"])
        x = x + DP._mlp(p, h, cfg)
        return x, (kc, vc)

    x, (k, v) = jax.lax.scan(
        body, x, _scan_xs(sxp, cfg, cache["k"], cache["v"]))
    new_cache = {"k": k, "v": v, "pos": pos + 1}
    x = L.rmsnorm(x, sxp.final_ln)
    logits = L.logits_out(_head(sxp), x, cfg.cim)[:, 0, : cfg.vocab]
    return logits, new_cache


def decode_step_paged(sxp: StackedParams, views_k: jnp.ndarray,
                      views_v: jnp.ndarray, pos: jnp.ndarray,
                      tokens: jnp.ndarray, cfg: ModelConfig):
    """One continuous-batching decode step over gathered paged-KV views,
    compiled as a single scan (the loop runtime's ``jnp.stack(ks)`` becomes
    the scan's ys). Same signature/semantics as
    ``deployed.decode_step_paged``."""
    x = L.embed(sxp.embed, tokens, cfg.param_dtype)

    def body(x, xs):
        li, p_dense, w, t, kview, vview = xs
        p = _layer_view(sxp, p_dense, li)
        cfg_l = transformer._with_theta(cfg, t)
        h = L.rmsnorm(x, p["ln1"])
        attn, kn, vn = L.decode_attention_multi(p, h, kview, vview, pos,
                                                cfg_l, window=w)
        x = x + attn
        h = L.rmsnorm(x, p["ln2"])
        x = x + DP._mlp(p, h, cfg)
        return x, (kn[:, 0], vn[:, 0])

    x, (ks, vs) = jax.lax.scan(
        body, x, _scan_xs(sxp, cfg, views_k, views_v))
    x = L.rmsnorm(x, sxp.final_ln)
    logits = L.logits_out(_head(sxp), x, cfg.cim)[:, 0, : cfg.vocab]
    return logits, ks, vs


def decode_step_masked(sxp: StackedParams, views_k: jnp.ndarray,
                       views_v: jnp.ndarray, pos: jnp.ndarray,
                       tokens: jnp.ndarray, cfg: ModelConfig,
                       attn_on: jnp.ndarray, mlp_on: jnp.ndarray):
    """One decode step over a SUBLAYER SUBSET of the same stacked envelope.

    ``attn_on`` / ``mlp_on`` are (L,) 0/1 masks scanned alongside the layer
    index: a masked-off sublayer is skipped with ``lax.cond`` - the branch
    genuinely elides the BSR matmuls (HLO conditional, not a multiply-by-
    zero), so a layer-skip draft really costs ~``keep`` of a target step.
    This is the layer-skip speculative draft's forward: the SAME
    StackedWeight envelope, no second packing, no extra weight memory -
    PR 4's layer-indexed kernel makes any layer subset addressable for
    free. Skipped-attention layers return zero KV rows; nothing ever reads
    them (a layer whose attention is off never attends), and draft KV is
    never committed to the pool anyway.

    Same signature/returns as :func:`decode_step_paged` plus the masks."""
    x = L.embed(sxp.embed, tokens, cfg.param_dtype)

    def body(x, xs):
        li, p_dense, w, t, kview, vview, a_on, m_on = xs
        p = _layer_view(sxp, p_dense, li)
        cfg_l = transformer._with_theta(cfg, t)

        def run_attn(args):
            x, kview, vview = args
            h = L.rmsnorm(x, p["ln1"])
            attn, kn, vn = L.decode_attention_multi(p, h, kview, vview, pos,
                                                    cfg_l, window=w)
            return x + attn, kn[:, 0], vn[:, 0]

        def skip_attn(args):
            x, kview, _ = args
            z = jnp.zeros_like(kview[:, 0])
            return x, z, z

        x, kn, vn = jax.lax.cond(a_on > 0, run_attn, skip_attn,
                                 (x, kview, vview))

        def run_mlp(x):
            h = L.rmsnorm(x, p["ln2"])
            return x + DP._mlp(p, h, cfg)

        x = jax.lax.cond(m_on > 0, run_mlp, lambda x: x, x)
        return x, (kn, vn)

    x, (ks, vs) = jax.lax.scan(
        body, x, _scan_xs(sxp, cfg, views_k, views_v, attn_on, mlp_on))
    x = L.rmsnorm(x, sxp.final_ln)
    logits = L.logits_out(_head(sxp), x, cfg.cim)[:, 0, : cfg.vocab]
    return logits, ks, vs


# MLP over (B, T, D) with sequential-decode semantics per token - one
# source of truth, shared with the loop runtime (docstring there)
_mlp_tokenwise = DP._mlp_tokenwise


def verify_step(sxp: StackedParams, views_k: jnp.ndarray,
                views_v: jnp.ndarray, pos: jnp.ndarray, tokens: jnp.ndarray,
                cfg: ModelConfig):
    """Batched multi-token target pass for speculative decoding.

    ``tokens`` (B, T) are row b's next T input tokens at absolute positions
    ``pos[b] .. pos[b]+T-1`` (the pending token followed by the draft run);
    a prefill-style causal pass over the gathered paged views with per-row
    positions (``layers.decode_attention_multi``), compiled as the same
    single ``lax.scan`` as :func:`decode_step_paged`.

    Returns (logits (B, T, V), k_new (L, B, T, KV, dh), v_new): position
    ``t``'s logits are BIT-IDENTICAL to what T sequential
    ``decode_step_paged`` calls would produce after consuming
    ``tokens[:, :t+1]`` - every op is row/position-independent and masked
    view padding is numerically inert - so greedy acceptance against these
    logits reproduces target-only greedy decode exactly. The caller commits
    only the accepted prefix of k_new/v_new to the KV pool (rejecting a
    draft suffix is a write-back rollback, not a compute rollback)."""
    x = L.embed(sxp.embed, tokens, cfg.param_dtype)  # (B, T, D)

    def body(x, xs):
        li, p_dense, w, t, kview, vview = xs
        p = _layer_view(sxp, p_dense, li)
        cfg_l = transformer._with_theta(cfg, t)
        h = L.rmsnorm(x, p["ln1"])
        attn, kn, vn = L.decode_attention_multi(p, h, kview, vview, pos,
                                                cfg_l, window=w)
        x = x + attn
        h = L.rmsnorm(x, p["ln2"])
        x = x + _mlp_tokenwise(p, h, cfg)
        return x, (kn, vn)

    x, (ks, vs) = jax.lax.scan(
        body, x, _scan_xs(sxp, cfg, views_k, views_v))
    x = L.rmsnorm(x, sxp.final_ln)
    logits = L.logits_out(_head(sxp), x, cfg.cim)[..., : cfg.vocab]
    return logits, ks, vs


def model_fns(cfg: ModelConfig) -> registry.ModelFns:
    """ModelFns over a :class:`StackedParams` - plug into ``serve.Engine``
    (``fns=stacked.model_fns(cfg)``) to serve the compiled runtime through
    the same loop as the registry/loop engines."""
    DP._check_family(cfg)

    def _no_init(*a, **k):
        raise NotImplementedError(
            "StackedParams are built from ServingParams via serve.stacked."
            "stack, not initialized")

    return registry.ModelFns(
        init_params=_no_init,
        train_loss=_no_init,
        prefill=prefill,
        decode_step=decode_step,
        init_cache=transformer.init_cache,
    )
