"""Compressed serving: registry params -> BSR-packed weights on the hot path.

MARS's inference premise (§III) is that the compressed representation - the
nonzero group-sets plus index codes - is BOTH the at-rest and the at-compute
form. For the LM zoo that means every CIM-mapped projection must execute
through ``core.deploy.deployed_matmul`` (the int8 block-sparse Pallas
kernel) at serving time, not just in kernel benchmarks.

This module provides the bridge:

  * :class:`ServingParams` - per-layer serving weights for the dense / moe /
    vlm families, registered as a jax pytree. Leaves are either raw arrays
    (dense serving) or :class:`~repro.core.deploy.DeployedWeight` (compressed
    serving); ``models.layers.cim_matmul`` dispatches per leaf, so the SAME
    forward code serves both.
  * :func:`compress` - walks a registry model's params and runs every
    2-D CIM-mapped projection (QKV/O, MLP, LM head) through ``deploy_weight``.
    The (bk, bn) block shape per projection comes from a ``sched.search``
    schedule, so the tile the simulator chose IS the tile the kernel runs.
  * :func:`model_fns` - prefill / decode_step with the registry signatures
    (python loop over per-layer packed weights), so ``serve.Engine`` serves
    compressed weights unchanged. This is the LOOP runtime - the reference
    the compiled ``serve.stacked`` scan runtime must reproduce bit-exactly.
  * :func:`decode_step_paged` - the per-row-position decode step the
    continuous-batching server drives over a paged KV view.
  * :func:`save_artifact` / :func:`load_artifact` - the offline serving
    artifact flow: mapping search + quantize + prune + BSR packing run ONCE
    at compile time, the packed :class:`ServingParams` lands on disk via
    ``train.checkpoint``, and a serving host boots from the directory
    without re-packing (``launch/serve.py --artifact DIR``).
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import deploy as D
from ..kernels import ops
from ..models import registry, transformer
from ..models import layers as L
from ..models.config import ModelConfig
from ..sched import (NetworkSchedule, lm_graph, schedule_from_search,
                     search_mapping)
from ..train import checkpoint as ckpt

# projections deployed per transformer block (2-D leaves only: MoE expert
# stacks are 3-D and stay on the dense/QAT path)
PROJECTIONS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")

SUPPORTED_FAMILIES = ("dense", "moe", "vlm")


@dataclasses.dataclass
class ServingParams:
    """Per-layer serving weights (pytree). ``layers[i]`` holds one block's
    params; projection leaves are arrays or DeployedWeight.

    ``head_t`` caches the tied-embeddings output head (``embed.T``),
    materialized ONCE at build time instead of re-transposing the full
    (V, D) embedding inside every prefill/decode trace. It is None whenever
    an explicit ``head`` exists, and is rebuilt (not stored) by the artifact
    loader."""

    embed: Any
    final_ln: Any
    layers: List[dict]
    head: Any = None  # None => tied embeddings (use head_t == embed.T)
    mm_proj: Any = None  # vlm projector (kept in float)
    head_t: Any = None  # precomputed embed.T for tied embeddings

    def deployed(self) -> Dict[str, D.DeployedWeight]:
        """Name -> DeployedWeight for every compressed projection."""
        out = {}
        for i, p in enumerate(self.layers):
            for k, v in p.items():
                if isinstance(v, D.DeployedWeight):
                    out[f"blk{i}_{k}"] = v
        if isinstance(self.head, D.DeployedWeight):
            out["head"] = self.head
        return out

    def report(self) -> dict:
        """Table IV-style storage accounting over the deployed projections."""
        return D.deployment_report(self.deployed())


jax.tree_util.register_pytree_node(
    ServingParams,
    lambda sp: ((sp.embed, sp.final_ln, sp.layers, sp.head, sp.mm_proj,
                 sp.head_t), None),
    lambda aux, ch: ServingParams(*ch),
)


def _check_family(cfg: ModelConfig) -> None:
    if cfg.family not in SUPPORTED_FAMILIES:
        raise NotImplementedError(
            f"serve.deployed supports families {SUPPORTED_FAMILIES}, not "
            f"{cfg.family!r} (ssm/hybrid/encdec caches have no paged-KV "
            "adaptation yet)")


def _layer_source(params: dict, layer: Optional[Callable[[int], dict]]):
    """``layer(i)`` -> layer i's param dict: the caller's one-layer-at-a-
    time source (``transformer.init_layerwise``), or a slice of the
    stacked ``params["layers"]``."""
    if layer is not None:
        return layer
    return lambda i: jax.tree.map(lambda a: a[i], params["layers"])


def _serving(cfg: ModelConfig, params: dict, layers: List[dict],
             head) -> ServingParams:
    return ServingParams(
        embed=params["embed"], final_ln=params["final_ln"], layers=layers,
        head=head, mm_proj=params.get("mm_proj"),
        # tied embeddings: materialize the output head once, not per trace
        head_t=None if head is not None else jnp.asarray(params["embed"]).T,
    )


def from_params(cfg: ModelConfig, params: dict,
                layer: Optional[Callable[[int], dict]] = None
                ) -> ServingParams:
    """Unstack registry params (stacked (L, ...) leaves) into per-layer
    dicts, without compressing anything. ``layer`` (i -> layer i's dict)
    replaces ``params["layers"]`` when given."""
    _check_family(cfg)
    source = _layer_source(params, layer)
    return _serving(cfg, params, [source(i) for i in range(cfg.n_layers)],
                    params.get("head"))


def default_schedule(cfg: ModelConfig, seq_len: int = 128,
                     groups=None, alphas=None,
                     sparsity_gs: float = 0.6,
                     uniform: bool = False) -> NetworkSchedule:
    """Mapping search over the model's CIM projection graph: the returned
    schedule's per-layer (group, alpha) becomes the serving (bk, bn).
    ``uniform=True`` restricts the search to tiles that exactly divide every
    projection (the stacked-deployment envelope). The grid defaults to the
    tiles the BSR kernel runs on this backend (``kernels.ops.tile_sizes``),
    and only tiles on its quantum are searched."""
    graph = lm_graph(cfg, seq_len=seq_len, sparsity_gs=sparsity_gs)
    result = search_mapping(graph, w_bits=cfg.w_bits, a_bits=cfg.a_bits,
                            groups=groups or ops.tile_sizes(),
                            alphas=alphas or ops.tile_sizes(),
                            uniform=uniform, quantum=ops.tile_quantum())
    return schedule_from_search(graph, result, w_bits=cfg.w_bits,
                                a_bits=cfg.a_bits)


def _projection_shapes(sp: ServingParams) -> List[Tuple[int, int]]:
    """(d_in, d_out) of every 2-D projection that compress() would pack
    (or already has - re-packing flows like the speculative draft tier
    walk compressed ServingParams too)."""

    def dims(w) -> Optional[Tuple[int, int]]:
        if isinstance(w, D.DeployedWeight):
            return (w.d_in, w.d_out)
        if getattr(w, "ndim", 0) == 2:
            return (int(w.shape[-2]), int(w.shape[-1]))
        return None

    shapes = []
    for p in sp.layers:
        for proj in PROJECTIONS:
            d = dims(p.get(proj))
            if d is not None:
                shapes.append(d)
    if sp.head is not None:
        d = dims(sp.head)
        if d is not None:
            shapes.append(d)
    return shapes


def compress(cfg: ModelConfig, params: dict,
             target_sparsity: Optional[float] = None,
             schedule: Optional[NetworkSchedule] = None,
             tile: Optional[Tuple[int, int]] = None,
             uniform: bool = False,
             layer: Optional[Callable[[int], dict]] = None) -> ServingParams:
    """Pack every CIM-mapped 2-D projection for the BSR kernel.

    ``schedule`` (from ``sched.search`` over ``lm_graph(cfg)``) supplies the
    per-projection tile; without one, ``tile`` (or the model's ``cim_alpha``)
    is used (clipped to exact divisors). MoE expert stacks (3-D) and norm
    gains stay dense. ``target_sparsity=0`` packs every block (no pruning) -
    the numerically-honest configuration that must reproduce dense-math
    tokens.

    ``uniform=True`` packs the WHOLE network (head included) with one
    (bk, bn): the schedule's ``uniform_tile`` (or the requested ``tile``)
    clipped once to the largest shape that divides every projection, instead
    of per-projection clipping. This is the envelope contract
    ``serve.stacked`` / ``core.deploy.stack_deployed`` require.

    Layers are packed one at a time and their dense weights dropped as soon
    as they are packed; with ``layer`` (i -> layer i's dict, e.g. from
    ``transformer.init_layerwise``) in place of ``params["layers"]`` the
    whole dense layer stack never exists at once.
    """
    _check_family(cfg)
    source = _layer_source(params, layer)
    cim = cfg.cim
    tiles = {}
    if schedule is not None:
        tiles = {s.name: (s.group, s.alpha) for s in schedule.layers}
    fallback = tile if tile is not None else (cfg.cim_alpha, cfg.cim_alpha)
    if uniform:
        g, a = schedule.uniform_tile if schedule is not None else fallback
        p0 = jax.eval_shape(source, 0)  # shapes only: nothing is drawn
        shapes = [p0[proj].shape for proj in PROJECTIONS
                  if getattr(p0.get(proj), "ndim", 0) == 2]
        if params.get("head") is not None:
            shapes.append(params["head"].shape)
        tiles, fallback = {}, D.uniform_fit_tile(shapes, g, a)

    def pack(name: str, w) -> D.DeployedWeight:
        d_in, d_out = int(w.shape[-2]), int(w.shape[-1])
        g, a = tiles.get(name, fallback)
        bk, bn = D.fit_tile(d_in, d_out, g, a)
        return D.deploy_weight(w, cim, bk=bk, bn=bn,
                               target_sparsity=target_sparsity)

    layers = []
    for i in range(cfg.n_layers):
        p = source(i)
        for proj in PROJECTIONS:
            w = p.get(proj)
            if w is None or getattr(w, "ndim", 0) != 2:
                continue  # MoE expert stacks are (E, d, ff): leave dense
            p[proj] = pack(f"blk{i}_{proj}", w)
        layers.append(p)
    head = params.get("head")
    return _serving(cfg, params, layers,
                    pack("head", head) if head is not None else None)


def shard(sp: ServingParams, mesh) -> ServingParams:
    """Lay a compressed model over the serving macro cluster.

    Every :class:`~repro.core.deploy.DeployedWeight` is column-sharded over
    the mesh's ``macro`` axis with the SAME LPT policy the scheduler uses to
    balance kernel-groups over macros (``sched.allocate.device_assignment``
    on the per-column surviving-block counts). Projections whose column
    count does not divide the axis stay replicated (``replicate_weight``:
    every device runs the whole projection) - sharding never changes
    which blocks exist, so tokens are bit-identical to single-device
    serving. Dense leaves (embed, norms, MoE stacks) stay replicated.
    """
    from ..sched.allocate import device_assignment

    def maybe(v):
        if isinstance(v, D.DeployedWeight):
            return D.replicate_weight(
                D.shard_weight(v, mesh, assign=device_assignment), mesh)
        return v

    return ServingParams(
        embed=sp.embed, final_ln=sp.final_ln,
        layers=[{k: maybe(v) for k, v in p.items()} for p in sp.layers],
        head=maybe(sp.head) if sp.head is not None else None,
        mm_proj=sp.mm_proj, head_t=sp.head_t,
    )


# ---------------------------------------------------------------------------
# Offline serving artifacts: pack once, boot many times
# ---------------------------------------------------------------------------

# Manifest schema version. Bump when the manifest layout changes
# incompatibly; loaders refuse artifacts NEWER than they understand
# (artifacts saved before versioning carry no field and load as legacy).
ARTIFACT_SCHEMA = 1


def packed_tiles(sp: ServingParams) -> List[Tuple[int, int]]:
    """Sorted unique (bk, bn) tiles across every deployed projection.
    A single-element list means the packing is UNIFORM - the stacked-scan
    envelope (and therefore in-place hot-swap) is possible."""
    return sorted({dw.tile for dw in sp.deployed().values()})


def validate_artifact(path: str, extra: dict, *,
                      arch: Optional[str] = None,
                      family: Optional[str] = None,
                      tile: Optional[Tuple[int, int]] = None) -> None:
    """The hot-swap compatibility gate: check a loaded manifest against
    what the serving host expects and raise a CLEAR error (artifact path +
    expected vs found) instead of letting a mismatched artifact fail deep
    inside ``core.deploy.stack_deployed``.

    Every check is skipped when the expectation (or the manifest field) is
    absent, so legacy artifacts written before versioning still load."""
    schema = extra.get("schema")
    if schema is not None and int(schema) > ARTIFACT_SCHEMA:
        raise ValueError(
            f"{path}: artifact manifest schema {schema} is newer than this "
            f"host supports ({ARTIFACT_SCHEMA}) - upgrade the serving host "
            "or re-save the artifact")
    if arch is not None and extra.get("arch") not in (None, arch):
        raise ValueError(
            f"{path}: artifact arch mismatch - expected {arch!r}, found "
            f"{extra['arch']!r}")
    if family is not None and extra.get("family") not in (None, family):
        raise ValueError(
            f"{path}: artifact family mismatch - expected {family!r}, "
            f"found {extra['family']!r}")
    if tile is not None and extra.get("tiles"):
        found = [tuple(t) for t in extra["tiles"]]
        if len(found) != 1 or found[0] != tuple(tile):
            raise ValueError(
                f"{path}: artifact tile mismatch - expected uniform "
                f"{tuple(tile)}, found {found} (re-pack with that tile, or "
                "stage a re-jit instead of hot-swapping)")


def _strip_placement(sp: ServingParams) -> ServingParams:
    """Serialization form: logical column order, no mesh, no derived
    tied-head cache."""

    def strip(v):
        if isinstance(v, D.DeployedWeight):
            return D.unshard_weight(v)
        return v

    return ServingParams(
        embed=sp.embed, final_ln=sp.final_ln,
        layers=[{k: strip(v) for k, v in p.items()} for p in sp.layers],
        head=strip(sp.head) if sp.head is not None else None,
        mm_proj=sp.mm_proj, head_t=None,
    )


def save_artifact(path: str, sp: ServingParams, cfg: ModelConfig,
                  extra: Optional[dict] = None,
                  draft: Optional[ServingParams] = None) -> str:
    """Persist a (compressed or dense) ServingParams as a boot-ready
    serving artifact.

    Placement is stripped before serialization (macro-sharded projections
    are restored to logical column order via ``core.deploy.unshard_weight``;
    the mesh never enters the serialized aux), and the derived tied-head
    cache is dropped - the loader rebuilds both, so one artifact serves any
    mesh shape. Written atomically through ``train.checkpoint``.

    ``draft`` makes the artifact two-tier (speculative serving): the
    higher-sparsity draft packing is stored alongside the target. Dense
    leaves the tiers share BY REFERENCE (embed, norms - how
    ``spec.draft_serving`` builds them) are stored ONCE; the checkpoint
    spec dedupes identical leaf objects.
    """
    meta = {"schema": ARTIFACT_SCHEMA, "arch": cfg.name,
            "family": cfg.family, "n_layers": cfg.n_layers,
            "tiles": [list(t) for t in packed_tiles(sp)],
            **(extra or {})}
    clean = _strip_placement(sp)
    if draft is None:
        return ckpt.save_pytree(path, clean, extra=meta)
    meta["two_tier"] = True
    tree = {"target": clean, "draft": _strip_placement(draft)}
    return ckpt.save_pytree(path, tree, extra=meta)


def _rebuild_tied_head(sp: ServingParams) -> ServingParams:
    if sp.head is None and sp.head_t is None:
        sp.head_t = jnp.asarray(sp.embed).T
    return sp


def load_artifact_tiers(path: str, *, arch: Optional[str] = None,
                        tile: Optional[Tuple[int, int]] = None
                        ) -> Tuple[ServingParams,
                                   Optional[ServingParams], dict]:
    """Boot EVERY tier of a serving artifact from ONE deserialization pass.

    Returns (target, draft-or-None, manifest-extra). This is the
    speculative-serving boot path: loading the two-tier tree once keeps
    the dense leaves the tiers share deduped IN MEMORY too (the draft's
    embed/norm leaves are the same loaded arrays as the target's), where
    two separate :func:`load_artifact` calls would materialize the whole
    artifact twice.

    ``arch`` / ``tile`` are expectations checked by
    :func:`validate_artifact` against the MANIFEST (before any array
    deserialization), so a mismatched artifact fails with its path and
    the expected-vs-found fields instead of deep inside ``stack()``."""
    probe = load_artifact_extra(path)
    if probe:
        validate_artifact(path, probe, arch=arch, tile=tile)
    tree, manifest = ckpt.load_pytree(path)
    extra = manifest.get("extra", manifest)
    if isinstance(tree, ServingParams):
        return _rebuild_tied_head(tree), None, extra
    if isinstance(tree, dict) and "target" in tree:
        draft = tree.get("draft")
        return (_rebuild_tied_head(tree["target"]),
                _rebuild_tied_head(draft) if draft is not None else None,
                extra)
    raise TypeError(f"{path}: artifact does not contain ServingParams")


def load_artifact(path: str, tier: str = "target", *,
                  arch: Optional[str] = None,
                  tile: Optional[Tuple[int, int]] = None
                  ) -> Tuple[ServingParams, dict]:
    """Boot a ServingParams from :func:`save_artifact` output WITHOUT
    re-running search/quantize/prune/pack. Returns (sp, manifest-extra).
    The tied-head cache is recomputed; re-shard with :func:`shard` if a
    macro mesh is wanted.

    ``tier`` selects the packing of a two-tier (speculative) artifact:
    ``"target"`` (also the whole content of a single-tier artifact) or
    ``"draft"`` (raises on artifacts saved without one). To boot BOTH
    tiers, use :func:`load_artifact_tiers` - one deserialization pass
    instead of two. ``arch`` / ``tile`` gate the manifest first (see
    :func:`validate_artifact`)."""
    target, draft, extra = load_artifact_tiers(path, arch=arch, tile=tile)
    if tier == "target":
        return target, extra
    if tier == "draft":
        if draft is None:
            raise ValueError(
                f"{path}: artifact has no draft packing - re-save with "
                "save_artifact(..., draft=...) for speculative serving")
        return draft, extra
    raise ValueError(f"{path}: unknown tier {tier!r}")


def load_artifact_extra(path: str) -> dict:
    """Read ONLY the manifest-extra of a serving artifact (no array
    deserialization). Returns {} when the artifact does not exist - this
    is the cheap pre-boot probe for manifest-carried state (autotune
    cache, spec calibration)."""
    step = ckpt.latest_step(path)
    if step is None:
        return {}
    with open(os.path.join(path, f"step_{step:08d}", "manifest.json")) as f:
        manifest = json.load(f)
    return manifest.get("extra", {}) or {}


def update_artifact_extra(path: str, updates: dict) -> None:
    """Merge ``updates`` into an existing artifact's manifest-extra WITHOUT
    re-serializing the weight tree. This is how post-serve measurements
    (the spec-acceptance calibration) persist next to the packing they
    measured: the manifest is rewritten atomically, arrays untouched."""
    step = ckpt.latest_step(path)
    if step is None:
        raise FileNotFoundError(f"no artifact at {path}")
    d = os.path.join(path, f"step_{step:08d}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    manifest.setdefault("extra", {}).update(updates)
    tmp = os.path.join(d, ".manifest.json.tmp")
    with open(tmp, "w") as f:
        json.dump(manifest, f)
    os.replace(tmp, os.path.join(d, "manifest.json"))


# ---------------------------------------------------------------------------
# Forward paths: the LOOP runtime (python loop over per-layer weights).
# ``serve.stacked`` is the compiled lax.scan form over the uniform envelope;
# it must reproduce these functions' tokens bit-exactly.
# ---------------------------------------------------------------------------


def _layer_window_theta(cfg: ModelConfig) -> Tuple[list, list]:
    """Static per-layer (window, rope_theta) - mirrors
    ``transformer._layer_kind_arrays`` but as python values for the loop."""
    kinds = cfg.layer_kinds()
    windows = [cfg.window if k == 1 else 0 for k in kinds]
    if cfg.local_global_ratio > 0:
        thetas = [cfg.rope_theta if k == 1 else 1e6 for k in kinds]
    else:
        thetas = [cfg.rope_theta] * cfg.n_layers
    return windows, thetas


def _embed_inputs(sp: ServingParams, batch: dict, cfg: ModelConfig):
    return transformer._embed_inputs(
        {"embed": sp.embed, "mm_proj": sp.mm_proj}, batch, cfg)


def _head(sp: ServingParams):
    """Output head: explicit, or the build-time transposed tied embedding
    (never re-materialized per call)."""
    if sp.head is not None:
        return sp.head
    return sp.head_t if sp.head_t is not None else sp.embed.T


def prefill_hidden(sp: ServingParams, batch: dict, cfg: ModelConfig):
    """Full-sequence forward. Returns (hidden (B,S,D), cache k/v
    (L,B,S,KV,dh)) - the same math as ``transformer.forward_hidden`` for the
    dense/moe/vlm families, but layer-by-layer so projection leaves may be
    DeployedWeight."""
    x = _embed_inputs(sp, batch, cfg)
    _, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    windows, thetas = _layer_window_theta(cfg)
    ks, vs = [], []
    for i, p in enumerate(sp.layers):
        x, _, (k, v) = transformer._attn_mlp_body(
            p, x, cfg, windows[i], thetas[i], positions)
        ks.append(k)
        vs.append(v)
    x = L.rmsnorm(x, sp.final_ln)
    return x, {"k": jnp.stack(ks), "v": jnp.stack(vs)}


def prefill(sp: ServingParams, batch: dict, cfg: ModelConfig):
    """Registry-signature prefill: (last-position logits, cache w/ 'pos')."""
    hidden, cache = prefill_hidden(sp, batch, cfg)
    logits = L.logits_out(_head(sp), hidden[:, -1:, :], cfg.cim)[:, 0, : cfg.vocab]
    total = batch["tokens"].shape[1]
    if cfg.family == "vlm":
        total += batch["patch_embeds"].shape[1]
    cache["pos"] = jnp.asarray(total, jnp.int32)
    return logits, cache


def prefill_last(sp: ServingParams, tokens: jnp.ndarray, true_len: jnp.ndarray,
                 cfg: ModelConfig):
    """Prefill for the batch server: ``tokens`` (B, S_pad) may be padded past
    the prompt; logits are taken at ``true_len - 1``. Causality guarantees
    the pad positions cannot influence them, and their (garbage) cache
    entries sit at positions >= true_len, which decode overwrites before it
    ever attends to them."""
    hidden, cache = prefill_hidden(sp, {"tokens": tokens}, cfg)
    h_last = jnp.take(hidden, jnp.asarray(true_len - 1, jnp.int32), axis=1)
    logits = L.logits_out(_head(sp), h_last[:, None, :], cfg.cim)[:, 0, : cfg.vocab]
    return logits, cache["k"], cache["v"]


def _mlp(p: dict, h, cfg: ModelConfig):
    if cfg.family == "moe":
        y, _ = L.moe_block(p, h, cfg)
        return y
    return L.gated_mlp(p, h, cfg.cim)


def decode_step(sp: ServingParams, cache: dict, tokens: jnp.ndarray,
                cfg: ModelConfig):
    """Registry-signature decode: contiguous per-batch cache, scalar pos.
    Math-identical to ``transformer.decode_step`` (dense branch)."""
    x = L.embed(sp.embed, tokens, cfg.param_dtype)
    pos = cache["pos"]
    windows, thetas = _layer_window_theta(cfg)
    ks, vs = [], []
    for i, p in enumerate(sp.layers):
        cfg_l = transformer._with_theta(cfg, thetas[i])
        h = L.rmsnorm(x, p["ln1"])
        attn, kc, vc = L.decode_attention(p, h, cache["k"][i], cache["v"][i],
                                          pos, cfg_l, window=windows[i])
        x = x + attn
        h = L.rmsnorm(x, p["ln2"])
        x = x + _mlp(p, h, cfg)
        ks.append(kc)
        vs.append(vc)
    new_cache = {"k": jnp.stack(ks), "v": jnp.stack(vs), "pos": pos + 1}
    x = L.rmsnorm(x, sp.final_ln)
    logits = L.logits_out(_head(sp), x, cfg.cim)[:, 0, : cfg.vocab]
    return logits, new_cache


def decode_step_paged(sp: ServingParams, views_k: jnp.ndarray,
                      views_v: jnp.ndarray, pos: jnp.ndarray,
                      tokens: jnp.ndarray, cfg: ModelConfig):
    """One continuous-batching decode step over a gathered paged-KV view.

    views_k/views_v: (L, B, Sv, KV, dh) gathered blocks (logical positions
    0..Sv-1 per slot); pos: (B,) per-slot absolute positions; tokens: (B, 1).
    Returns (logits (B, V), k_new (L, B, KV, dh), v_new) - the new entries
    are written back into the block pool by the caller.
    """
    x = L.embed(sp.embed, tokens, cfg.param_dtype)
    windows, thetas = _layer_window_theta(cfg)
    ks, vs = [], []
    for i, p in enumerate(sp.layers):
        cfg_l = transformer._with_theta(cfg, thetas[i])
        h = L.rmsnorm(x, p["ln1"])
        attn, kn, vn = L.decode_attention_multi(
            p, h, views_k[i], views_v[i], pos, cfg_l, window=windows[i])
        x = x + attn
        h = L.rmsnorm(x, p["ln2"])
        x = x + _mlp(p, h, cfg)
        ks.append(kn[:, 0])
        vs.append(vn[:, 0])
    x = L.rmsnorm(x, sp.final_ln)
    logits = L.logits_out(_head(sp), x, cfg.cim)[:, 0, : cfg.vocab]
    return logits, jnp.stack(ks), jnp.stack(vs)


def _mlp_tokenwise(p: dict, h, cfg: ModelConfig):
    """MLP over (B, T, D) with SEQUENTIAL-DECODE semantics per token.

    The dense-family MLP is position-independent, but ``moe_block`` routes
    with a capacity computed from the sequence length - a T-token pass
    would share capacity across the T tokens and could drop a (token,
    expert) pair that a one-token decode step keeps. Folding T into the
    batch axis gives every token the exact s=1 routing the sequential
    decode steps use, which is what the verify pass's bit-exactness
    contract requires."""
    if cfg.family != "moe":
        return _mlp(p, h, cfg)
    b, t, d = h.shape
    return _mlp(p, h.reshape(b * t, 1, d), cfg).reshape(b, t, d)


def verify_step(sp: ServingParams, views_k: jnp.ndarray,
                views_v: jnp.ndarray, pos: jnp.ndarray, tokens: jnp.ndarray,
                cfg: ModelConfig):
    """Batched multi-token pass over gathered paged views (loop runtime).

    ``tokens`` (B, T) are row b's next T input tokens at absolute positions
    ``pos[b] .. pos[b]+T-1``. Position ``t``'s logits are BIT-IDENTICAL to
    what T sequential :func:`decode_step_paged` calls would produce after
    consuming ``tokens[:, :t+1]`` - every op is row/position-independent
    and masked view padding is numerically inert. The mirror of
    ``serve.stacked.verify_step`` for per-layer (non-stacked) weights;
    the suffix-prefill path after a prefix-cache hit runs the unshared
    prompt span through this in one pass instead of T decode steps.

    Returns (logits (B, T, V), k_new (L, B, T, KV, dh), v_new)."""
    x = L.embed(sp.embed, tokens, cfg.param_dtype)  # (B, T, D)
    windows, thetas = _layer_window_theta(cfg)
    ks, vs = [], []
    for i, p in enumerate(sp.layers):
        cfg_l = transformer._with_theta(cfg, thetas[i])
        h = L.rmsnorm(x, p["ln1"])
        attn, kn, vn = L.decode_attention_multi(
            p, h, views_k[i], views_v[i], pos, cfg_l, window=windows[i])
        x = x + attn
        h = L.rmsnorm(x, p["ln2"])
        x = x + _mlp_tokenwise(p, h, cfg)
        ks.append(kn)
        vs.append(vn)
    x = L.rmsnorm(x, sp.final_ln)
    logits = L.logits_out(_head(sp), x, cfg.cim)[..., : cfg.vocab]
    return logits, jnp.stack(ks), jnp.stack(vs)


def model_fns(cfg: ModelConfig) -> registry.ModelFns:
    """ModelFns whose prefill/decode consume a :class:`ServingParams` in
    place of raw params - plug into ``serve.Engine`` via its ``fns`` arg to
    serve compressed (or unstacked dense) weights."""
    _check_family(cfg)

    def _no_init(*a, **k):
        raise NotImplementedError(
            "ServingParams are built from trained params via "
            "serve.deployed.from_params/compress, not initialized")

    return registry.ModelFns(
        init_params=_no_init,
        train_loss=_no_init,
        prefill=prefill,
        decode_step=decode_step,
        init_cache=transformer.init_cache,
    )
