"""Continuous-batching serve loop over (optionally compressed) weights.

One :class:`BatchServer` drives ``deployed.decode_step_paged`` over a fixed
number of slots. Every step decodes all slots in one batched call; finished
requests free their KV blocks and the freed slot admits the next queued
request immediately (continuous batching). With ``continuous=False`` the
same loop becomes the static baseline: admission waits until EVERY slot has
drained, so lanes idle exactly as a static batcher's padding rows do -
making static-vs-continuous a pure scheduling-policy comparison (identical
kernels, identical per-step cost).

Because the model functions route every projection through
``layers.cim_matmul``, the server serves raw float weights and BSR-packed
:class:`~repro.serve.deployed.ServingParams` identically - compressed
serving is a constructor argument, not a separate engine.

Admission reserves worst-case blocks (prompt + max_new) so a mid-stream
request can never deadlock the pool; a request that cannot fit even in an
empty pool is rejected at ``run`` time.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..kernels.cim_bsr_matmul import MACRO_AXIS
from ..models.config import ModelConfig
from ..obs import NULL_METRICS, NULL_TRACER, phase_scope
from . import deployed, stacked
from . import spec as spec_mod
from .batching import PagedKVCache, Request, RequestQueue, Slot, kv_view_spec
from .engine import ServeConfig, sample_tokens
from .prefix import PrefixTrie


@dataclasses.dataclass
class BatchConfig:
    n_slots: int = 4
    block_size: int = 8
    # KV block budget PER DEVICE: when a macro-mesh server shards every
    # block's heads over N devices, the same per-device memory holds N x
    # blocks and the pool scales to n_blocks * N; if the heads do NOT
    # divide the mesh the views stay replicated and the pool stays at
    # n_blocks (scaling it would overrun every device's budget N-fold)
    n_blocks: int = 64
    # round the gathered view up to a multiple of this many blocks so jit
    # recompiles O(log) times instead of once per sequence-length block
    view_bucket: int = 2
    idle_wait_s: float = 0.002
    # radix-tree prefix KV reuse: admissions whose prompt shares a
    # full-block prefix with an earlier request adopt the cached block
    # chain (refcount bump, copy-on-write on divergence) and prefill only
    # the unshared suffix. Greedy tokens are bit-identical either way.
    prefix_cache: bool = True


def _percentiles(xs: List[float]) -> dict:
    """Latency percentiles; empty or non-finite-only traces (a run that
    decoded nothing) report zeros instead of NaN-poisoning the benchmark
    JSON."""
    a = np.asarray([x for x in xs if np.isfinite(x)], np.float64)
    if a.size == 0:
        return {"p50": 0.0, "p99": 0.0, "mean": 0.0}
    return {"p50": float(np.percentile(a, 50)),
            "p99": float(np.percentile(a, 99)),
            "mean": float(a.mean())}


@dataclasses.dataclass
class ServeReport:
    """Throughput / latency summary of one trace."""

    n_requests: int
    total_tokens: int
    wall_s: float
    n_decode_steps: int
    ttft_s: List[float]  # per request
    tpot_s: List[float]  # per decode token, pooled across requests
    outputs: Dict[str, np.ndarray]
    kv_stats: dict
    spec: Optional[dict] = None  # speculative-decode acceptance telemetry
    # per-request admission-minus-arrival: the scheduling share of TTFT
    queue_wait_s: List[float] = dataclasses.field(default_factory=list)
    metrics: Optional[dict] = None  # obs snapshot (instrumented runs only)
    # prefix-cache telemetry: trie hit/insert/evict counts plus the
    # hit-vs-miss split of service TTFT (None when prefix_cache=False)
    prefix: Optional[dict] = None
    # gateway attribution: which tenant this report covers ("" = the
    # whole single-tenant server run)
    tenant: str = ""

    @property
    def tokens_per_s(self) -> float:
        """0.0 for an empty trace or a zero-duration run (nothing decoded
        in no time is throughput 0, not 0/0)."""
        if self.total_tokens == 0 or self.wall_s <= 0.0:
            return 0.0
        return self.total_tokens / self.wall_s

    _n_slots: int = 1

    @property
    def slot_efficiency(self) -> float:
        """Fraction of decoded lanes that produced a kept token (prefill
        emits each request's first token, so those don't count)."""
        if self.n_decode_steps == 0 or self._n_slots < 1:
            return 1.0
        return min(1.0, max(0.0, self.total_tokens - self.n_requests)
                   / (self.n_decode_steps * self._n_slots))

    def to_json(self) -> dict:
        out = {
            **({"tenant": self.tenant} if self.tenant else {}),
            "n_requests": self.n_requests,
            "total_tokens": self.total_tokens,
            "wall_s": round(self.wall_s, 4),
            "tokens_per_s": round(self.tokens_per_s, 2),
            "n_decode_steps": self.n_decode_steps,
            "slot_efficiency": round(self.slot_efficiency, 4),
            "ttft": {k: round(v, 5) for k, v in _percentiles(self.ttft_s).items()},
            "tpot": {k: round(v, 5) for k, v in _percentiles(self.tpot_s).items()},
            "kv": self.kv_stats,
        }
        # TTFT = queue wait (scheduling) + service (prefill-to-first-token):
        # reported separately so load-induced queueing can't masquerade as a
        # prefill regression (and vice versa)
        service = [max(t - w, 0.0)
                   for t, w in zip(self.ttft_s, self.queue_wait_s)]
        out["queue_wait"] = {k: round(v, 5) for k, v in
                            _percentiles(self.queue_wait_s).items()}
        out["ttft_service"] = {k: round(v, 5) for k, v in
                               _percentiles(service).items()}
        if self.spec is not None:
            out["spec"] = self.spec
        if self.metrics is not None:
            out["metrics"] = self.metrics
        if self.prefix is not None:
            out["prefix"] = self.prefix
        return out


class BatchServer:
    """Slot-based serving engine (continuous or static batching)."""

    def __init__(self, cfg: ModelConfig, sp: deployed.ServingParams,
                 scfg: Optional[ServeConfig] = None,
                 bcfg: Optional[BatchConfig] = None,
                 continuous: bool = True, mesh: Optional[Mesh] = None,
                 engine: str = "loop",
                 draft: Optional[deployed.ServingParams] = None,
                 spec: Optional[spec_mod.SpecConfig] = None,
                 tracer=None, metrics=None):
        """``mesh`` (with a ``macro`` axis) turns on macro-cluster serving:
        pass ``deployed.shard(sp, mesh)`` as ``sp`` so projections run
        tensor-parallel, the gathered KV views are sharded heads-wise, and
        the block pool scales to ``bcfg.n_blocks`` per device. The loop
        itself is unchanged - 1 and N devices run the same code.

        ``engine`` picks the decode runtime over the SAME weights:
        ``"loop"`` (python loop over per-layer packed weights), ``"scan"``
        (``serve.stacked``: one jitted lax.scan per step over the uniform
        envelope, views donated), or ``"spec"`` (self-speculative: a draft
        tier proposes up to ``spec.k`` tokens with the scan runtime - the
        reprune family over a higher-sparsity ``draft`` packing, the
        layerskip family by an nnz-ranked sublayer subset of the target's
        own envelope - and ONE multi-token target verify accepts the
        longest greedy-matching prefix plus a correction token; per-slot
        adaptive k collapses the draft length when acceptance dies). All
        three produce
        bit-identical greedy tokens; spec additionally requires greedy
        decoding (temperature 0) - with sampling the acceptance rule would
        need distribution-preserving rejection sampling, which this engine
        does not implement.

        ``tracer`` / ``metrics`` (a :class:`repro.obs.Tracer` /
        :class:`repro.obs.MetricsRegistry`) opt the loop into phase spans
        (mirrored into the JAX profiler's trace), per-request lifecycle
        tracks and occupancy gauges. Default is the shared no-op
        singletons: every phase boundary fence is gated on them, so the
        un-instrumented hot path is byte-identical to an uninstrumented
        server."""
        if cfg.family == "vlm":
            raise NotImplementedError(
                "BatchServer serves token-only requests; vlm prefill needs "
                "per-request patch embeddings (use serve.Engine)")
        deployed._check_family(cfg)
        if engine not in ("loop", "scan", "spec"):
            raise ValueError(
                f"engine must be 'loop', 'scan' or 'spec', got {engine!r}")
        if engine != "spec" and (draft is not None or spec is not None):
            raise ValueError(
                f"draft/spec are speculative-decode arguments but engine="
                f"{engine!r} - pass engine='spec' to use them")
        self.cfg = cfg
        self.sp = sp
        self.engine = engine
        self.scfg = scfg if scfg is not None else ServeConfig()
        self.bcfg = bcfg if bcfg is not None else BatchConfig()
        self.continuous = continuous
        self.mesh = mesh
        self.n_devices = (int(mesh.shape[MACRO_AXIS])
                          if mesh is not None and MACRO_AXIS in mesh.axis_names
                          else 1)
        # pool scaling is earned by head sharding, not by device count
        self._kv_scale = (self.n_devices
                          if mesh is not None
                          and kv_view_spec(cfg, mesh) is not None else 1)
        # the gathered views are throwaways: donate them so the scan's
        # in-view dynamic_update_slice KV writes reuse the buffers
        # (CPU XLA can't alias freshly-transferred host arrays and only
        # warns, so donation is gated to real accelerator backends)
        donate = (1, 2) if jax.default_backend() != "cpu" else ()
        jit, cfg_arg = stacked.serving_jit, ("cfg",)
        self.spec = None
        if engine == "spec":
            self.spec = spec if spec is not None else spec_mod.SpecConfig()
            if self.spec.draft == "reprune" and draft is None:
                raise ValueError(
                    "engine='spec' with the reprune family needs a draft "
                    "tier: pass draft=spec.draft_serving(cfg, sp, "
                    "draft_sparsity)")
            if self.spec.draft == "layerskip" and draft is not None:
                raise ValueError(
                    "the layerskip family drafts with a sublayer subset of "
                    "the TARGET envelope - there is no draft packing; drop "
                    "the draft= argument (or pick draft='reprune')")
            if self.scfg.temperature > 0.0:
                raise ValueError(
                    "engine='spec' is greedy-only (temperature=0): the "
                    "accept rule matches draft tokens against the target's "
                    "argmaxes, which is exact only for greedy decode")
            self._params = spec_mod.SpecParams.build(sp, draft)
            self._prefill = jit(stacked.prefill_last, static_argnames=cfg_arg)
            self._verify = jit(stacked.verify_step, static_argnames=cfg_arg,
                               donate_argnums=donate)
            if self.spec.draft == "layerskip":
                # sublayer masks, ranked by the packed envelope's own nnz:
                # sublayers the compression already killed are dropped first
                # (skipping them cannot change a logit)
                imp = spec_mod.sublayer_importance(self._params.target)
                attn_on, mlp_on = spec_mod.layerskip_masks(
                    cfg.n_layers, self.spec.keep, importance=imp)
                self.spec_masks = (attn_on, mlp_on)
                self._attn_on = jnp.asarray(attn_on, jnp.float32)
                self._mlp_on = jnp.asarray(mlp_on, jnp.float32)
                self._draft_propose = jit(
                    spec_mod.draft_propose_layerskip,
                    static_argnames=("cfg", "k"), donate_argnums=donate)
            else:
                self.spec_masks = None
                self._draft_propose = jit(spec_mod.draft_propose,
                                          static_argnames=("cfg", "k"),
                                          donate_argnums=donate)
        elif engine == "scan":
            self._params = stacked.stack(sp)
            self._prefill = jit(stacked.prefill_last, static_argnames=cfg_arg)
            self._decode = jit(stacked.decode_step_paged,
                               static_argnames=cfg_arg, donate_argnums=donate)
            # multi-token pass for the prefix-cache suffix prefill
            self._verify = jit(stacked.verify_step, static_argnames=cfg_arg,
                               donate_argnums=donate)
        else:
            # packings live on the host until a runtime places them: the
            # loop runtime runs the per-layer form, so it goes on the device
            self._params = jax.device_put(sp)
            self._prefill = jit(deployed.prefill_last, static_argnames=cfg_arg)
            self._decode = jit(deployed.decode_step_paged,
                               static_argnames=cfg_arg)
            self._verify = jit(deployed.verify_step, static_argnames=cfg_arg)
        # speculative lookahead: a verify writes KV up to pos+k, so
        # worst-case reservation must cover k extra positions per slot
        self._lookahead = self.spec.k if self.spec is not None else 0
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics if metrics is not None else NULL_METRICS
        self._obs = bool(self.tracer.recording or self.metrics.recording)

    def _phase(self, name: str, **args):
        return phase_scope(self.tracer, self.metrics, name, **args)

    def _sample_row(self, logits: jnp.ndarray, key) -> np.ndarray:
        return np.asarray(sample_tokens(logits, key, self.scfg), np.int32)

    # -- admission ----------------------------------------------------------

    def _worst_blocks(self, req: Request) -> int:
        """Worst-case block demand: prompt + every decode token, plus the
        speculative lookahead (a verify pass writes candidate KV up to k
        positions past the committed stream)."""
        worst = len(req.prompt) + req.max_new_tokens + self._lookahead
        return -(-worst // self.bcfg.block_size)

    def _reserved(self, slots: List[Optional[Slot]], kv: PagedKVCache) -> int:
        """Blocks active slots may still demand beyond what they hold."""
        r = 0
        for i, s in enumerate(slots):
            if s is not None:
                r += max(0, kv.blocks_for(s.worst_positions + self._lookahead)
                         - len(kv.tables[i]))
        return r

    def _admit(self, q: RequestQueue, slots: List[Optional[Slot]],
               kv: PagedKVCache, now: float, key) -> None:
        if not self.continuous and any(s is not None for s in slots):
            return  # static policy: only whole-batch admission
        for i in range(self.bcfg.n_slots):
            if slots[i] is not None:
                continue
            req = q.pop_ready(now)
            if req is None:
                return
            wb = self._worst_blocks(req)
            if wb > kv.n_blocks - 1:
                raise ValueError(
                    f"{req.rid}: needs {wb} blocks, pool "
                    f"has {kv.n_blocks - 1} - raise n_blocks/block_size")
            # prefix-cache lookup: adopt the matched chain FIRST (refcount
            # bump) so the trie eviction below can never free it out from
            # under this admission
            shared: List[int] = []
            if self._trie is not None:
                shared = self._trie.match(req.prompt)
                if shared:
                    kv.adopt(i, shared)
                if self._obs:
                    self.metrics.counter("prefix_lookups").inc()
                    if shared:
                        self.metrics.counter("prefix_hits").inc()
                        self.metrics.counter("prefix_tokens_reused").inc(
                            len(shared) * self.bcfg.block_size)
            # sharing-aware reservation: adopted blocks are already live,
            # so only the UNSHARED span demands fresh blocks
            need = wb - len(shared)
            avail = kv.free_blocks - self._reserved(slots, kv)
            if need > avail and self._trie is not None:
                # drop cold cached prefixes the trie is the last holder of
                self._trie.evict(need - avail)
                avail = kv.free_blocks - self._reserved(slots, kv)
            if need > avail:
                kv.free_slot(i)  # roll back the adoption - leaks nothing
                q.requeue(req)  # backpressure: wait for a drain, keep FIFO
                return
            if self._obs and self._trie is not None:
                self.metrics.gauge("prefix_trie_blocks").set(
                    self._trie.held_blocks())
            key, sub = jax.random.split(key)
            # stamp queue wait at THIS request's admission, not the wave's
            # entry time: when several slots fill in one wave, the time a
            # later request spent behind earlier prefills is queue wait,
            # not its own service (TTFT splits on that boundary)
            slots[i] = self._prefill_slot(
                i, req, kv, sub, n_shared=len(shared),
                queue_wait=max(0.0, self._now() - max(req.arrival, 0.0)))

    def _prefill_slot(self, i: int, req: Request, kv: PagedKVCache,
                      key, queue_wait: float = 0.0, n_shared: int = 0) -> Slot:
        with self._phase("prefill", rid=req.rid, slot=i,
                         shared_blocks=n_shared):
            return self._prefill_impl(i, req, kv, key, queue_wait, n_shared)

    def _prefill_impl(self, i: int, req: Request, kv: PagedKVCache,
                      key, queue_wait: float, n_shared: int = 0) -> Slot:
        bs = self.bcfg.block_size
        tlen = len(req.prompt)
        if n_shared:
            logits = self._suffix_prefill(i, req, kv, n_shared)
        else:
            pad = (-tlen) % bs
            toks = np.pad(req.prompt, (0, pad))[None]  # (1, S_pad)
            target = (self._params.target if self.spec is not None
                      else self._params)
            logits, k, v = self._prefill(target, jnp.asarray(toks),
                                         jnp.asarray(tlen, jnp.int32),
                                         cfg=self.cfg)
            kv.write_prefill(i, k[:, 0], v[:, 0], tlen)
            if self.spec is not None and self._params.draft is not None:
                # reprune draft-tier prefill: keeps the draft cache in
                # lockstep with the target from the first decode step (its
                # logits are unused - the first emitted token is the
                # TARGET's, like any engine). The layerskip family has no
                # draft cache: its draft reads the target's own KV.
                _, kd, vd = self._prefill(self._params.draft,
                                          jnp.asarray(toks),
                                          jnp.asarray(tlen, jnp.int32),
                                          cfg=self.cfg)
                kv.write_prefill(i, kd[:, 0], vd[:, 0], tlen, tier=1)
        if self._trie is not None:
            # register this prompt's full blocks AFTER the KV writes land
            # (inserting first would let the writes copy-on-write the fresh
            # blocks away from their own prefill); chunks already cached
            # keep their existing block - including the ones just adopted
            nf = tlen // bs
            if nf:
                self._trie.insert(req.prompt[: nf * bs], kv.tables[i][:nf])
        if self.spec is not None and self.spec.adaptive_k:
            self._adaptive[i] = spec_mod.AdaptiveK(
                k_max=self.spec.k, ewma=self.spec.ewma,
                collapse_below=self.spec.collapse_below,
                expand_above=self.spec.expand_above)
        tok = int(self._sample_row(logits, key)[0])
        now = self._now()
        return Slot(req=req, pos=tlen, next_token=tok, out=[tok],
                    t_admit=now, token_times=[now], queue_wait_s=queue_wait,
                    prefix_tokens=n_shared * bs)

    def _suffix_prefill(self, i: int, req: Request, kv: PagedKVCache,
                        n_shared: int) -> jnp.ndarray:
        """Prefix-cache hit: positions [0, n_shared*bs) were adopted from
        the trie, so only the unshared suffix runs - ONE multi-token
        ``verify_step`` over the gathered paged views (the same pass
        speculative decode verifies drafts with) computes the suffix KV and
        the last real position's logits. Cache-hit TTFT is therefore one
        (multi-token) decode step, not a full prefill."""
        bs = self.bcfg.block_size
        tlen = len(req.prompt)
        m = n_shared * bs
        t = tlen - m  # >= 1 by the trie's match cap
        t_pad = -(-t // bs) * bs
        kv.ensure(i, tlen)
        # pad suffix tokens sit at positions >= tlen: causal per-row masking
        # keeps them out of every real position's logits/KV, and their own
        # KV is simply never committed
        toks = jnp.asarray(np.pad(req.prompt[m:], (0, t_pad - t))[None])
        pos = jnp.asarray([m], jnp.int32)
        nv = -(-kv.blocks_for(m + t_pad) // self.bcfg.view_bucket) \
            * self.bcfg.view_bucket
        target = (self._params.target if self.spec is not None
                  else self._params)
        vk, vv = kv.gather(nv, tier=0, slots=[i])
        logits, ks, vs = self._verify(target, vk, vv,
                                      pos, toks, cfg=self.cfg)
        kv.write_run(i, m, ks[:, 0, :t], vs[:, 0, :t])
        if self.spec is not None and self._params.draft is not None:
            # reprune draft tier: same suffix pass over the tier-1 views, so
            # the draft cache stays in lockstep from the first spec round
            dk, dv = kv.gather(nv, tier=1, slots=[i])
            _, kd, vd = self._verify(self._params.draft,
                                     dk, dv,
                                     pos, toks, cfg=self.cfg)
            kv.write_run(i, m, kd[:, 0, :t], vd[:, 0, :t], tier=1)
        return logits[:, t - 1]

    # -- main loop -----------------------------------------------------------

    def _now(self) -> float:
        return time.monotonic() - self._t0

    def _gather_views(self, slots: List[Optional[Slot]], kv: PagedKVCache,
                      active: List[int], lookahead: int, tier: int = 0):
        """Grow tables to cover this step's writes, then gather a bucketed
        contiguous view of one KV tier on the device: the block table to
        the device, then the K view and the V view taken from the pool.

        Traced, each stage is a child span: ``kv.tables``, ``kv.h2d``
        (``tensor="table"``) with the table's ``bytes`` and the
        ``useful_bytes`` of its entries that name an active lane's block,
        then ``kv.gather`` per tensor. A traced ``kv.h2d`` or ``kv.gather``
        fences its own output, so it holds its own time."""
        with self._phase("kv.tables"):
            for i in active:
                kv.ensure(i, slots[i].pos + 1 + lookahead)
            nv = max(len(kv.tables[i]) for i in active)
            nv = -(-nv // self.bcfg.view_bucket) * self.bcfg.view_bucket
            tbl = kv.view_tables(nv)
        useful = (tbl.itemsize * int(np.count_nonzero(tbl[active]))
                  if self._obs else 0)
        with self._phase("kv.h2d", tensor="table", bytes=tbl.nbytes,
                         useful_bytes=useful):
            tbl = kv.place(tbl)
            if self._obs:
                jax.block_until_ready(tbl)
        views = []
        for tensor in ("k", "v"):
            with self._phase("kv.gather", tensor=tensor):
                views.append(kv.gather_view(tbl, tensor, tier))
                if self._obs:
                    jax.block_until_ready(views[-1])
        return tuple(views)

    def _decode_step(self, slots: List[Optional[Slot]], kv: PagedKVCache,
                     active: List[int], key) -> List[tuple]:
        """One single-token decode over all slots (loop/scan engines).
        Returns [(slot index, [token]), ...] after committing the KV.

        Instrumented phases fence at their boundary (``block_until_ready``
        / host transfers) so the spans partition the step honestly; with
        observability off no extra fence runs and dispatch stays async."""
        with self._phase("step.gather", n_active=len(active)):
            views_k, views_v = self._gather_views(slots, kv, active, 0)
        pos = np.array([s.pos if s else 0 for s in slots], np.int32)
        toks = np.array([[s.next_token if s else 0] for s in slots],
                        np.int32)
        with self._phase("step.dispatch", engine=self.engine):
            logits, k_new, v_new = self._decode(
                self._params, views_k, views_v, jnp.asarray(pos),
                jnp.asarray(toks), cfg=self.cfg)
            if self._obs:
                jax.block_until_ready((logits, k_new, v_new))
        with self._phase("step.writeback"):
            pb, off = kv.write_coords([s.pos if s else None for s in slots])
            kv.write_token(pb, off, k_new, v_new)
        with self._phase("step.sample"):
            sampled = self._sample_row(logits, key)
        return [(i, [int(sampled[i])]) for i in active]

    def _round_k(self, active: List[int]) -> int:
        """This round's draft length: the MAX of the active slots' adaptive
        k (one mispredicting slot can therefore never drag the whole batch
        down to its collapsed k - it just stops accepting, while the batch
        keeps drafting for the slots that do), or the static spec.k with
        adaptation off. The doubling ladder keeps the set of distinct round
        shapes - and thus jit recompiles - at O(log k_max)."""
        if not self.spec.adaptive_k:
            return self.spec.k
        return max(self._adaptive[i].k for i in active)

    def _spec_step(self, slots: List[Optional[Slot]], kv: PagedKVCache,
                   active: List[int]) -> List[tuple]:
        """One draft-k-verify speculative round over all slots.

        The jitted draft loop proposes ``k`` tokens per row - the reprune
        family over its own higher-sparsity tier-1 views, the layerskip
        family by early-exit over the TARGET's envelope and tier-0 views;
        ONE batched multi-token ``verify_step`` scores the pending token
        plus the whole draft run on the target tier. Per slot, the longest
        prefix of the draft run matching the target's own greedy argmaxes
        is accepted, plus the target's correction token - so the emitted
        stream is bit-identical to target-only greedy decode. Only the
        accepted entries of the candidate KV are committed (``write_run``;
        both tiers for reprune, target-only for layerskip - its draft
        writes nothing anywhere); rejected draft KV never reaches the
        pool - that is the rollback. Returns [(slot index, tokens), ...]
        with 1..k+1 tokens per slot."""
        t_round = time.monotonic()
        k = self._round_k(active)
        layerskip = self._params.draft is None
        pos_np = np.array([s.pos if s else 0 for s in slots], np.int32)
        toks = np.array([[s.next_token if s else 0] for s in slots],
                        np.int32)
        pos = jnp.asarray(pos_np)
        with self._phase("spec.draft", k=k, n_active=len(active)):
            if layerskip:
                dk, dv = self._gather_views(slots, kv, active, k, tier=0)
                props = self._draft_propose(
                    self._params.target, dk, dv, pos, jnp.asarray(toks),
                    cfg=self.cfg, k=k, attn_on=self._attn_on,
                    mlp_on=self._mlp_on)
                d_ks = d_vs = None
            else:
                dk, dv = self._gather_views(slots, kv, active, k, tier=1)
                props, d_ks, d_vs = self._draft_propose(
                    self._params.draft, dk, dv, pos, jnp.asarray(toks),
                    cfg=self.cfg, k=k)
            # fencing props is ~free (the verify consumes them immediately)
            # and makes the draft/verify wall-time split honest
            props = jax.block_until_ready(props)
        t_draft = time.monotonic()
        with self._phase("spec.verify", k=k, n_active=len(active)):
            tk, tv = self._gather_views(slots, kv, active, k, tier=0)
            ver_toks = jnp.concatenate([jnp.asarray(toks), props], axis=1)
            logits, t_ks, t_vs = self._verify(self._params.target, tk, tv,
                                              pos, ver_toks, cfg=self.cfg)
            # greedy targets for every position of the run (B, k+1)
            y = np.asarray(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        t_verify = time.monotonic()
        with self._phase("spec.commit"):
            props_np = np.asarray(props)
            if not layerskip:
                d_ks, d_vs = np.asarray(d_ks), np.asarray(d_vs)
            t_ks, t_vs = np.asarray(t_ks), np.asarray(t_vs)
            runs = []
            for i in active:
                s = slots[i]
                a = spec_mod.accept_greedy(props_np[i], y[i, :k])
                emitted = [int(t) for t in y[i, : a + 1]]
                # cap at the request budget and cut at EOS - exactly where
                # sequential decode would have stopped emitting
                emitted = emitted[: s.req.max_new_tokens - len(s.out)]
                if self.scfg.eos_id >= 0 and self.scfg.eos_id in emitted:
                    emitted = emitted[: emitted.index(self.scfg.eos_id) + 1]
                e = len(emitted)
                kv.write_run(i, s.pos, t_ks[:, i, :e], t_vs[:, i, :e], tier=0)
                if not layerskip:
                    kv.write_run(i, s.pos, d_ks[:, i, :e], d_vs[:, i, :e],
                                 tier=1)
                self._spec_stats.record(n_proposed=k,
                                        n_accepted=min(a, e - 1),
                                        n_emitted=e)
                if self.spec.adaptive_k:
                    # the tracker sees the RAW agreement a/k (not the
                    # budget/EOS-capped commit count): end-of-request
                    # truncation says nothing about draft quality
                    ad = self._adaptive[i]
                    k_was = ad.k
                    ad.observe(n_proposed=k, n_accepted=a)
                    if ad.k < k_was:
                        self._spec_stats.k_collapses += 1
                        if self._obs:
                            self.metrics.counter("spec_k_collapses").inc()
                    elif ad.k > k_was:
                        self._spec_stats.k_expands += 1
                runs.append((i, emitted))
                if self._obs:
                    self.metrics.counter("spec_accepted_tokens").inc(
                        min(a, e - 1))
                    self.metrics.counter("spec_rejected_tokens").inc(
                        k - min(a, e - 1))
        self._spec_stats.draft_s.append(t_draft - t_round)
        self._spec_stats.verify_s.append(t_verify - t_draft)
        self._spec_stats.round_s.append(time.monotonic() - t_round)
        return runs

    def run(self, requests: List[Request]) -> ServeReport:
        cfg, bcfg, scfg = self.cfg, self.bcfg, self.scfg
        q = RequestQueue(requests)
        kv = PagedKVCache(cfg, bcfg.n_slots, bcfg.n_blocks * self._kv_scale,
                          bcfg.block_size, mesh=self.mesh,
                          # only the reprune family keeps a second KV tier;
                          # the layerskip draft reads the target's own cache
                          tiers=2 if (self.spec is not None
                                      and self._params.draft is not None)
                          else 1)
        slots: List[Optional[Slot]] = [None] * bcfg.n_slots
        # the trie lives per run() so traces are independent (and warmup
        # runs never warm the cache of a timed run)
        self._trie = PrefixTrie(kv) if bcfg.prefix_cache else None
        outputs: Dict[str, np.ndarray] = {}
        ttft: List[float] = []
        tpot: List[float] = []
        queue_wait: List[float] = []
        ttft_hit: List[float] = []  # service TTFT, split hit vs miss
        ttft_miss: List[float] = []
        key = jax.random.PRNGKey(scfg.seed)
        n_steps = 0
        self._spec_stats = (spec_mod.SpecStats(self.spec.k,
                                               self.spec.draft_sparsity,
                                               family=self.spec.draft,
                                               keep=self.spec.keep)
                            if self.spec is not None else None)
        # per-slot adaptive-k trackers, created at admission and dropped
        # with the slot (a new request starts from the optimistic prior)
        self._adaptive: Dict[int, spec_mod.AdaptiveK] = {}
        self._t0 = time.monotonic()

        def finish(i: int) -> None:
            s = slots[i]
            outputs[s.req.rid] = np.asarray(s.out, np.int32)
            ttft.append(s.token_times[0] - max(s.req.arrival, 0.0))
            queue_wait.append(s.queue_wait_s)
            service = max(ttft[-1] - s.queue_wait_s, 0.0)
            (ttft_hit if s.prefix_tokens else ttft_miss).append(service)
            tpot.extend(np.diff(s.token_times).tolist())
            if self.tracer.recording:
                # retroactive lifecycle spans: queued -> served, on a queue
                # track plus the slot's own track (slots serialize requests,
                # so per-track spans never overlap). Slot clocks are
                # t0-relative; the tracer wants epoch-relative seconds.
                off = self._t0 - self.tracer.epoch
                arr = max(s.req.arrival, 0.0)
                self.tracer.complete(
                    f"queued:{s.req.rid}", off + arr,
                    off + arr + s.queue_wait_s, track="queue", rid=s.req.rid)
                self.tracer.complete(
                    f"req:{s.req.rid}", off + s.t_admit,
                    off + s.token_times[-1], track=f"slot{i}",
                    rid=s.req.rid, tokens=len(s.out))
            self.metrics.counter("requests_finished").inc()
            kv.free_slot(i)
            slots[i] = None
            self._adaptive.pop(i, None)

        while len(q) or any(s is not None for s in slots):
            key, k_adm, k_dec = jax.random.split(key, 3)
            with self._phase("step.admit"):
                self._admit(q, slots, kv, self._now(), k_adm)
            # a request may be done straight out of prefill (max_new=1/EOS)
            for i, s in enumerate(slots):
                if s is not None and (s.done or s.next_token == scfg.eos_id):
                    finish(i)
            active = [i for i, s in enumerate(slots) if s is not None]
            if not active:
                if len(q):
                    nxt = q.next_arrival()
                    wait = 0.0 if nxt is None else nxt - self._now()
                    if wait > 0:
                        time.sleep(min(wait, bcfg.idle_wait_s))
                continue

            # ``rids`` names the lanes this step gives a token: a request's
            # gaps are its prefill's end, then the ends of these spans
            with self._phase("decode_step", step=n_steps,
                             engine=self.engine, n_active=len(active),
                             rids=([slots[i].req.rid for i in active]
                                   if self._obs else None)):
                if self.spec is not None:
                    runs = self._spec_step(slots, kv, active)
                else:
                    runs = self._decode_step(slots, kv, active, k_dec)
            now = self._now()
            n_steps += 1
            if self._obs:
                in_use = kv.blocks_in_use
                self.metrics.gauge("slots_active").set(len(active))
                self.metrics.gauge("kv_blocks_in_use").set(in_use)
                self.metrics.gauge("kv_utilization").set(
                    in_use / kv.n_blocks)
                self.metrics.counter("decode_steps").inc()
                self.tracer.counter("serve", slots_active=len(active),
                                    kv_blocks_in_use=in_use)
            for i, toks in runs:
                s = slots[i]
                for tok in toks:
                    s.pos += 1
                    s.out.append(tok)
                    s.token_times.append(now)
                    s.next_token = tok
                if s.done or s.next_token == scfg.eos_id:
                    finish(i)

        wall = self._now()
        total = sum(len(o) for o in outputs.values())
        stats = kv.stats()
        stats["n_devices"] = self.n_devices
        snap = None
        if self._obs:
            snap = self.metrics.snapshot() or None
        prefix = None
        if self._trie is not None:
            prefix = {k: (round(v, 4) if isinstance(v, float) else v)
                      for k, v in self._trie.stats().items()}
            prefix["cow_copies"] = kv.n_cow
            # hit-vs-miss split of SERVICE TTFT (queue wait excluded): the
            # number a cache hit is supposed to shrink toward one decode step
            prefix["ttft_service_hit"] = {
                k: round(v, 5) for k, v in _percentiles(ttft_hit).items()}
            prefix["ttft_service_miss"] = {
                k: round(v, 5) for k, v in _percentiles(ttft_miss).items()}
        rep = ServeReport(
            n_requests=len(outputs), total_tokens=total, wall_s=wall,
            n_decode_steps=n_steps, ttft_s=ttft, tpot_s=tpot,
            outputs=outputs, kv_stats=stats,
            spec=(self._spec_stats.to_json()
                  if self._spec_stats is not None else None),
            queue_wait_s=queue_wait, metrics=snap, prefix=prefix,
        )
        rep._n_slots = bcfg.n_slots
        return rep
