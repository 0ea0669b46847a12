"""Serving driver: continuous-batching (default) or legacy static engine.

  # continuous batching over a synthetic mixed-length trace
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke

  # same trace, weights BSR-compressed with a searched schedule tile
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \\
      --compressed --target-sparsity 0.5

  # compiled runtime: one jitted lax.scan decode step over the uniform
  # envelope (bit-identical tokens to the default loop runtime)
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \\
      --compressed --runtime scan

  # offline artifact: first run packs + saves, later runs boot from disk
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \\
      --compressed --artifact /tmp/yi6b-artifact

  # tensor-parallel compressed decode over a 4-device macro cluster
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \\
      --compressed --mesh macro=4 --tile 16x16

  # self-speculative decode: a 0.9-sparsity draft packing of the SAME
  # weights proposes 4 tokens per target verify (greedy tokens stay
  # bit-identical to target-only decode; --spec auto picks k and the
  # draft sparsity from the simulated reload+compute cost)
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \\
      --compressed --spec k=4,draft_sparsity=0.9

  # instrumented serve: Perfetto-loadable trace + metrics snapshot of the
  # measured (post-warmup) run; add --profile DIR for an XLA-level
  # jax.profiler trace
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \\
      --compressed --runtime scan --trace-out /tmp/serve-trace.json \\
      --metrics-out /tmp/serve-metrics.json

  # legacy static-batch Engine (any registry family)
  PYTHONPATH=src python -m repro.launch.serve --arch zamba2-1.2b --smoke \\
      --engine legacy --batch 4 --prompt-len 16 --new-tokens 32

  # multi-tenant gateway: several tenants (artifacts or fresh inits)
  # behind ONE shared KV pool, priced admission, optional mid-run
  # hot-swaps; see the README's "Multi-tenant gateway" for tenants.json
  PYTHONPATH=src python -m repro.launch.serve --smoke \\
      --gateway tenants.json --gateway-parity-check
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..models import registry, transformer
from ..obs import MetricsRegistry, Tracer
from ..serve import (BatchConfig, BatchServer, Engine, Request, ServeConfig,
                     SpecConfig, deployed, stacked)
from ..serve import spec as spec_mod
from . import compile_cache


def _legacy(args, cfg, params, fns=None):
    rng = np.random.default_rng(args.seed)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jnp.asarray(
            rng.standard_normal((args.batch, cfg.n_patches, cfg.d_model)) * 0.02,
            cfg.param_dtype)
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.standard_normal((args.batch, cfg.enc_seq, cfg.d_model)) * 0.02,
            cfg.param_dtype)

    eng = Engine(cfg, params, ServeConfig(max_new_tokens=args.new_tokens,
                                          temperature=args.temperature,
                                          seed=args.seed), fns=fns)
    t0 = time.time()
    out = eng.generate(batch)
    dt = time.time() - t0
    tps = args.batch * out.shape[1] / dt
    print(f"generated {out.shape} in {dt:.2f}s ({tps:.1f} tok/s)")
    for row in out[: min(4, args.batch)]:
        print("  ", row.tolist())


def synthetic_trace(cfg, n_requests: int, max_prompt: int, max_new: int,
                    seed: int = 0, long_every: int = 4):
    """Mixed-length trace: every ``long_every``-th request decodes the full
    ``max_new`` tokens, the rest draw short lengths - the skew that makes
    static batching idle its lanes."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        plen = int(rng.integers(2, max(3, max_prompt)))
        n_new = max_new if i % long_every == 0 else int(
            rng.integers(1, max(2, max_new // 6)))
        reqs.append(Request(f"r{i}", rng.integers(0, cfg.vocab, plen), n_new))
    return reqs


def prefix_skew_trace(cfg, n_requests: int, shared_len: int, suffix_max: int,
                      max_new: int, seed: int = 0,
                      shared_frac: float = 0.9):
    """Prefix-skewed trace (the production shape: most requests share one
    system prompt). ``shared_frac`` of the requests open with the SAME
    ``shared_len``-token prefix followed by a short unique suffix; the rest
    are fully unique prompts of comparable length. Which requests share is
    DETERMINISTIC (position mod 10), so the served hit-rate is a stable
    property of the trace, not of the seed."""
    rng = np.random.default_rng(seed)
    system = rng.integers(0, cfg.vocab, shared_len).astype(np.int32)
    cut = int(round(shared_frac * 10))
    reqs = []
    for i in range(n_requests):
        if i % 10 < cut:
            sfx = rng.integers(0, cfg.vocab,
                               int(rng.integers(1, max(2, suffix_max + 1))))
            p = np.concatenate([system, sfx.astype(np.int32)])
        else:
            p = rng.integers(0, cfg.vocab,
                             shared_len + max(1, suffix_max // 2)
                             ).astype(np.int32)
        reqs.append(Request(f"r{i}", p, max_new))
    return reqs


def _parse_mesh(spec):
    """'macro=N' -> a macro_mesh(N); None -> single-device serving."""
    if not spec:
        return None
    from .shardings import macro_mesh
    axis, _, n = spec.partition("=")
    if axis != "macro" or not n.isdigit():
        raise SystemExit(f"--mesh expects macro=N, got {spec!r}")
    return macro_mesh(int(n))


def _parse_tile(spec):
    if not spec:
        return None
    bk, _, bn = spec.lower().partition("x")
    if not (bk.isdigit() and bn.isdigit() and int(bk) > 0 and int(bn) > 0):
        raise SystemExit(f"--tile expects BKxBN (e.g. 16x16), got {spec!r}")
    return (int(bk), int(bn))


def _load_calibration(args):
    """Boot the measured acceptance prior from the artifact manifest (the
    same persistence slot as the autotune cache). Returns an empty
    SpecCalibration when there is none - search_spec then falls back to
    the uncalibrated priors."""
    from ..sched.search import SpecCalibration
    stored = None
    if args.artifact:
        try:
            stored = deployed.load_artifact_extra(
                args.artifact).get("spec_calibration")
        except (OSError, ValueError):
            stored = None
    if stored is None:
        return SpecCalibration()
    try:
        cal = SpecCalibration.from_json(stored)
        print(f"spec: loaded acceptance calibration "
              f"({len(cal.rows)} measured row(s)) from the artifact "
              "manifest")
        return cal
    except ValueError as e:
        print(f"spec: stored calibration unusable ({e}) - using "
              "uncalibrated priors")
        return SpecCalibration()


def _parse_spec(arg, cfg, target_sparsity, calibration=None):
    """'k=4,draft_sparsity=0.9' or 'draft=layerskip,keep=0.5,k=4' ->
    SpecConfig; 'auto' picks (family, k, knob) from the simulated cost and
    the calibrated acceptance prior - and returns None (serve the scan
    engine) when even the best candidate models a LOSS; '' -> None (no
    speculation)."""
    if not arg:
        return None
    if arg == "auto":
        from ..sched import search_spec
        res = search_spec(cfg, target_sparsity=target_sparsity,
                          calibration=calibration, arch=cfg.name)
        d = res.decision
        print("spec auto:", json.dumps(d))
        if d["accept_source"] != "calibrated":
            print(f"spec auto: acceptance {d['accept']} is a MODELED prior "
                  "(sched.search uncalibrated fallback), not a measurement "
                  "- serve one spec run with --artifact and the measured "
                  "rate is persisted for the next pick")
        if d["verdict"] == "declined":
            print(f"spec auto: declined: scan wins (best candidate "
                  f"{d['family']} k={d['k']} models "
                  f"{d['predicted_speedup']}x vs target-only decode) - "
                  "serving the scan engine")
            return None
        print(f"spec auto: serving {d['family']} k={d['k']} "
              f"({'keep' if d['family'] == 'layerskip' else 'draft_sparsity'}"
              f"={d['knob']}, predicted {d['predicted_speedup']}x, "
              f"accept={d['accept']} [{d['accept_source']}])")
        if d["family"] == "layerskip":
            return SpecConfig(k=int(d["k"]), draft="layerskip",
                              keep=float(d["knob"]))
        return SpecConfig(k=int(d["k"]), draft_sparsity=float(d["knob"]))
    usage = (f"--spec expects 'auto' or comma-joined k=INT, "
             f"draft=reprune|layerskip, draft_sparsity=FLOAT, keep=FLOAT, "
             f"adaptive_k=0|1, got {arg!r}")
    kw = {}
    for part in arg.split(","):
        key, _, val = part.partition("=")
        key = key.strip()
        try:
            if key == "k":
                kw["k"] = int(val)
            elif key == "draft_sparsity":
                kw["draft_sparsity"] = float(val)
            elif key == "draft":
                kw["draft"] = val.strip()
            elif key == "keep":
                kw["keep"] = float(val)
            elif key == "adaptive_k":
                kw["adaptive_k"] = bool(int(val))
            else:
                raise SystemExit(usage)
        except ValueError:
            raise SystemExit(usage) from None
    try:
        return SpecConfig(**kw)
    except ValueError as e:
        raise SystemExit(f"--spec: {e}") from None


def _run_autotune(args, cfg):
    """Measured-latency tile pick for a fresh pack: shortlist the top-N
    simulated tiles, time each through the real stacked BSR kernels, return
    (AutotuneResult, AutotuneCache) - the cache is persisted into the
    artifact manifest so later boots reuse the measurement."""
    from ..sched import autotune as AT

    cache = AT.AutotuneCache()
    res = AT.autotune(cfg, top_n=args.autotune,
                      target_sparsity=args.target_sparsity, cache=cache)
    bk, bn = res.best_tile
    sbk, sbn = res.simulated_tile
    print(f"autotune: measured tile {bk}x{bn} over {len(res.table)} "
          f"candidate(s) on {res.backend} (simulated pick {sbk}x{sbn})")
    for row in res.table:
        print(f"autotune:   tile {row['tile'][0]}x{row['tile'][1]} "
              f"total {row['total_s'] * 1e3:.2f} ms "
              f"(prefill {row['prefill_s'] * 1e3:.2f}, "
              f"decode {row['decode_s'] * 1e3:.2f}; "
              f"sim {row['sim_fps']} fps)")
    return res, cache


def _report_artifact_autotune(cfg, meta):
    """Boot-path cache report: the stored packing is served either way
    (artifacts are immutable); this only says whether the stored autotune
    measurement covers THIS (arch, shapes, backend)."""
    from ..sched import autotune as AT

    stored = meta.get("autotune")
    if not stored:
        print("autotune: artifact carries no autotune cache - serving "
              "stored packing unchanged (re-pack with --autotune to tune)")
        return
    try:
        cache = AT.AutotuneCache.from_json(stored)
    except ValueError as e:
        print(f"autotune: stored cache unusable ({e}) - serving stored "
              "packing unchanged")
        return
    hit = cache.get(AT.autotune_key(cfg))
    if hit is None:
        print("autotune: cache MISS for this (arch, shapes, backend) - the "
              "stored packing was tuned elsewhere; serving as stored "
              "(point --artifact at a fresh directory to re-tune here)")
    else:
        bt = hit["best_tile"]
        print(f"autotune: cache hit ({bt[0]}x{bt[1]}, measured on "
              f"{hit.get('backend')}) - boot reuses the measurement, "
              "no re-timing")


def _serving_params(args, cfg, spec_cfg=None):
    """Build (or boot) the serving weights: the artifact flow runs the
    full search+quantize+prune+pack pipeline ONCE and later boots skip
    straight to weights-on-device. A fresh build draws the weights from
    ``--seed`` one layer at a time (``transformer.init_layerwise``) and
    packs each layer as it is drawn, so the dense model never sits on the
    device whole. Returns (target, draft-or-None,
    spec_cfg); with ``spec_cfg`` the draft tier rides the same artifact
    (two-tier) - an existing single-tier artifact is upgraded in place
    (draft re-packed from the STORED target packing, then re-saved with
    its original manifest extra merged, not rebuilt from current flags).
    A stored draft tier is served AS STORED: if its packed sparsity
    differs from the requested one, the returned spec_cfg adopts the
    stored value so telemetry reports the packing actually served."""
    sp = draft = None
    if args.artifact:
        try:
            sp, draft, meta = deployed.load_artifact_tiers(args.artifact)
        except FileNotFoundError:
            sp = None
        if sp is not None:
            if meta.get("arch") not in (None, cfg.name):
                raise SystemExit(
                    f"--artifact {args.artifact} holds arch "
                    f"{meta.get('arch')!r}, not {cfg.name!r} - point it at a "
                    "fresh directory to re-pack")
            if bool(meta.get("compressed", args.compressed)) != args.compressed:
                print(f"note: artifact was saved with compressed="
                      f"{meta.get('compressed')} - serving it as stored "
                      "(packing flags only apply when building)")
            print(f"artifact: loaded {args.artifact} "
                  f"(arch={meta.get('arch')}, no re-packing)")
            if args.autotune > 0:
                _report_artifact_autotune(cfg, meta)
            if spec_cfg is None:
                return sp, None, None
            if spec_cfg.draft == "layerskip":
                # the layerskip family drafts with a sublayer subset of the
                # TARGET envelope - no second packing to load or build
                return sp, None, spec_cfg
            if draft is not None:
                stored_ds = meta.get("draft_sparsity")
                if (stored_ds is not None
                        and stored_ds != spec_cfg.draft_sparsity):
                    print(f"note: artifact's draft tier was packed at "
                          f"sparsity {stored_ds}, not the requested "
                          f"{spec_cfg.draft_sparsity} - serving it as "
                          "stored (point --artifact at a fresh directory "
                          "to re-pack)")
                    spec_cfg = SpecConfig(k=spec_cfg.k,
                                          draft_sparsity=float(stored_ds))
                return sp, draft, spec_cfg
            draft = spec_mod.draft_serving(
                cfg, sp, spec_cfg.draft_sparsity,
                tile=_parse_tile(args.tile))
            out = deployed.save_artifact(
                args.artifact, sp, cfg, draft=draft,
                extra={**meta,
                       "draft_sparsity": spec_cfg.draft_sparsity})
            print(f"artifact: upgraded to two-tier (draft packed at "
                  f"sparsity {spec_cfg.draft_sparsity}) at {out}")
            return sp, draft, spec_cfg
    at_result = at_cache = None
    tile = _parse_tile(args.tile)
    if args.compressed and args.autotune > 0 and tile is None:
        at_result, at_cache = _run_autotune(args, cfg)
        tile = at_result.best_tile
    params, layer = transformer.init_layerwise(cfg,
                                               jax.random.PRNGKey(args.seed))
    t0 = time.perf_counter()
    sp = (deployed.compress(cfg, params, target_sparsity=args.target_sparsity,
                            schedule=(None if tile else
                                      deployed.default_schedule(cfg)),
                            tile=tile, uniform=at_result is not None,
                            layer=layer)
          if args.compressed
          else deployed.from_params(cfg, params, layer=layer))
    print(f"pack: {time.perf_counter() - t0:.1f}s, tiles "
          f"{deployed.packed_tiles(sp)}")
    if spec_cfg is not None and spec_cfg.draft == "reprune":
        draft = spec_mod.draft_serving(cfg, sp, spec_cfg.draft_sparsity,
                                       tile=tile)
    if args.artifact:
        extra = {"compressed": args.compressed}
        if draft is not None:
            extra["draft_sparsity"] = spec_cfg.draft_sparsity
        if at_result is not None:
            extra["autotune"] = at_cache.to_json()
            extra["autotune_tile"] = list(at_result.best_tile)
        out = deployed.save_artifact(args.artifact, sp, cfg, draft=draft,
                                     extra=extra)
        print(f"artifact: packed + saved to {out}")
    return sp, draft, spec_cfg


def _batch(args, cfg):
    mesh = _parse_mesh(args.mesh)
    calibration = _load_calibration(args) if args.spec else None
    spec_cfg = _parse_spec(args.spec, cfg, args.target_sparsity,
                           calibration=calibration)
    sp, draft, spec_cfg = _serving_params(args, cfg, spec_cfg)
    if args.compressed:
        print("compression:", json.dumps(sp.report()))
    if spec_cfg is not None and spec_cfg.draft == "layerskip":
        print(f"spec: layerskip draft over the target envelope, "
              f"keep={spec_cfg.keep}, k={spec_cfg.k} (no second packing)")
    elif spec_cfg is not None:
        print(f"spec: draft tier packed at sparsity "
              f"{spec_cfg.draft_sparsity} "
              f"({json.dumps(draft.report())}), k={spec_cfg.k}")
    if mesh is not None:
        sp = deployed.shard(sp, mesh)
        if draft is not None:
            draft = deployed.shard(draft, mesh)
        n_sharded = sum(dw.sharded for dw in sp.deployed().values())
        print(f"macro mesh: {mesh.shape} - {n_sharded} projections "
              "column-sharded (rest replicated)")
    bcfg = BatchConfig(n_slots=args.slots, block_size=args.block_size,
                       n_blocks=args.kv_blocks,
                       prefix_cache=not args.no_prefix_cache)
    engine = "spec" if spec_cfg is not None else args.runtime
    print(f"runtime: {engine}"
          + {"scan": " (single jitted lax.scan decode step)",
             "loop": " (python loop over per-layer weights)",
             "spec": " (draft-k-verify speculative decode, greedy-exact)"
             }[engine])
    tracer = Tracer() if args.trace_out else None
    # a registry records the phases, which then also land in the profile
    metrics = (MetricsRegistry()
               if args.metrics_out or args.trace_out or args.profile
               else None)
    srv = BatchServer(cfg, sp, ServeConfig(temperature=args.temperature,
                                           seed=args.seed), bcfg,
                      continuous=(args.engine == "batch"), mesh=mesh,
                      engine=engine, draft=draft, spec=spec_cfg,
                      tracer=tracer, metrics=metrics)
    if spec_cfg is not None and spec_cfg.draft == "layerskip":
        a_on, m_on = srv.spec_masks
        print(f"spec: layerskip masks attn={list(a_on)} mlp={list(m_on)} "
              f"(executes {spec_mod.kept_fraction(a_on, m_on):.2f} of the "
              "sublayer units; nnz-ranked importance)")
    if args.shared_prefix > 0:
        # align the shared span up to a block multiple: the trie matches in
        # whole blocks, so an unaligned span would leave a partial block
        # unshared every time
        shared_len = -(-args.shared_prefix // args.block_size) \
            * args.block_size
        trace = lambda: prefix_skew_trace(
            cfg, args.requests, shared_len, max(2, args.block_size // 2),
            args.new_tokens, seed=args.seed)
    else:
        trace = lambda: synthetic_trace(cfg, args.requests, args.prompt_len,
                                        args.new_tokens, seed=args.seed)
    srv.run(trace())  # compile
    # the warmup run's spans/samples are compile noise: drop them (the
    # tracer keeps its epoch + track names so the measured run's clocks
    # stay consistent)
    if tracer is not None:
        tracer.clear()
    if metrics is not None:
        metrics.clear()
    prof = (jax.profiler.trace(args.profile) if args.profile
            else contextlib.nullcontext())
    with prof:
        rep = srv.run(trace())
    out = rep.to_json()
    if spec_cfg is not None and args.parity_check:
        # greedy-exactness audit: target-only scan decode over the same
        # trace must emit bit-identical tokens
        ref = BatchServer(cfg, sp, ServeConfig(seed=args.seed), bcfg,
                          continuous=(args.engine == "batch"), mesh=mesh,
                          engine="scan").run(trace())
        out["tokens_match_target"] = bool(all(
            np.array_equal(rep.outputs[r.rid], ref.outputs[r.rid])
            for r in trace()))
    if args.prefix_parity_check and rep.prefix is not None:
        # sharing-exactness audit: the same engine with the prefix cache
        # OFF must emit bit-identical tokens over the same trace
        ref = BatchServer(cfg, sp, ServeConfig(temperature=args.temperature,
                                               seed=args.seed),
                          dataclasses.replace(bcfg, prefix_cache=False),
                          continuous=(args.engine == "batch"), mesh=mesh,
                          engine=engine, draft=draft,
                          spec=spec_cfg).run(trace())
        out["tokens_match_unshared"] = bool(all(
            np.array_equal(rep.outputs[r.rid], ref.outputs[r.rid])
            for r in trace()))
    print(json.dumps(out, indent=1))
    if (spec_cfg is not None and rep.spec is not None
            and rep.spec.get("proposed", 0) > 0):
        # close the calibration loop: fold the MEASURED acceptance into the
        # prior and persist it into the artifact manifest (next --spec auto
        # picks from data, not the uncalibrated prior)
        gap = (1.0 - spec_cfg.keep if spec_cfg.draft == "layerskip"
               else spec_cfg.draft_sparsity - args.target_sparsity)
        calibration.add(cfg.name, spec_cfg.draft, gap,
                        rep.spec["acceptance_rate"],
                        weight=float(rep.spec["proposed"]))
        if args.artifact:
            deployed.update_artifact_extra(
                args.artifact, {"spec_calibration": calibration.to_json()})
            print(f"spec: measured acceptance "
                  f"{rep.spec['acceptance_rate']} folded into the "
                  f"calibration ({len(calibration.rows)} row(s)) and "
                  "persisted to the artifact manifest")
        pf = rep.prefix
        print(f"prefix cache: {pf['hits']}/{pf['lookups']} hits "
              f"(hit_rate={pf['hit_rate']}, reused {pf['hit_tokens']} "
              "tokens)")
    for rid in list(rep.outputs)[:3]:
        print(f"  {rid}:", rep.outputs[rid].tolist())
    if tracer is not None:
        tracer.save(args.trace_out)
        print(f"trace: {len(tracer.to_chrome()['traceEvents'])} events -> "
              f"{args.trace_out} (open in Perfetto / chrome://tracing)")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(rep.metrics or {}, f, indent=1)
        print(f"metrics: snapshot -> {args.metrics_out}")
    if args.profile:
        print(f"profile: jax.profiler trace -> {args.profile}")
    stats = jax.devices()[0].memory_stats()
    if stats and "peak_bytes_in_use" in stats:
        print(f"device: {jax.devices()[0].device_kind} peak_bytes_in_use="
              f"{stats['peak_bytes_in_use']}")


def _config(args, arch):
    """The registry config ``--smoke`` / ``--dtype`` select."""
    dtype = args.dtype or ("float32" if args.smoke else "")
    overrides = {"dtype": dtype} if dtype else {}
    return (registry.get_smoke_config(arch, **overrides) if args.smoke
            else registry.get_config(arch, **overrides))


def _tenant_cfg_sp(entry, args):
    """One tenants.json entry -> (cfg, ServingParams). An ``artifact``
    boots the stored packing (validated against the entry's arch); else a
    fresh init from ``seed`` is served dense or uniformly compressed."""
    arch = entry.get("arch") or args.arch
    if not arch:
        raise SystemExit(
            f"tenant {entry.get('name')!r}: no 'arch' in tenants.json and "
            "no --arch fallback")
    cfg = _config(args, arch)
    tile = _parse_tile(entry.get("tile", ""))
    if entry.get("artifact"):
        sp, _, _ = deployed.load_artifact_tiers(
            entry["artifact"], arch=cfg.name, tile=tile)
        print(f"gateway: tenant {entry['name']} loaded artifact "
              f"{entry['artifact']} (arch={cfg.name})")
        return cfg, sp
    params = registry.model_fns(cfg).init_params(
        cfg, jax.random.PRNGKey(int(entry.get("seed", 0))))
    if entry.get("compressed"):
        sp = deployed.compress(
            cfg, params,
            target_sparsity=float(entry.get("target_sparsity", 0.5)),
            tile=tile if tile else (16, 16), uniform=True)
    else:
        sp = deployed.from_params(cfg, params)
    return cfg, sp


def _gateway(args):
    """Multi-tenant serving: tenants.json -> Gateway run (+ optional
    per-tenant parity audit against dedicated single-tenant servers)."""
    from ..gateway import (AdmissionController, Gateway, GatewayConfig,
                           SwapEvent, TenantRuntime, TenantSLO)
    from ..sched.pricing import Pricer

    with open(args.gateway) as f:
        spec = json.load(f)
    entries = spec.get("tenants")
    if not entries:
        raise SystemExit(f"{args.gateway}: no 'tenants' list")
    tenants, swaps, traces = [], [], {}
    for i, entry in enumerate(entries):
        name = entry.get("name")
        if not name:
            raise SystemExit(f"{args.gateway}: tenants[{i}] has no 'name'")
        cfg, sp = _tenant_cfg_sp(entry, args)
        tenants.append(TenantRuntime(
            name, cfg, sp, priority=int(entry.get("priority", 0)),
            slo=TenantSLO.from_json(entry.get("slo")),
            sparsity=float(entry.get("sparsity", 0.0)),
            artifact=entry.get("artifact", "")))
        n_req = int(entry.get("requests", args.requests))
        reqs = synthetic_trace(cfg, n_req, args.prompt_len, args.new_tokens,
                               seed=args.seed + i)
        deadline_s = entry.get("deadline_s")
        traces[name] = [dataclasses.replace(
            r, rid=f"{name}-{r.rid}", tenant=name,
            priority=int(entry.get("priority", 0)),
            deadline=(r.arrival + float(deadline_s)
                      if deadline_s is not None else None))
            for r in reqs]
        hs = entry.get("hot_swap")
        if hs:
            if hs.get("artifact"):
                sp2, _, _ = deployed.load_artifact_tiers(
                    hs["artifact"], arch=cfg.name)
            else:
                sp2 = _tenant_cfg_sp(
                    {**entry, "seed": hs.get("reseed", 1),
                     "artifact": "", "tile": hs.get("tile",
                                                    entry.get("tile", ""))},
                    args)[1]
            swaps.append(SwapEvent(at_step=int(hs.get("at_step", 1)),
                                   tenant=name, sp=sp2))
    gspec = spec.get("gateway", {})
    gcfg = GatewayConfig(
        n_slots=int(gspec.get("n_slots", args.slots)),
        block_size=int(gspec.get("block_size", args.block_size)),
        n_blocks=int(gspec.get("n_blocks", args.kv_blocks)),
        prefill_chunk=int(gspec.get("prefill_chunk", args.prefill_chunk)),
        prefill_device=gspec.get("prefill_device", args.prefill_device),
        max_backlog_s=float(gspec.get("max_backlog_s", args.max_backlog_s)),
        max_pending=(int(gspec["max_pending"]) if "max_pending" in gspec
                     else args.max_pending))
    controller = AdmissionController(pricer=Pricer(),
                                     max_backlog_s=gcfg.max_backlog_s)
    gw = Gateway(tenants, gcfg, ServeConfig(seed=args.seed),
                 controller=controller)
    all_reqs = [r for reqs in traces.values() for r in reqs]
    print(f"gateway: {len(tenants)} tenant(s) "
          f"({', '.join(t.name for t in tenants)}), {len(all_reqs)} "
          f"request(s), one shared pool of {gcfg.n_blocks} blocks")
    rep = gw.run(all_reqs, swaps=swaps)
    for ev in rep.shed:
        print(f"gateway: shed rid={ev['rid']} tenant={ev['tenant']} "
              f"priority={ev['priority']} reason={ev['reason']}")
    out = rep.to_json()
    if args.gateway_parity_check:
        # per-tenant bit-exactness audit: each tenant's gateway tokens vs
        # a dedicated single-tenant BatchServer over the same requests
        swapped = {ev.tenant for ev in swaps}
        for t in tenants:
            if t.name in swapped:
                # pre-swap tokens came from weights the tenant no longer
                # holds - a post-hoc re-serve cannot reproduce them
                print(f"gateway: tenant={t.name} "
                      "tokens_match_dedicated=skipped(hot-swap)")
                continue
            served = rep.per_tenant[t.name].outputs
            bcfg = BatchConfig(n_slots=gcfg.n_slots,
                               block_size=gcfg.block_size,
                               n_blocks=gcfg.n_blocks)
            ded = BatchServer(t.cfg, t.sp, ServeConfig(seed=args.seed),
                              bcfg, engine="scan").run(
                [Request(r.rid, r.prompt, r.max_new_tokens)
                 for r in traces[t.name] if r.rid in served])
            match = bool(all(np.array_equal(served[rid], o)
                             for rid, o in ded.outputs.items()))
            out.setdefault("parity", {})[t.name] = match
            print(f"gateway: tenant={t.name} "
                  f"tokens_match_dedicated={match}")
    print(json.dumps(out, indent=1))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="",
                    help="registry architecture (required unless --gateway "
                    "names per-tenant arches)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", choices=["batch", "static", "legacy"],
                    default="batch",
                    help="batch = continuous batching (default); static = "
                    "same server, whole-batch admission; legacy = Engine")
    ap.add_argument("--compressed", action="store_true",
                    help="serve deploy_weight-packed (BSR) projections")
    ap.add_argument("--runtime", choices=["loop", "scan"], default="loop",
                    help="decode runtime: loop = python loop over per-layer "
                    "weights; scan = one jitted lax.scan over the stacked "
                    "uniform envelope (bit-identical tokens)")
    ap.add_argument("--spec", default="",
                    help="speculative decode: comma-joined k=INT, "
                    "draft=reprune|layerskip, draft_sparsity=FLOAT (reprune "
                    "knob: packs a second higher-sparsity tier), keep=FLOAT "
                    "(layerskip knob: draft runs the nnz-ranked top keep "
                    "fraction of the TARGET envelope's sublayers - no "
                    "second packing), adaptive_k=0|1. 'auto' picks (family, "
                    "k, knob) from simulated cost + the calibrated "
                    "acceptance prior, or declines and serves the scan "
                    "engine when speculation models a loss")
    ap.add_argument("--parity-check", action="store_true",
                    help="with --spec: also run target-only scan decode "
                    "over the trace and report tokens_match_target (the "
                    "greedy bit-exactness contract)")
    ap.add_argument("--artifact", default="",
                    help="serving-artifact directory: boot from it when it "
                    "exists (no re-packing), else pack once and save there")
    ap.add_argument("--mesh", default="",
                    help="macro=N: shard compressed projections column-wise "
                    "and KV heads over an N-device macro cluster")
    ap.add_argument("--tile", default="",
                    help="BKxBN packing tile override (e.g. 16x16); default "
                    "is the searched schedule's tile")
    ap.add_argument("--autotune", type=int, default=0, metavar="TOPN",
                    help="with --compressed: time the top-TOPN simulated "
                    "tiles through the real stacked BSR kernels (fenced) "
                    "and pack with the measured winner; the measurement is "
                    "cached in the artifact manifest keyed by (arch, "
                    "shapes, backend). 0 = trust the simulator (default). "
                    "Ignored when --tile pins the tile explicitly")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome trace-event JSON of the measured "
                    "run (phase spans, request lifecycle tracks, occupancy "
                    "counters) - open in Perfetto / chrome://tracing")
    ap.add_argument("--metrics-out", default="",
                    help="write the measured run's metrics snapshot "
                    "(counters/gauges/phase histograms) as JSON")
    ap.add_argument("--profile", default="",
                    help="directory for a jax.profiler trace of the "
                    "measured run (XLA-level, TensorBoard-loadable), with "
                    "the serving phases as host annotations")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="serve a prefix-skewed trace instead of the mixed-"
                    "length one: 90%% of requests share an N-token system "
                    "prompt (N is aligned up to a block multiple) plus a "
                    "short unique suffix - the radix-tree prefix cache "
                    "should hit on nearly all of them")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable radix-tree prefix KV reuse (default on; "
                    "tokens are bit-identical either way)")
    ap.add_argument("--prefix-parity-check", action="store_true",
                    help="also serve the trace with the prefix cache OFF "
                    "and report tokens_match_unshared (the sharing "
                    "bit-exactness contract)")
    ap.add_argument("--gateway", default="", metavar="TENANTS_JSON",
                    help="multi-tenant gateway mode: serve the tenants "
                    "described in TENANTS_JSON behind one shared KV pool "
                    "with simulator-priced admission (see the README's "
                    "'Multi-tenant gateway' for the schema)")
    ap.add_argument("--gateway-parity-check", action="store_true",
                    help="with --gateway: re-serve each tenant's requests "
                    "on a dedicated single-tenant server and report "
                    "tokens_match_dedicated (the isolation bit-exactness "
                    "contract)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="gateway: advance pending prefills at most N "
                    "tokens per step, interleaved with decode rounds "
                    "(0 = whole prompt at admission)")
    ap.add_argument("--prefill-device", type=int, default=None,
                    help="gateway: pin chunked-prefill dispatches to this "
                    "device index (prefill/decode disaggregation)")
    ap.add_argument("--max-backlog-s", type=float, default=float("inf"),
                    help="gateway: shed (lowest-priority-first) once the "
                    "simulator-predicted backlog exceeds this many seconds")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="gateway: bound the request queue; overflow sheds "
                    "the lowest-priority pending request (counted, never "
                    "silent)")
    ap.add_argument("--target-sparsity", type=float, default=0.5)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--kv-blocks", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--dtype", default="",
                    help="parameter dtype; default float32 with --smoke, "
                    "else the published config's own (bfloat16)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    compile_cache.enable()
    if args.gateway:
        _gateway(args)
        return
    if not args.arch:
        ap.error("--arch is required (unless --gateway names per-tenant "
                 "arches)")

    cfg = _config(args, args.arch)

    use_legacy = args.engine == "legacy"
    if not use_legacy and cfg.family not in ("dense", "moe"):
        print(f"note: no batch-server path for the {cfg.family} family yet; "
              "falling back to the legacy Engine")
        use_legacy = True

    if use_legacy:
        if args.compressed:
            sp, _, _ = _serving_params(args, cfg)
            print("compression:", json.dumps(sp.report()))
            if args.runtime == "scan":
                _legacy(args, cfg, stacked.stack(sp),
                        fns=stacked.model_fns(cfg))
            else:
                _legacy(args, cfg, jax.device_put(sp),
                        fns=deployed.model_fns(cfg))
        else:
            # uncompressed legacy serving already runs the registry's
            # scan-over-layers forward - both --runtime values coincide
            params = registry.model_fns(cfg).init_params(
                cfg, jax.random.PRNGKey(args.seed))
            _legacy(args, cfg, params)
    else:
        _batch(args, cfg)


if __name__ == "__main__":
    main()
