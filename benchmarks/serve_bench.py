"""Serving benchmark: static vs continuous batching vs compressed weights
vs macro-mesh (tensor-parallel) compressed serving.

One synthetic mixed-length trace (every 4th request decodes long, the rest
short - the skew that makes a static batcher idle its lanes) served four
ways on the smoke LM:

  * ``static``     - BatchServer with whole-batch admission (lanes drain
    together; a freed slot waits for the batch);
  * ``continuous`` - the same server, slot-level admission into freed lanes;
  * ``compressed`` - continuous batching where every CIM projection runs on
    the int8 BSR Pallas kernel (``serve.deployed.compress`` with a
    ``sched.search``-chosen tile); this is the LOOP runtime (python loop
    over per-layer packed weights - L kernel dispatches per decode step);
  * ``compressed_scan`` - the SAME weights through the compiled runtime
    (``BatchServer(engine="scan")``: uniform-envelope stacks + one jitted
    ``lax.scan`` decode step, zero per-layer dispatches). The loop-vs-scan
    summary row reports decode-step latency, first-run trace/compile time,
    tokens/s, and the ``tokens_match`` parity bit (bit-exactness contract);
  * ``sharded``    - the compressed server column-sharded over a 4-device
    macro mesh of the devices this process has (reported as not run with
    fewer; on a CPU host ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
    gives four). On CPU fake devices this measures the orchestration
    overhead, not a speedup - the row's purpose is the contract: tokens
    bit-identical to single-device (``tokens_match``);
  * ``spec``       - self-speculative decode with the LAYERSKIP draft
    family: the draft runs the nnz-ranked top-``SPEC_KEEP`` fraction of
    the TARGET envelope's sublayers (no second packing) and proposes k
    tokens per batched multi-token target verify. Reports the measured
    acceptance rate, accepted-length histogram, decode-step p50 and
    tokens/s against ``compressed_scan``, the ``tokens_match_target``
    greedy bit-exactness bit, and the calibrated ``--spec auto`` decision:
    the measured acceptance is folded into a ``sched.search``
    SpecCalibration (persisted into the shared artifact manifest, like
    the autotune cache) and the re-run search either picks a (family, k,
    knob) or records ``declined: scan wins``.

A separate prefix-skew trace (``serve_prefix_skew`` row) serves ~90%
shared-system-prompt requests through the scan runtime with the radix-tree
prefix cache on vs off: cache-hit requests adopt the shared blocks and
prefill only their suffix, so the row reports the hit rate, cache-hit vs
miss service TTFT p50, the hit-TTFT-over-decode-step ratio, tokens/s both
ways and the ``tokens_match_unshared`` parity bit.

The single-host engines share kernels and per-step cost, so static-vs-
continuous isolates the scheduling policy. Each engine is warmed on the
identical trace first (shape buckets compile once); the reported run is
jit-warm and every bench clock fences with ``jax.block_until_ready``.
Results land in ``BENCH_serve.json`` with TTFT / per-token-latency
percentiles (queue wait split out of TTFT), plus a ``sim_vs_measured``
row from a separate ``repro.obs``-instrumented scan run: fenced
decode-step p50 against the event-driven simulator's one-token step on
the modeled CIM fabric (the ratio's drift, not its value, is the signal).
The sharded row carries its own ``sim_vs_measured`` against the
all-gather-aware prediction (``serve_gap(..., n_devices=4)``), so the
collective's modeled share is confronted with the measured step cost.

Packings are cached as serving artifacts under one shared directory
(``MARS_BENCH_ARTIFACTS``, default ``bench-artifacts/`` in the checkout,
git-ignored): repeat benchmark runs boot via
``serve.deployed.load_artifact`` and skip the search+quantize+prune+pack
pipeline entirely.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import numpy as np

from repro.models import registry
from repro.obs import MetricsRegistry
from repro.obs import gap as obs_gap
from repro.serve import (BatchConfig, BatchServer, Request, ServeConfig,
                         SpecConfig)
from repro.serve import deployed as DP
from repro.serve import spec as SP
from repro.sched.search import SpecCalibration, search_spec
from repro.launch.serve import prefix_skew_trace, synthetic_trace

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ART_ROOT = os.environ.get("MARS_BENCH_ARTIFACTS",
                          os.path.join(REPO, "bench-artifacts"))

ARCH = "yi-6b"
N_REQUESTS = 12
MAX_PROMPT = 20
MAX_NEW = 36
TARGET_SPARSITY = 0.6
SHARD_DEVICES = 4
SHARD_TILE = (16, 16)  # small tile -> enough block columns to split
SPEC_K = 4
SPEC_DRAFT_SPARSITY = 0.85  # the cached artifact's reprune draft tier
SPEC_FAMILY = "layerskip"   # the family the spec row serves
SPEC_KEEP = 0.5
# prefix-skew trace: ~90% of requests share one system prompt (the
# production workload the radix-tree prefix cache exists for). The shared
# span is a block multiple so the trie can cache every full block of it.
PREFIX_REQUESTS = 24
PREFIX_SHARED = 64
PREFIX_SUFFIX_MAX = 6
PREFIX_MAX_NEW = 8
# two-tenant gateway row: both tenants serve the SAME cached packing over
# ONE shared block pool; the queue is bounded below the offered load so
# the overload contract visibly sheds the low-priority tenant's tail
GATEWAY_REQUESTS = 6   # per tenant
GATEWAY_MAX_NEW = 8
GATEWAY_MAX_PENDING = 8  # < 2 * GATEWAY_REQUESTS -> forced overflow
GATEWAY_TTFT_SLO_MS = 120000.0  # generous: CI runners are interp-mode


def _serve(cfg, sp, continuous: bool, trace_fn, repeats: int = 2,
           engine: str = "loop", **kw):
    rep, _ = _serve_timed(cfg, sp, continuous, trace_fn, repeats=repeats,
                          engine=engine, **kw)
    return rep


def _serve_timed(cfg, sp, continuous: bool, trace_fn, repeats: int = 2,
                 warmup: int = 1, engine: str = "loop", bcfg=None, **kw):
    """Like ``_serve`` but also returns the first-run wall time - dominated
    by trace+compile, the cost the scan runtime amortizes over layers.

    Every bench clock is FENCED: ``jax.block_until_ready`` over each run's
    outputs before the stopwatch stops, so async dispatch can't leak device
    work past a timer. The first run (and any extra ``warmup`` iterations)
    is trace+compile and is excluded from the measured repeats; warmup
    samples are also dropped from any attached obs sinks."""
    srv = BatchServer(cfg, sp, ServeConfig(),
                      bcfg or BatchConfig(n_slots=4, block_size=8,
                                          n_blocks=64),
                      continuous=continuous, engine=engine, **kw)
    t0 = time.perf_counter()
    jax.block_until_ready(srv.run(trace_fn()).outputs)  # compile all buckets
    compile_s = time.perf_counter() - t0
    for _ in range(warmup - 1):
        jax.block_until_ready(srv.run(trace_fn()).outputs)
    # warmup spans/samples are compile noise, not serving cost
    srv.metrics.clear()
    srv.tracer.clear()
    best = None
    for _ in range(repeats):
        rep = srv.run(trace_fn())
        jax.block_until_ready(rep.outputs)
        if best is None or rep.tokens_per_s > best.tokens_per_s:
            best = rep
    return best, compile_s


def _cached_packing(name: str, cfg, build_fn, draft: bool = False,
                    want: dict | None = None):
    """Load a packed ServingParams from the shared artifact dir, or build
    it ONCE with ``build_fn() -> (sp, draft_sp_or_None, extra)`` and save
    it there - repeat runs boot without re-packing.

    ``want`` pins the packing constants the caller is about to report
    (sparsities, forced tile; the arch is always pinned): a cached
    artifact whose stored meta disagrees is STALE (the constants changed
    since it was packed) and is rebuilt rather than silently served under
    the new labels."""
    want = {"arch": cfg.name, **(want or {})}
    path = os.path.join(ART_ROOT, name)
    try:
        sp, dsp, meta = DP.load_artifact_tiers(path)
        if (all(meta.get(k) == v for k, v in want.items())
                and (dsp is not None or not draft)):
            return sp, dsp, meta
    except (FileNotFoundError, ValueError, TypeError):
        pass
    sp, dsp, extra = build_fn()
    extra = {**want, **extra}
    DP.save_artifact(path, sp, cfg, draft=dsp, extra=extra)
    return sp, dsp, extra


def _row(name: str, j: dict) -> dict:
    return {
        "name": f"serve_{name}",
        "tokens_per_s": j["tokens_per_s"],
        "ttft_p50_ms": round(j["ttft"]["p50"] * 1e3, 2),
        "ttft_p99_ms": round(j["ttft"]["p99"] * 1e3, 2),
        "tpot_p50_ms": round(j["tpot"]["p50"] * 1e3, 2),
        "tpot_p99_ms": round(j["tpot"]["p99"] * 1e3, 2),
        "slot_efficiency": j["slot_efficiency"],
    }


def _shard_packing(cfg):
    """The 16x16-tile packing the sharded row serves, cached as a shared
    artifact so repeat runs boot it instead of re-packing."""

    def build():
        params = registry.model_fns(cfg).init_params(cfg,
                                                     jax.random.PRNGKey(0))
        sp = DP.compress(cfg, params, target_sparsity=TARGET_SPARSITY,
                         tile=SHARD_TILE)
        return sp, None, {}

    return _cached_packing("sharded%dx%d" % SHARD_TILE, cfg, build,
                           want={"tile": list(SHARD_TILE),
                                 "target_sparsity": TARGET_SPARSITY})[0]


def _sharded_report(cfg):
    """The compressed server column-sharded over ``SHARD_DEVICES`` devices
    of THIS process, against single-device serving on the same trace.

    The row runs on the devices present and is reported as not run when
    there are fewer than ``SHARD_DEVICES`` - it never moves to another
    backend behind the caller's back (on a CPU host,
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` provides them)."""
    n = len(jax.devices())
    if n < SHARD_DEVICES:
        return {"not_run": f"needs {SHARD_DEVICES} devices, found {n}"}
    from repro.launch.shardings import macro_mesh

    spc = _shard_packing(cfg)
    trace_fn = lambda: synthetic_trace(cfg, N_REQUESTS, MAX_PROMPT, MAX_NEW)
    single = _serve(cfg, spc, True, trace_fn, repeats=1)

    mesh = macro_mesh(SHARD_DEVICES)
    sps = DP.shard(spc, mesh)
    n_sharded = sum(dw.sharded for dw in sps.deployed().values())
    srv = BatchServer(cfg, sps, ServeConfig(),
                      BatchConfig(n_slots=4, block_size=8, n_blocks=64),
                      continuous=True, mesh=mesh)
    srv.run(trace_fn())  # compile
    rep = srv.run(trace_fn())
    match = all(np.array_equal(rep.outputs[r.rid], single.outputs[r.rid])
                for r in trace_fn())
    out = rep.to_json()
    out["n_devices"] = SHARD_DEVICES
    out["n_sharded_projections"] = n_sharded
    out["tile"] = list(SHARD_TILE)
    out["tokens_match_single_device"] = match
    # the all-gather-aware prediction (perf_model's ring collective at every
    # column-sharded projection) against the sharded run's fenced tpot p50
    out["sim_vs_measured"] = obs_gap.serve_gap(
        cfg, float(out["tpot"]["p50"]), TARGET_SPARSITY,
        n_devices=SHARD_DEVICES)
    return out


def _gateway_report(cfg, spc):
    """Two tenants, one pool, bounded queue: per-tenant goodput + SLO
    attainment under forced overload. Both tenants serve the same cached
    packing, so the row isolates the gateway's scheduling, not the
    kernels; the shed evidence pins the strictly-lowest-priority-first
    overload contract."""
    from repro.gateway import (AdmissionController, Gateway, GatewayConfig,
                               TenantRuntime, TenantSLO)

    tenants = [
        TenantRuntime("prio", cfg, spc, priority=1,
                      slo=TenantSLO(ttft_ms=GATEWAY_TTFT_SLO_MS)),
        TenantRuntime("batch", cfg, spc, priority=0),
    ]
    gcfg = GatewayConfig(n_slots=4, block_size=8, n_blocks=96,
                         max_pending=GATEWAY_MAX_PENDING)
    gw = Gateway(tenants, gcfg, ServeConfig())

    def trace():
        reqs = []
        for pi, (name, prio) in enumerate((("prio", 1), ("batch", 0))):
            for r in synthetic_trace(cfg, GATEWAY_REQUESTS, MAX_PROMPT,
                                     GATEWAY_MAX_NEW, seed=pi):
                reqs.append(dataclasses.replace(
                    r, rid=f"{name}-{r.rid}", tenant=name, priority=prio))
        return reqs

    gw.run(trace())  # compile all shape buckets (sheds here are warmup's)
    gw.controller = AdmissionController()  # fresh admission accounting
    rep = gw.run(trace())
    j = rep.to_json()
    lowest = min(t.priority for t in tenants)
    return {
        "n_requests": 2 * GATEWAY_REQUESTS,
        "max_pending": GATEWAY_MAX_PENDING,
        "tenants": {
            name: {
                "priority": t["priority"],
                "n_requests": t["n_requests"],
                "tokens_per_s": t["tokens_per_s"],
                "goodput_tokens_per_s": t["goodput_tokens_per_s"],
                "slo_attainment": t["slo_attainment"],
                "ttft_p50_ms": round(t["ttft"]["p50"] * 1e3, 2),
            } for name, t in j["tenants"].items()},
        "n_shed": j["n_shed"],
        # the overload contract's evidence bit: every shed victim sat at
        # the lowest priority level present in the trace
        "shed_lowest_priority_only": bool(
            j["shed_events"]
            and all(ev["priority"] == lowest for ev in j["shed_events"])),
        "admission": j["admission"],
    }


def run():
    cfg = registry.get_smoke_config(ARCH, dtype="float32")
    params = registry.model_fns(cfg).init_params(cfg, jax.random.PRNGKey(0))
    sp = DP.from_params(cfg, params)

    def build_compressed():
        schedule = DP.default_schedule(cfg)
        spc = DP.compress(cfg, params, target_sparsity=TARGET_SPARSITY,
                          schedule=schedule)
        draft = SP.draft_serving(cfg, spc, SPEC_DRAFT_SPARSITY)
        return spc, draft, {"tile": list(schedule.candidate.tile)}

    # the searched-tile packing + its speculative draft tier ride one
    # shared two-tier artifact: repeat runs (and anything else pointed at
    # ART_ROOT) boot without re-running search/quantize/prune/pack
    spc, draft, meta = _cached_packing(
        "compressed", cfg, build_compressed, draft=True,
        want={"target_sparsity": TARGET_SPARSITY,
              "draft_sparsity": SPEC_DRAFT_SPARSITY})
    schedule_tile = list(meta.get("tile", []))

    trace_fn = lambda: synthetic_trace(cfg, N_REQUESTS, MAX_PROMPT, MAX_NEW)

    loop_rep, loop_compile_s = _serve_timed(cfg, spc, True, trace_fn)
    scan_rep, scan_compile_s = _serve_timed(cfg, spc, True, trace_fn,
                                            engine="scan")
    scan_match = all(
        np.array_equal(scan_rep.outputs[r.rid], loop_rep.outputs[r.rid])
        for r in trace_fn())
    spec_cfg = SpecConfig(k=SPEC_K, draft=SPEC_FAMILY, keep=SPEC_KEEP)
    spec_rep = _serve(cfg, spc, True, trace_fn, engine="spec",
                      spec=spec_cfg)
    spec_match = all(
        np.array_equal(spec_rep.outputs[r.rid], scan_rep.outputs[r.rid])
        for r in trace_fn())

    # sim-vs-measured gap: a separate short instrumented scan run (the
    # comparison rows above stay un-instrumented, so phase fencing never
    # taxes their numbers); fenced decode-step p50 + per-phase wall-time
    # shares confronted with the event-driven simulator's one-token step
    # on the modeled CIM fabric. The ratio is cycles-model-vs-host-backend,
    # so its VALUE is not ~1 - CI tracks that it stays finite and stable.
    gap_metrics = MetricsRegistry()
    _serve(cfg, spc, True, trace_fn, repeats=1, engine="scan",
           metrics=gap_metrics)
    snap = gap_metrics.snapshot()
    step_h = snap["histograms"].get("serve_phase_s{phase=decode_step}", {})
    # empty phase table (instrumentation regressed / zero decode steps):
    # fall back to the fenced tpot p50 rather than feeding 0.0 into the gap
    step_p50 = (float(step_h["p50"]) if step_h.get("count")
                else float(scan_rep.to_json()["tpot"]["p50"]))
    sim_gap = obs_gap.serve_gap(
        cfg, step_p50, TARGET_SPARSITY,
        measured_phases={k: v for k, v in
                         obs_gap.measured_phase_shares(snap).items()
                         if k.startswith("step.")})

    reports = {
        "static": _serve(cfg, sp, False, trace_fn),
        "continuous": _serve(cfg, sp, True, trace_fn),
        "compressed": loop_rep,
        "compressed_scan": scan_rep,
        "spec": spec_rep,
    }
    sharded = _sharded_report(cfg)
    loop_vs_scan = {
        # per-decode-step latency: all slots advance one token per step,
        # so tpot is the step cost; the scan runtime compiles the layer
        # loop into ONE dispatch instead of L kernel launches per step
        "decode_step_p50_ms_loop": round(
            loop_rep.to_json()["tpot"]["p50"] * 1e3, 3),
        "decode_step_p50_ms_scan": round(
            scan_rep.to_json()["tpot"]["p50"] * 1e3, 3),
        "compile_s_loop": round(loop_compile_s, 2),
        "compile_s_scan": round(scan_compile_s, 2),
        "tokens_per_s_loop": loop_rep.to_json()["tokens_per_s"],
        "tokens_per_s_scan": scan_rep.to_json()["tokens_per_s"],
        "layer_dispatches_per_step_loop": cfg.n_layers,
        "layer_dispatches_per_step_scan": 1,
        "tokens_match": scan_match,
    }

    scan_j = scan_rep.to_json()
    spec_j = spec_rep.to_json()
    # close the calibration loop: fold the MEASURED acceptance into a
    # sched.search prior, persist it into the shared artifact manifest
    # (the slot --spec auto boots from), and record the decision the
    # calibrated search would serve next - a winning (family, k, knob)
    # or "declined: scan wins"
    calibration = SpecCalibration()
    calibration.add(cfg.name, SPEC_FAMILY, 1.0 - SPEC_KEEP,
                    spec_j["spec"]["acceptance_rate"],
                    weight=float(max(spec_j["spec"]["proposed"], 1)))
    DP.update_artifact_extra(os.path.join(ART_ROOT, "compressed"),
                             {"spec_calibration": calibration.to_json()})
    auto_decision = search_spec(cfg, target_sparsity=TARGET_SPARSITY,
                                calibration=calibration,
                                arch=cfg.name).decision
    # the bench HAS the end-to-end measurement - the recorded decision is
    # measurement-first: a simulated win that measured a throughput loss
    # on this backend is declined (the auto contract: never ship a loss)
    measured_speedup = round(
        spec_j["tokens_per_s"] / max(scan_j["tokens_per_s"], 1e-9), 4)
    auto_decision["measured_speedup"] = measured_speedup
    if measured_speedup < 1.0 and auto_decision["verdict"] == "spec":
        auto_decision = {**auto_decision, "verdict": "declined",
                         "reason": "scan wins (measured tokens/s)"}
    spec_summary = {
        # draft-k-verify vs the compiled target-only baseline: same
        # weights, same trace - what speculation buys (or costs) end to end
        "family": SPEC_FAMILY,
        "k": SPEC_K,
        "keep": SPEC_KEEP,
        # the artifact also carries the cached reprune draft tier; its
        # compression ratio documents the alternative family's packing
        "reprune_draft_compression_x": round(
            draft.report()["compression_x"], 2),
        "acceptance_rate": spec_j["spec"]["acceptance_rate"],
        "accepted_len_hist": spec_j["spec"]["accepted_len_hist"],
        "spec_k_collapses": spec_j["spec"]["spec_k_collapses"],
        "tokens_per_verify": spec_j["spec"]["tokens_per_verify"],
        # spec tokens materialize in bursts (one round = draft loop +
        # verify), so its per-token latency is the round p50 divided by
        # tokens/round - NOT the pooled token_times diffs, whose
        # intra-burst entries are legitimately zero
        "round_p50_ms_spec": spec_j["spec"]["round_p50_ms"],
        "decode_p50_ms_spec": spec_j["spec"]["ms_per_token_p50"],
        "decode_p50_ms_scan": round(scan_j["tpot"]["p50"] * 1e3, 3),
        "tokens_per_s_spec": spec_j["tokens_per_s"],
        "tokens_per_s_scan": scan_j["tokens_per_s"],
        "tokens_match_target": spec_match,
        "auto_decision": auto_decision,
    }

    # prefix-skew trace through the compiled runtime: ~90% of requests
    # share one 64-token system prompt, so after the first admission the
    # radix trie serves their prefix KV from cache and prefill shrinks to
    # the unshared suffix. Cache on vs off on the SAME trace isolates what
    # reuse buys; the parity bit pins the greedy bit-exactness contract.
    pfx_bcfg = BatchConfig(n_slots=4, block_size=8, n_blocks=96)
    pfx_trace = lambda: prefix_skew_trace(cfg, PREFIX_REQUESTS,
                                          PREFIX_SHARED, PREFIX_SUFFIX_MAX,
                                          PREFIX_MAX_NEW)
    pfx_rep = _serve(cfg, spc, True, pfx_trace, engine="scan",
                     bcfg=pfx_bcfg)
    pfx_off_rep = _serve(cfg, spc, True, pfx_trace, engine="scan",
                         bcfg=dataclasses.replace(pfx_bcfg,
                                                  prefix_cache=False))
    pfx_match = all(
        np.array_equal(pfx_rep.outputs[r.rid], pfx_off_rep.outputs[r.rid])
        for r in pfx_trace())
    pfx_j = pfx_rep.to_json()
    pfx = pfx_j["prefix"]
    pfx_step_ms = round(pfx_j["tpot"]["p50"] * 1e3, 3)
    pfx_hit_ms = round(pfx["ttft_service_hit"]["p50"] * 1e3, 3)
    prefix_summary = {
        # the headline: a cache-hit request's service TTFT (queue wait
        # excluded) lands within ~a decode step of admission, because its
        # first forward pass covers only the unshared suffix
        "n_requests": PREFIX_REQUESTS,
        "shared_tokens": PREFIX_SHARED,
        "hit_rate": pfx["hit_rate"],
        "hit_tokens": pfx["hit_tokens"],
        "ttft_hit_p50_ms": pfx_hit_ms,
        "ttft_miss_p50_ms": round(
            pfx["ttft_service_miss"]["p50"] * 1e3, 3),
        "decode_step_p50_ms": pfx_step_ms,
        "ttft_hit_over_decode_step": round(
            pfx_hit_ms / max(pfx_step_ms, 1e-9), 2),
        "tokens_per_s": pfx_j["tokens_per_s"],
        "tokens_per_s_unshared": pfx_off_rep.to_json()["tokens_per_s"],
        "tokens_match_unshared": pfx_match,
        "cow_copies": pfx["cow_copies"],
    }

    gateway_summary = _gateway_report(cfg, spc)

    report = {
        "arch": cfg.name,
        "trace": {"n_requests": N_REQUESTS, "max_prompt": MAX_PROMPT,
                  "max_new": MAX_NEW},
        "schedule_tile": schedule_tile,
        "compression": spc.report(),
        "speedup_continuous_vs_static": round(
            reports["continuous"].tokens_per_s
            / max(reports["static"].tokens_per_s, 1e-9), 3),
        **{k: v.to_json() for k, v in reports.items()},
        "loop_vs_scan": loop_vs_scan,
        "spec_vs_scan": spec_summary,
        "sharded": sharded,
        "sim_vs_measured": sim_gap,
        "prefix_skew": prefix_summary,
        "gateway_two_tenant": gateway_summary,
    }
    with open(os.path.abspath(OUT_PATH), "w") as f:
        json.dump(report, f, indent=1)

    rows = [_row(name, rep.to_json()) for name, rep in reports.items()]
    for r in rows:
        if r["name"] == "serve_compressed_scan":
            r["tokens_match"] = scan_match
        if r["name"] == "serve_spec":
            r["acceptance_rate"] = spec_summary["acceptance_rate"]
            r["tokens_match_target"] = spec_match
    if "not_run" in sharded:
        rows.append({"name": "serve_sharded_macro%d" % SHARD_DEVICES,
                     "not_run": sharded["not_run"]})
    else:
        srow = _row("sharded_macro%d" % SHARD_DEVICES, sharded)
        srow["tokens_match"] = sharded["tokens_match_single_device"]
        rows.append(srow)
    rows.append({"name": "serve_loop_vs_scan", **loop_vs_scan})
    rows.append({"name": "serve_spec_vs_scan", **spec_summary})
    rows.append({"name": "serve_prefix_skew", **prefix_summary})
    rows.append({"name": "serve_gateway_two_tenant", **gateway_summary})
    rows.append({
        "name": "serve_sim_vs_measured",
        "gap": sim_gap["sim_vs_measured"],
        "predicted_us": round(sim_gap["predicted_s"] * 1e6, 2),
        "measured_us": round(sim_gap["measured_s"] * 1e6, 2),
    })
    if "not_run" not in sharded:
        sharded_gap = sharded["sim_vs_measured"]
        rows.append({
            "name": "serve_sharded_sim_vs_measured",
            "gap": sharded_gap["sim_vs_measured"],
            "n_devices": SHARD_DEVICES,
            "collective_share": sharded_gap["predicted_phase_shares"].get(
                "collective", 0.0),
            "predicted_us": round(sharded_gap["predicted_s"] * 1e6, 2),
            "measured_us": round(sharded_gap["measured_s"] * 1e6, 2),
        })
    rows.append({
        "name": "serve_continuous_speedup",
        "vs_static": report["speedup_continuous_vs_static"],
        "compression_x": round(report["compression"]["compression_x"], 2),
    })
    return rows


def main():
    for r in run():
        print(",".join(f"{k}={v}" for k, v in r.items()))


if __name__ == "__main__":
    main()
