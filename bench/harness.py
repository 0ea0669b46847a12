"""One run of one cell: set-up, the measured window, metrics, ``correct``.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix, ``bench/configs/<config>.json`` holds
the configuration as it is run, ``bench/traffic/<mix>.json`` the mix,
``bench/cells/<cell>.json`` the cell's measured knee and its limits, and
``bench/metrics/<metric>.py`` the reader of each metric. Adding any of
them takes new files and entries, no edit here.

A configuration serves one checkpoint: its weights are drawn from the
file's ``weight_seed``, packed once, and kept as a serving artifact in
``bench-artifacts/<config>-<key>/``; later runs boot from it, so every run
serves the same packed shapes and finds each program in the compile
cache. ``--seed`` draws the traffic.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import hashlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import check, traffic, weights, work

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
ARTIFACTS = os.path.join(ROOT, "bench-artifacts")
# served tokens the correctness sample covers at least
CHECK_TOKENS = 256


class Unavailable(Exception):
    """The machine cannot run the cell (no TPU, too few chips, no src/)."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    config: str      # the configuration's name in BENCHMARK.json
    chips: int
    conf: dict       # bench/configs/<config>.json
    mix: dict        # bench/traffic/<mix>.json
    data: dict       # bench/cells/<cell>.json
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT, bench_dir: str = BENCH_DIR
              ) -> Cell:
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[name]
    confs = {c["name"]: c for c in spec["configs"]}
    conf = load_json(os.path.join(root, confs[w["config"]]["file"]))
    return Cell(
        name=name, config=w["config"], chips=int(w["chips"]), conf=conf,
        mix=load_json(os.path.join(bench_dir, "traffic",
                                   w["traffic"] + ".json")),
        data=load_json(os.path.join(bench_dir, "cells", name + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def reader(metric: str, bench_dir: str = BENCH_DIR):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- the system under test ----------------------------------------------------

CONFIG_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
               "num_attention_heads": "n_heads",
               "num_key_value_heads": "n_kv_heads", "head_dim": "dh",
               "intermediate_size": "d_ff", "vocab_size": "vocab",
               "rope_theta": "rope_theta", "w_bits": "w_bits",
               "a_bits": "a_bits", "quant_group": "cim_alpha"}


def model_config(conf: dict, registry):
    """The program's ModelConfig for ``conf``; every size the file states
    must be the one the program runs."""
    get = (registry.get_smoke_config if conf.get("preset") == "smoke"
           else registry.get_config)
    cfg = get(conf["arch"], dtype=conf["dtype"])
    for key, attr in CONFIG_KEYS.items():
        if float(getattr(cfg, attr)) != float(conf[key]):
            raise ValueError(f"{conf['arch']}: {key}={conf[key]} in the "
                             f"configuration file, {getattr(cfg, attr)} "
                             "in the program")
    if cfg.tie_embeddings or cfg.family != "dense":
        raise ValueError(f"{conf['arch']}: the reference serves untied "
                         "dense models")
    return cfg


def pack(cfg, conf: dict, seed: int):
    """Draw the weights from ``seed`` and compress them through the
    program's ``deployed.compress``, one layer at a time."""
    from repro.serve import deployed

    g = weights.globals_(conf, seed)
    params = {"embed": g["embed"], "final_ln": g["final_ln"],
              "head": g["head"]}
    sp = deployed.compress(
        cfg, params, target_sparsity=float(conf["target_sparsity"]),
        tile=tuple(conf["tile"]),
        layer=lambda i: weights.layer(conf, seed, i))
    tiles = deployed.packed_tiles(sp)
    if tiles != [tuple(conf["tile"])]:
        raise ValueError(f"packed tiles {tiles}, configuration states "
                         f"{conf['tile']}")
    return sp


# what the packed weights are made by: the program's compression and
# serving code, and the benchmark's own weight generator
ARTIFACT_SOURCES = ("src/repro/core", "src/repro/serve", "src/repro/kernels",
                    "src/repro/models", "bench/weights.py")


def artifact_key(conf: dict, root: str = ROOT) -> str:
    """Hash of the configuration and of the source the packing depends on."""
    h = hashlib.sha256(json.dumps(conf, sort_keys=True).encode())
    for src in ARTIFACT_SOURCES:
        path = os.path.join(root, src)
        files = ([path] if path.endswith(".py") else sorted(
            glob.glob(os.path.join(path, "**", "*.py"), recursive=True)))
        for f in files:
            h.update(os.path.relpath(f, root).encode())
            with open(f, "rb") as fh:
                h.update(fh.read())
    return h.hexdigest()[:16]


def serving_params(cfg, cell: Cell, log=print):
    """The configuration's packed weights, from its serving artifact. The
    first run in a checkout draws them from ``weight_seed``, packs them
    and writes the artifact (removing any other of this configuration),
    then boots from it like every later run."""
    import jax.numpy as jnp
    from repro.serve import deployed

    conf, name = cell.conf, cell.config
    path = os.path.join(ARTIFACTS, f"{name}-{artifact_key(conf)}")
    tile = tuple(conf["tile"])
    if not deployed.load_artifact_extra(path):
        t = time.monotonic()
        sp = pack(cfg, conf, int(conf["weight_seed"]))
        log(f"pack_s={time.monotonic() - t:.3f} (weights from weight_seed "
            f"{conf['weight_seed']}, tile {conf['tile']}, sparsity "
            f"{conf['target_sparsity']})")
        for old in glob.glob(os.path.join(ARTIFACTS, f"{name}-*")):
            shutil.rmtree(old, ignore_errors=True)
        t = time.monotonic()
        # the artifact format writes bfloat16 leaves as raw bytes that its
        # loader cannot cast back: such leaves are stored widened to
        # float32 (exact) and narrowed again on boot
        wide = [f.name for f in dataclasses.fields(sp)
                if getattr(getattr(sp, f.name), "dtype", None) == jnp.bfloat16]
        sp = dataclasses.replace(sp, **{
            k: np.asarray(getattr(sp, k), np.float32) for k in wide})
        deployed.save_artifact(path, sp, cfg, extra={
            "weight_seed": conf["weight_seed"], "bfloat16_leaves": wide})
        del sp
        gc.collect()
        log(f"artifact_save_s={time.monotonic() - t:.3f} ({path})")
    t = time.monotonic()
    sp, extra = deployed.load_artifact(path, arch=cfg.name, tile=tile)
    sp = dataclasses.replace(sp, **{
        k: jnp.asarray(getattr(sp, k), jnp.bfloat16)
        for k in extra["bfloat16_leaves"]})
    log(f"artifact_load_s={time.monotonic() - t:.3f} ({path})")
    return sp


def server(cfg, sp, mix: dict, seed: int, tracer=None):
    from repro.serve import BatchConfig, BatchServer, ServeConfig

    s = mix["serving"]
    n_blocks = s["n_slots"] * -(-traffic.max_context(mix)
                                // s["block_size"]) + 1
    return BatchServer(
        cfg, sp, ServeConfig(temperature=0.0, eos_id=-1, seed=seed % 2**31),
        BatchConfig(n_slots=s["n_slots"], block_size=s["block_size"],
                    n_blocks=n_blocks, view_bucket=s["view_bucket"]),
        engine="scan", tracer=tracer)


def warm_up(srv, cfg, mix: dict) -> int:
    """Compile (or load from the cache) every shape the mix can reach and
    run each once: one served pass with a request per padded prompt
    length, then the decode step at every view. Returns the number of
    programs warmed."""
    import jax
    import jax.numpy as jnp
    from repro.serve import Request

    sh = traffic.shapes(mix)
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    # distinct tokens, so that no prompt finds another's prefix cached
    reqs = [Request(f"warm{n}", np.full(min(max(n, lo), hi), i + 1, np.int32),
                    2) for i, n in enumerate(sh["prefill_tokens"])]
    srv.run(reqs)
    s = mix["serving"]
    n, bs = s["n_slots"], s["block_size"]
    for nb in sh["view_blocks"]:
        view = (cfg.n_layers, n, nb * bs, cfg.n_kv_heads_eff, cfg.dh)
        out = srv._decode(srv._params, jnp.zeros(view, cfg.param_dtype),
                          jnp.zeros(view, cfg.param_dtype),
                          jnp.zeros((n,), jnp.int32),
                          jnp.zeros((n, 1), jnp.int32), cfg=cfg)
        jax.block_until_ready(out)
    return len(sh["prefill_tokens"]) + len(sh["view_blocks"])


class CompileCounter:
    """Backend compiles and persistent-cache loads, with their times."""

    def __init__(self):
        import jax

        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.monotonic())

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.times.append(time.monotonic())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)


@dataclasses.dataclass
class Run:
    """What a metric reader may read. Times are seconds from the window's
    start on the host's monotonic clock."""

    cell: Cell
    seed: int
    seconds: float
    setup_s: float
    window_s: float
    planned: list
    report: Any                      # repro.serve.ServeReport
    compiles_in_window: int
    peak: dict                       # bench/peaks.json row
    spans: Optional[List[tuple]] = None   # (name, start, end, args)
    device: Any = None               # bench.devtrace.DeviceTrace


def metric_values(run: Run, metrics: List[dict]) -> Dict[str, dict]:
    out = {}
    for m in metrics:
        v = reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def tracer_spans(tracer, t_window0: float) -> List[tuple]:
    """(name, start, end, args) of the program's phase spans, seconds from
    the window's start."""
    off = tracer.epoch - t_window0
    return [(e["name"], off + e["ts"] / 1e6, off + (e["ts"] + e["dur"]) / 1e6,
             e.get("args", {}))
            for e in tracer.events if e.get("ph") == "X"]


def trace_dir(cell: str) -> str:
    return os.path.join(ARTIFACTS, "trace", cell)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, log=print, require_tpu: bool = True,
             peaks_file: str = work.PEAKS_FILE) -> dict:
    """Set up, serve the window, read the metrics and check the output.
    Returns the result line's object. ``require_tpu=False`` lets a test
    drive the same run on the CPU at a small size."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Unavailable(f"needs a TPU, JAX's backend is "
                          f"{devs[0].platform!r}")
    if len(devs) < cell.chips:
        raise Unavailable(f"cell asks for {cell.chips} chips, JAX finds "
                          f"{len(devs)}")
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise Unavailable(f"the program's src/ is missing under {ROOT}")
    sys.path.insert(0, src)
    from repro.launch import compile_cache
    from repro.models import registry

    dev = devs[0]
    peak = work.peaks(dev.device_kind, peaks_file)
    log(f"compile cache: {compile_cache.enable()}")
    # keep every program, however quick to compile, so that a run after
    # the first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileCounter()
    cfg = model_config(cell.conf, registry)
    sp = serving_params(cfg, cell, log)
    tracer = None
    if trace:
        from repro.obs import Tracer
        tracer = Tracer(process_name="bench")
    t = time.monotonic()
    srv = server(cfg, sp, cell.mix, seed, tracer)
    del sp
    log(f"server_s={time.monotonic() - t:.3f}")
    t = time.monotonic()
    n0 = len(compiles.times)
    n_shapes = warm_up(srv, cfg, cell.mix)
    log(f"warm_up_s={time.monotonic() - t:.3f} shapes={n_shapes} "
        f"compiles_or_cache_loads={len(compiles.times) - n0}")
    if tracer is not None:
        tracer.clear()
    planned = traffic.generate(cell.mix, cell.data, cfg.vocab, seed, seconds)
    from repro.serve import Request
    reqs = [Request(p.rid, p.prompt, p.max_new_tokens, arrival=p.arrival)
            for p in planned]
    gc.collect()
    devtrace = None
    if trace:
        from . import devtrace as DT
        shutil.rmtree(trace_dir(cell.name), ignore_errors=True)
        DT.start(trace_dir(cell.name))
    t_w0 = time.monotonic()
    setup_s = t_w0 - t_start
    if trace:
        with DT.window_mark():
            t_w0 = time.monotonic()
            rep = srv.run(reqs)
            t_w1 = time.monotonic()
        DT.stop()
    else:
        rep = srv.run(reqs)
        t_w1 = time.monotonic()
    window_s = rep.wall_s
    n_comp = compiles.between(t_w0, t_w1)
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", -1))
    log(f"window: offered={len(planned)} finished={rep.n_requests} "
        f"tokens={rep.total_tokens} window_s={window_s:.3f} "
        f"drain_s={window_s - seconds:.3f} decode_steps={rep.n_decode_steps}"
        f" compiles_in_window={n_comp} memory_peak_bytes={mem_peak}")
    spans = None
    if trace:
        spans = tracer_spans(tracer, t_w0)
        t = time.monotonic()
        devtrace = DT.load(trace_dir(cell.name), dev.id)
        log(f"trace: read in {time.monotonic() - t:.3f}s, "
            f"{devtrace.n_ops} device ops")
    run = Run(cell=cell, seed=seed, seconds=seconds, setup_s=setup_s,
              window_s=window_s, planned=planned, report=rep,
              compiles_in_window=n_comp, peak=peak, spans=spans,
              device=devtrace)
    metrics = metric_values(run, cell.per_layer if trace else cell.end_to_end)
    outputs = rep.outputs
    breakdown = (devtrace.breakdown(spans, window_s)
                 if devtrace is not None else None)
    busy = devtrace.busy_s(0.0, window_s) if devtrace is not None else None
    del srv, rep, run, tracer
    gc.collect()
    # -- correctness, once the program's state is freed
    failed = [p.rid for p in planned
              if p.rid not in outputs
              or len(outputs[p.rid]) != p.max_new_tokens
              or not np.all((outputs[p.rid] >= 0)
                            & (outputs[p.rid] < cfg.vocab))]
    t = time.monotonic()
    picked = check.sample(planned, outputs, seed, CHECK_TOKENS)
    g = check.gaps(cell.conf, picked, outputs,
                   pad_to=traffic.max_context(cell.mix))
    limits = cell.data["limits"]
    checks = {
        "mean_logit_gap": {"value": g["served_gap_mean"],
                           "limit": float(limits["mean_logit_gap"])},
        "failed_requests": {"value": len(failed), "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    log(f"check: {len(picked)} requests, {g['checked_tokens']} served tokens "
        f"against the reference in {time.monotonic() - t:.3f}s; widest gap "
        f"{g['served_gap_max']}, off the reference's first choice "
        f"{g['served_off_share']}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    if devtrace is not None:
        device["busy_s"] = busy
        device["window_s"] = window_s
    result = {"correct": bool(correct), "attempted": len(planned),
              "failed": len(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
