"""Measured-latency tile autotuner: close the search loop on the stopwatch.

``search_mapping`` ranks (group, alpha) tiles by SIMULATED cycles on the
modeled MARS fabric - but the tile is also the Pallas BSR kernel's block
shape, and what a block shape costs on the backend actually serving the
model (CPU interpret in CI, TPU in deployment) is not what it costs on the
modeled 28 nm fabric. CIM-Tuner's answer, reproduced here: keep the
analytic search as the PROPOSER, then time the top-N proposals through the
real ``bsr_matmul_stacked`` kernels - prefill and decode row counts, fenced
with :class:`~repro.kernels.timing.DispatchTimer` - and let measured wall
clock pick the winner. The simulated pick is always in the shortlist, so
the measured winner is never slower than it on the timed workload.

Measurements are expensive (each candidate packs + dispatches every
distinct projection shape), so results persist as an :class:`AutotuneCache`
keyed by (arch, projection shapes, backend) inside the PR 4 serving
artifact's manifest - a booted artifact reuses the measurement instead of
re-timing, and a backend change (cache taken on TPU, booted on CPU) misses
the key and falls back to the simulated tile rather than trusting a stale
clock. The per-sample (phase cycles, measured seconds) pairs feed
``perf_model.fit_cycle_constants`` so the simulator's constants track the
machine (``refit_from_table``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core import perf_model as PM
from ..core.deploy import bm_for_rows
from ..core.perf_model import DEFAULT_HW, HardwareConfig
from ..core.sparsity import prune_mask_2d
from ..kernels import ops
from ..kernels.timing import DispatchTimer
from .graph import lm_graph
from .search import SearchResult, search_mapping

CACHE_SCHEMA = 1


# ---------------------------------------------------------------------------
# Workload signature: the shapes a serving config actually dispatches
# ---------------------------------------------------------------------------


def projection_shapes(cfg) -> List[Tuple[int, int, int]]:
    """Distinct CIM projection shapes of ``cfg`` as (d_in, d_out, count),
    sorted - the workload signature the autotuner times and keys on.
    Counts aggregate identical shapes across blocks (timing one and
    weighting by count, instead of re-timing the same matmul L times)."""
    counts: Dict[Tuple[int, int], int] = {}
    for node in lm_graph(cfg, seq_len=1).nodes.values():
        l = node.layer
        key = (l.kh * l.kw * l.cin, l.cout)
        counts[key] = counts.get(key, 0) + 1
    return sorted((d_in, d_out, n) for (d_in, d_out), n in counts.items())


def autotune_key(cfg, backend: Optional[str] = None) -> str:
    """Cache key: arch | backend | shape signature. The backend is part of
    the key on purpose - a wall-clock ranking taken on one backend says
    nothing about another, so a mismatch must read as a MISS."""
    import jax

    backend = backend or jax.default_backend()
    shapes = ";".join(f"{i}x{o}x{n}" for i, o, n in projection_shapes(cfg))
    return f"{cfg.name}|{backend}|{shapes}"


# ---------------------------------------------------------------------------
# Measurement: one tile through the real stacked kernel, fenced
# ---------------------------------------------------------------------------


def _stack_packs(packs: List[dict]) -> Tuple:
    """Stack per-layer ``pack_for_kernel`` dicts into the uniform-envelope
    arrays ``bsr_matmul_stacked`` takes, padding to the widest nnz_max
    (padding blocks are zero -> mathematically inert)."""
    nnz_max = max(int(p["row_idx"].shape[1]) for p in packs)

    def pad(a, width):
        a = np.asarray(a)
        if a.shape[1] == width:
            return a
        pads = [(0, 0)] * a.ndim
        pads[1] = (0, width - a.shape[1])
        return np.pad(a, pads)

    import jax.numpy as jnp

    blocks = jnp.asarray(np.stack([pad(p["blocks"], nnz_max) for p in packs]))
    scales = jnp.asarray(np.stack([pad(p["scales"], nnz_max) for p in packs]))
    row_idx = jnp.asarray(np.stack([pad(p["row_idx"], nnz_max) for p in packs]))
    nnz = jnp.asarray(np.stack([np.asarray(p["nnz"]) for p in packs]))
    return blocks, scales, row_idx, nnz


def measure_tile(shapes: Sequence[Tuple[int, int, int]],
                 tile: Tuple[int, int], sparsity: float,
                 w_bits: int = 8, a_bits: int = 8,
                 prefill_rows: int = 32, decode_rows: int = 4,
                 repeats: int = 2, stack_layers: int = 2,
                 timer: Optional[DispatchTimer] = None,
                 hw: HardwareConfig = DEFAULT_HW) -> dict:
    """Fenced wall clock of ONE candidate tile over a workload signature.

    For every (d_in, d_out, count) shape, packs ``stack_layers`` synthetic
    pruned weights into a uniform envelope and dispatches the real
    ``bsr_matmul_stacked`` kernel at prefill and decode row counts; the
    first dispatch per shape is compile/trace and is excluded. Returns a
    JSON-ready row: count-weighted prefill/decode/total seconds plus the
    per-sample (phase cycles, measured seconds) pairs the cost-constant
    re-fit consumes."""
    import jax
    import jax.numpy as jnp

    bk, bn = int(tile[0]), int(tile[1])
    timer = timer if timer is not None else DispatchTimer(enabled=True)
    hw_t = dataclasses.replace(hw, group=bk, alpha=bn)
    rng = np.random.default_rng(0)
    layer0 = jnp.asarray(0, jnp.int32)
    prefill_s = decode_s = 0.0
    samples: List[dict] = []
    for d_in, d_out, count in shapes:
        packs = []
        for _ in range(max(stack_layers, 1)):
            w = rng.standard_normal((d_in, d_out)).astype(np.float32) * 0.05
            if sparsity > 0:
                w = w * np.asarray(prune_mask_2d(jnp.asarray(w), bk, bn,
                                                 sparsity))
            packs.append(ops.pack_for_kernel(w, bits=w_bits, bk=bk, bn=bn))
        stacked = _stack_packs(packs)
        for phase, rows in (("prefill", prefill_rows), ("decode", decode_rows)):
            x = jnp.asarray(
                rng.standard_normal((rows, d_in)).astype(np.float32))
            bm = bm_for_rows(rows)
            args = (x, *stacked, layer0)
            # warm call outside the timer: trace + compile, not dispatch
            jax.block_until_ready(ops.bsr_matmul_stacked(*args, bm=bm))
            best = None
            for _ in range(max(repeats, 1)):
                n_before = len(timer.records)
                timer.timed(f"autotune.{phase}", (rows, d_in, d_out),
                            (bk, bn), ops.bsr_matmul_stacked, *args, bm=bm)
                s = timer.records[n_before].seconds
                best = s if best is None else min(best, s)
            best = max(best, 1e-9)
            if phase == "prefill":
                prefill_s += best * count
            else:
                decode_s += best * count
            layer = PM.ConvLayer(1, 1, d_in, d_out, 1, rows, sparsity)
            samples.append({
                "shape": [rows, d_in, d_out],
                "phases": PM.layer_phase_cycles(layer, w_bits, a_bits,
                                                hw=hw_t),
                "measured_s": best,
            })
    return {
        "tile": [bk, bn],
        "backend": jax.default_backend(),
        "prefill_s": prefill_s,
        "decode_s": decode_s,
        "total_s": prefill_s + decode_s,
        "samples": samples,
    }


def refit_from_table(table: Sequence[dict],
                     hw: HardwareConfig = DEFAULT_HW) -> PM.RefitResult:
    """Cost-constant re-fit over every (phases, measured_s) sample a
    ``measure_tile`` table collected."""
    samples = [(s["phases"], s["measured_s"])
               for row in table for s in row.get("samples", ())]
    return PM.fit_cycle_constants(samples, hw=hw)


# ---------------------------------------------------------------------------
# Cache: measurements persist inside the serving artifact manifest
# ---------------------------------------------------------------------------


class AutotuneCache:
    """Per-(arch, shapes, backend) measured-tile store, JSON round-trippable
    through the serving artifact's ``extra`` manifest slot."""

    def __init__(self, entries: Optional[Dict[str, dict]] = None):
        self.entries: Dict[str, dict] = dict(entries or {})

    def get(self, key: str) -> Optional[dict]:
        return self.entries.get(key)

    def put(self, key: str, result: "AutotuneResult") -> None:
        self.entries[key] = {
            "backend": result.backend,
            "best_tile": list(result.best_tile),
            "simulated_tile": list(result.simulated_tile),
            "table": [{k: v for k, v in row.items() if k != "samples"}
                      for row in result.table],
        }

    def to_json(self) -> dict:
        return {"schema": CACHE_SCHEMA, "entries": self.entries}

    @classmethod
    def from_json(cls, obj) -> "AutotuneCache":
        if not isinstance(obj, dict) or "entries" not in obj:
            raise ValueError(f"autotune cache: malformed payload {type(obj)}")
        if obj.get("schema") != CACHE_SCHEMA:
            raise ValueError(
                f"autotune cache: schema {obj.get('schema')!r} != {CACHE_SCHEMA}")
        entries = obj["entries"]
        if not isinstance(entries, dict):
            raise ValueError("autotune cache: entries is not a mapping")
        for key, e in entries.items():
            tile = e.get("best_tile") if isinstance(e, dict) else None
            if (not isinstance(tile, (list, tuple)) or len(tile) != 2
                    or not all(isinstance(t, int) and t > 0 for t in tile)):
                raise ValueError(f"autotune cache: entry {key!r} has bad "
                                 f"best_tile {tile!r}")
        return cls(entries)


# ---------------------------------------------------------------------------
# The autotuner
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class AutotuneResult:
    """Outcome of one autotune pass. ``best_tile`` is what to pack with;
    ``table`` holds the measured rows (empty on a cache hit or when
    measurement was disabled and the simulated tile won by default)."""

    best_tile: Tuple[int, int]
    simulated_tile: Tuple[int, int]
    table: List[dict]
    cache_hit: bool
    key: str
    backend: str

    def to_json(self) -> dict:
        return {
            "best_tile": list(self.best_tile),
            "simulated_tile": list(self.simulated_tile),
            "cache_hit": self.cache_hit,
            "backend": self.backend,
            "table": [{k: v for k, v in row.items() if k != "samples"}
                      for row in self.table],
        }


def autotune(cfg, top_n: int = 3, *, target_sparsity: float = 0.6,
             groups: Sequence[int] = (8, 16, 32),
             alphas: Sequence[int] = (8, 16, 32),
             seq_len: int = 128, prefill_rows: int = 32,
             decode_rows: int = 4, repeats: int = 2, stack_layers: int = 2,
             hw: HardwareConfig = DEFAULT_HW,
             cache: Optional[AutotuneCache] = None,
             timer: Optional[DispatchTimer] = None,
             allow_measure: bool = True,
             search: Optional[SearchResult] = None) -> AutotuneResult:
    """Pick the serving tile by measured wall clock.

    Runs the uniform-envelope mapping search (unless a ``search`` result is
    passed in), shortlists its top-``top_n`` tiles by simulated FPS, times
    each through the real stacked BSR kernels and returns the measured
    winner. A populated ``cache`` short-circuits the measurement entirely
    (cache HIT); with ``allow_measure=False`` a MISS falls back to the
    simulated tile instead of timing (the offline / wrong-backend path)."""
    import jax

    backend = jax.default_backend()
    key = autotune_key(cfg, backend)
    if search is None:
        graph = lm_graph(cfg, seq_len=seq_len, sparsity_gs=target_sparsity)
        search = search_mapping(graph, hw, cfg.w_bits, cfg.a_bits,
                                groups=groups, alphas=alphas, uniform=True,
                                quantum=ops.tile_quantum())
    sim_tile = search.best.candidate.tile
    if cache is not None:
        hit = cache.get(key)
        if hit is not None:
            return AutotuneResult(tuple(hit["best_tile"]), sim_tile,
                                  [], True, key, backend)
    if not allow_measure:
        return AutotuneResult(sim_tile, sim_tile, [], False, key, backend)

    shapes = projection_shapes(cfg)
    ranked = sorted(search.table, key=lambda r: r.fps, reverse=True)
    seen: set = set()
    shortlist = []
    for r in ranked:
        if r.candidate.tile not in seen:
            seen.add(r.candidate.tile)
            shortlist.append(r)
        if len(shortlist) >= max(top_n, 1):
            break
    table = []
    for r in shortlist:
        row = measure_tile(shapes, r.candidate.tile, target_sparsity,
                           w_bits=cfg.w_bits, a_bits=cfg.a_bits,
                           prefill_rows=prefill_rows,
                           decode_rows=decode_rows, repeats=repeats,
                           stack_layers=stack_layers, timer=timer, hw=hw)
        row["sim_fps"] = round(r.fps, 2)
        row["sim_cycles"] = round(r.cycles, 1)
        table.append(row)
    best = min(table, key=lambda row: row["total_s"])
    result = AutotuneResult(tuple(best["tile"]), sim_tile, table, False,
                            key, backend)
    if cache is not None:
        cache.put(key, result)
    return result
