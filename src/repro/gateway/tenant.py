"""Tenant bindings: an offline artifact compiled into a serving runtime.

A tenant is one model behind the gateway: a PR 4 serving artifact (uniform
BSR packing, optionally two-tier) bound to the compiled scan runtime
(``serve.stacked``), plus the policy the gateway prices it by - priority,
SLO targets, a token-rate quota, and the sparsity the admission simulator
prices its requests at.

Hot-swap contract (the "pack once, swap without recompiling" promise of
the artifact flow):

  * **in-place** - the incoming packing's stacked envelope has the SAME
    treedef and leaf shapes/dtypes as the serving one and the ModelConfig
    is equal. The new weights are handed to the SAME jitted callables;
    jax's jit cache is keyed on (treedef, shapes, dtypes, statics), so the
    next step is a cache hit - zero recompiles, verified by the tenant's
    :class:`CompileCounter`.
  * **staged** - anything else with the same KV geometry (e.g. a different
    uniform tile): the runtime re-stacks and re-jits; the next step traces
    fresh kernels, and the swap report says so explicitly.
  * **rejected** - a packing whose KV geometry (n_layers, KV heads, head
    dim, dtype) differs from the serving one can never share the
    gateway's block pool and raises instead of swapping.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np

from ..models.config import ModelConfig
from ..serve import deployed, stacked


@dataclasses.dataclass(frozen=True)
class TenantSLO:
    """Per-tenant service objectives the admission controller gates on.

    ``ttft_ms`` / ``tpot_ms`` are latency targets (p50, reported as
    attainment fractions); ``token_rate`` is an admission quota in
    tokens/s - a tenant over it has its requests DEFERRED (smoothed),
    never shed. All fields are optional: None means no target."""

    ttft_ms: Optional[float] = None
    tpot_ms: Optional[float] = None
    token_rate: Optional[float] = None

    def __post_init__(self):
        if self.token_rate is not None and self.token_rate <= 0:
            raise ValueError(f"token_rate must be > 0, got {self.token_rate}")

    def to_json(self) -> dict:
        return {k: v for k, v in dataclasses.asdict(self).items()
                if v is not None}

    @classmethod
    def from_json(cls, obj: Optional[dict]) -> "TenantSLO":
        obj = obj or {}
        known = {f.name for f in dataclasses.fields(cls)}
        bad = set(obj) - known
        if bad:
            raise ValueError(f"unknown SLO field(s) {sorted(bad)} - "
                             f"expected {sorted(known)}")
        return cls(**obj)


class CompileCounter:
    """Counts TRACES of a tenant's jitted serving fns.

    The increment lives inside the traced function, so it runs only when
    jax actually traces (first call per shape/static combination) - a jit
    cache hit leaves it untouched. This is the evidence the hot-swap
    contract is judged by: an in-place swap followed by warm-shape steps
    must leave ``n`` unchanged."""

    __slots__ = ("n",)

    def __init__(self):
        self.n = 0


def _counted(fn, counter: CompileCounter):
    def wrapped(params, *args, cfg):
        counter.n += 1  # trace-time only: retraces are what we count
        return fn(params, *args, cfg=cfg)
    return wrapped


def envelope_signature(params) -> Tuple:
    """(treedef, ((shape, dtype), ...)) of a stacked envelope - equality
    of two signatures is exactly the jit-cache-hit condition for the
    weight argument."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    return (treedef, tuple((getattr(l, "shape", ()),
                            str(getattr(l, "dtype", type(l).__name__)))
                           for l in leaves))


def kv_geometry(cfg: ModelConfig) -> Tuple:
    """The block-pool shape a config demands: every tenant behind one
    shared :class:`~repro.serve.batching.PagedKVCache` must agree on it."""
    return (cfg.n_layers, cfg.n_kv_heads_eff, cfg.dh,
            str(np.dtype(cfg.param_dtype)))


class TenantRuntime:
    """One tenant's compiled serving state + swap machinery."""

    def __init__(self, name: str, cfg: ModelConfig,
                 sp: deployed.ServingParams, priority: int = 0,
                 slo: Optional[TenantSLO] = None, sparsity: float = 0.0,
                 artifact: str = ""):
        if not name:
            raise ValueError("tenant needs a non-empty name")
        deployed._check_family(cfg)
        self.name = name
        self.priority = int(priority)
        self.slo = slo if slo is not None else TenantSLO()
        self.sparsity = float(sparsity)
        self.artifact = artifact
        self.compiles = CompileCounter()
        self.swaps: List[dict] = []
        self._bind(cfg, sp)

    def _bind(self, cfg: ModelConfig, sp: deployed.ServingParams) -> None:
        tiles = deployed.packed_tiles(sp)
        if len(tiles) > 1:
            raise ValueError(
                f"tenant {self.name!r}: packing is non-uniform ({tiles}) - "
                "the gateway serves the stacked scan runtime, which needs "
                "one (bk, bn) for the whole network (pack with "
                "uniform=True)")
        self.cfg = cfg
        self.sp = sp
        self.tile = tiles[0] if tiles else None
        self.params = stacked.stack(sp)
        self._signature = envelope_signature(self.params)
        self._jit()

    def _jit(self) -> None:
        c = self.compiles
        self._prefill = stacked.serving_jit(_counted(stacked.prefill_last, c),
                                            static_argnames=("cfg",))
        self._decode = stacked.serving_jit(_counted(stacked.decode_step_paged, c),
                                           static_argnames=("cfg",))
        self._verify = stacked.serving_jit(_counted(stacked.verify_step, c),
                                           static_argnames=("cfg",))

    @property
    def kv_geometry(self) -> Tuple:
        return kv_geometry(self.cfg)

    def hot_swap(self, sp_new: deployed.ServingParams,
                 cfg_new: Optional[ModelConfig] = None) -> dict:
        """Swap this tenant's weights; returns the swap report
        (mode=inplace|staged, tile, compile count at swap time).

        See the module docstring for the in-place / staged / rejected
        contract. The gateway applies swaps BETWEEN steps, so in-flight
        decode rounds always finish on the packing they started on."""
        cfg_new = cfg_new if cfg_new is not None else self.cfg
        if kv_geometry(cfg_new) != self.kv_geometry:
            raise ValueError(
                f"tenant {self.name!r}: hot-swap KV geometry mismatch - "
                f"serving {self.kv_geometry}, incoming "
                f"{kv_geometry(cfg_new)}; the shared block pool cannot be "
                "reshaped mid-run (boot a new gateway for this artifact)")
        params_new = stacked.stack(sp_new)
        inplace = (cfg_new == self.cfg
                   and envelope_signature(params_new) == self._signature)
        if inplace:
            # same treedef + shapes + statics: handing the new arrays to
            # the SAME jitted callables is a jit cache hit by construction
            self.sp = sp_new
            self.params = params_new
        else:
            self._bind(cfg_new, sp_new)
        report = {
            "tenant": self.name,
            "mode": "inplace" if inplace else "staged",
            "tile": list(self.tile) if self.tile else None,
            "compiles_at_swap": int(self.compiles.n),
        }
        self.swaps.append(report)
        return report


class TenantRegistry:
    """Ordered name -> :class:`TenantRuntime` map behind one gateway."""

    def __init__(self, tenants: List[TenantRuntime]):
        if not tenants:
            raise ValueError("gateway needs at least one tenant")
        self._tenants: Dict[str, TenantRuntime] = {}
        for t in tenants:
            if t.name in self._tenants:
                raise ValueError(f"duplicate tenant name {t.name!r}")
            self._tenants[t.name] = t
        geo = {t.name: t.kv_geometry for t in tenants}
        if len(set(geo.values())) > 1:
            raise ValueError(
                "tenants cannot share one KV block pool: geometries "
                "(n_layers, kv_heads, dh, dtype) differ - " +
                "; ".join(f"{n}={g}" for n, g in geo.items()))

    def __getitem__(self, name: str) -> TenantRuntime:
        try:
            return self._tenants[name]
        except KeyError:
            raise KeyError(
                f"unknown tenant {name!r} - gateway serves "
                f"{sorted(self._tenants)}") from None

    def __contains__(self, name: str) -> bool:
        return name in self._tenants

    def __iter__(self) -> Iterator[TenantRuntime]:
        return iter(self._tenants.values())

    def __len__(self) -> int:
        return len(self._tenants)

    @property
    def names(self) -> List[str]:
        return list(self._tenants)

    def hot_swap(self, name: str, sp_new: deployed.ServingParams,
                 cfg_new: Optional[ModelConfig] = None) -> dict:
        return self[name].hot_swap(sp_new, cfg_new)
