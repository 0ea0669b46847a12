"""repro.obs - observability for the serving stack.

Three dependency-free cores:

  * :mod:`trace` - thread-safe span tracer (context-manager spans, instant
    events, counter samples, per-thread tracks) exporting Chrome
    trace-event JSON loadable in Perfetto / ``chrome://tracing``;
  * :mod:`metrics` - labeled counter / gauge / histogram registry with
    JSON snapshots;
  * :mod:`gap` - the CIMinus/CIM-Tuner loop: measured per-phase timings
    confronted with ``core.perf_model`` / the ``repro.sched`` simulator's
    predictions, emitting the ``sim_vs_measured`` ratio the benchmarks
    regression-track;
  * :mod:`history` - append-only JSONL bench history keyed by (git sha,
    backend, arch) with the tolerance-band regression gate CI runs
    (``python -m repro.obs.history``).

Everything is disabled-by-default at near-zero cost: :data:`NULL_TRACER`
and :data:`NULL_METRICS` are shared no-op singletons (zero allocation on
the hot path), so an un-instrumented ``BatchServer`` pays only a handful
of attribute calls per step. ``repro.kernels.timing`` is the companion
fenced-dispatch hook for per-(shape, tile, backend) kernel wall times.
"""
from __future__ import annotations

import time

from . import gap, history, metrics, trace  # noqa: F401
from .metrics import (MetricsRegistry, NullMetricsRegistry,  # noqa: F401
                      NULL_METRICS, ScopedMetrics,
                      validate_metrics_snapshot)
from .trace import (NullTracer, NULL_TRACER, Tracer,  # noqa: F401
                    validate_chrome_trace, validate_chrome_trace_file)


class _PhaseScope:
    """Span + phase-latency histogram + profiler annotation in one context
    manager."""

    __slots__ = ("_tracer", "_metrics", "_name", "_args", "_span", "_t0",
                 "_annotation")

    def __init__(self, tracer, metrics_reg, name, args):
        self._tracer = tracer
        self._metrics = metrics_reg
        self._name = name
        self._args = args

    def __enter__(self) -> "_PhaseScope":
        # jax only when a phase records: the tracer stays stdlib-only
        from jax import profiler

        scalars = {k: v for k, v in self._args.items()
                   if isinstance(v, (bool, int, float, str))}
        if "step" in scalars:
            step = scalars.pop("step")
            self._annotation = profiler.StepTraceAnnotation(
                self._name, step_num=step, **scalars)
        else:
            self._annotation = profiler.TraceAnnotation(self._name, **scalars)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        self._span = self._tracer.span(self._name, **self._args)
        self._span.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._span.__exit__(*exc)
        self._metrics.histogram("serve_phase_s", phase=self._name).observe(
            time.perf_counter() - self._t0)
        self._annotation.__exit__(*exc)


def phase_scope(tracer, metrics_reg, name: str, **args):
    """One instrumented phase: a tracer span, a
    ``serve_phase_s{phase=name}`` histogram observation, and a
    ``jax.profiler.TraceAnnotation`` carrying the scalar args, so a
    profiler session shows the phase on the device trace's own clock. A
    phase with a ``step`` arg is a ``StepTraceAnnotation`` numbered by it.
    With both sinks disabled this returns the shared no-op span - the
    zero-cost path."""
    if not (tracer.recording or metrics_reg.recording):
        return trace._NULL_SPAN
    return _PhaseScope(tracer, metrics_reg, name, args)


__all__ = [
    "MetricsRegistry", "NullMetricsRegistry", "NULL_METRICS",
    "ScopedMetrics", "NullTracer", "NULL_TRACER", "Tracer",
    "gap", "history", "metrics", "phase_scope", "trace",
    "validate_chrome_trace", "validate_chrome_trace_file",
    "validate_metrics_snapshot",
]
