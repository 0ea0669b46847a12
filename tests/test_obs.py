"""Observability layer tests.

Contracts under test:

  * the tracer is thread-safe, gives each thread its own track, and nested
    spans are properly contained in their parent's [ts, ts+dur] window;
  * the exported JSON is valid Chrome trace-event format (golden schema
    check via ``validate_chrome_trace`` - the same validator CI runs on
    emitted files);
  * the disabled path allocates nothing: NULL sinks hand back shared
    singleton no-op objects;
  * histogram percentiles interpolate correctly and snapshots validate;
  * the per-phase cycle split in ``perf_model`` sums back to the exact
    ``_layer_cycles`` totals (the gap comparator's prediction side);
  * an instrumented ``BatchServer`` emits the step-phase spans, request
    lifecycle tracks and queue-wait split - and its tokens are
    bit-identical to an un-instrumented server's.
"""
import json
import threading

import jax
import numpy as np
import pytest

from repro.core import perf_model as PM
from repro.kernels.timing import DispatchTimer
from repro.models import registry
from repro.obs import (MetricsRegistry, NULL_METRICS, NULL_TRACER, Tracer,
                       gap, history, phase_scope, trace as trace_mod,
                       validate_chrome_trace, validate_metrics_snapshot)
from repro.serve import BatchConfig, BatchServer, Request, ServeConfig
from repro.serve import deployed as DP


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_span_nesting_contained():
    tr = Tracer()
    with tr.span("outer", step=1):
        with tr.span("inner"):
            tr.instant("mark", note="x")
    ev = {e["name"]: e for e in tr.to_chrome()["traceEvents"]
          if e["ph"] in ("X", "i")}
    outer, inner = ev["outer"], ev["inner"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert outer["args"]["step"] == 1
    assert ev["mark"]["ph"] == "i"


def test_tracer_thread_safety():
    tr = Tracer()
    n_threads, n_spans = 8, 50

    def work(t):
        for i in range(n_spans):
            with tr.span(f"t{t}.s{i}"):
                pass

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    events = [e for e in tr.to_chrome()["traceEvents"] if e["ph"] == "X"]
    assert len(events) == n_threads * n_spans
    # each worker thread recorded on its own track
    assert len({e["tid"] for e in events}) == n_threads


def test_chrome_trace_schema_golden(tmp_path):
    tr = Tracer()
    with tr.span("phase", k=2):
        tr.instant("tick")
    tr.counter("pool", used=3, free=5)
    tr.complete("retro", 0.001, 0.002, track="queue", rid="r0")
    obj = tr.to_chrome()
    # golden structural facts of the trace-event format
    assert obj["displayTimeUnit"] == "ms"
    phs = {e["ph"] for e in obj["traceEvents"]}
    assert phs == {"M", "X", "i", "C"}
    for e in obj["traceEvents"]:
        assert {"name", "ph", "pid", "tid"} <= set(e)
    n = validate_chrome_trace(obj)
    assert n == len(obj["traceEvents"])
    p = tmp_path / "trace.json"
    tr.save(str(p))
    from repro.obs import validate_chrome_trace_file
    assert validate_chrome_trace_file(str(p)) == n
    # named track got a thread_name metadata record
    names = [e["args"]["name"] for e in obj["traceEvents"] if e["ph"] == "M"]
    assert "queue" in names


def test_validator_rejects_malformed():
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "X"}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"no": "events"})
    with pytest.raises(ValueError):
        validate_metrics_snapshot({"counters": {}})


def test_clear_keeps_epoch_and_tracks():
    tr = Tracer()
    t = tr.track("queue")
    with tr.span("warmup"):
        pass
    epoch = tr.epoch
    tr.clear()
    assert tr.epoch == epoch
    assert tr.track("queue") == t
    assert all(e["ph"] == "M" for e in tr.to_chrome()["traceEvents"])


# ---------------------------------------------------------------------------
# no-op fast path
# ---------------------------------------------------------------------------


def test_null_sinks_allocate_nothing():
    # disabled spans are ONE shared object, not per-call allocations
    assert NULL_TRACER.span("a") is NULL_TRACER.span("b")
    assert not NULL_TRACER.recording
    with NULL_TRACER.span("x", arg=1):
        pass
    NULL_TRACER.counter("c", v=1)
    NULL_TRACER.complete("r", 0.0, 1.0)
    assert NULL_TRACER.to_chrome()["traceEvents"] == []
    # same for metrics: one shared instrument regardless of name/labels
    assert (NULL_METRICS.counter("a") is NULL_METRICS.histogram("b", x=1))
    assert NULL_METRICS.snapshot() == {}
    # phase_scope with both sinks off returns the shared null span
    assert (phase_scope(NULL_TRACER, NULL_METRICS, "p")
            is phase_scope(NULL_TRACER, NULL_METRICS, "q", k=1))


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_histogram_percentiles():
    reg = MetricsRegistry()
    h = reg.histogram("lat", phase="x")
    for v in range(1, 101):  # 1..100
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100 and s["sum"] == pytest.approx(5050.0)
    assert s["min"] == 1.0 and s["max"] == 100.0
    assert s["p50"] == pytest.approx(50.5)  # linear interpolation
    assert s["p99"] == pytest.approx(99.01)
    snap = reg.snapshot()
    assert validate_metrics_snapshot(snap) >= 1
    assert "lat{phase=x}" in snap["histograms"]


def test_registry_memoizes_and_counts():
    reg = MetricsRegistry()
    assert reg.counter("n", k="a") is reg.counter("n", k="a")
    reg.counter("n", k="a").inc()
    reg.counter("n", k="a").inc(2)
    reg.gauge("g").set(7)
    snap = reg.snapshot()
    assert snap["counters"]["n{k=a}"] == 3
    assert snap["gauges"]["g"] == 7
    reg.clear()
    assert reg.snapshot()["counters"] == {}


# ---------------------------------------------------------------------------
# dispatch timer
# ---------------------------------------------------------------------------


def test_dispatch_timer_fences_and_groups():
    timer = DispatchTimer(enabled=True)
    import jax.numpy as jnp
    x = jnp.ones((8, 8))
    for _ in range(3):
        timer.timed("matmul", (8, 8), (4, 4), lambda a: a @ a, x)
    rows = timer.summary()
    assert len(rows) == 1
    r = rows[0]
    assert r["calls"] == 3 and r["tile"] == [4, 4]
    assert 0.0 <= r["min_ms"] <= r["p50_ms"] <= r["max_ms"]
    # disabled timer records nothing and passes the value through
    off = DispatchTimer(enabled=False)
    out = off.timed("m", None, None, lambda: 41 + 1)
    assert out == 42 and off.summary() == []


# ---------------------------------------------------------------------------
# perf-model phase split + gap comparator
# ---------------------------------------------------------------------------


def test_phase_cycles_sum_to_layer_cycles():
    hw = PM.DEFAULT_HW
    for l in PM.vgg16_cifar_layers()[:4]:
        total = l.groupsets_for(hw.group, hw.alpha)
        nnz = l.nnz_for(hw.group, hw.alpha)
        cycles, _ = PM._layer_cycles(l, nnz, total, 8, 4, True, hw=hw)
        p = PM.layer_phase_cycles(l, 8, 4, hw=hw)
        assert (max(p["compute"], p["fm"]) + p["reload"] + p["ctrl"]
                == pytest.approx(cycles))
    net = PM.network_phase_breakdown(PM.vgg16_cifar_layers()[:4], 8, 4)
    assert all(v >= 0 for v in net.values()) and net["compute"] > 0


def test_gap_report_contract():
    g = gap.gap_report(2e-6, 4e-4, predicted_phases={"a": 3.0, "b": 1.0},
                       measured_phases={"x": 0.2})
    assert g["sim_vs_measured"] == pytest.approx(200.0)
    assert g["predicted_phase_shares"] == {"a": 0.75, "b": 0.25}
    assert g["measured_phase_shares"] == {"x": 1.0}
    for bad in (0.0, float("nan"), float("inf"), -1.0):
        with pytest.raises(ValueError):
            gap.gap_report(bad, 1.0)
        with pytest.raises(ValueError):
            gap.gap_report(1.0, bad)


def test_measured_phase_shares_parses_labels():
    reg = MetricsRegistry()
    reg.histogram("serve_phase_s", phase="step.dispatch").observe(0.3)
    reg.histogram("serve_phase_s", phase="step.gather").observe(0.1)
    reg.histogram("other_metric", phase="x").observe(9.0)
    ph = gap.measured_phase_shares(reg.snapshot())
    assert ph == {"step.dispatch": pytest.approx(0.3),
                  "step.gather": pytest.approx(0.1)}


def test_measured_phase_shares_tolerates_malformed_snapshot():
    # hand-built snapshot with every malformation the parser must skip:
    # a non-dict histogram, a label block with no '=', and a non-finite sum
    snap = {"histograms": {
        "serve_phase_s{phase=good}": {"sum": 0.4, "count": 2},
        "serve_phase_s{phase=poison}": {"sum": float("nan"), "count": 1},
        "serve_phase_s{nolabels}": {"sum": 1.0, "count": 1},
        "serve_phase_s{phase=notdict}": "garbage",
        "serve_phase_s{phase=badsum}": {"sum": "NaN-ish", "count": 1},
    }}
    assert gap.measured_phase_shares(snap) == {"good": pytest.approx(0.4)}


def test_shares_drop_nonfinite_phases():
    s = gap._shares({"a": 3.0, "b": float("inf"), "c": float("nan"),
                     "d": 1.0})
    assert s == {"a": 0.75, "d": 0.25}
    assert gap._shares({"a": float("nan")}) == {}


def test_clamp_measured_guards():
    # honest samples pass through as their min
    assert gap.clamp_measured([2e-3, 5e-3]) == pytest.approx(2e-3)
    # zero-duration clock reads are floored, not propagated as 0 / raised
    assert gap.clamp_measured([0.0]) == gap.MIN_MEASURED_S
    # non-finite samples are dropped before the min
    assert gap.clamp_measured([float("nan"), 3e-3]) == pytest.approx(3e-3)
    # empty phase table / all-garbage samples is a hard error with a
    # message that names the cause, not a silent zero
    for bad in ([], [float("nan")], [float("inf")], [-1.0]):
        with pytest.raises(ValueError, match="no usable measured samples"):
            gap.clamp_measured(bad)


def test_serve_gap_zero_duration_floored(smoke_model):
    cfg, _ = smoke_model
    # a zero p50 (empty histogram quirk) must not crash gap_report with
    # "measured_s must be finite > 0" - it gets floored upstream
    g = gap.serve_gap(cfg, 0.0, 0.6)
    assert g["measured_s"] == gap.MIN_MEASURED_S
    assert np.isfinite(g["sim_vs_measured"])


def test_dispatch_timer_emits_metric_histograms():
    reg = MetricsRegistry()
    timer = DispatchTimer(enabled=True, metrics=reg)
    import jax.numpy as jnp
    x = jnp.ones((8, 8))
    for _ in range(3):
        timer.timed("matmul", (8, 8), (4, 4), lambda a: a @ a, x)
    timer.timed("gemv", (1, 8), None, lambda a: a.sum(), x)
    snap = reg.snapshot()
    validate_metrics_snapshot(snap)
    hk = [k for k in snap["histograms"] if k.startswith("kernel_dispatch_s{")]
    assert len(hk) == 2  # one labeled series per (name, shape, tile) group
    be = jax.default_backend()
    mm = snap["histograms"][
        f"kernel_dispatch_s{{backend={be},kernel=matmul,shape=8x8,tile=4x4}}"]
    assert mm["count"] == 3 and mm["sum"] > 0
    gv = snap["histograms"][
        f"kernel_dispatch_s{{backend={be},kernel=gemv,shape=1x8,tile=none}}"]
    assert gv["count"] == 1
    # a metrics-less timer still works and emits nothing
    bare = DispatchTimer(enabled=True)
    bare.timed("m", None, None, lambda: 1)
    # NULL metrics (recording=False) must not be written to either
    null_timer = DispatchTimer(enabled=True, metrics=NULL_METRICS)
    null_timer.timed("m", None, None, lambda: 1)
    assert NULL_METRICS.snapshot() == {}


# ---------------------------------------------------------------------------
# bench history (append-only JSONL + regression gate)
# ---------------------------------------------------------------------------


def _hist_row(ts, metrics, backend="cpu", arch="smoke"):
    return {"schema": history.SCHEMA_VERSION, "ts": ts, "git_sha": "abc1234",
            "backend": backend, "arch": arch, "metrics": metrics}


def test_history_append_load_round_trip(tmp_path):
    p = tmp_path / "hist.jsonl"
    r1 = history.make_row({"serve.gap": 3.0}, git_sha="s1", backend="cpu",
                          arch="smoke")
    r2 = history.make_row({"serve.gap": 3.1}, git_sha="s2", backend="cpu",
                          arch="smoke")
    history.append_row(str(p), r1)
    history.append_row(str(p), r2)
    rows = history.load_history(str(p))
    assert [r["git_sha"] for r in rows] == ["s1", "s2"]
    assert rows[0]["schema"] == history.SCHEMA_VERSION
    # appending a malformed row is refused before it hits the file
    with pytest.raises(ValueError):
        history.append_row(str(p), {"schema": "x"})
    assert len(history.load_history(str(p))) == 2


def test_history_load_rejects_malformed(tmp_path):
    p = tmp_path / "bad.jsonl"
    # line 1 is a valid row; line 2 is not JSON - the error names the line
    p.write_text(json.dumps(_hist_row("t0", {"m": 1.0})) + "\nnot json\n")
    with pytest.raises(ValueError, match="bad.jsonl:2"):
        history.load_history(str(p))
    # a structurally-bad row (valid JSON) is also rejected with its line
    p.write_text('{"schema": 1}\n')
    with pytest.raises(ValueError, match="bad.jsonl:1"):
        history.load_history(str(p))
    p2 = tmp_path / "bad2.jsonl"
    p2.write_text(json.dumps(_hist_row("t", {"m": "NaN-string"})) + "\n")
    with pytest.raises(ValueError):
        history.load_history(str(p2))


def test_history_check_needs_baseline():
    # a single row has nothing to regress against: green, no findings
    assert history.check_history([_hist_row("t0", {"serve.gap": 3.0})]) == []


def test_history_check_flags_gap_drift_and_throughput_drop():
    rows = [
        _hist_row("t0", {"serve.gap": 3.0, "serve.s.tokens_per_s": 50.0}),
        _hist_row("t1", {"serve.gap": 3.2, "serve.s.tokens_per_s": 52.0}),
        _hist_row("t2", {"serve.gap": 30.0, "serve.s.tokens_per_s": 5.0}),
    ]
    kinds = {f["kind"] for f in history.check_history(rows)}
    assert kinds == {"gap-drift", "throughput-drop"}
    # drift fires in BOTH directions (a 10x better gap is also suspicious)
    rows[2]["metrics"] = {"serve.gap": 0.1, "serve.s.tokens_per_s": 52.0}
    assert [f["kind"] for f in history.check_history(rows)] == ["gap-drift"]
    # within tolerance: green
    rows[2]["metrics"] = {"serve.gap": 3.4, "serve.s.tokens_per_s": 48.0}
    assert history.check_history(rows) == []


def test_history_check_groups_by_backend_and_arch():
    # a cpu baseline must never judge a tpu row
    rows = [
        _hist_row("t0", {"serve.gap": 3.0}, backend="cpu"),
        _hist_row("t1", {"serve.gap": 3.0}, backend="cpu"),
        _hist_row("t2", {"serve.gap": 300.0}, backend="tpu"),
    ]
    assert history.check_history(rows) == []


def test_history_flatten_bench_reports():
    sched = {"vgg16_w8a8": {
        "fps_searched": 100.0,
        "sim_vs_measured": {"sim_vs_measured": 60.0,
                            "post_refit": {"gap": 0.7}}}}
    m = history.flatten_sched(sched)
    assert m == {"sched.vgg16_w8a8.gap": 60.0,
                 "sched.vgg16_w8a8.gap_post_refit": 0.7,
                 "sched.vgg16_w8a8.fps_searched": 100.0}
    serve = {"arch": "yi-6b",
             "sim_vs_measured": {"sim_vs_measured": 3.0},
             "sharded": {"sim_vs_measured": {"sim_vs_measured": 5.0}},
             "scan": {"tokens_per_s": 400.0}}
    s = history.flatten_serve(serve)
    assert s["serve.gap"] == 3.0
    assert s["serve.sharded.gap"] == 5.0
    assert s["serve.scan.tokens_per_s"] == 400.0


def test_history_tracks_spec_acceptance():
    serve = {"arch": "yi-6b",
             "spec": {"tokens_per_s": 300.0},
             "spec_vs_scan": {"acceptance_rate": 0.62,
                              "tokens_per_s_spec": 300.0}}
    s = history.flatten_serve(serve)
    assert s["serve.spec.tokens_per_s"] == 300.0
    assert s["serve.spec_vs_scan.acceptance_rate"] == 0.62
    # acceptance gates like throughput: a large relative drop regresses
    rows = [history.make_row(
        {"serve.spec_vs_scan.acceptance_rate": v}, backend="cpu",
        arch="yi-6b") for v in (0.6, 0.6, 0.1)]
    findings = history.check_history(rows)
    assert [f["metric"] for f in findings] == \
        ["serve.spec_vs_scan.acceptance_rate"]
    assert findings[0]["kind"] == "throughput-drop"
    assert not history.check_history(rows[:2] + [
        history.make_row({"serve.spec_vs_scan.acceptance_rate": 0.55},
                         backend="cpu", arch="yi-6b")])


def test_history_cli_end_to_end(tmp_path, capsys):
    p = tmp_path / "h.jsonl"
    sched_p = tmp_path / "BENCH_sched.json"
    sched_p.write_text(json.dumps({"net_w8a8": {
        "fps_searched": 10.0,
        "sim_vs_measured": {"sim_vs_measured": 50.0,
                            "post_refit": {"gap": 0.9}}}}))
    args = ["append", "--out", str(p), "--sched", str(sched_p),
            "--sha", "deadbee", "--backend", "cpu", "--arch", "bench"]
    history.main(args)  # returns without raising on success
    history.main(args)
    capsys.readouterr()
    history.main(["check", str(p)])
    assert "no regressions" in capsys.readouterr().out
    # inject a regression: check exits 1, --warn-only exits 0
    bad = history.make_row({"sched.net_w8a8.gap": 5000.0,
                            "sched.net_w8a8.fps_searched": 1.0},
                           git_sha="bad", backend="cpu", arch="bench")
    history.append_row(str(p), bad)
    with pytest.raises(SystemExit) as ei:
        history.main(["check", str(p)])
    assert ei.value.code == 1
    capsys.readouterr()
    history.main(["check", str(p), "--warn-only"])  # warn-only: no exit
    assert "REGRESSION" in capsys.readouterr().out
    # malformed history hard-fails with exit 2 even under --warn-only
    badfile = tmp_path / "corrupt.jsonl"
    badfile.write_text("not json\n")
    with pytest.raises(SystemExit) as ei:
        history.main(["check", str(badfile), "--warn-only"])
    assert ei.value.code == 2


# ---------------------------------------------------------------------------
# instrumented server smoke
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_model():
    cfg = registry.get_smoke_config("yi-6b", dtype="float32")
    params = registry.model_fns(cfg).init_params(cfg, jax.random.PRNGKey(0))
    return cfg, DP.from_params(cfg, params)


def _reqs(cfg, n=4, seed=5):
    rng = np.random.default_rng(seed)
    return [Request(f"r{i}", rng.integers(0, cfg.vocab,
                                          int(rng.integers(2, 10))),
                    int(rng.integers(1, 6))) for i in range(n)]


def test_batchserver_instrumented_smoke(smoke_model, tmp_path):
    cfg, sp = smoke_model
    tr, mr = Tracer(), MetricsRegistry()
    srv = BatchServer(cfg, sp, ServeConfig(),
                      BatchConfig(n_slots=2, block_size=4, n_blocks=32),
                      tracer=tr, metrics=mr)
    rep = srv.run(_reqs(cfg))

    obj = tr.to_chrome()
    validate_chrome_trace(obj)
    names = {e["name"] for e in obj["traceEvents"]}
    for phase in ("step.admit", "prefill", "decode_step", "step.gather",
                  "step.dispatch", "step.sample", "step.writeback"):
        assert phase in names, f"missing phase span {phase}"
    # per-request lifecycle spans landed on the queue/slot tracks
    assert any(n.startswith("queued:") for n in names)
    assert any(n.startswith("req:") for n in names)

    j = rep.to_json()
    # queue wait is split out of TTFT: queue + service ~= ttft per request
    assert len(rep.queue_wait_s) == j["n_requests"]
    for t, w in zip(rep.ttft_s, rep.queue_wait_s):
        assert 0.0 <= w <= t + 1e-9
    assert "queue_wait" in j and "ttft_service" in j
    assert (j["queue_wait"]["p50"] + j["ttft_service"]["p50"]
            <= j["ttft"]["p99"] + j["ttft"]["p50"])

    snap = j["metrics"]
    validate_metrics_snapshot(snap)
    assert snap["counters"]["requests_finished"] == j["n_requests"]
    assert any(k.startswith("serve_phase_s{") for k in snap["histograms"])
    assert 0.0 <= snap["gauges"]["kv_utilization"] <= 1.0
    # the decode dispatch is timed by its step.dispatch span alone
    assert "kernel_dispatch" not in snap
    assert not any(k.startswith("kernel_dispatch_s{")
                   for k in snap["histograms"])

    # tokens identical to an un-instrumented server (observability is
    # read-only: it must never perturb the decode stream)
    ref = BatchServer(cfg, sp, ServeConfig(),
                      BatchConfig(n_slots=2, block_size=4, n_blocks=32))
    ref_rep = ref.run(_reqs(cfg))
    assert ref_rep.metrics is None and "metrics" not in ref_rep.to_json()
    for rid in rep.outputs:
        np.testing.assert_array_equal(rep.outputs[rid], ref_rep.outputs[rid])


def test_serve_gap_from_instrumented_run(smoke_model):
    cfg, sp = smoke_model
    mr = MetricsRegistry()
    srv = BatchServer(cfg, sp, ServeConfig(),
                      BatchConfig(n_slots=2, block_size=4, n_blocks=32),
                      metrics=mr)
    srv.run(_reqs(cfg, n=3))
    snap = mr.snapshot()
    step = snap["histograms"]["serve_phase_s{phase=decode_step}"]
    g = gap.serve_gap(cfg, float(step["p50"]), 0.6,
                      measured_phases=gap.measured_phase_shares(snap))
    assert np.isfinite(g["sim_vs_measured"]) and g["sim_vs_measured"] > 0
    assert set(g["predicted_phase_shares"]) == {"compute", "reload", "fm",
                                                "stall"}
    # each share is rounded to 4 decimals, so the sum can drift by up to
    # 5e-5 per phase off exactly 1.0
    shares = g["measured_phase_shares"]
    assert abs(sum(shares.values()) - 1.0) < 5e-5 * max(len(shares), 1)
