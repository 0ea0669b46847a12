"""The readers of the program's KV and token-gap spans, on hand-built spans
with known answers and on the spans of a served smoke run."""
import types

import jax
import numpy as np
import pytest

from bench import harness, spans as S

KV = ("kv_host_gather_ms", "kv_h2d_ms", "kv_h2d_mb_per_step",
      "kv_h2d_useful_share")
TAIL = ("itl_tail_gather_share", "itl_tail_prefill_share")


def _run(spans):
    return types.SimpleNamespace(spans=spans)


def _h2d(s, e, tensor, nbytes, useful):
    return ("kv.h2d", s, e, {"tensor": tensor, "bytes": nbytes,
                             "useful_bytes": useful})


# two decode steps, then a prefix-cache suffix prefill whose gather lies
# outside any step.gather and must not count
KV_SPANS = [
    ("decode_step", 1.0, 1.2, {}),
    ("step.gather", 1.0, 1.1, {}),
    ("kv.tables", 1.0, 1.01, {}),
    ("kv.gather", 1.01, 1.03, {"tensor": "k"}),
    _h2d(1.03, 1.04, "k", 100, 40),
    ("kv.gather", 1.04, 1.06, {"tensor": "v"}),
    _h2d(1.06, 1.08, "v", 100, 40),
    ("decode_step", 2.0, 2.3, {}),
    ("step.gather", 2.0, 2.2, {}),
    ("kv.gather", 2.0, 2.05, {"tensor": "k"}),
    _h2d(2.05, 2.1, "k", 300, 60),
    ("kv.gather", 2.1, 2.15, {"tensor": "v"}),
    _h2d(2.15, 2.2, "v", 300, 60),
    ("prefill", 3.0, 3.5, {"rid": "x"}),
    ("kv.gather", 3.1, 3.4, {"tensor": "k"}),
    _h2d(3.4, 3.45, "k", 10_000, 10_000),
]


def test_kv_metrics_count_only_the_decode_steps():
    run = _run(KV_SPANS)
    got = {m: harness.reader(m)(run) for m in KV}
    assert got["kv_host_gather_ms"] == pytest.approx(70.0)  # (40 + 100) / 2
    assert got["kv_h2d_ms"] == pytest.approx(65.0)  # (30 + 100) / 2
    assert got["kv_h2d_mb_per_step"] == pytest.approx(400e-6)
    assert got["kv_h2d_useful_share"] == pytest.approx(25.0)  # 200 / 800


def _step(s, e, rids, gather_end):
    return [("decode_step", s, e, {"rids": rids}),
            ("step.gather", s, gather_end, {})]


# a's fourth gap (0.4 -> 1.4) is the only one in the tail: b's prefill
# stalls it for 0.8 s, then the step that ends it gathers for 0.1 s
TAIL_SPANS = (
    [("prefill", 0.0, 0.1, {"rid": "a"})]
    + _step(0.1, 0.2, ["a"], 0.15) + _step(0.2, 0.3, ["a"], 0.25)
    + _step(0.3, 0.4, ["a"], 0.35)
    + [("prefill", 0.4, 1.2, {"rid": "b"})]
    + _step(1.2, 1.4, ["a", "b"], 1.3) + _step(1.4, 1.5, ["a", "b"], 1.45)
    + _step(1.5, 1.6, ["b"], 1.55))


def test_token_gaps_follow_the_named_requests():
    gaps = S.token_gaps(TAIL_SPANS)
    assert sorted(round(e - s, 6) for s, e in gaps) == sorted(
        [0.1, 0.1, 0.1, 1.0, 0.1, 0.2, 0.1, 0.1])
    assert (0.4, 1.4) in gaps


def test_tail_shares_split_a_gap_between_phases():
    run = _run(TAIL_SPANS)
    assert harness.reader("itl_tail_prefill_share")(run) == pytest.approx(80)
    assert harness.reader("itl_tail_gather_share")(run) == pytest.approx(10)


def test_covered_is_a_union_inside_the_gap():
    assert S.covered((0.0, 1.0), [(0.1, 0.5), (0.3, 0.6), (0.9, 2.0),
                                  (-1.0, -0.5)]) == pytest.approx(0.6)


@pytest.mark.parametrize("spans", [None, [
    ("decode_step", 0.0, 0.1, {"n_active": 1}), ("step.gather", 0.0, 0.05,
                                                 {}),
    ("prefill", 0.2, 0.3, {"rid": "a"})]], ids=["untraced", "older_program"])
def test_readers_are_silent_without_their_spans(spans):
    """A program that writes no kv.* spans and no rids (the parent of this
    instrumentation) leaves the metrics out rather than reading zero."""
    for m in KV + TAIL:
        assert harness.reader(m)(_run(spans)) is None


def test_readers_on_a_served_smoke_run():
    """Spans of a real traced run, through the harness's own conversion:
    every reader reads, and the gather's two stages fit inside it."""
    import time

    from repro.models import registry
    from repro.obs import Tracer
    from repro.serve import BatchConfig, BatchServer, Request, ServeConfig
    from repro.serve import deployed as DP

    cfg = registry.get_smoke_config("yi-6b", dtype="float32")
    sp = DP.from_params(cfg, registry.model_fns(cfg).init_params(
        cfg, jax.random.PRNGKey(0)))
    tr = Tracer()
    srv = BatchServer(cfg, sp, ServeConfig(),
                      BatchConfig(n_slots=2, block_size=4, n_blocks=48,
                                  prefix_cache=False),
                      engine="scan", tracer=tr)
    rng = np.random.default_rng(3)
    t0 = time.monotonic()
    rep = srv.run([Request(f"r{i}", rng.integers(0, cfg.vocab, 6 + i), 6)
                   for i in range(4)])
    run = _run(harness.tracer_spans(tr, t0))
    got = {m: harness.reader(m)(run) for m in KV + TAIL}
    assert all(v is not None for v in got.values()), got
    gather = harness.reader("kv_gather_ms")(run)
    assert got["kv_host_gather_ms"] + got["kv_h2d_ms"] <= gather
    assert 0 < got["kv_h2d_useful_share"] <= 100
    assert got["kv_h2d_mb_per_step"] * 1e6 * rep.n_decode_steps == \
        pytest.approx(rep.kv_stats["h2d_bytes"])
    assert 0 <= got["itl_tail_gather_share"] + \
        got["itl_tail_prefill_share"] <= 100
