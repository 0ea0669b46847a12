"""Spans of the KV pool and the serving loop, and their copies in the JAX
profiler's trace.

Contracts under test, on the smoke model:

  * every ``step.gather`` nests ``kv.tables``, then ``kv.h2d`` of the
    block table, then ``kv.gather`` for K and for V, inside its bounds;
  * the ``bytes`` args of ``kv.h2d`` are the block table's bytes, sum to
    the pool's ``h2d_bytes`` counter (kept traced or not), and
    ``useful_bytes`` counts the table entries that name an active lane's
    block;
  * every ``decode_step`` names the requests it gives a token (``rids``),
    so span ends rebuild each request's token gaps;
  * the phases land in a profiler session's host plane, on a clock that
    differs from the tracer's by one offset;
  * tracing changes no token.
"""
import glob
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.models import registry
from repro.obs import Tracer
from repro.serve import BatchConfig, BatchServer, Request, ServeConfig
from repro.serve import deployed as DP

N_SLOTS, BLOCK, BUCKET = 2, 4, 2
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke_model():
    cfg = registry.get_smoke_config("yi-6b", dtype="float32")
    params = registry.model_fns(cfg).init_params(cfg, jax.random.PRNGKey(0))
    return cfg, DP.from_params(cfg, params)


def _reqs(cfg, n=5, seed=7):
    rng = np.random.default_rng(seed)
    return [Request(f"r{i}", rng.integers(0, cfg.vocab,
                                          int(rng.integers(3, 15))),
                    int(rng.integers(2, 8))) for i in range(n)]


def _serve(smoke_model, tracer=None):
    cfg, sp = smoke_model
    srv = BatchServer(cfg, sp, ServeConfig(),
                      BatchConfig(n_slots=N_SLOTS, block_size=BLOCK,
                                  n_blocks=48, view_bucket=BUCKET,
                                  prefix_cache=False),
                      engine="scan", tracer=tracer)
    return srv.run(_reqs(cfg))


@pytest.fixture(scope="module")
def traced(smoke_model):
    tr = Tracer()
    rep = _serve(smoke_model, tr)
    return rep, [e for e in tr.events if e["ph"] == "X"]


def _inside(events, parent):
    p0, p1 = parent["ts"], parent["ts"] + parent["dur"]
    return [e for e in events if e is not parent and p0 <= e["ts"]
            and e["ts"] + e["dur"] <= p1 + 1e-3]


def _named(events, name):
    return [e for e in events if e["name"] == name]


def test_step_gather_nests_the_kv_stages(traced):
    rep, evs = traced
    gathers = _named(evs, "step.gather")
    assert len(gathers) == rep.n_decode_steps > 0
    for g in gathers:
        kids = sorted(_inside(evs, g), key=lambda e: e["ts"])
        assert [(e["name"], e["args"].get("tensor")) for e in kids] == [
            ("kv.tables", None), ("kv.h2d", "table"), ("kv.gather", "k"),
            ("kv.gather", "v")]
    # every kv stage of the decode path belongs to a step.gather
    assert sum(len(_named(evs, n)) for n in ("kv.tables", "kv.gather",
                                             "kv.h2d")) == 4 * len(gathers)


def test_h2d_bytes_are_the_views(smoke_model, traced):
    """Per step, one int32 block table of n_slots x n_view entries, with
    n_view the longest active lane's blocks rounded up to the bucket;
    useful bytes are the entries that name the active lanes' blocks
    (positions [0, pos] of each)."""
    cfg, _ = smoke_model
    rep, evs = traced
    prompt = {r.rid: len(r.prompt) for r in _reqs(cfg)}
    steps_done = {rid: 0 for rid in prompt}
    total = 0
    for step, g in zip(_named(evs, "decode_step"), _named(evs, "step.gather")):
        pos = [prompt[r] + steps_done[r] for r in step["args"]["rids"]]
        held = [-(-(p + 1) // BLOCK) for p in pos]
        n_view = -(-max(held) // BUCKET) * BUCKET
        h2d = _named(_inside(evs, g), "kv.h2d")
        assert len(h2d) == 1
        for e in h2d:
            assert e["args"]["bytes"] == N_SLOTS * n_view * 4
            assert 0 < e["args"]["useful_bytes"] == 4 * sum(held) \
                <= e["args"]["bytes"]
        total += sum(e["args"]["bytes"] for e in h2d)
        for r in step["args"]["rids"]:
            steps_done[r] += 1
    assert total == rep.kv_stats["h2d_bytes"] > 0
    # the counter is kept with tracing off too
    assert _serve(smoke_model).kv_stats["h2d_bytes"] == total


def test_decode_steps_name_their_lanes(smoke_model, traced):
    """Span ends rebuild every request's tokens and gaps: its prefill's
    end is token 1, the end of each decode_step naming it the next."""
    cfg, _ = smoke_model
    rep, evs = traced
    ends = {e["args"]["rid"]: [e["ts"] + e["dur"]]
            for e in _named(evs, "prefill")}
    for e in _named(evs, "decode_step"):
        for rid in e["args"]["rids"]:
            ends[rid].append(e["ts"] + e["dur"])
    assert {rid: len(t) for rid, t in ends.items()} == {
        rid: len(o) for rid, o in rep.outputs.items()}
    gaps = sorted(g for t in ends.values() for g in np.diff(t) * 1e-6)
    np.testing.assert_allclose(gaps, sorted(rep.tpot_s), atol=1e-3)
    # tracing is read-only: the same tokens as an untraced server
    plain = _serve(smoke_model)
    assert plain.outputs.keys() == rep.outputs.keys()
    for rid, out in rep.outputs.items():
        np.testing.assert_array_equal(out, plain.outputs[rid])


def _host_events(profile_dir):
    (path,) = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [e for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events]


def test_phases_land_in_the_profiler_trace(smoke_model, tmp_path):
    tr = Tracer()
    _serve(smoke_model, tr)  # compile outside the session
    tr.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(smoke_model, tr)
    finally:
        jax.profiler.stop_trace()
    host = _host_events(tmp_path)
    ours = [e for e in tr.events if e["ph"] == "X"]
    diffs = []
    for name in ("step.gather", "kv.h2d", "decode_step"):
        prof = sorted(e.start_ns for e in host if e.name == name)
        mine = sorted(e["ts"] * 1e3 for e in ours if e["name"] == name)
        assert len(prof) == len(mine) > 0, name
        diffs += list(np.subtract(prof, mine))
    # one offset between the two clocks puts every start within 1 ms
    np.testing.assert_allclose(diffs, np.median(diffs), atol=1e6)
    steps = [e for e in host if e.name == "decode_step"]
    assert sorted(dict(e.stats)["step_num"] for e in steps) == list(
        range(len(steps)))
    h2d = [dict(e.stats) for e in host if e.name == "kv.h2d"]
    assert all(s["bytes"] > 0 and s["tensor"] == "table" for s in h2d)


def test_launch_serve_profile_holds_the_phases(tmp_path):
    """``launch.serve --profile`` alone records the serving phases into
    the profile it writes."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "yi-6b",
         "--smoke", "--runtime", "scan", "--requests", "2",
         "--new-tokens", "3", "--profile", str(tmp_path / "prof")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    names = {e.name for e in _host_events(tmp_path / "prof")}
    assert {"prefill", "decode_step", "step.gather", "kv.gather",
            "kv.h2d", "step.dispatch"} <= names
