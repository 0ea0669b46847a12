"""Continuous batching + compressed serving tests.

The contracts under test:

  * batching must never change tokens: every request served through the
    slot/paged-KV machinery (continuous OR static admission) reproduces the
    single-request Engine stream exactly;
  * the paged KV pool really recycles blocks across admissions and bounds
    peak usage below the padded worst case;
  * compressed serving is numerically honest: at target_sparsity=0 the
    deployed (BSR-kernel) engine reproduces the dense-math QAT engine's
    greedy tokens exactly, and at paper-style sparsity every packed
    projection matches ``deploy.reference_matmul``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import deploy as D
from repro.models import registry
from repro.models import layers as L
from repro.serve import (BatchConfig, BatchServer, Engine, PagedKVCache,
                         Request, RequestQueue, ServeConfig)
from repro.serve import deployed as DP


@pytest.fixture(scope="module")
def dense_model():
    cfg = registry.get_smoke_config("yi-6b", dtype="float32")
    params = registry.model_fns(cfg).init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _trace(cfg, n=7, seed=5, max_prompt=14, max_new=9):
    rng = np.random.default_rng(seed)
    return [Request(f"r{i}", rng.integers(0, cfg.vocab, int(rng.integers(2, max_prompt))),
                    int(rng.integers(1, max_new))) for i in range(n)]


def _engine_reference(cfg, params, reqs):
    out = {}
    for r in reqs:
        eng = Engine(cfg, params, ServeConfig(max_new_tokens=r.max_new_tokens))
        out[r.rid] = eng.generate({"tokens": jnp.asarray(r.prompt[None])})[0]
    return out


@pytest.mark.parametrize("continuous", [True, False])
def test_batching_matches_single_request_engine(dense_model, continuous):
    cfg, params = dense_model
    reqs = _trace(cfg)
    want = _engine_reference(cfg, params, reqs)
    srv = BatchServer(cfg, DP.from_params(cfg, params), ServeConfig(),
                      BatchConfig(n_slots=3, block_size=4, n_blocks=32),
                      continuous=continuous)
    rep = srv.run(_trace(cfg))
    assert set(rep.outputs) == {r.rid for r in reqs}
    for r in reqs:
        np.testing.assert_array_equal(
            rep.outputs[r.rid], want[r.rid],
            err_msg=f"{r.rid}: batched decode diverged from Engine")
    assert rep.total_tokens == sum(len(o) for o in want.values())
    assert len(rep.ttft_s) == len(reqs)


def test_slot_admission_and_paged_reuse(dense_model):
    """More requests than slots and a pool far smaller than padded worst
    case: freed slots must admit the queue tail and freed blocks must be
    physically reused."""
    cfg, params = dense_model
    reqs = _trace(cfg, n=9, seed=11)
    bcfg = BatchConfig(n_slots=2, block_size=4, n_blocks=12)
    srv = BatchServer(cfg, DP.from_params(cfg, params), ServeConfig(), bcfg)
    rep = srv.run(reqs)
    assert len(rep.outputs) == 9  # every queued request completed
    st = rep.kv_stats
    assert st["reused_blocks"] > 0, "free list never recycled a block"
    assert st["peak_blocks"] <= bcfg.n_blocks - 1
    # paged: peak is bounded by live sequences, not n_requests * max_len
    assert st["peak_blocks"] < st["allocations"]
    # and correctness held while recycling:
    want = _engine_reference(cfg, params, reqs)
    for r in reqs:
        np.testing.assert_array_equal(rep.outputs[r.rid], want[r.rid])


def test_oversized_request_rejected(dense_model):
    cfg, params = dense_model
    srv = BatchServer(cfg, DP.from_params(cfg, params), ServeConfig(),
                      BatchConfig(n_slots=2, block_size=4, n_blocks=4))
    huge = Request("big", np.zeros(30, np.int32), 10)
    with pytest.raises(ValueError, match="blocks"):
        srv.run([huge])


def test_arrival_times_honored(dense_model):
    cfg, params = dense_model
    reqs = [Request("early", np.arange(4), 2, arrival=0.0),
            Request("late", np.arange(6), 2, arrival=0.05)]
    srv = BatchServer(cfg, DP.from_params(cfg, params), ServeConfig(),
                      BatchConfig(n_slots=2, block_size=4, n_blocks=16))
    rep = srv.run(reqs)
    assert set(rep.outputs) == {"early", "late"}
    # TTFT is measured from arrival, so the late request's wait is excluded
    assert all(t >= 0 for t in rep.ttft_s)


def test_request_queue_requeue_keeps_fifo():
    a = Request("a", np.arange(3), 1)
    b = Request("b", np.arange(3), 1)
    q = RequestQueue([a, b])
    popped = q.pop_ready(now=0.0)
    assert popped.rid == "a"
    q.requeue(popped)  # backpressure: "a" must stay ahead of "b"
    assert q.pop_ready(now=0.0).rid == "a"
    assert q.pop_ready(now=0.0).rid == "b"


def test_request_queue_ordering():
    q = RequestQueue([Request("b", np.arange(3), 1, arrival=0.2),
                      Request("a", np.arange(3), 1, arrival=0.0)])
    assert q.pop_ready(now=0.0).rid == "a"
    assert q.pop_ready(now=0.0) is None  # "b" not arrived yet
    assert q.next_arrival() == 0.2
    assert q.pop_ready(now=0.3).rid == "b"
    assert len(q) == 0


def test_paged_kv_gather_roundtrip(dense_model):
    """Writing per-token K/V through block tables and gathering the view
    back must reproduce a contiguous cache."""
    cfg, _ = dense_model
    kv = PagedKVCache(cfg, n_slots=2, n_blocks=8, block_size=4)
    L_, KV, dh = cfg.n_layers, cfg.n_kv_heads_eff, cfg.dh
    rng = np.random.default_rng(0)
    ref = np.zeros((L_, 2, 8, KV, dh), np.float32)
    for pos in range(8):
        for slot in range(2):
            kv.ensure(slot, pos + 1)
        k = rng.standard_normal((L_, 2, KV, dh)).astype(np.float32)
        v = rng.standard_normal((L_, 2, KV, dh)).astype(np.float32)
        ref[:, :, pos] = k
        pb, off = kv.write_coords([pos, pos])
        kv.write_token(pb, off, jnp.asarray(k), jnp.asarray(v))
    got_k, _ = kv.gather(n_view=2)
    np.testing.assert_allclose(np.asarray(got_k), ref, rtol=0, atol=0)
    # freeing returns blocks and the next allocation reuses them
    held = list(kv.tables[0])
    kv.free_slot(0)
    kv.ensure(0, 1)
    assert kv.tables[0][0] == held[0]


def test_decode_attention_multi_matches_per_row(dense_model):
    """Per-row-position attention over a gathered view == scalar-pos
    decode_attention run row by row on a contiguous cache."""
    cfg, params = dense_model
    p = jax.tree.map(lambda a: a[0], params["layers"])
    rng = np.random.default_rng(2)
    B, Sv, KV, dh = 3, 8, cfg.n_kv_heads_eff, cfg.dh
    x = jnp.asarray(rng.standard_normal((B, 1, cfg.d_model)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, Sv, KV, dh)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, Sv, KV, dh)), jnp.float32)
    pos = jnp.asarray([2, 5, 0], jnp.int32)
    y, kn, vn = L.decode_attention_multi(p, x, kc, vc, pos, cfg)
    assert kn.shape == (B, 1, KV, dh)  # (B, T, KV, dh) with T=1
    for b in range(B):
        yb, kb, vb = L.decode_attention(p, x[b:b + 1], kc[b:b + 1],
                                        vc[b:b + 1], pos[b], cfg)
        np.testing.assert_allclose(np.asarray(y[b]), np.asarray(yb[0]),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(kn[b, 0]),
                                   np.asarray(kb[0, pos[b]]), rtol=0, atol=0)


# ---------------------------------------------------------------------------
# Report guards: empty traces and zero-duration runs must not divide by zero
# ---------------------------------------------------------------------------


def test_run_with_empty_trace_reports_zeros(dense_model):
    cfg, params = dense_model
    srv = BatchServer(cfg, DP.from_params(cfg, params), ServeConfig(),
                      BatchConfig(n_slots=2, block_size=4, n_blocks=8))
    rep = srv.run([])
    assert rep.n_requests == 0 and rep.total_tokens == 0
    assert rep.tokens_per_s == 0.0
    assert rep.slot_efficiency == 1.0
    j = rep.to_json()  # must serialize without NaN/inf
    assert j["ttft"] == {"p50": 0.0, "p99": 0.0, "mean": 0.0}
    assert j["tpot"] == {"p50": 0.0, "p99": 0.0, "mean": 0.0}
    assert np.isfinite(j["tokens_per_s"])


def test_report_zero_duration_run():
    from repro.serve.server import ServeReport

    rep = ServeReport(n_requests=0, total_tokens=0, wall_s=0.0,
                      n_decode_steps=0, ttft_s=[], tpot_s=[],
                      outputs={}, kv_stats={})
    assert rep.tokens_per_s == 0.0
    assert rep.slot_efficiency == 1.0
    # tokens but zero wall clock (a mocked/degenerate timer) stays finite
    rep2 = ServeReport(n_requests=1, total_tokens=3, wall_s=0.0,
                       n_decode_steps=2, ttft_s=[0.1], tpot_s=[0.01],
                       outputs={}, kv_stats={})
    assert rep2.tokens_per_s == 0.0
    assert np.isfinite(rep2.slot_efficiency)


def test_percentiles_guard_empty_and_nonfinite():
    from repro.serve.server import _percentiles

    assert _percentiles([]) == {"p50": 0.0, "p99": 0.0, "mean": 0.0}
    assert _percentiles([np.nan, np.inf]) == {"p50": 0.0, "p99": 0.0,
                                              "mean": 0.0}
    p = _percentiles([0.5, np.nan, 1.5])  # finite entries still summarized
    assert p["mean"] == pytest.approx(1.0)


def test_slot_efficiency_never_negative():
    from repro.serve.server import ServeReport

    # pathological accounting (more requests than tokens) clamps at 0
    rep = ServeReport(n_requests=5, total_tokens=2, wall_s=1.0,
                      n_decode_steps=3, ttft_s=[], tpot_s=[],
                      outputs={}, kv_stats={})
    rep._n_slots = 2
    assert rep.slot_efficiency == 0.0


# ---------------------------------------------------------------------------
# Compressed serving
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def qat_model():
    cfg = registry.get_smoke_config("yi-6b", dtype="float32", cim_mode="qat")
    params = registry.model_fns(cfg).init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_compressed_sparsity0_tokens_exact(qat_model):
    """target_sparsity=0: the BSR-kernel engine must reproduce the dense
    (QAT-math) engine's greedy tokens EXACTLY - compression may only drop
    zero blocks, never change numerics."""
    cfg, params = qat_model
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(4).integers(0, cfg.vocab, (2, 7)), jnp.int32)}
    want = Engine(cfg, params, ServeConfig(max_new_tokens=5)).generate(batch)
    sp = DP.compress(cfg, params, target_sparsity=0.0,
                     schedule=DP.default_schedule(cfg))
    got = Engine(cfg, sp, ServeConfig(max_new_tokens=5),
                 fns=DP.model_fns(cfg)).generate(batch)
    np.testing.assert_array_equal(got, want)


def test_compressed_batch_server_sparsity0_tokens_exact(qat_model):
    """Same honesty bar for the continuous-batching path."""
    cfg, params = qat_model
    reqs = _trace(cfg, n=4, seed=9, max_new=6)
    want = _engine_reference(cfg, params, reqs)
    sp = DP.compress(cfg, params, target_sparsity=0.0)
    srv = BatchServer(cfg, sp, ServeConfig(),
                      BatchConfig(n_slots=2, block_size=4, n_blocks=24))
    rep = srv.run(_trace(cfg, n=4, seed=9, max_new=6))
    for r in reqs:
        np.testing.assert_array_equal(rep.outputs[r.rid], want[r.rid])


def test_compressed_projections_match_reference(qat_model):
    """Paper-sparsity packing: every deployed projection must match the
    dense quantized oracle (same mask + quant, dense math) within float
    tolerance - the schedule's tile is the kernel's tile."""
    cfg, params = qat_model
    ts = 0.5
    sched = DP.default_schedule(cfg)
    sp = DP.compress(cfg, params, target_sparsity=ts, schedule=sched)
    deployed = sp.deployed()
    assert len(deployed) == cfg.n_layers * 7 + 1  # QKV/O + 3 MLP + head
    per_layer = [jax.tree.map(lambda a: a[i], params["layers"])
                 for i in range(cfg.n_layers)]
    rng = np.random.default_rng(1)
    checked = 0
    for name, dw in deployed.items():
        if name == "head":
            w = params["head"]
        else:
            blk, proj = name.split("_", 1)
            w = per_layer[int(blk[3:])][proj]
        x = jnp.asarray(rng.standard_normal((4, dw.d_in)), jnp.float32)
        bk, bn = dw.tile
        got = D.deployed_matmul(x, dw, a_bits=cfg.cim.quant.a_bits)
        want = D.reference_matmul(x, w, cfg.cim, target_sparsity=ts,
                                  bk=bk, bn=bn)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4, err_msg=name)
        checked += 1
    assert checked == len(deployed)
    # compression actually dropped blocks at this sparsity
    assert sp.report()["compression_x"] > 4.0


def test_compress_respects_schedule_tile(qat_model):
    cfg, params = qat_model
    sched = DP.default_schedule(cfg)
    sp = DP.compress(cfg, params, target_sparsity=0.3, schedule=sched)
    by_name = {s.name: s for s in sched.layers}
    for name, dw in sp.deployed().items():
        if name == "head":
            continue
        g, a = by_name[name].group, by_name[name].alpha
        assert dw.tile == D.fit_tile(dw.d_in, dw.d_out, g, a), name


def test_serving_params_pytree_roundtrip(qat_model):
    cfg, params = qat_model
    sp = DP.compress(cfg, params, target_sparsity=0.25)
    leaves, treedef = jax.tree.flatten(sp)
    sp2 = jax.tree.unflatten(treedef, leaves)
    batch = {"tokens": jnp.asarray(np.arange(10, dtype=np.int32).reshape(2, 5))}
    a, _ = DP.prefill(sp, batch, cfg)
    b, _ = DP.prefill(sp2, batch, cfg)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prune_mask_keeps_everything_at_zero_sparsity():
    from repro.core import sparsity as S
    w = jnp.asarray(np.random.default_rng(0).standard_normal((32, 32)),
                    jnp.float32)
    assert float(S.prune_mask_2d(w, 8, 8, 0.0).mean()) == 1.0
    assert float(S.prune_mask_conv(w.reshape(2, 2, 8, 32), 8, 8, 0.0).mean()) == 1.0


# ---------------------------------------------------------------------------
# Block lifecycle: atomic exhaustion, scrub-on-free (ISSUE 8 satellites)
# ---------------------------------------------------------------------------


def test_ensure_exhaustion_is_atomic(dense_model):
    """On pool exhaustion ``ensure`` must raise WITHOUT growing the table -
    a caller that catches the error and requeues the request would
    otherwise leak every block appended before the failure."""
    cfg, _ = dense_model
    kv = PagedKVCache(cfg, n_slots=2, n_blocks=6, block_size=4)
    kv.ensure(0, 12)  # 3 of the 5 usable blocks
    free_before = list(kv._free)
    with pytest.raises(RuntimeError, match="exhausted"):
        kv.ensure(1, 16)  # needs 4, only 2 free
    assert kv.tables[1] == []  # nothing leaked into the failed table
    assert kv._free == free_before  # nothing popped either
    assert kv.free_blocks + kv.blocks_in_use == kv.n_blocks - 1
    kv.ensure(1, 8)  # a fitting request still succeeds afterwards
    assert len(kv.tables[1]) == 2


def test_freed_blocks_are_scrubbed(dense_model):
    """``free_slot`` must zero returned blocks: once blocks are shared, a
    reused block carrying the previous request's K/V would surface in
    another slot's gathered view."""
    cfg, _ = dense_model
    kv = PagedKVCache(cfg, n_slots=1, n_blocks=4, block_size=2)
    L_, KV, dh = cfg.n_layers, cfg.n_kv_heads_eff, cfg.dh
    k = np.ones((L_, 4, KV, dh), np.float32)
    kv.write_prefill(0, jnp.asarray(k), jnp.asarray(2 * k), true_len=4)
    held = list(kv.tables[0])
    assert all(np.any(kv.pool_k[0, b]) for b in held)
    kv.free_slot(0)
    for b in held:
        assert not np.any(kv.pool_k[0, b]), f"block {b} kept stale K"
        assert not np.any(kv.pool_v[0, b]), f"block {b} kept stale V"
    # and a realloc-then-gather sees zeros, not the old payload
    kv.ensure(0, 2)
    got_k, got_v = kv.gather(n_view=1)
    assert not np.any(np.asarray(got_k)) and not np.any(np.asarray(got_v))


def test_debug_poison_fills_freed_blocks_with_nan(dense_model):
    """Under ``debug_poison`` a freed float block is NaN-filled so any
    gather that wrongly references it poisons its output loudly."""
    cfg, _ = dense_model
    kv = PagedKVCache(cfg, n_slots=1, n_blocks=4, block_size=2,
                      debug_poison=True)
    L_, KV, dh = cfg.n_layers, cfg.n_kv_heads_eff, cfg.dh
    k = np.ones((L_, 2, KV, dh), np.float32)
    kv.write_prefill(0, jnp.asarray(k), jnp.asarray(k), true_len=2)
    held = list(kv.tables[0])
    kv.free_slot(0)
    for b in held:
        assert np.all(np.isnan(kv.pool_k[0, b]))
        assert np.all(np.isnan(kv.pool_v[0, b]))


# ---------------------------------------------------------------------------
# The pool on the device
# ---------------------------------------------------------------------------


def _filled_pool(cfg, tiers=2):
    """A pool whose every (tier, block) holds its own random K/V."""
    kv = PagedKVCache(cfg, n_slots=3, n_blocks=10, block_size=4, tiers=tiers)
    rng = np.random.default_rng(11)
    for tier in range(tiers):
        k = rng.standard_normal(
            (cfg.n_layers, 9 * 4, cfg.n_kv_heads_eff, cfg.dh)).astype(
                np.float32)
        kv.write_prefill(0, jnp.asarray(k), jnp.asarray(-k), 9 * 4,
                         tier=tier)
    return kv


def _reference_view(pool, tbl, tier):
    g = np.asarray(pool)[tier][tbl]  # (B, n_view, L, bs, KV, dh)
    b, n_view, n_layers, bs, n_kv, dh = g.shape
    return g.transpose(2, 0, 1, 3, 4, 5).reshape(n_layers, b, n_view * bs,
                                                 n_kv, dh)


@pytest.mark.parametrize("tier", [0, 1])
@pytest.mark.parametrize("slots", [None, [1], [2, 0]],
                         ids=["all", "one", "two"])
def test_device_gather_matches_a_numpy_gather(dense_model, tier, slots):
    """The device gather takes exactly the rows a numpy gather of the same
    pool takes: random tables, rows padded with the scratch block."""
    cfg, _ = dense_model
    kv = _filled_pool(cfg)
    assert isinstance(kv.pool_k, jax.Array)
    assert isinstance(kv.pool_v, jax.Array)
    rng = np.random.default_rng(tier)
    owned = rng.permutation(kv.tables[0])
    kv.tables = [list(owned[:3]), list(owned[3:4]), list(owned[4:9])]
    for n_view in (2, 6):
        tbl = kv.view_tables(n_view, slots)
        assert n_view == 2 or (tbl == 0).any()  # scratch-padded rows
        got_k, got_v = kv.gather(n_view, tier=tier, slots=slots)
        np.testing.assert_array_equal(
            np.asarray(got_k), _reference_view(kv.pool_k, tbl, tier))
        np.testing.assert_array_equal(
            np.asarray(got_v), _reference_view(kv.pool_v, tbl, tier))


def test_operand_committed_to_a_device_leaves_the_pool_uncommitted(
        dense_model):
    """K/V committed to a device (a gateway's dedicated prefill device)
    land in the pool without committing it, so its views stay uncommitted
    like any view the decode program was warmed with."""
    cfg, _ = dense_model
    kv = PagedKVCache(cfg, n_slots=1, n_blocks=4, block_size=4)
    x = np.arange(cfg.n_layers * 4 * cfg.n_kv_heads_eff * cfg.dh,
                  dtype=np.float32).reshape(cfg.n_layers, 4,
                                            cfg.n_kv_heads_eff, cfg.dh)
    kv.write_prefill(0, jax.device_put(x, jax.devices()[0]),
                     jax.device_put(-x, jax.devices()[0]), 4)
    got_k, got_v = kv.gather(1)
    assert not kv.pool_k.committed and not got_k.committed
    np.testing.assert_array_equal(np.asarray(got_k)[:, 0], x)
    np.testing.assert_array_equal(np.asarray(got_v)[:, 0], -x)


def test_h2d_bytes_count_the_block_tables(dense_model, monkeypatch):
    """What a served run places on the device for its gathers is their
    block tables, and nothing of the K/V views."""
    cfg, params = dense_model
    placed = []
    real = PagedKVCache.view_tables

    def recorded(self, *a, **k):
        tbl = real(self, *a, **k)
        placed.append(tbl.nbytes)
        return tbl

    monkeypatch.setattr(PagedKVCache, "view_tables", recorded)
    srv = BatchServer(cfg, DP.from_params(cfg, params), ServeConfig(),
                      BatchConfig(n_slots=3, block_size=4, n_blocks=32),
                      engine="scan")
    rep = srv.run(_trace(cfg))
    assert rep.n_decode_steps > 0
    assert rep.kv_stats["h2d_bytes"] == sum(placed)
    # one (n_slots, n_view) int32 table per step: 3 slots x at most 6 blocks
    assert rep.kv_stats["h2d_bytes"] <= rep.n_decode_steps * 3 * 6 * 4


class _Compiles:
    """Backend compiles and persistent-cache loads while the block runs."""

    def __enter__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._compiled)
        jax.monitoring.register_event_listener(self._loaded)
        return self

    def _compiled(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _loaded(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.n += 1

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._compiled)
        jax.monitoring.unregister_event_listener(self._loaded)


def _exercise_pool(kv, slot, tier, start):
    """Every program of the pool once: a prefill, a shared block written
    (copy-on-write), a decode step's write, a run, a gather, the scrub."""
    cfg = kv.cfg
    rng = np.random.default_rng(start)
    x = rng.standard_normal((cfg.n_layers, 8, cfg.n_kv_heads_eff,
                             cfg.dh)).astype(np.float32)
    kv.write_prefill(slot, jnp.asarray(x), jnp.asarray(x), 7, tier=tier,
                     start=start)
    other = 1 - slot
    kv.adopt(other, kv.tables[slot][start // kv.block_size:])
    positions = [None, None]
    positions[other] = 1
    pb, off = kv.write_coords(positions)
    kv.write_token(pb, off, x[:, :2], x[:, :2], tier=tier)
    kv.write_run(slot, start + 2, x[:, :3], x[:, :3], tier=tier)
    kv.gather(6, tier=tier)
    kv.free_slot(0)
    kv.free_slot(1)


def test_pool_programs_take_ids_as_operands(dense_model):
    """Other blocks, offsets, tier and scrub value than the first time
    compile nothing: none of them is a static argument."""
    cfg, _ = dense_model
    first = PagedKVCache(cfg, n_slots=2, n_blocks=12, block_size=4, tiers=2)
    _exercise_pool(first, slot=0, tier=0, start=0)
    again = PagedKVCache(cfg, n_slots=2, n_blocks=12, block_size=4, tiers=2,
                         debug_poison=True)
    again.ensure(0, 4)  # the exercise's blocks move down the free list
    with _Compiles() as c:
        _exercise_pool(again, slot=1, tier=1, start=4)
    assert c.n == 0


@pytest.mark.parametrize("engine", ["loop", "scan", "spec"])
def test_second_identical_run_compiles_nothing(dense_model, engine):
    """The pool's programs take block ids, offsets, tiers and fill values
    as operands: a second run of the same trace on the same server finds
    every program it needs compiled."""
    from repro.serve.spec import SpecConfig

    cfg, params = dense_model
    sp = DP.from_params(cfg, params)
    spec = {"draft": sp, "spec": SpecConfig(k=2, draft_sparsity=0.5)} \
        if engine == "spec" else {}
    srv = BatchServer(cfg, sp, ServeConfig(),
                      BatchConfig(n_slots=2, block_size=4, n_blocks=24),
                      engine=engine, **spec)
    jax.clear_caches()  # what earlier tests compiled is compiled again
    with _Compiles() as c:
        first = srv.run(_trace(cfg, n=5, seed=9))
    assert c.n > 0
    with _Compiles() as c:
        second = srv.run(_trace(cfg, n=5, seed=9))
    assert c.n == 0
    for rid, out in first.outputs.items():
        np.testing.assert_array_equal(second.outputs[rid], out)
