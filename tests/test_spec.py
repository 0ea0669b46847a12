"""Self-speculative decoding over two-tier CIM compression.

The contracts under test:

  * greedy exactness: ``BatchServer(engine="spec")`` emits BIT-IDENTICAL
    tokens to target-only greedy decode - dense and compressed targets,
    single-device and macro-sharded (subprocess mesh parity);
  * verify honesty: ``stacked.verify_step`` over T tokens reproduces T
    sequential ``decode_step_paged`` calls bit-exactly (the property the
    accept rule stands on);
  * draft-tier construction: re-pruning keeps the uniform tile, strictly
    drops blocks, and surviving blocks stay bit-identical to the target's;
  * KV hygiene: two-tier pools share one block layout and rejected draft
    KV never reaches the pool;
  * two-tier artifacts round-trip (shared dense leaves stored once) and
    the booted tiers serve identically;
  * the speculative cost model behaves (monotone in acceptance, search
    returns a simulated-feasible winner).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import perf_model as PM
from repro.models import registry
from repro.serve import (BatchConfig, BatchServer, Request, ServeConfig,
                         SpecConfig)
from repro.serve import deployed as DP
from repro.serve import spec as SP
from repro.serve import stacked as ST


@pytest.fixture(scope="module")
def qat_model():
    cfg = registry.get_smoke_config("yi-6b", dtype="float32", cim_mode="qat")
    params = registry.model_fns(cfg).init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _trace(cfg, n=5, seed=7, max_prompt=12, max_new=9):
    rng = np.random.default_rng(seed)
    return [Request(f"r{i}",
                    rng.integers(0, cfg.vocab, int(rng.integers(2, max_prompt))),
                    int(rng.integers(1, max_new))) for i in range(n)]


_BCFG = dict(n_slots=2, block_size=4, n_blocks=32)


# ---------------------------------------------------------------------------
# Greedy exactness: spec tokens == target-only tokens
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ts", [0.0, 0.5])
def test_spec_matches_target_only_compressed(qat_model, ts):
    cfg, params = qat_model
    sp = DP.compress(cfg, params, target_sparsity=ts, tile=(16, 16))
    draft = SP.draft_serving(cfg, sp, 0.85)
    bcfg = BatchConfig(**_BCFG)
    want = BatchServer(cfg, sp, ServeConfig(), bcfg,
                       engine="scan").run(_trace(cfg))
    got = BatchServer(cfg, sp, ServeConfig(), bcfg, engine="spec",
                      draft=draft,
                      spec=SpecConfig(k=3, draft_sparsity=0.85)
                      ).run(_trace(cfg))
    for r in _trace(cfg):
        np.testing.assert_array_equal(got.outputs[r.rid], want.outputs[r.rid],
                                      err_msg=f"ts={ts} {r.rid}")
    assert got.spec["n_rounds"] > 0
    assert got.spec["slot_rounds"] >= got.spec["n_rounds"]
    assert 0.0 <= got.spec["acceptance_rate"] <= 1.0
    assert got.spec["tokens_per_verify"] >= 1.0


@pytest.mark.parametrize("k", [1, 4])
def test_spec_matches_target_only_dense(qat_model, k):
    cfg, params = qat_model
    sp = DP.from_params(cfg, params)
    draft = SP.draft_serving(cfg, sp, 0.8, tile=(16, 16))
    bcfg = BatchConfig(**_BCFG)
    want = BatchServer(cfg, sp, ServeConfig(), bcfg,
                       engine="scan").run(_trace(cfg, seed=11))
    got = BatchServer(cfg, sp, ServeConfig(), bcfg, engine="spec",
                      draft=draft,
                      spec=SpecConfig(k=k, draft_sparsity=0.8)
                      ).run(_trace(cfg, seed=11))
    for r in _trace(cfg, seed=11):
        np.testing.assert_array_equal(got.outputs[r.rid], want.outputs[r.rid],
                                      err_msg=f"k={k} {r.rid}")


def test_spec_identical_tiers_accept_everything(qat_model):
    """Draft == target packing: every draft token the budget allows must
    be accepted (the accept rule compares the target against itself)."""
    cfg, params = qat_model
    sp = DP.compress(cfg, params, target_sparsity=0.5, tile=(16, 16))
    reqs = [Request(f"r{i}", np.arange(4) + i, 24) for i in range(3)]
    rep = BatchServer(cfg, sp, ServeConfig(), BatchConfig(**_BCFG),
                      engine="spec", draft=sp,
                      spec=SpecConfig(k=3, draft_sparsity=0.5)).run(reqs)
    st = rep.spec
    # only end-of-budget truncation may leave proposals unconverted: per
    # request at most one final partial round
    assert st["proposed"] - st["accepted"] <= st["k"] * len(reqs)
    assert st["tokens_per_verify"] > 2.0


def test_spec_eos_stops_inside_accepted_run(qat_model):
    """An EOS inside an accepted run must cut the stream exactly where
    sequential decode would have stopped."""
    cfg, params = qat_model
    sp = DP.compress(cfg, params, target_sparsity=0.5, tile=(16, 16))
    bcfg = BatchConfig(**_BCFG)
    reqs = [Request("r0", np.arange(5), 20)]
    ref = BatchServer(cfg, sp, ServeConfig(), bcfg, engine="scan").run(
        [Request("r0", np.arange(5), 20)])
    eos = int(ref.outputs["r0"][2])  # force a stop on the 3rd greedy token
    want = BatchServer(cfg, sp, ServeConfig(eos_id=eos), bcfg,
                       engine="scan").run([Request("r0", np.arange(5), 20)])
    got = BatchServer(cfg, sp, ServeConfig(eos_id=eos), bcfg, engine="spec",
                      draft=sp, spec=SpecConfig(k=4, draft_sparsity=0.5)
                      ).run([Request("r0", np.arange(5), 20)])
    np.testing.assert_array_equal(got.outputs["r0"], want.outputs["r0"])


def test_spec_matches_target_macro_sharded():
    """Acceptance: spec decode over macro-sharded two-tier envelopes
    reproduces single-device target-only tokens at mesh macro=2
    (subprocess: forced host devices must exist before jax imports)."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = " ".join(
        ([env["XLA_FLAGS"]] if env.get("XLA_FLAGS") else [])
        + ["--xla_force_host_platform_device_count=8"])
    env["PYTHONPATH"] = os.path.join(repo, "src")
    code = """
import numpy as np, jax
from repro.models import registry
from repro.serve import BatchConfig, BatchServer, ServeConfig, Request, SpecConfig
from repro.serve import deployed as DP
from repro.serve import spec as SP
from repro.launch.shardings import macro_mesh

cfg = registry.get_smoke_config("yi-6b", dtype="float32", cim_mode="qat")
params = registry.model_fns(cfg).init_params(cfg, jax.random.PRNGKey(0))
def trace():
    rng = np.random.default_rng(7)
    return [Request(f"r{i}", rng.integers(0, cfg.vocab, int(rng.integers(2, 10))),
                    int(rng.integers(1, 7))) for i in range(3)]
sp = DP.compress(cfg, params, target_sparsity=0.5, tile=(16, 16))
draft = SP.draft_serving(cfg, sp, 0.85)
bcfg = BatchConfig(n_slots=2, block_size=4, n_blocks=24)
want = BatchServer(cfg, sp, ServeConfig(), bcfg, engine="scan").run(trace())
mesh = macro_mesh(2)
srv = BatchServer(cfg, DP.shard(sp, mesh), ServeConfig(), bcfg, mesh=mesh,
                  engine="spec", draft=DP.shard(draft, mesh),
                  spec=SpecConfig(k=3, draft_sparsity=0.85))
assert any(sw.mesh is not None for sw in srv._params.target.packed.values()), \\
    "no target envelope actually sharded"
assert any(sw.mesh is not None for sw in srv._params.draft.packed.values()), \\
    "no draft envelope actually sharded"
rep = srv.run(trace())
for r in trace():
    np.testing.assert_array_equal(rep.outputs[r.rid], want.outputs[r.rid],
                                  err_msg=f"macro=2 {r.rid}")
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=repo, timeout=420)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "OK" in r.stdout


# ---------------------------------------------------------------------------
# Verify pass honesty: multi-token == sequential, bit for bit
# ---------------------------------------------------------------------------


def test_verify_step_matches_sequential_decode(qat_model):
    cfg, params = qat_model
    sp = DP.compress(cfg, params, target_sparsity=0.5, tile=(16, 16))
    sxp = ST.stack(sp)
    B, T, Sv = 2, 4, 16
    L, KV, dh = cfg.n_layers, cfg.n_kv_heads_eff, cfg.dh
    rng = np.random.default_rng(0)
    vk = jnp.asarray(rng.standard_normal((L, B, Sv, KV, dh)), jnp.float32)
    vv = jnp.asarray(rng.standard_normal((L, B, Sv, KV, dh)), jnp.float32)
    pos = jnp.asarray([3, 5], jnp.int32)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, T)), jnp.int32)
    lg_multi, ks, vs = ST.verify_step(sxp, vk, vv, pos, toks, cfg)
    assert ks.shape == (L, B, T, KV, dh)
    rows = jnp.arange(B)
    vk2, vv2 = vk, vv
    for t in range(T):
        lg, kn, vn = ST.decode_step_paged(sxp, vk2, vv2, pos + t,
                                          toks[:, t:t + 1], cfg)
        np.testing.assert_array_equal(np.asarray(lg_multi[:, t]),
                                      np.asarray(lg), err_msg=f"t={t}")
        np.testing.assert_array_equal(np.asarray(ks[:, :, t]),
                                      np.asarray(kn))
        vk2 = vk2.at[:, rows, pos + t].set(kn)
        vv2 = vv2.at[:, rows, pos + t].set(vn)


def test_draft_propose_consistent_with_sequential(qat_model):
    """The jitted draft loop's proposals are the draft tier's own greedy
    chain (and its KV covers k+1 positions for the lockstep commit)."""
    cfg, params = qat_model
    sp = DP.compress(cfg, params, target_sparsity=0.5, tile=(16, 16))
    sxp = ST.stack(sp)
    B, k, Sv = 2, 3, 16
    L, KV, dh = cfg.n_layers, cfg.n_kv_heads_eff, cfg.dh
    rng = np.random.default_rng(1)
    vk = jnp.asarray(rng.standard_normal((L, B, Sv, KV, dh)), jnp.float32)
    vv = jnp.asarray(rng.standard_normal((L, B, Sv, KV, dh)), jnp.float32)
    pos = jnp.asarray([2, 6], jnp.int32)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, 1)), jnp.int32)
    props, ks, vs = SP.draft_propose(sxp, vk, vv, pos, toks, cfg, k)
    assert props.shape == (B, k) and ks.shape == (L, B, k + 1, KV, dh)
    rows = jnp.arange(B)
    vk2, vv2, tok = vk, vv, toks
    for t in range(k):
        lg, kn, vn = ST.decode_step_paged(sxp, vk2, vv2, pos + t, tok, cfg)
        vk2 = vk2.at[:, rows, pos + t].set(kn)
        vv2 = vv2.at[:, rows, pos + t].set(vn)
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)[:, None]
        np.testing.assert_array_equal(np.asarray(props[:, t]),
                                      np.asarray(tok[:, 0]), err_msg=f"t={t}")


# ---------------------------------------------------------------------------
# Draft tier construction
# ---------------------------------------------------------------------------


def test_draft_serving_is_sparser_same_tile(qat_model):
    cfg, params = qat_model
    sp = DP.compress(cfg, params, target_sparsity=0.4, tile=(16, 16))
    draft = SP.draft_serving(cfg, sp, 0.9)
    t_dep, d_dep = sp.deployed(), draft.deployed()
    assert set(t_dep) == set(d_dep)
    for name in t_dep:
        assert d_dep[name].tile == t_dep[name].tile, name
        assert d_dep[name].density <= t_dep[name].density + 1e-9, name
    assert (sum(d.density for d in d_dep.values())
            < 0.6 * sum(d.density for d in t_dep.values()))
    # dense leaves are shared BY REFERENCE (two-tier artifacts dedupe them)
    assert draft.embed is sp.embed
    assert draft.layers[0]["ln1"] is sp.layers[0]["ln1"]


def test_draft_surviving_blocks_bit_identical(qat_model):
    """Re-pruning only drops blocks: a draft block that survives must carry
    the target's exact int8 levels (the draft differs in WHICH blocks
    exist, never in their values)."""
    cfg, params = qat_model
    sp = DP.compress(cfg, params, target_sparsity=0.3, tile=(16, 16))
    draft = SP.draft_serving(cfg, sp, 0.8)
    dw_t = sp.layers[0]["wq"]
    dw_d = draft.layers[0]["wq"]
    pt, pd = dw_t.packed[0], dw_d.packed[0]
    bt = np.asarray(pt["blocks"])
    rt = np.asarray(pt["row_idx"])
    nt = np.asarray(pt["nnz"])
    bd = np.asarray(pd["blocks"])
    rd = np.asarray(pd["row_idx"])
    nd = np.asarray(pd["nnz"])
    assert nd.sum() < nt.sum()  # strictly sparser
    for j in range(bd.shape[0]):
        tmap = {int(rt[j, s]): bt[j, s] for s in range(int(nt[j]))}
        for s in range(int(nd[j])):
            row = int(rd[j, s])
            assert row in tmap, f"draft kept a block the target pruned ({j},{row})"
            np.testing.assert_array_equal(bd[j, s], tmap[row])


def test_draft_of_dense_target_is_packed(qat_model):
    cfg, params = qat_model
    sp = DP.from_params(cfg, params)
    draft = SP.draft_serving(cfg, sp, 0.85, tile=(16, 16))
    assert len(draft.deployed()) > 0
    tiles = {dw.tile for dw in draft.deployed().values()}
    assert len(tiles) == 1  # uniform: the draft must stack
    ST.stack(draft)


def test_spec_params_validation(qat_model):
    cfg, params = qat_model
    sp16 = DP.compress(cfg, params, target_sparsity=0.5, tile=(16, 16))
    sp32 = DP.compress(cfg, params, target_sparsity=0.5, tile=(32, 32))
    with pytest.raises(ValueError, match="tile"):
        SP.SpecParams.build(sp16, sp32)
    with pytest.raises(ValueError, match="k must"):
        SpecConfig(k=0)
    with pytest.raises(ValueError, match="draft_sparsity"):
        SpecConfig(draft_sparsity=1.0)


def test_spec_server_guards(qat_model):
    cfg, params = qat_model
    sp = DP.from_params(cfg, params)
    with pytest.raises(ValueError, match="draft"):
        BatchServer(cfg, sp, engine="spec")
    draft = SP.draft_serving(cfg, sp, 0.85, tile=(16, 16))
    with pytest.raises(ValueError, match="greedy"):
        BatchServer(cfg, sp, ServeConfig(temperature=0.7), engine="spec",
                    draft=draft)


# ---------------------------------------------------------------------------
# Two-tier KV pool
# ---------------------------------------------------------------------------


def test_paged_kv_tiers_share_layout(qat_model):
    from repro.serve import PagedKVCache
    cfg, _ = qat_model
    kv = PagedKVCache(cfg, n_slots=2, n_blocks=8, block_size=4, tiers=2)
    L, KV, dh = cfg.n_layers, cfg.n_kv_heads_eff, cfg.dh
    rng = np.random.default_rng(0)
    kv.ensure(0, 6)
    run_t = rng.standard_normal((L, 3, KV, dh)).astype(np.float32)
    run_d = rng.standard_normal((L, 3, KV, dh)).astype(np.float32)
    kv.write_run(0, 2, run_t, run_t, tier=0)
    kv.write_run(0, 2, run_d, run_d, tier=1)
    gk_t, _ = kv.gather(2, tier=0)
    gk_d, _ = kv.gather(2, tier=1)
    np.testing.assert_array_equal(np.asarray(gk_t[:, 0, 2:5]), run_t)
    np.testing.assert_array_equal(np.asarray(gk_d[:, 0, 2:5]), run_d)
    # one free list, one table: freeing releases both tiers' storage
    assert kv.blocks_in_use == 2
    kv.free_slot(0)
    assert kv.blocks_in_use == 0


def test_write_run_partial_commit_is_rollback(qat_model):
    """Only the accepted prefix reaches the pool; positions past it keep
    their prior content (the rejected suffix was never committed)."""
    from repro.serve import PagedKVCache
    cfg, _ = qat_model
    kv = PagedKVCache(cfg, n_slots=1, n_blocks=4, block_size=4, tiers=2)
    L, KV, dh = cfg.n_layers, cfg.n_kv_heads_eff, cfg.dh
    kv.ensure(0, 8)
    before = kv.pool_k[1].copy()
    run = np.ones((L, 2, KV, dh), np.float32)
    kv.write_run(0, 1, run, run, tier=1)  # accept 2 of a longer candidate
    gk, _ = kv.gather(2, tier=1)
    np.testing.assert_array_equal(np.asarray(gk[:, 0, 1:3]), run)
    # position 3 onward untouched
    np.testing.assert_array_equal(np.asarray(gk[:, 0, 3:]),
                                  np.zeros((L, 5, KV, dh), np.float32))
    # tier 0 untouched entirely
    np.testing.assert_array_equal(kv.pool_k[0], before)


# ---------------------------------------------------------------------------
# Two-tier artifacts
# ---------------------------------------------------------------------------


def test_two_tier_artifact_roundtrip(qat_model, tmp_path):
    cfg, params = qat_model
    sp = DP.compress(cfg, params, target_sparsity=0.5, tile=(16, 16))
    draft = SP.draft_serving(cfg, sp, 0.85)
    d = DP.save_artifact(str(tmp_path / "two"), sp, cfg, draft=draft,
                         extra={"draft_sparsity": 0.85})
    sp2, meta = DP.load_artifact(str(tmp_path / "two"))
    draft2, _ = DP.load_artifact(str(tmp_path / "two"), tier="draft")
    assert meta["two_tier"] is True and meta["draft_sparsity"] == 0.85
    bcfg = BatchConfig(**_BCFG)
    want = BatchServer(cfg, sp, ServeConfig(), bcfg, engine="spec",
                       draft=draft,
                       spec=SpecConfig(k=3, draft_sparsity=0.85)
                       ).run(_trace(cfg))
    got = BatchServer(cfg, sp2, ServeConfig(), bcfg, engine="spec",
                      draft=draft2,
                      spec=SpecConfig(k=3, draft_sparsity=0.85)
                      ).run(_trace(cfg))
    for r in _trace(cfg):
        np.testing.assert_array_equal(got.outputs[r.rid], want.outputs[r.rid])


def test_two_tier_artifact_dedupes_shared_leaves(qat_model, tmp_path):
    """Dense leaves the draft shares by reference with the target must be
    stored ONCE (checkpoint leaf dedup)."""
    import json as _json, os as _os
    cfg, params = qat_model
    sp = DP.compress(cfg, params, target_sparsity=0.5, tile=(16, 16))
    draft = SP.draft_serving(cfg, sp, 0.85)
    d1 = DP.save_artifact(str(tmp_path / "single"), sp, cfg)
    d2 = DP.save_artifact(str(tmp_path / "two"), sp, cfg, draft=draft)

    def n_arrays(d):
        with open(_os.path.join(d, "manifest.json")) as f:
            return _json.load(f)["n_arrays"]

    # the two-tier artifact adds ONLY the draft's packed arrays, not a
    # second copy of embed/norm/head leaves
    n_shared = sum(1 for p in sp.layers for k, v in p.items()
                   if not hasattr(v, "packed")) + 1  # + embed
    assert n_arrays(d2) < 2 * n_arrays(d1) - n_shared + 1


def test_single_tier_artifact_has_no_draft(qat_model, tmp_path):
    cfg, params = qat_model
    sp = DP.compress(cfg, params, target_sparsity=0.5, tile=(16, 16))
    DP.save_artifact(str(tmp_path / "one"), sp, cfg)
    with pytest.raises(ValueError, match="draft"):
        DP.load_artifact(str(tmp_path / "one"), tier="draft")


# ---------------------------------------------------------------------------
# Cost model
# ---------------------------------------------------------------------------


def test_expected_spec_tokens():
    assert PM.expected_spec_tokens(4, 0.0) == pytest.approx(1.0)
    assert PM.expected_spec_tokens(4, 1.0) == pytest.approx(5.0)
    # monotone in acceptance
    vals = [PM.expected_spec_tokens(4, a) for a in (0.1, 0.4, 0.7, 0.95)]
    assert all(b > a for a, b in zip(vals, vals[1:]))


def test_speculative_summary_tradeoff():
    # a free draft with perfect acceptance multiplies throughput by ~k+1
    s = PM.speculative_summary(0.0, 100.0, 4, 1.0)
    assert s["tokens_per_kcycle"] == pytest.approx(50.0)
    # zero acceptance with a costly draft is strictly worse than target-only
    s0 = PM.speculative_summary(100.0, 100.0, 4, 0.0)
    assert s0["tokens_per_round"] == pytest.approx(1.0)
    assert s0["cycles_per_round"] > 100.0


def test_search_spec_picks_simulated_best(qat_model):
    from repro.sched import search_spec
    cfg, _ = qat_model
    res = search_spec(cfg, target_sparsity=0.6,
                      draft_sparsities=(0.8, 0.9), ks=(2, 4),
                      keeps=(0.5,))
    # (2 reprune sparsities + 1 layerskip keep) x 2 ks
    assert len(res.table) == 6
    assert {r["family"] for r in res.table} == {"reprune", "layerskip"}
    best = max(res.table, key=lambda r: r["tokens_per_kcycle"])
    assert res.best == best
    assert res.decision["verdict"] in ("spec", "declined")
    for row in res.table:
        assert row["cycles_per_round"] > 0
        assert 1.0 <= row["tokens_per_round"] <= row["k"] + 1
        # layerskip rounds run k draft steps, reprune k+1
        assert row["draft_steps"] == \
            (row["k"] if row["family"] == "layerskip" else row["k"] + 1)


# ---------------------------------------------------------------------------
# Multi-token attention building block (T>1 generalization)
# ---------------------------------------------------------------------------


def test_decode_attention_multi_t_gt_1_matches_chained(qat_model):
    from repro.models import layers as L
    cfg, params = qat_model
    p = jax.tree.map(lambda a: a[0], params["layers"])
    rng = np.random.default_rng(3)
    B, T, Sv, KV, dh = 2, 3, 12, cfg.n_kv_heads_eff, cfg.dh
    x = jnp.asarray(rng.standard_normal((B, T, cfg.d_model)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, Sv, KV, dh)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, Sv, KV, dh)), jnp.float32)
    pos = jnp.asarray([2, 5], jnp.int32)
    y, kn, vn = L.decode_attention_multi(p, x, kc, vc, pos, cfg)
    assert y.shape == (B, T, cfg.d_model) and kn.shape == (B, T, KV, dh)
    rows = jnp.arange(B)
    kc2, vc2 = kc, vc
    for t in range(T):
        yt, kt, vt = L.decode_attention_multi(p, x[:, t:t + 1], kc2, vc2,
                                              pos + t, cfg)
        np.testing.assert_array_equal(np.asarray(y[:, t]),
                                      np.asarray(yt[:, 0]), err_msg=f"t={t}")
        kc2 = kc2.at[rows, pos + t].set(kt[:, 0])
        vc2 = vc2.at[rows, pos + t].set(vt[:, 0])


# ---------------------------------------------------------------------------
# Layer-skip draft family: masks, importance, bit-exactness
# ---------------------------------------------------------------------------


def test_decode_step_masked_all_on_matches_paged(qat_model):
    """With every sublayer on, the masked step IS decode_step_paged -
    bit for bit (the identity the layer-skip draft degrades from)."""
    cfg, params = qat_model
    sp = DP.compress(cfg, params, target_sparsity=0.5, tile=(16, 16))
    sxp = ST.stack(sp)
    rng = np.random.default_rng(11)
    B, Sv, KV, dh = 2, 10, cfg.n_kv_heads_eff, cfg.dh
    vk = jnp.asarray(rng.standard_normal((cfg.n_layers, B, Sv, KV, dh)),
                     jnp.float32)
    vv = jnp.asarray(rng.standard_normal((cfg.n_layers, B, Sv, KV, dh)),
                     jnp.float32)
    pos = jnp.asarray([3, 6], jnp.int32)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, 1)), jnp.int32)
    ones = jnp.ones(cfg.n_layers, jnp.float32)
    want_l, want_k, want_v = ST.decode_step_paged(sxp, vk, vv, pos, toks, cfg)
    got_l, got_k, got_v = ST.decode_step_masked(sxp, vk, vv, pos, toks, cfg,
                                               ones, ones)
    np.testing.assert_array_equal(np.asarray(want_l), np.asarray(got_l))
    np.testing.assert_array_equal(np.asarray(want_k), np.asarray(got_k))
    np.testing.assert_array_equal(np.asarray(want_v), np.asarray(got_v))


def test_layerskip_masks_rank_and_floor():
    L = 4
    # keep=1: everything on
    a_on, m_on = SP.layerskip_masks(L, 1.0)
    assert a_on == (1,) * L and m_on == (1,) * L
    # positional prior drops MLPs front-first, then attentions front-first
    a_on, m_on = SP.layerskip_masks(L, 0.5)
    assert m_on == (0, 0, 0, 0) and a_on == (1, 1, 1, 1)
    assert SP.kept_fraction(a_on, m_on) == 0.5
    # the LAST layer's attention survives even the floor
    a_on, m_on = SP.layerskip_masks(L, 0.0)
    assert a_on[-1] == 1 and sum(a_on) + sum(m_on) == 1
    # nnz importance overrides position: dead units (score 0) go first
    attn_imp = np.array([5.0, 0.0, 7.0, 9.0])
    mlp_imp = np.array([3.0, 0.0, 8.0, 6.0])
    a_on, m_on = SP.layerskip_masks(L, 0.5, importance=(attn_imp, mlp_imp))
    assert a_on[1] == 0 and m_on[1] == 0  # both dead units dropped
    assert m_on[0] == 0 and a_on[0] == 0  # then the cheapest live ones
    assert a_on[3] == 1 and m_on[2] == 1  # most important survive


def _dead_sublayer_packing(cfg, params):
    """A compressed packing with known dead sublayers: wk/wv are zero in
    every layer (nothing survives pruning, so every attention outputs 0)
    and layer 0's w_down is zero (its MLP outputs 0)."""
    layers = dict(params["layers"])
    layers["wk"] = jnp.zeros_like(layers["wk"])
    layers["wv"] = jnp.zeros_like(layers["wv"])
    layers["w_down"] = layers["w_down"].at[0].set(0.0)
    return DP.compress(cfg, {**params, "layers": layers},
                       target_sparsity=0.6,
                       schedule=DP.default_schedule(cfg))


def test_sublayer_importance_detects_dead_sublayers(qat_model):
    """The nnz ranking must score pruning-killed sublayers exactly 0
    (skipping them is free)."""
    cfg, params = qat_model
    sxp = ST.stack(_dead_sublayer_packing(cfg, params))
    attn, mlp = SP.sublayer_importance(sxp)
    assert attn.shape == (cfg.n_layers,) and mlp.shape == (cfg.n_layers,)
    assert np.all(attn >= 0) and np.all(mlp >= 0)
    # wk/wv lost every block -> every attention is dead
    assert np.all(attn == 0)
    assert mlp[0] == 0 and np.all(mlp[1:] > 0)
    # masks at keep=0.5 must then shed ONLY dead/cheapest units
    a_on, m_on = SP.layerskip_masks(cfg.n_layers, 0.5,
                                    importance=(attn, mlp))
    dropped = [(k, li) for k, on in (("attn", a_on), ("mlp", m_on))
               for li, v in enumerate(on) if v == 0]
    imp = {"attn": attn, "mlp": mlp}
    kept_scores = [imp[k][li] for k, on in (("attn", a_on), ("mlp", m_on))
                   for li, v in enumerate(on) if v == 1]
    assert all(imp[k][li] <= min(kept_scores) for k, li in dropped)


def test_layerskip_spec_matches_scan(qat_model):
    """Greedy bit-exactness for the layerskip family: no draft packing,
    the draft runs a sublayer subset of the TARGET envelope."""
    cfg, params = qat_model
    sp = _dead_sublayer_packing(cfg, params)
    bcfg = BatchConfig(**_BCFG)
    want = BatchServer(cfg, sp, ServeConfig(), bcfg, engine="scan"
                       ).run(_trace(cfg))
    srv = BatchServer(cfg, sp, ServeConfig(), bcfg, engine="spec",
                      spec=SpecConfig(k=3, draft="layerskip", keep=0.5))
    rep = srv.run(_trace(cfg))
    for r in _trace(cfg):
        np.testing.assert_array_equal(rep.outputs[r.rid],
                                      want.outputs[r.rid], err_msg=r.rid)
    st = rep.spec
    assert st["family"] == "layerskip" and st["keep"] == 0.5
    # the nnz masks shed the dead sublayers -> the draft actually agrees
    assert st["acceptance_rate"] >= 0.3
    assert sum(st["accepted_len_hist"]) == st["slot_rounds"]


def test_layerskip_spec_matches_scan_macro2():
    """Layerskip spec decode over a macro-sharded TARGET envelope (the
    draft shares it - nothing extra to shard) reproduces single-device
    target-only tokens at mesh macro=2 (subprocess: forced host devices
    must exist before jax imports)."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = " ".join(
        ([env["XLA_FLAGS"]] if env.get("XLA_FLAGS") else [])
        + ["--xla_force_host_platform_device_count=8"])
    env["PYTHONPATH"] = os.path.join(repo, "src")
    code = """
import numpy as np, jax
from repro.models import registry
from repro.serve import BatchConfig, BatchServer, ServeConfig, Request, SpecConfig
from repro.serve import deployed as DP
from repro.launch.shardings import macro_mesh

cfg = registry.get_smoke_config("yi-6b", dtype="float32", cim_mode="qat")
params = registry.model_fns(cfg).init_params(cfg, jax.random.PRNGKey(0))
def trace():
    rng = np.random.default_rng(7)
    return [Request(f"r{i}", rng.integers(0, cfg.vocab, int(rng.integers(2, 10))),
                    int(rng.integers(1, 7))) for i in range(3)]
sp = DP.compress(cfg, params, target_sparsity=0.5, tile=(16, 16))
bcfg = BatchConfig(n_slots=2, block_size=4, n_blocks=24)
want = BatchServer(cfg, sp, ServeConfig(), bcfg, engine="scan").run(trace())
mesh = macro_mesh(2)
srv = BatchServer(cfg, DP.shard(sp, mesh), ServeConfig(), bcfg, mesh=mesh,
                  engine="spec", spec=SpecConfig(k=3, draft="layerskip", keep=0.5))
assert any(sw.mesh is not None for sw in srv._params.target.packed.values()), \\
    "no target envelope actually sharded"
assert srv._params.draft is None, "layerskip must not carry a draft packing"
rep = srv.run(trace())
for r in trace():
    np.testing.assert_array_equal(rep.outputs[r.rid], want.outputs[r.rid],
                                  err_msg=f"macro=2 {r.rid}")
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=repo, timeout=420)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "OK" in r.stdout


def test_layerskip_server_rejects_draft(qat_model):
    cfg, params = qat_model
    sp = DP.compress(cfg, params, target_sparsity=0.5, tile=(16, 16))
    with pytest.raises(ValueError, match="layerskip"):
        BatchServer(cfg, sp, ServeConfig(), BatchConfig(**_BCFG),
                    engine="spec", draft=sp,
                    spec=SpecConfig(k=2, draft="layerskip", keep=0.5))


# ---------------------------------------------------------------------------
# Adaptive k: collapse / recovery state machine
# ---------------------------------------------------------------------------


def test_adaptive_k_collapse_and_recovery():
    ad = SP.AdaptiveK(k_max=4, ewma=0.5, collapse_below=0.2,
                      expand_above=0.6)
    assert ad.k == 4 and ad.acc == pytest.approx(0.6)  # optimistic start
    assert ad.observe(4, 0) == 4          # acc 0.30: in the band, hold
    assert ad.observe(4, 0) == 1          # acc 0.15 < 0.2: COLLAPSE
    assert ad.collapses == 1
    # recovery through the doubling ladder on perfect probe acceptance
    assert ad.observe(1, 1) == 1          # acc 0.575: still below expand
    assert ad.observe(1, 1) == 2          # acc 0.7875 >= 0.6: 1 -> 2
    assert ad.observe(2, 2) == 4          # 2 -> 4 (capped at k_max)
    assert ad.expands == 2 and ad.k == 4
    assert ad.observe(4, 4) == 4          # at k_max: no further expand
    assert ad.expands == 2


def test_adaptive_k_hysteresis_band_holds():
    ad = SP.AdaptiveK(k_max=8, ewma=0.35, collapse_below=0.2,
                      expand_above=0.6)
    ad.observe(8, 0)  # knock acc below expand_above
    k0, c0, e0 = ad.k, ad.collapses, ad.expands
    for _ in range(20):
        assert ad.observe(k0, int(0.4 * k0)) == k0  # borderline slot
    assert ad.collapses == c0 and ad.expands == e0


def test_adaptive_k_collapses_in_server(qat_model):
    """A mismatched layerskip draft (positional masks on a packing whose
    live compute is elsewhere) must drive per-slot k down; greedy tokens
    stay bit-identical through every k trajectory."""
    cfg, params = qat_model
    sp = _dead_sublayer_packing(cfg, params)
    bcfg = BatchConfig(**_BCFG)
    want = BatchServer(cfg, sp, ServeConfig(), bcfg, engine="scan"
                       ).run(_trace(cfg))
    srv = BatchServer(cfg, sp, ServeConfig(), bcfg, engine="spec",
                      spec=SpecConfig(k=4, draft="layerskip", keep=0.25))
    # keep=0.25 with nnz masks keeps only the live layer-0 MLP path's
    # cheapest units - force the POSITIONAL prior instead so the draft
    # mispredicts and the tracker must collapse
    import jax.numpy as jnp2
    a_on, m_on = SP.layerskip_masks(cfg.n_layers, 0.25)
    srv.spec_masks = (a_on, m_on)
    srv._attn_on = jnp2.asarray(a_on, jnp2.float32)
    srv._mlp_on = jnp2.asarray(m_on, jnp2.float32)
    rep = srv.run(_trace(cfg))
    for r in _trace(cfg):
        np.testing.assert_array_equal(rep.outputs[r.rid],
                                      want.outputs[r.rid], err_msg=r.rid)


# ---------------------------------------------------------------------------
# Calibration: measured rows -> fitted prior -> search decision
# ---------------------------------------------------------------------------


def test_calibration_roundtrip_and_fit(qat_model):
    from repro.sched.search import SpecCalibration, search_spec
    cfg, _ = qat_model
    cal = SpecCalibration()
    cal.add(cfg.name, "layerskip", 0.5, 0.7, weight=120.0)
    cal.add(cfg.name, "layerskip", 0.25, 0.9, weight=80.0)
    cal2 = SpecCalibration.from_json(cal.to_json())
    m = cal2.accept_model(cfg.name, "layerskip")
    # exact re-queries reproduce the measurements (the other measured
    # point keeps a sub-percent inverse-distance share)
    assert m(0.5) == pytest.approx(0.7, abs=5e-3)
    assert m(0.25) == pytest.approx(0.9, abs=5e-3)
    # in-between gaps interpolate inside the measured bracket
    assert 0.7 < m(0.4) < 0.9
    # the fitted prior prices the search: the winning row's expected
    # tokens/round must be the cost model's at the fitted acceptance
    res = search_spec(cfg, target_sparsity=0.6, calibration=cal2,
                      arch=cfg.name, ks=(2, 4), draft_sparsities=(0.85,),
                      keeps=(0.5, 0.75))
    for row in res.table:
        if row["accept_source"] == "calibrated":
            want = PM.expected_spec_tokens(row["k"], row["accept"])
            # both row fields are rounded to 4 decimals in the summary
            assert row["tokens_per_round"] == pytest.approx(want, abs=1e-3)
    assert any(r["accept_source"] == "calibrated" for r in res.table)


def test_calibration_rejects_malformed():
    from repro.sched.search import SpecCalibration
    with pytest.raises(ValueError):
        SpecCalibration.from_json({"schema": 99, "rows": []})
    with pytest.raises(ValueError):
        SpecCalibration.from_json({"schema": 1, "rows": [{"arch": "a"}]})
    cal = SpecCalibration()
    with pytest.raises(ValueError):
        cal.add("a", "layerskip", 0.5, 1.5)  # accept out of range
    with pytest.raises(ValueError):
        cal.add("a", "layerskip", 0.5, 0.5, weight=0.0)


def test_calibration_trust_decays_to_prior(qat_model):
    from repro.sched.search import SpecCalibration
    cfg, _ = qat_model
    cal = SpecCalibration()
    cal.add(cfg.name, "layerskip", 0.5, 0.95, weight=100.0)
    prior = lambda g: max(0.0, 1.0 - g)
    m = cal.accept_model(cfg.name, "layerskip", prior=prior)
    # at the measured gap: the measurement
    assert m(0.5) == pytest.approx(0.95, abs=1e-3)
    # far from all data the answer falls back TOWARD the prior instead of
    # flat-extrapolating the single measurement across the knob axis
    far = m(0.9)
    assert prior(0.9) < far < 0.95
    assert far - prior(0.9) < 0.95 - prior(0.9)


def test_search_spec_declines_when_calibrated_dead(qat_model):
    """Measured-dead acceptance across both families must produce the
    'declined' verdict - the auto policy never ships a modeled loss."""
    from repro.sched.search import SpecCalibration, search_spec
    cfg, _ = qat_model
    cal = SpecCalibration()
    for fam, gaps in (("reprune", (0.15, 0.25, 0.35)),
                      ("layerskip", (0.25, 0.5, 0.75))):
        for g in gaps:
            cal.add(cfg.name, fam, g, 0.0, weight=500.0)
    res = search_spec(cfg, target_sparsity=0.6, calibration=cal,
                      arch=cfg.name)
    d = res.decision
    assert d["verdict"] == "declined" and d["reason"] == "scan wins"
    assert d["accept_source"] == "calibrated"


def test_spec_stats_histogram_and_counters():
    st = SP.SpecStats(k=4, draft_sparsity=0.0, family="layerskip", keep=0.5)
    st.record(n_proposed=4, n_accepted=4, n_emitted=5)
    st.record(n_proposed=4, n_accepted=0, n_emitted=1)
    st.record(n_proposed=1, n_accepted=1, n_emitted=2)  # collapsed round
    j = st.to_json()
    assert j["family"] == "layerskip" and j["keep"] == 0.5
    assert j["proposed"] == 9 and j["accepted"] == 5
    assert j["spec_accepted_tokens"] == 5
    assert j["spec_rejected_tokens"] == 4
    assert j["accepted_len_hist"] == [1, 1, 0, 0, 1]
    assert j["acceptance_rate"] == pytest.approx(5 / 9, abs=1e-3)
