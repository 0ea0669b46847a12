"""Uniform-envelope stacked deployment + compiled (lax.scan) runtime tests.

The contracts under test:

  * envelope honesty: ``stack_deployed`` pads the slot axis but keeps the
    per-layer ``nnz``/``row_idx`` exact, so the layer-indexed stacked kernel
    is BIT-IDENTICAL to the per-layer ``deployed_matmul`` - including
    all-zero layers (nothing survives), fully-dense layers (maximal
    ``nnz_max``: the envelope for everyone else), and truncated layers
    (true counts exceed stored slots - padding must stay inert);
  * runtime honesty: the scan runtime (``serve.stacked`` /
    ``BatchServer(engine="scan")``) reproduces the loop runtime's greedy
    tokens exactly - dense and compressed, single-device and macro-sharded;
  * artifact honesty: ``save_artifact``/``load_artifact`` round-trips the
    packed model (int8 blocks stay int8, mesh never serialized) and the
    booted model serves identical tokens;
  * uniform-tile mode: the search only returns network-feasible tiles and
    the schedule exposes them as one envelope.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import deploy as D
from repro.core.cim_layer import CIMConfig
from repro.core.mapping import pack_bsr
from repro.core.quant import QuantConfig
from repro.core.sparsity import SparsityConfig
from repro.models import registry
from repro.serve import (BatchConfig, BatchServer, Engine, Request,
                         ServeConfig)
from repro.serve import deployed as DP
from repro.serve import stacked as ST
from repro.train import checkpoint as ckpt


def _cim(ts=0.5):
    return CIMConfig(
        quant=QuantConfig(w_bits=8, a_bits=8, group_size=16, a_signed=True),
        sparsity=SparsityConfig(alpha=16, n=16, target_sparsity=ts),
        mode="qat")


def _layer_stack(seed=0, d_in=64, d_out=128, bk=16, bn=16):
    """Four layers spanning the envelope edge cases: no pruning (densest
    layer sets the envelope), paper sparsity, extreme sparsity, all-zero."""
    cim = _cim()
    rng = np.random.default_rng(seed)
    dws, ws = [], []
    for ts in (0.0, 0.5, 0.9, 1.0):
        w = rng.standard_normal((d_in, d_out)).astype(np.float32) * 0.2
        if ts >= 1.0:
            w = np.zeros_like(w)
            ts = 0.5
        ws.append(w)
        dws.append(D.deploy_weight(w, cim, bk=bk, bn=bn, target_sparsity=ts))
    return dws, ws


# ---------------------------------------------------------------------------
# Envelope padding: stacked layer-indexed kernel == per-layer kernel
# ---------------------------------------------------------------------------


def test_stack_deployed_envelope_geometry():
    dws, _ = _layer_stack()
    sw = D.stack_deployed(dws)
    nnz_maxes = [dw.packed[0]["row_idx"].shape[1] for dw in dws]
    assert sw.blocks.shape[:2] == (4, 8)
    assert sw.blocks.shape[2] == max(nnz_maxes)  # padded to the max
    # per-layer counts stay exact - padding is envelope-only
    for i, dw in enumerate(dws):
        np.testing.assert_array_equal(np.asarray(sw.nnz[i]),
                                      np.asarray(dw.packed[0]["nnz"]))
    # padding slots carry zero scales (inert even past a truncated guard)
    for i, nm in enumerate(nnz_maxes):
        if nm < sw.blocks.shape[2]:
            assert float(np.abs(np.asarray(sw.scales[i][:, nm:])).max()) == 0.0
            assert float(np.abs(np.asarray(sw.blocks[i][:, nm:])).max()) == 0.0


def test_stacked_kernel_matches_per_layer_bit_exact():
    dws, _ = _layer_stack()
    sw = D.stack_deployed(dws)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((5, 64)), jnp.float32)
    for i, dw in enumerate(dws):
        want = np.asarray(D.deployed_matmul(x, dw, a_bits=8, interpret=True))
        got = np.asarray(D.stacked_matmul(x, sw, i, a_bits=8, interpret=True))
        np.testing.assert_array_equal(got, want, err_msg=f"layer {i}")


def test_stacked_kernel_under_scan_matches_per_layer():
    """The traced layer index (a scan carry) must hit the same kernel."""
    dws, _ = _layer_stack(seed=3)
    sw = D.stack_deployed(dws)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((4, 64)),
                    jnp.float32)

    def body(c, i):
        return c, D.stacked_matmul(x, sw, i, a_bits=8, interpret=True)

    _, ys = jax.jit(lambda: jax.lax.scan(body, 0, jnp.arange(4)))()
    for i, dw in enumerate(dws):
        want = np.asarray(D.deployed_matmul(x, dw, a_bits=8, interpret=True))
        np.testing.assert_array_equal(np.asarray(ys[i]), want,
                                      err_msg=f"layer {i}")


def test_stacked_all_zero_layer_outputs_zero():
    dws, _ = _layer_stack()
    sw = D.stack_deployed(dws)
    x = jnp.ones((3, 64), jnp.float32)
    out = np.asarray(D.stacked_matmul(x, sw, 3, interpret=True))
    assert np.all(out == 0.0)
    assert int(np.asarray(sw.nnz[3]).sum()) == 0


def test_stacked_truncated_layer_padding_is_inert():
    """A layer packed with nnz_max SMALLER than its true counts (truncation)
    keeps ``nnz`` > stored slots; when the stacked guard walks past the
    stored slots into envelope padding, the zero blocks/scales must
    contribute exactly nothing - parity with the per-layer kernel holds."""
    rng = np.random.default_rng(5)
    levels = rng.integers(-127, 128, (64, 128)).astype(np.int8)
    scale = 1.0 / 2.0 ** 7

    def mk(bsr):
        return D.DeployedWeight([{
            "blocks": jnp.asarray(bsr.blocks),
            "scales": jnp.asarray(np.full(bsr.row_idx.shape, scale,
                                          np.float32)),
            "row_idx": jnp.asarray(bsr.row_idx),
            "nnz": jnp.asarray(bsr.nnz),
            "density": bsr.density,
        }], 64, 128, 8)

    trunc = mk(pack_bsr(levels, 16, 16, nnz_max=2))
    full = mk(pack_bsr(levels, 16, 16))
    assert int(np.asarray(trunc.packed[0]["nnz"]).max()) > 2  # truly truncated
    sw = D.stack_deployed([trunc, full])  # envelope >> truncated slots
    assert sw.blocks.shape[2] > 2
    x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    for i, dw in enumerate((trunc, full)):
        want = np.asarray(D.deployed_matmul(x, dw, interpret=True))
        got = np.asarray(D.stacked_matmul(x, sw, i, interpret=True))
        np.testing.assert_array_equal(got, want, err_msg=f"layer {i}")


def test_stack_deployed_rejects_mixed_geometry():
    cim = _cim()
    rng = np.random.default_rng(0)
    a = D.deploy_weight(rng.standard_normal((64, 128)).astype(np.float32),
                        cim, bk=16, bn=16, target_sparsity=0.5)
    b = D.deploy_weight(rng.standard_normal((64, 128)).astype(np.float32),
                        cim, bk=32, bn=16, target_sparsity=0.5)
    with pytest.raises(ValueError, match="uniform"):
        D.stack_deployed([a, b])
    c = D.deploy_weight(rng.standard_normal((64, 64)).astype(np.float32),
                        cim, bk=16, bn=16, target_sparsity=0.5)
    with pytest.raises(ValueError, match="geometry"):
        D.stack_deployed([a, c])


def test_stacked_weight_pytree_roundtrip():
    dws, _ = _layer_stack()
    sw = D.stack_deployed(dws)
    leaves, treedef = jax.tree.flatten(sw)
    sw2 = jax.tree.unflatten(treedef, leaves)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((3, 64)),
                    jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(D.stacked_matmul(x, sw, 1, interpret=True)),
        np.asarray(D.stacked_matmul(x, sw2, 1, interpret=True)))


def test_stacked_parity_hypothesis():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    cim = _cim()

    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1), st.integers(2, 4),
           st.sampled_from([(32, 32), (64, 32), (32, 64)]))
    def prop(seed, n_layers, shape):
        d_in, d_out = shape
        rng = np.random.default_rng(seed)
        dws = []
        for _ in range(n_layers):
            w = rng.standard_normal((d_in, d_out)).astype(np.float32)
            ts = float(rng.uniform(0.0, 0.95))
            dws.append(D.deploy_weight(w, cim, bk=16, bn=16,
                                       target_sparsity=ts))
        sw = D.stack_deployed(dws)
        x = jnp.asarray(rng.standard_normal((3, d_in)), jnp.float32)
        for i, dw in enumerate(dws):
            np.testing.assert_array_equal(
                np.asarray(D.stacked_matmul(x, sw, i, a_bits=8,
                                            interpret=True)),
                np.asarray(D.deployed_matmul(x, dw, a_bits=8,
                                             interpret=True)))

    prop()


# ---------------------------------------------------------------------------
# Retrace bucketing (deployed_matmul row tiles)
# ---------------------------------------------------------------------------


def test_bm_for_rows_bucket_ladder():
    assert [D.bm_for_rows(n) for n in (1, 7, 8)] == [8, 8, 8]
    assert [D.bm_for_rows(n) for n in (9, 16)] == [16, 16]
    assert D.bm_for_rows(17) == 32
    assert D.bm_for_rows(100) == 128
    assert D.bm_for_rows(5000) == 128  # capped
    # admission growing the active batch 1..8 shares ONE bucket
    assert len({D.bm_for_rows(n) for n in range(1, 9)}) == 1


def test_deployed_matmul_same_result_across_buckets():
    dws, ws = _layer_stack()
    rng = np.random.default_rng(9)
    x12 = jnp.asarray(rng.standard_normal((12, 64)), jnp.float32)
    # rows 12 pads to a 16-bucket; each row's result must equal the same
    # row computed alone (8-bucket) - bucketing never changes numerics
    full = np.asarray(D.deployed_matmul(x12, dws[1], a_bits=8, interpret=True))
    one = np.asarray(D.deployed_matmul(x12[:1], dws[1], a_bits=8,
                                       interpret=True))
    np.testing.assert_array_equal(full[:1], one)


def test_serving_jit_rounds_every_op_to_its_dtype():
    """A serving step's bf16 math rounds exactly as the program states,
    op by op - not at whatever precision the compiler's fusions keep (a
    plain jax.jit of this function differs from the op-by-op result on
    the CPU) - so the bits cannot depend on how a mesh changes fusion."""
    def f(x, w):
        h = x @ w
        h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True)
                              + jnp.bfloat16(1e-5))
        return jax.nn.softmax(h, -1) * h + jax.nn.silu(h)

    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (64, 256), jnp.bfloat16)
    w = jax.random.normal(kw, (256, 512), jnp.bfloat16)
    with jax.disable_jit():
        op_by_op = np.asarray(f(x, w), np.float32)
    got = np.asarray(ST.serving_jit(f)(x, w), np.float32)
    np.testing.assert_array_equal(got, op_by_op)


# ---------------------------------------------------------------------------
# Scan runtime == loop runtime (tokens bit-exact)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def qat_model():
    cfg = registry.get_smoke_config("yi-6b", dtype="float32", cim_mode="qat")
    params = registry.model_fns(cfg).init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _trace(cfg, n=5, seed=7, max_prompt=12, max_new=7):
    rng = np.random.default_rng(seed)
    return [Request(f"r{i}",
                    rng.integers(0, cfg.vocab, int(rng.integers(2, max_prompt))),
                    int(rng.integers(1, max_new))) for i in range(n)]


@pytest.mark.parametrize("ts", [0.0, 0.5])
def test_scan_batch_server_matches_loop_compressed(qat_model, ts):
    cfg, params = qat_model
    sp = DP.compress(cfg, params, target_sparsity=ts, tile=(16, 16))
    bcfg = BatchConfig(n_slots=2, block_size=4, n_blocks=24)
    want = BatchServer(cfg, sp, ServeConfig(), bcfg).run(_trace(cfg))
    got = BatchServer(cfg, sp, ServeConfig(), bcfg,
                      engine="scan").run(_trace(cfg))
    for r in _trace(cfg):
        np.testing.assert_array_equal(got.outputs[r.rid], want.outputs[r.rid],
                                      err_msg=f"ts={ts} {r.rid}")


def test_scan_batch_server_matches_loop_dense(qat_model):
    cfg, params = qat_model
    sp = DP.from_params(cfg, params)
    bcfg = BatchConfig(n_slots=2, block_size=4, n_blocks=24)
    want = BatchServer(cfg, sp, ServeConfig(), bcfg).run(_trace(cfg, seed=11))
    got = BatchServer(cfg, sp, ServeConfig(), bcfg,
                      engine="scan").run(_trace(cfg, seed=11))
    for r in _trace(cfg, seed=11):
        np.testing.assert_array_equal(got.outputs[r.rid], want.outputs[r.rid])


def test_scan_engine_matches_loop_engine(qat_model):
    cfg, params = qat_model
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(4).integers(0, cfg.vocab, (2, 7)), jnp.int32)}
    sp = DP.compress(cfg, params, target_sparsity=0.5, tile=(16, 16))
    want = Engine(cfg, sp, ServeConfig(max_new_tokens=5),
                  fns=DP.model_fns(cfg)).generate(batch)
    got = Engine(cfg, ST.stack(sp), ServeConfig(max_new_tokens=5),
                 fns=ST.model_fns(cfg)).generate(batch)
    np.testing.assert_array_equal(got, want)


def test_stack_validates_mixed_packing(qat_model):
    cfg, params = qat_model
    sp = DP.compress(cfg, params, target_sparsity=0.5, tile=(16, 16))
    sp.layers[1]["wq"] = jnp.zeros((cfg.d_model,
                                    cfg.n_heads_eff * cfg.dh), jnp.float32)
    with pytest.raises(ValueError, match="packed in"):
        ST.stack(sp)


def test_server_rejects_unknown_engine(qat_model):
    cfg, params = qat_model
    with pytest.raises(ValueError, match="engine"):
        BatchServer(cfg, DP.from_params(cfg, params), engine="vliw")


def test_scan_matches_loop_macro_sharded():
    """Acceptance: the scan runtime over a macro-sharded uniform envelope
    reproduces the single-device loop runtime's tokens at macro=2 and 4
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
from repro.serve import BatchConfig, BatchServer, ServeConfig, Request
from repro.serve import deployed as DP
from repro.launch.shardings import macro_mesh

cfg = registry.get_smoke_config("yi-6b", dtype="float32", cim_mode="qat")
params = registry.model_fns(cfg).init_params(cfg, jax.random.PRNGKey(0))
def trace():
    rng = np.random.default_rng(7)
    return [Request(f"r{i}", rng.integers(0, cfg.vocab, int(rng.integers(2, 12))),
                    int(rng.integers(1, 7))) for i in range(4)]
sp = DP.compress(cfg, params, target_sparsity=0.5, tile=(16, 16))
bcfg = BatchConfig(n_slots=2, block_size=4, n_blocks=24)
want = BatchServer(cfg, sp, ServeConfig(), bcfg).run(trace())
for n in (2, 4):
    mesh = macro_mesh(n)
    sps = DP.shard(sp, mesh)
    srv = BatchServer(cfg, sps, ServeConfig(), bcfg, mesh=mesh, engine="scan")
    assert any(sw.mesh is not None for sw in srv._params.packed.values()), \\
        "no envelope actually sharded"
    rep = srv.run(trace())
    for r in trace():
        np.testing.assert_array_equal(rep.outputs[r.rid], want.outputs[r.rid],
                                      err_msg=f"macro={n} {r.rid}")
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=repo, timeout=420)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "OK" in r.stdout


# ---------------------------------------------------------------------------
# Offline artifacts: pack once, boot bit-identically
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrips_deployed_and_stacked(tmp_path):
    dws, _ = _layer_stack()
    sw = D.stack_deployed(dws)
    tree = {"dw": dws[1], "sw": sw, "raw": jnp.arange(6, dtype=jnp.int8)}
    ckpt.save_pytree(str(tmp_path / "c"), tree, extra={"k": 1})
    got, manifest = ckpt.load_pytree(str(tmp_path / "c"))
    assert manifest["extra"] == {"k": 1}
    assert isinstance(got["dw"], D.DeployedWeight)
    assert isinstance(got["sw"], D.StackedWeight)
    assert got["sw"].mesh is None and got["dw"].mesh is None
    # int8 leaves round-trip as int8 - no float detour
    assert got["raw"].dtype == jnp.int8
    assert np.asarray(got["sw"].blocks).dtype == np.int8
    np.testing.assert_array_equal(np.asarray(got["sw"].blocks),
                                  np.asarray(sw.blocks))
    x = jnp.asarray(np.random.default_rng(0).standard_normal((3, 64)),
                    jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(D.stacked_matmul(x, got["sw"], 2, interpret=True)),
        np.asarray(D.stacked_matmul(x, sw, 2, interpret=True)))
    np.testing.assert_array_equal(
        np.asarray(D.deployed_matmul(x, got["dw"], interpret=True)),
        np.asarray(D.deployed_matmul(x, dws[1], interpret=True)))


def test_checkpoint_refuses_sharded_serialization():
    from jax.sharding import Mesh
    dws, _ = _layer_stack()
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("macro",))
    dw = dws[1]
    dw_sharded = D.DeployedWeight(dw.packed, dw.d_in, dw.d_out, dw.bits,
                                  mesh=mesh)
    with pytest.raises(ValueError, match="mesh"):
        ckpt.save_pytree("/tmp/never-written", dw_sharded)


def test_artifact_roundtrip_serves_identically(qat_model, tmp_path):
    cfg, params = qat_model
    sp = DP.compress(cfg, params, target_sparsity=0.5, tile=(16, 16))
    DP.save_artifact(str(tmp_path / "art"), sp, cfg, extra={"note": "t"})
    sp2, meta = DP.load_artifact(str(tmp_path / "art"))
    assert meta["arch"] == cfg.name and meta["note"] == "t"
    bcfg = BatchConfig(n_slots=2, block_size=4, n_blocks=24)
    want = BatchServer(cfg, sp, ServeConfig(), bcfg).run(_trace(cfg))
    for engine in ("loop", "scan"):
        rep = BatchServer(cfg, sp2, ServeConfig(), bcfg,
                          engine=engine).run(_trace(cfg))
        for r in _trace(cfg):
            np.testing.assert_array_equal(rep.outputs[r.rid],
                                          want.outputs[r.rid],
                                          err_msg=f"{engine} {r.rid}")


def test_artifact_rebuilds_tied_head(tmp_path):
    """A tied-embeddings model's head_t is derived, not stored - the loader
    must rebuild it."""
    cfg = registry.get_smoke_config("yi-6b", dtype="float32")
    params = registry.model_fns(cfg).init_params(cfg, jax.random.PRNGKey(1))
    params.pop("head", None)  # force the tied path
    sp = DP.from_params(cfg, params)
    assert sp.head is None and sp.head_t is not None
    d = DP.save_artifact(str(tmp_path / "tied"), sp, cfg)
    import json as _json, os as _os
    with open(_os.path.join(d, "manifest.json")) as f:
        n_arrays = _json.load(f)["n_arrays"]
    sp2, _ = DP.load_artifact(str(tmp_path / "tied"))
    assert sp2.head_t is not None
    np.testing.assert_array_equal(np.asarray(sp2.head_t),
                                  np.asarray(sp.head_t))
    # head_t was NOT serialized (derived data stays out of the artifact)
    flat_with_head = len(jax.tree.leaves(sp))
    assert n_arrays == flat_with_head - 1


# ---------------------------------------------------------------------------
# Uniform-tile mode
# ---------------------------------------------------------------------------


def test_uniform_search_only_feasible_tiles(qat_model):
    from repro.sched import lm_graph
    from repro.sched.search import (search_mapping, tile_divides_graph,
                                    uniform_tile_candidates)
    cfg, _ = qat_model
    graph = lm_graph(cfg, seq_len=32)
    res = search_mapping(graph, groups=(16, 48), alphas=(16, 48),
                        uniform=True)
    for row in res.table:
        assert tile_divides_graph(graph, row.candidate.group,
                                  row.candidate.alpha)
    cands = uniform_tile_candidates(graph, (16, 48), (16, 48))
    assert all(tile_divides_graph(graph, c.group, c.alpha) for c in cands)
    # 48 divides neither d_model=64 nor d_ff=128
    assert not tile_divides_graph(graph, 48, 16)


def test_schedule_uniform_tile_property(qat_model):
    cfg, _ = qat_model
    sched = DP.default_schedule(cfg, uniform=True)
    g, a = sched.uniform_tile
    assert all((s.group, s.alpha) == (g, a) for s in sched.layers)


def test_compress_uniform_packs_one_tile(qat_model):
    cfg, params = qat_model
    sp = DP.compress(cfg, params, target_sparsity=0.5, tile=(16, 16),
                     uniform=True)
    tiles = {dw.tile for dw in sp.deployed().values()}
    assert len(tiles) == 1, tiles
    net_tile = tiles.pop()
    sxp = ST.stack(sp)  # the uniform envelope must be stackable
    assert sxp.packed
    assert all(sw.tile == net_tile for sw in sxp.packed.values())


# ---------------------------------------------------------------------------
# Tied-head precompute
# ---------------------------------------------------------------------------


def test_head_t_precomputed_once():
    cfg = registry.get_smoke_config("yi-6b", dtype="float32")
    params = registry.model_fns(cfg).init_params(cfg, jax.random.PRNGKey(0))
    sp = DP.from_params(cfg, params)
    assert sp.head is not None and sp.head_t is None  # untied: no cache
    params.pop("head")
    spt = DP.from_params(cfg, params)
    assert spt.head is None
    np.testing.assert_array_equal(np.asarray(spt.head_t),
                                  np.asarray(params["embed"]).T)
    assert DP._head(spt) is spt.head_t  # the SAME array every call
