"""Deployment path: QAT-sim oracle == BSR-kernel serving path, the packing
round-trip / kernel differential suite over randomized shapes, tilings and
sparsity levels, plus the Table IV-style storage accounting on a trained
LM."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import deploy
from repro.core import mapping as M
from repro.core.cim_layer import CIMConfig
from repro.core.quant import QuantConfig
from repro.core.sparsity import SparsityConfig
from repro.kernels import cim_bsr_matmul as K
from repro.models import registry


def _cim(w_bits=4, a_bits=8, ts=0.5, alpha=16):
    return CIMConfig(
        quant=QuantConfig(w_bits=w_bits, a_bits=a_bits, group_size=alpha,
                          a_signed=True),
        sparsity=SparsityConfig(alpha=alpha, n=alpha, target_sparsity=ts),
        mode="qat",
    )


@pytest.mark.parametrize("w_bits,ts", [(4, 0.5), (8, 0.75), (4, 0.0)])
def test_deployed_matmul_matches_reference(w_bits, ts):
    cim = _cim(w_bits=w_bits, ts=ts)
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (128, 64)) * 0.2
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 128))
    dw = deploy.deploy_weight(w, cim, bk=16, bn=16, target_sparsity=ts)
    got = deploy.deployed_matmul(x, dw, a_bits=cim.quant.a_bits,
                                 interpret=True)
    want = deploy.reference_matmul(x, w, cim, target_sparsity=ts, bk=16, bn=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    if ts > 0:
        assert dw.density < 1.0  # blocks actually dropped


# ---------------------------------------------------------------------------
# pack_bsr <-> bsr_to_dense round-trip and kernel differential, randomized
# over shapes, tilings and sparsity (seeded; the hypothesis variants live in
# tests/test_properties.py)
# ---------------------------------------------------------------------------


def _block_sparse(rng, gi, go, bk, bn, density):
    keep = rng.random((gi, go)) < density
    w = rng.standard_normal((gi * bk, go * bn)).astype(np.float32)
    return w * np.repeat(np.repeat(keep, bk, 0), bn, 1), keep


@pytest.mark.parametrize("seed", range(6))
def test_pack_bsr_roundtrip_randomized(seed):
    rng = np.random.default_rng(seed)
    bk, bn = int(rng.choice([4, 8, 16])), int(rng.choice([4, 8, 16]))
    gi, go = int(rng.integers(1, 6)), int(rng.integers(1, 6))
    w, keep = _block_sparse(rng, gi, go, bk, bn, float(rng.uniform(0, 1)))
    bsr = M.pack_bsr(w, bk, bn)
    np.testing.assert_array_equal(M.bsr_to_dense(bsr), w)
    assert bsr.nnz.sum() == keep.sum()


@pytest.mark.parametrize("seed", range(6, 10))
def test_pack_bsr_nnz_max_truncation(seed):
    """An explicit nnz_max below the true max drops the LAST surviving rows
    of over-full columns; ``nnz`` keeps the TRUE counts (for stats) while
    ``bsr_to_dense`` reconstructs only the stored slots."""
    rng = np.random.default_rng(seed)
    bk = bn = 8
    gi, go = int(rng.integers(3, 7)), int(rng.integers(1, 5))
    w, keep = _block_sparse(rng, gi, go, bk, bn, 0.9)
    cap = int(rng.integers(1, max(keep.sum(axis=0).max(), 2)))
    bsr = M.pack_bsr(w, bk, bn, nnz_max=cap)
    assert bsr.blocks.shape[1] == cap
    np.testing.assert_array_equal(bsr.nnz, keep.sum(axis=0))  # true counts
    want = np.zeros_like(w)
    for j in range(go):
        for i in np.flatnonzero(keep[:, j])[:cap]:
            want[i * bk:(i + 1) * bk, j * bn:(j + 1) * bn] = \
                w[i * bk:(i + 1) * bk, j * bn:(j + 1) * bn]
    np.testing.assert_array_equal(M.bsr_to_dense(bsr), want)


def test_pack_bsr_all_zero_weight():
    """Everything pruned: one padding slot per column, row_idx 0, and the
    kernel must still produce exact zeros (padding is masked, not summed)."""
    w = np.zeros((32, 24), np.float32)
    bsr = M.pack_bsr(w, 8, 8)
    assert bsr.nnz.tolist() == [0, 0, 0]
    assert bsr.blocks.shape[1] == 1  # nnz_max floors at one (inert) slot
    np.testing.assert_array_equal(M.bsr_to_dense(bsr), w)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((5, 32)),
                    jnp.float32)
    y = K.bsr_matmul(x, jnp.asarray(bsr.blocks),
                     jnp.ones(bsr.row_idx.shape, jnp.float32),
                     jnp.asarray(bsr.row_idx), jnp.asarray(bsr.nnz),
                     bm=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(y), np.zeros((5, 24)))


@pytest.mark.parametrize("seed", range(10, 16))
def test_bsr_kernel_matches_dense_randomized(seed):
    """cim_bsr_matmul == x @ bsr_to_dense(packing) across random shapes,
    tilings and densities - including truncated packings, where BOTH sides
    see only the stored slots."""
    rng = np.random.default_rng(seed)
    bk, bn = int(rng.choice([8, 16])), int(rng.choice([8, 16]))
    gi, go = int(rng.integers(1, 5)), int(rng.integers(1, 5))
    m = int(rng.integers(1, 17))
    w, keep = _block_sparse(rng, gi, go, bk, bn, float(rng.uniform(0, 1)))
    truncate = bool(rng.integers(2)) and keep.sum(axis=0).max() > 1
    cap = (int(rng.integers(1, keep.sum(axis=0).max() + 1)) if truncate
           else None)
    bsr = M.pack_bsr(w, bk, bn, nnz_max=cap)
    x = rng.standard_normal((m, gi * bk)).astype(np.float32)
    y = K.bsr_matmul(jnp.asarray(x), jnp.asarray(bsr.blocks),
                     jnp.ones(bsr.row_idx.shape, jnp.float32),
                     jnp.asarray(bsr.row_idx), jnp.asarray(bsr.nnz),
                     bm=max(8, min(128, m)), interpret=True)
    want = x @ M.bsr_to_dense(bsr)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("seed", range(16, 20))
def test_deployed_matmul_randomized_shapes(seed):
    """deploy_weight -> deployed_matmul == reference_matmul on random
    (d_in, d_out), tile and sparsity draws (the end-to-end differential the
    serving path rides on)."""
    rng = np.random.default_rng(seed)
    bk, bn = int(rng.choice([8, 16, 32])), int(rng.choice([8, 16, 32]))
    d_in = bk * int(rng.integers(1, 5))
    d_out = bn * int(rng.integers(1, 5))
    ts = float(rng.choice([0.0, 0.25, 0.5, 0.75]))
    w_bits = int(rng.choice([4, 8]))
    cim = _cim(w_bits=w_bits, ts=ts)
    w = jnp.asarray(rng.standard_normal((d_in, d_out)) * 0.2, jnp.float32)
    x = jnp.asarray(rng.standard_normal((int(rng.integers(1, 9)), d_in)),
                    jnp.float32)
    dw = deploy.deploy_weight(w, cim, bk=bk, bn=bn, target_sparsity=ts)
    got = deploy.deployed_matmul(x, dw, a_bits=cim.quant.a_bits,
                                 interpret=True)
    want = deploy.reference_matmul(x, w, cim, target_sparsity=ts,
                                   bk=bk, bn=bn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_deploy_stacked_lm_layers():
    """Deploy a real (stacked) LM projection and check accounting."""
    cfg = registry.get_smoke_config("yi-6b", dtype="float32")
    fns = registry.model_fns(cfg)
    params = fns.init_params(cfg, jax.random.PRNGKey(0))
    cim = _cim(w_bits=8, ts=0.5)
    dw = deploy.deploy_weight(params["layers"]["w_up"], cim, bk=16, bn=16)
    assert len(dw.packed) == cfg.n_layers
    x = jax.random.normal(jax.random.PRNGKey(2), (4, cfg.d_model))
    for layer in range(cfg.n_layers):
        y = deploy.deployed_matmul(x, dw, layer=layer, interpret=True)
        assert y.shape == (4, cfg.d_ff)
        assert bool(jnp.all(jnp.isfinite(y)))
    rep = deploy.deployment_report({"w_up": dw})
    # fp32 dense -> 8-bit weights at ~50% block sparsity: > 4x compression
    assert rep["compression_x"] > 4.0, rep
    assert rep["weight_Mb"] < rep["dense_Mb"]


@pytest.mark.parametrize("seed", [0, 7, 23])
def test_stack_deployed_matches_per_layer_seeded(seed):
    """Seeded envelope-parity sweep: mixed per-layer sparsities (including
    an all-zero layer) stacked into one uniform envelope must reproduce the
    per-layer kernel bit-for-bit through the layer-indexed entry point."""
    cim = _cim(w_bits=8, ts=0.5)
    rng = np.random.default_rng(seed)
    dws = []
    for ts in (0.0, float(rng.uniform(0.2, 0.8)), 1.0):
        w = rng.standard_normal((64, 96)).astype(np.float32) * 0.3
        if ts >= 1.0:
            w = np.zeros_like(w)
            ts = 0.5
        dws.append(deploy.deploy_weight(w, cim, bk=16, bn=16,
                                        target_sparsity=ts))
    sw = deploy.stack_deployed(dws)
    assert sw.n_layers == 3 and sw.tile == (16, 16)
    x = jnp.asarray(rng.standard_normal((6, 64)), jnp.float32)
    for i, dw in enumerate(dws):
        np.testing.assert_array_equal(
            np.asarray(deploy.stacked_matmul(x, sw, i, a_bits=8,
                                             interpret=True)),
            np.asarray(deploy.deployed_matmul(x, dw, a_bits=8,
                                              interpret=True)),
            err_msg=f"seed={seed} layer={i}")


def test_stack_deployed_accepts_multilayer_weight():
    """A deploy_weight over a stacked (L, d, d) master weight already holds
    L packed dicts - stack_deployed folds them into the same envelope as L
    separate single-layer weights."""
    cfg = registry.get_smoke_config("yi-6b", dtype="float32")
    params = registry.model_fns(cfg).init_params(cfg, jax.random.PRNGKey(0))
    cim = _cim(w_bits=8, ts=0.5)
    dw = deploy.deploy_weight(params["layers"]["w_up"], cim, bk=16, bn=16)
    sw = deploy.stack_deployed(dw)
    assert sw.n_layers == cfg.n_layers
    x = jax.random.normal(jax.random.PRNGKey(2), (4, cfg.d_model))
    for layer in range(cfg.n_layers):
        np.testing.assert_array_equal(
            np.asarray(deploy.stacked_matmul(x, sw, layer, interpret=True)),
            np.asarray(deploy.deployed_matmul(x, dw, layer=layer,
                                              interpret=True)))


def test_uniform_fit_tile():
    shapes = [(64, 64), (64, 32), (128, 64), (64, 256)]
    assert deploy.uniform_fit_tile(shapes, 16, 16) == (16, 16)
    assert deploy.uniform_fit_tile(shapes, 48, 48) == (32, 32)
    assert deploy.uniform_fit_tile([(60, 90)], 16, 16) == (15, 15)
    assert deploy.uniform_fit_tile([], 16, 16) == (16, 16)


@pytest.mark.parametrize("arch", ["yi-6b", "phi3.5-moe-42b-a6.6b",
                                  "llava-next-34b"])
def test_init_layerwise_matches_init_params(arch):
    """The one-layer-at-a-time init draws exactly init_params' values."""
    from repro.models import transformer

    cfg = registry.get_smoke_config(arch, dtype="float32")
    key = jax.random.PRNGKey(3)
    full = transformer.init_params(cfg, key)
    glob, layer = transformer.init_layerwise(cfg, key)
    assert set(glob) == set(full) - {"layers"}
    for k, v in glob.items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(full[k]))
    for i in range(cfg.n_layers):
        got = layer(i)
        assert set(got) == set(full["layers"])
        for k, v in got.items():
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(full["layers"][k][i]),
                err_msg=f"{k} layer {i}")


def test_compress_from_layer_source_matches_stacked_params():
    """Packing layer by layer from init_layerwise gives the packing of the
    stacked params, leaf for leaf."""
    from repro.models import transformer
    from repro.serve import deployed as DP

    cfg = registry.get_smoke_config("yi-6b", dtype="float32",
                                    cim_mode="qat")
    key = jax.random.PRNGKey(0)
    want = DP.compress(cfg, transformer.init_params(cfg, key),
                       target_sparsity=0.5, tile=(16, 16), uniform=True)
    glob, layer = transformer.init_layerwise(cfg, key)
    got = DP.compress(cfg, glob, target_sparsity=0.5, tile=(16, 16),
                      uniform=True, layer=layer)
    a, b = jax.tree.leaves(want), jax.tree.leaves(got)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # packings stay on the host until a runtime places them
    assert isinstance(got.layers[0]["wq"].packed[0]["blocks"], np.ndarray)


def test_compressed_matmul_keeps_activation_dtype():
    """bf16 activations come back bf16 even though the kernel runs on the
    f32-quantized activation (the scan carry's dtype must not change)."""
    dw = deploy.deploy_weight(
        np.random.default_rng(0).standard_normal((64, 32)).astype(np.float32),
        _cim(w_bits=8), bk=16, bn=16, target_sparsity=0.5)
    x = jnp.ones((3, 64), jnp.bfloat16)
    assert deploy.deployed_matmul(x, dw, a_bits=8).dtype == jnp.bfloat16
    sw = deploy.stack_deployed([dw, dw])
    assert deploy.stacked_matmul(x, sw, 1, a_bits=8).dtype == jnp.bfloat16


def test_tpu_tile_rule(monkeypatch):
    """With the compiled kernel (TPU), only 128-multiple tiles fit, and a
    tile that cannot fit is refused by name."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    assert ops.tile_quantum() == 128
    assert ops.tile_sizes() == K.COMPILED_TILES
    # 11008 = 256 * 43: the largest 128-multiple edge <= 512 is 256
    assert deploy.fit_tile(4096, 11008, 512, 512) == (512, 256)
    assert deploy.fit_tile(11008, 4096, 512, 512) == (256, 512)
    yi = [(4096, 4096), (4096, 512), (4096, 11008), (11008, 4096),
          (4096, 64000)]
    assert deploy.uniform_fit_tile(yi, 512, 512) == (256, 256)
    with pytest.raises(ValueError, match="16x16"):
        deploy.fit_tile(4096, 4096, 16, 16)
    with pytest.raises(ValueError, match="64x128"):
        K.bsr_matmul(jnp.ones((8, 64)), jnp.ones((1, 1, 64, 128), jnp.int8),
                     jnp.ones((1, 1)), jnp.zeros((1, 1), jnp.int32),
                     jnp.ones((1,), jnp.int32), interpret=False)
    # off-TPU every tile runs (interpret mode)
    monkeypatch.setattr(ops, "default_interpret", lambda: True)
    assert deploy.fit_tile(64, 48, 16, 32) == (16, 24)
