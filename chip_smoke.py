#!/usr/bin/env python3
"""Chip smoke test: full-width yi-6b served through the compiled BSR kernels.

    python3 chip_smoke.py [--seed N]       # one TPU chip
    python3 chip_smoke.py --four-chips     # macro=4 sharded vs one device

One process does everything (the process that touches JAX holds the chip).
The default run builds yi-6b at its published widths (32 layers, d_model
4096, GQA 32:4, d_ff 11008, vocab 64000) in bfloat16, draws the weights
from ``--seed`` and packs them to int8 BSR blocks one layer at a time, then:

  1. serves 8 requests (16-64 prompt tokens, 16 new tokens each) through
     ``BatchServer(engine="scan")`` twice and checks every request got its
     16 tokens, all in the vocabulary, the same both times;
  2. checks one full-width projection (w_gate of one layer, through the
     layer-stacked kernel the server runs) against ``kernels/ref.py``:
     max |kernel - reference| <= 1e-3 * max |reference|;
  3. checks that the compiled decode step holds a ``tpu_custom_call``
     (the Mosaic kernel, not interpret mode);
  4. checks that the device's peak bytes in use stayed below its HBM.

``--four-chips`` runs only the sharded path: the same packing served on one
device and column-sharded over a ``macro=4`` mesh, whose greedy tokens must
be bit-identical.

Earlier lines report each phase; the last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``. Without a TPU, or
without the repository's ``src`` next to this file, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "yi-6b"
DTYPE = "bfloat16"
TARGET_SPARSITY = 0.5  # launch.serve's default
N_REQUESTS = 8
PROMPT_LENS = (16, 64)
NEW_TOKENS = 16
# block 32: prompts pad to 32 or 64 tokens and views to 2 or 4 blocks, so
# the run compiles few shapes
BATCH = dict(n_slots=8, block_size=32, n_blocks=64)
KERNEL_PROJ = "w_gate"
KERNEL_LAYER = 3
KERNEL_ROWS = 8
KERNEL_RTOL = 1e-3  # of max |reference|


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def requests(cfg, seed: int):
    from repro.serve import Request

    rng = np.random.default_rng(seed)
    return [Request(f"r{i}",
                    rng.integers(0, cfg.vocab, int(rng.integers(
                        PROMPT_LENS[0], PROMPT_LENS[1] + 1))).astype(np.int32),
                    NEW_TOKENS) for i in range(N_REQUESTS)]


def pack(cfg, seed: int):
    """Draw the weights from ``seed`` and pack them the way
    ``launch.serve --compressed`` does without ``--tile``."""
    import jax
    from repro.kernels import ops
    from repro.models import transformer
    from repro.serve import deployed

    t0 = time.perf_counter()
    params, layer = transformer.init_layerwise(cfg, jax.random.PRNGKey(seed))
    sp = deployed.compress(cfg, params, target_sparsity=TARGET_SPARSITY,
                           schedule=deployed.default_schedule(cfg),
                           layer=layer)
    pack_s = time.perf_counter() - t0
    tiles = deployed.packed_tiles(sp)
    q = ops.tile_quantum()
    print(f"pack: {pack_s:.1f}s from seed {seed}, tiles {tiles} "
          f"(multiples of {q}), sparsity {TARGET_SPARSITY}")
    check(all(bk % q == 0 and bn % q == 0 for bk, bn in tiles),
          f"tiles {tiles} are not {q}-aligned")
    return sp


def serve(cfg, sp, seed: int, mesh=None):
    """One BatchServer (scan runtime) run over the smoke trace."""
    from repro.serve import BatchConfig, BatchServer, ServeConfig

    srv = BatchServer(cfg, sp, ServeConfig(seed=seed), BatchConfig(**BATCH),
                      mesh=mesh, engine="scan")
    t0 = time.perf_counter()
    rep = srv.run(requests(cfg, seed))
    return srv, rep, time.perf_counter() - t0


def check_outputs(cfg, rep, seed: int) -> None:
    for r in requests(cfg, seed):
        out = np.asarray(rep.outputs[r.rid])
        check(out.shape == (NEW_TOKENS,),
              f"{r.rid}: {out.shape[0]} tokens, expected {NEW_TOKENS}")
        check(bool(np.all((out >= 0) & (out < cfg.vocab))),
              f"{r.rid}: token outside the vocabulary")


def packed_bytes(srv) -> int:
    import jax

    return int(sum(getattr(a, "nbytes", 0) for a in jax.tree.leaves(
        (srv._params.packed, srv._params.head))))


def kernel_check(cfg, srv, seed: int) -> float:
    """One full-width projection through the layer-stacked kernel the
    server runs, against the dense reference built by ``kernels/ref.py``
    (float64 matmul on the host). Returns the relative error."""
    import jax
    import jax.numpy as jnp
    from repro.core.deploy import bm_for_rows
    from repro.kernels import ops, ref

    sw = srv._params.packed[KERNEL_PROJ]
    li = min(KERNEL_LAYER, cfg.n_layers - 1)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (KERNEL_ROWS, sw.d_in), jnp.bfloat16)
    got = np.asarray(ops.bsr_matmul_stacked(
        x, sw.blocks, sw.scales, sw.row_idx, sw.nnz, jnp.int32(li),
        bm=bm_for_rows(KERNEL_ROWS)), np.float64)
    host = sw.layer(li).packed[0]
    w = np.asarray(ref.bsr_dense(host["blocks"], host["scales"],
                                 host["row_idx"], host["nnz"], sw.d_in),
                   np.float64)
    want = np.asarray(x, np.float64) @ w
    err = float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))
    print(f"kernel: {KERNEL_PROJ} layer {li} ({sw.d_in}->{sw.d_out}, tile "
          f"{sw.tile[0]}x{sw.tile[1]}, {KERNEL_ROWS} rows) max|err|/max|ref| "
          f"= {err:.3e} (tolerance {KERNEL_RTOL:g})")
    check(np.all(np.isfinite(got)), "kernel output not finite")
    check(err <= KERNEL_RTOL, f"kernel error {err:.3e} > {KERNEL_RTOL:g}")
    return err


def decode_step_text(cfg, srv) -> str:
    """Compiled HLO of the server's decode step at the trace's largest
    view (a persistent-cache hit after the served run)."""
    import jax
    import jax.numpy as jnp

    bs, n = BATCH["block_size"], BATCH["n_slots"]
    blocks = -(-(PROMPT_LENS[1] + NEW_TOKENS) // bs)
    blocks = -(-blocks // srv.bcfg.view_bucket) * srv.bcfg.view_bucket
    view = jax.ShapeDtypeStruct(
        (cfg.n_layers, n, blocks * bs, cfg.n_kv_heads_eff, cfg.dh),
        cfg.param_dtype)
    pos = jax.ShapeDtypeStruct((n,), jnp.int32)
    toks = jax.ShapeDtypeStruct((n, 1), jnp.int32)
    return srv._decode.lower(srv._params, view, view, pos, toks,
                             cfg=cfg).compile().as_text()


def device_peak(dev) -> int:
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", -1))
    limit = int(stats.get("bytes_limit", 0))
    print(f"memory: {dev} peak_bytes_in_use={peak} bytes_limit={limit}")
    check(peak >= 0, "the device reports no peak_bytes_in_use")
    check(not limit or peak < limit, f"peak {peak} >= limit {limit}")
    return peak


def one_chip(cfg, sp, seed: int) -> None:
    import jax

    srv, rep, first_s = serve(cfg, sp, seed)
    print(f"serve: first run {first_s:.1f}s (compiles every shape), "
          f"{rep.total_tokens} tokens, {len(rep.outputs)} requests; "
          f"packed weights on device {packed_bytes(srv)} bytes")
    check_outputs(cfg, rep, seed)
    t0 = time.perf_counter()
    again = srv.run(requests(cfg, seed))
    second_s = time.perf_counter() - t0
    print(f"serve: second run {second_s:.1f}s; compile ~ first - second = "
          f"{first_s - second_s:.1f}s")
    for rid, out in rep.outputs.items():
        check(np.array_equal(out, again.outputs[rid]),
              f"{rid}: tokens differ between two runs")
    print("serve: " + " ".join(f"{rid}={np.asarray(o)[:4].tolist()}.."
                              for rid, o in list(rep.outputs.items())[:3]))
    kernel_check(cfg, srv, seed)
    t0 = time.perf_counter()
    text = decode_step_text(cfg, srv)
    n_kernels = text.count("tpu_custom_call")
    print(f"decode step: {n_kernels} tpu_custom_call sites in the compiled "
          f"HLO ({time.perf_counter() - t0:.1f}s to lower and compile)")
    check(n_kernels > 0, "decode step has no tpu_custom_call (interpret mode)")
    device_peak(jax.devices()[0])


def four_chips(cfg, sp, seed: int) -> None:
    import jax
    from repro.launch.shardings import macro_mesh
    from repro.serve import deployed

    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    _, single, single_s = serve(cfg, sp, seed)
    print(f"single device: {single_s:.1f}s, {single.total_tokens} tokens")
    check_outputs(cfg, single, seed)
    mesh = macro_mesh(4)
    sps = deployed.shard(sp, mesh)
    n_sharded = sum(dw.sharded for dw in sps.deployed().values())
    _, sharded, sharded_s = serve(cfg, sps, seed, mesh=mesh)
    match = all(np.array_equal(single.outputs[r.rid], sharded.outputs[r.rid])
                for r in requests(cfg, seed))
    print(f"macro=4: {sharded_s:.1f}s, {n_sharded} of "
          f"{len(sps.deployed())} projections column-sharded, "
          f"tokens_match_single_device={match}")
    check(match, "sharded greedy tokens differ from single-device serving")
    for dev in jax.devices()[:4]:
        device_peak(dev)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="serve column-sharded over macro=4 and check tokens "
                    "against one device; runs no other phase")
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's backend is "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    try:
        from repro.launch import compile_cache
        from repro.models import registry
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is missing: {e}",
              file=sys.stderr)
        return 1
    print(f"compile cache: {compile_cache.enable()}")
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    cfg = registry.get_config(ARCH, dtype=DTYPE)
    print(f"config: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}:{cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} dtype={cfg.dtype}")
    try:
        sp = pack(cfg, args.seed)
        (four_chips if args.four_chips else one_chip)(cfg, sp, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
