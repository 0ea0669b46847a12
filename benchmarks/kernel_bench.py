"""Kernel benchmark: structural bytes + wall time per call.

The kernels run compiled on a TPU and in Pallas interpret mode on any other
backend (``kernels.ops``); every row names the backend, and a time from a
non-TPU backend measures the interpreter, not the chip. The bytes-touched
model and the sparse-vs-dense op-count ratio are structural and transfer:
the BSR kernel touches density-proportional weight bytes, which is the
paper's zero-group-set skip."""
from __future__ import annotations

import numpy as np

from benchmarks.common import timeit
from repro.core.mapping import pack_bsr
from repro.kernels import ops

import jax
import jax.numpy as jnp


def run():
    rows = []
    backend = jax.default_backend()
    m, k, n, bk, bn = 256, 1024, 1024, 128, 128
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)

    for density in [1.0, 0.5, 0.25, 0.05]:
        gi, go = k // bk, n // bn
        keep = rng.random((gi, go)) < density
        w = rng.integers(-7, 8, (k, n)).astype(np.int8)
        w *= np.repeat(np.repeat(keep, bk, 0), bn, 1).astype(np.int8)
        bsr = pack_bsr(w, bk, bn)
        scales = np.full(bsr.row_idx.shape, 1 / 8, np.float32)
        packed = {"blocks": jnp.asarray(bsr.blocks),
                  "scales": jnp.asarray(scales),
                  "row_idx": jnp.asarray(bsr.row_idx),
                  "nnz": jnp.asarray(bsr.nnz)}
        us = timeit(lambda: ops.bsr_matmul(x, packed), iters=3)
        weight_bytes = int(bsr.nnz.sum()) * bk * bn  # int8
        rows.append({
            "name": f"kernel_bsr_density{density}",
            "us_per_call": round(us, 1), "backend": backend,
            "weight_bytes_touched": weight_bytes,
            "dense_weight_bytes": k * n,
            "bytes_skipped_ratio": round(1 - weight_bytes / (k * n), 3),
        })

    w = rng.integers(-127, 128, (k, n)).astype(np.int8)
    scale = np.full((n,), 0.01, np.float32)
    us = timeit(lambda: ops.quant_matmul(x, jnp.asarray(w),
                                         jnp.asarray(scale)), iters=3)
    rows.append({"name": "kernel_quant_matmul_dense",
                 "us_per_call": round(us, 1), "backend": backend,
                 "weight_bytes_touched": k * n,
                 "dense_weight_bytes": k * n, "bytes_skipped_ratio": 0.0})

    big = jnp.asarray(rng.standard_normal((512, 2048)), jnp.float32)
    us = timeit(lambda: ops.fake_quant(big, 4), iters=3)
    rows.append({"name": "kernel_fake_quant_4b",
                 "us_per_call": round(us, 1), "backend": backend,
                 "weight_bytes_touched": big.size * 4,
                 "dense_weight_bytes": big.size * 4, "bytes_skipped_ratio": 0.0})
    return rows


def main():
    for r in run():
        print(",".join(f"{k}={v}" for k, v in r.items()))


if __name__ == "__main__":
    main()
