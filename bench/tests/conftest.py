"""Shared fixtures of the benchmark's CPU tests (no TPU is touched)."""
from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

# the yi-6b block at the program's smoke preset: every code path of the
# full configuration, at widths the Pallas interpreter runs in seconds
SMOKE_CONF = {
    "arch": "yi-6b", "preset": "smoke", "reference": "dense_gqa",
    "dtype": "bfloat16", "num_hidden_layers": 2, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "vocab_size": 256, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "norm_gain": 1.0, "w_bits": 8, "a_bits": 8, "quant_group": 128,
    "target_sparsity": 0.5, "weight_seed": 2**31 + 5, "tile": [16, 16]}

SMOKE_MIX = {
    "arrival": {"kind": "poisson"}, "schedule_seed": 0, "rate_share_of_knee": 0.8,
    "prompt_tokens": {"kind": "lognormal", "median": 12, "sigma": 0.4,
                      "min": 4, "max": 16},
    "output_tokens": {"kind": "lognormal", "median": 5, "sigma": 0.3,
                      "min": 3, "max": 6},
    "serving": {"n_slots": 4, "block_size": 8, "view_bucket": 2}}

# limit of the smoke cell, between its readings on the CPU: mean gap of
# the served tokens 0.0015-0.0024, of the float8 control's choices
# 0.115-0.147 (two layers at norm gain 1)
SMOKE_LIMIT = 0.02


@pytest.fixture
def artifacts(tmp_path, monkeypatch):
    """Serving artifacts go to a directory of the test's own."""
    from bench import harness

    path = tmp_path / "bench-artifacts"
    monkeypatch.setattr(harness, "ARTIFACTS", str(path))
    return path


@pytest.fixture
def smoke_cell(artifacts):
    from bench import harness

    return harness.Cell(
        "smoke.chat", "smoke", 1, dict(SMOKE_CONF), dict(SMOKE_MIX),
        {"knee_req_s": 4.0, "limits": {"mean_logit_gap": SMOKE_LIMIT}},
        [{"name": n, "unit": u} for n, u in (
            ("tokens_per_s", "tokens/s"), ("itl_p95_ms", "ms"),
            ("setup_s", "s"))], [])


@pytest.fixture
def cpu_peaks(tmp_path):
    path = tmp_path / "peaks.json"
    path.write_text(json.dumps({"cpu": {
        "bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e11,
        "source": "test"}}))
    return str(path)


@pytest.fixture
def quiet_cache(monkeypatch):
    """Keep the harness's compile-cache settings out of the other tests
    of this worker: no cache directory is set, and the cache threshold is
    restored afterwards."""
    import jax
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "enable", lambda: "off (test)")
    old = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old)
