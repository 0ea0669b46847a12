"""bench/work.py and bench/peaks.json, against numbers worked out by hand."""
import json

import pytest

from bench import work

YI = {"hidden_size": 4096, "num_attention_heads": 32,
      "num_key_value_heads": 4, "head_dim": 128, "intermediate_size": 11008,
      "vocab_size": 64000, "num_hidden_layers": 32, "target_sparsity": 0.5}
V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}


def test_one_yi_projection_by_hand():
    # wq at decode: 16 rows x (4096 x 4096), half the weights survive.
    # ops = 2 * 16 * 4096 * 4096 * 0.5 = 268435456
    # bytes = 4096*4096*0.5 (int8) + 16*4096*4 (f32 in) + 16*4096*4 (out)
    #       = 8388608 + 262144 + 262144 = 8912896
    w = work.bsr_call(16, 4096, 4096, 0.5)
    assert w == {"ops": 268435456.0, "bytes": 8912896.0}
    t, bound = work.least_time(w, V5E)
    assert bound == "memory"
    assert t == pytest.approx(8912896 / 819e9)  # 10.88 us


def test_prefill_rows_make_the_call_compute_bound():
    # 4096 rows of w_gate: ops 2*4096*4096*11008*0.5 = 1.847e11 -> 0.47 ms
    # at 393 TOP/s, bytes 22.5 MB + 67 MB + 180 MB -> 0.33 ms at 819 GB/s
    w = work.bsr_call(4096, 4096, 11008, 0.5)
    assert w["ops"] == 2 * 4096 * 4096 * 11008 * 0.5
    assert work.least_time(w, V5E)[1] == "compute"


def test_projections_and_token_ops():
    p = work.projections(YI)
    assert p["wk"] == (4096, 512) and p["w_down"] == (11008, 4096)
    assert p["head"] == (4096, 64000)
    per_layer = 4096 * (4096 + 512 + 512) + 4096 * 4096 + 3 * 4096 * 11008
    mm = 2 * 0.5 * (32 * per_layer + 4096 * 64000)
    assert work.token_ops(YI, 0) == pytest.approx(mm)
    # attention over 100 positions adds 4 * layers * heads * dh * 100
    assert work.token_ops(YI, 100) - mm == pytest.approx(
        4 * 32 * 32 * 128 * 100)


def test_request_ops_counts_prompt_and_fed_back_tokens():
    mm = work.token_ops(YI, 0)
    a = work.token_ops(YI, 1) - mm
    # 3 prompt tokens + 2 generated (1 fed back): positions 0..3 attend
    # over 1..4 positions
    assert work.request_ops(YI, 3, 2) == pytest.approx(4 * mm + a * 10)


def test_peaks_lookup_and_unknown_device(tmp_path):
    row = work.peaks("TPU v5 lite")
    assert row["bf16_flops"] == 197e12 and row["int8_ops"] == 393e12
    assert row["hbm_bytes_per_s"] == 819e9 and "Google Cloud" in row["source"]
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")
    with open(work.PEAKS_FILE) as f:
        for kind, r in json.load(f).items():
            assert {"bf16_flops", "int8_ops", "hbm_bytes_per_s",
                    "source"} <= set(r), kind
