"""Operations and bytes the compressed model's work needs, from its shapes.

Counts come from the configuration (widths and ``target_sparsity``), not
from the packing: a kernel that reads its padding, or computes blocks the
pruning removed, does more than this and reads below 100% of its roofline.
One BSR call multiplies ``rows`` activations, quantized to ``a_bits``, by a
``(d_in, d_out)`` int8 weight of which ``1 - target_sparsity`` survives.
It must read the surviving weights once and its activations, and write its
float32 output.

The peaks are those of ``bench/peaks.json``, keyed by JAX's ``device_kind``;
a device that is not there is an error, never a default.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")

ACT_BYTES = 4   # the kernel takes its quantized activations in float32
OUT_BYTES = 4   # and writes float32
WEIGHT_BYTES = 1  # int8 levels


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}"
                       f" (known: {sorted(table)})")
    return table[device_kind]


def projections(conf: dict) -> Dict[str, Tuple[int, int]]:
    """(d_in, d_out) of every projection the kernel runs for one token:
    each layer's seven, and the output head."""
    d = conf["hidden_size"]
    q = conf["num_attention_heads"] * conf["head_dim"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    ff = conf["intermediate_size"]
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d),
            "head": (d, conf["vocab_size"])}


def bsr_call(rows: int, d_in: int, d_out: int, density: float) -> dict:
    """Work of one BSR call: integer operations and bytes moved."""
    w = d_in * d_out * density
    return {"ops": 2.0 * rows * w,
            "bytes": w * WEIGHT_BYTES + rows * d_in * ACT_BYTES
            + rows * d_out * OUT_BYTES}


def least_time(work: dict, peak: dict) -> Tuple[float, str]:
    """The least seconds the chip needs for ``work`` and the bound that
    sets it. Both operands are 8-bit levels, so the int8 peak applies."""
    t_ops = work["ops"] / peak["int8_ops"]
    t_mem = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def bsr_pass_least_time(conf: dict, rows: int, peak: dict) -> float:
    """Least time of one pass's BSR calls, each call bounded on its own."""
    dens = 1.0 - float(conf["target_sparsity"])
    t = 0.0
    for name, (di, do) in projections(conf).items():
        n = 1 if name == "head" else conf["num_hidden_layers"]
        t += n * least_time(bsr_call(rows, di, do, dens), peak)[0]
    return t


def token_ops(conf: dict, context: int) -> float:
    """Operations the model needs for one token that attends over
    ``context`` positions: the surviving weights of every projection and
    the head, and attention's two products."""
    dens = 1.0 - float(conf["target_sparsity"])
    mm = 0.0
    for name, (di, do) in projections(conf).items():
        n = 1 if name == "head" else conf["num_hidden_layers"]
        mm += n * 2.0 * di * do * dens
    attn = (4.0 * conf["num_hidden_layers"] * conf["num_attention_heads"]
            * conf["head_dim"] * context)
    return mm + attn


def request_ops(conf: dict, prompt: int, generated: int) -> float:
    """Operations of one served request: its prompt (position i attends
    over i+1 positions) and each generated token fed back (all but the
    last, which no step consumes)."""
    mm = token_ops(conf, 0)
    attn_per_pos = token_ops(conf, 1) - mm
    fed = prompt + max(generated - 1, 0)
    # position i attends over i + 1 positions, i = 0 .. fed - 1
    return fed * mm + attn_per_pos * fed * (fed + 1) / 2.0
