"""The comparison that decides ``correct``.

Once the window has closed, a sample of the finished requests, drawn from
the seed and holding the longest one, goes through the configuration's
plain reference: each prompt followed by its served tokens. At every
position where the server chose a token, the reference's logits are read,
and the gap by which the served token's logit lies below the reference's
best is taken. Greedy serving of the same model gives gaps near zero.

The number compared with the cell's limit is the mean gap over the
sample. The widest gap is printed too, but it is set by the one closest
tie among some hundreds of tokens, and on the chip it separated the
program from the control by less than three times (see PERF.md).

The control reads, at the same positions, the gap of the token that the
reference computed one precision lower puts first.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Sequence

import numpy as np


def sample(planned: Sequence, outputs: Dict[str, np.ndarray], seed: int,
           min_tokens: int) -> List:
    """Finished requests to check: the longest (prompt plus output), then
    others in an order drawn from the seed until ``min_tokens`` served
    tokens are covered."""
    done = [r for r in planned if r.rid in outputs]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + r.max_new_tokens,
                                       r.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([int(seed), 4]).permutation(len(rest))
    picked, n = [longest], len(outputs[longest.rid])
    for i in order:
        if n >= min_tokens:
            break
        picked.append(rest[i])
        n += len(outputs[rest[i].rid])
    return picked


def reference_module(conf: dict):
    return importlib.import_module(f"bench.references.{conf['reference']}")


def gaps(conf: dict, picked: Sequence, outputs: Dict[str, np.ndarray],
         control: str = "", pad_to: int = 0) -> dict:
    """Mean and widest logit gap of the served tokens (and of the
    control's first choices, when ``control`` names a rounding) over the
    picked requests, and the share of tokens off the reference's first
    choice. The reference draws the weights again from the
    configuration's ``weight_seed``. ``pad_to`` fixes the reference's
    padded length, so that every run compiles the same program."""
    ref = reference_module(conf)
    seqs, rows, served = [], [], []
    for r in picked:
        out = np.asarray(outputs[r.rid], np.int32)
        seqs.append(np.concatenate([r.prompt, out[:-1]]))
        # logits at position p choose the token at p + 1
        rows.append(np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + len(out)))
        served.append(out)
    rounds = ("float32",) + ((control,) if control else ())
    lg = ref.logits(conf, int(conf["weight_seed"]), seqs, rows,
                    roundings=rounds, pad_to=pad_to)
    res = {"checked_tokens": int(sum(len(s) for s in served))}
    res.update(_stats("served", lg["float32"], served))
    if control:
        picks = [np.argmax(lc, axis=-1) for lc in lg[control]]
        res.update(_stats("control", lg["float32"], picks))
    return res


def _stats(name: str, ref_logits, tokens) -> dict:
    g, off = [], []
    for l, t in zip(ref_logits, tokens):
        g.append(l.max(axis=-1) - l[np.arange(len(t)), t])
        off.append(np.asarray(t) != np.argmax(l, axis=-1))
    g, off = np.concatenate(g), np.concatenate(off)
    return {f"{name}_gap_mean": float(g.mean()),
            f"{name}_gap_max": float(g.max()),
            f"{name}_off_share": float(off.mean())}
