"""What the program's phase spans say about a decode step and a request.

Spans are ``(name, start, end, args)`` in seconds from the window's start
(``Run.spans``). A decode step's KV stages are the ``kv.*`` spans nested
inside its ``step.gather``; ``kv.*`` spans elsewhere (a prefix-cache
suffix prefill, a speculative round) are not a decode step's. A request's
tokens land at the end of its ``prefill`` (the first) and at the end of
each ``decode_step`` whose ``rids`` name it. Every function returns None
where the spans it reads are not there, as in a program that does not
write them.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


def step_children(spans: Sequence[tuple], child: str) -> Optional[list]:
    """Per ``step.gather`` span, the ``child`` spans inside it; None when
    no step holds one."""
    steps = sorted((s, e) for n, s, e, *_ in spans if n == "step.gather")
    kids = sorted(((s, e, a) for n, s, e, a in spans if n == child),
                  key=lambda k: k[0])
    per_step: List[list] = [[] for _ in steps]
    starts = np.array([s for s, _ in steps])
    for s, e, a in kids:
        i = int(np.searchsorted(starts, s, side="right")) - 1
        if i >= 0 and e <= steps[i][1] + 1e-9:
            per_step[i].append((s, e, a))
    if not any(per_step):
        return None
    return per_step


def mean_step_ms(run, child: str) -> Optional[float]:
    """Mean summed time of the ``child`` spans per decode step, in ms."""
    if run.spans is None:
        return None
    per = step_children(run.spans, child)
    if per is None:
        return None
    return 1e3 * float(np.mean([sum(e - s for s, e, _ in k) for k in per]))


def h2d_bytes(run) -> Optional[Tuple[List[int], List[int]]]:
    """Per decode step, the summed ``bytes`` and ``useful_bytes`` of its
    ``kv.h2d`` spans."""
    if run.spans is None:
        return None
    per = step_children(run.spans, "kv.h2d")
    if per is None:
        return None
    return ([sum(a["bytes"] for *_, a in k) for k in per],
            [sum(a["useful_bytes"] for *_, a in k) for k in per])


def token_gaps(spans: Sequence[tuple]) -> Optional[List[Tuple[float,
                                                              float]]]:
    """Every gap between two tokens of a request, as (start, end); None
    when the decode steps do not name their requests."""
    steps = [(e, a["rids"]) for n, _, e, a in spans
             if n == "decode_step" and a.get("rids") is not None]
    if not steps:
        return None
    times: Dict[str, List[float]] = {
        a["rid"]: [e] for n, _, e, a in spans if n == "prefill"}
    for e, rids in sorted(steps, key=lambda x: x[0]):
        for rid in rids:
            times[rid].append(e)
    return [(t[i], t[i + 1]) for t in times.values()
            for i in range(len(t) - 1)]


def covered(gap: Tuple[float, float], intervals: Sequence[tuple]) -> float:
    """Length of ``gap`` covered by the union of ``intervals``."""
    g0, g1 = gap
    iv = sorted((max(s, g0), min(e, g1)) for s, e in intervals
                if e > g0 and s < g1)
    tot, end = 0.0, g0
    for s, e in iv:
        if e > end:
            tot += e - max(s, end)
            end = e
    return tot


def tail_share(run, phase: str) -> Optional[float]:
    """Of the token gaps at or above their pooled 95th percentile, the
    share of their summed length covered by ``phase`` spans, in %."""
    if run.spans is None:
        return None
    gaps = token_gaps(run.spans)
    if not gaps:
        return None
    lengths = np.array([e - s for s, e in gaps])
    cut = np.percentile(lengths, 95)
    tail = [g for g, n in zip(gaps, lengths) if n >= cut]
    total = sum(e - s for s, e in tail)
    if total <= 0:
        return None
    iv = [(s, e) for n, s, e, *_ in run.spans if n == phase]
    return 100.0 * sum(covered(g, iv) for g in tail) / total
