"""Helpers the metric readers share."""
from __future__ import annotations

from typing import Optional


def mean_span_ms(run, name: str) -> Optional[float]:
    """Mean duration of the program's phase span ``name``, in ms."""
    if run.spans is None:
        return None
    d = [e - s for n, s, e, *_ in run.spans if n == name]
    return 1e3 * sum(d) / len(d) if d else None
