"""95th percentile of every gap between two output tokens of a request,
pooled over all requests: a decode step, plus any prefill that stalled
it."""
import numpy as np


def read(run):
    t = run.report.tpot_s
    return float(np.percentile(t, 95)) * 1e3 if t else None
