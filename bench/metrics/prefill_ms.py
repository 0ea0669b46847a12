"""Mean device time of one run of the jitted prefill step."""
import numpy as np

from bench.devtrace import PREFILL_PROGRAM


def read(run):
    if run.device is None:
        return None
    t = run.device.program_times(PREFILL_PROGRAM)
    return float(np.mean(t)) * 1e3 if t else None
