"""Mean device time of one run of the jitted decode step (the scan over
layers, every projection's BSR kernel and the head)."""
import numpy as np

from bench.devtrace import DECODE_PROGRAM


def read(run):
    if run.device is None:
        return None
    t = run.device.program_times(DECODE_PROGRAM)
    return float(np.mean(t)) * 1e3 if t else None
