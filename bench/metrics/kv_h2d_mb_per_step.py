"""Mean bytes copied host to device per decode step, in MB (1e6 bytes):
the summed ``bytes`` args of the ``kv.h2d`` spans inside ``step.gather``,
every lane's view out to the bucketed longest context."""
import numpy as np

from bench.spans import h2d_bytes


def read(run):
    b = h2d_bytes(run)
    return float(np.mean(b[0])) / 1e6 if b else None
