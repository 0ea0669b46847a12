"""Share of the decode steps' host-to-device KV bytes that active lanes
read or write (positions 0..pos): summed ``useful_bytes`` over summed
``bytes`` of the ``kv.h2d`` spans inside ``step.gather``, over the
window. The rest is idle lanes and padding out to the view's length."""
from bench.spans import h2d_bytes


def read(run):
    b = h2d_bytes(run)
    return 100.0 * sum(b[1]) / sum(b[0]) if b and sum(b[0]) else None
