"""Mean host time per decode step of the ``kv.gather`` spans inside
``step.gather``: the numpy index, transpose and reshape of the K and V
views out of the host pool."""
from bench.spans import mean_step_ms


def read(run):
    return mean_step_ms(run, "kv.gather")
