"""Mean time per decode step of the ``kv.h2d`` spans inside
``step.gather``: the K and V views' copies to the device, each span fenced
on its copy in a traced run."""
from bench.spans import mean_step_ms


def read(run):
    return mean_step_ms(run, "kv.h2d")
