"""Mean host time of the ``step.writeback`` phase per decode step: the new
K/V entries copied back from the device into the host pool."""
from bench.metrics_common import mean_span_ms


def read(run):
    return mean_span_ms(run, "step.writeback")
