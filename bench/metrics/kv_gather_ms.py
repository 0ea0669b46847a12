"""Mean host time of the ``step.gather`` phase per decode step: growing
the block tables and gathering the paged KV view from the host pool onto
the device."""
from bench.metrics_common import mean_span_ms


def read(run):
    return mean_span_ms(run, "step.gather")
