"""Of the gaps between two tokens of a request at or above their pooled
95th percentile, the share of their summed length that ``step.gather``
spans cover: how much of the ITL tail is the host KV gather."""
from bench.spans import tail_share


def read(run):
    return tail_share(run, "step.gather")
