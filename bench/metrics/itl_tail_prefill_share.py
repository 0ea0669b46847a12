"""Of the gaps between two tokens of a request at or above their pooled
95th percentile, the share of their summed length that ``prefill`` spans
cover: how much of the ITL tail is other requests' prefills stalling the
decode."""
from bench.spans import tail_share


def read(run):
    return tail_share(run, "prefill")
