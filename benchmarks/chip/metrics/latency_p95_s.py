"""95th percentile client latency of the requests due in the window (open loop)."""
from chipbench import readers


def read(run):
    return readers.latency_percentile(run, 95)
