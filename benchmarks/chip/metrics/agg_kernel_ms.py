"""Device milliseconds per step of the aggregation layer's operations
(the rule's kernels, by name), from the device trace."""
from chipbench.readers import agg_seconds_per_step


def read(run):
    s = agg_seconds_per_step(run)
    return None if s is None else s * 1e3
