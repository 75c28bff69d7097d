"""The aggregation's share of its roofline: the least time the chip
needs for the rule's minimum work on the (n, P) arena (read once, write
the result once, plus the Gram FLOPs of pairwise rules; chipbench.work),
over the device time its operations took per step."""
from chipbench.readers import agg_seconds_per_step
from chipbench.work import agg_min_work, roofline_s


def read(run):
    s = agg_seconds_per_step(run)
    if s is None:
        return None
    flops, nbytes = agg_min_work(run.rule, run.n_agents, run.n_params,
                                 run.elem_bytes, run.masked)
    t_min, _bound = roofline_s(flops, nbytes, run.peak)
    return 100.0 * t_min / s
