"""Median device milliseconds of one run of a replicated prefill program
(the scheduler's jitted ``pf``, one per prompt length), from the device
trace."""
import numpy as np

from chipbench.trace import module_runs


def read(run):
    if run.kind != "serve":
        return None
    runs = module_runs(run.plain, r"^jit_pf\b|^jit\(pf\)")
    return float(np.median(runs)) * 1e3 if runs else None
