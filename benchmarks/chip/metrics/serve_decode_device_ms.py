"""Median device milliseconds of one run of the replicated decode
program (the scheduler's jitted ``dec``), from the device trace."""
import numpy as np

from chipbench.trace import module_runs


def read(run):
    if run.kind != "serve":
        return None
    runs = module_runs(run.plain, r"^jit_dec\b|^jit\(dec\)")
    return float(np.median(runs)) * 1e3 if runs else None
