"""Median host-clock milliseconds of a scheduler step that only decoded
(no admission), over the traced window: the scheduler's host work plus
the replicated decode it waits for."""
import numpy as np


def read(run):
    if run.kind != "serve" or not run.decode_step_s:
        return None
    return float(np.median(run.decode_step_s)) * 1e3
