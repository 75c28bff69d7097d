"""Median milliseconds of pulling a decode step's (r, B, V) logits to
NumPy: the program's ``sched.to_host`` spans outside ``sched.admit``."""
import numpy as np

from chipbench import spans


def read(run):
    if run.kind != "serve":
        return None
    pulls = spans.outside(spans.find(run.plain, spans.TO_HOST),
                          spans.find(run.plain, spans.ADMIT))
    return float(np.median(spans.durations_ms(pulls))) if pulls else None
