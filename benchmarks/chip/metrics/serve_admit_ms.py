"""Median milliseconds of admitting one request (its prefill, first
token and cache splice): the program's ``sched.admit`` spans."""
import numpy as np

from chipbench import spans


def read(run):
    if run.kind != "serve":
        return None
    admits = spans.find(run.plain, spans.ADMIT)
    return float(np.median(spans.durations_ms(admits))) if admits else None
