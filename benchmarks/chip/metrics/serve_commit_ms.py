"""Median milliseconds of a decode step's commit on the host (argmax,
the f+1 commit walk and, where it runs, the vote): the program's
``sched.commit`` spans outside ``sched.admit``."""
import numpy as np

from chipbench import spans


def read(run):
    if run.kind != "serve":
        return None
    commits = spans.outside(spans.find(run.plain, spans.COMMIT),
                            spans.find(run.plain, spans.ADMIT))
    return float(np.median(spans.durations_ms(commits))) if commits else None
