"""p75 milliseconds from a request's submit to the start of its
admission: the program's ``sched.queued`` spans.  The traced window
holds about 45 requests; p75 is the highest percentile with ten or more
beyond it."""
import numpy as np

from chipbench import spans


def read(run):
    if run.kind != "serve":
        return None
    waits = spans.durations_ms(spans.find(run.plain, spans.QUEUED))
    return float(np.percentile(waits, 75)) if waits else None
