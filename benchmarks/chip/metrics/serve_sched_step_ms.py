"""Median milliseconds of a scheduler step that decoded and admitted
nothing, from the program's ``sched.step`` spans (those holding a
``sched.decode`` and no ``sched.admit``) on the profiler's clock: the
in-program twin of ``serve_decode_step_ms``."""
import numpy as np

from chipbench import spans


def read(run):
    if run.kind != "serve":
        return None
    steps = spans.holding(spans.find(run.plain, spans.STEP),
                          spans.find(run.plain, spans.DECODE))
    admitting = spans.holding(steps, spans.find(run.plain, spans.ADMIT))
    decode_only = [s for s in steps if s not in admitting]
    if not decode_only:
        return None
    return float(np.median(spans.durations_ms(decode_only)))
