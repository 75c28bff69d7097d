"""Share of the device's idle time in the traced serving window (the
first device, as ``device_idle_share.serve`` counts it) that falls
inside the program's ``sched.to_host`` or ``sched.commit`` spans: the
device waiting on the host's logits pull and vote."""
from chipbench import spans


def read(run):
    if run.kind != "serve" or not run.trace_window_s:
        return None
    held = (spans.find(run.plain, spans.TO_HOST)
            + spans.find(run.plain, spans.COMMIT))
    busy = spans.device_busy(run.plain)
    if not held or not busy:
        return None
    idle = run.trace_window_s * 1e9 - sum(e - s for s, e in busy)
    if idle <= 0:
        return None
    return 100.0 * spans.idle_ns(held, busy) / idle
