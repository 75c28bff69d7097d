"""Flight-recorder observability substrate (PR 6).

Its layers, consumed by the training loops, the async simulator and the
replicated serving engine:

:mod:`repro.obs.counters`
    Process-global compile counters (the promoted ``core/tracecount``),
    with a public ``snapshot()``/``reset()`` API — the substrate every
    compile-regression test and the recorder's recompile ledger read from.

:mod:`repro.obs.spans`
    Host spans on the JAX profiler's clock (``TraceAnnotation``): the
    serving scheduler's step, decode, logits pull, commit, vote,
    admission and queue wait, and the training loop's step, batch,
    dispatch, log and checkpoint.  They share the device trace's clock,
    so XProf or Perfetto shows what the host did in each device gap;
    the chip benchmark's serving metrics read them by name.

:mod:`repro.obs.telemetry`
    Per-step aggregation telemetry: fixed-shape per-agent selection
    weights emitted as aux outputs of the jitted steps
    (``spec.aggregate_with_telemetry``), host-side accumulation into
    per-agent time series, and derived *suspicion scores*
    (selection-rate vs the uniform baseline — the signal every
    detection-based defense in the survey starts from).

:mod:`repro.obs.recorder`
    The :class:`Recorder`: a JSONL event log (run metadata, step spans,
    telemetry rows, compile events, membership/fault annotations) plus a
    Chrome-trace/Perfetto export so a churn+crash run is visually
    inspectable in ``chrome://tracing`` / ui.perfetto.dev.

:mod:`repro.obs.report`
    Renders a recorded trace into the per-agent suspicion table,
    staleness/quorum percentiles, recompile ledger and rule-dispatch
    breakdown (``python -m repro.launch.report trace.jsonl``).

Hard contract: telemetry OFF is bit-identical to the pre-observability
code path (the telemetry branch is a static Python flag — same jaxpr, no
added recompiles); telemetry ON adds only fixed-shape aux outputs, so the
elastic-bucket compile budget is unchanged and the aggregation output
stays bit-for-bit (tests/test_obs.py pins both).
"""
from repro.obs import counters
from repro.obs.provenance import provenance
from repro.obs.recorder import Recorder, chrome_trace, read_trace
from repro.obs.telemetry import (agent_series, dispatch_record,
                                 suspicion_scores)

__all__ = [
    "counters", "provenance", "Recorder", "chrome_trace", "read_trace",
    "agent_series", "dispatch_record", "suspicion_scores",
]
