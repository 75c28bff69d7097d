"""Program spans on the JAX profiler's clock.

A span is a host-side ``jax.profiler.TraceAnnotation``: while a profiler
session runs (``jax.profiler.start_trace`` / ``trace``) it lands in the
session's own trace, on the same clock as the device's operations, so a
gap in the device's work can be named by what the host was doing in it.
With no session active a span costs about a microsecond and records
nothing; its keyword arguments are not encoded.

The serving scheduler and the training loop open the spans named below;
``benchmarks/chip/metrics/`` reads them by name.  Inside the jitted
training steps the layer boundaries are ``jax.named_scope`` instead
(``grads``, ``arena``, ``attack``, ``aggregate``, ``buffer``,
``unravel``, ``optimizer``): they name the program's operations in its
HLO metadata, not host time.
"""
from __future__ import annotations

import jax

# serving scheduler (repro.serving.sched.scheduler)
SCHED_STEP = "sched.step"          # all of ReplicatedScheduler.step()
SCHED_DECODE = "sched.decode"      # replicated decode, dispatch to ready
SCHED_TO_HOST = "sched.to_host"    # the (r, B, V) logits pulled to NumPy
SCHED_COMMIT = "sched.commit"      # host argmax, commit walk, vote
SCHED_VOTE = "sched.vote"          # the full vote of a fallback
SCHED_ADMIT = "sched.admit"        # one admitted request
SCHED_PREFILL = "sched.prefill"    # its replicated prefill, to ready
SCHED_SPLICE = "sched.splice"      # its cache rows written into the slab
SCHED_RESIZE = "sched.resize"      # the slab moved to another bucket
SCHED_QUEUED = "sched.queued"      # submit() to the start of its admit

# training loop (repro.simulator.async_loop.async_train_loop)
TRAIN_STEP = "train"               # one iteration (a step annotation)
TRAIN_BATCH = "train.batch"        # dataset.batch
TRAIN_DISPATCH = "train.dispatch"  # the jitted step call
TRAIN_LOG = "train.log"            # recorder feed and log line, with syncs
TRAIN_CHECKPOINT = "train.checkpoint"


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A context manager timing its body as ``name``; ``args`` show in
    XProf beside the span."""
    return jax.profiler.TraceAnnotation(name, **args)


def step_span(name: str, n: int) -> jax.profiler.StepTraceAnnotation:
    """A step annotation: the profiler's per-step analysis keys on it."""
    return jax.profiler.StepTraceAnnotation(name, step_num=n)


def open_span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """Start a span that a later call ends with :func:`close_span`, on
    the same thread."""
    s = jax.profiler.TraceAnnotation(name, **args)
    s.__enter__()
    return s


def close_span(s: jax.profiler.TraceAnnotation) -> None:
    s.__exit__(None, None, None)


__all__ = ["span", "step_span", "open_span", "close_span"]
