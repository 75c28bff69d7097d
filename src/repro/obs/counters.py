"""Process-global counters — the compile-accounting substrate.

Promoted from ``repro.core.tracecount`` (which remains as a
backward-compat shim): ``count_trace(site)`` is called from INSIDE
jit-traced step functions (the async/sync training steps, the serving
agreement step).  Python side effects run once per TRACE, never per
execution, so the counter increments exactly when XLA (re)compiles that
site — zero runtime cost on the compiled path.  The membership-retrace
suite asserts compile bounds on the real loops with it, and the flight
recorder (:mod:`repro.obs.recorder`) diffs :func:`snapshot` around every
step to emit its recompile ledger.

Counters are monotonic; consumers snapshot before/after rather than
resetting blindly (tests sharing the process must not clobber each
other).  What the host is doing over time is not counted here: the
loops open profiler spans for that (:mod:`repro.obs.spans`).
"""
from __future__ import annotations

from collections import Counter

# the ONE counter store — repro.core.tracecount aliases this same object,
# so legacy TRACE_COUNTS reads see every inc() and vice versa
COUNTERS: Counter = Counter()

# legacy alias (same object, not a copy)
TRACE_COUNTS: Counter = COUNTERS


def inc(name: str, by: int = 1) -> None:
    """Increment a counter (monotonic)."""
    COUNTERS[name] += by


def count_trace(site: str) -> None:
    """Record one tracing of ``site`` (call from INSIDE the traced fn)."""
    inc(site)


def trace_count(site: str) -> int:
    return COUNTERS[site]


def snapshot() -> dict:
    """Point-in-time copy: ``{"counters": {...}}``.

    Plain dicts (detached from the live stores), so two snapshots diff
    safely across any amount of intervening work."""
    return {"counters": dict(COUNTERS)}


def counter_delta(before: dict, after: dict | None = None) -> dict:
    """Per-site counter increments between two :func:`snapshot` calls
    (``after=None`` means "now").  Sites with zero delta are omitted —
    the recorder emits one compile event per nonzero entry."""
    after = after if after is not None else snapshot()
    b = before.get("counters", {})
    out = {}
    for site, n in after.get("counters", {}).items():
        d = n - b.get(site, 0)
        if d:
            out[site] = d
    return out


def reset(name: str | None = None) -> None:
    """Clear counters (one name, or everything).  Prefer snapshot-
    diffing in tests — reset is for interactive sessions."""
    if name is None:
        COUNTERS.clear()
    else:
        COUNTERS.pop(name, None)


def reset_traces(site: str | None = None) -> None:
    """Legacy alias of :func:`reset` restricted to counters."""
    if site is None:
        COUNTERS.clear()
    else:
        COUNTERS.pop(site, None)


__all__ = [
    "COUNTERS", "TRACE_COUNTS", "inc", "count_trace", "trace_count",
    "snapshot", "counter_delta", "reset", "reset_traces",
]
