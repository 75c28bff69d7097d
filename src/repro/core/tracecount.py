"""Backward-compat shim — the trace-count bookkeeping moved to
:mod:`repro.obs.counters` (PR 6), which adds the public
``snapshot()``/``reset()`` API the flight recorder builds its
recompile ledger on.  ``TRACE_COUNTS`` here IS the same Counter object as
``repro.obs.counters.COUNTERS``, so existing snapshot-diff tests keep
working unchanged.  New code should import from ``repro.obs.counters``.
"""
from __future__ import annotations

from repro.obs.counters import (TRACE_COUNTS, count_trace, reset,
                                reset_traces, snapshot, trace_count)

__all__ = ["TRACE_COUNTS", "count_trace", "trace_count", "reset_traces",
           "snapshot", "reset"]
