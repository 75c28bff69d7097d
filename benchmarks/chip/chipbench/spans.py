"""The program's own spans in a trace's plain form (``chipbench.trace``).

The program opens host spans through ``jax.profiler.TraceAnnotation``
(``repro.obs.spans``); they land on the ``/host:`` planes of the trace,
on the device's clock, under their names.  Spans are [start_ns, end_ns]
pairs; one span lies inside another where its interval is contained in
the other's, which is how nesting is decided.  A program without spans
gives empty lists, and the readers built on them then find nothing.
"""
from __future__ import annotations

from bisect import bisect_right

from chipbench import trace

STEP = "sched.step"
DECODE = "sched.decode"
TO_HOST = "sched.to_host"
COMMIT = "sched.commit"
ADMIT = "sched.admit"
QUEUED = "sched.queued"


def find(plain: dict, name: str) -> list:
    """Every host span named ``name``, as sorted [start_ns, end_ns]."""
    return sorted([ev[1], ev[1] + ev[2]]
                  for p in plain["planes"] if p["name"].startswith("/host:")
                  for ln in p["lines"] for ev in ln["events"]
                  if ev[0] == name)


def durations_ms(spans: list) -> list:
    return [(e - s) * 1e-6 for s, e in spans]


def _inside(sp, outer) -> bool:
    return outer[0] <= sp[0] and sp[1] <= outer[1]


def outside(spans: list, outer: list) -> list:
    """The spans that lie inside none of ``outer``."""
    return [sp for sp in spans if not any(_inside(sp, o) for o in outer)]


def holding(outer: list, inner: list) -> list:
    """The spans of ``outer`` that contain at least one of ``inner``."""
    return [o for o in outer if any(_inside(sp, o) for sp in inner)]


def device_busy(plain: dict) -> list:
    """Union of the device op intervals of the first device that ran any
    (sorted, disjoint), as ``device_idle_share`` counts them."""
    for p in trace.device_planes(plain):
        ops = trace.line(p, "XLA Ops")
        if ops:
            return trace.merged((ev[1], ev[1] + ev[2]) for ev in ops)
    return []


def idle_ns(intervals: list, busy: list) -> float:
    """Nanoseconds of the union of ``intervals`` in which the device ran
    nothing (``busy`` sorted and disjoint)."""
    total = 0.0
    starts = [s for s, _ in busy]
    for s, e in trace.merged(intervals):
        covered = 0.0
        i = max(bisect_right(starts, s) - 1, 0)
        while i < len(busy) and busy[i][0] < e:
            covered += max(0.0, min(e, busy[i][1]) - max(s, busy[i][0]))
            i += 1
        total += (e - s) - covered
    return total
