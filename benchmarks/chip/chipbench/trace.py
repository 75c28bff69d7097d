"""Capture a profiler trace of a window, and reduce it to numbers.

A trace is first turned into a plain form that the reduction reads and
the tests can keep on disk: ``{"planes": [{"name", "lines": [{"name",
"events": [[name, start_ns, dur_ns, {stat: value}], ...]}]}]}``.
Device planes are ``/device:TPU:<i>``; their ``XLA Ops`` line holds one
event per device operation and ``XLA Modules`` one per program run.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import shutil
import tempfile

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
KEEP_STATS = ("tf_op", "long_name", "hlo_module", "hlo_op", "name")


class Capture:
    """``start()`` / ``stop()`` around a window; ``plain()`` afterwards
    gives the trace's plain form.  The files live in a temporary
    directory that ``plain()`` removes."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def plain(self) -> dict:
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                return {"planes": []}
            return from_xspace(paths[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def from_xspace(path: str) -> dict:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    planes = []
    for pl in pd.planes:
        device = bool(DEVICE_PLANE.match(pl.name))
        lines = []
        for ln in pl.lines:
            evs = []
            for e in ln.events:
                st = {}
                if device:
                    for k, v in e.stats:
                        if k in KEEP_STATS:
                            st[k] = str(v)[:200]
                evs.append([e.name, float(e.start_ns), float(e.duration_ns),
                            st])
            lines.append({"name": ln.name, "events": evs})
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def save(plain: dict, path: str) -> None:
    with gzip.open(path, "wt") as fh:
        json.dump(plain, fh)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as fh:
        return json.load(fh)


# ---------------------------------------------------------------------------
# reduction


def device_planes(plain: dict) -> list:
    return [p for p in plain["planes"] if DEVICE_PLANE.match(p["name"])]


def line(plane: dict, name: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def merged(intervals) -> list:
    """Union of [start, end] intervals, as sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_s(plain: dict) -> float | None:
    """Seconds in which some operation ran on the device, averaged over
    the device planes that ran any; None where no device op was traced."""
    per = []
    for p in device_planes(plain):
        ops = line(p, "XLA Ops")
        if ops:
            per.append(sum(e - s for s, e in merged(
                (ev[1], ev[1] + ev[2]) for ev in ops)) * 1e-9)
    return sum(per) / len(per) if per else None


def op_seconds(plain: dict, match) -> float:
    """Device seconds of the ops for which ``match(event)`` is true,
    summed over all device planes."""
    return sum(ev[2] for p in device_planes(plain)
               for ev in line(p, "XLA Ops") if match(ev)) * 1e-9


def module_runs(plain: dict, pattern: str) -> list:
    """Durations (seconds) of each run of the programs whose name matches
    ``pattern``, from the ``XLA Modules`` line of the first device that
    ran any."""
    rx = re.compile(pattern)
    for p in device_planes(plain):
        runs = [ev[2] * 1e-9 for ev in line(p, "XLA Modules")
                if rx.search(ev[0])]
        if runs:
            return runs
    return []


_HLO = re.compile(r"^(%[\w.\-]+) = .*?[)}\]] ([a-z][\w\-]*)\(")


def short_name(name: str) -> str:
    """An HLO instruction's name and opcode ("%coord_stat.1 custom-call")
    for a device op whose trace name is the whole instruction."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:120]


def top_ops(plain: dict, k: int = 10) -> list:
    """The k device operations that took most time: [[name, seconds]]."""
    tot: dict = {}
    planes = device_planes(plain)
    for p in planes:
        for ev in line(p, "XLA Ops"):
            name = short_name(ev[0])
            tot[name] = tot.get(name, 0.0) + ev[2] * 1e-9
    n = max(len(planes), 1)
    return [[name, s / n] for name, s in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(plain: dict, k: int = 10) -> list:
    """The k longest stretches with no device op on the first device,
    each named by the innermost host span that covers its middle."""
    planes = [p for p in device_planes(plain) if line(p, "XLA Ops")]
    if not planes:
        return []
    busy = merged((ev[1], ev[1] + ev[2]) for ev in line(planes[0],
                                                       "XLA Ops"))
    gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])]
    gaps.sort(reverse=True)
    host = [ev for p in plain["planes"] if p["name"].startswith("/host:")
            for ln in p["lines"] for ev in ln["events"]]
    out = []
    for dur, s, e in gaps[:k]:
        mid = (s + e) / 2
        cover = [ev for ev in host if ev[1] <= mid <= ev[1] + ev[2]]
        name = (min(cover, key=lambda ev: ev[2])[0] if cover
                else "no host span")
        out.append([f"host: {name}", dur * 1e-9])
    return out


def describe(plain: dict, k: int = 25) -> dict:
    """A short summary of a trace's layout, for looking at one by hand:
    per plane and line, the event count and the k names that took most
    time, each with one event's kept stats."""
    out = []
    for p in plain["planes"]:
        lines = []
        for ln in p["lines"]:
            tot: dict = {}
            sample: dict = {}
            for ev in ln["events"]:
                tot[ev[0]] = tot.get(ev[0], 0.0) + ev[2]
                sample.setdefault(ev[0], ev[3])
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
            lines.append({"name": ln["name"], "events": len(ln["events"]),
                          "top": [[n, s * 1e-9, sample[n]] for n, s in top]})
        out.append({"name": p["name"], "lines": lines})
    return {"planes": out}
