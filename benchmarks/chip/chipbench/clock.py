"""The compile cache, and an itemized account of set-up.

Set-up is everything from process start to the window's start.  JAX's
own monitoring events split its compile work into tracing, lowering and
backend compile (which on a cache hit is the load from disk); the
phases the harness names (imports, weights, warm-up) are timed on the
host clock.  Compiles are counted all through the run, so a compile
inside the window shows.
"""
from __future__ import annotations

import gc
import os
import time
from collections import Counter
from contextlib import contextmanager

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"


def enable_cache(root: str) -> str:
    """JAX's persistent compile cache at ``<root>/.jax_cache``, a fixed
    path inside the checkout, caching every program however small or
    quick to compile."""
    import jax
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class SetupClock:
    """Host-clock phases from ``t_start`` plus JAX's compile events."""

    def __init__(self, t_start: float):
        import jax.monitoring as mon
        self.t_start = t_start
        self.phases: dict = {}
        self.secs: Counter = Counter()
        self.counts: Counter = Counter()
        self.gc_pauses: list = []    # (generation, seconds) of each
        self._gc_t = 0.0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        gc.callbacks.append(self._on_gc)

    def _on_duration(self, event, secs, **_):
        if event in (TRACE, LOWER, COMPILE):
            self.secs[event] += secs
            self.counts[event] += 1

    def _on_event(self, event, **_):
        if event in (HIT, MISS):
            self.counts[event] += 1

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        else:
            self.gc_pauses.append((info["generation"],
                                   time.perf_counter() - self._gc_t))

    def mark(self) -> tuple:
        """Where the counts stand, for :meth:`since`."""
        return (self.counts[COMPILE], self.counts[TRACE],
                len(self.gc_pauses))

    def since(self, mark: tuple) -> str:
        """Programs compiled or loaded, functions traced, and the host's
        garbage collections (with the longest pause) since ``mark``: a
        window that stalls shows here whether the program or the
        collector held the host."""
        c0, t0, g0 = mark
        gcs = self.gc_pauses[g0:]
        gen, worst = max(gcs, key=lambda x: x[1]) if gcs else (0, 0.0)
        return (f"compiles {self.counts[COMPILE] - c0}, traces "
                f"{self.counts[TRACE] - t0}, garbage collections "
                f"{len(gcs)} taking {sum(x for _, x in gcs):.4f} s, the "
                f"longest {worst:.4f} s (generation {gen})")

    def now(self) -> float:
        return time.perf_counter() - self.t_start

    @contextmanager
    def phase(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = self.phases.get(name, 0.0) + (
                time.perf_counter() - t)

    def summary(self) -> dict:
        return {"phases_s": {k: round(v, 3) for k, v in self.phases.items()},
                "trace_s": round(self.secs[TRACE], 3),
                "lower_s": round(self.secs[LOWER], 3),
                "compile_or_load_s": round(self.secs[COMPILE], 3),
                "programs": self.counts[COMPILE],
                "cache_hits": self.counts[HIT],
                "cache_misses": self.counts[MISS]}
