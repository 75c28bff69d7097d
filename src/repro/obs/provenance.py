"""Run provenance: the environment fingerprint stamped into every bench
JSON and recorded trace.

ROADMAP item 1 moves the bench trajectories from CPU-interpret Pallas to
real TPU cores; numbers from the two regimes are not comparable, and a
``BENCH_*.json`` without a fingerprint cannot be told apart after the
fact.  One dict, cheap to compute, safe everywhere (every lookup is
individually guarded — a missing git binary or a non-repo checkout
degrades to ``"unknown"``, never an exception)."""
from __future__ import annotations

import os
import subprocess
import time


def _git_sha() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=5)
        if out.returncode == 0:
            return out.stdout.strip()
    except Exception:
        pass
    return "unknown"


def provenance() -> dict:
    """Environment fingerprint: jax version, backend, device kind,
    Pallas interpret-mode default, git SHA, wall-clock timestamp."""
    rec = {
        "jax_version": "unknown",
        "backend": "unknown",
        "device_kind": "unknown",
        "device_count": 0,
        "interpret": None,
        "git_sha": _git_sha(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
    }
    try:
        import jax
        rec["jax_version"] = jax.__version__
        rec["backend"] = jax.default_backend()
        devs = jax.devices()
        rec["device_count"] = len(devs)
        if devs:
            rec["device_kind"] = devs[0].device_kind
    except Exception:
        pass
    try:
        from repro.kernels.tiling import default_interpret
        rec["interpret"] = bool(default_interpret())
    except Exception:
        pass
    return rec


__all__ = ["provenance"]
