"""Argument parsing, the chip gate, and the result line."""
from __future__ import annotations

import argparse
import importlib
import json
import sys
from types import SimpleNamespace

from chipbench import spec as spec_mod


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def device_gate(chips: int):
    """The devices of a run that may measure: TPUs, at least ``chips``."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {devices[0].platform!r}; "
                     "this benchmark measures only on the chip")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devices)}")
    return devices


def parse(argv):
    ap = argparse.ArgumentParser(prog="benchmarks/chip/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # measurement aids, never used by a benchmark run: put a control in
    # the program's place beside the run, or keep a trace's layout
    ap.add_argument("--control", choices=("fp8", "halfbatch"), default=None)
    ap.add_argument("--dump-trace", default=None)
    return ap.parse_args(argv)


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _finite(x: float) -> float:
    return x if x == x and abs(x) != float("inf") else 1e300


def main(argv, root: str, t_start: float, gate=device_gate,
         bench_dir: str = spec_mod.BENCH_DIR) -> int:
    args = parse(argv)
    try:
        cell = spec_mod.load_cell(root, args.workload, bench_dir)
    except (LookupError, ValueError, FileNotFoundError) as e:
        _say(f"chipbench: refused before any run: {e}")
        return 2
    try:
        devices = gate(cell.chips)
    except NoChip as e:
        _say(f"chipbench: {e}")
        return 3
    from chipbench.clock import SetupClock, enable_cache
    clock = SetupClock(t_start)
    clock.phases["import"] = clock.now()
    enable_cache(root)
    kind = importlib.import_module(f"chipbench.{cell.traffic['kind']}")
    res = kind.run(cell, args, clock, _say)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(res.memory["peak_bytes_in_use"])}
    out = {"correct": all(c["value"] <= c["limit"]
                          for c in res.checks.values()),
           "attempted": int(res.attempted), "failed": int(res.failed)}
    if args.trace:
        from chipbench import trace as trace_mod
        from chipbench.work import peaks
        plain = res.capture.plain()
        if args.dump_trace:
            with open(args.dump_trace + ".describe.json", "w") as fh:
                json.dump(trace_mod.describe(plain), fh, indent=1)
            trace_mod.save(plain, args.dump_trace + ".json.gz")
        busy = trace_mod.busy_s(plain)
        run = SimpleNamespace(**vars(res.run), plain=plain, busy_s=busy,
                              trace_window_s=res.run.trace_s,
                              peak=peaks(dev.device_kind, bench_dir))
        out["metrics"] = spec_mod.read_per_layer(cell, run)
        device["busy_s"] = busy if busy is not None else 0.0
        device["window_s"] = res.run.trace_s
        out["device"] = device
        out["breakdown"] = {"device_ops": trace_mod.top_ops(plain),
                            "idle_gaps": trace_mod.idle_gaps(plain)}
    else:
        out["metrics"] = {m["name"]: {"value": float(res.e2e[m["name"]]),
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
        out["device"] = device
    for name, c in res.checks.items():
        _say(f"check {name} {c['value']!r} limit {c['limit']!r}")
    # a number that never came (inf, nan) prints as a huge one: JSON has
    # no infinity
    out["checks"] = {k: {"value": _finite(c["value"]), "limit": c["limit"]}
                     for k, c in res.checks.items()}
    print(json.dumps(out), flush=True)
    return 0
