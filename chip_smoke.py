"""On-chip smoke test of the main paths, on one TPU chip.

    python chip_smoke.py

Runs, in one process and in this order, through the entry points a user
calls, at the published widths of ``paper-100m`` (124.7M params, random
weights from a seed):

  (a) device gate — exits non-zero unless JAX's first device is a TPU and
      the kernels resolve to compiled (not interpreted) Pallas;
  (b) train — ``train_loop``, n=8 agents, f=2, ALIE attack, trimmed_mean
      (impl="auto"), seq 256, per-agent batch 2, remat, AdamW, 5 steps,
      every loss finite;
  (c) async — the same roster with multi_krum under a straggler schedule
      and a quorum of 6, so the masked pairwise kernels and the donated
      in-flight buffer run: 4 steps, every loss finite;
  (d) kernels — compiled impl="pallas" against impl="gather" on a seeded
      (8, 124.7M) fp32 arena for median, trimmed_mean and multi_krum, plain
      and masked, to a relative 1e-6;
  (e) serve — ``ReplicatedScheduler`` with r=5, f=2 and a
      coordinate_median vote: 6 requests (prompt lengths from {32, 64}, 16
      new tokens) give the same token streams with 2 replicas holding
      corrupted params as with none.

The last line of standard output is ``{"ok": true, "device": {...}}``;
any failed gate raises, so the process exits non-zero before printing it.
Compiled programs are cached where ``JAX_COMPILATION_CACHE_DIR`` says,
else under ``.jax_cache/`` next to this file.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

MODEL = "paper-100m"


def _say(msg: str) -> None:
    print(msg, flush=True)


def _gate(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"gate failed: {what}")


def _steps_ok(hist, steps: int, what: str) -> list:
    losses = [h["loss"] for h in hist]
    _gate(len(hist) == steps, f"{what}: {len(hist)} of {steps} steps ran")
    _gate(all(math.isfinite(l) for l in losses),
          f"{what}: non-finite loss in {losses}")
    return losses


def _memory_stats() -> dict:
    """The device allocator's byte counters (peak_bytes_in_use among them),
    where the backend reports them."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {k: v for k, v in stats.items() if "bytes" in k}


def _step_times(hist) -> tuple[float, list]:
    """(first step incl. compile, later per-step wall seconds) from the
    loop's cumulative ``wall_s`` (logged every step, so host-synced)."""
    wall = [h["wall_s"] for h in hist]
    return wall[0], [b - a for a, b in zip(wall, wall[1:])]


def _roster(n: int, f: int, rule: str, remat: bool = True, **hyper):
    from repro.core.aggregators import make_spec
    from repro.training import ByzantineConfig
    return ByzantineConfig(
        n_agents=n, f=f, attack="alie", remat=remat,
        aggregator=make_spec(rule, f=f, n=n, impl="auto", **hyper))


def phase_train(cfg, *, n=8, f=2, seq=256, batch=2, steps=5, seed=0):
    """(b) synchronous robust training through ``train_loop``."""
    from repro.data import SyntheticLM
    from repro.optim import adamw, constant
    from repro.training import train_loop
    ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq, n_agents=n,
                     per_agent_batch=batch)
    bz = _roster(n, f, "trimmed_mean")
    _, hist = train_loop(cfg, bz, adamw(constant(3e-4)), ds, steps=steps,
                         seed=seed, log_every=1, log_fn=lambda s: None)
    losses = _steps_ok(hist, steps, "train")
    first, rest = _step_times(hist)
    return {"losses": losses, "first_step_s": first, "step_s": rest,
            "memory": _memory_stats()}


def phase_async(cfg, *, n=8, f=2, seq=256, batch=2, steps=4, quorum=6,
                seed=0):
    """(c) asynchronous quorum training: stragglers deliver late, so steps
    aggregate a masked, staleness-weighted subset of the roster."""
    from repro.data import SyntheticLM
    from repro.optim import adamw, constant
    from repro.simulator import SimConfig, Straggler
    from repro.training import train_loop
    ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq, n_agents=n,
                     per_agent_batch=batch)
    bz = _roster(n, f, "multi_krum")
    sim = SimConfig(faults=(Straggler(dist="constant", scale=3.0,
                                      agents=(n - 2, n - 1)),),
                    quorum=quorum, seed=seed)
    _, hist = train_loop(cfg, bz, adamw(constant(3e-4)), ds, steps=steps,
                         seed=seed, log_every=1, log_fn=lambda s: None,
                         sim=sim)
    losses = _steps_ok(hist, steps, "async")
    arrived = [h["arrived"] for h in hist]
    _gate(any(a < n for a in arrived),
          f"async: every step delivered all {n} rows {arrived}")
    first, rest = _step_times(hist)
    return {"losses": losses, "arrived": arrived, "first_step_s": first,
            "step_s": rest}


def phase_kernels(p: int, *, n=8, f=2, seed=0, tol=1e-6):
    """(d) compiled kernels against the gather reference on one seeded
    (n, p) fp32 arena.  Rows get distinct scales, so Krum scores are far
    apart: a different multi_krum pick would move the output by O(1),
    not by rounding."""
    import jax
    import jax.numpy as jnp
    from repro.core.aggregators import make_spec
    k_g, k_w = jax.random.split(jax.random.PRNGKey(seed))
    scale = 1.0 + 0.25 * jnp.arange(n, dtype=jnp.float32)
    arena = jax.random.normal(k_g, (n, p), jnp.float32) * scale[:, None]
    mask = jnp.arange(n) < n - 2                       # 2 rows absent
    w = jax.random.uniform(k_w, (n,), minval=0.5, maxval=1.0)
    out = {}
    for rule in ("coordinate_median", "trimmed_mean", "multi_krum"):
        for mode, (m, wt) in (("plain", (None, None)),
                              ("masked", (mask, w))):
            got, ref = (jax.jit(make_spec(rule, f=f, n=n, impl=impl)
                                .aggregate_flat)(arena, mask=m, weights=wt)
                        for impl in ("pallas", "gather"))
            diff = float(jnp.max(jnp.abs(got - ref)))
            rel = diff / max(float(jnp.max(jnp.abs(ref))), 1e-30)
            _gate(got.shape == (p,) and bool(jnp.all(jnp.isfinite(got))),
                  f"kernels: {rule}/{mode} output not finite ({p},)")
            _gate(rel <= tol, f"kernels: {rule}/{mode} pallas vs gather "
                  f"max|diff| {diff!r} (relative {rel!r} > {tol})")
            out[f"{rule}/{mode}"] = {"max_abs_diff": diff, "rel": rel}
    return out


def phase_serve(cfg, *, r=5, f=2, prompt_lens=(32, 64), new_tokens=16,
                n_requests=6, seed=0):
    """(e) replicated serving: 2 replicas with corrupted params must not
    change a single committed token, on the early-commit path and on the
    full coordinate_median vote."""
    import jax
    import jax.numpy as jnp
    from repro.core.aggregators import make_spec
    from repro.models import init_params
    from repro.serving.sched import (ReplicatedScheduler, Request,
                                     poisson_requests)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    bad = init_params(cfg, jax.random.PRNGKey(seed + 1))
    clean = jax.tree.map(lambda l: jnp.stack([l] * r), params)
    corrupt = jax.tree.map(
        lambda l, b: jnp.stack([l] * (r - f) + [b] * f), params, bad)
    spec = make_spec("coordinate_median", f=f, n=r)
    reqs = poisson_requests(1.0, 10.0 * n_requests, seed=seed,
                            vocab_size=cfg.vocab_size,
                            prompt_lens=prompt_lens,
                            new_tokens=(new_tokens,),
                            max_requests=n_requests)
    _gate(len(reqs) == n_requests, f"serve: {len(reqs)} requests drawn")

    def run(stack, early_commit):
        rs = [Request(q.rid, q.tokens, q.max_new_tokens, q.arrival)
              for q in reqs]
        sched = ReplicatedScheduler(
            cfg, stack, spec, seq_capacity=max(prompt_lens) + new_tokens,
            early_commit=early_commit)
        t0 = time.time()
        sched.submit_all(rs)
        sched.run()
        return [q.out for q in rs], time.time() - t0

    ref, t_ref = run(clean, True)
    _gate(all(len(o) == new_tokens for o in ref),
          f"serve: stream lengths {[len(o) for o in ref]}")
    out = {"clean_s": t_ref, "prompt_lens": [q.prompt_len for q in reqs]}
    for early in (True, False):
        got, t = run(corrupt, early)
        _gate(got == ref, f"serve: corrupted run (early_commit={early}) "
              "changed the token streams")
        out[f"corrupted_early_commit_{early}_s"] = t
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv:
        raise SystemExit(f"chip_smoke.py takes no arguments, got {argv}")
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _say(f"chip_smoke: no TPU (first device is {dev.platform!r}); "
             "this script only runs on the chip")
        return 2

    import importlib.metadata as md

    import jaxlib
    from repro.configs import get_config, num_params
    from repro.kernels import default_interpret
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    interp = default_interpret()
    _say(f"(a) device {dev.device_kind!r} x{len(jax.devices())}  "
         f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
         f"libtpu {md.version('libtpu')}  default_interpret={interp}  "
         f"compile cache {cache}")
    _gate(interp is False, "kernels would run in interpret mode on the chip")

    cfg = get_config(MODEL)
    p = num_params(cfg)
    _say(f"model {MODEL}: {p} params ({cfg.dtype})")

    t = time.time()
    tr = phase_train(cfg)
    _say(f"(b) train: losses {tr['losses']}  first step (with compile) "
         f"{tr['first_step_s']:.1f}s  later steps {tr['step_s']} s  "
         f"peak_bytes_in_use {tr['memory'].get('peak_bytes_in_use')}  "
         f"[{time.time() - t:.0f}s]")
    _say(f"(b) device memory counters {tr['memory']}")
    t = time.time()
    asy = phase_async(cfg)
    _say(f"(c) async: losses {asy['losses']}  arrived {asy['arrived']}  "
         f"first step {asy['first_step_s']:.1f}s  later {asy['step_s']} s  "
         f"[{time.time() - t:.0f}s]")
    t = time.time()
    ker = phase_kernels(p)
    for k, v in ker.items():
        _say(f"(d) {k}: max|pallas-gather| {v['max_abs_diff']!r}  "
             f"relative {v['rel']!r}")
    _say(f"(d) kernels within 1e-6  [{time.time() - t:.0f}s]")
    t = time.time()
    sv = phase_serve(cfg)
    _say(f"(e) serve: 6 streams x 16 tokens identical with 2 of 5 replicas "
         f"corrupted (early commit and full vote); prompt lengths "
         f"{sv['prompt_lens']}  [{time.time() - t:.0f}s]")
    _say(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
