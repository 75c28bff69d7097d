"""Serving launcher: plain decode, replicated f-of-r decode, or the
continuous-batching scheduler — with flight-recorder attachment.

  # plain batched prefill + greedy decode demo
  PYTHONPATH=src python -m repro.launch.serve --arch paper-100m --smoke \
      --batch 4 --prompt-len 32 --new-tokens 16

  # f-of-r replicated decode through the robust vote
  PYTHONPATH=src python -m repro.launch.serve --arch paper-100m --smoke \
      --replicas 5 --f 2 --aggregator coordinate_median --record t.jsonl

  # the serving control plane: Poisson arrivals through the scheduler,
  # early commit + suspicion-driven eviction, then the suspicion report
  PYTHONPATH=src python -m repro.launch.serve --arch paper-100m --smoke \
      --sched --replicas 5 --f 2 --rate 0.8 --requests 12 \
      --deadline 2.0 --record t.jsonl
  PYTHONPATH=src python -m repro.launch.report t.jsonl
"""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-100m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    # replicated decode (r > 1 switches the engine)
    ap.add_argument("--replicas", type=int, default=1,
                    help="decode replicas r (1 = plain single-model)")
    ap.add_argument("--f", type=int, default=1,
                    help="tolerated Byzantine replicas")
    ap.add_argument("--aggregator", default="coordinate_median",
                    help="robust rule voting the per-step logits")
    ap.add_argument("--record", default=None, metavar="PATH",
                    help="write a flight-recorder JSONL trace here "
                         "(render it with `python -m repro.launch.report`)")
    # scheduler mode (implies --replicas)
    ap.add_argument("--sched", action="store_true",
                    help="drive the continuous-batching scheduler with a "
                         "Poisson workload instead of one fixed batch")
    ap.add_argument("--rate", type=float, default=0.5,
                    help="sched: request arrivals per virtual second")
    ap.add_argument("--requests", type=int, default=8,
                    help="sched: number of requests in the workload")
    ap.add_argument("--deadline", type=float, default=None,
                    help="sched: early-commit SLO deadline (virtual s)")
    ap.add_argument("--no-early-commit", action="store_true",
                    help="sched: always run the full quorum vote")
    ap.add_argument("--evict-window", type=int, default=0,
                    help="sched: >0 attaches a SuspicionPolicy with this "
                         "zero-selection eviction window")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving import generate, generate_replicated

    cfg = get_config(args.arch + ("-smoke" if args.smoke else ""))
    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)

    recorder = None
    if args.record:
        from repro.obs.recorder import Recorder
        recorder = Recorder(args.record, meta={"launcher": "serve"})

    if args.sched:

        from repro.core.aggregators import make_spec
        from repro.serving.sched import (ReplicatedScheduler,
                                         SuspicionPolicy, poisson_requests)
        r = max(args.replicas, 2 * args.f + 1)
        stack = jax.tree.map(lambda l: jnp.stack([l] * r), params)
        spec = make_spec(args.aggregator, f=args.f, n=r)
        policy = (SuspicionPolicy(r, args.f, window=args.evict_window)
                  if args.evict_window > 0 else None)
        cap = args.prompt_len + args.new_tokens
        sched = ReplicatedScheduler(
            cfg, stack, spec, seq_capacity=cap,
            slot_buckets=(2, 4, 8), deadline=args.deadline,
            early_commit=not args.no_early_commit,
            policy=policy, recorder=recorder)
        reqs = poisson_requests(
            args.rate, args.requests / max(args.rate, 1e-9), seed=args.seed,
            vocab_size=cfg.vocab_size,
            prompt_lens=(args.prompt_len // 2, args.prompt_len),
            new_tokens=(args.new_tokens,), max_requests=args.requests)
        sched.submit_all(reqs)
        metrics = sched.run()
        print(f"scheduler: {spec.describe()} over r={r} replicas")
        for req in reqs:
            print(f"  req {req.rid} (T={req.prompt_len}, "
                  f"t={req.arrival:.2f}): {req.out}")
        for k, v in metrics.summary().items():
            print(f"  {k}: {v:.4g}" if isinstance(v, float)
                  else f"  {k}: {v}")
        if policy is not None and policy.events:
            print("  roster events:", policy.events)
    elif args.replicas > 1:
        from repro.core.aggregators import make_spec
        r = args.replicas
        stack = jax.tree.map(lambda l: jnp.stack([l] * r), params)
        spec = make_spec(args.aggregator, f=args.f, n=r)
        batch = {"tokens": jax.random.randint(
            key, (args.batch, args.prompt_len), 0, cfg.vocab_size)}
        out = generate_replicated(cfg, stack, batch, args.new_tokens, spec,
                                  recorder=recorder)
        print(f"replicated ({spec.describe()}, r={r}) token ids:")
        for row in out.tolist():
            print(" ", row)
    else:
        batch = {"tokens": jax.random.randint(
            key, (args.batch, args.prompt_len), 0, cfg.vocab_size)}
        if cfg.frontend == "vision":
            batch["vision_embeds"] = jnp.zeros(
                (args.batch, cfg.frontend_tokens, cfg.d_model),
                jnp.dtype(cfg.dtype))
        if cfg.frontend == "audio":
            batch["audio_embeds"] = jnp.zeros(
                (args.batch, cfg.encoder_seq, cfg.d_model),
                jnp.dtype(cfg.dtype))
        out = generate(cfg, params, batch, args.new_tokens)
        print("generated token ids:")
        for row in out.tolist():
            print(" ", row)

    if recorder is not None:
        recorder.close()
        print(f"trace written to {recorder.path} "
              f"({len(recorder.events)} events) — render with "
              f"`python -m repro.launch.report {recorder.path}`")


if __name__ == "__main__":
    main()
