"""Serving cells: ``ReplicatedScheduler.step()`` on the wall clock.

Set-up makes the replica stack from the seed (the mix's ``bad_replicas``
hold weights from seed + 1), builds the scheduler as the configuration
states and warms up every prompt length of the mix and every slot-bucket
move (grow and shrink between each pair of buckets).  The window is an
open loop: each request of the seed's schedule is submitted when it
falls due, every committed token is stamped on the host clock after the
step that commits it (the step ends in a host sync), and after the
window the scheduler drains what is in flight.

The check takes a sample of finished requests drawn from the seed, the
longest among them, and runs the float32 reference once over each
prompt and its served tokens: the number compared is the widest gap by
which a served token's reference logit lies below the reference's best.
"""
from __future__ import annotations

import gc
import time
from collections import deque
from types import SimpleNamespace

import numpy as np

from chipbench import traffic as traffic_mod
from chipbench import weights
from chipbench.train import device_memory


def _warm_waves(buckets, lens):
    """Request waves that take the scheduler through every slot-bucket
    move: each wave is a list of (submit_after_steps, count, budget).
    Short requests (budget 2) retire after one decode step, long ones
    (budget 4) stay, so the active count falls to a chosen bucket."""
    b = list(buckets)
    waves = [[(0, b[0], 2)]]                         # admit at the smallest
    for j in range(1, len(b)):
        for i in range(j):                           # grow b0->bj, bj->bi
            long_ = b[i] if i else 0
            waves.append([(0, long_, 4), (0, b[j] - long_, 2)])
    for i in range(1, len(b)):
        for j in range(i + 1, len(b)):               # grow bi->bj
            waves.append([(0, b[i], 4), (1, b[j] - b[i], 2)])
    return waves


def warm_up(sched, buckets, lens, vocab, rng):
    from repro.serving.sched import Request
    rid = -1
    k = 0
    for wave in _warm_waves(buckets, lens):
        steps = 0
        todo = sorted(wave)
        while True:
            while todo and todo[0][0] <= steps:
                _, count, budget = todo.pop(0)
                for _ in range(count):
                    T = int(lens[k % len(lens)])
                    k += 1
                    sched.submit(Request(rid, rng.integers(
                        0, vocab, size=T).astype(np.int32), budget, 0.0))
                    rid -= 1
            if not sched.step() and not todo:
                break
            steps += 1


def run(cell, args, clock, say):
    import jax
    from repro.configs.base import ArchConfig
    from repro.core.aggregators import make_spec
    from repro.serving.sched import ReplicatedScheduler, Request

    tr, dep = cell.traffic, cell.config["deployment"]
    seed = args.seed
    seconds = (min(args.seconds, tr["trace_seconds"]) if args.trace
               else args.seconds)
    with clock.phase("build"):
        cfg = ArchConfig(name=cell.config_name, **cell.config["model"])
        shapes = weights.param_shapes(cfg)
        r, nbad = dep["replicas"], tr["bad_replicas"]
        spec = make_spec(dep["vote"], f=dep["f"], n=r)
        schedule = traffic_mod.serve_requests(tr, seed, seconds,
                                              cfg.vocab_size)
    with clock.phase("weights"):
        stack = weights.make(shapes, (seed + 1,) * nbad + (seed,) * (r - nbad))
        jax.block_until_ready(stack)
    buckets = tuple(dep["slot_buckets"])
    with clock.phase("scheduler"):
        sched = ReplicatedScheduler(
            cfg, stack, spec, slot_buckets=buckets,
            seq_capacity=dep["seq_capacity"],
            early_commit=dep["early_commit"])
    with clock.phase("warmup"):
        warm_up(sched, buckets, tr["prompt_lens"], cfg.vocab_size,
                np.random.default_rng(np.random.SeedSequence([seed, 1])))

    capture = None
    if args.trace:
        from chipbench.trace import Capture
        capture = Capture()
        capture.start()
    n = len(schedule)
    reqs = [Request(i, q["tokens"], q["max_new"], 0.0)
            for i, q in enumerate(schedule)]
    t0 = time.perf_counter()
    setup_s = t0 - clock.t_start
    mark = clock.mark()
    due = t0 + np.array([q["due"] for q in schedule])
    end = t0 + seconds
    drain_end = end + tr["drain_seconds"]
    pending = deque(range(n))
    inflight: dict = {}
    stamps = [[] for _ in range(n)]
    late = np.zeros(n)
    steps = []                       # (host seconds, decoded, admitted)
    queued = []                      # queue depth after each step
    trace_s = None
    while True:
        now = time.perf_counter()
        while pending and due[pending[0]] <= now:
            i = pending.popleft()
            late[i] = now - due[i]
            sched.submit(reqs[i])
            inflight[i] = 0
        if capture is not None and trace_s is None and now >= end:
            trace_s = now - t0
            capture.stop()
        if now >= drain_end:
            break
        busy_before = any(s is not None for s in sched.slots)
        ts = time.perf_counter()
        if not sched.step():
            if not pending and now >= end:
                break
            nxt = due[pending[0]] if pending else end
            time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
            continue
        te = time.perf_counter()
        admitted = 0
        for i in list(inflight):
            got = len(reqs[i].out)
            if got > inflight[i]:
                admitted += inflight[i] == 0
                stamps[i].extend([te] * (got - inflight[i]))
                inflight[i] = got
            if reqs[i].done:
                del inflight[i]
        steps.append((te - ts, busy_before, admitted))
        queued.append((te, len(sched.queue)))
    in_window = clock.since(mark)
    t_stop = time.perf_counter()

    ttft, tpot, tokens_in_window, failed = [], [], 0, 0
    for i, q in enumerate(schedule):
        st = stamps[i]
        tokens_in_window += sum(1 for t in st if t <= end)
        done = len(st) >= q["max_new"]
        failed += not done
        first = st[0] if st else t_stop
        ttft.append(first - due[i])
        last = st[-1] if done else t_stop
        tpot.append((last - first) / max(q["max_new"] - 1, 1))
    e2e = {"serve_ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3,
           "serve_tpot_p95_ms": float(np.percentile(tpot, 95)) * 1e3,
           "serve_tokens_per_s": tokens_in_window / seconds,
           "setup_s": setup_s}
    say(f"setup {clock.summary()}  setup_s {setup_s:.3f}")
    decode_s = [s for s, busy, adm in steps if busy and not adm]
    say(f"window {seconds} s: {n} requests, {failed} unfinished after "
        f"{t_stop - end:.2f} s of drain, {len(steps)} steps "
        f"({len(decode_s)} decode-only); inside the window and the drain: "
        f"{in_window}; generator lateness p50 "
        f"{np.median(late) * 1e3:.2f} ms max {late.max() * 1e3:.2f} ms; "
        f"queue depth max {max((q for _, q in queued), default=0)}, at the "
        f"window's end {next((q for t, q in queued if t >= end), 0)}")
    say("e2e " + "  ".join(f"{k} {v!r}" for k, v in e2e.items()))
    mem = device_memory(cell.chips)
    say(f"device memory after the window: {mem}")

    finished = [i for i in range(n) if reqs[i].done]
    sample = sample_requests(finished, reqs, seed, tr["check"]["tokens"])
    checked = [(np.asarray(reqs[i].tokens), list(reqs[i].out))
               for i in sample]
    del sched, stack, reqs
    gc.collect()
    t = time.perf_counter()
    gaps, control_gaps = reference_gaps(cell, seed, checked,
                                        control=args.control == "fp8")
    served = sum(len(o) for _, o in checked)
    checks = {"logit_gap": {"value": gaps,
                            "limit": tr["check"]["limits"]["logit_gap"]}}
    say(f"reference check {time.perf_counter() - t:.2f} s over "
        f"{len(checked)} requests, {served} served tokens")
    control = None
    if args.control == "fp8":
        control = {"logit_gap": {"value": control_gaps,
                                 "limit": checks["logit_gap"]["limit"]}}
        say(f"control fp8: logit_gap {control_gaps!r}")
    run_ns = SimpleNamespace(
        kind="serve", model=cell.config["model"], trace_s=trace_s,
        decode_step_s=decode_s,
        steps=steps, queue_max=max((q for _, q in queued), default=0),
        queue_end=next((q for t, q in queued if t >= end), 0))
    return SimpleNamespace(e2e=e2e, attempted=n, failed=failed,
                           checks=checks, memory=mem, capture=capture,
                           run=run_ns, control=control)


def sample_requests(finished: list, reqs: list, seed: int, tokens: int):
    """The finished request with the most served tokens, then others in
    an order drawn from the seed, until ``tokens`` served tokens."""
    if not finished:
        return []
    longest = max(finished, key=lambda i: (len(reqs[i].out),
                                           reqs[i].prompt_len))
    rest = [i for i in finished if i != longest]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    rng.shuffle(rest)
    out, got = [longest], len(reqs[longest].out)
    for i in rest:
        if got >= tokens:
            break
        out.append(i)
        got += len(reqs[i].out)
    return out


def reference_gaps(cell, seed: int, checked: list, control: bool = False):
    """Widest gap, over every served token of ``checked``, between the
    reference's best logit and the served token's; with ``control``, also
    the same for the token that the fp8 reference puts first.  The model
    is the configuration's reference module (``cell.reference``)."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ArchConfig
    if not checked:
        return float("inf"), float("inf")
    lm = cell.reference
    m, dep = cell.config["model"], cell.config["deployment"]
    cap = dep["seq_capacity"]
    shapes = weights.param_shapes(ArchConfig(name="ref", **m))
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          weights.make(shapes, (seed,)))

    @jax.jit
    def gaps(p, toks, targets):
        lg = lm.logits(m, p, toks[None], "f32")[0]            # (cap, V)
        best = jnp.max(lg, axis=-1)
        g = best - jnp.take_along_axis(lg, targets[:, None], -1)[:, 0]
        if not control:
            return g, g
        c = jnp.argmax(lm.logits(m, p, toks[None], "fp8")[0], axis=-1)
        return g, best - jnp.take_along_axis(lg, c[:, None], -1)[:, 0]

    worst = worst_c = 0.0
    for prompt, out in checked:
        T, k = len(prompt), len(out)
        seq = np.zeros(cap, np.int32)
        seq[:T] = prompt
        seq[T:T + k - 1] = out[:-1]
        tgt = np.zeros(cap, np.int32)
        tgt[T - 1:T - 1 + k] = out
        g, gc_ = (np.asarray(x)[T - 1:T - 1 + k]
                  for x in gaps(params, jnp.asarray(seq), jnp.asarray(tgt)))
        if not (np.all(np.isfinite(g)) and np.all(np.isfinite(gc_))):
            return float("inf"), float("inf")
        worst, worst_c = max(worst, float(g.max())), max(worst_c,
                                                          float(gc_.max()))
    return worst, worst_c
