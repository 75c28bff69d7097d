"""Training cells: one ``repro.training.train_loop`` call, timed.

Set-up makes the weights and the feed from the seed and starts the one
``train_loop`` call that the window then measures: its first steps
compile and warm up, the window opens at a logged step after
``warmup_steps`` and closes at the last logged step before
``--seconds`` have passed (the loop logs, and syncs the host, every
``log_every`` steps, its default).  The harness stops the loop from its
``log_fn``.

The output check follows that same call.  While the loop asks the feed
for the batches of steps 1 to K (K = ``check.steps``), the feed reads
the loop's state as it stands: each step's loss, the optimizer's first
moment after step 1 (which is (1 - b1) times the first gradient as the
optimizer got it) and the parameters after step K.  After the window the
reference repeats those K steps in float32 from the same seed and
batches; each compared number is printed beside its limit.
"""
from __future__ import annotations

import gc
import re
import sys
import time
from types import SimpleNamespace

import numpy as np

from chipbench import traffic as traffic_mod
from chipbench import weights


class WindowClosed(Exception):
    """Raised from the loop's log_fn to end the measured call."""


def slice_norms(tree):
    """float32 norm of every leaf, one per layer for the stacked block
    leaves (leading layer axis), in tree order: the leaves the check
    compares."""
    import jax
    import jax.numpy as jnp
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = jnp.square(leaf.astype(jnp.float32))
        stacked = getattr(path[0], "key", None) == "blocks"
        out.append(jnp.sqrt(jnp.sum(x.reshape(x.shape[0], -1), axis=1))
                   if stacked else jnp.sqrt(jnp.sum(x))[None])
    return jnp.concatenate(out)


def slice_names(tree) -> list:
    """Names of the entries of :func:`slice_norms`, in its order."""
    import jax
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        stacked = getattr(path[0], "key", None) == "blocks"
        out += ([f"{name}[{i}]" for i in range(leaf.shape[0])] if stacked
                else [name])
    return out


def _norms_fn():
    import jax
    return jax.jit(slice_norms)


def _delta_fn():
    import jax
    return jax.jit(lambda a, b: slice_norms(jax.tree.map(
        lambda x, y: x.astype("float32") - y.astype("float32"), a, b)))


class Feed:
    """The loop's dataset.  Batches come from the seed and the step; for
    steps 1..K it also reads the calling loop's state for the check."""

    def __init__(self, make, k: int, p0):
        self.make, self.k, self.p0 = make, k, p0
        self.step = -1
        self.batches: list = []
        self.losses: list = []
        self.m1 = self.dk = None
        self._norms, self._delta = _norms_fn(), _delta_fn()

    def batch(self, key, step):
        self.step = int(step)
        if 1 <= self.step <= self.k:
            self._read_state(sys._getframe(1).f_locals)
        b = self.make(self.step)
        if self.step < self.k:
            self.batches.append(b)
        return b

    def _read_state(self, loop):
        # the state after step (self.step - 1), before the next step
        # donates it: only norms and the loss are kept
        self.losses.append(loop["metrics"]["loss"])
        if self.step == 1:
            self.m1 = self._norms(loop["opt_state"]["m"])
        if self.step == self.k:
            self.dk = self._delta(loop["params"], self.p0)
            self.p0 = None


class Window:
    """The loop's ``log_fn``: opens the window at the first log at or
    after ``warmup`` steps and closes it once ``seconds`` have passed."""

    def __init__(self, feed, warmup: int, seconds: float, clock,
                 capture=None, trace_seconds: float | None = None):
        self.feed, self.warmup, self.clock = feed, warmup, clock
        self.capture = capture
        self.seconds = (min(seconds, trace_seconds) if capture is not None
                        and trace_seconds else seconds)
        self.t0 = self.s0 = self.end = self.prev = None
        self.mark = None
        self.logs: list = []         # (host time, step) of each log
        self.arrived: dict = {}      # gradients counted, at logged steps

    def __call__(self, msg):
        now, s = time.perf_counter(), self.feed.step
        self.logs.append((now, s))
        got = re.search(r"\barr\s+(\d+)", msg)
        if got and self.t0 is not None:
            k = int(got.group(1))
            self.arrived[k] = self.arrived.get(k, 0) + 1
        if self.t0 is None:
            if s >= self.warmup:
                if self.capture is not None:
                    self.capture.start()
                    now = time.perf_counter()
                self.t0, self.s0 = now, s
                self.prev = (now, s)
                self.mark = self.clock.mark()
            return
        if now - self.t0 < self.seconds:
            self.prev = (now, s)
            return
        if self.capture is not None:
            # the traced window is the measured one: end at this log
            self.end = (now, s)
            self.trace_s = now - self.t0
            self.capture.stop()
        else:
            self.end = self.prev if self.prev[1] > self.s0 else (now, s)
        raise WindowClosed


def _program(cell, seed):
    """The system under test, built as the configuration and the traffic
    mix state it."""
    import repro.simulator as simulator
    from repro.configs.base import ArchConfig
    from repro.core.aggregators import make_spec
    from repro.optim import adamw, constant
    from repro.training import ByzantineConfig
    dep, tr = cell.config["deployment"], cell.traffic
    cfg = ArchConfig(name=cell.config_name, **cell.config["model"])
    n, f = dep["n_agents"], dep["f"]
    spec = make_spec(tr["rule"], f=f, n=n, impl=tr.get("impl", "auto"),
                     **tr.get("rule_hyper", {}))
    bz = ByzantineConfig(n_agents=n, f=f, attack=tr["attack"],
                         attack_hyper=dict(tr.get("attack_hyper", {})),
                         remat=dep["remat"], aggregator=spec)
    o = dep["optimizer"]
    opt = adamw(constant(o["lr"]), b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=o.get("weight_decay", 0.0))
    sim = None
    if tr.get("faults") or tr.get("quorum"):
        faults = tuple(
            getattr(simulator, x["type"])(**{
                k: (tuple(v) if isinstance(v, list) else v)
                for k, v in x.items() if k != "type"})
            for x in tr.get("faults", ()))
        st = tr.get("staleness", {})
        sim = simulator.SimConfig(
            faults=faults, quorum=tr.get("quorum"), seed=seed & 0x7FFFFFFF,
            staleness_weighting=st.get("weighting", "poly"),
            staleness_power=st.get("power", 1.0))
    return cfg, bz, opt, sim


def run(cell, args, clock, say):
    import jax
    from repro.training import train_loop

    tr, dep = cell.traffic, cell.config["deployment"]
    seed = args.seed
    with clock.phase("build"):
        cfg, bz, opt, sim = _program(cell, seed)
        shapes = weights.param_shapes(cfg)
    with clock.phase("weights"):
        p0 = weights.make(shapes, (seed,))
        jax.block_until_ready(p0)
    k = tr["check"]["steps"]
    feed = Feed(traffic_mod.train_feed(tr, dep["n_agents"],
                                       cfg.vocab_size, seed), k, p0)
    capture = None
    if args.trace:
        from chipbench.trace import Capture
        capture = Capture()
    win = Window(feed, tr["warmup_steps"], args.seconds, clock, capture,
                 tr.get("trace_seconds"))
    t_loop = time.perf_counter()
    try:
        train_loop(cfg, bz, opt, SimpleNamespace(batch=feed.batch),
                   steps=tr["max_steps"], seed=seed & 0x7FFFFFFF,
                   params=p0, log_fn=win, sim=sim)
    except WindowClosed:
        pass
    if win.end is None:
        raise RuntimeError(f"the loop ended after {tr['max_steps']} steps "
                           "before the window closed: raise max_steps")
    clock.phases["warmup"] = win.t0 - t_loop
    (t1, s1), t0, s0 = win.end, win.t0, win.s0
    in_window = clock.since(win.mark)
    tokens_per_step = (dep["n_agents"] * tr["per_agent_batch"] * tr["seq"])
    steps = s1 - s0
    window_s = t1 - t0
    setup_s = t0 - clock.t_start
    say(f"setup {clock.summary()}  setup_s {setup_s:.3f}")
    say(f"window: steps {s0}..{s1} ({steps} steps) in {window_s:.4f} s; "
        f"inside it: {in_window}")
    say("log intervals in the window: " + interval_summary(win.logs, t0, t1))
    if win.arrived:
        say("gradients counted at the window's logged steps: " + ", ".join(
            f"{k} on {v}" for k, v in sorted(win.arrived.items())))
    mem = device_memory(cell.chips)
    say(f"device memory after the window: {mem}")

    losses = [float(x) for x in feed.losses]
    prog = {"losses": losses,
            "g1": np.asarray(feed.m1) / (1.0 - dep["optimizer"]["b1"]),
            "dk": np.asarray(feed.dk)}
    batches = feed.batches
    run_ns = SimpleNamespace(
        kind="train", model=cell.config["model"], seq=tr["seq"],
        flops_per_token=cell.reference.train_flops_per_token(
            cell.config["model"], tr["seq"]),
        rule=tr["rule"], n_agents=dep["n_agents"], masked=sim is not None,
        elem_bytes=jax.numpy.dtype(cfg.dtype).itemsize,
        n_params=int(sum(np.prod(s.shape) for s in jax.tree.leaves(shapes))),
        steps=steps, trace_s=getattr(win, "trace_s", None),
        tokens_per_s=steps * tokens_per_step / window_s)
    del feed, win, p0
    gc.collect()
    t = time.perf_counter()
    ref = reference_readings(cell, seed, batches)
    checks = compare(prog, ref, tr["check"]["limits"])
    say(f"reference check {time.perf_counter() - t:.2f} s: losses program "
        f"{losses} reference {ref['losses']}")
    names = slice_names(shapes)
    for key in ("g1", "dk"):
        gap = np.abs(prog[key] - ref[key]) / np.maximum(
            ref[key], np.median(ref[key]))
        w = np.argsort(-gap)[:3]
        say(f"worst leaves of {key}: " + "; ".join(
            f"{names[i]} program {prog[key][i]!r} reference "
            f"{ref[key][i]!r}" for i in w))
    control = None
    if args.control:
        control = compare(reference_readings(cell, seed, batches,
                                             variant=args.control), ref,
                          tr["check"]["limits"])
        say(f"control {args.control}: "
            + "  ".join(f"{k} {v['value']!r}" for k, v in control.items()))
    return SimpleNamespace(
        e2e={"train_tokens_per_s": run_ns.tokens_per_s, "setup_s": setup_s},
        attempted=steps, failed=0, checks=checks, memory=mem,
        capture=capture, run=run_ns, control=control)


def interval_summary(logs, t0: float, t1: float) -> str:
    """The host time between consecutive logs inside the window: their
    median and the three slowest, each with the step it ended at, so a
    run that stalled shows where."""
    ts = [(t, s) for t, s in logs if t0 <= t <= t1]
    d = [(b[0] - a[0], b[1]) for a, b in zip(ts, ts[1:])]
    if not d:
        return "none"
    slow = sorted(d, reverse=True)[:3]
    return (f"{len(d)}, median {np.median([x for x, _ in d]):.4f} s, "
            "slowest " + ", ".join(f"{x:.4f} s to step {s}" for x, s in slow))


def device_memory(chips: int) -> dict:
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()[:chips]]
    return {"peak_bytes_in_use": max(s.get("peak_bytes_in_use", 0)
                                     for s in stats),
            "peak_bytes_reserved": max(s.get("peak_bytes_reserved", 0)
                                       for s in stats),
            "bytes_limit": max(s.get("bytes_limit", 0) for s in stats)}


# ---------------------------------------------------------------------------
# the reference and the comparison


def delivery(cell, steps: int):
    """(refresh, contrib, weights) of the first ``steps`` updates, each
    (steps, n), by the reference's own schedule (``reference.
    delivery_plan``): every agent delivers every step where the mix
    names no fault or quorum.  Only stragglers of constant latency are
    written out; weights are the staleness discount (1 + s)^-power on
    counted deliveries, 0 elsewhere."""
    from chipbench import reference as ref
    tr, n = cell.traffic, cell.config["deployment"]["n_agents"]
    delays = np.ones(n)
    for x in tr.get("faults", ()):
        if (x["type"] != "Straggler" or x.get("dist") != "constant"
                or x.get("prob", 1.0) != 1.0):
            raise NotImplementedError(f"no reference schedule for {x}")
        delays[list(x.get("agents") or range(n))] *= x["scale"]
    st = tr.get("staleness", {})
    if st.get("weighting", "poly") != "poly":
        raise NotImplementedError(f"no reference discount {st}")
    refresh, contrib, stale = ref.delivery_plan(delays, tr.get("quorum"),
                                                steps)
    w = np.where(contrib, (1.0 + stale) ** -st.get("power", 1.0), 0.0)
    return refresh, contrib, w.astype(np.float32)


def reference_readings(cell, seed: int, batches: list,
                       variant: str | None = None) -> dict:
    """The first K steps of the configured training, plainly: per-agent
    float32 gradients into a buffer of each agent's latest, the attack on
    the buffer, the rule over the delivered rows and AdamW coordinate by
    coordinate, parameters stored in the configuration's dtype between
    steps.  A selection rule sees undelivered rows at the weighted mean
    of the delivered ones and scales its result by their mean weight.
    The model is the configuration's reference module
    (``cell.reference``), the rest ``chipbench.reference``.
    ``variant`` puts a control in the program's place: "fp8" (matmul
    operands rounded to float8) or "halfbatch" (each agent's loss over
    the first half of its rows only)."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference as ref
    lm = cell.reference
    m, dep, tr = cell.config["model"], cell.config["deployment"], \
        cell.traffic
    n, f, o = dep["n_agents"], dep["f"], dep["optimizer"]
    mode = "fp8" if variant == "fp8" else "f32"
    from repro.configs.base import ArchConfig
    shapes = weights.param_shapes(ArchConfig(name="ref", **m))
    store = jnp.dtype(m.get("dtype", "bfloat16"))
    hyper = tr.get("attack_hyper", {})
    attack = lambda x: ref.ATTACKS[tr["attack"]](x, f, **hyper)  # noqa
    refresh, contrib, wts = delivery(cell, len(batches))

    f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)  # noqa
    with jax.default_matmul_precision("highest"):
        # parameters cross every jit boundary in the configuration's dtype:
        # inside one jit XLA may skip a bf16 round trip (excess precision
        # is allowed), and the program rounds its stored params every step
        grad = jax.jit(lambda p, b: jax.value_and_grad(
            lambda q: lm.loss(m, q, b, mode))(f32(p)))

        @jax.jit
        def coordwise(stack):
            return ref.RULES[tr["rule"]](attack(stack), min(f, (n - 1) // 2))

        @jax.jit
        def imputed(stack, mask, wn):
            x = attack(stack).reshape(n, -1)
            mean = jnp.einsum("i,ip->p", wn, x, precision=ref.HIGHEST)
            return jnp.where(mask[:, None], x, mean[None])

        @jax.jit
        def sq_dists(stack, mask, wn):
            x = imputed(stack, mask, wn)
            return jnp.stack([jnp.sum(jnp.square(x - x[i]), axis=1)
                              for i in range(n)])

        @jax.jit
        def combine(stack, mask, wn, sel):
            x = imputed(stack, mask, wn)
            return jnp.einsum("i,ip->p", sel, x, precision=ref.HIGHEST
                              ).reshape(stack.shape[1:])

        def aggregate(buf, mask, w):
            per_agent = [jax.tree.leaves(b) for b in buf]
            tdef = jax.tree.structure(buf[0])
            stacks = [lambda j=j: jnp.stack([a[j] for a in per_agent])
                      for j in range(len(per_agent[0]))]
            if tr["rule"] in ref.RULES:
                if not mask.all():
                    raise NotImplementedError(
                        "the reference's coordinate-wise rules assume "
                        "every agent delivers")
                return jax.tree.unflatten(tdef, [coordwise(s()) for s in
                                                 stacks])
            mk, wn = jnp.asarray(mask), jnp.asarray(w / w.sum())
            d2 = sum(np.asarray(sq_dists(s(), mk, wn), np.float64)
                     for s in stacks)
            pick = ref.SELECT[tr["rule"]](d2, f, **tr.get("rule_hyper", {}))
            sel = np.zeros(n, np.float32)
            sel[pick] = w.sum() / mask.sum() / len(pick)
            sel = jnp.asarray(sel)
            return jax.tree.unflatten(tdef, [combine(s(), mk, wn, sel)
                                             for s in stacks])

        @jax.jit
        def apply(p, g, mo, vo, step):
            leaves, tdef = jax.tree.flatten(f32(p))
            out = [ref.adamw_update(*a, step, o) for a in zip(
                leaves, *(jax.tree.leaves(t) for t in (g, mo, vo)))]
            part = lambda i: jax.tree.unflatten(tdef, [r[i] for r in out])
            return (jax.tree.map(lambda x: x.astype(store), part(0)),
                    part(1), part(2))

        p = p_start = weights.make(shapes, (seed,), store)
        mo = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)
        vo = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)
        losses, g1, buf = [], None, [None] * n
        for s, batch in enumerate(batches, start=1):
            rows = batch["tokens"].shape[1]
            keep = rows // 2 if variant == "halfbatch" else rows
            outs = [grad(p, {"tokens": batch["tokens"][i, :keep],
                             "labels": batch["labels"][i, :keep]})
                    for i in range(n)]
            losses.append(float(np.mean([float(l) for l, _ in outs[f:]])))
            for i in np.flatnonzero(refresh[s - 1]):
                buf[i] = outs[i][1]
            del outs
            agg = aggregate(buf, contrib[s - 1], wts[s - 1])
            if s == 1:
                g1 = np.asarray(_norms_fn()(agg))
            p, mo, vo = apply(p, agg, mo, vo, jnp.float32(s))
        dk = np.asarray(_delta_fn()(p, p_start))
    return {"losses": losses, "g1": g1, "dk": dk}


def norm_gap(got, want, keep=None) -> float:
    """Worst leaf's gap between two norms, over the larger of that leaf's
    reference norm and the median leaf's."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if keep is not None:
        got, want = got[keep], want[keep]
    scale = np.maximum(want, np.median(want))
    gap = np.abs(got - want) / scale
    return float(np.max(gap)) if np.all(np.isfinite(gap)) else float("inf")


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """The compared numbers, each with its limit."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss_gap = (float(np.max(np.abs(lp - lr) / np.abs(lr)))
                if lp.shape == lr.shape and np.all(np.isfinite(lp))
                else float("inf"))
    # leaves whose reference gradient is nought to rounding move by
    # round-off alone under Adam: left out of the update's comparison
    g = np.asarray(ref["g1"])
    moving = g >= 1e-3 * np.median(g)
    values = {"loss_gap": loss_gap,
              "grad_norm_gap": norm_gap(prog["g1"], ref["g1"]),
              "update_norm_gap": norm_gap(prog["dk"], ref["dk"], moving)}
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}
