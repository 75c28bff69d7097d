"""Bounded-staleness asynchronous training over the fault-injection
simulator — the execution layer for the survey's non-Byzantine fault modes
(crash/recover, stragglers, message loss) and the staleness-aware
asynchronous setting of the Zeno++/Kardam line of work.

Pipeline per server step t (one parameter version):

  1. the host reads row t of the precompiled :class:`AsyncTrace`
     (who dispatches, who delivers, how stale) — fixed shapes, so the jitted
     step compiles once regardless of the fault schedule;
  2. agents dispatching at version t compute gradients against the current
     params and write them into the in-flight buffer (their delivery may
     land many versions later);
  3. delivered gradients are aggregated with the robust filter catalogue via
     the config's :class:`~repro.core.aggregators.AggregatorSpec`
     (``spec.aggregate(sent, mask=..., weights=...)``), weighted by a
     staleness discount; stateful rules (Zeno, the delay-adaptive
     ``zeno_pp``) have their state threaded explicitly through the jitted
     step; ``impl="pallas"`` specs run the fused masked kernels
     (:mod:`repro.kernels.masked`) here — the quorum mask and discount
     weights enter the kernel as ordinary traced operands, so the step
     compiles ONCE per shape regardless of the fault schedule, and the
     threaded ``agg_state`` pytree passes through the kernel path
     untouched; if the quorum was missed (stragglers/crashes) the loop can
     fall back to Draco-style gradient coding over the same (n, P) arena
     (:func:`repro.core.redundancy.coding.flat_draco_aggregate` with the
     delivery mask; mixed-dtype trees decode leaf-wise) — under elastic
     membership the code regroups the PACKED live rows with the bucket's
     :func:`~repro.core.redundancy.coding.coding_groups` table, derived
     once per bucket at step-build time;
  4. the server optimizer applies the update, creating version t+1.

The synchronous loop is the degenerate case: with no faults every trace row
is "pure" (all n agents deliver zero-staleness gradients computed at the
current version) and the host dispatches to the *exact* synchronous
train-step from :mod:`repro.training.step`, so ``train_loop`` ==
``async_train_loop`` bit-for-bit when latency is uniform and quorum = n.

Elastic membership: when the schedule contains Join/Rejoin/Churn specs the
trace carries a per-step roster, and an elastic-n aggregator
(``make_spec(..., n=elastic(n_max, buckets=...))``) packs the LIVE agents
into per-bucket fixed-shape stacks — the rule's (n, f) plan tracks the
live roster, the roster indices are traced operands, and churn over the
bucketed range costs at most ``len(buckets)`` step compilations
(tests/test_membership_retrace.py).  A non-elastic spec under churn keeps
its n_max plan and masks departed rows (one compile, imputed ghosts).
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save
from repro.core.aggregators import tree_stack_ravel, tree_where_agents
from repro.core.flat import (FlatPlan, QUANT_DTYPES, fake_quantize,
                             quantize_rows)
from repro.obs.counters import count_trace
from repro.obs.spans import (TRAIN_BATCH, TRAIN_CHECKPOINT, TRAIN_DISPATCH,
                             TRAIN_LOG, TRAIN_STEP, span, step_span)
from repro.core.attacks import (get_attack, is_adaptive_attack,
                                make_adaptive_attack, make_byzantine_mask)
from repro.core.momentum import init_momentum, worker_momentum
from repro.core.redundancy.coding import (coding_groups,
                                          flat_draco_aggregate,
                                          tree_draco_aggregate)
from repro.data import label_flip
from repro.distributed.context import layer_remat
from repro.models import init_params, loss_fn
from repro.optim import apply_updates
from repro.simulator.events import AsyncTrace, simulate_arrivals
from repro.simulator.faults import compile_schedule

# NOTE: repro.training.step is imported lazily inside the factories below —
# training.loop delegates here, so a module-level import would be circular.


@dataclass(frozen=True)
class SimConfig:
    """Cluster-simulation knobs for :func:`async_train_loop`."""
    faults: tuple = ()                    # fault specs (simulator.faults)
    quorum: Optional[int] = None          # None -> n_agents (full barrier)
    max_staleness: Optional[int] = None   # None -> unbounded
    staleness_weighting: str = "poly"     # none | poly | exp
    staleness_power: float = 1.0          # poly: (1 + s)^-power
    staleness_gamma: float = 0.7          # exp: gamma^s
    base_delay: float = 1.0               # virtual time of one computation
    seed: int = 0                         # fault-schedule seed
    coded_fallback_r: int = 0             # >0: draco(r) when quorum missed


def staleness_weights(sim: SimConfig, atrace: AsyncTrace) -> np.ndarray:
    """(steps, n) float32 per-delivery weights: staleness discount on
    contributors, 0 elsewhere (the discount table itself lives in
    :func:`repro.core.aggregators.staleness_discount_table`)."""
    from repro.core.aggregators import staleness_discount_table
    s = atrace.staleness.astype(np.float64)
    w = staleness_discount_table(s, sim.staleness_weighting,
                                 sim.staleness_power, sim.staleness_gamma)
    return (w * atrace.contrib).astype(np.float32)


def plan_arrivals(sim: SimConfig, n_agents: int, steps: int) -> AsyncTrace:
    """Compile the fault schedule and run the virtual clock exactly as
    :func:`async_train_loop` will — shared so benchmarks/analysis report
    the same trace the loop executes."""
    ftrace = compile_schedule(sim.faults, n_agents, steps + 1, seed=sim.seed,
                              base_delay=sim.base_delay)
    return simulate_arrivals(ftrace, steps, quorum=sim.quorum,
                             max_staleness=sim.max_staleness)


def make_async_step(cfg, bz, optimizer, fallback_r: int = 0,
                    bucket: int | None = None, telemetry: bool = False):
    """Returns async_step(params, opt_state, momentum, buffer, agg_state,
    batch, key, refresh, contrib_w, use_coded[, roster_idx, roster_valid])
    -> (params, opt_state, momentum, buffer, agg_state, metrics).

    ``telemetry`` (static Python flag): metrics additionally carry a
    fixed-shape ``"telemetry"`` struct — the aggregator's (n,) selection
    weights, delivery mask and contribution weights
    (``spec.selection_weights``, see :mod:`repro.obs`).  ``False`` emits
    the EXACT historical jaxpr; ``True`` adds only (n,)-sized aux
    outputs — bit-identical results and the same elastic-bucket compile
    budget either way.

    ``refresh``   (n,) bool  — agents computing a fresh gradient this step;
    ``contrib_w`` (n,) f32   — staleness-discounted delivery weights
                               (0 = not delivered);
    ``agg_state`` pytree     — aggregator state (``spec.init_state``; {}
                               for stateless rules), threaded explicitly;
    ``use_coded`` () bool    — quorum missed: aggregate with the gradient
                               code over delivered rows instead of the
                               filter (requires ``fallback_r``).

    ``bucket`` (elastic membership): the step additionally takes
    ``roster_idx`` (bucket,) int32 — live agent slots, padded by repeating
    a live slot — and ``roster_valid`` (bucket,) bool — which slots are
    real.  The live rows are packed into a (bucket, ...) stack and
    aggregated by ``spec.respecialize(bucket)`` (per-bucket f and static
    plans), with pad slots masked out; both roster operands are TRACED, so
    churn within a bucket never recompiles and churn across the bucketed
    range compiles at most once per bucket."""
    from repro.training.step import tree_attack
    adaptive_name = bz.attack if is_adaptive_attack(bz.attack) else None
    attack_fn = get_attack(bz.attack, **bz.attack_hyper) \
        if bz.attack != "none" and adaptive_name is None else None
    byz_mask = make_byzantine_mask(bz.n_agents, bz.f)
    spec = bz.resolve_spec()
    if spec.staleness_aware:                 # recurses through wrappers
        # this loop already converts staleness to discount multipliers
        # (SimConfig.staleness_weighting) — a staleness_aware spec would
        # re-interpret those multipliers as round counts and INVERT the
        # discounting, so reject loudly instead of silently mis-weighting
        raise ValueError(
            f"{spec.name} consumes raw staleness counts, but the async "
            "loop passes discount multipliers — configure "
            "SimConfig.staleness_weighting and use the inner spec instead")
    # agg_dtype in QUANT_DTYPES selects the compressed-exchange pipeline:
    # per-row codes + scale sidecar quantized at ravel time, in-tile
    # dequant (see training/step.py — same contract)
    quant = bool(bz.agg_dtype) and bz.agg_dtype in QUANT_DTYPES
    if bz.agg_dtype and not quant:
        spec = spec.with_impl_hyper_if_supported(native_dtype=True)
    spec = spec.respecialize(bucket) if bucket is not None else spec
    stateful = spec.stateful
    # defense-aware attack, compiled against the spec the defense actually
    # runs — the respecialized BUCKET spec under elastic membership (the
    # adversary tracks the live (n, f) window), applied on the full
    # in-flight arena.  Attack state rides inside the agg_state slot as
    # {"agg": ..., "atk": ...} so the jitted signature is unchanged.
    adaptive = (make_adaptive_attack(adaptive_name, spec, **bz.attack_hyper)
                if adaptive_name is not None else None)
    # roster-aware gradient coding: the group table is derived HERE, at
    # step-build (respecialize) time, from the bucket capacity — lru-cached
    # per (n, r) like the trim tables, baked into the traced step as a
    # static constant.  The static path validates n % r == 0 (ValueError);
    # elastic buckets may carry a ragged trailing group.
    r_code = bz.draco_r if bz.draco_r > 0 else fallback_r
    n_agg = bucket if bucket is not None else bz.n_agents
    groups = (coding_groups(n_agg, r_code, allow_ragged=bucket is not None)
              if r_code > 0 else None)
    # zero-copy flat pipeline: dense-stack impls ravel the delivered
    # gradients ONCE per step into an (n, P) arena at the communication
    # boundary and unravel once at optimizer-apply; the coded paths ride
    # the same arena (the vote is Gram-based, the application a one-hot
    # weighted sum — kernels.pairwise/wsum)
    use_flat = spec.flat_capable

    def agent_loss(p, agent_batch):
        return loss_fn(cfg, p, agent_batch)

    def async_step(params, opt_state, momentum, buffer, agg_state, batch,
                   key, refresh, contrib_w, use_coded,
                   roster_idx=None, roster_valid=None):
        count_trace("async_step")
        atk_state = None
        if adaptive is not None:
            atk_state, agg_state = agg_state["atk"], agg_state["agg"]
        # (2) fresh gradients at the current version for dispatching agents
        # (bz.remat: per-layer activation checkpointing, as in the
        # synchronous step)
        with jax.named_scope("grads"):
            with layer_remat(bz.remat):
                losses, grads = jax.vmap(
                    jax.value_and_grad(agent_loss), in_axes=(None, 0))(
                        params, batch)
            if bz.momentum_alpha > 0.0:
                new_m, sent_now = worker_momentum(momentum, grads,
                                                  bz.momentum_alpha)
                momentum = tree_where_agents(refresh, new_m, momentum)
                grads = sent_now
        with jax.named_scope("buffer"):
            buffer = tree_where_agents(refresh, grads, buffer)

        # (3) Byzantine corruption happens at delivery time, on whatever is
        # in flight — stale honest gradients stay honest, Byzantine rows are
        # arbitrary every round (matches the synchronous injection point)
        sent = buffer
        if attack_fn is not None:
            with jax.named_scope("attack"):
                sent = tree_attack(attack_fn, key, sent, byz_mask)
        elif adaptive is not None:
            # defense-aware attacks operate on the raveled (n, P) arena —
            # min-max needs whole-row geometry, not per-leaf slices.  The
            # omniscient adversary also reads the defense's carried center
            # (state-aware threat model).
            with jax.named_scope("attack"):
                aplan = FlatPlan.for_tree(sent)
                arows = aplan.ravel(sent, jnp.float32)
                dvec = None
                if stateful and "server_grad" in agg_state:
                    dvec = tree_stack_ravel(jax.tree.map(
                        lambda l: l.astype(jnp.float32)[None],
                        agg_state["server_grad"]))[0]
                arows, atk_state = adaptive(key, arows, byz_mask, atk_state,
                                            dvec)
                sent = aplan.unravel_stack(arows)
        mask = contrib_w > 0.0
        with jax.named_scope("arena"):
            if bz.agg_dtype and not quant:
                sent = jax.tree.map(
                    lambda l: l.astype(jnp.dtype(bz.agg_dtype)), sent)
            plan = FlatPlan.for_tree(sent)
            codes = qs = arena = None
            if quant:
                # quantize the wire: codes + per-row fp32 scale.  Codes
                # feed the scaled kernels only on the plain flat path; the
                # coded vote (Gram-based, no scaled kernels) and the tree
                # fallbacks see the fake-quantized fp32 stack instead —
                # identical compressed-exchange semantics on every path.
                arena = plan.ravel(sent, jnp.float32)
                qdt = jnp.dtype(bz.agg_dtype)
                if use_flat and bz.draco_r == 0:
                    if fallback_r > 0:
                        arena = fake_quantize(arena, qdt)
                    else:
                        codes, qs = quantize_rows(arena, qdt)
                else:
                    sent = plan.unravel_stack(fake_quantize(arena, qdt))
                    arena = None
            elif (bz.draco_r == 0 and use_flat
                  and plan.uniform_dtype is not None):
                # ONE ravel into the (n, P) arena at the communication
                # boundary; the single unravel happens below, at
                # optimizer-apply
                arena = plan.ravel(sent)
        if bucket is not None:
            w_b = jnp.where(roster_valid, contrib_w[roster_idx], 0.0)
        vec = None
        with jax.named_scope("aggregate"):
            if bz.draco_r > 0:
                # coded regime: the repetition code already handles
                # partial delivery (vote among delivered group members);
                # under elastic membership the PACKED live rows are
                # regrouped by the bucket's table (exact in the parallel
                # regime — every agent computes the same shard).
                # tree_draco_aggregate rides the (n, P) arena internally
                # for uniform-dtype trees.
                if bucket is not None:
                    sent_b = jax.tree.map(lambda l: l[roster_idx], sent)
                    agg = tree_draco_aggregate(sent_b, bz.draco_r,
                                               mask=w_b > 0.0,
                                               groups=groups)
                else:
                    agg = tree_draco_aggregate(sent, bz.draco_r,
                                               mask=mask, groups=groups)
            elif use_flat and (quant or plan.uniform_dtype is not None):
                # the (n, P) arena raveled above: the quorum mask and
                # staleness discounts enter the masked kernels as traced
                # operands.  Mixed-dtype trees keep the tree path: a fp32
                # arena would impute masked rows without each leaf's
                # native rounding (not bitwise) — except under quantized
                # exchange, which erases leaf dtypes anyway.
                wire = codes if codes is not None else arena
                if bucket is not None:
                    rows, rmask, rw = wire[roster_idx], w_b > 0.0, w_b
                    rqs = qs[roster_idx] if qs is not None else None
                else:
                    rows, rmask, rw = wire, mask, contrib_w
                    rqs = qs
                vec = spec.aggregate_flat(
                    rows, mask=rmask, weights=rw, scale=rqs,
                    state=agg_state if stateful else None)
                if fallback_r > 0:
                    # quorum missed: decode the repetition code over the
                    # SAME arena rows (both candidates are (P,) fp32 — one
                    # select, one unravel; under quant, rows are the
                    # fake-quantized fp32 arena — codes are only cut when
                    # fallback_r == 0)
                    coded = flat_draco_aggregate(rows, fallback_r,
                                                 mask=rmask, groups=groups)
                    vec = jnp.where(use_coded, coded, vec)
            elif bucket is not None:
                # elastic membership: pack the live rows into the
                # bucket's fixed-shape stack; pad slots (repeated live
                # rows) are masked out, so the rule runs its per-bucket
                # (n, f) plan over the live roster only
                sent_b = jax.tree.map(lambda l: l[roster_idx], sent)
                agg = spec.aggregate(sent_b, mask=w_b > 0.0, weights=w_b,
                                     state=agg_state if stateful else None)
                if fallback_r > 0:
                    coded = tree_draco_aggregate(sent_b, fallback_r,
                                                 mask=w_b > 0.0,
                                                 groups=groups)
                    agg = jax.tree.map(
                        lambda a, c: jnp.where(use_coded,
                                               c.astype(a.dtype), a),
                        agg, coded)
            else:
                agg = spec.aggregate(sent, mask=mask, weights=contrib_w,
                                     state=agg_state if stateful else None)
                if fallback_r > 0:
                    coded = tree_draco_aggregate(sent, fallback_r,
                                                 mask=mask, groups=groups)
                    agg = jax.tree.map(
                        lambda a, c: jnp.where(use_coded,
                                               c.astype(a.dtype), a),
                        agg, coded)
        if vec is not None:
            with jax.named_scope("unravel"):
                agg = plan.unravel(vec)
        telem = None
        if telemetry:
            # fixed-shape (n,) aux outputs, computed OUTSIDE the aggregate
            # call (the update above is untouched — results stay
            # bit-identical) and BEFORE the state transition (the rule
            # selected against the pre-step state)
            n = bz.n_agents
            st = agg_state if stateful else None
            mf = mask.astype(jnp.float32)
            particip = mf / jnp.maximum(jnp.sum(mf), 1.0)
            if bz.draco_r > 0:
                sel = particip          # per-group votes: delivery shares
            elif bucket is not None:
                stack_b = (arena[roster_idx]
                           if use_flat and (quant
                                            or plan.uniform_dtype is not None)
                           else jax.tree.map(lambda l: l[roster_idx], sent))
                sel_b = spec.selection_weights(stack_b, mask=w_b > 0.0,
                                               weights=w_b, state=st)
                sel = jnp.zeros((n,), jnp.float32).at[roster_idx].add(
                    jnp.where(roster_valid, sel_b, 0.0))
                if fallback_r > 0:
                    # quorum missed -> the coded vote aggregated instead
                    sel = jnp.where(use_coded, particip, sel)
            else:
                stack = (arena
                         if use_flat and (quant
                                          or plan.uniform_dtype is not None)
                         else sent)
                sel = spec.selection_weights(stack, mask=mask,
                                             weights=contrib_w, state=st)
                if fallback_r > 0:
                    # quorum missed -> the coded vote aggregated instead
                    sel = jnp.where(use_coded, particip, sel)
            telem = {"sel_w": sel, "mask": mask,
                     "contrib_w": contrib_w.astype(jnp.float32)}
        if stateful:
            agg_state = spec.update_state(agg_state, agg)
        if adaptive is not None:
            agg_state = {"agg": agg_state, "atk": atk_state}

        # (4) server-side optimizer
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(agg, opt_state, params)
            params = apply_updates(params, updates)

        gnorm = jnp.sqrt(sum(
            jnp.sum(jnp.square(l.astype(jnp.float32)))
            for l in jax.tree.leaves(agg)))
        honest = ~byz_mask
        metrics = {
            "loss": jnp.sum(losses * honest) / jnp.sum(honest),
            "loss_all": jnp.mean(losses),
            "grad_norm": gnorm,
        }
        if telem is not None:
            metrics["telemetry"] = telem
        return params, opt_state, momentum, buffer, agg_state, metrics

    return async_step


def _record_step(recorder, step, st0, metrics, telem, atrace, arrived,
                 roster):
    """Feed one step's metrics, telemetry and faults to the recorder
    (``float()`` syncs on the step's outputs)."""
    mrec = ({k: float(v) for k, v in metrics.items()}
            if metrics is not None else {})
    mrec["arrived"] = arrived
    mrec["n_live"] = atrace.n_live(step)
    mrec["staleness_mean"] = (
        float(atrace.staleness[step][atrace.contrib[step]].mean())
        if arrived else 0.0)
    mrec["staleness_max"] = (
        int(atrace.staleness[step][atrace.contrib[step]].max())
        if arrived else 0)
    mrec["quorum_ok"] = bool(atrace.quorum_met[step])
    if not atrace.quorum_met[step]:
        recorder.fault(step, "quorum_miss", arrived=arrived)
    recorder.step(step, t0=st0, t1=recorder.now(), metrics=mrec,
                  telemetry=telem,
                  roster=(roster[step] if roster is not None else None))


def _log_step(history, step, metrics, atrace, arrived, pure, t0,
              log_fn) -> None:
    """Append one step's row to ``history`` and print it through
    ``log_fn`` (``float()`` syncs on the step's outputs)."""
    if metrics is None:
        m = {"loss": float("nan"), "loss_all": float("nan"),
             "grad_norm": 0.0}
    else:
        m = {k: float(v) for k, v in metrics.items()}
    m["step"] = step
    m["wall_s"] = time.time() - t0
    m["arrived"] = arrived
    m["n_live"] = atrace.n_live(step)
    m["staleness_mean"] = (
        float(atrace.staleness[step][atrace.contrib[step]].mean())
        if arrived else 0.0)
    m["vclock"] = float(atrace.vclock[step])
    history.append(m)
    extra = ("" if pure else
             f"  arr {arrived:2d}  stal {m['staleness_mean']:.2f}")
    log_fn(f"step {step:5d}  loss {m['loss']:.4f}  "
           f"gnorm {m['grad_norm']:.3f}{extra}")


def async_train_loop(cfg, bz, optimizer, dataset, steps: int,
                     sim: Optional[SimConfig] = None, seed: int = 0,
                     log_every: int = 10, ckpt_dir: str | None = None,
                     ckpt_every: int = 0, poison_labels: bool = False,
                     jit: bool = True, params=None, log_fn=print,
                     recorder=None, telemetry: Optional[bool] = None,
                     _force_general: bool = False):
    """Returns (params, history list of metric dicts).

    sim=None (or any schedule whose trace stays synchronous) reproduces the
    historical synchronous ``train_loop`` bit-for-bit: pure steps dispatch
    to the exact synchronous train step.  ``_force_general`` routes pure
    steps through the general async path too (testing only).

    ``recorder`` (a :class:`repro.obs.recorder.Recorder`): the loop feeds
    it run metadata, per-step spans/metrics, the aggregator's selection
    telemetry, roster-delta annotations and the recompile ledger — all on
    host, between steps, so recording adds ZERO compiles and leaves
    results bit-identical.  ``telemetry`` forces the fixed-shape
    selection aux outputs on/off explicitly (default: on exactly when a
    recorder is attached)."""
    from repro.training.step import make_train_step
    sim = sim if sim is not None else SimConfig()
    n = bz.n_agents
    spec = bz.resolve_spec()
    atrace = plan_arrivals(sim, n, steps)
    roster = atrace.roster                 # (steps, n) bool | None
    el = spec.elastic_n                    # wrapper chains delegate
    if el is not None:
        if el.n_max != n:
            raise ValueError(
                f"elastic aggregator {spec.describe()} was built for "
                f"n_max={el.n_max} but the config declares "
                f"n_agents={n}")
        if bz.draco_r > 0 or sim.coded_fallback_r > 0:
            # warm the per-bucket coding group tables up front (lru-cached
            # with the step plans, same trick as the trim tables) and
            # surface a bad r at BUILD time, not mid-run
            r_code = bz.draco_r if bz.draco_r > 0 else sim.coded_fallback_r
            coding_groups(n, r_code)           # master roster: r must | n
            for b in el.buckets:
                coding_groups(int(b), r_code, allow_ragged=True)
        if roster is None:
            # membership never changes: run the concrete n_max spec (the
            # elastic master is bit-for-bit its own n_max bucket)
            bz = dataclasses.replace(bz, aggregator=spec.respecialize(n))
            spec = bz.resolve_spec()
            el = None
    stateful = spec.stateful
    adaptive = is_adaptive_attack(bz.attack)
    contrib_w = staleness_weights(sim, atrace)
    if (bz.group_size > 1 or bz.reshard) and (stateful
                                              or not atrace.is_synchronous()):
        # the general async step implements neither knob — stateful specs
        # always run it, so don't silently drop grouping/resharding
        raise NotImplementedError(
            "group_size/reshard perf knobs assume the synchronous step "
            "(synchronous delivery and a stateless aggregator)")

    key = jax.random.PRNGKey(seed)
    k_init, k_run = jax.random.split(key)
    if params is None:
        params = init_params(cfg, k_init)
    elif jit and jax.default_backend() != "cpu":
        # the steps donate params (below): keep the caller's arrays alive
        params = jax.tree.map(jnp.copy, params)
    opt_state = optimizer.init(params)
    momentum = None
    if bz.momentum_alpha > 0.0:
        proto = jax.tree.map(
            lambda p: jnp.zeros((n,) + p.shape, jnp.float32), params)
        momentum = init_momentum(proto)

    telemetry = (recorder is not None) if telemetry is None else telemetry
    if recorder is not None:
        from repro.obs.telemetry import dispatch_record
        recorder.emit("run", steps=steps, n_agents=n,
                      dispatch=dispatch_record(spec),
                      quorum=sim.quorum, max_staleness=sim.max_staleness,
                      attack=bz.attack, f=bz.f, seed=seed,
                      faults=[repr(f) for f in sim.faults])
    # stateful aggregators must observe (and update) their state every
    # step, so they always run the general path; likewise defense-aware
    # attacks (their state and the defense's center thread through the
    # async step).  The synchronous train step stays the stateless,
    # static-attack fast path.
    step_fn = (None if stateful or adaptive
               else make_train_step(cfg, bz, optimizer,
                                    telemetry=telemetry))
    # donate params, optimizer state and momentum (and, on the async path,
    # the in-flight gradient buffer): every step returns their updated
    # twins, so on accelerator backends those HBM blocks are reused in
    # place instead of held twice across the step.  CPU ignores donation,
    # so skip it there to keep logs clean
    on_cpu = jax.default_backend() == "cpu"
    donate_sync = () if on_cpu else (0, 1, 2)
    donate = () if on_cpu else (0, 1, 2, 3)
    async_fn = make_async_step(cfg, bz, optimizer,
                               fallback_r=sim.coded_fallback_r,
                               telemetry=telemetry)
    if jit:
        step_fn = (jax.jit(step_fn, donate_argnums=donate_sync)
                   if step_fn is not None else None)
        async_fn = jax.jit(async_fn, donate_argnums=donate)

    # elastic membership: one step function per roster BUCKET (built
    # lazily, compiled at most len(el.buckets) times over the whole run —
    # the roster operands themselves are traced, so churn within a bucket
    # reuses the bucket's single compilation)
    bucket_fns: dict = {}

    def bucket_fn(b: int):
        if b not in bucket_fns:
            fn = make_async_step(cfg, bz, optimizer, bucket=b,
                                 telemetry=telemetry)
            bucket_fns[b] = (jax.jit(fn, donate_argnums=donate) if jit
                             else fn)
        return bucket_fns[b]
    byz_mask = make_byzantine_mask(n, bz.f)
    agg_state = (spec.init_state(jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params))
        if stateful else {})
    if adaptive:
        # attack state bundles into the agg_state slot — the jitted step
        # signature and every call site stay unchanged.  State structure
        # is bucket-independent, so it threads across respecializations.
        agg_state = {"agg": agg_state,
                     "atk": make_adaptive_attack(
                         bz.attack, spec, **bz.attack_hyper).init_state()}

    # a step is "pure" iff it is exactly the synchronous step: the FULL
    # roster dispatches AND delivers with zero staleness
    pure = (atrace.contrib.all(1) & atrace.refresh.all(1)
            & (atrace.staleness.max(1, initial=0) == 0))
    if roster is not None:
        pure &= roster.all(1)
    if _force_general or stateful or adaptive:
        pure = np.zeros(steps, bool)

    # in-flight gradient buffer (fp32 covers every exchange dtype) and
    # refreshes deferred across update-less steps: params are unchanged
    # there, so the gradient is computed at the correct parameter version
    # (the data batch is a fresh sample from a later step index — iid-
    # equivalent, though not the literal batch of the dispatch instant).
    # Only the general path reads the buffer: a run whose every step is
    # pure never allocates its (n, P) fp32
    buffer = (None if pure.all() else jax.tree.map(
        lambda p: jnp.zeros((n,) + p.shape, jnp.float32), params))
    pending_refresh = np.zeros(n, bool)

    history = []
    t0 = time.time()
    for step in range(steps):
        with step_span(TRAIN_STEP, step):
            k_run, k_data, k_step = jax.random.split(k_run, 3)
            with span(TRAIN_BATCH):
                batch = dataset.batch(k_data, step)
            if poison_labels:
                batch = label_flip(batch, byz_mask, cfg.vocab_size)
            arrived = int(atrace.contrib[step].sum())
            st0 = recorder.now() if recorder is not None else None
            with span(TRAIN_DISPATCH):
                if pure[step]:
                    params, opt_state, momentum, metrics = step_fn(
                        params, opt_state, momentum, batch, k_step)
                elif arrived == 0:
                    # nobody delivered: version unchanged, defer this
                    # step's dispatches to the next step that actually runs
                    pending_refresh |= atrace.refresh[step]
                    metrics = None
                else:
                    refresh = atrace.refresh[step] | pending_refresh
                    pending_refresh = np.zeros(n, bool)
                    use_coded = bool(not atrace.quorum_met[step]
                                     and sim.coded_fallback_r > 0)
                    if el is not None:
                        # pack the live roster into its bucket's fixed
                        # shape (arrived > 0 here, and contributors are
                        # members, so the roster row has a live agent)
                        b, idx, valid = el.pack(np.flatnonzero(roster[step]))
                        (params, opt_state, momentum, buffer, agg_state,
                         metrics) = bucket_fn(int(b))(
                            params, opt_state, momentum, buffer, agg_state,
                            batch, k_step, jnp.asarray(refresh),
                            jnp.asarray(contrib_w[step]),
                            jnp.asarray(use_coded), jnp.asarray(idx),
                            jnp.asarray(valid))
                    else:
                        (params, opt_state, momentum, buffer, agg_state,
                         metrics) = async_fn(
                            params, opt_state, momentum, buffer, agg_state,
                            batch, k_step, jnp.asarray(refresh),
                            jnp.asarray(contrib_w[step]),
                            jnp.asarray(use_coded))
            telem = metrics.pop("telemetry", None) if metrics else None
            if recorder is not None:
                with span(TRAIN_LOG):
                    _record_step(recorder, step, st0, metrics, telem,
                                 atrace, arrived, roster)
            if step % log_every == 0 or step == steps - 1:
                with span(TRAIN_LOG):
                    _log_step(history, step, metrics, atrace, arrived,
                              pure[step], t0, log_fn)
            if ckpt_dir and ckpt_every and step and step % ckpt_every == 0:
                with span(TRAIN_CHECKPOINT):
                    save(ckpt_dir, step, {"params": params,
                                          "opt": opt_state})
    if ckpt_dir:
        with span(TRAIN_CHECKPOINT):
            save(ckpt_dir, steps, {"params": params, "opt": opt_state})
    return params, history
