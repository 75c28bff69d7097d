"""Byzantine-robust distributed training step.

Maps the survey's server-based BGD framework (Algorithm 2) onto an SPMD TPU
program:

  1. the global batch is split along the leading AGENT axis (agents =
     data-parallel ranks; batch leaves are (n_agents, per_agent, ...));
  2. per-agent gradients are computed with vmap(grad) — agent axis sharded
     over the mesh's data axes;
  3. Byzantine behaviour is *injected* by rewriting the gradients of the f
     adversarial agents (SPMD-uniform where on the agent index — semantically
     identical to f agents sending arbitrary vectors, line 11 of Alg. 2);
  4. a gradient filter aggregates across the agent axis (eq. 17) —
     ``impl="gather"`` reproduces the survey's server literally,
     ``impl="fused"`` uses the stats->weights decomposition,
     ``impl="pallas"`` runs the rule's tiled TPU kernels, and
     ``impl="auto"`` picks pallas where the rule's caps match an
     available kernel (see repro.core.aggregators);
  5. the server-side optimizer applies the filtered update.

Worker momentum (§3.3.4 variance reduction) and Draco-style coded
aggregation (§3.3.3) slot in between (2) and (4).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.aggregators import AggregatorSpec, make_spec
from repro.core.attacks import get_attack, make_byzantine_mask
from repro.core.flat import (FlatPlan, QUANT_DTYPES, fake_quantize,
                             quantize_rows)
from repro.core.momentum import worker_momentum
from repro.obs.counters import count_trace
from repro.core.redundancy.coding import coding_groups, tree_draco_aggregate
from repro.models import loss_fn
from repro.optim import apply_updates


@dataclass(frozen=True)
class ByzantineConfig:
    n_agents: int = 16
    f: int = 3
    # robust aggregation: EITHER a first-class spec (preferred) ...
    aggregator: Optional[AggregatorSpec] = None
    # ... or the legacy string triple, resolved to a spec by resolve_spec()
    filter_name: str = "trimmed_mean"
    filter_hyper: dict = field(default_factory=dict)
    # fused | gather | pallas | auto ("auto" upgrades kernelized rules to
    # the Pallas path; the default stays "fused" so existing configs keep
    # their historical sharding-aware program bit-for-bit)
    impl: str = "fused"
    attack: str = "none"
    attack_hyper: dict = field(default_factory=dict)
    momentum_alpha: float = 0.0         # 0 = raw gradients
    draco_r: int = 0                    # >0 = coded aggregation instead
    remat: bool = False
    # ---- §Perf knobs (EXPERIMENTS.md) ----
    # >1: median-of-means grouping [19] — group-mean the sent gradients in
    # g groups of group_size BEFORE filtering (psum inside mesh subgroups
    # instead of gathering all n agent stacks).
    group_size: int = 1
    # cast the exchanged gradients to this dtype before aggregation
    # (beyond-paper quantized exchange; fp32 re-accumulated after):
    agg_dtype: str = ""                 # "" = keep native
    # reshard the (n, ...) gradient stack so the agent axis is replicated
    # and the parameter dims are sharded over BOTH mesh axes before the
    # coordinate-wise filter (beyond-paper collective schedule):
    reshard: bool = False

    def __post_init__(self):
        # the repetition code's shape contract, checked at CONFIG time —
        # the historical bare assert inside the aggregate vanished under
        # python -O and let a bad r reach a silently wrong reshape
        if self.draco_r:
            coding_groups(self.n_agents, self.draco_r)

    def resolve_spec(self) -> AggregatorSpec:
        """The aggregator actually used by the training loops: the explicit
        ``aggregator`` spec if set, else the legacy string triple compiled
        to a spec (hyper validated here, at config time).

        An explicit spec must agree with the config's threat model — a
        spec built for a different f (or n) would make the defense
        silently weaker than the configured attack."""
        if self.aggregator is not None:
            spec = self.aggregator
            if spec.f != self.f:
                raise ValueError(
                    f"aggregator {spec.describe()} was built for "
                    f"f={spec.f} but the config declares f={self.f} — "
                    "build the spec with the same Byzantine budget")
            if spec.n is not None and spec.n != self.n_agents:
                raise ValueError(
                    f"aggregator {spec.describe()} was built for "
                    f"n={spec.n} but the config declares "
                    f"n_agents={self.n_agents}")
            return spec
        return make_spec(self.filter_name, f=self.f, impl=self.impl,
                         n=self.n_agents, **self.filter_hyper)


def tree_attack(attack_fn, key, grads, byz_mask):
    """Apply a gradient attack leaf-wise (all implemented attacks are
    coordinate-decomposable, so leaf-wise == flat-wise)."""
    leaves, treedef = jax.tree.flatten(grads)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, l in zip(keys, leaves):
        n = l.shape[0]
        flat = l.reshape(n, -1).astype(jnp.float32)
        out.append(attack_fn(k, flat, byz_mask).reshape(l.shape).astype(
            l.dtype))
    return jax.tree.unflatten(treedef, out)


def _group_mean(grads, group_size: int):
    """Median-of-means stage 1 [19]: mean of the *sent* gradients within
    consecutive groups (aligned with mesh data-axis subgroups, so XLA lowers
    it to subgroup reductions instead of a full agent-stack gather).

    Intentionally NOT the `bucketed` composition wrapper: here the group
    mean must run BEFORE the reshard sharding constraint so the measured
    collective schedule applies to the grouped (k, ...) stack; standalone
    users should prefer ``aggregators.bucketed(spec, group_size)``."""
    def leaf(l):
        n = l.shape[0]
        k = n // group_size
        return jnp.mean(
            l.astype(jnp.float32).reshape((k, group_size) + l.shape[1:]),
            axis=1).astype(l.dtype)
    return jax.tree.map(leaf, grads)


def _reshard_specs(grads, mesh_sizes):
    """Specs that replicate the agent axis and shard parameter dims over
    both mesh axes (first two dims that divide), for the coordinate-wise
    filter's local sort."""
    from jax.sharding import PartitionSpec as P

    def leaf(l):
        axes_left = ["data", "model"]
        dims = [None]                    # agent axis replicated
        for d in l.shape[1:]:
            placed = None
            if axes_left and d % mesh_sizes.get(axes_left[0], 1) == 0:
                placed = axes_left.pop(0)
            dims.append(placed)
        return P(*dims)
    return jax.tree.map(leaf, grads)


def make_train_step(cfg, bz: ByzantineConfig, optimizer,
                    mesh_sizes: dict | None = None,
                    bucket: int | None = None, telemetry: bool = False):
    """Returns train_step(params, opt_state, momentum, batch, key[,
    roster_idx, roster_valid]) -> (params, opt_state, momentum, metrics).

    ``telemetry`` (static Python flag): metrics additionally carry a
    fixed-shape ``"telemetry"`` struct — the aggregator's (n,) selection
    weights, delivery mask and contribution weights
    (``spec.selection_weights``, see :mod:`repro.obs`).  ``False`` emits
    the EXACT historical jaxpr (bit-identical results, same compile
    count); ``True`` adds only (n,)-sized aux outputs, so the compile
    budget is unchanged either way.

    ``bucket`` (elastic membership): per-agent gradients are still computed
    for the full n_agents batch, but aggregation runs over the LIVE roster
    packed into a (bucket,)-row stack — ``roster_idx`` (bucket,) int32 are
    the live slots (padded by repeating a live slot), ``roster_valid``
    (bucket,) bool marks the real ones.  The spec is re-specialized to the
    bucket's (n, f) plan; both roster operands are traced, so membership
    churn compiles at most once per bucket.  ``bucket=None`` is exactly the
    historical n-static step, bit-for-bit."""
    from repro.core.attacks import is_adaptive_attack
    if is_adaptive_attack(bz.attack):
        raise NotImplementedError(
            f"{bz.attack} is a defense-aware attack — run it through the "
            "async loop (repro.simulator.async_loop threads attack state "
            "and the defense's center alongside aggregator state)")
    attack_fn = get_attack(bz.attack, **bz.attack_hyper) \
        if bz.attack != "none" else None
    byz_mask = make_byzantine_mask(bz.n_agents, bz.f)
    spec = bz.resolve_spec()
    if spec.stateful:
        raise NotImplementedError(
            f"{spec.name} is stateful — run it through the async loop "
            "(repro.simulator.async_loop threads aggregator state)")
    # agg_dtype in QUANT_DTYPES (int8 / float8_e4m3fn) selects the
    # compressed-exchange pipeline: the fp32 arena is quantized per-row
    # with a scale sidecar right after ravel (core.flat.quantize_rows)
    # and the kernels dequantize inside the tile — NOT a tree-wide cast
    # (astype(int8) would truncate gradients to garbage)
    quant = bool(bz.agg_dtype) and bz.agg_dtype in QUANT_DTYPES
    if bz.agg_dtype and not quant:
        # sort/exchange in agg_dtype wherever the rule supports it —
        # reaches through composition wrappers to the executing rule
        # (weighted rules accumulate their statistics in fp32 regardless;
        # the pallas path, like gather, accumulates fp32 and ignores it)
        spec = spec.with_impl_hyper_if_supported(native_dtype=True)
    if bucket is not None:
        if bz.group_size > 1 or bz.reshard:
            raise NotImplementedError(
                "group_size/reshard are positional over the static "
                "roster — not supported with elastic membership")
        spec = spec.respecialize(bucket)
    if bz.group_size > 1:
        k = bz.n_agents // bz.group_size
        spec = spec.with_f_capped(max((k - 1) // 2, 0))
    # roster-aware gradient coding: the bucket's group table is derived
    # HERE, at step-build (respecialize) time — lru-cached per (n, r) like
    # the trim tables, a static constant of the traced step.  The packed
    # live rows are regrouped positionally (exact in the parallel regime).
    groups = (coding_groups(bucket if bucket is not None else bz.n_agents,
                            bz.draco_r, allow_ragged=bucket is not None)
              if bz.draco_r > 0 else None)
    # zero-copy flat pipeline: dense-stack impls ravel the gradients ONCE
    # into an (n, P) arena right after the communication boundary and
    # unravel ONCE at optimizer-apply — the aggregation dispatch never
    # touches a pytree.  The coded path rides the same arena (inside
    # tree_draco_aggregate for uniform-dtype trees).  reshard stays on the
    # tree path: its whole point is a leaf-wise sharding constraint the
    # flattening would erase.
    use_flat = spec.flat_capable and not bz.reshard

    def agent_loss(p, agent_batch):
        return loss_fn(cfg, p, agent_batch)

    def train_step(params, opt_state, momentum, batch, key,
                   roster_idx=None, roster_valid=None):
        count_trace("train_step")
        # (2) per-agent gradients — agent axis on the data mesh axes.
        # bz.remat = PER-LAYER activation checkpointing inside the scan
        # (whole-loss jax.checkpoint leaves the scan's stacked residuals in
        # place — measured in EXPERIMENTS.md §Perf pair A iteration A5)
        import contextlib

        from repro.distributed.context import layer_remat
        ctx = layer_remat(True) if bz.remat else contextlib.nullcontext()
        with jax.named_scope("grads"):
            with ctx:
                losses, grads = jax.vmap(
                    jax.value_and_grad(agent_loss), in_axes=(None, 0))(
                        params, batch)

            # variance reduction: agents send momentum, not raw gradients
            if bz.momentum_alpha > 0.0:
                momentum, grads = worker_momentum(momentum, grads,
                                                  bz.momentum_alpha)

        # (3) Byzantine injection at the communication boundary
        if attack_fn is not None:
            with jax.named_scope("attack"):
                grads = tree_attack(attack_fn, key, grads, byz_mask)

        # (4) robust aggregation via the AggregatorSpec (+ §Perf variants)
        with jax.named_scope("arena"):
            if bz.agg_dtype and not quant:
                grads = jax.tree.map(
                    lambda l: l.astype(jnp.dtype(bz.agg_dtype)), grads)
            if bz.group_size > 1:
                grads = _group_mean(grads, bz.group_size)
            if bz.reshard and mesh_sizes:
                grads = jax.lax.with_sharding_constraint(
                    grads, _reshard_specs(grads, mesh_sizes))
            plan = FlatPlan.for_tree(grads)
            codes = qs = None
            if quant:
                # quantize the wire: per-row codes + fp32 scale sidecar.
                # The pre-quantization f32 arena is kept ONLY as a local
                # for telemetry (it exists anyway — it's what was
                # quantized); the aggregate itself sees codes + scale.
                # Paths without a scale-aware entry point (coded votes,
                # reshard, non-flat specs) see the fake-quantized stack
                # instead, so every path has identical compressed-
                # exchange semantics.
                arena = plan.ravel(grads, jnp.float32)
                if use_flat and bz.draco_r == 0:
                    codes, qs = quantize_rows(arena, jnp.dtype(bz.agg_dtype))
                else:
                    grads = plan.unravel_stack(
                        fake_quantize(arena, jnp.dtype(bz.agg_dtype)))
            elif (bz.draco_r == 0 and use_flat
                  and plan.uniform_dtype is not None):
                # zero-copy: ONE ravel into the (n, P) arena here, the
                # aggregation runs on the arena, and the single unravel
                # below happens at optimizer-apply — plan offsets are
                # precomputed (FlatPlan is cached per tree structure), so
                # the dispatch itself moves no model-sized memory.
                # Mixed-dtype trees keep the tree path: flattening them
                # would impute masked rows at fp32 instead of each leaf's
                # native rounding (not bitwise).
                arena = plan.ravel(grads)
        vec = None
        with jax.named_scope("aggregate"):
            if bz.draco_r > 0:
                if bucket is not None:
                    # elastic membership: regroup the packed live rows
                    # with the bucket's table; pad slots are masked out of
                    # the vote
                    live = jax.tree.map(lambda l: l[roster_idx], grads)
                    agg = tree_draco_aggregate(live, bz.draco_r,
                                               mask=roster_valid,
                                               groups=groups)
                else:
                    agg = tree_draco_aggregate(grads, bz.draco_r,
                                               groups=groups)
            elif codes is not None:
                # compressed flat path: codes on the wire, per-row scale
                # as a sidecar operand — the scaled kernels dequantize
                # in-tile (no (n, P) f32 copy; mixed-dtype trees are fine
                # here since the exchange dtype erases per-leaf dtypes)
                if bucket is not None:
                    vec = spec.aggregate_flat(codes[roster_idx],
                                              mask=roster_valid,
                                              scale=qs[roster_idx])
                else:
                    vec = spec.aggregate_flat(codes, scale=qs)
            elif use_flat and plan.uniform_dtype is not None:
                if bucket is not None:
                    vec = spec.aggregate_flat(arena[roster_idx],
                                              mask=roster_valid)
                else:
                    vec = spec.aggregate_flat(arena)
            elif bucket is not None:
                # elastic membership: the rule sees only the live roster,
                # packed into the bucket's fixed-shape stack (pad slots
                # are repeated live rows, masked out under the documented
                # masked semantics)
                live = jax.tree.map(lambda l: l[roster_idx], grads)
                agg = spec.aggregate(live, mask=roster_valid)
            else:
                agg = spec.aggregate(grads)
        if vec is not None:
            with jax.named_scope("unravel"):
                agg = plan.unravel(vec)

        # (5) server-side optimizer
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
        if telemetry:
            # fixed-shape (n,) aux outputs computed OUTSIDE the aggregate
            # call — the update above is untouched, so results stay
            # bit-identical with telemetry on
            n = bz.n_agents
            if bz.draco_r > 0:
                # the repetition code votes per group: per-agent
                # attribution is uniform participation over the live roster
                m_full = (jnp.zeros((n,), bool).at[roster_idx].max(
                    roster_valid) if bucket is not None
                    else jnp.ones((n,), bool))
                mf = m_full.astype(jnp.float32)
                sel = mf / jnp.maximum(jnp.sum(mf), 1.0)
            elif bucket is not None:
                # quantized runs attribute weights on the PRE-quantization
                # f32 arena (it exists anyway — it is what was quantized);
                # observability must not add a dequantized (n, P) copy
                flat_stack = codes is not None or (
                    use_flat and plan.uniform_dtype is not None)
                stack = (arena[roster_idx] if flat_stack
                         else jax.tree.map(lambda l: l[roster_idx], grads))
                sel_b = spec.selection_weights(stack, mask=roster_valid)
                sel = jnp.zeros((n,), jnp.float32).at[roster_idx].add(
                    jnp.where(roster_valid, sel_b, 0.0))
                m_full = jnp.zeros((n,), bool).at[roster_idx].max(
                    roster_valid)
            else:
                flat_stack = codes is not None or (
                    use_flat and plan.uniform_dtype is not None)
                stack = arena if flat_stack else grads
                sel = spec.selection_weights(stack)
                m_full = jnp.ones((n,), bool)
                if bz.group_size > 1:
                    # rules ran on the k group means: attribute each
                    # group's weight evenly to its members
                    sel = jnp.repeat(sel, bz.group_size) / bz.group_size
            metrics["telemetry"] = {
                "sel_w": sel, "mask": m_full,
                "contrib_w": m_full.astype(jnp.float32)}
        return params, opt_state, momentum, metrics

    return train_step
