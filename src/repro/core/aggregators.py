"""Unified robust-aggregation API: typed, stateful, composable.

The survey's core object — the robust aggregation rule — used to be
dispatched through four stringly-typed surfaces (``FILTERS[name]``,
``tree_aggregate(name, ...)``, ``filter_weights(name, ...)``,
``tree_masked_aggregate(name, ...)``) with capability sets duplicated in
ad-hoc constants and stateful rules (Zeno's ``server_grad``) smuggled
through ``**hyper``.  This module replaces all of that with one object:

:class:`AggregatorSpec`
    A frozen dataclass naming a registered rule plus its static
    configuration (``f``, hyper-parameters, ``impl``).  Hyper-parameters
    are validated against the rule's declared keys at *build* time, so a
    typo raises immediately instead of deep inside jit; impl-only keys
    (``native_dtype``) are split off once into ``impl_hyper``.

``spec.aggregate(grads, mask=None, weights=None, state=None)``
    One entry point subsuming the legacy ``tree_aggregate`` (mask/weights
    None), ``tree_masked_aggregate`` (mask given) and ``filter_weights``
    (via :meth:`AggregatorSpec.weights`).  ``impl="gather"`` is the
    paper-faithful dense path, ``impl="fused"`` the sharding-aware
    stats->weights / leaf-wise decomposition — bit-for-bit identical to
    the historical functions (tests/test_aggregator_spec.py) —
    ``impl="pallas"`` the tiled TPU-kernel path (:mod:`repro.kernels`),
    auto-selected by ``make_spec`` where the rule's caps match an
    available kernel and proven against the gather path by
    tests/test_kernels_parity.py.

Registered rules — caps, impls, masked kernels, elastic, telemetry, compression
    ==================  =========================  ==================  ======  ==================  =========  =========
    rule                caps                       impls               m-pls   elastic             telemetry  compress
    ==================  =========================  ==================  ======  ==================  =========  =========
    mean                weight_decomposable        fused, gather       --      yes                 exact w    q (deq)
    krum                weight_decomp, pairwise    fused, gather, pls  yes     yes (nbr counts)    exact w    q (deq)
    multi_krum          weight_decomp, pairwise    fused, gather, pls  yes     yes (nbr counts)    exact w    q (deq)
    m_krum              weight_decomp, pairwise    fused, gather, pls  yes     yes (nbr counts)    exact w    q (deq)
    mda                 weight_decomp, pairwise    fused, gather, pls  yes     yes (subset tables) exact w    q (deq)
    cge                 weight_decomp, pairwise    fused, gather, pls  yes     yes (keep counts)   exact w    q (deq)
    cgc                 weight_decomposable        fused, gather       --      yes                 exact w    q (deq)
    zeno                weight_decomp, stateful    fused, gather       --      yes (state n-free)  exact w    --
    zeno_pp             weight_decomp, stateful    custom (fused)      --      yes (state n-free)  exact w    --
    coordinate_median   coordwise                  fused, gather, pls  yes     yes                 particip.  q in-tile
    trimmed_mean        coordwise                  fused, gather, pls  yes     yes (trim counts)   particip.  q in-tile
    phocas              coordwise                  fused, gather       --      yes                 particip.  q (deq)
    mean_around_median  coordwise                  fused, gather       --      yes                 particip.  q (deq)
    geometric_median    iterative                  fused, gather       --      yes                 particip.  q (deq)
    rfa                 iterative                  fused, gather       --      yes                 particip.  q (deq)
    median_of_means     iterative                  fused, gather       --      yes                 particip.  q (deq)
    bulyan              iterative, pairwise        fused, gather, pls  yes     yes (theta/beta)    theta sel  q (deq)
    sign_sgd            coordwise                  fused, gather, pls  yes     yes                 particip.  1-bit vote
    sparse_mean         coordwise (custom+flat)    flat, gather law    yes     yes                 particip.  sparse
    centered_clip       iterative, STATEFUL        flat, gather, pls*  own     yes (state n-free)  clip w     --
    clipped             wrapper                    delegates to inner  --      via inner           via inner  --
    bucketed            wrapper                    delegates to inner  --      via inner           particip.  --
    staleness_disc.     wrapper                    delegates to inner  --      via inner           via inner  --
    server_momentum     wrapper, STATEFUL          delegates to inner  --      via inner           via inner  --
    ==================  =========================  ==================  ======  ==================  =========  =========

    Defenses with MEMORY (the PR-10 history filters — the survey's answer
    to adaptive, defense-aware attackers): ``centered_clip`` iteratively
    re-clips every row to radius ``tau`` around the CARRIED server center
    (state key ``server_grad``, EMA of past aggregates via ``ema``), so a
    poison small enough to survive one round still cannot move the
    estimate more than ``iters * tau`` per step; its telemetry (*clip w*)
    exposes the effective per-row clip weights ``lam_i``, and ``pls*``
    marks the explicit-opt-in fused MAC (``impl="pallas"`` routes the
    per-iteration multiply-accumulate through
    ``kernels.wsum.clipped_weighted_sum``; ``auto`` keeps the dense body
    — different reduce association).  ``server_momentum`` wraps ANY inner
    rule and EMAs its outputs (``beta``), de-correlating round-to-round
    adaptive bias; both thread state through the ordinary
    ``init_state``/``update_state`` protocol the async loop already
    carries for zeno (``state["inner"]`` nests wrapper chains).  The
    defense-aware attack side lives in :mod:`repro.core.attacks.adaptive`
    (``spec_alie`` / ``min_max`` line-search their poison against the
    executing spec itself; ``slow_drift`` accumulates bias below
    per-round thresholds) and the two sides meet in
    ``benchmarks/bench_convergence.py``'s leaderboard.

    ``compress`` (the compressed robust exchange layer, ROADMAP item 3):
    *1-bit vote* — ``sign_sgd`` exchanges sign(g) (1 bit/coordinate) and
    aggregates by per-coordinate majority vote; *sparse* —
    ``sparse_mean`` treats a zero coordinate as NOT SENT and averages
    each coordinate over ``(coord_sent) * weight`` with explicit-zero
    guards (the fed_dropout_avg shape); *q in-tile* — int8 / fp8 arena
    codes (``repro.core.flat.quantize_rows`` per-row scale sidecar,
    ``aggregate_flat(..., scale=)``) are dequantized INSIDE the Pallas
    tile — no dequantized (n, P) copy is ever materialized (jaxpr-gated
    by tests/test_kernels_parity.py); *q (deq)* — quantized arenas are
    accepted but dequantized at engine level before the rule runs (a
    one-time ``warn_once`` names the rule); stateful rules reject
    quantized arenas by construction (no flat path).

    ``telemetry`` (:meth:`AggregatorSpec.selection_weights`, consumed by
    :mod:`repro.obs`): *exact w* — the rule's own (n,) application
    weights (synchronous fused path reconstructs the aggregate exactly
    via ``tree_weighted_sum``); *theta sel* — bulyan's krum-stage
    selection, 1/theta on chosen rows; *particip.* — normalized delivery
    weights (every arrived row enters the order statistics); *via
    inner* — the wrapper applies its row transform, then reads the inner
    rule's telemetry.  ``spec.aggregate_with_telemetry`` /
    ``aggregate_flat_with_telemetry`` bundle the aggregate with the
    fixed-shape ``{sel_w, mask, contrib_w}`` struct the flight recorder
    accumulates into per-agent suspicion scores.

    ``elastic``: every rule supports elastic-n specs — build with
    ``make_spec(name, n=elastic(n_max, buckets=...), f=frac(0.2))`` and
    the per-bucket static plans named in parentheses are precomputed at
    BUILD time; ``spec.respecialize(n_live)`` then selects the bucket's
    concrete spec (dataclass-equal to a fresh ``make_spec(..., n=b)``, so
    jit caches hit and membership churn over the bucketed range costs at
    most ``len(buckets)`` compilations).  ``f = frac(ratio)`` re-derives
    the Byzantine budget per bucket so breakdown bounds track the live
    roster; a static int ``f`` is carried unchanged across buckets.

    Coding x elastic: the draco/detox repetition decoders
    (:mod:`repro.core.redundancy.coding`) sit UPSTREAM of this registry —
    they vote over coded groups, then (detox) feed bucket means into a
    registered rule above.  Their group tables are the same trim-table
    trick as the per-bucket plans: ``coding_groups(n, r)`` is an
    lru-cached read-only host array re-derived per elastic bucket at
    respecialize time (``allow_ragged=True`` admits a smaller trailing
    group when ``r`` does not divide the bucket), so coded aggregation
    under membership churn stays within the same ``len(buckets)``
    compile budget and rides the flat arena
    (:func:`~repro.core.redundancy.coding.flat_draco_aggregate`)
    bit-for-bit with the tree entry point.

    ``m-pls`` (masked-selection column): the rule's masked/weighted
    pallas path is a FUSED imputation-free kernel — mean-imputation
    happens inside the sort tile (repro.kernels.masked) for the
    coordinate rules and inside the Gram / application tiles
    (repro.kernels.pairwise.masked_gram + repro.kernels.wsum) for the
    selection family, so the imputed (n, d) stack is NEVER materialized
    and quorum masks / staleness weights stay traced operands (fault
    schedules and rosters never recompile, never allocate).  Rules
    without a masked kernel impute at tree level (a one-time warning
    fires if a pallas spec falls back there on mixed-dtype leaves).
    All pallas entries run in interpret mode off-TPU (same code path);
    ``impl="auto"`` (the ``make_spec`` default) picks pallas exactly for
    the rules marked above (bulyan: only for its classic ``base="krum"``)
    and :func:`pallas_available` is the predicate.

Zero-copy flat pipeline
    Dense-stack impls (gather / pallas, stateless non-wrapper rules —
    ``spec.flat_capable``) also expose ``spec.aggregate_flat(arena,
    mask=..., weights=...)`` over a pre-raveled (n, P) gradient arena
    (:class:`repro.core.flat.FlatPlan`): the training loops ravel ONCE
    per step at gradient production, the serving engine reshapes the
    logits stack for free, and the single unravel happens at
    optimizer-apply — the aggregation dispatch itself never touches a
    pytree and never re-concatenates the model-sized stack.  Bit-for-bit
    with the tree engine for uniform-dtype trees.

Capability flags (:class:`AggregatorCaps`)
    coordwise / weight-decomposable / iterative / masked-capable /
    sharding-aware / stateful — engine dispatch is driven purely by these
    flags and the per-rule callables, so registering a new rule is ONE
    :func:`register_aggregator` call: no dispatch chains, no constants.

State protocol
    Stateful rules (Zeno's server gradient, the delay-adaptive
    ``zeno_pp``) declare ``init_state`` / ``update_state`` hooks; callers
    thread the returned pytree explicitly instead of hiding arrays in
    ``**hyper``:

        state = spec.init_state(proto)
        agg   = spec.aggregate(grads, mask=m, weights=w, state=state)
        state = spec.update_state(state, agg)

Composition wrappers (specs themselves)
    :func:`clipped` (pre-aggregation norm clipping), :func:`bucketed`
    (median-of-means style pre-bucketing) and :func:`staleness_discounted`
    (Kardam/Zeno++-line delay discounting) wrap an inner spec and are
    ordinary registry entries, so they nest:  ``clipped(bucketed(spec))``.

Static work (MDA subset enumeration, trim counts) is precomputed once per
(n, f) via caches at spec-build time (``make_spec(..., n=...)``) or on
first trace, instead of on every call.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import warnings
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.filters import dense as D
from repro.core.flat import FlatPlan
from repro.kernels.ref import sparse_mean_law, weighted_row_sum


class AggregatorDeprecationWarning(DeprecationWarning):
    """Raised (as a warning) by the legacy string-dispatch shims in
    :mod:`repro.core.aggregation` — internal code must use specs."""


_WARNED_ONCE: set = set()


def warn_once(key, message, category=UserWarning, stacklevel=3):
    """Warn exactly once per ``key`` across the process.

    stdlib location-dedup ("default" action) is version-gated on the
    global warning filters, which jax mutates on ordinary dispatches —
    without manual dedup a warning inside a training loop would re-fire
    every single step.  THE one dedup mechanism: the deprecation shims
    (:mod:`repro.core.aggregation`) and the kernel-fallback notices below
    both key into this set."""
    if key in _WARNED_ONCE:
        return
    _WARNED_ONCE.add(key)
    warnings.warn(message, category, stacklevel=stacklevel)


# ---------------------------------------------------------------------------
# tree helpers (agent axis = leading axis of every leaf)


def tree_stack_ravel(grads):
    """(pytree with leading n) -> (n, P) dense stack (one concatenate;
    leaf dtypes preserved — mixed-dtype trees promote like concatenate)."""
    leaves = jax.tree.leaves(grads)
    n = leaves[0].shape[0]
    return jnp.concatenate([l.reshape(n, -1) for l in leaves], axis=1)


def tree_unravel_like(vec, proto):
    """(P,) -> pytree shaped like one agent's grads (proto has leading n).

    Offsets/sizes come from the proto's cached :class:`FlatPlan` — computed
    once per tree structure, never per call (the legacy version re-derived
    ``np.prod`` sizes inside every trace)."""
    return FlatPlan.for_tree(proto).unravel(vec)


def tree_sqnorms(grads):
    """Per-agent squared norms, accumulated leaf-wise: (n,) fp32.

    NO reshapes: flattening (n, d1, d2, ...) -> (n, -1) merges sharded and
    unsharded dims, which forces the SPMD partitioner to regroup (gather)
    the whole stack.  Axis-tuple reductions keep the contraction local +
    one tiny psum."""
    def leaf(l):
        axes = tuple(range(1, l.ndim))
        return jnp.sum(jnp.square(l.astype(jnp.float32)), axis=axes)
    return functools.reduce(jnp.add, [leaf(l) for l in jax.tree.leaves(grads)])


def tree_gram(grads):
    """Pairwise inner products, accumulated leaf-wise: (n, n) fp32
    (multi-dim tensordot — sharding-preserving, no reshape)."""
    def leaf(l):
        axes = tuple(range(1, l.ndim))
        return jnp.tensordot(l.astype(jnp.float32), l.astype(jnp.float32),
                             axes=(axes, axes))
    return functools.reduce(jnp.add, [leaf(l) for l in jax.tree.leaves(grads)])


def tree_dot(grads, vec_tree):
    """<g_i, v> per agent: (n,) fp32 (sharding-preserving)."""
    def leaf(l, v):
        axes = tuple(range(1, l.ndim))
        return jnp.tensordot(l.astype(jnp.float32), v.astype(jnp.float32),
                             axes=(axes, tuple(range(v.ndim))))
    return functools.reduce(
        jnp.add, jax.tree.leaves(jax.tree.map(leaf, grads, vec_tree)))


def tree_weighted_sum(grads, w):
    """sum_i w_i * g_i per leaf."""
    return jax.tree.map(lambda l: weighted_row_sum(l, w).astype(l.dtype),
                        grads)


def tree_where_agents(mask, a, b):
    """Per-agent select on n-leading pytrees (keeps b's leaf dtypes)."""
    def leaf(x, y):
        m = mask.reshape((-1,) + (1,) * (x.ndim - 1))
        return jnp.where(m, x.astype(y.dtype), y)
    return jax.tree.map(leaf, a, b)


def _gram_to_d2(gram):
    sq = jnp.diag(gram)
    return jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * gram, 0.0)


def _n_agents(grads) -> int:
    return jax.tree.leaves(grads)[0].shape[0]


# ---------------------------------------------------------------------------
# static plans — combinatorial / count work shared across traces


@functools.lru_cache(maxsize=None)
def mda_combos(n: int, f: int) -> np.ndarray:
    """All (n-f)-subsets for minimum-diameter averaging, enumerated ONCE
    per (n, f) (the legacy path re-enumerated per trace)."""
    combos = np.asarray(list(itertools.combinations(range(n), n - f)))
    if len(combos) > 200_000:
        raise ValueError(f"MDA infeasible for n={n}, f={f}")
    return combos


@functools.lru_cache(maxsize=None)
def trim_count(n: int, f: int, beta: float | None) -> int:
    """Per-side trim count of the coordinate-wise trimmed mean."""
    b = int(np.ceil((beta if beta is not None else f / n) * n)) if n else 0
    return min(b, (n - 1) // 2)


# ---------------------------------------------------------------------------
# elastic membership: n as a bucketed range, f as a live-roster policy


@dataclass(frozen=True)
class ElasticN:
    """A bucketed range of live agent counts for elastic-n specs.

    ``buckets`` are ascending capacities ending at ``n_max``; a live roster
    of ``n_live`` agents is served by the smallest bucket >= n_live (live
    rows are packed into the bucket's stack, surplus slots are ghost rows
    masked out under the engine's documented masked semantics).  Build via
    :func:`elastic`."""
    n_max: int
    buckets: Tuple[int, ...]

    def __post_init__(self):
        if not self.buckets:
            raise ValueError("elastic: need at least one bucket")
        if list(self.buckets) != sorted(set(self.buckets)):
            raise ValueError(
                f"elastic: buckets must be strictly ascending, got "
                f"{self.buckets}")
        if self.buckets[-1] != self.n_max or self.buckets[0] < 1:
            raise ValueError(
                f"elastic: buckets must lie in [1, n_max={self.n_max}] and "
                f"end at n_max, got {self.buckets}")

    def bucket_for(self, n_live: int) -> int:
        """Smallest bucket capacity serving ``n_live`` live agents."""
        if n_live > self.n_max:
            raise ValueError(
                f"n_live={n_live} exceeds the elastic n_max={self.n_max}")
        if n_live < 1:
            raise ValueError(f"n_live must be >= 1, got {n_live}")
        for b in self.buckets:
            if b >= n_live:
                return b
        raise AssertionError("unreachable: last bucket == n_max")

    def pack(self, live):
        """Pack live agent indices into their bucket's fixed shape.

        ``live``: 1-d array of live agent slots (ascending, >= 1 entry —
        raises on an empty roster; the loops only reach the elastic path
        when something was delivered, which implies a live member).
        Returns ``(bucket, idx, valid)``: ``idx`` (bucket,) int32 — the
        live slots padded by REPEATING the first live slot — and ``valid``
        (bucket,) bool marking the real ones.  The one shared packing
        idiom of the async loop, the sync step driver and the serving
        engine, so pad strategy and error behaviour can never diverge."""
        live = np.asarray(live, np.int32)
        b = self.bucket_for(len(live))       # raises on an empty roster
        idx = np.concatenate([live, np.full(b - len(live), live[0],
                                            np.int32)])
        valid = np.arange(b) < len(live)
        return b, idx, valid


def elastic(n_max: int, buckets: int | Tuple[int, ...] = 3,
            n_min: int | None = None) -> ElasticN:
    """Elastic agent count for ``make_spec(..., n=elastic(n_max, ...))``.

    ``buckets`` is either an explicit ascending tuple of capacities (the
    last must equal ``n_max``) or a bucket COUNT: capacities are then
    spread evenly over [n_min (default ~n_max/2), n_max].  More buckets =
    tighter plans under churn but more (bounded, build-time-planned)
    compilations; ``buckets=tuple(range(n_min, n_max + 1))`` degenerates
    to one plan per live count — the naive re-jit baseline the benchmarks
    compare against."""
    if isinstance(buckets, int):
        lo = n_min if n_min is not None else max(1, (n_max + 1) // 2)
        if not 1 <= lo <= n_max:
            raise ValueError(f"n_min={lo} outside [1, n_max={n_max}]")
        k = max(1, int(buckets))
        if k == 1:
            return ElasticN(n_max=n_max, buckets=(n_max,))
        pts = np.unique(np.linspace(lo, n_max, k).round().astype(int))
        return ElasticN(n_max=n_max, buckets=tuple(int(b) for b in pts))
    return ElasticN(n_max=n_max, buckets=tuple(int(b) for b in buckets))


@dataclass(frozen=True)
class FracF:
    """A Byzantine-budget POLICY: ``f = max(min_f, floor(ratio * n))``,
    re-derived per elastic bucket so the breakdown bound tracks the live
    roster.  Build via :func:`frac`."""
    ratio: float
    min_f: int = 0

    def __post_init__(self):
        if not 0.0 <= self.ratio < 1.0:
            raise ValueError(f"frac ratio must be in [0, 1), got "
                             f"{self.ratio}")

    def resolve(self, n: int) -> int:
        # epsilon guards fp products landing just below an integer
        # (0.29 * 100 == 28.999999999999996): the budget must not silently
        # tolerate one fewer adversary than the stated ratio
        return max(self.min_f, int(np.floor(self.ratio * n + 1e-9)))


def frac(ratio: float, min_f: int = 0) -> FracF:
    """``f=frac(0.2)``: tolerate 20% of the LIVE roster per bucket."""
    return FracF(ratio=ratio, min_f=min_f)


# ---------------------------------------------------------------------------
# capability flags + registry


@dataclass(frozen=True)
class AggregatorCaps:
    """What an aggregation rule can do — drives engine dispatch."""
    coordwise: bool = False           # leaf-wise per-coordinate rule
    weight_decomposable: bool = False  # filter(g) == sum_i w_i g_i exactly
    iterative: bool = False           # fixed-point / multi-round tree rule
    masked_capable: bool = True       # supports mask/weights aggregation
    sharding_aware: bool = False      # fused impl avoids full-stack gather
    stateful: bool = False            # carries init_state/update_state
    staleness_aware: bool = False     # `weights` = raw staleness ROUNDS,
    #                                   not discount multipliers
    pairwise: bool = False            # selection statistics derivable from
    #                                   the (n, n) Gram of the stack
    #                                   (pairwise distances / norm diagonal)


@dataclass(frozen=True)
class AggregatorDef:
    """Registry record: capabilities + the callables the engine dispatches
    to.  All callables take the spec first, so hyper/state plumbing is
    uniform and new rules never touch the engine."""
    name: str
    caps: AggregatorCaps
    hyper_keys: frozenset          # allowed hyper-parameter names
    impl_keys: frozenset           # impl-only keys (split into impl_hyper)
    state_keys: frozenset          # keys that must arrive via state=, not hyper
    gather_keys: frozenset         # hyper forwarded to the dense gather fn
    dense_fn: Optional[Callable] = None    # (stack, f, **hyper) -> (P,)
    weights_fn: Optional[Callable] = None  # (spec, grads, state) -> (n,)
    tree_fn: Optional[Callable] = None     # (spec, grads, state) -> tree
    custom_fn: Optional[Callable] = None   # (spec, grads, mask, w, state)
    masked_fn: Optional[Callable] = None   # masked-path override
    flat_fn: Optional[Callable] = None     # (spec, stack, mask, w, state,
    #                                        qscale) -> (P,) — rules whose
    #                                        flat law is NOT impute-then-
    #                                        scale (per-coordinate weights)
    gather_state_fn: Optional[Callable] = None  # (spec, state) -> extra hyper
    init_state_fn: Optional[Callable] = None    # (spec, proto) -> state
    update_state_fn: Optional[Callable] = None  # (spec, state, agg) -> state
    is_wrapper: bool = False       # requires inner spec
    tags: tuple = ()               # e.g. ("table2",)


REGISTRY: dict[str, AggregatorDef] = {}


def register_aggregator(name: str, *, caps: AggregatorCaps,
                        hyper: tuple = (), impl_keys: tuple = (),
                        state_keys: tuple = (), gather: tuple = (),
                        dense_fn=None, weights_fn=None, tree_fn=None,
                        masked_fn=None, flat_fn=None, gather_state_fn=None,
                        init_state=None, update_state=None,
                        is_wrapper: bool = False, tags: tuple = ()):
    """Register an aggregation rule.  Returns a DECORATOR — apply it to
    the rule's custom aggregate function

        @register_aggregator("my_rule", caps=AggregatorCaps(...))
        def my_rule(spec, grads, mask, weights, state): ...

    or, when the rule is fully described by the keyword callables
    (dense_fn/weights_fn/tree_fn), apply it to None:

        register_aggregator("my_rule", caps=..., weights_fn=...)(None)

    This is the single extension point: no capability constants, no
    dispatch chains, no edits anywhere else."""
    def _add(custom_fn):
        if name in REGISTRY:
            raise ValueError(f"aggregator {name!r} already registered")
        REGISTRY[name] = AggregatorDef(
            name=name, caps=caps, hyper_keys=frozenset(hyper),
            impl_keys=frozenset(impl_keys), state_keys=frozenset(state_keys),
            gather_keys=frozenset(gather), dense_fn=dense_fn,
            weights_fn=weights_fn, tree_fn=tree_fn, custom_fn=custom_fn,
            masked_fn=masked_fn, flat_fn=flat_fn,
            gather_state_fn=gather_state_fn,
            init_state_fn=init_state, update_state_fn=update_state,
            is_wrapper=is_wrapper, tags=tags)
        return custom_fn

    return _add


def _register_plain(name, **kw):
    register_aggregator(name, **kw)(None)


def get_aggregator_def(name: str) -> AggregatorDef:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown aggregator {name!r}; registered: "
            f"{sorted(REGISTRY)}") from None


def list_aggregators(tag: str | None = None) -> list[str]:
    return sorted(n for n, d in REGISTRY.items()
                  if tag is None or tag in d.tags)


# ---------------------------------------------------------------------------
# the spec


@dataclass(frozen=True)
class AggregatorSpec:
    """Typed handle to a registered aggregation rule.

    Build with :func:`make_spec` (validates hyper keys, splits impl-only
    keys, precomputes static plans when ``n`` is known).  Frozen and
    array-free, so specs pass freely through jit closures and configs.
    """
    name: str
    f: int = 0
    hyper: tuple = ()                 # sorted ((key, value), ...) — static
    impl: str = "fused"               # fused | gather
    impl_hyper: tuple = ()            # impl-only keys, e.g. native_dtype
    inner: Optional["AggregatorSpec"] = None   # wrapper composition
    n: Optional[int] = None           # static agent count (plan precompute)
    # elastic-n: the bucketed live-count range this spec was built for
    # (``n`` then holds n_max and ``f`` the budget resolved at n_max);
    # ``respecialize(n_live)`` selects the per-bucket concrete spec
    elastic: Optional[ElasticN] = None
    f_policy: Optional[FracF] = None  # f re-derived per bucket when set

    # -- introspection ----------------------------------------------------
    @property
    def caps(self) -> AggregatorCaps:
        return get_aggregator_def(self.name).caps

    @property
    def stateful(self) -> bool:
        d = get_aggregator_def(self.name)
        return d.caps.stateful or (self.inner is not None
                                   and self.inner.stateful)

    @property
    def staleness_aware(self) -> bool:
        """True if this spec (or any nested inner) interprets ``weights``
        as raw staleness round counts rather than discount multipliers."""
        d = get_aggregator_def(self.name)
        return d.caps.staleness_aware or (self.inner is not None
                                          and self.inner.staleness_aware)

    @property
    def elastic_n(self) -> Optional[ElasticN]:
        """The ElasticN governing this spec — its own, or the wrapped
        chain's (composition wrappers delegate elasticity to their inner
        rule, however deeply nested).  This is what the training/serving
        loops consult: reading ``.elastic`` alone would silently miss
        wrapper(elastic-inner) specs."""
        if self.elastic is not None:
            return self.elastic
        return self.inner.elastic_n if self.inner is not None else None

    @property
    def hyper_dict(self) -> dict:
        return dict(self.hyper)

    @property
    def impl_hyper_dict(self) -> dict:
        return dict(self.impl_hyper)

    def hp(self, key: str, default=None):
        for k, v in self.hyper:
            if k == key:
                return v
        return default

    def describe(self) -> str:
        h = ", ".join(f"{k}={v}" for k, v in self.hyper)
        el = (f", elastic[{'/'.join(map(str, self.elastic.buckets))}]"
              if self.elastic else "")
        inner = f" -> {self.inner.describe()}" if self.inner else ""
        return (f"{self.name}(f={self.f}{', ' + h if h else ''}{el})"
                + inner)

    # -- evolution --------------------------------------------------------
    def with_f(self, f: int) -> "AggregatorSpec":
        return dataclasses.replace(self, f=f)

    def with_f_capped(self, f_max: int) -> "AggregatorSpec":
        """Cap f on this spec AND every nested inner spec — the rule that
        actually executes inside composition wrappers must respect the
        reduced budget (e.g. after pre-aggregation grouping shrinks n)."""
        inner = self.inner.with_f_capped(f_max) if self.inner else None
        return dataclasses.replace(self, f=min(self.f, f_max), inner=inner)

    def with_impl(self, impl: str) -> "AggregatorSpec":
        return dataclasses.replace(
            self, impl=_resolve_impl(self.name, impl, self.hyper_dict))

    def respecialize(self, n_live: int) -> "AggregatorSpec":
        """The concrete spec serving a live roster of ``n_live`` agents.

        Elastic specs select the smallest bucket >= n_live: the returned
        spec is dataclass-EQUAL (hence hash-equal, hence jit-cache-equal)
        to a fresh ``make_spec(name, f=f_b, impl=..., n=b, **hyper)`` with
        ``f_b`` re-derived by the ``frac`` policy when one was given —
        bit-for-bit parity is pinned by
        tests/test_membership_conformance.py.  All bucket specs are
        prebuilt at ``make_spec`` time, so this never retraces and never
        enumerates plans on the hot path; churn over the bucketed range
        costs at most ``len(buckets)`` step compilations.

        Non-elastic specs return themselves when ``n_live`` matches (or
        ``n`` was never pinned); a mismatched static n raises — silently
        serving a different roster than the spec was planned for would
        void the (n, f) guarantee."""
        return _respecialize(self, n_live)

    def with_impl_hyper(self, **kw) -> "AggregatorSpec":
        d = get_aggregator_def(self.name)
        merged = dict(self.impl_hyper)
        for k, v in kw.items():
            if k not in d.impl_keys:
                raise ValueError(
                    f"{self.name}: {k!r} is not an impl key "
                    f"(allowed: {sorted(d.impl_keys)})")
            merged[k] = v
        return dataclasses.replace(self,
                                   impl_hyper=tuple(sorted(merged.items())))

    def with_impl_hyper_if_supported(self, **kw) -> "AggregatorSpec":
        """Set impl-only keys on this spec AND every nested inner spec,
        wherever the rule declares them — a no-op elsewhere.  This is how
        loop-level knobs (``agg_dtype`` -> ``native_dtype``) reach the rule
        that actually executes inside composition wrappers."""
        d = get_aggregator_def(self.name)
        inner = (self.inner.with_impl_hyper_if_supported(**kw)
                 if self.inner else None)
        spec = dataclasses.replace(self, inner=inner)
        supported = {k: v for k, v in kw.items() if k in d.impl_keys}
        return spec.with_impl_hyper(**supported) if supported else spec

    # -- state protocol ---------------------------------------------------
    def init_state(self, proto):
        """Initial aggregator state for a single-agent gradient prototype
        (pytree without the agent axis).  {} for stateless rules."""
        d = get_aggregator_def(self.name)
        state = d.init_state_fn(self, proto) if d.init_state_fn else {}
        if self.inner is not None and self.inner.stateful:
            state = dict(state)
            state["inner"] = self.inner.init_state(proto)
        return state

    def update_state(self, state, agg):
        """Post-step state transition given the aggregate just produced."""
        d = get_aggregator_def(self.name)
        inner_state = None
        if self.inner is not None and self.inner.stateful:
            inner_state = self.inner.update_state(state["inner"], agg)
        new = (d.update_state_fn(self, state, agg)
               if d.update_state_fn else dict(state))
        if inner_state is not None:
            new = dict(new)
            new["inner"] = inner_state
        return new

    # -- the one entry point ----------------------------------------------
    def aggregate(self, grads, mask=None, weights=None, state=None):
        """Aggregate per-agent gradients (leading axis = agent).

        ``mask``    (n,) bool — rows that actually arrived (None = all);
        ``weights`` (n,) float — per-agent multipliers (staleness
                    discounts); zeroed where ``mask`` is False;
        ``state``   pytree from :meth:`init_state` for stateful rules.

        mask=None and weights=None is the synchronous case (legacy
        ``tree_aggregate``); otherwise the masked/weighted semantics of
        the legacy ``tree_masked_aggregate`` apply, bit-for-bit."""
        d = get_aggregator_def(self.name)
        if self.stateful and state is None:
            raise ValueError(
                f"{self.describe()} is stateful: pass "
                "state=spec.init_state(proto) (called on THIS spec — for "
                "composed specs it nests the inner state correctly)")
        if d.custom_fn is not None:
            return d.custom_fn(self, grads, mask, weights, state)
        if mask is None and weights is None:
            return _sync_aggregate(self, d, grads, state)
        if not d.caps.masked_capable:
            raise ValueError(f"{self.name} does not support masked "
                             f"aggregation")
        if mask is None:
            mask = jnp.ones((_n_agents(grads),), bool)
        if d.masked_fn is not None:
            return d.masked_fn(self, grads, mask, weights, state)
        return _masked_aggregate(self, d, grads, mask, weights, state)

    def weights(self, grads, state=None):
        """Per-agent weights w with filter(g) == sum_i w_i g_i (exact) —
        only for weight-decomposable rules (legacy ``filter_weights``)."""
        d = get_aggregator_def(self.name)
        if d.weights_fn is None:
            raise ValueError(f"{self.name} is not weight-decomposable")
        if d.caps.stateful and state is None:
            raise ValueError(
                f"{self.name} is stateful: pass state=spec.init_state(...)")
        return d.weights_fn(self, grads, state)

    # -- the zero-copy flat path ------------------------------------------
    @property
    def flat_capable(self) -> bool:
        """True iff this spec can aggregate a pre-raveled (n, P) arena via
        :meth:`aggregate_flat` — the dense-stack impls (gather / pallas)
        of plain stateless rules, plus stateful rules that registered an
        explicit flat law.  Composition wrappers, other custom-path
        rules and the fused (leaf-wise, sharding-aware) impl keep the
        tree engine: their arithmetic is defined on leaves, and flattening
        would silently change reduce orders."""
        d = get_aggregator_def(self.name)
        if d.is_wrapper:
            return False
        if self.stateful:
            # stateful rules ride the arena only through an explicit flat
            # law (state raveling is rule-specific — see centered_clip);
            # the caller then passes state= to aggregate_flat
            return d.flat_fn is not None
        if d.flat_fn is not None:
            return True
        return (d.custom_fn is None and d.masked_fn is None
                and self.impl in ("gather", "pallas"))

    def aggregate_flat(self, stack, mask=None, weights=None, state=None,
                       scale=None):
        """Aggregate a pre-raveled (n, P) gradient arena -> (P,) fp32.

        The flat-pipeline twin of :meth:`aggregate`: the caller raveled
        the per-agent gradients ONCE at production time
        (:meth:`repro.core.flat.FlatPlan.ravel`) and unravels the result
        once at optimizer-apply, so the aggregation dispatch itself moves
        no model-sized memory.  Masked/weighted semantics are the gather
        path's impute-then-scale law, bit-for-bit with the tree engine
        for uniform-dtype trees; ``impl="pallas"`` runs the fused masked
        kernels (imputation inside the tile — the (n, P) imputed copy is
        never materialized).

        ``scale``: per-row (n,) fp32 dequantization sidecar for a
        QUANTIZED arena (``stack`` then holds int8 / fp8 exchange codes
        from :func:`repro.core.flat.quantize_rows`; row i decodes as
        ``stack[i].astype(f32) * scale[i]``).  Kernelized coordinate
        rules dequantize INSIDE the tile (no dequantized (n, P) copy is
        materialized — jaxpr-gated by tests/test_kernels_parity.py);
        other rules dequantize at engine level with a one-time
        warning.

        ``stack`` may also be a mixed-dtype tree's SEGMENTED arena, a
        tuple of (n, P_k) arrays (:meth:`FlatPlan.ravel`); the result is
        then the tuple of per-segment (P_k,) fp32 vectors (see
        :func:`_flat_segments_vec`)."""
        d = get_aggregator_def(self.name)
        if not self.flat_capable:
            raise ValueError(
                f"{self.describe()} (impl={self.impl}) has no flat path — "
                "check spec.flat_capable before routing the arena")
        if isinstance(stack, tuple):
            return _flat_segments_vec(self, d, stack, mask, weights, state,
                                      scale)
        if d.flat_fn is not None:
            return d.flat_fn(self, stack, mask, weights, state, scale)
        if mask is None and weights is None:
            return _flat_sync_vec(self, d, stack, state, scale)
        if not d.caps.masked_capable:
            raise ValueError(f"{self.name} does not support masked "
                             f"aggregation")
        if mask is None:
            mask = jnp.ones((stack.shape[0],), bool)
        return _flat_masked_vec(self, d, stack, mask, weights, state, scale)

    # -- aggregation telemetry (repro.obs) --------------------------------
    def selection_weights(self, grads, mask=None, weights=None, state=None):
        """(n,) fp32 per-agent selection/application weights — the
        telemetry signal every detection-based defense starts from.

        For weight-decomposable rules these are the rule's OWN application
        weights (synchronous fused path: ``aggregate(grads) ==
        tree_weighted_sum(grads, selection_weights(grads))`` exactly;
        masked paths: the weights over the imputed stack, matching the
        engine's masked law for the spec's impl).  Bulyan reports its
        theta-selection (1/theta on chosen rows); coordinate-wise and
        iterative rules report *participation* weights (the normalized
        delivery weights — every arrived row enters the order statistics);
        wrappers transform and recurse.  ``grads`` may be a pytree or a
        bare (n, P) arena stack (the flat pipeline's view).

        Fixed shape, no data-dependent control flow: safe to emit as an
        aux output of a jitted step without changing the compile budget.
        """
        d = get_aggregator_def(self.name)
        if self.stateful and state is None:
            raise ValueError(
                f"{self.describe()} is stateful: pass "
                "state=spec.init_state(proto), as for aggregate()")
        return _selection_weights(self, d, grads, mask, weights, state)

    def aggregate_with_telemetry(self, grads, mask=None, weights=None,
                                 state=None):
        """:meth:`aggregate` plus the fixed-shape telemetry struct:
        ``(agg, {"sel_w": (n,) f32, "mask": (n,) bool, "contrib_w":
        (n,) f32})``.  The aggregate is computed by the SAME engine call
        as :meth:`aggregate` — bit-for-bit identical output; the aux
        struct adds only (n,)-sized work, so emitting it from a jitted
        step changes neither results nor the compile budget."""
        agg = self.aggregate(grads, mask=mask, weights=weights, state=state)
        return agg, self._telemetry(grads, mask, weights, state)

    def aggregate_flat_with_telemetry(self, stack, mask=None, weights=None,
                                      state=None, scale=None):
        """:meth:`aggregate_flat` plus the telemetry struct (see
        :meth:`aggregate_with_telemetry`)."""
        vec = self.aggregate_flat(stack, mask=mask, weights=weights,
                                  state=state, scale=scale)
        return vec, self._telemetry(stack, mask, weights, state)

    def _telemetry(self, grads, mask, weights, state):
        n = _n_agents(grads)
        m = jnp.ones((n,), bool) if mask is None else mask.astype(bool)
        cw = (m.astype(jnp.float32) if weights is None
              else weights.astype(jnp.float32) * m.astype(jnp.float32))
        sel = self.selection_weights(grads, mask=mask, weights=weights,
                                     state=state)
        return {"sel_w": sel.astype(jnp.float32), "mask": m,
                "contrib_w": cw}


@functools.lru_cache(maxsize=None)
def _respecialize(spec: AggregatorSpec, n_live: int) -> AggregatorSpec:
    """Cached respecialization: repeat calls for the same (spec, n_live)
    return the SAME object, so hot loops pay one dict probe and jit
    closures see a stable static."""
    if spec.elastic is None:
        if spec.inner is not None and spec.inner.elastic_n is not None:
            # key the wrapper on the RESOLVED inner (recursing through
            # however many wrapper levels sit above the elastic rule), so
            # every n_live that maps to the same bucket yields the same
            # wrapper object
            return _with_inner(spec, _respecialize(spec.inner, n_live))
        if spec.n is None or spec.n == n_live:
            return spec
        raise ValueError(
            f"{spec.describe()} was built for static n={spec.n}, not "
            f"n_live={n_live} — build it with n=elastic(...) to allow "
            "membership changes")
    return _bucket_spec(spec, spec.elastic.bucket_for(n_live))


@functools.lru_cache(maxsize=None)
def _with_inner(spec: AggregatorSpec, inner: AggregatorSpec):
    return dataclasses.replace(spec, inner=inner)


@functools.lru_cache(maxsize=None)
def _bucket_spec(spec: AggregatorSpec, b: int) -> AggregatorSpec:
    """The concrete per-bucket spec of an elastic spec — cached, so every
    respecialize() call for the same bucket returns the SAME object."""
    f_b = spec.f_policy.resolve(b) if spec.f_policy is not None else spec.f
    inner = spec.inner
    if inner is not None and inner.elastic_n is not None:
        inner = inner.respecialize(b)
    out = dataclasses.replace(spec, n=b, f=f_b, elastic=None,
                              f_policy=None, inner=inner)
    _warm_plan(out, b)
    return out


def pallas_available(name: str) -> bool:
    """True iff ``name`` has a registered Pallas kernel path AND its caps
    declare the matching structure (coordinate-wise order statistics or
    Gram-derivable selection) — the condition ``impl="auto"`` checks."""
    d = get_aggregator_def(name)
    if d.is_wrapper or not (d.caps.coordwise or d.caps.pairwise):
        return False
    from repro.kernels import pallas_supported
    return pallas_supported(name)


def _pallas_supports_hyper(name: str, hyper: dict | None) -> bool:
    """Hyper-level kernel gate: bulyan's kernels implement only the
    classic krum base (the generic-base path calls an arbitrary inner
    filter per selection round — not Gram-derivable)."""
    if name == "bulyan":
        return (hyper or {}).get("base", "krum") == "krum"
    return True


def _resolve_impl(name: str, impl: str, hyper: dict | None = None) -> str:
    """``auto`` -> ``pallas`` where caps + kernel availability (and the
    rule's hyper, e.g. bulyan's base) allow, else ``fused``; explicit
    ``pallas`` on an unsupported rule raises HERE (at build time), not
    deep inside jit."""
    if impl not in ("auto", "fused", "gather", "pallas"):
        raise ValueError(
            f"impl must be auto|fused|gather|pallas, got {impl!r}")
    supported = pallas_available(name) and _pallas_supports_hyper(name,
                                                                 hyper)
    if impl == "auto":
        return "pallas" if supported else "fused"
    if impl == "pallas" and not supported:
        from repro.kernels.dispatch import FLAT_SELF_KERNELED
        if name in FLAT_SELF_KERNELED:
            # the rule's flat_fn dispatches its own fused kernel stages
            # (centered_clip's clipped-weighted-sum MAC); the tree path
            # stays dense.  ``auto`` deliberately does NOT select this —
            # the kernel's reduce association differs from the dense body.
            return impl
        from repro.kernels import pallas_supported
        if not pallas_supported(name):
            reason = "no Pallas kernel registered for it"
        elif not _pallas_supports_hyper(name, hyper):
            reason = "its hyper-parameters select a non-kernelized variant"
        else:
            reason = "its caps are neither coordwise nor pairwise"
        raise ValueError(
            f"{name}: impl='pallas' requested but {reason} "
            "(see repro.kernels.dispatch.PALLAS_RULES)")
    return impl


def make_spec(name: str, f: "int | FracF" = 0, impl: str = "auto",
              inner: AggregatorSpec | None = None,
              n: "int | ElasticN | None" = None,
              **hyper) -> AggregatorSpec:
    """Build a validated :class:`AggregatorSpec`.

    Unknown hyper keys raise HERE (not deep inside jit); impl-only keys
    (``native_dtype``) are split off once into ``impl_hyper``; state-like
    keys (``server_grad``) must be threaded via ``state=`` instead.  When
    ``n`` is given, static plans (MDA subset tables, trim counts) are
    precomputed at build time.

    ``n=elastic(n_max, buckets=...)`` builds an ELASTIC spec: static plans
    are precomputed per bucket at build time and
    :meth:`AggregatorSpec.respecialize` selects the bucket's concrete spec
    without retracing when membership changes.  ``f`` may then be a
    :func:`frac` policy, re-derived per bucket so breakdown bounds track
    the live roster (a plain int f is carried unchanged).

    ``impl="auto"`` (the default) resolves to ``"pallas"`` when the rule's
    :class:`AggregatorCaps` (coordwise / pairwise) match a registered
    kernel in :mod:`repro.kernels.dispatch`, else ``"fused"`` — pass
    ``impl=`` explicitly to override.

    NOTE — masked semantics of the new default: ``pallas`` follows the
    GATHER path's masked/weighted semantics (impute-then-scale).  For
    coordinate-wise rules fused is numerically identical, but for the
    weight-decomposable kernelized rules (krum, cge) the fused path folds
    the per-agent weights into the selection weights instead — a
    different (also valid) estimator.  Default-built krum/cge specs
    therefore changed masked behavior when the default moved from
    ``"fused"`` to ``"auto"``: pass ``impl="fused"`` to keep the
    historical masked semantics (``ByzantineConfig.impl`` still defaults
    to it).  tests/test_kernels_parity.py pins all three."""
    d = get_aggregator_def(name)
    el = n if isinstance(n, ElasticN) else None
    n_int = el.n_max if el is not None else n
    f_policy = f if isinstance(f, FracF) else None
    if f_policy is not None:
        if n_int is None:
            raise ValueError(
                f"{name}: f=frac(...) needs n= to resolve the budget — "
                "pass n=<int> or n=elastic(...)")
        f = f_policy.resolve(n_int)
        if el is None:
            f_policy = None           # static n: nothing to re-derive
    if f < 0:
        raise ValueError(f"f must be >= 0, got {f}")
    if d.is_wrapper and inner is None:
        raise ValueError(f"{name} is a composition wrapper: pass inner=")
    if not d.is_wrapper and inner is not None:
        raise ValueError(f"{name} takes no inner spec")
    plain, impl_only = {}, {}
    for k, v in hyper.items():
        if k in d.state_keys:
            raise ValueError(
                f"{name}: {k!r} is aggregator STATE, not a hyper-parameter "
                f"— pass it via state= (see AggregatorSpec.init_state)")
        if k in d.impl_keys:
            impl_only[k] = v
        elif k in d.hyper_keys:
            plain[k] = v
        else:
            raise ValueError(
                f"{name}: unknown hyper-parameter {k!r} "
                f"(allowed: {sorted(d.hyper_keys | d.impl_keys)})")
    impl = _resolve_impl(name, impl, plain)
    spec = AggregatorSpec(name=name, f=f,
                          hyper=tuple(sorted(plain.items())), impl=impl,
                          impl_hyper=tuple(sorted(impl_only.items())),
                          inner=inner, n=n_int, elastic=el,
                          f_policy=f_policy)
    if el is not None:
        for b in el.buckets:          # prebuild every bucket's plans NOW
            _bucket_spec(spec, b)
    elif n_int is not None:
        _warm_plan(spec, n_int)
    return spec


def _warm_plan(spec: AggregatorSpec, n: int):
    """Precompute per-(n, f) static work at spec-build time."""
    if spec.name == "mda":
        mda_combos(n, spec.f)
    if spec.name == "trimmed_mean":
        trim_count(n, spec.f, spec.hp("beta"))
    if spec.inner is not None:
        _warm_plan(spec.inner, n)


# ---------------------------------------------------------------------------
# engine: synchronous path (legacy tree_aggregate, bit-for-bit)


def _sync_aggregate(spec, d, grads, state):
    if spec.impl == "pallas":
        # kernel path: same dense (n, P) fp32 contract as the gather path,
        # with the sort / Gram / selection / application stages running as
        # tiled Pallas kernels (interpret mode off-TPU — same code path)
        from repro.kernels import pallas_aggregate
        stack = tree_stack_ravel(
            jax.tree.map(lambda l: l.astype(jnp.float32), grads))
        return tree_unravel_like(
            pallas_aggregate(spec.name, stack, spec.f, spec.hyper), grads)
    if spec.impl == "gather":
        stack = tree_stack_ravel(
            jax.tree.map(lambda l: l.astype(jnp.float32), grads))
        hyper = {k: v for k, v in spec.hyper if k in d.gather_keys}
        if d.gather_state_fn is not None:
            hyper.update(d.gather_state_fn(spec, state))
        return tree_unravel_like(d.dense_fn(stack, spec.f, **hyper), grads)
    if d.caps.coordwise:
        return d.tree_fn(spec, grads, state)
    if d.caps.weight_decomposable:
        return tree_weighted_sum(grads, d.weights_fn(spec, grads, state))
    if d.caps.iterative:
        return d.tree_fn(spec, grads, state)
    raise ValueError(f"{spec.name}: no fused path registered")


# ---------------------------------------------------------------------------
# engine: masked / staleness-weighted path (legacy tree_masked_aggregate)


def _masked_prelude(grads, mask, weights):
    mask = mask.astype(bool)
    mf = mask.astype(jnp.float32)
    w = mf if weights is None else weights.astype(jnp.float32) * mf
    cnt = jnp.maximum(jnp.sum(mf), 1.0)
    tot = jnp.maximum(jnp.sum(w), 1e-30)
    return mask, w, cnt, tot


def _masked_aggregate(spec, d, grads, mask, weights, state):
    """Robust aggregation over a *varying subset* of agents with per-agent
    weights.  The rules are fixed-n (one jit shape across rounds); the
    masked law differs by rule class:

      * coordinate-wise order statistics and the sign vote
        (_ARRIVED_STAT_RULES) — the statistic over the ARRIVED rows only:
        absent rows enter the sort as +inf sentinels and the kept rank
        window follows the traced arrived count, then the result is
        scaled by the mean weight of arrived rows (a staleness-adaptive
        step size).  Imputing the absent rows at the delivered mean is
        NOT robust — the mean is attack-contaminated, so the ghost rows
        land inside the trim window and one straggler lets the attack
        through;
      * weight-decomposable — rule weights on the mean-imputed stack,
        times the per-agent weights, renormalized (imputed rows carry the
        average arrived weight so a selection landing on them is
        neutral); the imputed ghosts are outliers to the selection
        distances, not candidates inside a trust window;
      * remaining coordinate-wise / iterative — rule on the mean-imputed
        stack, scaled by the mean arrived weight (a known robustness gap
        under attack + absence — see ROADMAP).

    With mask all-True and weights all-one this reduces to the synchronous
    path up to exact-arithmetic no-ops.

    ``impl="pallas"`` + a registered masked kernel (every coordinate-wise
    AND pairwise kernelized rule — see kernels.dispatch.PALLAS_MASKED_
    RULES) takes the FUSED imputation-free path: imputation happens
    inside the sort / Gram / application tiles, so no imputed (n, d)
    copy is ever materialized and the mask/weights stay traced operands
    (fault schedules never recompile).  Arithmetic is identical to the
    imputation below, bit-for-bit in fp32 — the gather path's masked
    semantics exactly.  A pallas spec over MIXED-dtype leaves cannot take
    the fused kernel (one exchange dtype per stack) and falls back to the
    imputed path below with a one-time warning."""
    mask, w, cnt, tot = _masked_prelude(grads, mask, weights)
    # tot is eps-clamped: with EVERY delivered weight zero (possible under
    # sparse/dropout weighting) tot/cnt would be eps-garbage — the update
    # must be an explicit zero instead (tot == sum(w) whenever sum(w) > 0,
    # so the guard is bit-free on every live path)
    scale = jnp.where(jnp.sum(w) > 0, tot / cnt, 0.0)
    if spec.impl == "pallas":
        from repro.kernels import (pallas_masked_aggregate,
                                   pallas_masked_supported)
        leaves = jax.tree.leaves(grads)
        uniform = all(l.dtype == leaves[0].dtype for l in leaves)
        if pallas_masked_supported(spec.name) and uniform:
            stack = tree_stack_ravel(grads)        # native dtype, no cast
            vec = pallas_masked_aggregate(
                spec.name, stack, mask.astype(jnp.float32), w / tot,
                spec.f, spec.hyper)
            agg = tree_unravel_like(vec, grads)
            return jax.tree.map(
                lambda l: (l.astype(jnp.float32) * scale).astype(l.dtype),
                agg)
        if pallas_masked_supported(spec.name) and d.caps.coordwise:
            # mixed-dtype tree, coordinate-wise rule: per-coordinate
            # statistics never mix columns, so per-DTYPE sub-arenas still
            # get the fused kernel — group leaves by dtype, launch one
            # kernel per uniform segment, slice back per leaf.  Bitwise
            # the uniform path per segment (columns are independent), so
            # this equals the gather reference leaf for leaf.
            flat_leaves, treedef = jax.tree.flatten(grads)
            n = flat_leaves[0].shape[0]
            by_dt: dict = {}
            for i, l in enumerate(flat_leaves):
                by_dt.setdefault(jnp.dtype(l.dtype), []).append(i)
            outs: list = [None] * len(flat_leaves)
            for dt, idxs in by_dt.items():
                seg = jnp.concatenate(
                    [flat_leaves[i].reshape(n, -1) for i in idxs], axis=1)
                vec = pallas_masked_aggregate(
                    spec.name, seg, mask.astype(jnp.float32), w / tot,
                    spec.f, spec.hyper)
                off = 0
                for i in idxs:
                    sz = flat_leaves[i][0].size
                    outs[i] = (vec[off:off + sz].astype(dt)
                               .astype(jnp.float32)
                               * scale).astype(dt).reshape(
                                   flat_leaves[i].shape[1:])
                    off += sz
            return jax.tree.unflatten(treedef, outs)
        if pallas_masked_supported(spec.name):
            # pairwise kernels need one exchange dtype for the WHOLE row
            # (the Gram couples every column); a mixed tree falls back to
            # the imputed tree path — same estimator, just the slow path
            dts = tuple(sorted({jnp.dtype(l.dtype).name for l in leaves}))
            warn_once(
                ("masked-pallas-mixed-dtype", spec.name, dts),
                f"{spec.name}: masked pallas kernel skipped — gradient "
                f"leaves carry mixed dtypes {dts}; falling back to the "
                "tree-level imputed path (materializes the imputed "
                "(n, d) stack).  Cast the leaves to one exchange dtype "
                "to restore the fused kernel.")
    if d.caps.coordwise and spec.name in _ARRIVED_STAT_RULES:
        # arrived-window law (see _ARRIVED_STAT_RULES), leaf-wise:
        # coordinate statistics never couple columns, so per-leaf equals
        # the arena path column for column — and the same double rounding
        # through the leaf dtype keeps it bit-for-bit with the kernels
        def _leaf(l):
            vec = _arrived_coord_vec(
                spec, l.reshape(l.shape[0], -1).astype(jnp.float32), mask)
            out = vec.astype(l.dtype)
            return (out.astype(jnp.float32) * scale).astype(
                l.dtype).reshape(l.shape[1:])
        return jax.tree.map(_leaf, grads)
    wn = w / tot
    mean_sel = tree_weighted_sum(grads, wn)
    imputed = tree_where_agents(
        mask, grads,
        jax.tree.map(lambda m, l: jnp.broadcast_to(
            m.astype(l.dtype)[None], l.shape), mean_sel, grads))
    if d.caps.weight_decomposable and spec.impl == "fused":
        # imputed rows carry the average arrived weight: a rule selecting
        # one (it equals the weighted consensus) stays a valid update.
        # Normalize to the RULE's own total weight, not to 1: selection
        # rules sum to 1 so nothing changes, but cgc's clip attenuation
        # (sum < 1) must survive masking — and with mask all-True and
        # weights all-one, fw == rule_w bit-for-bit (the documented
        # full-roster identity the conformance suite pins)
        row_w = jnp.where(mask, w, tot / cnt)
        rule_w = d.weights_fn(spec, imputed, state)
        fw = rule_w * row_w
        fw = fw * (jnp.sum(rule_w) / jnp.maximum(jnp.sum(fw), 1e-30))
        return tree_weighted_sum(imputed, fw)
    agg = _sync_aggregate(spec, d, imputed, state)
    # scale <= 1, == 1 when all fresh; exact 0 when no weight was delivered
    return jax.tree.map(
        lambda l: (l.astype(jnp.float32) * scale).astype(l.dtype), agg)


# ---------------------------------------------------------------------------
# engine: flat-arena path (zero-copy pipeline — the loops ravel once at
# gradient production, this engine never touches a pytree, and the caller
# unravels exactly once at optimizer-apply)


def _flat_f32(stack):
    return stack if stack.dtype == jnp.float32 else stack.astype(jnp.float32)


def _flat_dequant(spec, stack, qscale):
    """Engine-level dequantization fallback for rules without an in-tile
    scaled kernel: materializes the (n, P) f32 copy, with a one-time
    notice (the kernelized coordinate rules never come here — their
    dequant happens inside the tile)."""
    from repro.core.flat import dequantize_rows
    if spec.impl == "pallas":
        warn_once(
            ("flat-scaled-dequant", spec.name),
            f"{spec.name}: no scaled (quantized-arena) kernel — "
            "dequantizing the (n, P) arena at engine level before "
            "aggregation.  Only the kernelized coordinate rules "
            "(coordinate_median, trimmed_mean, sign_sgd, sparse_mean) "
            "dequantize inside the tile.")
    return dequantize_rows(stack, qscale)


# the coordinate-wise rules whose masked law is the order statistic (or
# sign vote) over the ARRIVED rows only — absent rows are +inf sort
# sentinels, never statistics.  Mean-imputing them (the pairwise family's
# law) is not robust: the delivered mean is attack-contaminated, so the
# ghost rows land inside the trim window and one straggler lets the attack
# through.  sparse_mean is arrived-only by construction (absent rows carry
# zero weight).  phocas/mean_around_median ride the count-windowed
# closest-to-center law (kernels/ref.arrived_mean_closest_ref): center
# from the arrived-window statistic, then the cnt-f arrived rows closest
# to it per coordinate.
_ARRIVED_STAT_RULES = ("coordinate_median", "trimmed_mean", "sign_sgd",
                       "phocas", "mean_around_median")


def _arrived_coord_vec(spec, xf, mask):
    """(n, P) fp32 stack -> (P,) fp32 masked coordinate-wise law: the
    statistic over arrived rows, one arithmetic copy shared with the
    fused kernels (kernels/ref.py) so every impl is bit-for-bit."""
    from repro.kernels import ref
    if spec.name == "sign_sgd":
        return ref.masked_sign_vote_ref(xf, mask)
    if spec.name == "coordinate_median":
        return ref.masked_stat_ref(xf, mask, None, "median")
    if spec.name == "phocas":
        return ref.arrived_mean_closest_ref(xf, mask, "trimmed_mean",
                                            spec.f)
    if spec.name == "mean_around_median":
        return ref.arrived_mean_closest_ref(xf, mask, "median", spec.f)
    b = trim_count(xf.shape[0], spec.f, spec.hp("beta"))
    return ref.masked_stat_ref(xf, mask, None, "trimmed_mean", b=b)


def _flat_segments_vec(spec, d, segs, mask, weights, state, qscale):
    """Segmented arena (one (n, P_k) array per dtype) -> tuple of (P_k,)
    fp32.  A coordinate-wise rule never couples columns, so each segment
    is aggregated exactly as a uniform arena of its dtype is (the bf16
    segment keeps its kernel path).  Every other rule couples a row's
    columns (distances, norms, fixed points) and runs on the segments as
    a tree of one leaf per dtype: the tree engine, whose fused Gram sums
    the per-segment partials in fp32 before it selects."""
    if qscale is not None:
        raise ValueError("a quantized arena is one (n, P) array of codes")
    if d.caps.coordwise and not spec.stateful:
        return tuple(spec.aggregate_flat(s, mask, weights) for s in segs)
    agg = spec.aggregate(segs, mask=mask, weights=weights, state=state)
    return tuple(a.astype(jnp.float32) for a in agg)


def _flat_sync_vec(spec, d, stack, state, qscale=None):
    """(n, P) arena -> (P,) fp32: the dense sync engine without the
    per-call ravel/unravel (bit-for-bit with `_sync_aggregate` on the
    equivalent tree — the cast-then-concat and concat-then-cast orders
    produce identical fp32 bits).  ``qscale``: per-row dequant sidecar
    of a quantized arena (kernelized coordinate rules dequantize inside
    the tile; everything else pays an engine-level dequant copy)."""
    if qscale is not None and spec.impl == "pallas":
        from repro.kernels import (pallas_scaled_aggregate,
                                   pallas_scaled_supported)
        if pallas_scaled_supported(spec.name):
            return pallas_scaled_aggregate(spec.name, stack, qscale,
                                           spec.f, spec.hyper)
    if qscale is not None:
        stack = _flat_dequant(spec, stack, qscale)
    if spec.impl == "pallas":
        from repro.kernels import pallas_aggregate
        return pallas_aggregate(spec.name, _flat_f32(stack), spec.f,
                                spec.hyper)
    hyper = {k: v for k, v in spec.hyper if k in d.gather_keys}
    return d.dense_fn(_flat_f32(stack), spec.f, **hyper)


def _flat_masked_vec(spec, d, stack, mask, weights, state, qscale=None):
    """Masked/weighted flat path on the arena: the arrived-window law for
    the coordinate-wise rules (_ARRIVED_STAT_RULES — absent rows are +inf
    sort sentinels, never statistics), the impute-at-delivered-mean law
    for everything else, each scaled by tot/cnt.  ``impl="pallas"`` + a
    registered masked kernel fuses the whole law into the kernel tiles —
    no masked (n, P) copy is ever materialized and mask/weights stay
    traced operands.  With ``qscale`` (quantized arena) dequantization
    happens in-tile for the scaled kernels and the law runs in the
    dequantized fp32 domain."""
    mask, w, cnt, tot = _masked_prelude(stack, mask, weights)
    # all-zero delivered weights must yield an explicit zero update, not
    # an eps-scaled garbage row (tot is clamped at 1e-30); tot == sum(w)
    # whenever sum(w) > 0, so the guard changes no live-path bits
    scale = jnp.where(jnp.sum(w) > 0, tot / cnt, 0.0)
    out_dtype = jnp.float32 if qscale is not None else stack.dtype

    def scaled(vec):
        # the tree engine rounds the fp32 aggregate to the LEAF dtype
        # before applying the scale (unravel, then per-leaf
        # (l.astype(f32) * scale).astype(l.dtype)); replicate that
        # double rounding through the arena dtype so non-f32 uniform
        # trees stay bit-for-bit (a no-op round trip for f32 arenas).
        # Quantized arenas skip the round trip: their virtual dtype is
        # fp32 (rounding the f32 aggregate to int8 would destroy it)
        return vec.astype(out_dtype).astype(jnp.float32) * scale

    if qscale is not None and spec.impl == "pallas":
        from repro.kernels import (pallas_scaled_masked_aggregate,
                                   pallas_scaled_supported)
        if pallas_scaled_supported(spec.name):
            vec = pallas_scaled_masked_aggregate(
                spec.name, stack, qscale, mask.astype(jnp.float32),
                w / tot, spec.f, spec.hyper)
            return scaled(vec)
    if qscale is not None:
        stack = _flat_dequant(spec, stack, qscale)
    if spec.impl == "pallas":
        from repro.kernels import (pallas_masked_aggregate,
                                   pallas_masked_supported)
        if pallas_masked_supported(spec.name):
            vec = pallas_masked_aggregate(
                spec.name, stack, mask.astype(jnp.float32), w / tot,
                spec.f, spec.hyper)
            return scaled(vec)
    if d.caps.coordwise and spec.name in _ARRIVED_STAT_RULES:
        # arrived-window law (see _ARRIVED_STAT_RULES): shared arithmetic
        # with the fused kernels, so gather/pallas stay bit-for-bit
        return scaled(_arrived_coord_vec(spec, _flat_f32(stack), mask))
    wn = w / tot
    xf = _flat_f32(stack)
    mean_sel = weighted_row_sum(xf, wn).astype(stack.dtype)
    imputed = jnp.where(mask[:, None], stack, mean_sel[None])
    return scaled(_flat_sync_vec(spec, d, imputed, state))


# ---------------------------------------------------------------------------
# engine: selection-weight telemetry (repro.obs) — one (n,) read-out per
# rule class, mirroring the aggregate laws above.  Everything is fixed
# shape with no data-dependent control flow, so the loops can emit it as an
# aux output of a jitted step without touching the compile budget; the
# aggregate itself is NEVER computed through this path, so telemetry can't
# perturb results.


def _participation(grads, mask, weights):
    """Normalized delivery weights — the telemetry read-out for rules
    without a per-row application decomposition (coordinate-wise and
    iterative rules: every arrived row enters the order statistics)."""
    n = _n_agents(grads)
    if mask is None and weights is None:
        return jnp.full((n,), 1.0 / n, jnp.float32)
    m = jnp.ones((n,), bool) if mask is None else mask
    _, w, _, tot = _masked_prelude(grads, m, weights)
    return w / tot


def _selection_weights(spec, d, grads, mask, weights, state):
    name = spec.name
    # wrappers: apply the same row transform the aggregate path applies,
    # then read the inner rule's selection
    if d.is_wrapper:
        inner_state = _inner_state(spec, state)
        if name == "clipped":
            tau = spec.hp("tau", 1.0)
            norms = jnp.sqrt(jnp.maximum(tree_sqnorms(grads), 1e-30))
            scale = jnp.minimum(1.0, tau / norms)
            clipped_g = jax.tree.map(
                lambda l: (l.astype(jnp.float32)
                           * scale.reshape((-1,) + (1,) * (l.ndim - 1))
                           ).astype(l.dtype), grads)
            return spec.inner.selection_weights(
                clipped_g, mask=mask, weights=weights, state=inner_state)
        if name == "staleness_discounted":
            s = (jnp.zeros((_n_agents(grads),), jnp.float32)
                 if weights is None else weights.astype(jnp.float32))
            w = staleness_discount_table(s, spec.hp("weighting", "poly"),
                                         spec.hp("power", 1.0),
                                         spec.hp("gamma", 0.7))
            return spec.inner.selection_weights(
                grads, mask=mask, weights=w, state=inner_state)
        if name == "server_momentum":
            # momentum mixes on the OUTPUT; the per-row transform is the
            # identity, so attribution is the inner rule's selection
            return spec.inner.selection_weights(
                grads, mask=mask, weights=weights, state=inner_state)
        # bucketed (and any future group-transform wrapper): rows enter
        # through their group means — per-agent attribution is uniform
        return _participation(grads, mask, weights)
    if name == "zeno_pp":
        # the custom path's own weights (normalized over accepted rows)
        return _zeno_pp_weights(spec, grads, mask, weights, state)
    if name == "centered_clip":
        # effective clip weights of the final iteration, normalized — a
        # row the carried center distrusts (large ||g_i - v||) reports a
        # proportionally smaller share
        _, lam = _cclip_iterate(spec, grads, mask, weights, state)
        tot = jnp.sum(lam)
        return jnp.where(tot > 0, lam / jnp.maximum(tot, 1e-30), lam)
    if name == "bulyan":
        if spec.hp("base", "krum") != "krum":
            return _participation(grads, mask, weights)
        n, f = _n_agents(grads), spec.f
        theta = n - 2 * f
        if mask is None and weights is None:
            d2 = _gram_to_d2(tree_gram(grads))
        else:
            m = (jnp.ones((n,), bool) if mask is None
                 else mask.astype(bool))
            m, w, _, tot = _masked_prelude(grads, m, weights)
            mean_sel = tree_weighted_sum(grads, w / tot)
            imputed = tree_where_agents(
                m, grads,
                jax.tree.map(lambda mn, l: jnp.broadcast_to(
                    mn.astype(l.dtype)[None], l.shape), mean_sel, grads))
            d2 = _gram_to_d2(tree_gram(imputed))
        sel = _bulyan_theta_select(d2, n, f, theta)
        return sel.astype(jnp.float32) / theta
    if d.weights_fn is None:
        return _participation(grads, mask, weights)
    # weight-decomposable rules: the rule's own application weights
    if mask is None and weights is None:
        return d.weights_fn(spec, grads, state)
    n = _n_agents(grads)
    m = jnp.ones((n,), bool) if mask is None else mask.astype(bool)
    if name == "mean":
        # exact: the masked mean applies w/tot directly (no imputation)
        _, w, _, tot = _masked_prelude(grads, m, weights)
        return w / tot
    m, w, cnt, tot = _masked_prelude(grads, m, weights)
    mean_sel = tree_weighted_sum(grads, w / tot)
    imputed = tree_where_agents(
        m, grads,
        jax.tree.map(lambda mn, l: jnp.broadcast_to(
            mn.astype(l.dtype)[None], l.shape), mean_sel, grads))
    rule_w = d.weights_fn(spec, imputed, state)
    if spec.impl == "fused":
        # the fused masked law's exact decomposition (see
        # _masked_aggregate): agg == wsum(imputed, fw) bit-for-bit
        row_w = jnp.where(m, w, tot / cnt)
        fw = rule_w * row_w
        return fw * (jnp.sum(rule_w) / jnp.maximum(jnp.sum(fw), 1e-30))
    # gather / pallas masked law: rule weights over the imputed stack
    # (the aggregate additionally scales by tot/cnt — a global factor
    # that does not change per-agent shares)
    return rule_w


# ---------------------------------------------------------------------------
# fused per-rule implementations (ported verbatim from the legacy module)


def _w_mean(spec, grads, state):
    n = _n_agents(grads)
    return jnp.full((n,), 1.0 / n)


def _mean_masked(spec, grads, mask, weights, state):
    """Exact weighted mean of the arrived rows (no imputation needed)."""
    _, w, _, tot = _masked_prelude(grads, mask, weights)
    return tree_weighted_sum(grads, w / tot)


def _w_cge(spec, grads, state):
    n, f = _n_agents(grads), spec.f
    norms = jnp.sqrt(tree_sqnorms(grads))
    _, idx = jax.lax.top_k(-norms, n - f)
    w = jnp.zeros((n,)).at[idx].set(1.0)
    return w / (n - f) if spec.hp("normalize", True) else w


def _w_cgc(spec, grads, state):
    n, f = _n_agents(grads), spec.f
    norms = jnp.sqrt(tree_sqnorms(grads))
    tau = jnp.sort(norms)[n - f - 1]
    w = jnp.minimum(1.0, tau / jnp.maximum(norms, 1e-30))
    return w / n if spec.hp("normalize", True) else w


def _w_zeno(spec, grads, state):
    n, f = _n_agents(grads), spec.f
    v = state["server_grad"]
    rho = spec.hp("rho", 1e-3)
    lr = spec.hp("lr", 1.0)
    score = lr * tree_dot(grads, v) - rho * tree_sqnorms(grads)
    _, idx = jax.lax.top_k(score, n - f)
    return jnp.zeros((n,)).at[idx].set(1.0 / (n - f))


def _zeno_gather_state(spec, state):
    return {"server_grad": tree_stack_ravel(
        jax.tree.map(lambda l: l.astype(jnp.float32)[None],
                     state["server_grad"]))[0],
        **{k: v for k, v in spec.hyper if k in ("rho", "lr")}}


def _server_grad_zeros(proto):
    return {"server_grad": jax.tree.map(
        lambda l: jnp.zeros(l.shape, jnp.float32), proto)}


def _server_grad_ema(state, agg, ema):
    if not ema:
        return dict(state)             # externally-maintained v
    v = jax.tree.map(
        lambda s, a: (1.0 - ema) * s + ema * a.astype(jnp.float32),
        state["server_grad"], agg)
    return {**state, "server_grad": v}


def _zeno_init_state(spec, proto):
    if not spec.hp("ema", 0.0):
        # classic Zeno has no self-maintained state: with ema=0 the zeros
        # this returns would FREEZE and the defense silently degrades to
        # norm filtering.  Either set ema>0 (EMA of own aggregates) or
        # build the state dict yourself with a real validation gradient:
        # state = {"server_grad": v}.
        raise ValueError(
            "zeno with ema=0 needs an externally maintained validation "
            "gradient: pass state={'server_grad': v} yourself, or set "
            "ema>0 to self-maintain it from past aggregates")
    return _server_grad_zeros(proto)


def _zeno_update_state(spec, state, agg):
    return _server_grad_ema(state, agg, spec.hp("ema", 0.0))


def _w_krum(spec, grads, state):
    n = _n_agents(grads)
    d2 = _gram_to_d2(tree_gram(grads))
    s = D.krum_scores(d2, spec.f)
    return jax.nn.one_hot(jnp.argmin(s), n)


def _w_multi_krum(spec, grads, state):
    n = _n_agents(grads)
    m = spec.hp("m", 2)
    d2 = _gram_to_d2(tree_gram(grads))
    s = D.krum_scores(d2, spec.f)
    _, idx = jax.lax.top_k(-s, m)
    return jnp.zeros((n,)).at[idx].set(1.0 / m)


def _w_m_krum(spec, grads, state):
    n, f = _n_agents(grads), spec.f
    m = spec.hp("m", 2)
    d2 = _gram_to_d2(tree_gram(grads))
    # unrolled with a shrinking neighbour count (see D.krum_scores): the
    # fused path must select exactly the rows the dense reference selects
    mask = jnp.ones((n,), bool)
    w = jnp.zeros((n,))
    for it in range(m):
        s = D.krum_scores(d2, f, mask=mask, k=max(n - it - f - 2, 1))
        i = D.argmin_tiebreak(s, D.masked_row_sums(d2, mask))
        mask = mask.at[i].set(False)
        w = w.at[i].set(1.0 / m)
    return w


def _w_mda(spec, grads, state):
    n, f = _n_agents(grads), spec.f
    combos = mda_combos(n, f)
    d2 = _gram_to_d2(tree_gram(grads))
    sub = d2[combos[:, :, None], combos[:, None, :]]
    # equal-diameter ties broken by subset perimeter (permutation
    # invariance under elastic re-packing — see D.argmin_tiebreak)
    best = jnp.asarray(combos)[
        D.argmin_tiebreak(jnp.max(sub, axis=(1, 2)),
                          jnp.sum(sub, axis=(1, 2)))]
    return jnp.zeros((n,)).at[best].set(1.0 / (n - f))


# -- leaf-wise coordinate rules (fused path — exactly shardable) ------------
#
# Implemented natively on the N-d leaves (agent axis 0).  NO reshape to
# (n, -1): flattening merges sharded/unsharded dims and forces the SPMD
# partitioner to re-gather the whole gradient stack.  The sort itself still
# needs the agent axis local (one all-gather along the agent mesh axes) —
# that is the survey's inherent aggregation cost; everything else stays
# sharded.


def _mean_closest_nd(l, center, k):
    """Per-coordinate mean of the k values closest to ``center``."""
    dist = jnp.abs(l.astype(jnp.float32) - center[None].astype(jnp.float32))
    idx = jnp.argsort(dist, axis=0)[:k]
    vals = jnp.take_along_axis(l.astype(jnp.float32), idx, axis=0)
    return jnp.mean(vals, axis=0)


def _leafwise(spec, grads, state):
    name = spec.name
    native = spec.impl_hyper_dict.get("native_dtype")

    def leaf(l):
        n = l.shape[0]
        f = spec.f
        x = l if native else l.astype(jnp.float32)
        if name == "coordinate_median":
            out = jnp.median(x, axis=0)
        elif name == "trimmed_mean":
            b = trim_count(n, f, spec.hp("beta"))
            s = jnp.sort(x, axis=0)
            kept = s[b:n - b] if b else s
            # native_dtype: keep the mean in the exchange dtype too, else the
            # partitioner hoists the fp32 convert BEFORE the agent gather and
            # the halved-bytes exchange never materializes
            out = jnp.mean(kept if native else kept.astype(jnp.float32),
                           axis=0)
        elif name == "phocas":
            s = jnp.sort(x, axis=0)
            b = min(f, (n - 1) // 2)
            tm = jnp.mean((s[b:n - b] if b else s).astype(jnp.float32),
                          axis=0)
            out = _mean_closest_nd(x, tm, n - f)
        elif name == "mean_around_median":
            med = jnp.median(x.astype(jnp.float32), axis=0)
            out = _mean_closest_nd(x, med, n - f)
        elif name == "sign_sgd":
            # majority vote: the ±1/0 votes sum EXACTLY in fp32 for
            # n < 2^24, so this equals the dense/pallas paths bitwise
            out = jnp.sign(jnp.sum(jnp.sign(x).astype(jnp.float32),
                                   axis=0))
        else:
            raise KeyError(name)
        return out.astype(l.dtype)
    return jax.tree.map(leaf, grads)


# -- iterative rules on trees ----------------------------------------------


def tree_geometric_median(grads, iters: int = 32, eps: float = 1e-8):
    y = jax.tree.map(lambda l: jnp.mean(l.astype(jnp.float32), axis=0), grads)

    def body(y, _):
        diff_sq = tree_sqnorms(
            jax.tree.map(lambda l, c: l.astype(jnp.float32) - c[None], grads,
                         y))
        w = 1.0 / jnp.maximum(jnp.sqrt(diff_sq), eps)
        w = w / jnp.sum(w)
        y = jax.tree.map(
            lambda l: jnp.sum(
                l.astype(jnp.float32)
                * w.reshape((-1,) + (1,) * (l.ndim - 1)), axis=0),
            grads)
        return y, None
    y, _ = jax.lax.scan(body, y, None, length=iters)
    return jax.tree.map(lambda c, l: c.astype(l.dtype), y, grads)


def _t_geometric_median(spec, grads, state):
    return tree_geometric_median(
        grads, iters=spec.hp("iters", 32),
        eps=spec.hp("eps", spec.hp("nu", 1e-8)))


def tree_median_of_means(grads, f, num_groups=None, **gm_kw):
    n = _n_agents(grads)
    k = num_groups if num_groups else (min(n, 2 * f + 1) if f else n)
    while n % k:
        k += 1
    means = jax.tree.map(
        lambda l: jnp.mean(
            l.astype(jnp.float32).reshape((k, n // k) + l.shape[1:]), axis=1),
        grads)
    return tree_geometric_median(means, **gm_kw)


def _t_median_of_means(spec, grads, state):
    return tree_median_of_means(grads, spec.f,
                                num_groups=spec.hp("num_groups"))


def _bulyan_theta_select(d2, n, f, theta):
    """Bulyan's krum-based selection stage: (n,) bool mask of the theta
    rows picked.  Unrolled with a shrinking neighbour count (see
    D.krum_scores) so all theta selections are genuine — the scan version
    collapsed to index order after f + 2 picks.  Shared by the aggregate
    path and :meth:`AggregatorSpec.selection_weights` telemetry."""
    mask = jnp.ones((n,), bool)
    sel = jnp.zeros((n,), bool)
    for it in range(theta):
        s = D.krum_scores(d2, f, mask=mask, k=max(n - it - f - 2, 1))
        i = D.argmin_tiebreak(s, D.masked_row_sums(d2, mask))
        mask = mask.at[i].set(False)
        sel = sel.at[i].set(True)
    return sel


def tree_bulyan(grads, f):
    """Bulyan on trees: krum-based selection from the Gram matrix, then
    leaf-wise coordinate stage with a global selection mask."""
    n = _n_agents(grads)
    theta = n - 2 * f
    d2 = _gram_to_d2(tree_gram(grads))
    sel = _bulyan_theta_select(d2, n, f, theta)

    beta = max(theta - 2 * f, 1)

    def leaf(l):
        flat = l.astype(jnp.float32).reshape(n, -1)
        med = D._masked_median(flat, sel)
        big = jnp.asarray(jnp.inf, flat.dtype)
        dist = jnp.where(sel[:, None], jnp.abs(flat - med[None]), big)
        _, idx = jax.lax.top_k(-dist.T, beta)
        vals = jnp.take_along_axis(flat.T, idx, axis=1)
        return jnp.mean(vals, axis=1).reshape(l.shape[1:]).astype(l.dtype)
    return jax.tree.map(leaf, grads)


def _t_bulyan(spec, grads, state):
    return tree_bulyan(grads, spec.f)


# ---------------------------------------------------------------------------
# built-in registrations — survey Table 2 + Zeno (the registry IS the
# capability table; the legacy COORDWISE/WEIGHTED/ITERATIVE constants are
# derived views over these caps)

_T2 = ("table2",)

_register_plain(
    "mean",
    caps=AggregatorCaps(weight_decomposable=True, sharding_aware=True),
    dense_fn=D.mean, weights_fn=_w_mean, masked_fn=_mean_masked, tags=_T2)
_register_plain(
    "krum",
    caps=AggregatorCaps(weight_decomposable=True, sharding_aware=True,
                        pairwise=True),
    dense_fn=D.krum, weights_fn=_w_krum, tags=_T2)
_register_plain(
    "multi_krum",
    caps=AggregatorCaps(weight_decomposable=True, sharding_aware=True,
                        pairwise=True),
    hyper=("m",), gather=("m",),
    dense_fn=D.multi_krum, weights_fn=_w_multi_krum, tags=_T2)
_register_plain(
    "m_krum",
    caps=AggregatorCaps(weight_decomposable=True, sharding_aware=True,
                        pairwise=True),
    hyper=("m",), gather=("m",),
    dense_fn=D.m_krum, weights_fn=_w_m_krum, tags=_T2)
_register_plain(
    "mda",
    caps=AggregatorCaps(weight_decomposable=True, sharding_aware=True,
                        pairwise=True),
    dense_fn=D.mda, weights_fn=_w_mda, tags=_T2)
_register_plain(
    "cge",
    caps=AggregatorCaps(weight_decomposable=True, sharding_aware=True,
                        pairwise=True),
    hyper=("normalize",), gather=("normalize",),
    dense_fn=D.cge, weights_fn=_w_cge, tags=_T2)
_register_plain(
    "cgc",
    caps=AggregatorCaps(weight_decomposable=True, sharding_aware=True),
    hyper=("normalize",), gather=("normalize",),
    dense_fn=D.cgc, weights_fn=_w_cgc, tags=_T2)
_register_plain(
    "zeno",
    caps=AggregatorCaps(weight_decomposable=True, sharding_aware=True,
                        stateful=True),
    hyper=("rho", "lr", "ema"), state_keys=("server_grad",),
    dense_fn=D.zeno, weights_fn=_w_zeno, gather_state_fn=_zeno_gather_state,
    init_state=_zeno_init_state, update_state=_zeno_update_state, tags=_T2)
_register_plain(
    "coordinate_median",
    caps=AggregatorCaps(coordwise=True, sharding_aware=True),
    impl_keys=("native_dtype",),
    dense_fn=D.coordinate_median, tree_fn=_leafwise, tags=_T2)
_register_plain(
    "trimmed_mean",
    caps=AggregatorCaps(coordwise=True, sharding_aware=True),
    hyper=("beta",), gather=("beta",), impl_keys=("native_dtype",),
    dense_fn=D.trimmed_mean, tree_fn=_leafwise, tags=_T2)
_register_plain(
    "phocas",
    caps=AggregatorCaps(coordwise=True, sharding_aware=True),
    impl_keys=("native_dtype",),
    dense_fn=D.phocas, tree_fn=_leafwise, tags=_T2)
_register_plain(
    "mean_around_median",
    caps=AggregatorCaps(coordwise=True, sharding_aware=True),
    impl_keys=("native_dtype",),
    dense_fn=D.mean_around_median, tree_fn=_leafwise, tags=_T2)
_register_plain(
    "sign_sgd",
    caps=AggregatorCaps(coordwise=True, sharding_aware=True),
    impl_keys=("native_dtype",),
    dense_fn=D.sign_sgd, tree_fn=_leafwise, tags=("compressed",))
_register_plain(
    "geometric_median",
    caps=AggregatorCaps(iterative=True, sharding_aware=True),
    # "nu" kept as a legacy eps alias (the historical fused path accepted
    # it); the gather path forwards only the dense fn's real kwargs
    hyper=("iters", "eps", "nu"), gather=("iters", "eps"),
    dense_fn=D.geometric_median, tree_fn=_t_geometric_median, tags=_T2)
_register_plain(
    "rfa",
    caps=AggregatorCaps(iterative=True, sharding_aware=True),
    hyper=("iters", "nu", "eps"), gather=("iters", "nu"),
    dense_fn=D.rfa, tree_fn=_t_geometric_median, tags=_T2)
_register_plain(
    "median_of_means",
    caps=AggregatorCaps(iterative=True, sharding_aware=True),
    hyper=("num_groups",), gather=("num_groups",),
    dense_fn=D.median_of_means, tree_fn=_t_median_of_means, tags=_T2)
_register_plain(
    "bulyan",
    caps=AggregatorCaps(iterative=True, sharding_aware=True, pairwise=True),
    hyper=("base",), gather=("base",),
    # "meta" keeps bulyan out of the derived legacy ITERATIVE constant
    # (historically it was name-dispatched, not a member of that set)
    dense_fn=D.bulyan, tree_fn=_t_bulyan, tags=_T2 + ("meta",))


# ---------------------------------------------------------------------------
# delay-adaptive score filter (Zeno++ line) — registered SOLELY through the
# new API: one decorator, no capability constants, no dispatch chains.


def _zeno_pp_init_state(spec, proto):
    return _server_grad_zeros(proto)


def _zeno_pp_update_state(spec, state, agg):
    return _server_grad_ema(state, agg, spec.hp("ema", 0.2))


def _zeno_pp_weights(spec, grads, mask, weights, state):
    """The (n,) aggregation weights of the delay-adaptive score filter —
    shared by the custom aggregate path and ``spec.weights``."""
    n = _n_agents(grads)
    eps = spec.hp("eps", 1e-12)
    xi = spec.hp("xi", 0.5)
    if mask is None:
        mask = jnp.ones((n,), bool)
    mask, base_w, _, base_tot = _masked_prelude(grads, mask, weights)
    v = state["server_grad"]
    v_sq = jnp.maximum(tree_sqnorms(jax.tree.map(lambda l: l[None], v))[0],
                       0.0)
    g_norm = jnp.sqrt(jnp.maximum(tree_sqnorms(grads), eps))
    cos_v = tree_dot(grads, v) / (g_norm * jnp.sqrt(jnp.maximum(v_sq, eps)))
    # primary reference: the coordinate-wise median over ONLY the
    # delivered rows (order statistics with +/-inf padding — NO mean
    # imputation: the delivered mean is attacker-controlled, and imputing
    # with it would hand the adversary extra rows and flip the median) —
    # robust at EVERY step, including step 0 when v is still ~0.  The EMA
    # must never be the sole gatekeeper: it lags the true descent
    # direction (rejecting honest rows near convergence) and anything
    # that reaches the aggregate feeds back into it (self-poisoning).
    cnt_i = jnp.sum(mask).astype(jnp.int32)
    lo_i = jnp.maximum(cnt_i - 1, 0) // 2
    hi_i = cnt_i // 2

    def leaf_masked_median(l):
        m = mask.reshape((-1,) + (1,) * (l.ndim - 1))
        s = jnp.sort(jnp.where(m, l.astype(jnp.float32), jnp.inf), axis=0)
        return 0.5 * (jnp.take(s, lo_i, axis=0) + jnp.take(s, hi_i, axis=0))

    ref = jax.tree.map(leaf_masked_median, grads)
    ref_sq = tree_sqnorms(jax.tree.map(lambda l: l[None], ref))[0]
    cos_ref = tree_dot(grads, ref) / (
        g_norm * jnp.sqrt(jnp.maximum(ref_sq, eps)))
    disc = jnp.where(mask, base_w / jnp.maximum(jnp.max(base_w), eps), 0.0)
    thresh = xi * (1.0 - jnp.clip(disc, 0.0, 1.0))
    # norm-sanity gate (Zeno's rho||g||^2 penalty, made scale-free): near
    # convergence gradients are noise-dominated and alignment alone stops
    # discriminating — but a scaled attack still stands out by norm, so
    # rows farther than c_norm x the delivered rows' median norm are
    # rejected regardless of their cosine
    c_norm = spec.hp("c_norm", 2.5)
    s_norm = jnp.sort(jnp.where(mask, g_norm, jnp.inf))
    med_norm = 0.5 * (s_norm[lo_i] + s_norm[hi_i])
    sane = g_norm <= c_norm * med_norm
    # accept: delay-adaptive alignment with the instantaneous robust
    # reference, OR strong alignment (>= xi, the strictest threshold) with
    # the historically-honest EMA — the rescue path for stale rows whose
    # instantaneous alignment has rotated away
    rescue = (v_sq >= eps) & (cos_v >= xi)
    w = jnp.where(((cos_ref >= thresh) | rescue) & sane & mask,
                  base_w, 0.0)
    tot = jnp.sum(w)
    # fallback: discounted mean of the norm-sane delivered rows
    w_sane = jnp.where(sane & mask, base_w, 0.0)
    t_sane = jnp.sum(w_sane)
    fallback = jnp.where(t_sane > eps, w_sane / jnp.maximum(t_sane, eps),
                         base_w / base_tot)
    return jnp.where(tot > eps, w / jnp.maximum(tot, eps), fallback)


@register_aggregator(
    "zeno_pp",
    caps=AggregatorCaps(weight_decomposable=True, sharding_aware=True,
                        masked_capable=True, stateful=True),
    hyper=("xi", "ema", "eps", "c_norm"), state_keys=("server_grad",),
    weights_fn=lambda spec, grads, state: _zeno_pp_weights(
        spec, grads, None, None, state),
    init_state=_zeno_pp_init_state, update_state=_zeno_pp_update_state)
def zeno_pp(spec, grads, mask, weights, state):
    """Delay-adaptive Zeno++-style score filter.

    The PRIMARY acceptance test scores every delivered gradient against
    the coordinate-wise median of the delivered rows (a reference that is
    robust at every step, including step 0):

        accept_i  iff  cos(g_i, median) >= xi * (1 - w_i)

    where w_i in (0, 1] is the caller's staleness discount (1 = fresh):
    fresh gradients only need to be non-adversarial (threshold ~0), while
    very stale ones must align strongly with the current consensus
    direction — the Zeno++/Kardam insight that staleness and Byzantine
    corruption are the same hazard and the acceptance test must tighten
    with delay.

    The server additionally keeps a descent-direction estimate v (an EMA
    of its own past aggregates — the asynchronous analogue of Zeno's
    validation gradient) as a RESCUE path only: a row rejected by the
    instantaneous median test is still accepted if it aligns strongly
    (cos >= xi, the strictest threshold) with v.  The EMA is never the
    sole gatekeeper — it lags the true descent direction, and anything
    reaching the aggregate feeds back into it (self-poisoning).

    A norm-sanity gate (rows with ||g_i|| > c_norm x the delivered median
    norm are rejected regardless of cosine — Zeno's rho||g||^2 penalty
    made scale-free) covers the near-convergence regime where alignment
    stops discriminating.  Accepted gradients are averaged with their
    discounts; if nothing passes, the rule falls back to the discounted
    mean of the norm-sane rows (a pure-staleness step, never a frozen
    server)."""
    wn = _zeno_pp_weights(spec, grads, mask, weights, state)
    return tree_weighted_sum(grads, wn)


# ---------------------------------------------------------------------------
# defenses with memory (Karimireddy et al. line): iterative clipping around
# the carried server estimate, and the server-momentum composition wrapper.
# Both live on the same init_state/update_state protocol as zeno/zeno_pp —
# elastic respecialization and conformance coverage come free from the
# registry.


def _cclip_center(state, grads):
    """``state["server_grad"]`` shaped like one row of ``grads``: when the
    caller works on a bare (n, d)/(n, P) stack (conformance probes, the
    flat arena) but the carried center is a pytree, ravel it once (the
    ``_zeno_gather_state`` pattern)."""
    v = state["server_grad"]
    if hasattr(grads, "ndim"):
        leaves = jax.tree.leaves(v)
        if len(leaves) == 1 and leaves[0].ndim == grads.ndim - 1:
            return leaves[0].astype(jnp.float32)
        return tree_stack_ravel(jax.tree.map(
            lambda l: l.astype(jnp.float32)[None], v))[0]
    return jax.tree.map(lambda c: c.astype(jnp.float32), v)


def _cclip_iterate(spec, grads, mask, weights, state):
    """The centered-clipping fixed point on a gradient pytree (or bare
    stack): ``iters`` rounds of

        v <- v + sum_i w_i min(1, tau/||g_i - v||) (g_i - v) / sum_i w_i

    starting from the CARRIED center.  Absent rows are where-gated to an
    exact 0 before the norm (departed-content invariance: inf/NaN garbage
    in a dead row cannot reach the distance, the clip or the sum).
    Returns ``(v_final fp32 tree, lam_last (n,))`` — lam_last are the
    final iteration's effective clip weights, the telemetry signal."""
    n = _n_agents(grads)
    tau = spec.hp("tau", 1.0)
    iters = spec.hp("iters", 5)
    m = jnp.ones((n,), bool) if mask is None else mask.astype(bool)
    m, w, _, tot = _masked_prelude(grads, m, weights)
    wn = w / tot
    v0 = _cclip_center(state, grads)

    def lam_of(v):
        diff = jax.tree.map(
            lambda l, c: jnp.where(
                m.reshape((-1,) + (1,) * (l.ndim - 1)),
                l.astype(jnp.float32) - c.astype(jnp.float32)[None], 0.0),
            grads, v)
        dist = jnp.sqrt(jnp.maximum(tree_sqnorms(diff), 1e-30))
        return wn * jnp.minimum(1.0, tau / dist), diff

    def body(_, v):
        lam, diff = lam_of(v)
        return jax.tree.map(
            lambda vv, dd: vv + jnp.sum(
                dd * lam.reshape((-1,) + (1,) * (dd.ndim - 1)), axis=0),
            v, diff)

    v = jax.lax.fori_loop(0, iters, body, v0)
    lam, _ = lam_of(v)
    return v, lam


def _cclip_flat(spec, stack, mask, weights, state, qscale=None):
    """centered_clip on the (n, P) arena.  The per-iteration clip radius
    needs full-row norms (a cross-tile reduction), so the scalar stage is
    jnp; the model-sized multiply-accumulate rides the fused
    clipped-weighted-sum kernel (repro.kernels.wsum) under
    ``impl="pallas"``."""
    n, P = stack.shape
    if qscale is not None:
        from repro.core.flat import dequantize_rows
        xf = dequantize_rows(stack, qscale)
    else:
        xf = _flat_f32(stack)
    m = jnp.ones((n,), bool) if mask is None else mask.astype(bool)
    m, w, _, tot = _masked_prelude(stack, m, weights)
    wn = w / tot
    tau = spec.hp("tau", 1.0)
    iters = spec.hp("iters", 5)
    v0 = _cclip_center(state, xf)

    def lam_of(v):
        diff = jnp.where(m[:, None], xf - v[None], 0.0)
        dist = jnp.sqrt(jnp.maximum(jnp.sum(diff * diff, axis=1), 1e-30))
        return wn * jnp.minimum(1.0, tau / dist), diff

    use_kernel = spec.impl == "pallas"
    if use_kernel:
        from repro.kernels import clipped_weighted_sum
        from repro.kernels.tiling import TILE_D
        use_kernel = P % TILE_D == 0

    def body(_, v):
        lam, diff = lam_of(v)
        if use_kernel:
            return clipped_weighted_sum(lam, xf, v)
        return v + jnp.sum(diff * lam[:, None], axis=0)

    return jax.lax.fori_loop(0, iters, body, v0)


def _cclip_init_state(spec, proto):
    return _server_grad_zeros(proto)


def _cclip_update_state(spec, state, agg):
    # ema=1 (default): the center IS the last aggregate — Karimireddy et
    # al.'s v_{t} = agg_t; smaller ema trails it
    return _server_grad_ema(state, agg, spec.hp("ema", 1.0))


@register_aggregator(
    "centered_clip",
    caps=AggregatorCaps(iterative=True, sharding_aware=True,
                        masked_capable=True, stateful=True),
    hyper=("tau", "iters", "ema"), state_keys=("server_grad",),
    flat_fn=_cclip_flat,
    init_state=_cclip_init_state, update_state=_cclip_update_state,
    tags=("memory",))
def centered_clip(spec, grads, mask, weights, state):
    """Centered clipping (Karimireddy et al.): iteratively re-clip every
    row around the CARRIED server estimate v (an EMA of past aggregates),
    so a perturbation small enough to pass one round still cannot bias
    the aggregate by more than tau per step — the history-aware answer to
    ALIE/IPM-style inside-the-spread attacks.  The clip saturates: beyond
    f the adversary gains rows, never magnitude.  Masked rows contribute
    exact zeros (own masked law — no mean imputation: an imputed row
    would drag v toward the attacker-controlled delivered mean)."""
    v, _ = _cclip_iterate(spec, grads, mask, weights, state)
    return v


# ---------------------------------------------------------------------------
# compressed robust exchange: sparse/dropout per-coordinate weighting.  A
# zero coordinate means NOT SENT (the fed_dropout_avg convention), so the
# aggregate averages each coordinate over (coord_sent) * weight — per-
# coordinate weights, which the impute-then-scale masked law cannot
# express; hence custom_fn (tree) + flat_fn (arena) instead of the
# generic engine paths.


def _sparse_row_weights(n, mask, weights):
    """(n,) fp32 row weights with the mask folded in (dead rows -> 0)."""
    m = (jnp.ones((n,), bool) if mask is None
         else mask.astype(bool)).astype(jnp.float32)
    return m if weights is None else weights.astype(jnp.float32) * m


def _sparse_mean_flat(spec, stack, mask, weights, state, qscale=None):
    """sparse_mean on the (n, P) arena.  ``impl="pallas"`` runs the
    sparse kernel (repro.kernels.wsum) with sent-detection on the native
    codes and, for quantized arenas, in-tile dequantization — no
    dequantized (n, P) copy; other impls apply the jnp law."""
    n = stack.shape[0]
    w = _sparse_row_weights(n, mask, weights)
    if spec.impl == "pallas":
        from repro.kernels import (pallas_masked_aggregate,
                                   pallas_scaled_masked_aggregate)
        m = (jnp.ones((n,), jnp.float32) if mask is None
             else mask.astype(jnp.float32))
        if qscale is not None:
            return pallas_scaled_masked_aggregate(
                "sparse_mean", stack, qscale, m, w, spec.f, spec.hyper)
        return pallas_masked_aggregate(
            "sparse_mean", stack, m, w, spec.f, spec.hyper)
    if qscale is not None:
        from repro.core.flat import dequantize_rows
        xf = dequantize_rows(stack, qscale)
    else:
        xf = _flat_f32(stack)
    return sparse_mean_law(xf, w)


@register_aggregator(
    "sparse_mean",
    caps=AggregatorCaps(coordwise=True, sharding_aware=True),
    flat_fn=_sparse_mean_flat, tags=("compressed",))
def sparse_mean(spec, grads, mask, weights, state):
    """Sparse/dropout-aware weighted mean (tree path; see
    :func:`repro.core.filters.dense.sparse_mean` for the unit-weight
    dense oracle).  Per-coordinate weights are ``(coord_sent) * w_i``
    with ``w_i`` the caller's per-agent weight (dataset size, staleness
    discount) zeroed on masked-out rows; coordinates nobody sent yield
    an explicit zero update."""
    n = _n_agents(grads)
    w = _sparse_row_weights(n, mask, weights)
    if spec.impl == "pallas":
        # the law is per-coordinate, so the tree splits EXACTLY into
        # per-dtype (n, -1) segments riding the fused sparse kernel —
        # sent-detection and weighting stay inside the tile (no (n, d)
        # where/select materialized; jaxpr-gated by the parity suite)
        from repro.kernels import pallas_masked_aggregate
        m = (jnp.ones((n,), jnp.float32) if mask is None
             else mask.astype(jnp.float32))
        flat_leaves, treedef = jax.tree.flatten(grads)
        by_dtype = {}
        for i, l in enumerate(flat_leaves):
            by_dtype.setdefault(jnp.dtype(l.dtype), []).append(i)
        outs = [None] * len(flat_leaves)
        for dt, idxs in by_dtype.items():
            seg = jnp.concatenate(
                [flat_leaves[i].reshape(n, -1) for i in idxs], axis=1)
            vec = pallas_masked_aggregate("sparse_mean", seg, m, w,
                                          spec.f, spec.hyper)
            off = 0
            for i in idxs:
                sz = flat_leaves[i][0].size
                outs[i] = (vec[off:off + sz].astype(dt)
                           .reshape(flat_leaves[i].shape[1:]))
                off += sz
        return jax.tree.unflatten(treedef, outs)

    def leaf(l):
        xf = l.reshape(n, -1).astype(jnp.float32)
        return sparse_mean_law(xf, w).astype(l.dtype).reshape(l.shape[1:])
    return jax.tree.map(leaf, grads)


# ---------------------------------------------------------------------------
# composition wrappers — specs that transform, then delegate to spec.inner


def _clip_fn(spec, grads, mask, weights, state):
    """Pre-aggregation norm clipping (static-radius centered clipping):
    every row is scaled to ||g_i|| <= tau before the inner rule runs."""
    tau = spec.hp("tau", 1.0)
    norms = jnp.sqrt(jnp.maximum(tree_sqnorms(grads), 1e-30))
    scale = jnp.minimum(1.0, tau / norms)
    clipped_g = jax.tree.map(
        lambda l: (l.astype(jnp.float32)
                   * scale.reshape((-1,) + (1,) * (l.ndim - 1))
                   ).astype(l.dtype), grads)
    return spec.inner.aggregate(clipped_g, mask=mask, weights=weights,
                                state=_inner_state(spec, state))


def _bucket_fn(spec, grads, mask, weights, state):
    """Pre-aggregation bucketing (median-of-means stage 1): group-mean the
    rows in consecutive buckets of ``group_size`` before the inner rule —
    synchronous delivery only (bucket membership is static)."""
    if mask is not None or weights is not None:
        raise ValueError("bucketed: masked aggregation not supported "
                         "(bucket membership is static)")
    gs = spec.hp("group_size", 2)
    n = _n_agents(grads)
    if n % gs:
        raise ValueError(f"bucketed: n={n} not divisible by "
                         f"group_size={gs}")
    k = n // gs

    def leaf(l):
        return jnp.mean(
            l.astype(jnp.float32).reshape((k, gs) + l.shape[1:]),
            axis=1).astype(l.dtype)
    means = jax.tree.map(leaf, grads)
    f_eff = min(spec.inner.f, max((k - 1) // 2, 0))
    return spec.inner.with_f(f_eff).aggregate(
        means, state=_inner_state(spec, state))


def staleness_discount_table(s, weighting: str = "poly",
                             power: float = 1.0, gamma: float = 0.7):
    """Staleness rounds -> discount multipliers (Kardam/Zeno++ line):
    ``none`` -> 1, ``poly`` -> (1+s)^-power, ``exp`` -> gamma^s.  Plain
    operators, so it works on NumPy float64 (host-side trace planning)
    and jnp float32 (in-trace) alike — THE one copy of the table."""
    if weighting == "none":
        return s * 0.0 + 1.0
    if weighting == "poly":
        return (1.0 + s) ** (-power)
    if weighting == "exp":
        return gamma ** s
    raise KeyError(weighting)


def _staleness_fn(spec, grads, mask, weights, state):
    """Staleness discounting as a spec: ``weights`` here are raw staleness
    ROUND COUNTS s_i >= 0 (not multipliers); the wrapper converts them to
    the Kardam/Zeno++-line discounts and delegates."""
    s = (jnp.zeros((_n_agents(grads),), jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    w = staleness_discount_table(s, spec.hp("weighting", "poly"),
                                 spec.hp("power", 1.0),
                                 spec.hp("gamma", 0.7))
    return spec.inner.aggregate(grads, mask=mask, weights=w,
                                state=_inner_state(spec, state))


def _inner_state(spec, state):
    if spec.inner is not None and spec.inner.stateful:
        return (state or {}).get("inner")
    return None


def _server_momentum_fn(spec, grads, mask, weights, state):
    """Server momentum as a composition wrapper (the survey's history
    filter): the emitted update is an EMA of the inner rule's aggregates,

        out_t = beta * m_{t-1} + (1 - beta) * inner(g_t),   m_t = out_t

    so a single poisoned round moves the served direction by at most
    (1 - beta) of the inner rule's error, and round-to-round sign flips
    (the classic way adaptive attacks whipsaw one-shot rules) average
    out.  Wraps ANY registered rule; state nests the inner rule's own
    memory under ``state["inner"]`` like every other wrapper."""
    beta = spec.hp("beta", 0.9)
    inner = spec.inner.aggregate(grads, mask=mask, weights=weights,
                                 state=_inner_state(spec, state))
    m = state["server_grad"]
    return jax.tree.map(
        lambda mm, a: (beta * mm.astype(jnp.float32)
                       + (1.0 - beta) * a.astype(jnp.float32)), m, inner)


def _server_momentum_init(spec, proto):
    return _server_grad_zeros(proto)


def _server_momentum_update(spec, state, agg):
    # the momentum buffer IS the emitted update (out_t above)
    return _server_grad_ema(state, agg, 1.0)


register_aggregator(
    "clipped",
    caps=AggregatorCaps(masked_capable=True, sharding_aware=True),
    hyper=("tau",), is_wrapper=True)(_clip_fn)
register_aggregator(
    "bucketed",
    caps=AggregatorCaps(masked_capable=False, sharding_aware=True),
    hyper=("group_size",), is_wrapper=True)(_bucket_fn)
register_aggregator(
    "staleness_discounted",
    caps=AggregatorCaps(masked_capable=True, sharding_aware=True,
                        staleness_aware=True),
    hyper=("weighting", "power", "gamma"), is_wrapper=True)(_staleness_fn)
register_aggregator(
    "server_momentum",
    caps=AggregatorCaps(masked_capable=True, sharding_aware=True,
                        stateful=True),
    hyper=("beta",), state_keys=("server_grad",),
    init_state=_server_momentum_init,
    update_state=_server_momentum_update,
    is_wrapper=True)(_server_momentum_fn)


def clipped(inner: AggregatorSpec, tau: float = 1.0) -> AggregatorSpec:
    return make_spec("clipped", f=inner.f, inner=inner, tau=tau)


def bucketed(inner: AggregatorSpec, group_size: int = 2) -> AggregatorSpec:
    return make_spec("bucketed", f=inner.f, inner=inner,
                     group_size=group_size)


def staleness_discounted(inner: AggregatorSpec, weighting: str = "poly",
                         power: float = 1.0,
                         gamma: float = 0.7) -> AggregatorSpec:
    return make_spec("staleness_discounted", f=inner.f, inner=inner,
                     weighting=weighting, power=power, gamma=gamma)


def server_momentum(inner: AggregatorSpec,
                    beta: float = 0.9) -> AggregatorSpec:
    return make_spec("server_momentum", f=inner.f, inner=inner, beta=beta)


__all__ = [
    "AggregatorCaps", "AggregatorDef", "AggregatorSpec",
    "AggregatorDeprecationWarning", "REGISTRY", "register_aggregator",
    "get_aggregator_def", "list_aggregators", "make_spec", "warn_once",
    "pallas_available", "ElasticN", "FlatPlan", "FracF", "elastic", "frac",
    "clipped", "bucketed", "staleness_discounted", "server_momentum",
    "tree_stack_ravel", "tree_unravel_like", "tree_sqnorms", "tree_gram",
    "tree_dot", "tree_weighted_sum", "tree_where_agents",
    "tree_geometric_median", "tree_median_of_means", "tree_bulyan",
    "mda_combos", "trim_count",
]
