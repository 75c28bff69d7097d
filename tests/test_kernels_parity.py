"""Kernel-dispatch parity suite: ``impl="pallas"`` vs the gather-path
reference, for every kernelized rule x {plain, masked, weighted}.

The exactness bar comes from the survey's resilience story (CGE's provable
(f, eps) guarantees, the approximate-BFT line): a kernel that silently
disagrees with the reference rule voids the guarantee, so agreement is
asserted BIT-FOR-BIT for fp32 wherever the two paths share the reference
arithmetic:

  * coordinate_median / trimmed_mean — identical order statistics (the
    kernels pin the reduce order to the reference's, see coord_stats);
  * krum — one-hot application returns exactly the selected row's bits;
  * multi_krum / m_krum / mda / bulyan — selection-ORDER-preserving
    application (kernels/wsum.ordered_apply): the picked rows are summed
    in exactly the dense reference's order with the reduce and the
    divisor compilation pinned (optimization_barrier), so the multi-row
    averages are bit-for-bit too, across plain AND the imputation-free
    masked/weighted paths;
  * cge — the SELECTION is asserted bit-for-bit; the eager dense
    reference's gather+reduce fuses non-deterministically across XLA
    program boundaries, so the averaged output is asserted to ulp-level
    tolerance (the selected SET is what the (f, eps) guarantee depends
    on).

bfloat16 stacks are asserted to bf16-resolution tolerance.  Fuzzing is
seeded ``jax.random`` grids (no ``hypothesis`` here — not installed; the
importorskip pattern is reserved for optional deps) over odd/even n and
tile-aligned / non-multiple-of-block d, plus fault-schedule-driven quorum
masks from the async simulator and retrace counters proving fixed-shape
masks never recompile the kernel path and the flat-arena loops add ZERO
compiles over the per-leaf loops under churn + fault schedules.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregators import FlatPlan, make_spec, pallas_available
from repro.kernels import ref
from repro.kernels.coord_stats import coord_stat
from repro.kernels.masked import masked_coord_stat
from repro.kernels.ops import _pad_d
from repro.kernels.pairwise import gram
from repro.kernels.select import cge_select, krum_select

RULES = ["coordinate_median", "trimmed_mean", "krum", "cge",
         "multi_krum", "m_krum", "mda", "bulyan", "sign_sgd", "sparse_mean"]
# non-power-of-2 selection counts so the division-compilation pinning is
# exercised (a power-of-2 divisor would hide a reciprocal-multiply drift)
HYPER = {"multi_krum": {"m": 3}, "m_krum": {"m": 3}}
NS = [9, 12]                       # odd / even agent counts
DS = [512, 771]                    # exact tile / non-multiple-of-block
DTYPES = [jnp.float32, jnp.bfloat16]
MODES = ["plain", "masked", "weighted"]
SEEDS = [0, 1]
F = 2

# rules whose pallas OUTPUT is bit-for-bit with the gather path in fp32
# (cge: selection bitwise, application within ulp — see module docstring)
BITWISE_RULES = {"coordinate_median", "trimmed_mean", "krum",
                 "multi_krum", "m_krum", "mda", "bulyan",
                 "sign_sgd", "sparse_mean"}


def spec_pair(rule, n):
    """(pallas, gather) spec twins for one fuzz case."""
    hyper = HYPER.get(rule, {})
    return (make_spec(rule, f=F, impl="pallas", n=n, **hyper),
            make_spec(rule, f=F, impl="gather", n=n, **hyper))


def data(n, d, dtype, seed):
    g = jax.random.normal(jax.random.PRNGKey(seed), (n, d)) * 2.0
    return g.astype(dtype)


def mode_args(mode, n, seed):
    """(mask, weights) for one fuzz case; masks always keep >= n - F rows."""
    if mode == "plain":
        return None, None
    k1, k2 = jax.random.split(jax.random.PRNGKey(100 + seed))
    drop = jax.random.choice(k1, n, shape=(F,), replace=False)
    mask = jnp.ones((n,), bool).at[drop].set(False)
    if mode == "masked":
        return mask, None
    w = jax.random.uniform(k2, (n,), minval=0.3, maxval=1.0)
    return mask, w


def assert_agree(out, ref_out, dtype, rule):
    a, b = np.asarray(out), np.asarray(ref_out)
    assert a.dtype == b.dtype
    if dtype == jnp.float32 and rule in BITWISE_RULES:
        np.testing.assert_array_equal(a, b)
    elif dtype == jnp.float32:
        np.testing.assert_allclose(a, b, rtol=3e-6, atol=3e-6)
    else:                                      # bf16 resolution
        np.testing.assert_allclose(a.astype(np.float32),
                                   b.astype(np.float32),
                                   rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# 1. spec-level parity: impl="pallas" vs impl="gather", all modes


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("d", DS)
@pytest.mark.parametrize("rule", RULES)
def test_pallas_matches_gather_spec(rule, n, d, dtype, mode):
    pa, ga = spec_pair(rule, n)
    for seed in SEEDS:
        g = data(n, d, dtype, seed)
        mask, w = mode_args(mode, n, seed)
        out = pa.aggregate(g, mask=mask, weights=w)
        expect = ga.aggregate(g, mask=mask, weights=w)
        assert_agree(out, expect, dtype, rule)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("rule", ["coordinate_median", "trimmed_mean"])
def test_pallas_matches_gather_on_pytrees(rule, mode):
    """The fused masked kernel path also runs on raveled pytrees (the
    training loops' actual gradient structure)."""
    n = 10
    grads = {"a": data(n, 5 * 7, jnp.float32, 3).reshape(n, 5, 7),
             "b": {"c": data(n, 11, jnp.float32, 4)}}
    mask, w = mode_args(mode, n, 0)
    out = make_spec(rule, f=F, impl="pallas").aggregate(
        grads, mask=mask, weights=w)
    expect = make_spec(rule, f=F, impl="gather").aggregate(
        grads, mask=mask, weights=w)
    for x, y in zip(jax.tree.leaves(out), jax.tree.leaves(expect)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_masked_semantics_of_the_new_default_are_pinned():
    """The default impl moved from "fused" to "auto" (-> pallas for the
    kernelized rules).  Pallas follows the GATHER masked semantics
    (impute-then-scale); for coordinate-wise rules fused is numerically
    the same path, but for weight-decomposable rules (krum, cge) fused
    folds the weights into the selection instead — so default-built
    krum/cge specs CHANGED masked behavior with this PR.  This test makes
    that switch loud: default == pallas == gather, and fused remains the
    intentionally different historical estimator reachable via
    impl="fused" (ByzantineConfig's default)."""
    n = 10
    g = data(n, 640, jnp.float32, 21)
    mask, w = mode_args("weighted", n, 4)
    for rule in ("krum", "cge"):
        default = make_spec(rule, f=F, n=n)
        assert default.impl == "pallas"
        out_d = default.aggregate(g, mask=mask, weights=w)
        out_g = make_spec(rule, f=F, impl="gather", n=n).aggregate(
            g, mask=mask, weights=w)
        out_f = make_spec(rule, f=F, impl="fused", n=n).aggregate(
            g, mask=mask, weights=w)
        assert_agree(out_d, out_g, jnp.float32, rule)
        assert float(jnp.max(jnp.abs(out_d - out_f))) > 1e-3, (
            f"{rule}: fused masked semantics unexpectedly collapsed into "
            "the gather/pallas semantics — update the make_spec docstring")
    # coordinate-wise rules: all three impls agree bit-for-bit
    for rule in ("coordinate_median", "trimmed_mean"):
        outs = [make_spec(rule, f=F, impl=i, n=n).aggregate(
            g, mask=mask, weights=w) for i in ("pallas", "gather", "fused")]
        np.testing.assert_array_equal(np.asarray(outs[0]),
                                      np.asarray(outs[1]), err_msg=rule)
        np.testing.assert_array_equal(np.asarray(outs[0]),
                                      np.asarray(outs[2]), err_msg=rule)


def test_cge_selection_is_bitwise():
    """What the (f, eps) guarantee rests on: the kernel eliminates exactly
    the rows the dense reference eliminates."""
    for n, d, seed in [(9, 512, 0), (12, 771, 1), (16, 1300, 2)]:
        g = data(n, d, jnp.float32, seed)
        gp, _ = _pad_d(g)
        w_kernel = cge_select(gram(gp), n - F)
        w_ref = ref.cge_select_ref(g, n - F)
        np.testing.assert_array_equal(np.asarray(w_kernel),
                                      np.asarray(w_ref), err_msg=str((n, d)))


def test_selection_survives_nonfinite_adversary():
    """An inf-coordinate gradient (the unbounded Byzantine row this
    library exists to defend against) turns its d2 row NaN; NaN compares
    False against everything, so a naive comparison-rank would hand EVERY
    NaN row rank 0 and silently average multiple rows.  The kernels must
    keep the selection cardinality exact and pick only finite rows."""
    n, d, f = 8, 512, 2
    g = data(n, d, jnp.float32, 12)
    g = g.at[1, 7].set(jnp.inf).at[5, 3].set(-jnp.inf)   # 2 hostile rows
    gp, _ = _pad_d(g)
    gr = gram(gp)
    w_krum = np.asarray(krum_select(gr, f))
    assert w_krum.sum() == 1.0 and set(np.unique(w_krum)) <= {0.0, 1.0}
    assert w_krum[1] == 0.0 and w_krum[5] == 0.0         # finite row wins
    w_cge = np.asarray(cge_select(gr, n - f))
    assert w_cge.sum() == n - f and set(np.unique(w_cge)) <= {0.0, 1.0}
    assert w_cge[1] == 0.0 and w_cge[5] == 0.0           # inf norms dropped
    # and through the spec engine: the aggregate stays finite
    for rule in ("krum", "cge"):
        out = make_spec(rule, f=f, impl="pallas", n=n).aggregate(g)
        assert bool(jnp.all(jnp.isfinite(out))), rule


def test_krum_selection_is_bitwise():
    for n, d, seed in [(9, 512, 0), (12, 771, 1), (16, 1300, 2)]:
        g = data(n, d, jnp.float32, seed)
        gp, _ = _pad_d(g)
        w_kernel = krum_select(gram(gp), F)
        w_ref = ref.krum_select_ref(g, F)
        np.testing.assert_array_equal(np.asarray(w_kernel),
                                      np.asarray(w_ref), err_msg=str((n, d)))


def test_selection_family_survives_nonfinite_adversary():
    """The selection family under inf-coordinate hostile rows: NaN
    distances (inf - inf) order LAST at the d2 level, candidate-
    constrained tie-breaks can never re-pick a removed row, and the
    one-hot applications where-zero rejected rows — so the kernels stay
    finite even where the DENSE references break (multi_krum's one score
    pass and mda's argmin both degrade to index/enumeration order once
    NaN poisons every comparison), which is why this is asserted against
    the defense contract, not against gather."""
    from repro.kernels.select import iterative_order, multi_krum_order
    n, d, f = 8, 512, 2
    g = data(n, d, jnp.float32, 12)
    g = g.at[1, 7].set(jnp.inf).at[5, 3].set(-jnp.inf)   # 2 hostile rows
    mask, w = mode_args("weighted", n, 3)
    for rule, hyper in [("multi_krum", {"m": 3}), ("m_krum", {"m": 3}),
                        ("bulyan", {}), ("mda", {})]:
        spec = make_spec(rule, f=f, impl="pallas", n=n, **hyper)
        out = spec.aggregate(g)
        assert bool(jnp.all(jnp.isfinite(out))), rule
    # masked/weighted: the imputed ghost row inherits the (poisoned)
    # delivered mean, so only the selection rules that keep < n - f rows
    # can still dodge every hostile row (mda must keep n - f and cannot)
    for rule, hyper in [("multi_krum", {"m": 3}), ("m_krum", {"m": 3}),
                        ("bulyan", {})]:
        spec = make_spec(rule, f=f, impl="pallas", n=n, **hyper)
        out = spec.aggregate(g, mask=mask, weights=w)
        assert bool(jnp.all(jnp.isfinite(out))), rule
    gp, _ = _pad_d(g)
    gr = gram(gp)
    for m in (2, 3):
        order = np.asarray(multi_krum_order(gr, f, m))
        assert sorted(order[order < n]) == list(range(m))
        order = np.asarray(iterative_order(gr, f, m))
        assert sorted(order[order < n]) == list(range(m))


# ---------------------------------------------------------------------------
# 2. raw-kernel parity vs the pure-jnp oracles in kernels/ref.py


@pytest.mark.parametrize("d", [1024, 3000])       # 3000 % 128 != 0
@pytest.mark.parametrize("n", [8, 9, 12, 16])
@pytest.mark.parametrize("stat,b", [("median", 0), ("trimmed_mean", 2)])
def test_coord_stat_matches_oracle(n, d, stat, b):
    """Both tilings of coord_stat: lane-dense where n % 8 == 0 and
    d % 128 == 0, (n, TILE_D) blocks otherwise; the counter names the
    path that traced."""
    from repro.obs import counters
    g = data(n, d, jnp.float32, 5)
    path = "lane_dense" if n % 8 == 0 and d % 128 == 0 else "tiled"
    coord_stat.clear_cache()             # trace afresh: the counter moves
    before = counters.snapshot()
    out = coord_stat(g, stat, b=b)
    moved = counters.counter_delta(before)
    assert moved.get(f"kernels.coord_stat.{path}") == 1, moved
    expect = (ref.median_from_sorted if stat == "median"
              else lambda s: ref.trimmed_mean_from_sorted(s, b))(
                  jnp.sort(g, axis=0))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("rows,strip", [(16, 16), (24, 16), (40, 8)])
@pytest.mark.parametrize("n", [8, 16])
@pytest.mark.parametrize("stat,b", [("median", 0), ("trimmed_mean", 2)])
def test_coord_stat_lane_dense_blocks_match_oracle(n, rows, strip, stat, b):
    """The chip's lane-dense block arithmetic under the interpreter: a
    grid of several blocks with a ragged last one (40 rows of 128 lanes
    per agent), a tail strip (24 = 16 + 8) and a multi-strip loop."""
    from repro.kernels.coord_stats import _coord_stat_lane_dense
    g = data(n, 40 * 128, jnp.float32, 9)
    out = _coord_stat_lane_dense(g, stat, b, rows=rows, strip=strip,
                                 interpret=True)
    expect = (ref.median_from_sorted if stat == "median"
              else lambda s: ref.trimmed_mean_from_sorted(s, b))(
                  jnp.sort(g, axis=0))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("stat,b", [("median", 0), ("trimmed_mean", 2)])
def test_masked_coord_stat_matches_oracle(n, stat, b, dtype):
    g = data(n, 1024, dtype, 6)
    mask, _ = mode_args("masked", n, 7)
    w = jax.random.uniform(jax.random.PRNGKey(8), (n,), minval=0.2,
                           maxval=1.0) * mask
    wn = w / jnp.sum(w)
    out = masked_coord_stat(g, mask.astype(jnp.float32), wn, stat, b=b)
    expect = ref.masked_stat_ref(g, mask, wn, stat, b=b)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


# ---------------------------------------------------------------------------
# 3. caps-driven auto-selection (the acceptance criterion)


def test_make_spec_auto_selects_pallas():
    for rule in RULES:
        assert pallas_available(rule), rule
        assert make_spec(rule, n=12, f=F).impl == "pallas", rule
    # non-kernelized rules keep the fused default ...
    for rule in ("mean", "geometric_median", "rfa", "median_of_means",
                 "zeno", "zeno_pp", "cgc", "phocas", "mean_around_median"):
        assert make_spec(rule, f=1).impl == "fused", rule
    # ... wrappers never kernelize themselves (the inner spec does)
    from repro.core.aggregators import clipped
    spec = clipped(make_spec("trimmed_mean", f=F), tau=1.0)
    assert spec.impl == "fused" and spec.inner.impl == "pallas"


def test_bulyan_pallas_gated_on_krum_base():
    """Only bulyan's classic krum base is Gram-derivable: impl="auto"
    silently keeps fused for other bases, explicit pallas raises at BUILD
    time (not inside jit)."""
    assert make_spec("bulyan", f=1).impl == "pallas"
    assert make_spec("bulyan", f=1, base="krum").impl == "pallas"
    assert make_spec("bulyan", f=1, base="mean").impl == "fused"
    with pytest.raises(ValueError, match="non-kernelized"):
        make_spec("bulyan", f=1, impl="pallas", base="mean")


def test_impl_override_and_validation():
    assert make_spec("trimmed_mean", f=F, impl="fused").impl == "fused"
    assert make_spec("trimmed_mean", f=F, impl="gather").impl == "gather"
    spec = make_spec("trimmed_mean", f=F).with_impl("gather")
    assert spec.impl == "gather"
    assert spec.with_impl("auto").impl == "pallas"
    with pytest.raises(ValueError, match="pallas"):
        make_spec("geometric_median", f=F, impl="pallas")
    with pytest.raises(ValueError, match="impl must be"):
        make_spec("trimmed_mean", f=F, impl="vectorized")


# ---------------------------------------------------------------------------
# 4. async-loop fault masks: parity along a simulated fault trace, and
#    fixed shapes => the jitted kernel path never retraces


def _fault_trace_weights(n, steps):
    from repro.simulator.async_loop import (SimConfig, plan_arrivals,
                                            staleness_weights)
    from repro.simulator.faults import CrashRecover, MessageDrop, Straggler
    sim = SimConfig(faults=(Straggler(dist="lognormal", scale=0.6),
                            CrashRecover(rate=0.15, mean_down=2.0),
                            MessageDrop(p=0.15)),
                    quorum=max(2, n - 3), max_staleness=3, seed=11)
    atrace = plan_arrivals(sim, n, steps)
    return atrace, staleness_weights(sim, atrace)


@pytest.mark.parametrize("rule", RULES)
def test_parity_under_async_fault_masks(rule):
    """Every step of a chaos trace (stragglers + crash/recover + message
    drops): the kernel path agrees with the gather path on exactly the
    quorum masks and staleness discounts the async loop would feed it."""
    n, d, steps = 8, 640, 12
    pa = make_spec(rule, f=2, impl="pallas", n=n)
    ga = make_spec(rule, f=2, impl="gather", n=n)
    atrace, contrib_w = _fault_trace_weights(n, steps)
    g = data(n, d, jnp.float32, 9)
    for t in range(steps):
        mask = jnp.asarray(atrace.contrib[t])
        if not bool(mask.any()):
            continue
        w = jnp.asarray(contrib_w[t])
        out = pa.aggregate(g, mask=mask, weights=w)
        expect = ga.aggregate(g, mask=mask, weights=w)
        assert_agree(out, expect, jnp.float32, rule)


def test_async_loop_end_to_end_parity():
    """The tentpole, end to end: the async training loop under a fault
    schedule produces BIT-IDENTICAL parameters with impl="pallas" and
    impl="gather" aggregators — the kernel path is a drop-in for the
    reference inside the jitted step (threaded state, quorum masks,
    staleness weights and all)."""
    from repro.configs import get_config
    from repro.data import SyntheticLM
    from repro.optim import adamw, constant
    from repro.simulator.async_loop import SimConfig, async_train_loop
    from repro.simulator.faults import MessageDrop, Straggler
    from repro.training.step import ByzantineConfig

    cfg = get_config("paper-100m-smoke").replace(vocab_size=64,
                                                 dtype="float32")
    sim = SimConfig(faults=(Straggler(dist="lognormal", scale=0.7),
                            MessageDrop(p=0.15)),
                    quorum=6, max_staleness=3, seed=5)
    results = {}
    for impl in ("pallas", "gather"):
        ds = SyntheticLM(vocab_size=64, seq_len=8, n_agents=8,
                         per_agent_batch=1)
        bz = ByzantineConfig(
            n_agents=8, f=2, attack="sign_flip",
            aggregator=make_spec("trimmed_mean", f=2, impl=impl, n=8))
        # _force_general: every step runs the masked/weighted kernel path
        # (the path under test) and the sync fast path never compiles
        params, hist = async_train_loop(
            cfg, bz, adamw(constant(1e-3)), ds, steps=3, sim=sim,
            log_every=1, log_fn=lambda *_: None, _force_general=True)
        results[impl] = (params, hist)
    pa, ga = results["pallas"], results["gather"]
    for x, y in zip(jax.tree.leaves(pa[0]), jax.tree.leaves(ga[0])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert [m["loss"] for m in pa[1]] == [m["loss"] for m in ga[1]]


def test_fault_masks_do_not_retrace():
    """The masked kernels take the quorum mask and discounts as traced
    operands: 10 different fault-mask rows must reuse ONE compilation."""
    n, d = 8, 640
    spec = make_spec("trimmed_mean", f=2, impl="pallas", n=n)
    traces = []

    @jax.jit
    def step(g, mask, w):
        traces.append(1)                     # python side effect: tracing
        return spec.aggregate(g, mask=mask, weights=w)

    g = data(n, d, jnp.float32, 10)
    atrace, contrib_w = _fault_trace_weights(n, 10)
    for t in range(10):
        step(g, jnp.asarray(atrace.contrib[t]),
             jnp.asarray(contrib_w[t])).block_until_ready()
    assert len(traces) == 1, f"kernel path retraced {len(traces)} times"


# ---------------------------------------------------------------------------
# 5. the zero-copy flat pipeline: imputation-free masked kernels, the
#    flat-arena engine, and the compile-count gate on the real loops


def _collect_shapes(jaxpr, banned=("select_n", "broadcast_in_dim")):
    """Output shapes of every banned-primitive eqn OUTSIDE kernel bodies
    (recursion stops at pallas_call: the tile-level where IS the fusion —
    what must never exist is a full-size imputed copy feeding the
    kernel)."""
    import jax.extend.core as jcore
    hits = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                continue
            if eqn.primitive.name in banned:
                hits.extend(tuple(v.aval.shape) for v in eqn.outvars)
            for val in eqn.params.values():
                for sub in (val if isinstance(val, (list, tuple)) else
                            (val,)):
                    if isinstance(sub, jcore.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jcore.Jaxpr):
                        walk(sub)
    walk(jaxpr.jaxpr)
    return hits


@pytest.mark.parametrize("rule", ["krum", "cge", "multi_krum", "bulyan",
                                  "coordinate_median", "sign_sgd",
                                  "sparse_mean"])
def test_masked_pallas_is_imputation_free(rule):
    """The acceptance gate of the masked selection family: no full-size
    broadcast or where precedes the kernel call — the imputed (n, d)
    stack is never materialized.  The same detector run on the gather
    path DOES fire (it imputes at tree level), proving the check bites."""
    n, d = 8, 640
    g = data(n, d, jnp.float32, 4)
    mask, w = mode_args("weighted", n, 5)

    def big(spec):
        jaxpr = jax.make_jaxpr(
            lambda g, m, w: spec.aggregate(g, mask=m, weights=w))(g, mask, w)
        return [s for s in _collect_shapes(jaxpr)
                if len(s) == 2 and s[0] == n and s[1] >= d]

    pa = make_spec(rule, f=2, impl="pallas", n=n)
    assert not big(pa), f"{rule}: imputed (n, d) copy materialized: {big(pa)}"
    ga = make_spec(rule, f=2, impl="gather", n=n)
    if rule == "sign_sgd":
        # the arrived-only vote never imputes, even at gather level — its
        # engine fallback materializes the (n, d) masked vote product
        # instead, so the teeth check looks for that
        jaxpr = jax.make_jaxpr(
            lambda g, m, w: ga.aggregate(g, mask=m, weights=w))(g, mask, w)
        muls = [s for s in _collect_shapes(jaxpr, banned=("mul",))
                if len(s) == 2 and s[0] == n and s[1] >= d]
        assert muls, "detector lost its teeth: gather vote product not seen"
    else:
        assert big(ga), "detector lost its teeth: gather imputation not seen"


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("rule", RULES)
def test_aggregate_flat_matches_tree_engine(rule, mode, dtype):
    """spec.aggregate_flat on the pre-raveled arena == spec.aggregate on
    the tree, bit-for-bit, for both dense impls — the loops' flat
    pipeline cannot change a single bit of the n-static paths.  bf16
    covers the agg_dtype exchange trees of the async loop (the masked
    scale must round through the arena dtype exactly like the tree
    engine's per-leaf rounding)."""
    n, d = 9, 640
    g = data(n, d, dtype, 6)
    tree = {"a": g[:, :123].reshape(n, 3, 41), "b": {"c": g[:, 123:]}}
    mask, w = mode_args(mode, n, 7)
    for impl in ("pallas", "gather"):
        spec = make_spec(rule, f=F, impl=impl, n=n, **HYPER.get(rule, {}))
        assert spec.flat_capable
        expect = spec.aggregate(tree, mask=mask, weights=w)
        plan = FlatPlan.for_tree(tree)
        assert jnp.dtype(plan.uniform_dtype) == jnp.dtype(dtype)
        vec = spec.aggregate_flat(plan.ravel(tree), mask=mask, weights=w)
        got = plan.unravel(vec)
        for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(expect)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f"{rule}/{impl}")


def _mixed_tree(n, seed):
    """bf16 leaves beside f32 ones, interleaved in tree order (as
    Mamba-2's float32 A_log / dt_bias / D sit among bf16 matrices)."""
    g = data(n, 640, jnp.float32, seed)
    return {"a": g[:, :123].reshape(n, 3, 41).astype(jnp.bfloat16),
            "b": {"A_log": g[:, 123:130],
                  "c": g[:, 130:630].astype(jnp.bfloat16),
                  "d": g[:, 630:].reshape(n, 2, 5)}}


@pytest.mark.parametrize("mode", ["plain", "masked"])
@pytest.mark.parametrize("attack", ["none", "alie"])
@pytest.mark.parametrize("rule", RULES)
def test_segmented_arena_per_segment(rule, attack, mode):
    """A mixed-dtype tree's arena is one segment per dtype, each in its
    leaves' own dtype and no larger than they are.  A coordinate-wise
    rule gives, segment by segment and bit for bit, what today's uniform
    arena of that segment's leaves gives; a rule that couples a row's
    columns gives what the tree engine gives on the whole tree."""
    from repro.core.attacks import get_attack, make_byzantine_mask
    from repro.core.aggregators import get_aggregator_def
    from repro.training.step import tree_attack
    n = 9
    tree = _mixed_tree(n, 11)
    if attack != "none":
        tree = tree_attack(get_attack(attack, z=1.5), jax.random.PRNGKey(3),
                           tree, make_byzantine_mask(n, F))
    mask, w = mode_args("plain" if mode == "plain" else "weighted", n, 7)
    plan = FlatPlan.for_tree(tree)
    assert plan.uniform_dtype is None and plan.arena_dtype is None
    segs = plan.ravel(tree)
    leaves = jax.tree.leaves(tree)
    assert isinstance(segs, tuple) and len(segs) == 2
    for (dt, idx), seg in zip(plan.segments, segs):
        assert seg.dtype == jnp.dtype(dt)
        assert all(leaves[i].dtype == seg.dtype for i in idx)
    assert (sum(x.nbytes for x in segs)
            == sum(l.nbytes for l in leaves))
    coordwise = get_aggregator_def(rule).caps.coordwise
    for impl in ("pallas", "gather"):
        spec = make_spec(rule, f=F, impl=impl, n=n, **HYPER.get(rule, {}))
        vecs = spec.aggregate_flat(segs, mask=mask, weights=w)
        got = plan.unravel(vecs)
        for x, l in zip(jax.tree.leaves(got), leaves):
            assert x.dtype == l.dtype and x.shape == l.shape[1:]
        if coordwise:
            for (_, idx), vec in zip(plan.segments, vecs):
                sub = [leaves[i] for i in idx]
                uni = FlatPlan.for_tree(sub)
                want = spec.aggregate_flat(uni.ravel(sub), mask=mask,
                                           weights=w)
                np.testing.assert_array_equal(
                    np.asarray(vec), np.asarray(want),
                    err_msg=f"{rule}/{impl}")
        else:
            want = spec.aggregate(tree, mask=mask, weights=w)
            for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_allclose(
                    np.asarray(x, np.float32), np.asarray(y, np.float32),
                    rtol=1e-2, atol=1e-3, err_msg=f"{rule}/{impl}")


def test_uniform_arena_is_one_array_bit_for_bit():
    """A tree of one dtype keeps today's arena: one (n, P) array of that
    dtype, equal bit for bit to the concatenation of its leaves, one
    segment counted per ravel; the same unravel reads it as an array or
    as a one-segment tuple."""
    from repro.obs import counters
    n = 6
    g = data(n, 300, jnp.bfloat16, 4)
    tree = {"a": g[:, :120].reshape(n, 4, 30), "b": g[:, 120:]}
    plan = FlatPlan.for_tree(tree)
    assert plan.segments == (("bfloat16", (0, 1)),)
    assert plan.arena_dtype == jnp.bfloat16
    before = counters.snapshot()
    arena = plan.ravel(tree)
    assert counters.counter_delta(before) == {"core.flat.segments": 1}
    assert not isinstance(arena, tuple) and arena.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(arena, np.float32),
        np.asarray(jnp.concatenate([l.reshape(n, -1) for l in
                                    jax.tree.leaves(tree)], axis=1),
                   np.float32))
    vec = arena[2].astype(jnp.float32)
    for x, y in zip(jax.tree.leaves(plan.unravel(vec)),
                    jax.tree.leaves(plan.unravel((vec,)))):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))
    mixed = FlatPlan.for_tree(_mixed_tree(n, 2))
    before = counters.snapshot()
    mixed.ravel(_mixed_tree(n, 2))
    assert counters.counter_delta(before) == {"core.flat.segments": 2}


def test_flat_capability_boundaries():
    from repro.core.aggregators import clipped
    assert not make_spec("trimmed_mean", f=1, impl="fused").flat_capable
    assert not clipped(make_spec("krum", f=1), tau=1.0).flat_capable
    assert not make_spec("zeno_pp", f=1).flat_capable      # stateful
    assert not make_spec("mean", f=0, impl="gather").flat_capable  # custom
    with pytest.raises(ValueError, match="flat"):
        make_spec("trimmed_mean", f=1, impl="fused").aggregate_flat(
            jnp.zeros((4, 8)))


def test_unravel_plan_is_cached_and_bitwise():
    """tree_unravel_like now rides the shared FlatPlan: offsets computed
    once per structure (same object on repeat calls), output bitwise
    identical to the legacy per-call np.prod loop."""
    from repro.core.aggregators import tree_unravel_like
    n = 6
    proto = {"a": jnp.zeros((n, 3, 5), jnp.bfloat16),
             "b": [jnp.zeros((n, 7), jnp.float32)]}
    assert FlatPlan.for_tree(proto) is FlatPlan.for_tree(proto)
    plan = FlatPlan.for_tree(proto)
    assert plan.total == 22 and plan.offsets == (0, 15)
    vec = jax.random.normal(jax.random.PRNGKey(0), (22,))
    out = tree_unravel_like(vec, proto)
    np.testing.assert_array_equal(
        np.asarray(out["a"]),
        np.asarray(vec[:15].reshape(3, 5).astype(jnp.bfloat16)))
    np.testing.assert_array_equal(np.asarray(out["b"][0]),
                                  np.asarray(vec[15:]))


def test_masked_pallas_mixed_dtype_warns_once():
    """Satellite (updated): PAIRWISE kernels need one exchange dtype for
    the whole row (the Gram couples every column), so a mixed-dtype tree
    still falls back to the imputed tree path — and says so, exactly once
    (deduped against jax's warning-filter churn), numerically on the
    documented law.  Coordwise rules no longer warn: they route per-dtype
    SEGMENTS through the masked kernel (see the next test)."""
    from repro.core import aggregators as A
    n = 8
    grads = {"a": data(n, 64, jnp.float32, 8),
             "b": data(n, 40, jnp.bfloat16, 9)}
    mask, w = mode_args("weighted", n, 2)
    spec = make_spec("krum", f=2, impl="pallas", n=n)
    # the dedup set is process-global: clear this test's keys so the
    # assertion is independent of what ran before in the same process
    for key in [k for k in A._WARNED_ONCE
                if k[0] == "masked-pallas-mixed-dtype"]:
        A._WARNED_ONCE.discard(key)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        out = spec.aggregate(grads, mask=mask, weights=w)
        spec.aggregate(grads, mask=mask, weights=w)      # second call
    hits = [r for r in rec if "mixed dtypes" in str(r.message)]
    assert len(hits) == 1, [str(r.message) for r in rec]
    expect = make_spec("krum", f=2, impl="gather",
                       n=n).aggregate(grads, mask=mask, weights=w)
    for x, y in zip(jax.tree.leaves(out), jax.tree.leaves(expect)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_masked_pallas_mixed_dtype_coordwise_segments():
    """Coordwise rules stopped warning on mixed-dtype trees: each
    uniform-dtype SEGMENT rides the masked kernel (the per-coordinate law
    never couples columns, so splitting is exact) and the result matches
    the gather reference leaf-for-leaf — no warning fired."""
    from repro.core import aggregators as A
    n = 8
    grads = {"a": data(n, 64, jnp.float32, 8),
             "b": data(n, 40, jnp.bfloat16, 9)}
    mask, w = mode_args("weighted", n, 2)
    for key in [k for k in A._WARNED_ONCE
                if k[0] == "masked-pallas-mixed-dtype"]:
        A._WARNED_ONCE.discard(key)
    for rule in ("coordinate_median", "trimmed_mean", "sign_sgd",
                 "sparse_mean"):
        spec = make_spec(rule, f=2, impl="pallas", n=n)
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            out = spec.aggregate(grads, mask=mask, weights=w)
        assert not [r for r in rec if "mixed dtypes" in str(r.message)], rule
        expect = make_spec(rule, f=2, impl="gather", n=n).aggregate(
            grads, mask=mask, weights=w)
        for x, y in zip(jax.tree.leaves(out), jax.tree.leaves(expect)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=rule)


def test_flat_loops_add_zero_recompiles_under_churn_and_faults():
    """The tracecount gate of the flat pipeline: a 200-step run under
    membership churn + stragglers + message drops, aggregated by an
    elastic PALLAS spec through the flat-arena async loop, compiles the
    step at most once per bucket — exactly the per-leaf loops' historical
    bound, so the arena threading added ZERO compiles."""
    from repro.configs import get_config
    from repro.core.aggregators import elastic, frac
    from repro.core.tracecount import TRACE_COUNTS
    from repro.data import SyntheticLM
    from repro.optim import adamw, constant
    from repro.simulator import (Churn, Join, MessageDrop, SimConfig,
                                 Straggler, async_train_loop)
    from repro.training import ByzantineConfig

    cfg = get_config("paper-100m-smoke").replace(vocab_size=32,
                                                 dtype="float32")
    ds = SyntheticLM(vocab_size=32, seq_len=8, n_agents=8, per_agent_batch=1)
    el = elastic(8, buckets=(4, 6, 8))
    spec = make_spec("krum", f=frac(0.25), n=el)
    for b in el.buckets:
        assert spec.respecialize(b).impl == "pallas"
        assert spec.respecialize(b).flat_capable
    bz = ByzantineConfig(n_agents=8, f=2, aggregator=spec,
                         attack="sign_flip")
    sim = SimConfig(faults=(Join(agents=(7,), at=10),
                            Churn(rate=0.2, mean_out=2.0,
                                  agents=(1, 2, 3, 4)),
                            Straggler(dist="lognormal", scale=0.5),
                            MessageDrop(p=0.1)),
                    quorum=3, max_staleness=3, seed=0)
    before = TRACE_COUNTS["async_step"]
    before_sync = TRACE_COUNTS["train_step"]
    _, h = async_train_loop(cfg, bz, adamw(constant(1e-3)), ds, steps=200,
                            sim=sim, log_every=100, log_fn=lambda *_: None)
    assert np.isfinite(h[-1]["loss"])
    used = TRACE_COUNTS["async_step"] - before
    used_sync = TRACE_COUNTS["train_step"] - before_sync
    assert used + used_sync <= len(el.buckets) + 1, (used, used_sync)


# ---------------------------------------------------------------------------
# 6. compressed exchange: quantized arenas (int8 / fp8 + per-row scale
#    sidecar), the scaled in-tile-dequant kernels, and the zero-total
#    weight guards


from repro.core.flat import (QUANT_DTYPES, dequantize_rows,  # noqa: E402
                             fake_quantize, quantize_rows)

SCALED_RULES = ["coordinate_median", "trimmed_mean", "sign_sgd",
                "sparse_mean"]
QDTYPES = sorted(QUANT_DTYPES)


@pytest.mark.parametrize("qdt", QDTYPES)
def test_quantize_roundtrip_is_the_dequant_law(qdt):
    """quantize_rows -> dequantize_rows IS fake_quantize, bit-for-bit:
    the sidecar decode ``codes * scale[:, None]`` is THE parity oracle
    every in-tile dequant is asserted against."""
    n, d = 12, 771
    g = data(n, d, jnp.float32, 13)
    codes, qs = quantize_rows(g, jnp.dtype(qdt))
    assert codes.dtype == jnp.dtype(qdt) and qs.shape == (n,)
    assert bool(jnp.all(qs > 0))
    deq = dequantize_rows(codes, qs)
    np.testing.assert_array_equal(
        np.asarray(deq), np.asarray(fake_quantize(g, jnp.dtype(qdt))))
    if qdt == "int8":
        # symmetric round-to-nearest: error bounded by half a code step
        err = np.abs(np.asarray(deq) - np.asarray(g))
        assert float(np.max(err / np.asarray(qs)[:, None])) <= 0.5 + 1e-6


@pytest.mark.parametrize("qdt", QDTYPES)
def test_quantize_zero_row_guard(qdt):
    """An all-zero gradient row (a frozen / just-joined agent) must not
    divide by its zero amax: scale pins to 1.0, codes to 0, decode to 0."""
    g = jnp.zeros((4, 640), jnp.float32).at[1].set(
        data(1, 640, jnp.float32, 14)[0])
    codes, qs = quantize_rows(g, jnp.dtype(qdt))
    assert np.isfinite(np.asarray(qs)).all()
    np.testing.assert_array_equal(np.asarray(qs[0]), 1.0)
    np.testing.assert_array_equal(
        np.asarray(dequantize_rows(codes, qs)[0]), 0.0)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("qdt", QDTYPES)
@pytest.mark.parametrize("rule", SCALED_RULES)
def test_scaled_flat_matches_gather_dequant(rule, qdt, mode):
    """The tentpole parity gate: a quantized arena + per-row scale through
    ``impl="pallas"`` (dequant inside the tile) agrees BIT-FOR-BIT with
    ``impl="gather"`` (engine-level dequant), which itself agrees with
    running the rule on the explicitly dequantized rows — across odd/even
    n and the plain/masked/weighted modes."""
    for n in NS:
        g = data(n, 771, jnp.float32, 3)
        codes, qs = quantize_rows(g, jnp.dtype(qdt))
        mask, w = mode_args(mode, n, 5)
        pa = make_spec(rule, f=F, impl="pallas", n=n)
        ga = make_spec(rule, f=F, impl="gather", n=n)
        out = pa.aggregate_flat(codes, mask=mask, weights=w, scale=qs)
        expect = ga.aggregate_flat(codes, mask=mask, weights=w, scale=qs)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(expect),
                                      err_msg=f"{rule}/{qdt}/{mode}/n={n}")
        ref_out = ga.aggregate_flat(dequantize_rows(codes, qs),
                                    mask=mask, weights=w)
        np.testing.assert_array_equal(np.asarray(expect),
                                      np.asarray(ref_out),
                                      err_msg=f"{rule}/{qdt}/{mode}/n={n}")


@pytest.mark.parametrize("rule", SCALED_RULES)
def test_scaled_masked_pallas_is_dequant_copy_free(rule):
    """The acceptance gate of the int8/fp8 arena: NO dequantized (n, P)
    f32 copy is materialized outside the kernel — the cast and the
    scale-multiply live inside the tile.  The same detector run on the
    gather path DOES fire (it dequantizes at engine level), proving the
    check bites."""
    n, d = 8, 640
    g = data(n, d, jnp.float32, 4)
    codes, qs = quantize_rows(g, jnp.dtype("int8"))
    mask, w = mode_args("weighted", n, 5)
    banned = ("convert_element_type", "mul", "select_n", "broadcast_in_dim")

    def big(spec):
        jaxpr = jax.make_jaxpr(
            lambda c, s, m, w: spec.aggregate_flat(c, mask=m, weights=w,
                                                   scale=s))(codes, qs,
                                                             mask, w)
        return [s for s in _collect_shapes(jaxpr, banned=banned)
                if len(s) == 2 and s[0] == n and s[1] >= d
                ]

    pa = make_spec(rule, f=F, impl="pallas", n=n)
    assert not big(pa), (
        f"{rule}: dequantized (n, P) copy materialized: {big(pa)}")
    ga = make_spec(rule, f=F, impl="gather", n=n)
    assert big(ga), "detector lost its teeth: gather dequant not seen"


def test_scaled_fallback_rules_warn_once_and_stay_on_law():
    """Rules WITHOUT a scaled kernel (krum here) still accept a quantized
    arena through the engine-level dequant fallback — with a one-time
    warning naming the in-tile-capable rules — and stay bit-for-bit on
    the dequantize-then-aggregate law."""
    from repro.core import aggregators as A
    n = 8
    g = data(n, 640, jnp.float32, 15)
    codes, qs = quantize_rows(g, jnp.dtype("int8"))
    mask, w = mode_args("weighted", n, 6)
    spec = make_spec("krum", f=F, impl="pallas", n=n)
    for key in [k for k in A._WARNED_ONCE if k[0] == "flat-scaled-dequant"]:
        A._WARNED_ONCE.discard(key)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        out = spec.aggregate_flat(codes, mask=mask, weights=w, scale=qs)
        spec.aggregate_flat(codes, mask=mask, weights=w, scale=qs)
    hits = [r for r in rec if "no scaled" in str(r.message)]
    assert len(hits) == 1, [str(r.message) for r in rec]
    expect = spec.aggregate_flat(dequantize_rows(codes, qs),
                                 mask=mask, weights=w)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_masked_zero_total_weight_is_finite_zero():
    """Satellite pin: with every delivered weight zero (reachable under
    sparse/dropout weighting: coord_sent * dataset_size can vanish) the
    masked engine's tot/cnt scale used to go 0/eps-garbage — it now
    returns an exact finite zero, on the tree AND flat paths."""
    n, d = 8, 640
    g = data(n, d, jnp.float32, 2)
    mask = jnp.ones((n,), bool).at[jnp.arange(4)].set(False)
    w0 = jnp.zeros((n,))
    for rule in ("coordinate_median", "trimmed_mean", "sign_sgd",
                 "sparse_mean"):
        for impl in ("pallas", "gather"):
            spec = make_spec(rule, f=F, impl=impl, n=n)
            out = spec.aggregate(g, mask=mask, weights=w0)
            np.testing.assert_array_equal(np.asarray(out),
                                          np.zeros((d,), np.float32),
                                          err_msg=f"{rule}/{impl}/tree")
            vec = spec.aggregate_flat(g, mask=mask, weights=w0)
            np.testing.assert_array_equal(np.asarray(vec),
                                          np.zeros((d,), np.float32),
                                          err_msg=f"{rule}/{impl}/flat")


def test_quantized_flat_loop_compiles_once_per_bucket():
    """The compressed acceptance gate: the SAME 200-step churn + fault
    run as above, now with an int8 exchange dtype (agg_dtype="int8") —
    per-row quantize at ravel, scaled in-tile-dequant kernels at
    aggregate — still compiles at most once per elastic bucket: the
    quantize/scale threading added ZERO compiles."""
    from repro.configs import get_config
    from repro.core.aggregators import elastic, frac
    from repro.core.tracecount import TRACE_COUNTS
    from repro.data import SyntheticLM
    from repro.optim import adamw, constant
    from repro.simulator import (Churn, Join, MessageDrop, SimConfig,
                                 Straggler, async_train_loop)
    from repro.training import ByzantineConfig

    cfg = get_config("paper-100m-smoke").replace(vocab_size=32,
                                                 dtype="float32")
    ds = SyntheticLM(vocab_size=32, seq_len=8, n_agents=8, per_agent_batch=1)
    el = elastic(8, buckets=(4, 6, 8))
    spec = make_spec("trimmed_mean", f=frac(0.25), n=el)
    for b in el.buckets:
        assert spec.respecialize(b).impl == "pallas"
        assert spec.respecialize(b).flat_capable
    bz = ByzantineConfig(n_agents=8, f=2, aggregator=spec,
                         attack="sign_flip", agg_dtype="int8")
    sim = SimConfig(faults=(Join(agents=(7,), at=10),
                            Churn(rate=0.2, mean_out=2.0,
                                  agents=(1, 2, 3, 4)),
                            Straggler(dist="lognormal", scale=0.5),
                            MessageDrop(p=0.1)),
                    quorum=3, max_staleness=3, seed=0)
    before = TRACE_COUNTS["async_step"]
    before_sync = TRACE_COUNTS["train_step"]
    _, h = async_train_loop(cfg, bz, adamw(constant(1e-3)), ds, steps=200,
                            sim=sim, log_every=100, log_fn=lambda *_: None)
    assert np.isfinite(h[-1]["loss"])
    used = TRACE_COUNTS["async_step"] - before
    used_sync = TRACE_COUNTS["train_step"] - before_sync
    assert used + used_sync <= len(el.buckets) + 1, (used, used_sync)


# ---------------------------------------------------------------------------
# 10. centered_clip's fused MAC (PR 10): the kernel computes one
# fixed-point step of centered clipping; the scalar clip-radius stage
# stays outside (cross-tile row norms), so the kernel law is exactly
# (1 - sum lam) v + lam^T X — pinned here against that expression, with
# the lam > 0 gate keeping dead-row inf/NaN out of the accumulate


def test_clipped_weighted_sum_matches_the_law():
    from repro.kernels import clipped_weighted_sum

    n, d = 10, 512
    g = data(n, d, jnp.float32, 0)
    v = jax.random.normal(jax.random.PRNGKey(5), (d,))
    lam = jax.random.uniform(jax.random.PRNGKey(6), (n,),
                             minval=0.0, maxval=0.12)
    lam = lam.at[jnp.array([1, 4])].set(0.0)
    # a zeroed-lam row carrying non-finite payload must not leak
    g = g.at[1].set(jnp.inf).at[4].set(jnp.nan)
    out = clipped_weighted_sum(lam, g, v, interpret=True)
    xf = jnp.where((lam > 0.0)[:, None], g, 0.0)
    ref_out = (1.0 - jnp.sum(lam)) * v + lam @ xf
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("mode", MODES)
def test_centered_clip_flat_pallas_matches_gather(mode):
    """impl="pallas" routes centered_clip's per-iteration MAC through the
    fused kernel (explicit opt-in — auto keeps the dense body); the full
    fixed-point iterate must agree with the gather engine to fp32
    accumulation tolerance on tile-aligned P and fall back BIT-FOR-BIT
    on non-multiple-of-tile P (shared dense body)."""
    n = 12
    for d, bitwise in ((512, False), (771, True)):
        g = data(n, d, jnp.float32, 1)
        v = jax.random.normal(jax.random.PRNGKey(7), (d,))
        mask, w = mode_args(mode, n, 1)
        sp = make_spec("centered_clip", f=F, n=n, tau=1.0, impl="pallas")
        sg = make_spec("centered_clip", f=F, n=n, tau=1.0, impl="gather")
        st = {"server_grad": v}
        op = np.asarray(sp.aggregate_flat(g, mask=mask, weights=w,
                                          state=st))
        og = np.asarray(sg.aggregate_flat(g, mask=mask, weights=w,
                                          state=st))
        if bitwise:
            np.testing.assert_array_equal(op, og)
        else:
            np.testing.assert_allclose(op, og, rtol=3e-6, atol=3e-6)
