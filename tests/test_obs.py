"""Flight-recorder guarantees (PR 6, :mod:`repro.obs`).

The observability contract has teeth only if it is pinned:

  * telemetry OFF is the exact historical program — loop outputs are
    BIT-FOR-BIT identical with and without a recorder attached;
  * telemetry ON does not change the aggregation result and does not add
    recompiles — a 200-step churn-and-fault run with a recorder attached
    stays within the elastic-bucket compile budget (``<= len(buckets)``
    async traces, ``<= 1`` sync fast-path trace), proven by the
    :mod:`repro.obs.counters` substrate the recorder itself uses;
  * the (n,) selection weights are FAITHFUL: for weight-decomposable
    rules ``aggregate(grads) == tree_weighted_sum(grads, sel_w)``
    exactly, and the weights agree across the gather/fused/pallas impls
    of the same rule in the plain, masked and weighted regimes;
  * the report CLI renders the suspicion table / recompile ledger from a
    recorded trace, and the Chrome-trace export is structurally valid.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.aggregators import (elastic, frac, make_spec,
                                    tree_weighted_sum)
from repro.data import SyntheticLM
from repro.obs import counters
from repro.obs.recorder import Recorder, chrome_trace, read_trace
from repro.obs.telemetry import (agent_series, dispatch_record,
                                 suspicion_scores)
from repro.optim import adamw, constant
from repro.simulator import (Churn, Join, Rejoin, SimConfig, Straggler,
                             async_train_loop)
from repro.training import ByzantineConfig, train_loop

CFG = get_config("paper-100m-smoke").replace(vocab_size=32, dtype="float32")
N = 8
D = 96


def _stack(key, n=N, d=D, scale=1.0):
    return jax.random.normal(key, (n, d), jnp.float32) * scale


def _tree(key, n=N):
    ka, kb = jax.random.split(key)
    return {"w": jax.random.normal(ka, (n, 4, 6), jnp.float32),
            "b": jax.random.normal(kb, (n, 5), jnp.float32)}


def _leaves_equal(a, b):
    return all(bool((x == y).all())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------- counters

def test_tracecount_shim_is_the_obs_counter_object():
    """core.tracecount is a view of obs.counters — same live object, so
    historical snapshot-diff tests and the recorder agree on counts."""
    from repro.core import tracecount
    assert tracecount.TRACE_COUNTS is counters.TRACE_COUNTS
    assert tracecount.TRACE_COUNTS is counters.COUNTERS
    assert tracecount.count_trace is counters.count_trace
    assert tracecount.snapshot is counters.snapshot


def test_counter_snapshot_delta():
    before = counters.snapshot()
    counters.inc("obs_test_site")
    counters.inc("obs_test_site")
    delta = counters.counter_delta(before)
    assert delta.get("obs_test_site") == 2
    after = counters.snapshot()
    assert after["counters"]["obs_test_site"] - \
        before["counters"].get("obs_test_site", 0) == 2
    counters.reset("obs_test_site")
    assert counters.trace_count("obs_test_site") == 0


# ------------------------------------------------- selection-weight truth

WSUM_EXACT = ["mean", "krum", "multi_krum", "m_krum", "mda", "cge", "cgc"]


@pytest.mark.parametrize("rule", WSUM_EXACT)
def test_selection_weights_reconstruct_aggregate(rule):
    """For weight-decomposable rules the telemetry weights ARE the
    aggregation: tree_weighted_sum(grads, sel_w) == aggregate(grads)."""
    grads = _tree(jax.random.PRNGKey(3))
    spec = make_spec(rule, f=2, n=N, impl="gather")
    sel = spec.selection_weights(grads)
    assert sel.shape == (N,) and sel.dtype == jnp.float32
    agg = spec.aggregate(grads)
    rec = tree_weighted_sum(grads, sel)
    for x, y in zip(jax.tree.leaves(agg), jax.tree.leaves(rec)):
        # summation-order float noise only (mean-of-k vs weighted sum)
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-6)


def test_krum_weights_are_one_hot():
    grads = _stack(jax.random.PRNGKey(4))
    spec = make_spec("krum", f=2, n=N)
    sel = np.asarray(spec.selection_weights(grads))
    assert sel.sum() == pytest.approx(1.0)
    assert (sel > 0).sum() == 1
    # the hot index is exactly the krum pick
    agg = np.asarray(spec.aggregate(grads))
    np.testing.assert_array_equal(agg, np.asarray(grads)[sel.argmax()])


@pytest.mark.parametrize("rule", ["krum", "trimmed_mean", "cge",
                                  "coordinate_median"])
@pytest.mark.parametrize("regime", ["plain", "masked", "weighted"])
def test_weights_consistent_across_impls(rule, regime):
    """gather / fused / pallas report consistent selection weights for
    the same rule in every masking regime (CPU: pallas = interpret)."""
    key = jax.random.PRNGKey(5)
    grads = _stack(key, d=128)
    mask = weights = None
    if regime == "masked":
        mask = jnp.asarray([1, 1, 0, 1, 1, 1, 0, 1], bool)
    elif regime == "weighted":
        mask = jnp.asarray([1, 1, 1, 1, 1, 1, 1, 0], bool)
        weights = jnp.asarray([1, .5, 1, .25, 1, 1, .5, 0], jnp.float32)
    impls = ("gather", "fused", "pallas")
    if regime == "weighted" and rule in ("krum", "cge"):
        # selection rules follow a DIFFERENT weighted-masked law on the
        # fused impl (weights enter the rule, not just the imputation) —
        # the aggregates differ, so the weights rightly differ too
        impls = ("gather", "pallas")
    sels = {}
    for impl in impls:
        try:
            spec = make_spec(rule, f=2, n=N, impl=impl)
        except ValueError:
            continue                      # impl not registered for rule
        sels[impl] = np.asarray(
            spec.selection_weights(grads, mask=mask, weights=weights))
    assert len(sels) >= 2, f"{rule}: fewer than two impls resolved"
    ref_impl, ref = next(iter(sels.items()))
    for impl, sel in sels.items():
        np.testing.assert_allclose(
            sel, ref, rtol=0, atol=1e-6,
            err_msg=f"{rule}/{regime}: {impl} disagrees with {ref_impl}")
    # coordwise rules weight by participation: excluded agents carry
    # zero weight.  (Selection rules — krum/cge — run on the imputed
    # stack, so the consensus-filled row of a masked agent CAN win;
    # the weights faithfully report the imputation.)
    if mask is not None and rule in ("trimmed_mean", "coordinate_median"):
        for impl, sel in sels.items():
            assert np.all(sel[~np.asarray(mask)] == 0), (impl, sel)


def test_fused_weighted_masked_law_reconstructs():
    """The fused masked law's exact decomposition: for a selection rule
    under mask+weights, agg == wsum(imputed, fw) with the reported
    fused weights (the tot/cnt scale is folded into fw)."""
    g = _stack(jax.random.PRNGKey(9), d=64)
    mask = jnp.asarray([1, 1, 1, 1, 1, 1, 1, 0], bool)
    w = jnp.asarray([1, .5, 1, .25, 1, 1, .5, 0], jnp.float32)
    spec = make_spec("cge", f=2, n=N, impl="fused")
    agg = np.asarray(spec.aggregate(g, mask=mask, weights=w))
    sel = spec.selection_weights(g, mask=mask, weights=w)
    # impute exactly the way the masked law does: mean of arrived rows
    tot = float(w.sum())
    mean_sel = np.asarray(tree_weighted_sum(g, w / tot))
    gi = np.where(np.asarray(mask)[:, None], np.asarray(g), mean_sel)
    np.testing.assert_allclose(
        (np.asarray(sel)[:, None] * gi).sum(0), agg, rtol=1e-5, atol=1e-6)


def test_wrapper_and_stateful_weights():
    grads = _stack(jax.random.PRNGKey(6))
    clipped = make_spec("clipped", inner=make_spec("krum", f=2, n=N),
                        tau=1.0, f=2, n=N)
    sel = np.asarray(clipped.selection_weights(grads))
    assert sel.shape == (N,) and (sel > 0).sum() == 1
    zpp = make_spec("zeno_pp", xi=0.5, ema=0.2, n=N)
    st = zpp.init_state(jax.tree.map(lambda l: l[0], grads))
    sel = np.asarray(zpp.selection_weights(grads, state=st))
    assert sel.shape == (N,)
    with pytest.raises(ValueError):
        zpp.selection_weights(grads)      # stateful rule needs its state


def test_bulyan_theta_weights():
    grads = _stack(jax.random.PRNGKey(7), n=10, d=64)
    spec = make_spec("bulyan", f=1, n=10)
    sel = np.asarray(spec.selection_weights(grads))
    theta = 10 - 2 * 1                    # n - 2f selected, uniform 1/theta
    assert (sel > 0).sum() == theta
    np.testing.assert_allclose(sel[sel > 0], 1.0 / theta, atol=1e-7)


def test_aggregate_with_telemetry_matches_aggregate():
    grads = _tree(jax.random.PRNGKey(8))
    spec = make_spec("trimmed_mean", f=2, n=N)
    agg, telem = spec.aggregate_with_telemetry(grads)
    assert _leaves_equal(agg, spec.aggregate(grads))
    assert set(telem) == {"sel_w", "mask", "contrib_w"}
    assert telem["sel_w"].shape == (N,)


# ------------------------------------------- bit-for-bit loop equivalence

def _run_async(recorder, steps=12, seed=0):
    ds = SyntheticLM(vocab_size=32, seq_len=8, n_agents=N, per_agent_batch=1)
    bz = ByzantineConfig(n_agents=N, f=2,
                         aggregator=make_spec("cge", f=2, n=N),
                         attack="large_value", attack_hyper={})
    sim = SimConfig(faults=(Straggler(dist="pareto", scale=1.0, prob=0.5,
                                      agents=(0, 1)),),
                    quorum=6, max_staleness=3, seed=seed)
    return async_train_loop(CFG, bz, adamw(constant(1e-3)), ds, steps=steps,
                            sim=sim, log_every=steps, log_fn=lambda *_: None,
                            recorder=recorder)


def test_recorder_on_is_bit_identical(tmp_path):
    """The hard contract: attaching a Recorder (telemetry ON) leaves the
    trained parameters bitwise unchanged."""
    p_off, h_off = _run_async(None)
    rec = Recorder(str(tmp_path / "t.jsonl"))
    p_on, h_on = _run_async(rec)
    rec.close()
    assert _leaves_equal(p_off, p_on)
    assert [h["loss"] for h in h_off] == [h["loss"] for h in h_on]
    steps = [e for e in rec.events if e["kind"] == "step"]
    assert len(steps) == 12
    assert all(e.get("telemetry") for e in steps)


def test_stateful_loop_recorder_bit_identical():
    """The PR-10 extension of the contract: a STATEFUL rule (centered_clip
    carries its center across rounds) under a defense-aware attack takes
    the general async path with the {agg, atk} state bundle — attaching a
    Recorder must still leave the trained parameters bitwise unchanged."""
    ds = SyntheticLM(vocab_size=32, seq_len=8, n_agents=N, per_agent_batch=1)
    bz = ByzantineConfig(n_agents=N, f=2,
                         aggregator=make_spec("centered_clip", f=2, n=N,
                                              tau=1.0),
                         attack="slow_drift", attack_hyper={})
    sim = SimConfig(faults=(Straggler(dist="pareto", scale=1.0, prob=0.5,
                                      agents=(0, 1)),),
                    quorum=6, max_staleness=3, seed=0)

    def run(recorder):
        return async_train_loop(CFG, bz, adamw(constant(1e-3)), ds, steps=8,
                                sim=sim, log_every=8, log_fn=lambda *_: None,
                                recorder=recorder)
    p_off, h_off = run(None)
    rec = Recorder()
    p_on, h_on = run(rec)
    rec.close()
    assert _leaves_equal(p_off, p_on)
    assert [h["loss"] for h in h_off] == [h["loss"] for h in h_on]
    steps = [e for e in rec.events if e["kind"] == "step"]
    assert len(steps) == 8
    # the telemetry rows carry centered_clip's effective clip weights
    ser = agent_series(rec.events)
    assert ser["sel_w"].shape == (8, N)
    assert np.isfinite(ser["sel_w"][ser["mask"].astype(bool)]).all()


def test_sync_loop_recorder_bit_identical():
    ds = SyntheticLM(vocab_size=32, seq_len=8, n_agents=N, per_agent_batch=1)
    bz = ByzantineConfig(n_agents=N, f=2,
                         aggregator=make_spec("trimmed_mean", f=2, n=N))

    def run(recorder):
        return train_loop(CFG, bz, adamw(constant(1e-3)), ds, steps=6,
                          log_every=6, log_fn=lambda *_: None,
                          recorder=recorder)
    p_off, _ = run(None)
    rec = Recorder()
    p_on, _ = run(rec)
    rec.close()
    assert _leaves_equal(p_off, p_on)
    assert sum(1 for e in rec.events if e["kind"] == "step") == 6


# ----------------------------------------- zero-added-recompiles (churn)

def test_churn_run_with_recorder_adds_zero_recompiles():
    """200 churn+straggler steps over a 3-bucket elastic spec WITH a
    recorder attached: still <= 1 compile per bucket (async) and <= 1
    sync fast-path compile — telemetry aux outputs are fixed-shape, so
    observation costs no recompilation."""
    STEPS, BUCKETS = 200, (4, 6, 8)
    ds = SyntheticLM(vocab_size=32, seq_len=8, n_agents=N, per_agent_batch=1)
    spec = make_spec("trimmed_mean", f=frac(0.25),
                     n=elastic(N, buckets=BUCKETS))
    bz = ByzantineConfig(n_agents=N, f=2, aggregator=spec)
    churn = (Join(agents=(7,), at=10),
             Rejoin(agents=(6,), leave_at=40, rejoin_at=60),
             Churn(rate=0.2, mean_out=2.0, agents=(1, 2, 3, 4)),
             Straggler(dist="pareto", scale=1.0, prob=0.3, agents=(2,)))
    sim = SimConfig(faults=churn, seed=0)
    before = counters.snapshot()
    rec = Recorder()
    _, h = async_train_loop(CFG, bz, adamw(constant(1e-3)), ds, steps=STEPS,
                            sim=sim, log_every=STEPS, log_fn=lambda *_: None,
                            recorder=rec)
    rec.close()
    assert np.isfinite(h[-1]["loss"])
    delta = counters.counter_delta(before)
    assert delta.get("async_step", 0) <= len(BUCKETS), delta
    assert delta.get("train_step", 0) <= 1, delta
    # the recorder's own ledger attributes every compile to a step
    ledger = [e for e in rec.events if e["kind"] == "compile"]
    assert sum(e["count"] for e in ledger
               if e["site"] == "async_step") == delta.get("async_step", 0)
    # telemetry rows cover the run with the full fixed shape
    ser = agent_series(rec.events)
    assert ser["sel_w"].shape == (STEPS, N)
    assert ser["mask"].shape == (STEPS, N)


# ------------------------------------------------------ recorder + report

def _recorded_run(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    rec = Recorder(path, meta={"test": "obs"})
    _run_async(rec, steps=10)
    rec.close()
    return path, rec.events


def test_trace_jsonl_roundtrip(tmp_path):
    path, events = _recorded_run(tmp_path)
    loaded = read_trace(path)
    assert [e["kind"] for e in loaded] == [e["kind"] for e in events]
    meta = loaded[0]
    assert meta["kind"] == "meta"
    prov = meta["provenance"]
    for k in ("jax_version", "backend", "device_kind", "interpret",
              "git_sha"):
        assert k in prov, k


def test_chrome_trace_structure(tmp_path):
    _, events = _recorded_run(tmp_path)
    ct = chrome_trace(events)
    assert set(ct) >= {"traceEvents", "displayTimeUnit"}
    phases = {e["ph"] for e in ct["traceEvents"]}
    assert "X" in phases and "M" in phases      # spans + thread names
    spans = [e for e in ct["traceEvents"] if e["ph"] == "X"]
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in spans)
    json.dumps(ct)                              # perfetto-loadable JSON


def test_report_cli_renders(tmp_path, capsys):
    from repro.launch.report import main as report_main
    path, _ = _recorded_run(tmp_path)
    perfetto = str(tmp_path / "trace.json")
    report_main([path, "--perfetto", perfetto])
    out = capsys.readouterr().out
    assert "per-agent suspicion" in out
    assert "recompile ledger" in out
    assert "rule dispatch" in out
    assert "rule=cge" in out
    with open(perfetto) as fh:
        assert "traceEvents" in json.load(fh)


def test_suspicion_ranks_the_excluded_agents(tmp_path):
    """cge + large_value attackers (agents 0..f-1 by convention): the
    filtered-out byzantine agents must top the suspicion ranking."""
    _, events = _recorded_run(tmp_path)
    ser = agent_series(events)
    scores = suspicion_scores(ser["sel_w"], ser["mask"], ser["roster"])
    ranked = [s["agent"] for s in
              sorted(scores, key=lambda s: -s["suspicion"])]
    assert set(ranked[:2]) == {0, 1}, ranked
    by_agent = {s["agent"]: s for s in scores}
    assert all(0.0 <= s["suspicion"] <= 1.0 for s in scores)
    assert by_agent[0]["suspicion"] > by_agent[5]["suspicion"]


def test_dispatch_record_walks_wrapper_chain():
    spec = make_spec("clipped", inner=make_spec("trimmed_mean", f=2, n=N),
                     tau=2.0, f=2, n=N)
    d = dispatch_record(spec)
    assert d["rule"] == "clipped"
    assert d["inner"]["rule"] == "trimmed_mean"
    el = make_spec("trimmed_mean", f=frac(0.25),
                   n=elastic(N, buckets=(4, 6, 8)))
    assert tuple(dispatch_record(el)["elastic_buckets"]) == (4, 6, 8)


# -------------------------------------------------------------- serving

def test_serving_recorder_token_stream_identical(tmp_path):
    from repro.models import init_params
    from repro.serving import generate_replicated

    r, steps = 5, 12
    params = init_params(CFG, jax.random.PRNGKey(0))
    stack = jax.tree.map(lambda l: jnp.stack([l] * r), params)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                          CFG.vocab_size)}
    spec = make_spec("coordinate_median", f=1, n=r)

    def corrupt(step, logits):            # replica 0 emits garbage
        return logits.at[0].set(-logits[0] * 50.0)

    out_off = generate_replicated(CFG, stack, batch, steps, spec,
                                  fault_hook=corrupt)
    rec = Recorder(str(tmp_path / "serve.jsonl"))
    out_on = generate_replicated(CFG, stack, batch, steps, spec,
                                 fault_hook=corrupt, recorder=rec)
    rec.close()
    np.testing.assert_array_equal(np.asarray(out_off), np.asarray(out_on))
    step_events = [e for e in rec.events if e["kind"] == "step"]
    assert len(step_events) == steps
    ser = agent_series(rec.events, n=r)
    assert ser["sel_w"].shape == (steps, r)
    # the corrupted replica never carries weight under the median
    scores = {s["agent"]: s for s in
              suspicion_scores(ser["sel_w"], ser["mask"])}
    assert scores[0]["suspicion"] >= max(
        scores[i]["suspicion"] for i in range(1, r))


# ------------------------------------------------- satellites: summaries

def test_async_trace_summary_percentiles():
    churn = (Churn(rate=0.2, mean_out=2.0, agents=(1, 2, 3)),
             Straggler(dist="pareto", scale=1.0, prob=0.3, agents=(0,)))
    from repro.simulator import plan_arrivals
    sim = SimConfig(faults=churn, quorum=6, max_staleness=3, seed=0)
    s = plan_arrivals(sim, N, 50).summary()
    for k in ("staleness_p50", "staleness_p95", "arrived_p50",
              "arrived_p95", "min_arrived", "min_live", "live_p50",
              "live_fraction"):
        assert k in s, k
    assert len(s["live_fraction"]) == N
    assert all(0.0 <= f <= 1.0 for f in s["live_fraction"])
    # pinned agents (not in the churn set) are always live
    assert s["live_fraction"][0] == 1.0
    assert s["staleness_p50"] <= s["staleness_p95"] <= s["max_staleness"]


def test_provenance_keys():
    from repro.obs.provenance import provenance
    p = provenance()
    assert p["jax_version"] == jax.__version__
    assert p["backend"] == jax.default_backend()
    assert isinstance(p["interpret"], bool)
    assert isinstance(p["git_sha"], str) and p["git_sha"]
    json.dumps(p)


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-q"]))


# ----------------------------------------- live subscription (serving loop)

def test_subscriber_sees_every_event_in_order(tmp_path):
    """A subscriber receives the same dicts, in the same order, as the
    JSONL file — the live half the scheduler's suspicion policy rides."""
    rec = Recorder(str(tmp_path / "t.jsonl"))
    seen = []
    unsub = rec.subscribe(seen.append)
    rec.emit("note", message="a")
    rec.step(0, metrics={"loss": 1.0})
    rec.emit("note", message="b")
    rec.close()
    # meta predates the subscription; everything after lands live
    assert [e["kind"] for e in seen] == ["note", "step", "note"]
    assert seen == rec.events[1:]
    assert [e["kind"] for e in read_trace(rec.path)[1:]] == \
        [e["kind"] for e in seen]
    unsub()
    assert rec._subscribers == []


def test_unsubscribe_stops_delivery_and_file_unchanged(tmp_path):
    """File emission is byte-identical with or without subscribers, and
    an unsubscribed callback never fires again."""
    def run(path, attach):
        rec = Recorder(str(path))
        seen = []
        unsub = rec.subscribe(seen.append) if attach else None
        rec.emit("note", message="x")
        if unsub is not None:
            unsub()
            unsub()                               # idempotent
        rec.emit("note", message="y")
        rec.close()
        return seen, path.read_text()

    seen, with_sub = run(tmp_path / "a.jsonl", attach=True)
    assert [e["message"] for e in seen] == ["x"]

    _, without = run(tmp_path / "b.jsonl", attach=False)
    strip = lambda s: [json.loads(l) for l in s.splitlines()]  # noqa: E731
    a, b = strip(with_sub), strip(without)
    for ea, eb in zip(a, b):
        ea.pop("t"), eb.pop("t")
        ea.get("provenance", {}).pop("wall_time", None)
        eb.get("provenance", {}).pop("wall_time", None)
    assert a == b
