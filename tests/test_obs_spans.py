"""Program spans (``repro.obs.spans``) in a profiler trace.

A tiny replicated-decode run (admissions, bucket moves and a vote on
every commit) and a three-step ``train_loop`` are captured under
``jax.profiler`` and read back through the chip benchmark's reduction
(``chipbench.trace.from_xspace``): every span is there, nested where the
program opens it, and the results are bitwise those of a run with the
profiler off.  The named scopes of the jitted train steps are checked on
the lowered programs.
"""
from __future__ import annotations

import glob
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import spans as sp

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"
                       / "chip"))
from chipbench.trace import from_xspace  # noqa: E402

R, F = 5, 2
SCHED_NAMES = (sp.SCHED_STEP, sp.SCHED_DECODE, sp.SCHED_TO_HOST,
               sp.SCHED_COMMIT, sp.SCHED_VOTE, sp.SCHED_ADMIT,
               sp.SCHED_PREFILL, sp.SCHED_SPLICE, sp.SCHED_RESIZE,
               sp.SCHED_QUEUED)
TRAIN_SCOPES = ("grads", "arena", "attack", "aggregate", "unravel",
                "optimizer")


def _captured(tmp_path, fn):
    """``fn()`` under a profiler session; (its result, the host spans
    as {name: sorted [[start, end], ...]})."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    found: dict = {}
    for p in from_xspace(path)["planes"]:
        if p["name"].startswith("/host:"):
            for ln in p["lines"]:
                for ev in ln["events"]:
                    found.setdefault(ev[0], []).append(
                        [ev[1], ev[1] + ev[2]])
    return out, {k: sorted(v) for k, v in found.items()}


def _inside(a, b) -> bool:
    return b[0] <= a[0] and a[1] <= b[1]


def _parents(spans, child, parent):
    """For each ``child`` span, the ``parent`` spans that contain it."""
    return [[p for p in spans[parent] if _inside(c, p)]
            for c in spans[child]]


# ---------------------------------------------------------------------------
# serving


def _sched_run():
    from repro.configs import get_config
    from repro.core.aggregators import make_spec
    from repro.models import init_params
    from repro.serving.sched import ReplicatedScheduler, Request
    cfg = get_config("paper-100m-smoke")
    params = init_params(cfg, jax.random.PRNGKey(1))
    stack = jax.tree.map(lambda l: jnp.stack([l] * R), params)
    rng = np.random.default_rng(0)
    # arrivals spread over the virtual clock: the slab grows 1 -> 2 -> 4
    # and shrinks back as requests retire
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, size=4
                                    ).astype(np.int32), 2 + i % 4,
                    float(i // 2)) for i in range(6)]
    sched = ReplicatedScheduler(
        cfg, stack, make_spec("coordinate_median", f=F, n=R),
        slot_buckets=(1, 2, 4), seq_capacity=16, early_commit=False)
    assert sched.submit_all(reqs) == len(reqs)
    sched.run()
    return [list(r.out) for r in reqs]


@pytest.fixture(scope="module")
def sched_trace(tmp_path_factory):
    return _captured(tmp_path_factory.mktemp("sched"), _sched_run)


def test_scheduler_spans_all_present(sched_trace):
    _, spans = sched_trace
    for name in SCHED_NAMES:
        assert spans.get(name), name
    assert len(spans[sp.SCHED_ADMIT]) == 6
    assert len(spans[sp.SCHED_PREFILL]) == len(spans[sp.SCHED_SPLICE]) == 6


def test_scheduler_spans_nest(sched_trace):
    _, spans = sched_trace
    # one step holds each decode, admit and resize
    for child in (sp.SCHED_DECODE, sp.SCHED_ADMIT, sp.SCHED_RESIZE):
        assert all(len(p) == 1 for p in _parents(spans, child,
                                                  sp.SCHED_STEP)), child
    # an admission holds its prefill, splice, pull and commit
    for child in (sp.SCHED_PREFILL, sp.SCHED_SPLICE):
        assert all(len(p) == 1 for p in _parents(spans, child,
                                                 sp.SCHED_ADMIT)), child
    in_admit = [bool(p) for p in _parents(spans, sp.SCHED_TO_HOST,
                                          sp.SCHED_ADMIT)]
    assert sum(in_admit) == 6
    decodes = len(spans[sp.SCHED_DECODE])
    assert len(in_admit) - sum(in_admit) == decodes
    assert len(spans[sp.SCHED_COMMIT]) == decodes + 6
    # every commit voted (early commit off), inside the commit
    assert all(len(p) == 1 for p in _parents(spans, sp.SCHED_VOTE,
                                             sp.SCHED_COMMIT))
    assert len(spans[sp.SCHED_VOTE]) == len(spans[sp.SCHED_COMMIT])
    # the pull ends before its commit starts: siblings, not nested
    assert not any(_parents(spans, sp.SCHED_TO_HOST, sp.SCHED_COMMIT))


def test_one_queued_span_per_request(sched_trace):
    _, spans = sched_trace
    queued, admits = spans[sp.SCHED_QUEUED], spans[sp.SCHED_ADMIT]
    assert len(queued) == len(admits) == 6
    # each wait ends where its admission starts
    ends = sorted(e for _, e in queued)
    starts = sorted(s for s, _ in admits)
    assert all(e <= s for e, s in zip(ends, starts))
    assert all(s - e < 1e6 for e, s in zip(ends, starts))   # < 1 ms


def test_scheduler_tokens_bitwise_with_profiler_on_and_off(sched_trace):
    traced, _ = sched_trace
    assert traced == _sched_run()


# ---------------------------------------------------------------------------
# training


def _train_run(ckpt_dir):
    from repro.configs import get_config
    from repro.core.aggregators import make_spec
    from repro.data import SyntheticLM
    from repro.optim import adamw, constant
    from repro.training import ByzantineConfig, train_loop
    cfg = get_config("paper-100m-smoke")
    n = 4
    ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16, n_agents=n,
                     per_agent_batch=2)
    bz = ByzantineConfig(n_agents=n, f=1, attack="alie", aggregator=make_spec(
        "trimmed_mean", f=1, n=n))
    params, hist = train_loop(cfg, bz, adamw(constant(1e-3)), ds, 3,
                              log_every=1, ckpt_dir=str(ckpt_dir),
                              log_fn=lambda msg: None)
    return ([h["loss"] for h in hist],
            [np.asarray(x) for x in jax.tree.leaves(params)])


@pytest.fixture(scope="module")
def train_trace(tmp_path_factory):
    return _captured(tmp_path_factory.mktemp("train"),
                     lambda: _train_run(tmp_path_factory.mktemp("ckpt")))


def test_train_loop_spans_nest_in_steps(train_trace):
    _, spans = train_trace
    assert len(spans[sp.TRAIN_STEP]) == 3
    for child in (sp.TRAIN_BATCH, sp.TRAIN_DISPATCH, sp.TRAIN_LOG):
        assert len(spans[child]) == 3, child
        assert all(len(p) == 1 for p in _parents(spans, child,
                                                  sp.TRAIN_STEP)), child
    # one save at the end of the run, after the last step
    ck, = spans[sp.TRAIN_CHECKPOINT]
    assert ck[0] >= spans[sp.TRAIN_STEP][-1][1]


def test_train_losses_bitwise_with_profiler_on_and_off(train_trace,
                                                       tmp_path):
    (losses, leaves), _ = train_trace
    losses_off, leaves_off = _train_run(tmp_path)
    assert losses == losses_off
    for a, b in zip(leaves, leaves_off):
        np.testing.assert_array_equal(a, b)


def _lowered_text(async_step: bool) -> str:
    from repro.configs import get_config
    from repro.core.aggregators import make_spec
    from repro.data import SyntheticLM
    from repro.models import init_params
    from repro.optim import adamw, constant
    from repro.simulator.async_loop import make_async_step
    from repro.training import ByzantineConfig, make_train_step
    cfg = get_config("paper-100m-smoke")
    n = 4
    opt = adamw(constant(1e-3))
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    batch = jax.eval_shape(lambda: SyntheticLM(
        vocab_size=cfg.vocab_size, seq_len=16, n_agents=n,
        per_agent_batch=2).batch(jax.random.PRNGKey(0), 0))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(opt.init, params)
    bz = ByzantineConfig(n_agents=n, f=1, attack="alie", aggregator=make_spec(
        "trimmed_mean", f=1, n=n, impl="auto"))
    if not async_step:
        step = make_train_step(cfg, bz, opt)
        args = (params, opt_state, None, batch, key)
    else:
        step = make_async_step(cfg, bz, opt)
        buffer = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            (n,) + s.shape, jnp.float32), params)
        vec = lambda dt: jax.ShapeDtypeStruct((n,), dt)  # noqa: E731
        args = (params, opt_state, None, buffer, {}, batch, key,
                vec(jnp.bool_), vec(jnp.float32),
                jax.ShapeDtypeStruct((), jnp.bool_))
    return jax.jit(step).lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("async_step", [False, True],
                         ids=["sync", "async"])
def test_train_step_named_scopes(async_step):
    text = _lowered_text(async_step)
    scopes = TRAIN_SCOPES + (("buffer",) if async_step else ())
    for scope in scopes:
        assert f"/{scope}/" in text, scope
