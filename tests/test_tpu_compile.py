"""Compile the Pallas kernels and the paper-100m train steps for a TPU v5e
chip described here, not attached (no chip needed).

Interpret mode, which the rest of the suite runs, evaluates the kernel
bodies on one coarse block: it checks their arithmetic, not what the
chip's compiler (Mosaic) accepts, nor the compiled TILE_D tiling.  These
cases do: every kernel the dispatch tables, ``FLAT_SELF_KERNELED`` and
the coded path reach, compiled at n in {8, 16} and P = 2**20, must lower
to a ``tpu_custom_call``; the whole paper-100m train steps must compile
and fit one chip's 16 GiB.

The topology is described inside a module fixture, never at import time:
only one process may hold the TPU library, and every test worker imports
this file.  All cases stay in this one file for the same reason.
"""
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"
                       / "chip"))

P = 1 << 20
F = 2
HBM_BYTES = 16 * 2**30                 # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Code that asks the backend sees the CPU here: steer the kernels'
    interpret default to the chip's."""
    from repro.kernels import tiling
    monkeypatch.setattr(tiling, "_INTERPRET", False)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases():
    cases = [("plain", r) for r in dispatch.PALLAS_RULES]
    cases += [("masked", r) for r in dispatch.PALLAS_MASKED_RULES]
    cases += [("scaled", r) for r in dispatch.PALLAS_SCALED_RULES]
    cases += [("scaled_masked", r)
              for r in dispatch.PALLAS_SCALED_MASKED_RULES]
    cases += [("self", r) for r in sorted(dispatch.FLAT_SELF_KERNELED)]
    cases += [("coded", "flat_draco_aggregate")]
    return cases


def _kernel_fn(kind, rule, n):
    """(fn, arg dtypes) compiling the case's kernels with interpret=False."""
    from repro.core.redundancy.coding import flat_draco_aggregate
    from repro.kernels.wsum import clipped_weighted_sum
    f32, i8 = jnp.float32, jnp.int8
    if kind == "plain":
        return (lambda g: dispatch.PALLAS_RULES[rule](g, F, {}, False),
                [((n, P), f32)])
    if kind == "masked":
        return (lambda g, m, w: dispatch.PALLAS_MASKED_RULES[rule](
            g, m, w, F, {}, False),
            [((n, P), f32), ((n,), f32), ((n,), f32)])
    if kind == "scaled":
        return (lambda g, s: dispatch.PALLAS_SCALED_RULES[rule](
            g, s, F, {}, False), [((n, P), i8), ((n,), f32)])
    if kind == "scaled_masked":
        return (lambda g, s, m, w: dispatch.PALLAS_SCALED_MASKED_RULES[rule](
            g, s, m, w, F, {}, False),
            [((n, P), i8), ((n,), f32), ((n,), f32), ((n,), f32)])
    if kind == "self":                     # centered_clip's kernel stage
        assert rule == "centered_clip", rule
        return (lambda lam, g, v: clipped_weighted_sum(lam, g, v,
                                                       interpret=False),
                [((n,), f32), ((n, P), f32), ((P,), f32)])
    assert kind == "coded", kind           # Draco decode (r=2 groups)
    return (lambda g, m: flat_draco_aggregate(g, 2, mask=m,
                                              interpret=False),
            [((n, P), f32), ((n,), jnp.bool_)])


@pytest.mark.parametrize("n", [8, 16])
@pytest.mark.parametrize("kind,rule", _kernel_cases(),
                         ids=[f"{k}-{r}" for k, r in _kernel_cases()])
def test_kernel_compiles_for_v5e(one_chip, kind, rule, n):
    fn, args = _kernel_fn(kind, rule, n)
    compiled = _compile(fn, *(_sds(one_chip, s, dt) for s, dt in args))
    assert "tpu_custom_call" in compiled.as_text(), (kind, rule, n)


def test_coord_stat_reads_the_arena_in_place(one_chip):
    """trimmed_mean on an (8, P) f32 arena cast from bf16 inside the jit
    (as the train step makes it) takes the lane-dense coord_stat: one
    ``tpu_custom_call`` named ``coord_stat`` (the benchmark's aggregation
    reader finds it by that name), fed a bitcast of the arena's own
    T(8, 128) layout, with no arena-sized relayout copy or transpose."""
    from repro.kernels.coord_stats import lane_dense
    n = 8
    arena = jax.ShapeDtypeStruct((n, P), jnp.float32)
    assert lane_dense(arena)
    fn = lambda g: dispatch.pallas_aggregate(            # noqa: E731
        "trimmed_mean", g.astype(jnp.float32), F, interpret=False)
    text = _compile(fn, _sds(one_chip, (n, P), jnp.bfloat16)).as_text()
    calls = [ln.strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln and " = " in ln]
    assert len(calls) == 1 and calls[0].startswith("%coord_stat"), calls
    relayouts = [ln.strip() for ln in text.splitlines()
                 if (" copy(" in ln or " transpose(" in ln)
                 and "f32[" in ln and _elements(ln) >= n * P]
    assert not relayouts, relayouts


def _elements(hlo_line: str) -> int:
    """Element count of an HLO instruction's f32 result shape."""
    dims = hlo_line.split("f32[", 1)[1].split("]", 1)[0]
    out = 1
    for x in dims.split(","):
        out *= int(x) if x else 1
    return out


def _step_shapes(one_chip, cfg, n, seq, batch, opt):
    from repro.data import SyntheticLM
    from repro.models import init_params
    ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq, n_agents=n,
                     per_agent_batch=batch)
    place = lambda tree: jax.tree.map(           # noqa: E731
        lambda s: _sds(one_chip, s.shape, s.dtype), tree)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return (place(params), place(jax.eval_shape(opt.init, params)),
            place(jax.eval_shape(lambda: ds.batch(jax.random.PRNGKey(0), 0))),
            place(jax.eval_shape(lambda: jax.random.PRNGKey(0))))


@pytest.mark.parametrize("path", ["sync", "async"])
def test_paper_100m_train_step_fits_one_v5e(one_chip, compiled_kernels,
                                            path):
    """The steps ``chip_smoke.py`` runs, at the published widths (n=8,
    f=2, seq 256, per-agent batch 2, remat, AdamW, ALIE): the synchronous
    trimmed_mean step (every step pure, so the loop allocates no in-flight
    buffer) and the async masked multi_krum step (the buffer is its
    argument 3).  Both donate what the loop donates, so the bytes on the
    chip are arguments + outputs - aliased + temporaries."""
    from repro.configs import get_config
    from repro.core.aggregators import make_spec
    from repro.optim import adamw, constant
    from repro.simulator.async_loop import make_async_step
    from repro.training import ByzantineConfig, make_train_step
    cfg, n = get_config("paper-100m"), 8
    opt = adamw(constant(3e-4))
    params, opt_state, batch, key = _step_shapes(one_chip, cfg, n, 256, 2,
                                                 opt)
    rule = "trimmed_mean" if path == "sync" else "multi_krum"
    bz = ByzantineConfig(n_agents=n, f=F, attack="alie", remat=True,
                         aggregator=make_spec(rule, f=F, n=n, impl="auto"))
    if path == "sync":
        step = jax.jit(make_train_step(cfg, bz, opt), donate_argnums=(0, 1, 2))
        args = (params, opt_state, None, batch, key)
    else:
        step = jax.jit(make_async_step(cfg, bz, opt),
                       donate_argnums=(0, 1, 2, 3))
        buffer = jax.tree.map(
            lambda s: _sds(one_chip, (n,) + s.shape), params)
        vec = lambda dt: _sds(one_chip, (n,), dt)   # noqa: E731
        args = (params, opt_state, None, buffer, {}, batch, key,
                vec(jnp.bool_), vec(jnp.float32), _sds(one_chip, (),
                                                       jnp.bool_))
    compiled = step.lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the steps' named scopes leave the kernels' instruction names alone:
    # the benchmark's aggregation reader still finds them
    from chipbench.readers import AGG_OPS
    assert any(AGG_OPS.match(ln.strip()) for ln in text.splitlines())
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{path}: {total / 2**30:.2f} GiB"
