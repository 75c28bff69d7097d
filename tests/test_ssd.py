"""The chunked SSD of ``models/ssm.py`` against the plain recurrence.

Mamba-2's state-space duality (arXiv:2405.21060) says the chunked
matmul form equals the sequential recurrence

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,    y_t = C_t h_t + D x_t.

These tests hold ``ssd_chunked`` and ``mamba_block`` to that recurrence in
float32 at Mamba-2's published initialisation (A uniform in [1, 16], dt
log-uniform in [1e-3, 1e-1], D = 1, a non-zero conv bias), where the state
carried between chunks is a large share of the output, at a length that
pads the last chunk; a chunked scan that drops the carry must fail them.
They also move the SSD's trace counters and find its named scopes inside
the training step's ``grads`` scope.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.models import ssm
from repro.obs import counters

CHUNK = 16
T = 3 * CHUNK + 17                  # three carried chunks and a padded one
TOL = 1e-5                          # relative, float32


def _cfg(**kw):
    base = dict(name="ssd-test", family="ssm", num_layers=2, d_model=32,
                num_heads=0, num_kv_heads=0, d_ff=0, vocab_size=64,
                ssm_state=16, ssm_head_dim=8, ssm_expand=2, ssm_groups=2,
                ssm_chunk=CHUNK, dtype="float32")
    return ArchConfig(**{**base, **kw})


def _published_params(cfg, seed=0):
    """The block's parameters with Mamba-2's published SSM init."""
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    h = cfg.ssm_num_heads
    p = ssm.init_ssm(k[0], cfg, jnp.float32)
    a = jax.random.uniform(k[1], (h,), minval=1.0, maxval=16.0)
    dt = jnp.exp(jax.random.uniform(k[2], (h,), minval=np.log(1e-3),
                                    maxval=np.log(1e-1)))
    p["A_log"] = jnp.log(a)
    p["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1(dt)
    p["D_skip"] = jnp.ones((h,), jnp.float32)
    p["conv_b"] = 0.1 * jax.random.normal(k[3], p["conv_b"].shape)
    return p


def recurrence(x, dt, A, B, C):
    """Sequential SSD: x (b, T, h, p), dt (b, T, h), A (h,), B and C
    (b, T, g, n); returns y (b, T, h, p) without the D skip."""
    b, t, h, p = x.shape
    rep = h // B.shape[2]
    Bh, Ch = np.repeat(B, rep, axis=2), np.repeat(C, rep, axis=2)
    x, dt = np.asarray(x, np.float64), np.asarray(dt, np.float64)
    state = np.zeros((b, h, p, B.shape[3]))
    y = np.zeros((b, t, h, p))
    for i in range(t):
        decay = np.exp(dt[:, i] * A)[..., None, None]
        state = decay * state + np.einsum(
            "bhp,bhn->bhpn", x[:, i] * dt[:, i, :, None], Bh[:, i])
        y[:, i] = np.einsum("bhpn,bhn->bhp", state, Ch[:, i])
    return y


def _ssd_inputs(seed=1, b=2, h=4, p=8, g=2, n=16, t=T):
    """Inputs at the published init: dt log-uniform in [1e-3, 1e-1] and
    A uniform in [1, 16], so dt * A spans slow and fast decay."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (b, t, h, p))
    dt = jnp.exp(jax.random.uniform(k[1], (b, t, h), minval=np.log(1e-3),
                                    maxval=np.log(1e-1)))
    A = -jax.random.uniform(k[2], (h,), minval=1.0, maxval=16.0)
    B = jax.random.normal(k[3], (b, t, g, n))
    C = jax.random.normal(k[4], (b, t, g, n))
    return x, dt, A, B, C


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def _chunked(x, dt, A, B, C, chunk=CHUNK):
    y, _ = ssm.ssd_chunked(x * dt[..., None], dt * A, B, C, chunk)
    return y


def test_ssd_chunked_matches_the_recurrence():
    x, dt, A, B, C = _ssd_inputs()
    want = recurrence(x, dt, np.asarray(A, np.float64), np.asarray(B),
                      np.asarray(C))
    assert _rel(_chunked(x, dt, A, B, C), want) < TOL


def test_carry_is_a_large_share_at_the_published_init():
    """The planted fault: each chunk scanned alone, the state carried
    between chunks dropped.  It must fail the tolerance by far."""
    x, dt, A, B, C = _ssd_inputs()
    want = recurrence(x, dt, np.asarray(A, np.float64), np.asarray(B),
                      np.asarray(C))
    parts = []
    for s in range(0, T, CHUNK):
        sl = slice(s, s + CHUNK)
        parts.append(_chunked(x[:, sl], dt[:, sl], A, B[:, sl], C[:, sl]))
    dropped = jnp.concatenate(parts, axis=1)
    assert _rel(dropped, want) > 1e3 * TOL


def _block_reference(cfg, params, u):
    """``mamba_block`` written out plainly with the recurrence."""
    di, g, n = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
    h, p = cfg.ssm_num_heads, cfg.ssm_head_dim
    b, t, _ = u.shape
    zx = np.asarray(u, np.float64) @ np.asarray(params["in_proj"], np.float64)
    z, xbc, dt = zx[..., :di], zx[..., di:2 * di + 2 * g * n], \
        zx[..., 2 * di + 2 * g * n:]
    w = np.asarray(params["conv_w"], np.float64)
    k = w.shape[0]
    xp = np.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + t] * w[i] for i in range(k)) + np.asarray(
        params["conv_b"], np.float64)
    xbc = conv / (1.0 + np.exp(-conv))
    xs = xbc[..., :di].reshape(b, t, h, p)
    Bm = xbc[..., di:di + g * n].reshape(b, t, g, n)
    Cm = xbc[..., di + g * n:].reshape(b, t, g, n)
    dt = np.logaddexp(0.0, dt + np.asarray(params["dt_bias"], np.float64))
    A = -np.exp(np.asarray(params["A_log"], np.float64))
    y = recurrence(xs, dt, A, Bm, Cm)
    y = y + np.asarray(params["D_skip"], np.float64)[:, None] * xs
    y = y.reshape(b, t, di) * (z / (1.0 + np.exp(-z)))
    y = y / np.sqrt(np.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
    y = y * (1.0 + np.asarray(params["norm_scale"], np.float64))
    return y @ np.asarray(params["out_proj"], np.float64)


def test_mamba_block_matches_the_recurrence():
    cfg = _cfg()
    params = _published_params(cfg)
    u = jax.random.normal(jax.random.PRNGKey(7), (2, T, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got, _ = ssm.mamba_block(cfg, params, u)
    assert _rel(got, _block_reference(cfg, params, u)) < TOL


@pytest.mark.parametrize("t,chunk,padded", [(2048, 256, 0), (T, CHUNK, 1)])
def test_ssd_counters(t, chunk, padded):
    """Each trace counts ``models.ssd.chunked``; ``models.ssd.padded``
    only where the length is not a multiple of the chunk (not at the
    cell's 2048 over 256)."""
    args = _ssd_inputs(b=1, h=2, p=4, g=1, n=8, t=t)
    before = counters.snapshot()
    jax.eval_shape(lambda *a: _chunked(*a, chunk=chunk), *args)
    moved = counters.counter_delta(before)
    assert moved.get("models.ssd.chunked") == 1, moved
    assert moved.get("models.ssd.padded", 0) == padded, moved


SSD_SCOPES = ("ssm.in_proj", "ssm.conv", "ssd.diag", "ssd.states",
              "ssd.carry", "ssd.off", "ssm.out")


def test_ssd_named_scopes_sit_inside_the_steps_grads_scope():
    """A tiny bf16 Mamba-2 step under remat, as the cell runs it."""
    from repro.core.aggregators import make_spec
    from repro.data import SyntheticLM
    from repro.models import init_params
    from repro.optim import adamw, constant
    from repro.training import ByzantineConfig, make_train_step
    cfg = _cfg(dtype="bfloat16", ssm_chunk=8)
    n = 4
    opt = adamw(constant(1e-3))
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    batch = jax.eval_shape(lambda: SyntheticLM(
        vocab_size=cfg.vocab_size, seq_len=16, n_agents=n,
        per_agent_batch=1).batch(jax.random.PRNGKey(0), 0))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    bz = ByzantineConfig(n_agents=n, f=1, attack="alie", remat=True,
                         aggregator=make_spec("trimmed_mean", f=1, n=n,
                                              impl="auto"))
    text = jax.jit(make_train_step(cfg, bz, opt)).lower(
        params, jax.eval_shape(opt.init, params), None, batch,
        key).compile().as_text()
    # XProf names an op by its HLO op_name: each scope's ops sit under
    # the step's grads scope (the backward's reductions, named from the
    # remat checkpoint down, may not)
    for scope in SSD_SCOPES:
        names = re.findall(r'op_name="([^"]*/' + re.escape(scope)
                           + r'/[^"]*)"', text)
        assert any(n.startswith("jit(train_step)/grads/") for n in names), \
            scope
