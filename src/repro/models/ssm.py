"""Mamba2 (SSD — state-space duality) block, chunked matmul form.

Faithful to arXiv:2405.21060's "minimal SSD" reference: intra-chunk terms are
dense (MXU-friendly) attention-like matmuls through the 1-semiseparable decay
mask; inter-chunk terms pass an (h, p, n) recurrent state.  Decode is the O(1)
recurrent update.  The causal depthwise conv (kernel 4) over (x, B, C) is kept,
with a conv ring state for decode.

Under training the forward and backward carry ``jax.named_scope``s that a
profile shows inside the step's ``grads`` scope: ``ssm.in_proj``,
``ssm.conv``, ``ssd.diag`` (intra-chunk), ``ssd.states`` (per-chunk final
states), ``ssd.carry`` (the inter-chunk recurrence), ``ssd.off`` (the
carried state's output) and ``ssm.out`` (skip, gate, norm, out_proj).
Each trace of :func:`ssd_chunked` counts ``models.ssd.chunked``, and
``models.ssd.padded`` when the sequence is not a multiple of the chunk
(:mod:`repro.obs.counters`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, rms_norm
from repro.obs.counters import count_trace


# ---------------------------------------------------------------------------
# params


def init_ssm(key, cfg, dtype):
    D = cfg.d_model
    di = cfg.d_inner
    g, n = cfg.ssm_groups, cfg.ssm_state
    h = cfg.ssm_num_heads
    kq = cfg.ssm_conv
    ks = jax.random.split(key, 3)
    conv_dim = di + 2 * g * n
    return {
        "in_proj": dense_init(ks[0], (D, 2 * di + 2 * g * n + h), dtype),
        "conv_w": dense_init(ks[1], (kq, conv_dim), dtype, scale=0.5),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.zeros((h,), jnp.float32),          # A = -exp(A_log) = -1
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "D_skip": jnp.ones((h,), jnp.float32),
        "norm_scale": jnp.zeros((di,), dtype),
        "out_proj": dense_init(ks[2], (di, D), dtype),
    }


# ---------------------------------------------------------------------------
# SSD core


def _segsum(x):
    """Stable segment-sum: out[..., i, j] = sum_{j<k<=i} x[..., k] (i>=j)."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool))
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunked(x, a, B, C, chunk: int, initial_state=None):
    """Chunked SSD scan.

    x: (b, t, h, p)   pre-multiplied by dt
    a: (b, t, h)      log-decay per step (dt * A, negative)
    B, C: (b, t, g, n) with h % g == 0
    Returns y: (b, t, h, p), final_state: (b, h, p, n)
    """
    b, t, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    t_orig = t
    count_trace("models.ssd.chunked")
    pad = (-t) % chunk
    if pad:
        # zero-pad to a chunk multiple: zero x/B contribute nothing to the
        # state; a=0 (decay 1) carries the state through the padding
        count_trace("models.ssd.padded")
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
        t = t + pad
    c = t // chunk
    rep = h // g

    xr = x.reshape(b, c, chunk, h, p)
    ar = a.reshape(b, c, chunk, h).transpose(0, 3, 1, 2)       # (b,h,c,l)
    Br = jnp.repeat(B.reshape(b, c, chunk, g, n), rep, axis=3)  # (b,c,l,h,n)
    Cr = jnp.repeat(C.reshape(b, c, chunk, g, n), rep, axis=3)

    a_cs = jnp.cumsum(ar, axis=-1)                              # (b,h,c,l)

    # intra-chunk (diagonal blocks)
    with jax.named_scope("ssd.diag"):
        L = jnp.exp(_segsum(ar))                                # (b,h,c,l,l)
        y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp",
                            Cr, Br, L.astype(Cr.dtype), xr,
                            preferred_element_type=jnp.float32)

    # per-chunk final states
    with jax.named_scope("ssd.states"):
        decay_states = jnp.exp(a_cs[..., -1:] - a_cs)           # (b,h,c,l)
        states = jnp.einsum("bclhn,bhcl,bclhp->bchpn",
                            Br, decay_states.astype(Br.dtype), xr,
                            preferred_element_type=jnp.float32)

    # inter-chunk recurrence over c (small: t/chunk) via segsum matmul
    with jax.named_scope("ssd.carry"):
        if initial_state is None:
            initial_state = jnp.zeros((b, h, p, n), states.dtype)
        states = jnp.concatenate(
            [initial_state[:, None].astype(states.dtype), states], axis=1)
        chunk_decay = a_cs[..., -1]                             # (b,h,c)
        padded = jnp.pad(chunk_decay, ((0, 0), (0, 0), (1, 0)))
        decay_chunk = jnp.exp(_segsum(padded))              # (b,h,c+1,c+1)
        decay_chunk = jnp.where(jnp.isfinite(decay_chunk), decay_chunk, 0.0)
        new_states = jnp.einsum("bhzc,bchpn->bzhpn",
                                decay_chunk.astype(states.dtype), states)
        carried, final_state = new_states[:, :-1], new_states[:, -1]

    # contribution of carried state to each chunk position
    with jax.named_scope("ssd.off"):
        state_decay = jnp.exp(a_cs)                             # (b,h,c,l)
        y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp",
                           Cr, carried.astype(Cr.dtype),
                           state_decay.astype(Cr.dtype),
                           preferred_element_type=jnp.float32)

    y = (y_diag + y_off).reshape(b, t, h, p)[:, :t_orig]
    return y.astype(x.dtype), final_state


def ssd_decode(x, a, B, C, state):
    """One-step recurrence.  x: (b,h,p); a: (b,h); B,C: (b,g,n);
    state: (b,h,p,n)."""
    b, h, p = x.shape
    g, n = B.shape[1], B.shape[2]
    rep = h // g
    Br = jnp.repeat(B, rep, axis=1)          # (b,h,n)
    Cr = jnp.repeat(C, rep, axis=1)
    da = jnp.exp(a)[..., None, None]         # (b,h,1,1)
    new_state = state * da + jnp.einsum("bhp,bhn->bhpn", x, Br)
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Cr)
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# causal conv


def causal_conv(x, w, b):
    """Depthwise causal conv.  x: (B, T, C); w: (k, C)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k))
    return jax.nn.silu(out + b)


def conv_decode(x, conv_state, w, b):
    """x: (B, C) one step; conv_state: (B, k-1, C) previous inputs."""
    k = w.shape[0]
    window = jnp.concatenate([conv_state, x[:, None]], axis=1)   # (B,k,C)
    out = jnp.einsum("bkc,kc->bc", window, w)
    new_state = window[:, 1:]
    return jax.nn.silu(out + b), new_state


# ---------------------------------------------------------------------------
# full block


def _split_proj(cfg, zxbcdt):
    di = cfg.d_inner
    gn = cfg.ssm_groups * cfg.ssm_state
    h = cfg.ssm_num_heads
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di:di + di + 2 * gn]
    dt = zxbcdt[..., di + di + 2 * gn:]
    assert dt.shape[-1] == h
    return z, xBC, dt


def mamba_block(cfg, params, x):
    """Training / prefill forward.  x: (B, T, D) -> (y, final_state)."""
    Bsz, T, D = x.shape
    di, g, n = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
    h, p = cfg.ssm_num_heads, cfg.ssm_head_dim

    with jax.named_scope("ssm.in_proj"):
        zxbcdt = x @ params["in_proj"]
        z, xBC, dt = _split_proj(cfg, zxbcdt)
    with jax.named_scope("ssm.conv"):
        xBC = causal_conv(xBC, params["conv_w"], params["conv_b"])
    xs = xBC[..., :di].reshape(Bsz, T, h, p)
    Bm = xBC[..., di:di + g * n].reshape(Bsz, T, g, n)
    Cm = xBC[..., di + g * n:].reshape(Bsz, T, g, n)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])                       # (h,)
    y, state = ssd_chunked(xs * dt[..., None].astype(xs.dtype),
                           dt * A, Bm, Cm, cfg.ssm_chunk)
    with jax.named_scope("ssm.out"):
        y = y + (params["D_skip"][:, None]
                 * xs.astype(jnp.float32)).astype(y.dtype)
        y = y.reshape(Bsz, T, di)
        y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                     params["norm_scale"], cfg.norm_eps)
        return y @ params["out_proj"], state


def init_ssm_cache(cfg, batch: int, dtype):
    di, g, n = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
    h, p = cfg.ssm_num_heads, cfg.ssm_head_dim
    return {
        "state": jnp.zeros((batch, h, p, n), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, di + 2 * g * n), dtype),
    }


def mamba_decode(cfg, params, x, cache):
    """Single-token decode.  x: (B, 1, D)."""
    Bsz = x.shape[0]
    di, g, n = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
    h, p = cfg.ssm_num_heads, cfg.ssm_head_dim

    zxbcdt = x[:, 0] @ params["in_proj"]
    z, xBC, dt = _split_proj(cfg, zxbcdt)
    xBC, conv_state = conv_decode(xBC, cache["conv"], params["conv_w"],
                                  params["conv_b"])
    xs = xBC[..., :di].reshape(Bsz, h, p)
    Bm = xBC[..., di:di + g * n].reshape(Bsz, g, n)
    Cm = xBC[..., di + g * n:].reshape(Bsz, g, n)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B,h)
    A = -jnp.exp(params["A_log"])
    y, state = ssd_decode(xs * dt[..., None].astype(xs.dtype),
                          dt * A, Bm, Cm, cache["state"])
    y = y + (params["D_skip"][:, None] * xs.astype(jnp.float32)).astype(y.dtype)
    y = y.reshape(Bsz, di)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                 params["norm_scale"], cfg.norm_eps)
    out = (y @ params["out_proj"])[:, None]
    return out, {"state": state, "conv": conv_state}
