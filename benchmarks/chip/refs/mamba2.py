"""The plain reference of a Mamba-2 LM (``"family": "ssm"``).

Straightforward ``jax.numpy`` in float32 at ``HIGHEST`` matmul
precision; it imports nothing of the program.  Parameters are read by
name from the tree the benchmark makes (``chipbench.weights``).  The SSD
layer of arXiv:2405.21060 is computed as its sequential recurrence, one
``lax.scan`` step per position, never in the chunked form the program
uses, so the check also checks the duality the program relies on:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,    y_t = C_t h_t + D x_t

per head (x_t of the head's width P, B_t and C_t of its group's state
size N, h_t of shape (P, N)), with dt_t = softplus(dt_raw + dt_bias) and
A = -exp(A_log).  Around it: RMSNorm, the input projection into
(z, x, B, C, dt), a causal depthwise convolution with bias and SiLU over
(x, B, C), the gate y * SiLU(z) before the inner RMSNorm, the output
projection and the residual; then the final RMSNorm and the head tied to
the embedding.

Where the program departs from ``mamba_ssm`` (``Mamba2`` with
``rmsnorm=True, norm_before_gate=False``), this follows the program:
every RMSNorm scales by (1 + w), with w stored as 0 at the start, where
``mamba_ssm`` scales by w stored as 1; the vocabulary is the
configuration's ``vocab_size`` as it stands, not padded to a multiple
of 16; dt is not clamped (``mamba_ssm``'s default ``dt_limit`` is
(0, inf)).  The program keeps the residual stream in the configuration's
dtype between layers, where ``mamba_ssm`` can keep it in float32
(``residual_in_fp32``); here every quantity is float32 throughout.

``mode`` selects the arithmetic (``chipbench.reference.mm``): ``"f32"``
is the reference, ``"fp8"`` the control.  Each layer is checkpointed, so
the backward pass holds one layer's per-position states at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import mm


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def _sizes(m: dict):
    d_inner = m.get("ssm_expand", 2) * m["d_model"]
    p = m.get("ssm_head_dim", 64)
    return d_inner, d_inner // p, p, m.get("ssm_groups", 1), m["ssm_state"]


def causal_conv(x, w, b):
    """Depthwise causal convolution with bias and SiLU.  x: (B, T, C);
    w: (k, C), tap k - 1 on the current position."""
    k, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    out = sum(xp[:, i:i + t] * w[i] for i in range(k))
    return jax.nn.silu(out + b.astype(jnp.float32))


def ssd_recurrence(x, dt, A, B, C):
    """The SSD layer as its recurrence over time.  x: (b, T, H, P);
    dt: (b, T, H) (after softplus); A: (H,); B, C: (b, T, G, N), head j
    reads group j // (H / G).  Returns y: (b, T, H, P), D skip not
    added."""
    b, _, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    grp = jnp.arange(h) // (h // g)
    Bh, Ch = B[:, :, grp], C[:, :, grp]                    # (b, T, H, N)

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs
        decay = jnp.exp(dt_t * A)[..., None, None]         # (b, H, 1, 1)
        state = decay * state + jnp.einsum(
            "bhp,bhn->bhpn", x_t * dt_t[..., None], b_t,
            precision=jax.lax.Precision.HIGHEST)
        y_t = jnp.einsum("bhpn,bhn->bhp", state, c_t,
                         precision=jax.lax.Precision.HIGHEST)
        return state, y_t

    seq = [jnp.moveaxis(a, 1, 0) for a in (x, dt, Bh, Ch)]
    _, y = jax.lax.scan(step, jnp.zeros((b, h, p, n), jnp.float32), seq,
                        unroll=16)
    return jnp.moveaxis(y, 0, 1)


def mixer(m: dict, blk: dict, a, mode: str):
    """One Mamba-2 mixer on the normed (B, T, D) float32 input."""
    bsz, t, _ = a.shape
    di, h, p, g, n = _sizes(m)
    zxbcdt = mm("btd,de->bte", a, blk["in_proj"], mode)
    z = zxbcdt[..., :di]
    xbc = causal_conv(zxbcdt[..., di:2 * di + 2 * g * n], blk["conv_w"],
                      blk["conv_b"])
    dt = jax.nn.softplus(zxbcdt[..., 2 * di + 2 * g * n:]
                         + blk["dt_bias"].astype(jnp.float32))
    x = xbc[..., :di].reshape(bsz, t, h, p)
    B = xbc[..., di:di + g * n].reshape(bsz, t, g, n)
    C = xbc[..., di + g * n:].reshape(bsz, t, g, n)
    A = -jnp.exp(blk["A_log"].astype(jnp.float32))
    y = ssd_recurrence(x, dt, A, B, C)
    y = y + blk["D_skip"].astype(jnp.float32)[:, None] * x
    y = y.reshape(bsz, t, di) * jax.nn.silu(z)
    y = rms_norm(y, blk["norm_scale"], m.get("norm_eps", 1e-5))
    return mm("bte,ed->btd", y, blk["out_proj"], mode)


def logits(m: dict, params: dict, tokens, mode: str = "f32"):
    """(B, T) int tokens -> (B, T, V) float32 logits."""
    eps = m.get("norm_eps", 1e-5)
    x = params["embed"].astype(jnp.float32)[tokens]

    @jax.checkpoint
    def layer(h, blk):
        return h + mixer(m, blk["ssm"], rms_norm(h, blk["norm"], eps),
                         mode)

    def body(h, blk):
        return layer(h, blk), None
    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = rms_norm(x, params["final_norm"], eps)
    return mm("btd,vd->btv", x, params["embed"], mode)


def loss(m: dict, params: dict, batch: dict, mode: str = "f32"):
    """Mean next-token NLL over every position of the batch."""
    lg = logits(m, params, batch["tokens"], mode)
    logp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(nll)


# ---------------------------------------------------------------------------
# the work a training step must do, counted from the configuration's sizes


def matmul_params(model: dict) -> int:
    """Weights that enter a matmul per token: each layer's input and
    output projections and the tied head; the embedding gather, the
    convolution, the norms and the per-head A, dt bias and D are left
    out."""
    di, h, _, g, n = _sizes(model)
    d = model["d_model"]
    per_layer = d * (2 * di + 2 * g * n + h) + di * d
    return model["num_layers"] * per_layer + d * model["vocab_size"]


def ssd_flops_per_token(model: dict) -> int:
    """Forward FLOPs per token of the chunked SSD (arXiv:2405.21060,
    section 6 and its Listing 1), counting whole chunk blocks of Q
    positions: C B^T inside the chunk (2 Q N per group), its product with
    x (2 Q H P), the chunk's final state and the carried state's output
    (2 H P N each)."""
    _, h, p, g, n = _sizes(model)
    q = model.get("ssm_chunk", 256)
    return model["num_layers"] * (2 * q * n * g + 2 * q * h * p
                                  + 4 * h * p * n)


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward and backward FLOPs per token, recompute not counted: 6 N
    (arXiv:2001.08361, section 2.1) plus three times the SSD's forward
    count (backward twice the forward, as for the matmuls).  The SSD
    count is per whole chunk block, so it does not grow with ``seq``."""
    return 6.0 * matmul_params(model) + 3.0 * ssd_flops_per_token(model)
