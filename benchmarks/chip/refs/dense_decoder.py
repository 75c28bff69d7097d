"""The plain reference of a dense decoder LM (``"family": "dense"``).

Straightforward ``jax.numpy`` in float32 at ``HIGHEST`` matmul
precision, with no kernels, cache or batching tricks; it imports nothing
of the program.  Parameters are read by name from the tree the benchmark
makes (``chipbench.weights``).  Layer equations: pre-norm blocks with
RMSNorm scaled by (1 + w), grouped-query attention with rotary positions
(rotate-half, base ``rope_theta``) and a causal mask, a SwiGLU MLP, a
final RMSNorm and an untied output head.

``mode`` selects the arithmetic (``chipbench.reference.mm``): ``"f32"``
is the reference, ``"fp8"`` the control.  A configuration names this
module by its path in ``"reference"``; the harness calls ``loss`` and
``logits`` for the output check and ``train_flops_per_token`` for the
step's share of the peak.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import mm


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def rope(x, theta):
    """x: (B, T, heads, hd), rotated by position t along its two halves."""
    t = x.shape[1]
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv       # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(m: dict, blk: dict, x, mode: str):
    """One decoder block on (B, T, D) float32."""
    b, t, d = x.shape
    h, k = m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or d // h
    g = h // k
    eps = m.get("norm_eps", 1e-5)
    a = rms_norm(x, blk["attn_norm"], eps)
    at = blk["attn"]
    q = mm("btd,de->bte", a, at["wq"], mode).reshape(b, t, h, hd)
    kk = mm("btd,de->bte", a, at["wk"], mode).reshape(b, t, k, hd)
    v = mm("btd,de->bte", a, at["wv"], mode).reshape(b, t, k, hd)
    theta = m.get("rope_theta", 10000.0)
    q, kk = rope(q, theta), rope(kk, theta)
    # query head j reads kv head j // g
    q = q.reshape(b, t, k, g, hd)
    s = mm("btkgh,bskh->bkgts", q, kk, mode) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("bkgts,bskh->btkgh", p, v, mode).reshape(b, t, h * hd)
    x = x + mm("bte,ed->btd", o, at["wo"], mode)
    a = rms_norm(x, blk["mlp_norm"], eps)
    ml = blk["mlp"]
    gate = mm("btd,df->btf", a, ml["w_gate"], mode)
    up = mm("btd,df->btf", a, ml["w_up"], mode)
    return x + mm("btf,fd->btd", jax.nn.silu(gate) * up, ml["w_down"], mode)


def logits(m: dict, params: dict, tokens, mode: str = "f32"):
    """(B, T) int tokens -> (B, T, V) float32 logits."""
    x = params["embed"].astype(jnp.float32)[tokens]

    def body(h, blk):
        return block(m, blk, h, mode), None
    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = rms_norm(x, params["final_norm"], m.get("norm_eps", 1e-5))
    return mm("btd,dv->btv", x, params["lm_head"], mode)


def loss(m: dict, params: dict, batch: dict, mode: str = "f32"):
    """Mean next-token NLL over every position of the batch."""
    lg = logits(m, params, batch["tokens"], mode)
    logp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(nll)


# ---------------------------------------------------------------------------
# the work a training step must do, counted from the configuration's sizes


def matmul_params(model: dict) -> int:
    """Weights that enter a matmul per token: every block's projections
    and the output head; the input embedding (a gather) and the norms
    are left out."""
    d, hd = model["d_model"], model.get("head_dim") or (
        model["d_model"] // model["num_heads"])
    h, k, ff = model["num_heads"], model["num_kv_heads"], model["d_ff"]
    attn = d * h * hd + 2 * d * k * hd + h * hd * d
    mlp = 3 * d * ff if model.get("act", "silu") == "silu" else 2 * d * ff
    return model["num_layers"] * (attn + mlp) + d * model["vocab_size"]


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward and backward FLOPs per token, recompute not counted:
    6 N + 12 L H hd T (PaLM, arXiv:2204.02311, App. B)."""
    hd = model.get("head_dim") or model["d_model"] // model["num_heads"]
    return (6.0 * matmul_params(model)
            + 12.0 * model["num_layers"] * model["num_heads"] * hd * seq)
