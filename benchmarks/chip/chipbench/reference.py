"""The plain reference's parts that every configuration shares: the
matmul at the reference's precision and its control, and the
robust-training rules, attacks, delivery schedule and optimizer that the
training cells use.

Straightforward ``jax.numpy`` in float32 at ``HIGHEST`` matmul
precision; it imports nothing of the program.  A configuration's model
is checked by the module that its ``"reference"`` key names (for
example ``benchmarks/chip/refs/dense_decoder.py``), which may build on
``mm``.

``mode`` selects the arithmetic: ``"f32"`` is the reference; ``"fp8"``
rounds every matmul operand to float8_e4m3fn first, the control that a
lower precision than the configuration's bfloat16 must fail.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _op(x, mode: str):
    x = x.astype(jnp.float32)
    if mode == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def mm(eq: str, a, b, mode: str = "f32"):
    return jnp.einsum(eq, _op(a, mode), _op(b, mode), precision=HIGHEST,
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# robust aggregation, attacks and the optimizer, coordinate by coordinate;
# every function takes a (n, ...) stack of per-agent leaves


def alie(stack, f: int, z: float):
    """"A little is enough": the first f agents send mean - z * std of the
    honest rows (population std, per coordinate)."""
    honest = stack[f:]
    mu = jnp.mean(honest, axis=0)
    sd = jnp.sqrt(jnp.mean(jnp.square(honest - mu), axis=0) + 1e-12)
    bad = jnp.broadcast_to(mu - z * sd, (f,) + mu.shape)
    return jnp.concatenate([bad, honest], axis=0)


def trimmed_mean(stack, b: int):
    """Drop the b smallest and b largest values of each coordinate and
    average the rest."""
    s = jnp.sort(stack, axis=0)
    return jnp.mean(s[b:stack.shape[0] - b], axis=0)


def multi_krum_select(d2, f: int, m: int):
    """Rows that multi-Krum averages: the m of lowest score, a row's score
    being the sum of its n - f - 2 smallest squared distances to the other
    rows; equal scores go to the lower index.  ``d2`` is the (n, n)
    matrix of squared distances (numpy, float64)."""
    n = d2.shape[0]
    k = max(min(n - f - 2, n - 1), 1)
    d = d2 + np.diag(np.full(n, np.inf))
    scores = np.sort(d, axis=1)[:, :k].sum(axis=1)
    return np.argsort(scores, kind="stable")[:m]


ATTACKS = {"alie": alie}
# coordinate-wise rules: (n, ...) stack -> (...), leaf by leaf
RULES = {"trimmed_mean": trimmed_mean}
# selection rules over whole rows: squared distances -> chosen rows
SELECT = {"multi_krum": multi_krum_select}


def delivery_plan(delays, quorum, steps: int):
    """The asynchronous server's schedule for constant per-agent
    latencies: agent i's gradient arrives ``delays[i]`` after it is sent.
    Every agent sends at version 0; update t waits for ``quorum`` arrivals
    (every agent where None), takes in everything that has arrived by
    then (equal times in agent order), and its contributors send again
    at version t + 1.  Returns (refresh, contrib, staleness), each
    (steps, n): who sends at version t, who is counted in update t, and
    how many versions old each counted gradient is."""
    n = len(delays)
    q = n if quorum is None else max(1, min(int(quorum), n))
    refresh = np.zeros((steps, n), bool)
    contrib = np.zeros((steps, n), bool)
    stale = np.zeros((steps, n), np.int64)
    refresh[0] = True
    flying = sorted((float(delays[i]), i, 0) for i in range(n))
    for t in range(steps):
        now = flying[q - 1][0]
        got = [e for e in flying if e[0] <= now]
        flying = [e for e in flying if e[0] > now]
        for _, i, v in got:
            contrib[t, i], stale[t, i] = True, t - v
            if t + 1 < steps:
                refresh[t + 1, i] = True
            flying.append((now + float(delays[i]), i, t + 1))
        flying.sort()
    return refresh, contrib, stale


def adamw_update(p, g, m, v, step: int, opt: dict):
    """One AdamW step on float32 state; returns (p_new f32, m, v).
    ``step`` counts from 1."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    mhat = m / (1 - b1 ** step)
    vhat = v / (1 - b2 ** step)
    upd = mhat / (jnp.sqrt(vhat) + eps) + opt.get("weight_decay", 0.0) * p
    return p - opt["lr"] * upd, m, v
