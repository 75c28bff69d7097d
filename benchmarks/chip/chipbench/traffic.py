"""The one traffic generator: turns a traffic file's parameters and a
seed into the requests or the training batches a run feeds the program.

Every seed gets the same work: a serving window holds the same prompt
and answer lengths at the same arrival times whatever the seed, in one
order drawn from the mix's parameters alone; the seed draws only the
prompt tokens.  Two runs of one seed get identical inputs.
"""
from __future__ import annotations

import numpy as np


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), 0]))


def exact_counts(weights, total: int) -> np.ndarray:
    """Split ``total`` items over ``weights`` by largest remainder, so the
    counts follow the weights as closely as whole numbers can."""
    w = np.asarray(weights, np.float64)
    share = w / w.sum() * total
    counts = np.floor(share).astype(int)
    order = np.argsort(-(share - counts), kind="stable")
    counts[order[:total - counts.sum()]] += 1
    return counts


def menu_draw(values, weights, total: int, rng) -> np.ndarray:
    """``total`` values in the exact proportions of ``weights``, in an
    order drawn from ``rng``."""
    out = np.repeat(np.asarray(values, int), exact_counts(weights, total))
    rng.shuffle(out)
    return out


def serve_requests(tr: dict, seed: int, seconds: float,
                   vocab: int) -> list[dict]:
    """The open-loop schedule of one window: ``rate_per_s * seconds``
    requests, each ``{"due": s, "tokens": (T,) int32, "max_new": m}``
    with ``due`` in seconds from the window's start, ascending.

    Inter-arrival gaps are the quantiles of an exponential distribution
    of the mix's rate (a Poisson process with its randomness taken out of
    the count and the sizes).  Sizes and gaps are ordered by a fixed
    stream, not by ``seed``: the order decides how many tokens fall
    inside the window and how requests queue behind one another, so a
    seed that reordered them would change the work it measures."""
    n = max(int(round(tr["rate_per_s"] * seconds)), 1)
    order = _rng(0)
    prompts = menu_draw(tr["prompt_lens"], tr["prompt_weights"], n, order)
    answers = menu_draw(tr["new_tokens"], tr["new_token_weights"], n, order)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    order.shuffle(gaps)
    rng = _rng(seed)
    # request k is due after the first k gaps; all n gaps fill the window
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (
        seconds / gaps.sum())
    return [{"due": float(due[i]),
             "tokens": rng.integers(0, vocab, size=int(prompts[i]),
                                    dtype=np.int64).astype(np.int32),
             "max_new": int(answers[i])} for i in range(n)]


def train_feed(tr: dict, n_agents: int, vocab: int, seed: int):
    """A dataset for the training loop: ``batch(key, step)`` returns
    ``{"tokens", "labels"}`` of shape (n_agents, per_agent_batch, seq),
    drawn from the seed and the step alone (the loop's key is ignored,
    so the program's own key handling cannot change the data).

    Each row is an arithmetic sequence ``start + k * stride (mod V)``
    from its own random start: a learnable language, with rows that all
    differ within and across steps."""
    import jax
    import jax.numpy as jnp

    from chipbench.weights import seed_key
    b, seq, stride = tr["per_agent_batch"], tr["seq"], tr["stride"]
    base = jnp.asarray(seed_key(seed))

    @jax.jit
    def make(base, step):
        starts = jax.random.randint(jax.random.fold_in(base, step),
                                    (n_agents, b), 0, vocab)
        ks = jnp.arange(seq + 1)
        rows = (starts[..., None] + ks * stride) % vocab
        return {"tokens": rows[..., :-1].astype(jnp.int32),
                "labels": rows[..., 1:].astype(jnp.int32)}

    return lambda step: make(base, jnp.asarray(step, jnp.int32))
