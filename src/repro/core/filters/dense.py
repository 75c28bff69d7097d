"""Gradient filters (robust aggregation rules) — survey §3.3.2 / Table 2.

Reference implementations on dense stacks ``g: (n, d)`` (n agents, d params).
Uniform signature ``filter(g, f, **hyper) -> (d,)``.  All are pure jnp and
jit-able with static ``n``/``f``.  The sharded pytree variants live in
:mod:`repro.core.aggregation`; Pallas kernels for the hot coordinate-wise and
pairwise paths live in :mod:`repro.kernels` — this module is their oracle.

Survey Table 2 coverage: Krum, m-Krum, multi-Krum, coordinate-wise median,
coordinate-wise trimmed mean, Phocas, mean-around-median, geometric median,
median-of-means, MDA, CGC, CGE, Bulyan.  Plus: mean (the provably non-robust
baseline, Blanchard et al.), Zeno (§3.3.4), RFA (smoothed geometric median,
§3.4).
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

FILTERS: dict = {}


def register(name):
    def deco(fn):
        FILTERS[name] = fn
        return fn
    return deco


def get_filter(name: str, **hyper):
    fn = FILTERS[name]
    return functools.partial(fn, **hyper) if hyper else fn


# ---------------------------------------------------------------------------
# helpers


def pairwise_sq_dists(g):
    """(n, d) -> (n, n) squared euclidean distances (MXU-friendly form).
    The diagonal is exactly zero (fp cancellation there is masked)."""
    n = g.shape[0]
    sq = jnp.sum(jnp.square(g), axis=-1)
    gram = g @ g.T
    d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * gram, 0.0)
    return jnp.where(jnp.eye(n, dtype=bool), 0.0, d2)


def take_rows(g, idx):
    """``g[idx]`` for a static-length index vector, as one dynamic slice
    per row.  A row gather out of a model-wide (n, P) stack takes XLA's
    TPU compiler minutes (158 s at P = 124.7M, compiled for v5e); the
    slices compile in under a second and copy the same rows."""
    return jnp.stack([g[idx[k]] for k in range(idx.shape[0])])


def krum_scores(d2, f, mask=None, k=None):
    """Krum score s(i) = sum of distances to the k closest others
    (k defaults to the classic n - f - 2).

    ``mask``: bool (n,) — unavailable agents get +inf distance & +inf score
    (used by iterative m-Krum / Bulyan selection).  Iterative callers MUST
    shrink ``k`` with the remaining candidate count (k = remaining - f - 2):
    once k exceeds candidates - 1 every sum picks up +inf pads, every score
    collapses to inf, and argmin degrades to index order — the selection
    then silently depends on agent NUMBERING, which the membership
    conformance suite (permutation invariance) rejects.
    """
    n = d2.shape[0]
    big = jnp.asarray(jnp.inf, d2.dtype)
    d2 = d2 + jnp.where(jnp.eye(n, dtype=bool), big, 0.0)   # exclude self
    if mask is not None:
        d2 = jnp.where(mask[None, :], d2, big)
    k = (n - f - 2) if k is None else int(k)
    k = max(min(k, n - 1), 1)
    neg_top, _ = jax.lax.top_k(-d2, k)                      # k smallest
    scores = -jnp.sum(neg_top, axis=-1)
    if mask is not None:
        scores = jnp.where(mask, scores, big)
    return scores


def masked_row_sums(d2, mask):
    """Full-degree score: sum of a candidate's distances to ALL remaining
    candidates (masked rows get +inf).  The cheap O(n^2) tie-break
    secondary for the iterative selection loops — equal to
    ``krum_scores(..., k=candidates - 1)`` without its top_k sort."""
    n = d2.shape[0]
    off = ~jnp.eye(n, dtype=bool)
    s = jnp.sum(jnp.where(mask[None, :] & off, d2, 0.0), axis=-1)
    return jnp.where(mask, s, jnp.inf)


def argmin_tiebreak(primary, secondary):
    """Index of the minimum of ``primary``, with EXACT fp ties broken by
    ``secondary`` (and only then by index).  Iterative krum selection ties
    structurally — with one neighbour left, the closest PAIR shares one
    symmetric distance, so both rows carry bitwise-equal scores — and a
    bare argmin would resolve by agent NUMBERING, which elastic membership
    makes arbitrary (rows are re-packed per roster bucket).  Secondary =
    the full-degree score keeps the pick a function of the geometry
    alone."""
    tied = primary == jnp.min(primary)
    return jnp.argmin(jnp.where(tied, secondary, jnp.inf))


# ---------------------------------------------------------------------------
# baseline


@register("mean")
def mean(g, f=0):
    """No defence.  Blanchard et al. [6]: cannot tolerate a single Byzantine
    agent — reproduced in tests/benchmarks."""
    return jnp.mean(g, axis=0)


# ---------------------------------------------------------------------------
# compressed exchange (survey §5.2 scaling / Bernstein et al. signSGD)


@register("sign_sgd")
def sign_sgd(g, f=0):
    """signSGD with majority vote: agents send sign(g_i) (1 bit/coord),
    the server returns the per-coordinate sign of the vote.  The ±1/0
    votes sum EXACTLY in fp32 for n < 2^24, so every impl (gather, fused
    leaf-wise, pallas tile) is bitwise identical.  Output is magnitude-
    bounded (per-coordinate in [-1, 1]) — robust to <= f sign-flippers by
    majority, broken only by a vote majority (the conformance suite's
    bounded-output breakdown law)."""
    return jnp.sign(jnp.sum(jnp.sign(g).astype(jnp.float32), axis=0))


@register("sparse_mean")
def sparse_mean(g, f=0):
    """Sparse/dropout-aware mean: a zero coordinate means NOT SENT (the
    fed_dropout_avg convention), so each coordinate averages only the
    rows that carry it — agg_c = sum_i [g_ic != 0] g_ic / sum_i
    [g_ic != 0], with an explicit 0 where nobody sent the coordinate
    (never an eps-scaled garbage row).  Per-agent weights (dataset
    sizes, staleness discounts) enter via the spec engine's weighted
    path; this dense oracle is the unit-weight case."""
    sent = (g != 0).astype(jnp.float32)
    den = jnp.sum(sent, axis=0)
    num = jnp.sum(g.astype(jnp.float32) * sent, axis=0)
    return jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)


# ---------------------------------------------------------------------------
# angle / distance based


@register("krum")
def krum(g, f):
    d2 = pairwise_sq_dists(g)
    s = krum_scores(d2, f)
    return g[jnp.argmin(s)]


@register("multi_krum")
def multi_krum(g, f, m: int = 2):
    """Second variant of [6, 7]: average of the m smallest-score vectors."""
    d2 = pairwise_sq_dists(g)
    s = krum_scores(d2, f)
    _, idx = jax.lax.top_k(-s, m)
    return jnp.mean(take_rows(g, idx), axis=0)


@register("m_krum")
def m_krum(g, f, m: int = 2):
    """First (iterative) variant: recompute scores after each removal.
    Unrolled (m is static) so the neighbour count shrinks with the
    remaining candidate set — see :func:`krum_scores`."""
    n = g.shape[0]
    d2 = pairwise_sq_dists(g)
    mask = jnp.ones((n,), bool)
    acc = jnp.zeros_like(g[0])
    for it in range(m):
        s = krum_scores(d2, f, mask=mask, k=max(n - it - f - 2, 1))
        i = argmin_tiebreak(s, masked_row_sums(d2, mask))
        mask = mask.at[i].set(False)
        acc = acc + g[i]
    return acc / m


@register("mda")
def mda(g, f):
    """Minimum-diameter averaging [32, 76, 91]: average of the (n-f)-subset
    with smallest diameter.  O(C(n, f)) — static combinatorics, n <= 32."""
    n = g.shape[0]
    combos = np.asarray(list(itertools.combinations(range(n), n - f)))
    if len(combos) > 200_000:
        raise ValueError(f"MDA infeasible for n={n}, f={f}")
    d2 = pairwise_sq_dists(g)
    sub = d2[combos[:, :, None], combos[:, None, :]]   # (C, n-f, n-f)
    diam = jnp.max(sub, axis=(1, 2))
    # equal-diameter subsets tie STRUCTURALLY (different removals that
    # leave the same bottleneck pair): break by subset perimeter, not by
    # enumeration order (see argmin_tiebreak)
    best = jnp.asarray(combos)[
        argmin_tiebreak(diam, jnp.sum(sub, axis=(1, 2)))]
    return jnp.mean(take_rows(g, best), axis=0)


# ---------------------------------------------------------------------------
# coordinate-wise


@register("coordinate_median")
def coordinate_median(g, f=0):
    return jnp.median(g, axis=0)


@register("trimmed_mean")
def trimmed_mean(g, f, beta: float | None = None):
    """Drop the smallest/largest beta-fraction per coordinate [121].
    beta defaults to f/n (the minimum admissible)."""
    n = g.shape[0]
    b = int(np.ceil((beta if beta is not None else f / n) * n)) if n else 0
    b = min(b, (n - 1) // 2)
    s = jnp.sort(g, axis=0)
    kept = s[b:n - b] if b else s
    return jnp.mean(kept, axis=0)


@register("phocas")
def phocas(g, f):
    """Phocas [117]: mean of the n-f values per coordinate closest to the
    trimmed mean."""
    n = g.shape[0]
    tm = trimmed_mean(g, f)
    return _mean_closest(g, tm, n - f)


@register("mean_around_median")
def mean_around_median(g, f):
    """[116]: per-coordinate mean of the n-f values closest to the median."""
    n = g.shape[0]
    med = jnp.median(g, axis=0)
    return _mean_closest(g, med, n - f)


def _mean_closest(g, center, k):
    """Per-coordinate mean of the k values closest to ``center``."""
    dist = jnp.abs(g - center[None, :])                     # (n, d)
    neg_top, idx = jax.lax.top_k(-dist.T, k)                # (d, k) smallest
    vals = jnp.take_along_axis(g.T, idx, axis=1)            # (d, k)
    return jnp.mean(vals, axis=1)


# ---------------------------------------------------------------------------
# median based


@register("geometric_median")
def geometric_median(g, f=0, iters: int = 32, eps: float = 1e-8):
    """Weiszfeld fixed-point iteration for the geometric median [19, 21]."""
    y = jnp.mean(g, axis=0)

    def body(y, _):
        d = jnp.sqrt(jnp.sum(jnp.square(g - y[None]), axis=-1))
        w = 1.0 / jnp.maximum(d, eps)
        y = jnp.sum(w[:, None] * g, axis=0) / jnp.sum(w)
        return y, None
    y, _ = jax.lax.scan(body, y, None, length=iters)
    return y


@register("rfa")
def rfa(g, f=0, iters: int = 32, nu: float = 1e-6):
    """RFA [83]: smoothed Weiszfeld (federated robust aggregation)."""
    return geometric_median(g, f, iters=iters, eps=nu)


@register("median_of_means")
def median_of_means(g, f, num_groups: int | None = None):
    """[19]: partition into k > 2f groups, geometric median of group means."""
    n = g.shape[0]
    k = num_groups if num_groups else min(n, 2 * f + 1) if f else n
    while n % k:
        k += 1
    means = jnp.mean(g.reshape(k, n // k, -1), axis=1)
    return geometric_median(means, 0)


# ---------------------------------------------------------------------------
# norm based


@register("cge")
def cge(g, f, normalize: bool = True):
    """Comparative gradient elimination [43, 46, 49]: keep the n-f
    smallest-norm vectors.  Survey eq. (24) uses the raw sum
    (normalize=False); the practical variant averages."""
    n = g.shape[0]
    norms = jnp.linalg.norm(g, axis=-1)
    neg_top, idx = jax.lax.top_k(-norms, n - f)
    out = jnp.sum(take_rows(g, idx), axis=0)
    return out / (n - f) if normalize else out


@register("cgc")
def cgc(g, f, normalize: bool = True):
    """Comparative gradient clipping: scale the f largest norms down to the
    (n-f)-th smallest norm, keep everything (survey eq. 24)."""
    n = g.shape[0]
    norms = jnp.linalg.norm(g, axis=-1)
    tau = jnp.sort(norms)[n - f - 1]
    scale = jnp.minimum(1.0, tau / jnp.maximum(norms, 1e-30))
    out = jnp.sum(scale[:, None] * g, axis=0)
    return out / n if normalize else out


# ---------------------------------------------------------------------------
# meta


@register("bulyan")
def bulyan(g, f, base: str = "krum"):
    """Bulyan [76]: (1) select n-2f vectors by iterating ``base`` (closest-to
    -output each round), (2) per coordinate, average the theta-2f values
    closest to the median of the selected set."""
    n = g.shape[0]
    theta = n - 2 * f
    assert theta >= 1, "Bulyan needs n > 2f (and n >= 4f+3 for guarantees)"
    base_fn = FILTERS[base]

    # unrolled (theta is static): the krum neighbour count must shrink
    # with the remaining candidate set or every score collapses to inf
    # once fewer than n - f - 1 candidates remain (see krum_scores) — the
    # old scan selected only f + 2 genuine rows and tie-broke the rest by
    # agent index
    d2 = pairwise_sq_dists(g) if base == "krum" else None
    mask = jnp.ones((n,), bool)
    sel = jnp.zeros((n,), bool)
    for it in range(theta):
        # run base filter on the still-available set (mask via +inf trick
        # for krum; generic base: weight unavailable rows to the mean)
        if base == "krum":
            s = krum_scores(d2, f, mask=mask, k=max(n - it - f - 2, 1))
            i = argmin_tiebreak(s, masked_row_sums(d2, mask))
        else:
            avail_mean = (jnp.sum(jnp.where(mask[:, None], g, 0.0), axis=0)
                          / jnp.maximum(jnp.sum(mask), 1))
            out = base_fn(jnp.where(mask[:, None], g, avail_mean[None]), f)
            d = jnp.sum(jnp.square(g - out[None]), axis=-1)
            d = jnp.where(mask, d, jnp.inf)
            i = jnp.argmin(d)
        mask = mask.at[i].set(False)
        sel = sel.at[i].set(True)

    # stage 2: coordinate-wise trimmed average around the median of selected
    beta = max(theta - 2 * f, 1)
    big = jnp.asarray(jnp.inf, g.dtype)
    med = _masked_median(g, sel)
    dist = jnp.where(sel[:, None], jnp.abs(g - med[None]), big)
    neg_top, idx = jax.lax.top_k(-dist.T, beta)     # (d, beta)
    vals = jnp.take_along_axis(g.T, idx, axis=1)
    return jnp.mean(vals, axis=1)


def _masked_median(g, mask):
    """Median over rows where mask is True (count = sum(mask), static via
    sorting with +/- inf padding)."""
    n = g.shape[0]
    cnt = jnp.sum(mask)
    big = jnp.asarray(jnp.inf, g.dtype)
    padded = jnp.where(mask[:, None], g, big)
    s = jnp.sort(padded, axis=0)
    lo = (cnt - 1) // 2
    hi = cnt // 2
    return 0.5 * (s[lo] + s[hi])


# ---------------------------------------------------------------------------
# Zeno (server-validation based, §3.3.4)


@register("zeno")
def zeno(g, f, server_grad=None, rho: float = 1e-3, lr: float = 1.0):
    """Zeno [118]: suspicion score via a server-held validation gradient v:
    score_i = lr * <v, g_i> - rho * ||g_i||^2 ; average the n-f highest."""
    assert server_grad is not None, "zeno requires server_grad"
    n = g.shape[0]
    score = lr * (g @ server_grad) - rho * jnp.sum(jnp.square(g), axis=-1)
    _, idx = jax.lax.top_k(score, n - f)
    return jnp.mean(take_rows(g, idx), axis=0)


# ---------------------------------------------------------------------------
# filter combinators (survey §5.1 "future work": combinations of filters)


def compose(*names_or_fns, f_each=None):
    """Sequential composition is ill-typed ((n,d)->(d,)); instead this builds
    the *parallel ensemble*: run each filter, then output the coordinate-wise
    median of their outputs — the survey's suggested direction of applying
    multiple different filters in one algorithm."""
    fns = [FILTERS[x] if isinstance(x, str) else x for x in names_or_fns]

    def ensemble(g, f):
        outs = jnp.stack([fn(g, f) for fn in fns], axis=0)
        return jnp.median(outs, axis=0)
    return ensemble
