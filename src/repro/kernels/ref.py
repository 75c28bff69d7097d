"""Pure-jnp oracles for the Pallas kernels (allclose targets in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def coord_sort_ref(g):
    return jnp.sort(g.astype(jnp.float32), axis=0)


def gram_ref(g):
    x = g.astype(jnp.float32)
    return x @ x.T


def weighted_sum_ref(w, g):
    return w.astype(jnp.float32) @ g.astype(jnp.float32)


def median_from_sorted(s):
    n = s.shape[0]
    return 0.5 * (s[(n - 1) // 2] + s[n // 2])


def trimmed_mean_from_sorted(s, b: int):
    n = s.shape[0]
    kept = s[b:n - b] if b else s
    return jnp.mean(kept, axis=0)


def masked_impute_ref(g, mask, wn):
    """Mean-imputed stack, arithmetic mirroring the engine's masked path
    for the PAIRWISE rule family: fp32 weighted mean of arrived rows ->
    native-dtype round trip -> row-select.  Oracle for the Gram-based
    masked kernels.  The coordinate-wise rules do NOT use this law — the
    delivered mean is not robust, so a mean-imputed ghost row lands
    inside the trim window under attack; they use the arrived-window
    statistics below instead."""
    mean = weighted_row_sum(g, wn).astype(g.dtype)
    return jnp.where(mask.astype(bool)[:, None], g, mean[None])


def arrived_stat_from_sorted(s, mask, stat: str, b: int = 0):
    """Order statistic over the ARRIVED rows only.

    ``s``: (n, t) fp32, per-coordinate ascending sort of the stack with
    absent rows replaced by +inf (they occupy the top ``n - cnt`` ranks
    of every column, so the arrived values sit in ranks ``[0, cnt)``).
    The kept rank window is computed from the traced arrived count:

      * ``median``        — ranks ``[(cnt-1)//2, cnt - (cnt-1)//2)``
        (one rank when cnt is odd, the two middle ranks when even — the
        window mean IS the median);
      * ``trimmed_mean``  — ranks ``[b', cnt - b')`` with
        ``b' = min(b, (cnt-1)//2)``: the per-side trim clamps so the
        window never empties; below ``2b + 1`` arrivals the statistic
        degrades gracefully to the median of the arrived rows.

    The window indicator depends only on the scalar count, so the whole
    statistic is one sort + one masked reduce — fixed shapes, traced
    mask, no recompiles.  Zero arrivals return an exact 0 (the engine's
    zero-total guard scales the update to 0 anyway)."""
    import jax
    n = s.shape[0]
    cnt = jnp.sum(mask.astype(jnp.float32) > 0.5).astype(jnp.int32)
    if stat == "median":
        lo = (cnt - 1) // 2
    elif stat == "trimmed_mean":
        lo = jnp.minimum(jnp.int32(b), (cnt - 1) // 2)
    else:
        raise KeyError(stat)
    lo = jnp.maximum(lo, 0)
    hi = cnt - lo
    ranks = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    keep = (ranks >= lo) & (ranks < hi)
    width = jnp.maximum(hi - lo, 1).astype(jnp.float32)
    out = jnp.sum(jnp.where(keep, s, 0.0), axis=0) / width
    return jnp.where(cnt > 0, out, 0.0)


def masked_stat_ref(g, mask, wn, stat: str, b: int = 0):
    """(d,) fp32 oracle for masked_coord_stat: the arrived-window law
    (absent rows are +inf sort sentinels, never statistics)."""
    mb = mask.astype(bool)
    s = jnp.sort(jnp.where(mb[:, None], g.astype(jnp.float32), jnp.inf),
                 axis=0)
    return arrived_stat_from_sorted(s, mask, stat, b)


def arrived_mean_closest_ref(g, mask, stat: str, f: int):
    """(d,) fp32: the phocas / mean_around_median trust window over the
    ARRIVED rows only.

    Two count-windowed stages, both rank-indexed by the traced arrived
    count (fixed shapes, no recompiles):

      1. center — :func:`arrived_stat_from_sorted` on the +inf-sentinel
         sort (``trimmed_mean`` with b=f for phocas, ``median`` for
         mean_around_median);
      2. window — per coordinate, the ``k = clip(cnt - f, 1, cnt)``
         arrived values closest to the center, averaged.  Absent rows get
         +inf distances (their garbage never enters the distance, the
         ranking, or the sum — rank gating is a where-select, so inf/NaN
         cannot leak through a zero weight).

    Below ``f + 1`` arrivals the window degrades gracefully to the single
    closest arrived value; zero arrivals return an exact 0 (the engine's
    zero-total guard scales the update to 0 anyway)."""
    import jax
    mb = mask.astype(bool)
    xf = g.astype(jnp.float32)
    s = jnp.sort(jnp.where(mb[:, None], xf, jnp.inf), axis=0)
    b = f if stat == "trimmed_mean" else 0
    center = arrived_stat_from_sorted(s, mask, stat, b=b)
    cnt = jnp.sum(mask.astype(jnp.float32) > 0.5).astype(jnp.int32)
    k = jnp.clip(cnt - jnp.int32(f), 1, jnp.maximum(cnt, 1))
    dist = jnp.where(mb[:, None], jnp.abs(xf - center[None]), jnp.inf)
    order = jnp.argsort(dist, axis=0)           # stable: ties keep row order
    ranks = jnp.argsort(order, axis=0)
    keep = ranks < k
    out = jnp.sum(jnp.where(keep, xf, 0.0), axis=0) / k.astype(jnp.float32)
    return jnp.where(cnt > 0, out, 0.0)


def weighted_row_sum(x, w):
    """sum_i w_i x_i over the leading (agent) axis, in fp32, as ONE
    contraction.  THE one copy of every weighted row sum whose bits a
    parity test compares across paths (the imputed mean, the sparse
    law's numerator): written as a multiply and a reduce, XLA's CPU
    backend contracts the pair into FMAs in some fusion contexts and not
    in others, which moves results by an ulp between two calls of the
    same law; a dot is emitted the same way wherever it appears.
    ``HIGHEST`` keeps the TPU from running the fp32 contraction in bf16."""
    return jnp.tensordot(w.astype(jnp.float32), x.astype(jnp.float32),
                         axes=1, precision=jax.lax.Precision.HIGHEST)


def sparse_mean_law(xf, w):
    """xf: (n, t) fp32, w: (n,) fp32 row weights (0 on dead rows) -> (t,)
    ``agg_c = sum_i w_i x_ic / sum_i w_i`` over the rows that SENT
    coordinate c (x_ic != 0), explicit 0 where nobody did (never an
    eps-scaled garbage row).  The where-gate keeps 0 * non-finite == 0
    exactly, so dead-row garbage cannot leak through a zero weight.
    THE one copy of the law: the sparse kernels' tile body
    (repro.kernels.wsum) and the engine's jnp path both call it."""
    sent = xf != 0.0
    xs = jnp.where(sent & (w[:, None] > 0.0), xf, 0.0)
    num = jax.lax.dot_general(
        w[None], xs, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)[0]
    den = jnp.sum(jnp.where(sent, w[:, None], 0.0), axis=0)
    return jnp.where(den > 0.0, num / jnp.where(den > 0.0, den, 1.0), 0.0)


def masked_sign_vote_ref(g, mask):
    """(d,) fp32 oracle for masked_sign_vote: majority vote over the
    arrived rows only (absent rows cast no vote)."""
    votes = jnp.sign(g.astype(jnp.float32)) * mask.astype(jnp.float32)[:, None]
    return jnp.sign(jnp.sum(votes, axis=0))


def krum_select_ref(g, f: int):
    """(n,) one-hot Krum selection oracle (dense scores + argmin)."""
    import jax

    from repro.core.filters.dense import krum_scores, pairwise_sq_dists
    s = krum_scores(pairwise_sq_dists(g.astype(jnp.float32)), f)
    return jax.nn.one_hot(jnp.argmin(s), g.shape[0], dtype=jnp.float32)


def cge_select_ref(g, n_keep: int):
    """(n,) {0,1} smallest-norm keep-mask oracle (top_k selection)."""
    import jax
    norms = jnp.linalg.norm(g.astype(jnp.float32), axis=-1)
    _, idx = jax.lax.top_k(-norms, n_keep)
    return jnp.zeros((g.shape[0],), jnp.float32).at[idx].set(1.0)
