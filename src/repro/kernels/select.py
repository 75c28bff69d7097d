"""Pallas TPU kernels: the full selection family on the (n, n) Gram.

:mod:`repro.kernels.pairwise` reduces the O(n^2 d) work of the
distance-based filters to one tiled MXU pass; what remains is the O(n^2)
*selection* — Krum scores + argmin, CGE's smallest-norm top-k, multi-Krum's
top-m, and the shrinking-candidate iterative selections of m-Krum and
Bulyan.  These fit in a single VMEM block, so each runs as one grid-step
kernel producing either (n,) application weights (Krum/CGE) or an (n,)
int32 selection ORDER (position each row was picked at, sentinel = not
picked) that :func:`repro.kernels.wsum.ordered_apply` accumulates in
exactly the dense reference's summation order — that order-match is what
makes the multi-row rules bit-for-bit with ``impl="gather"``.

The iterative kernels honor the shrinking-candidate contract of
``repro.core.filters.dense.krum_scores``: the neighbour count k shrinks
with the remaining candidate set (k = remaining - f - 2, clamped) and
exact fp score ties break by the full-degree secondary score then first
index (``argmin_tiebreak``), so the membership-conformance permutation
invariants hold on the kernel path unmodified.

Bulyan's coordinate stage (:func:`bulyan_coord`) is also fused: median of
the selected set + mean of the beta closest values per coordinate run
inside the tile via iterative first-index min-extraction — no (n, d)
distance or sorted copy ever reaches HBM.

No ``jnp.sort`` / ``top_k`` inside the kernels: ordering is computed with a
static odd-even transposition network (rows of the distance matrix) and
exact comparison-rank selection with first-index tie-breaking — the same
selection ``jax.lax.top_k`` / ``argmin`` produce, so the chosen rows match
the dense reference bit-for-bit whenever the scores are not exactly tied.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.coord_stats import _sort_network
from repro.kernels.tiling import block_d, resolve_interpret


def _rank(values, ascending: bool = True):
    """Exact comparison rank with first-index tie-break: rank[i] = number
    of j that order strictly before i.  Matches argmin / top_k(-v) order.

    NaN scores (an inf-coordinate adversarial gradient turns the whole d2
    row NaN) are ordered LAST: NaN compares False against everything, so
    without the rewrite every NaN row would get rank 0 and the "one-hot"
    selection would silently become multi-hot — handing the adversary
    exactly the multi-row average the rule exists to prevent."""
    worst = jnp.float32(jnp.inf) if ascending else -jnp.float32(jnp.inf)
    values = jnp.where(jnp.isnan(values), worst, values)
    n = values.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)   # row = candidate i
    j = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    vi, vj = values[:, None], values[None, :]
    before = (vj < vi) if ascending else (vj > vi)
    before = before | ((vj == vi) & (j < i))
    return jnp.sum(before.astype(jnp.int32), axis=1)     # (n,)


def _first_true(b, axis: int):
    """Keep only the first True along ``axis`` (lowest index) — the
    first-index tie-break, as an iota comparison over the static axis
    (Mosaic has no cumsum)."""
    idx = jax.lax.broadcasted_iota(jnp.int32, b.shape, axis)
    first = jnp.min(jnp.where(b, idx, b.shape[axis]), axis=axis,
                    keepdims=True)
    return idx == first


def _eye_and_diag(gr):
    """(n, n) bool identity + the Gram diagonal as a (n,) vector, without
    a gather — THE one copy of the diagonal-extraction trick."""
    n = gr.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return eye, jnp.sum(jnp.where(eye, gr, 0.0), axis=1)


def _d2_from_gram(gr):
    eye, sq = _eye_and_diag(gr)
    d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * gr, 0.0)
    # NaN distances (inf - inf against a non-finite adversary row) order
    # LAST, like _rank's score policy: one NaN would otherwise DUPLICATE
    # through the sort network's min/max pairs and poison every finite
    # row's score.  Exact no-op on finite stacks.
    d2 = jnp.where(jnp.isnan(d2), jnp.float32(jnp.inf), d2)
    return jnp.where(eye, jnp.float32(jnp.inf), d2)      # self excluded


def _krum_select_kernel(gram_ref, out_ref, *, f):
    """(n, n) Gram -> (1, n) one-hot weights of the Krum minimizer."""
    gr = gram_ref[...].astype(jnp.float32)
    n = gr.shape[0]
    d2 = _d2_from_gram(gr)
    # per-row ascending sort of distances-to-others via the same static
    # network the coordinate kernels use (columns = rows of d2)
    srt = _sort_network(d2.T)                            # (n, n) cols sorted
    k = max(n - f - 2, 1)
    scores = jnp.sum(srt[:k], axis=0)                    # (n,)
    out_ref[...] = (_rank(scores) == 0).astype(jnp.float32)[None]


def _cge_select_kernel(gram_ref, out_ref, *, n_keep):
    """(n, n) Gram -> (1, n) {0,1} mask of the n_keep smallest-norm rows
    (norms off the Gram diagonal) — CGE's comparative elimination."""
    gr = gram_ref[...].astype(jnp.float32)
    _, sq = _eye_and_diag(gr)
    norms = jnp.sqrt(jnp.maximum(sq, 0.0))
    out_ref[...] = (_rank(norms) < n_keep).astype(jnp.float32)[None]


@functools.partial(jax.jit, static_argnames=("f", "interpret"))
def krum_select(gr, f: int, *, interpret: bool | None = None):
    """gr: (n, n) Gram -> (n,) one-hot fp32 Krum selection weights."""
    interpret = resolve_interpret(interpret)
    n = gr.shape[0]
    return pl.pallas_call(
        functools.partial(_krum_select_kernel, f=f),
        grid=(1,),
        in_specs=[pl.BlockSpec((n, n), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(gr)[0]


@functools.partial(jax.jit, static_argnames=("n_keep", "interpret"))
def cge_select(gr, n_keep: int, *, interpret: bool | None = None):
    """gr: (n, n) Gram -> (n,) {0,1} fp32 keep-mask of the n_keep
    smallest-norm rows (unnormalized: the caller divides after the sum,
    exactly like the dense reference)."""
    interpret = resolve_interpret(interpret)
    n = gr.shape[0]
    return pl.pallas_call(
        functools.partial(_cge_select_kernel, n_keep=n_keep),
        grid=(1,),
        in_specs=[pl.BlockSpec((n, n), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(gr)[0]


# ---------------------------------------------------------------------------
# selection ORDERS — (n,) int32, order[i] = position row i was picked at
# (in [0, k)), sentinel n if not picked.  The application stage replays
# the picks in this order, matching the dense reference's summation order
# bit-for-bit (jnp.mean over a top_k gather sums rank-ascending; the
# iterative rules sum pick-ascending).


def _multi_krum_order_kernel(gram_ref, out_ref, *, f, m):
    """multi-Krum: ONE score pass (classic k = n - f - 2), the m smallest
    scores selected simultaneously — order = score rank, exactly
    ``jax.lax.top_k(-scores, m)``'s output order."""
    gr = gram_ref[...].astype(jnp.float32)
    n = gr.shape[0]
    srt = _sort_network(_d2_from_gram(gr).T)
    k = max(min(n - f - 2, n - 1), 1)
    scores = jnp.sum(srt[:k], axis=0)
    rank = _rank(scores)
    out_ref[...] = jnp.where(rank < m, rank, n).astype(jnp.int32)[None]


def _iterative_order_kernel(gram_ref, out_ref, *, f, k_total):
    """Shrinking-candidate iterative Krum selection (m-Krum's m picks,
    Bulyan's theta picks): per round, Krum scores over the remaining
    candidate set with the SHRINKING neighbour count
    k = remaining - f - 2 (clamped), exact fp ties broken by the
    full-degree score then first index — the
    ``D.krum_scores``/``D.argmin_tiebreak`` contract, replicated
    comparison-for-comparison so the kernel picks exactly the dense
    reference's rows (the membership suite's permutation invariance
    depends on it)."""
    gr = gram_ref[...].astype(jnp.float32)
    n = gr.shape[0]
    big = jnp.float32(jnp.inf)
    eye, sq = _eye_and_diag(gr)
    d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * gr, 0.0)
    d2 = jnp.where(jnp.isnan(d2), big, d2)         # NaN orders last
    d2 = jnp.where(eye, 0.0, d2)                   # raw d2 (tie-break base)
    d2_self = jnp.where(eye, big, d2)              # self excluded for scores
    cand = jnp.ones((n,), bool)
    order = jnp.full((n,), n, jnp.int32)
    for it in range(k_total):
        k = max(min(max(n - it - f - 2, 1), n - 1), 1)
        srt = _sort_network(jnp.where(cand[None, :], d2_self, big).T)
        s = jnp.sum(srt[:k], axis=0)
        s = jnp.where(jnp.isnan(s), big, s)          # NaN orders last
        key = jnp.where(cand, s, big)
        sec = jnp.sum(jnp.where(cand[None, :] & ~eye, d2, 0.0), axis=1)
        sec = jnp.where(jnp.isnan(sec), big, sec)
        # candidate-CONSTRAINED argmin_tiebreak: every comparison set is
        # intersected with `cand`, so even an all-inf round (NaN-poisoned
        # adversary) picks a genuine candidate instead of re-picking a
        # removed row by index order; on finite data this is exactly
        # D.argmin_tiebreak (removed rows carry +inf primary AND
        # secondary there, so they never win a finite comparison)
        tied = (key == jnp.min(key)) & cand
        sec_eff = jnp.where(tied, sec, big)
        pool = tied & (sec_eff == jnp.min(sec_eff))
        pick = _first_true(pool, axis=0)
        order = jnp.where(pick, it, order)
        cand = cand & ~pick
    out_ref[...] = order[None]


def _order_call(kernel, gr, *, interpret):
    interpret = resolve_interpret(interpret)
    n = gr.shape[0]
    return pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((n, n), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
    )(gr)[0]


@functools.partial(jax.jit, static_argnames=("f", "m", "interpret"))
def multi_krum_order(gr, f: int, m: int, *, interpret: bool | None = None):
    """(n, n) Gram -> (n,) int32 order of the m smallest-score rows."""
    return _order_call(
        functools.partial(_multi_krum_order_kernel, f=f, m=m), gr,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("f", "k_total", "interpret"))
def iterative_order(gr, f: int, k_total: int, *, interpret: bool | None = None):
    """(n, n) Gram -> (n,) int32 pick order of ``k_total`` shrinking-k
    iterative Krum selections (m-Krum / Bulyan stage 1)."""
    return _order_call(
        functools.partial(_iterative_order_kernel, f=f, k_total=k_total),
        gr, interpret=interpret)


# ---------------------------------------------------------------------------
# Bulyan stage 2: fused per-coordinate trimmed average around the median
# of the selected set — tiled over d, selection mask pinned; the masked
# variant fuses the mean-imputation preamble (imputation-free quorum path)


def _impute_tile(x, m, mean):
    """The one imputation preamble: absent rows (``m``: (n, 1) column)
    replaced by the precomputed (1, T) mean slice
    (repro.kernels.pairwise.imputed_mean — bit-for-bit with the
    tree-level engine and kernels/masked.py)."""
    return jnp.where(m > 0.5, x, mean)


def _bulyan_stage2(x, sel, *, theta, beta, exact):
    """x: (n, T) fp32, sel: (n, 1) bool with exactly theta True.  Median of
    the selected rows via the sort network (+inf padding), then the mean
    of the beta selected values closest to it per coordinate — closeness
    ties by first index, summation in closeness order: exactly the dense
    reference's ``top_k`` + ``take_along_axis`` + ``mean``."""
    n = x.shape[0]
    big = jnp.float32(jnp.inf)
    padded = jnp.where(sel, x, big)
    s = _sort_network(padded)
    if exact:
        s = jax.lax.optimization_barrier(s)
    med = 0.5 * (s[(theta - 1) // 2] + s[theta // 2])
    dist = jnp.where(sel, jnp.abs(x - med[None]), big)
    avail = jnp.broadcast_to(sel, dist.shape)
    rows = []
    for _ in range(beta):
        cur = jnp.where(avail, dist, big)
        is_min = cur == jnp.min(cur, axis=0, keepdims=True)
        first = _first_true(is_min, axis=0)
        rows.append(jnp.sum(jnp.where(first, x, 0.0), axis=0))
        avail = avail & ~first
    stk = jnp.stack(rows, axis=0)
    if exact:
        stk = jax.lax.optimization_barrier(stk)
    # the reference is jnp.mean: divisor stays a visible constant so the
    # kernel gets the same reciprocal-multiply strength reduction
    # (true_div=False in kernels/wsum.py terms)
    return jnp.sum(stk, axis=0) / beta


def _bulyan_coord_kernel(g_ref, sel_ref, out_ref, *, theta, beta, exact):
    x = g_ref[...].astype(jnp.float32)
    sel = sel_ref[...] > 0.5                        # (n, 1) column
    out_ref[...] = _bulyan_stage2(x, sel, theta=theta, beta=beta,
                                  exact=exact)[None]


def _masked_bulyan_coord_kernel(g_ref, mask_ref, mean_ref, sel_ref, out_ref,
                                *, theta, beta, exact):
    x = _impute_tile(g_ref[...], mask_ref[...], mean_ref[...])
    sel = sel_ref[...] > 0.5                        # (n, 1) column
    out_ref[...] = _bulyan_stage2(x.astype(jnp.float32), sel, theta=theta,
                                  beta=beta, exact=exact)[None]


@functools.partial(jax.jit, static_argnames=("theta", "f", "interpret"))
def bulyan_coord(g, sel, theta: int, f: int, *, interpret: bool | None = None):
    """g: (n, d), sel: (n,) {0,1} f32 (theta rows selected) -> (d,) fp32
    Bulyan coordinate stage."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    beta = max(theta - 2 * f, 1)
    w = block_d(d, interpret)
    out = pl.pallas_call(
        functools.partial(_bulyan_coord_kernel, theta=theta, beta=beta,
                          exact=interpret),
        grid=(pl.cdiv(d, w),),
        in_specs=[
            pl.BlockSpec((n, w), lambda i: (0, i)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, w), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(g, sel.astype(jnp.float32).reshape(n, 1))
    return out[0]


@functools.partial(jax.jit, static_argnames=("theta", "f", "interpret"))
def masked_bulyan_coord(g, mask, mean, sel, theta: int, f: int, *,
                        interpret: bool | None = None):
    """Imputation-fused Bulyan coordinate stage: g stays native dtype,
    absent rows are imputed inside the tile from the precomputed (d,)
    ``mean`` (no (n, d) imputed copy)."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    beta = max(theta - 2 * f, 1)
    w = block_d(d, interpret)
    out = pl.pallas_call(
        functools.partial(_masked_bulyan_coord_kernel, theta=theta,
                          beta=beta, exact=interpret),
        grid=(pl.cdiv(d, w),),
        in_specs=[
            pl.BlockSpec((n, w), lambda i: (0, i)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, w), lambda i: (0, i)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, w), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(g, mask.astype(jnp.float32).reshape(n, 1), mean.reshape(1, d),
      sel.astype(jnp.float32).reshape(n, 1))
    return out[0]
