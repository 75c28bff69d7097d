"""jit'd public wrappers around the Pallas kernels: padding, reshaping, and
filter-level compositions (kernel-backed median / trimmed mean / Krum /
multi-Krum / m-Krum / CGE / MDA / Bulyan, plain and imputation-free
masked/weighted variants).

``interpret=None`` (the default) resolves per process through
:func:`repro.kernels.tiling.default_interpret`: compiled Mosaic kernels on a
TPU backend (the BlockSpecs are TPU-shaped: n sublanes x 512 lanes, fp32
accumulation; coord_stat reads a lane-dense fp32 arena in its own T(8, 128)
layout instead, see repro.kernels.coord_stats), the interpreter elsewhere.
Pass a bool to force either."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.coord_stats import coord_sort
from repro.kernels.pairwise import gram, imputed_mean, masked_gram
from repro.kernels.tiling import TILE_D, resolve_interpret
from repro.kernels.wsum import (masked_ordered_apply, masked_weighted_sum,
                                ordered_apply, weighted_sum)


def _pad_d(g, interpret: bool | None = None):
    """Zero-pad the columns of g to a TILE_D multiple in interpret mode;
    returns (stack, original d).  Compiled kernels tile a ragged last
    block in place (out-of-range columns are never written, and the Gram
    kernels mask them), so on the chip the (n, d) arena is not copied."""
    n, d = g.shape
    rem = (-d) % TILE_D
    if rem and resolve_interpret(interpret):
        g = jnp.pad(g, ((0, 0), (0, rem)))
    return g, d


@functools.partial(jax.jit, static_argnames=("interpret",))
def kernel_coordinate_median(g, f=0, *, interpret: bool | None = None):
    gp, d = _pad_d(g, interpret)
    s = coord_sort(gp, interpret=interpret)
    return ref.median_from_sorted(s)[:d]


@functools.partial(jax.jit, static_argnames=("b", "interpret"))
def kernel_trimmed_mean(g, b: int, *, interpret: bool | None = None):
    gp, d = _pad_d(g, interpret)
    s = coord_sort(gp, interpret=interpret)
    return ref.trimmed_mean_from_sorted(s, b)[:d]


@functools.partial(jax.jit, static_argnames=("interpret",))
def kernel_pairwise_sq_dists(g, *, interpret: bool | None = None):
    gp, _ = _pad_d(g, interpret)
    gr = gram(gp, interpret=interpret)
    sq = jnp.diag(gr)
    return jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * gr, 0.0)


def _drop_unselected(w, gp):
    """Zero the NON-selected rows before the weighted sum.  A rejected
    Byzantine row may carry +-inf/NaN coordinates, and 0.0 * inf = NaN
    would leak it straight into the aggregate the selection just excluded
    it from; an exact where-select costs one elementwise pass and keeps
    finite-data results bit-identical (0 * finite was already exact)."""
    return jnp.where((w > 0.0)[:, None], gp, 0.0)


@functools.partial(jax.jit, static_argnames=("f", "interpret"))
def kernel_krum(g, f: int, *, interpret: bool | None = None):
    """Krum, fully kernel-path: Pallas Gram -> Pallas score/argmin
    selection -> Pallas weighted-select (one-hot application is exactly
    the selected row's bits)."""
    from repro.kernels.select import krum_select
    gp, d = _pad_d(g, interpret)
    gr = gram(gp, interpret=interpret)
    w = krum_select(gr, f, interpret=interpret)
    return weighted_sum(w, _drop_unselected(w, gp), interpret=interpret)[:d]


@functools.partial(jax.jit, static_argnames=("f", "normalize", "interpret"))
def kernel_cge(g, f: int, normalize: bool = True, *, interpret: bool | None = None):
    """CGE, fully kernel-path: norms off the Pallas Gram diagonal, exact
    comparison-rank top-k selection, Pallas weighted sum (one MXU dot —
    the selected SET is bit-for-bit, the averaged application ulp-level:
    CGE keeps n - f rows, and an order-replaying accumulation would cost
    O((n-f) n T) VPU passes against the dot's single MXU pass for a rule
    whose guarantee rides on the selection, not the summation order);
    normalization divides AFTER the sum like the dense reference."""
    from repro.kernels.select import cge_select
    n = g.shape[0]
    gp, d = _pad_d(g, interpret)
    gr = gram(gp, interpret=interpret)
    w = cge_select(gr, n - f, interpret=interpret)
    out = weighted_sum(w, _drop_unselected(w, gp), interpret=interpret)[:d]
    return out / (n - f) if normalize else out


# ---------------------------------------------------------------------------
# the full selection family: multi-Krum / m-Krum / MDA / Bulyan off the
# same Gram + selection primitives, bit-for-bit with the dense reference
# (selection-order-preserving application — see kernels/wsum.py)


@functools.partial(jax.jit, static_argnames=("f", "m", "interpret"))
def kernel_multi_krum(g, f: int, m: int = 2, *, interpret: bool | None = None):
    """multi-Krum: one Krum score pass, the m smallest averaged in score
    order (exactly the dense ``jnp.mean(g[top_k_idx], axis=0)``)."""
    from repro.kernels.select import multi_krum_order
    gp, d = _pad_d(g, interpret)
    gr = gram(gp, interpret=interpret)
    order = multi_krum_order(gr, f, m, interpret=interpret)
    # jnp.mean reference -> divisor stays a visible constant (true_div=False)
    return ordered_apply(order, gp, m, div=m, true_div=False,
                         interpret=interpret)[:d]


@functools.partial(jax.jit, static_argnames=("f", "m", "interpret"))
def kernel_m_krum(g, f: int, m: int = 2, *, interpret: bool | None = None):
    """m-Krum (iterative): scores recomputed after each removal with the
    SHRINKING neighbour count, picks accumulated sequentially (the dense
    reference's unrolled ``acc = acc + g[i]`` chain)."""
    from repro.kernels.select import iterative_order
    gp, d = _pad_d(g, interpret)
    gr = gram(gp, interpret=interpret)
    order = iterative_order(gr, f, m, interpret=interpret)
    return ordered_apply(order, gp, m, chain=True, div=m,
                         interpret=interpret)[:d]


def _mda_order(d2, n: int, f: int):
    """MDA subset selection on the (n, n) squared distances: the static
    (n-f)-subset table is enumerated once per (n, f)
    (aggregators.mda_combos), the diameter argmin (ties by subset
    perimeter, then enumeration order — D.argmin_tiebreak) runs as plain
    O(C(n, f)) jnp with no d dependence; only the Gram and the
    application touch the model-sized stack.  NaN diameters (non-finite
    adversary rows) order LAST like the selection kernels' _rank."""
    from repro.core.aggregators import mda_combos          # lazy: no cycle
    from repro.core.filters.dense import argmin_tiebreak
    combos = mda_combos(n, f)
    sub = d2[combos[:, :, None], combos[:, None, :]]
    diam = jnp.max(sub, axis=(1, 2))
    diam = jnp.where(jnp.isnan(diam), jnp.inf, diam)
    per = jnp.sum(sub, axis=(1, 2))
    per = jnp.where(jnp.isnan(per), jnp.inf, per)
    best = jnp.asarray(combos)[argmin_tiebreak(diam, per)]
    return jnp.full((n,), n, jnp.int32).at[best].set(
        jnp.arange(n - f, dtype=jnp.int32))


def _d2_from_gram_jnp(gr):
    """(n, n) Gram -> squared distances, diagonal exactly 0 (the Gram
    diagonal IS the squared norm, so the cancellation is exact).  NaN
    distances (inf - inf against a non-finite adversary) order last —
    exact no-op on finite stacks."""
    sq = jnp.diag(gr)
    d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * gr, 0.0)
    return jnp.where(jnp.isnan(d2), jnp.inf, d2)


@functools.partial(jax.jit, static_argnames=("f", "interpret"))
def kernel_mda(g, f: int, *, interpret: bool | None = None):
    """Minimum-diameter averaging off the Pallas Gram: subset selection is
    d-free jnp on the (n, n) distances, the selected rows averaged in
    index order (the dense ``jnp.mean(g[best], axis=0)``)."""
    n = g.shape[0]
    gp, d = _pad_d(g, interpret)
    gr = gram(gp, interpret=interpret)
    order = _mda_order(_d2_from_gram_jnp(gr), n, f)
    return ordered_apply(order, gp, n - f, div=n - f, true_div=False,
                         interpret=interpret)[:d]


@functools.partial(jax.jit, static_argnames=("f", "interpret"))
def kernel_bulyan(g, f: int, *, interpret: bool | None = None):
    """Bulyan: theta = n - 2f shrinking-k iterative Krum selections on the
    Gram, then the fused per-coordinate trimmed-average-around-the-median
    stage — no (n, d) sorted/distance copy ever leaves the tile."""
    from repro.kernels.select import bulyan_coord, iterative_order
    n = g.shape[0]
    theta = n - 2 * f
    assert theta >= 1, "Bulyan needs n > 2f (and n >= 4f+3 for guarantees)"
    gp, d = _pad_d(g, interpret)
    gr = gram(gp, interpret=interpret)
    order = iterative_order(gr, f, theta, interpret=interpret)
    sel = (order < theta).astype(jnp.float32)
    return bulyan_coord(gp, sel, theta, f, interpret=interpret)[:d]


# ---------------------------------------------------------------------------
# imputation-free masked/weighted variants: the Gram, the selection AND the
# application all impute inside their tiles (kernels/masked.py trick), so
# the quorum path never materializes the imputed (n, d) stack and
# mask/weights stay traced operands


@functools.partial(jax.jit, static_argnames=("f", "interpret"))
def kernel_krum_masked(g, mask, wn, f: int, *, interpret: bool | None = None):
    """Masked Krum = Krum over the mean-imputed stack (gather law),
    imputation-free: the one-hot imputing weighted sum returns exactly
    the selected imputed row's bits."""
    from repro.kernels.select import krum_select
    gp, d = _pad_d(g, interpret)
    mean = imputed_mean(gp, wn)          # (d,) — computed ONCE, shared
    gr = masked_gram(gp, mask, wn, mean, interpret=interpret)
    w = krum_select(gr, f, interpret=interpret)
    return masked_weighted_sum(w, gp, mask, mean,
                               interpret=interpret)[:d]


@functools.partial(jax.jit, static_argnames=("f", "normalize", "interpret"))
def kernel_cge_masked(g, mask, wn, f: int, normalize: bool = True, *,
                      interpret: bool | None = None):
    """Imputation-free masked CGE (selection bitwise, application via the
    imputing MXU dot — the plain kernel's ulp-level contract)."""
    from repro.kernels.select import cge_select
    n = g.shape[0]
    gp, d = _pad_d(g, interpret)
    mean = imputed_mean(gp, wn)
    gr = masked_gram(gp, mask, wn, mean, interpret=interpret)
    w = cge_select(gr, n - f, interpret=interpret)
    out = masked_weighted_sum(w, gp, mask, mean, interpret=interpret)[:d]
    return out / (n - f) if normalize else out


@functools.partial(jax.jit, static_argnames=("f", "m", "interpret"))
def kernel_multi_krum_masked(g, mask, wn, f: int, m: int = 2, *,
                             interpret: bool | None = None):
    from repro.kernels.select import multi_krum_order
    gp, d = _pad_d(g, interpret)
    mean = imputed_mean(gp, wn)
    gr = masked_gram(gp, mask, wn, mean, interpret=interpret)
    order = multi_krum_order(gr, f, m, interpret=interpret)
    return masked_ordered_apply(order, gp, mask, mean, m, div=m,
                                true_div=False, interpret=interpret)[:d]


@functools.partial(jax.jit, static_argnames=("f", "m", "interpret"))
def kernel_m_krum_masked(g, mask, wn, f: int, m: int = 2, *,
                         interpret: bool | None = None):
    from repro.kernels.select import iterative_order
    gp, d = _pad_d(g, interpret)
    mean = imputed_mean(gp, wn)
    gr = masked_gram(gp, mask, wn, mean, interpret=interpret)
    order = iterative_order(gr, f, m, interpret=interpret)
    return masked_ordered_apply(order, gp, mask, mean, m, chain=True,
                                div=m, interpret=interpret)[:d]


@functools.partial(jax.jit, static_argnames=("f", "interpret"))
def kernel_mda_masked(g, mask, wn, f: int, *, interpret: bool | None = None):
    n = g.shape[0]
    gp, d = _pad_d(g, interpret)
    mean = imputed_mean(gp, wn)
    gr = masked_gram(gp, mask, wn, mean, interpret=interpret)
    order = _mda_order(_d2_from_gram_jnp(gr), n, f)
    return masked_ordered_apply(order, gp, mask, mean, n - f, div=n - f,
                                true_div=False, interpret=interpret)[:d]


@functools.partial(jax.jit, static_argnames=("f", "interpret"))
def kernel_bulyan_masked(g, mask, wn, f: int, *, interpret: bool | None = None):
    from repro.kernels.select import iterative_order, masked_bulyan_coord
    n = g.shape[0]
    theta = n - 2 * f
    assert theta >= 1, "Bulyan needs n > 2f (and n >= 4f+3 for guarantees)"
    gp, d = _pad_d(g, interpret)
    mean = imputed_mean(gp, wn)
    gr = masked_gram(gp, mask, wn, mean, interpret=interpret)
    order = iterative_order(gr, f, theta, interpret=interpret)
    sel = (order < theta).astype(jnp.float32)
    return masked_bulyan_coord(gp, mask, mean, sel, theta, f,
                               interpret=interpret)[:d]
