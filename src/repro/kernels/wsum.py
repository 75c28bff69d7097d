"""Pallas TPU kernels: the application stage of the selection filters.

:func:`weighted_sum` is the classic out = w^T G (Krum's one-hot, CGC clip
scales, Draco votes): given per-agent weights w (n,), produce sum_i w_i g_i
without materializing a gathered copy — fused per VMEM tile.

:func:`ordered_apply` replays a selection ORDER (from
:mod:`repro.kernels.select`): rows are one-hot-extracted and summed in the
order the rule picked them — ``chain=False`` stacks the k rows and reduces
(bit-for-bit with the dense reference's ``jnp.mean(g[top_k_idx], axis=0)``
— the optimization_barrier pins the reduce against reassociation through
the stack), ``chain=True`` adds them sequentially (bit-for-bit with
m-Krum's unrolled ``acc = acc + g[i]`` loop).  The one-hot extraction also
where-zeroes every non-selected row, so a rejected Byzantine row carrying
+-inf/NaN coordinates cannot leak 0*inf = NaN into the aggregate.

:func:`masked_weighted_sum` / :func:`masked_ordered_apply` are the
imputation-FREE variants: the stack stays native dtype and absent rows
are never even built — live selections read the raw rows, ghost
selections contribute the precomputed (d,) imputed mean
(repro.kernels.pairwise.imputed_mean) — algebraically and bitwise the
weighted sum over the imputed stack, without the (n, d) copy the
historical masked path materialized.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref
from repro.kernels.tiling import block_d, resolve_interpret


def _row_spec(n: int):
    return pl.BlockSpec((1, n), lambda i: (0, 0))


def _col_spec(n: int):
    return pl.BlockSpec((n, 1), lambda i: (0, 0))


def _row_and_col(v):
    """(n,) per-row operand -> its (1, n) row and (n, 1) column blocks.

    Mosaic cannot reshape a boolean (n,) vector into an (n, 1) column
    inside a kernel, so a kernel that needs a per-row value both as an
    MXU row operand and as a per-row gate over the (n, T) tile gets both
    layouts from the caller."""
    v = v.astype(jnp.float32)
    return v.reshape(1, -1), v.reshape(-1, 1)


def _wsum_kernel(w_ref, g_ref, out_ref):
    w = w_ref[...].astype(jnp.float32)            # (1, n)
    x = g_ref[...].astype(jnp.float32)            # (n, T)
    out_ref[...] = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (1, T)


@functools.partial(jax.jit, static_argnames=("interpret",))
def weighted_sum(w, g, *, interpret: bool | None = None):
    """w: (n,), g: (n, d) -> (d,) fp32."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    w_blk = block_d(d, interpret)
    out = pl.pallas_call(
        _wsum_kernel,
        grid=(pl.cdiv(d, w_blk),),
        in_specs=[
            _row_spec(n),
            pl.BlockSpec((n, w_blk), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, w_blk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(w.reshape(1, n), g)
    return out[0]


def _masked_wsum_kernel(w_ref, wc_ref, m_ref, mc_ref, g_ref, mean_ref,
                        out_ref):
    """Imputation-free w^T over the VIRTUALLY imputed stack: instead of
    materializing imputed rows even tile-locally, live selected rows are
    dotted raw (drop-unselected like the plain kernel) and ghost
    selections contribute their total weight times the precomputed mean —
    algebraically the same sum, and exactly the selected imputed row's
    bits for a one-hot w (0-terms are literal zeros, the ghost term is
    where-gated so 0 * inf cannot leak)."""
    w = w_ref[...]                                   # (1, n)
    live = m_ref[...] > 0.5                          # (1, n)
    gate = jnp.where(mc_ref[...] > 0.5, wc_ref[...], 0.0) > 0.0   # (n, 1)
    mean = mean_ref[...].astype(jnp.float32)         # (1, T)
    w_live = jnp.where(live, w, 0.0)
    xf = jnp.where(gate, g_ref[...].astype(jnp.float32), 0.0)
    out = jax.lax.dot_general(
        w_live, xf, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (1, T)
    ghost_w = jnp.sum(jnp.where(live, 0.0, w))
    out_ref[...] = out + jnp.where(ghost_w > 0.0, ghost_w * mean, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_weighted_sum(w, g, mask, mean, *, interpret: bool | None = None):
    """w: (n,) NON-NEGATIVE selection weights, g: (n, d) native dtype,
    mean: (d,) imputation value (repro.kernels.pairwise.imputed_mean) ->
    (d,) fp32 weighted sum over the MEAN-IMPUTED stack (imputation fused
    per tile; mask/mean traced).

    PRECONDITION: w >= 0 — the 0*inf guards gate rows on w > 0, so a
    negative weight would be silently dropped, not subtracted (the
    selection callers pass one-hot / {0,1} sets; signed weight vectors
    need the plain :func:`weighted_sum` on an imputed stack instead)."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    w_blk = block_d(d, interpret)
    out = pl.pallas_call(
        _masked_wsum_kernel,
        grid=(pl.cdiv(d, w_blk),),
        in_specs=[
            _row_spec(n), _col_spec(n), _row_spec(n), _col_spec(n),
            pl.BlockSpec((n, w_blk), lambda i: (0, i)),
            pl.BlockSpec((1, w_blk), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, w_blk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(*_row_and_col(w), *_row_and_col(mask), g, mean.reshape(1, d))
    return out[0]


def _cclip_wsum_kernel(lam_ref, lamc_ref, g_ref, v_ref, out_ref):
    """One centered-clip fixed-point step per tile:

        out = v + sum_i lam_i (x_i - v) = (1 - sum_i lam_i) v + lam^T X

    with lam_i = w_i/tot * min(1, tau/||x_i - v||) precomputed by the
    caller (the clip radius needs the FULL row norm — a cross-tile
    reduction — so the scalar stage stays outside; the model-sized
    multiply-accumulate is what fuses here).  Rows are gated on lam > 0,
    so a dead row carrying inf/NaN cannot leak through 0 * x."""
    lam = lam_ref[...]                                # (1, n)
    v = v_ref[...].astype(jnp.float32)                # (1, T)
    xf = jnp.where(lamc_ref[...] > 0.0, g_ref[...].astype(jnp.float32),
                   0.0)
    acc = jax.lax.dot_general(
        lam, xf, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # (1, T)
    out_ref[...] = (1.0 - jnp.sum(lam)) * v + acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def clipped_weighted_sum(lam, g, v, *, interpret: bool | None = None):
    """lam: (n,) NON-NEGATIVE clip-folded weights, g: (n, d) native dtype,
    v: (d,) fp32 current center -> (d,) fp32 updated center
    ``v + sum_i lam_i (g_i - v)`` — the application stage of one
    centered-clipping iteration (Karimireddy et al. momentum clipping),
    fused per tile without materializing the (n, d) difference stack."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    w_blk = block_d(d, interpret)
    out = pl.pallas_call(
        _cclip_wsum_kernel,
        grid=(pl.cdiv(d, w_blk),),
        in_specs=[
            _row_spec(n), _col_spec(n),
            pl.BlockSpec((n, w_blk), lambda i: (0, i)),
            pl.BlockSpec((1, w_blk), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, w_blk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(*_row_and_col(lam), g, v.astype(jnp.float32).reshape(1, d))
    return out[0]


def _sparse_wmean_kernel(g_ref, mask_ref, w_ref, out_ref):
    live = mask_ref[...][0] > 0.5
    w = jnp.where(live, w_ref[...][0].astype(jnp.float32), 0.0)   # (n,)
    out_ref[...] = ref.sparse_mean_law(g_ref[...].astype(jnp.float32),
                                       w)[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def sparse_masked_weighted_mean(g, mask, w, *, interpret: bool | None = None):
    """g: (n, d) native dtype, mask: (n,) {0,1}, w: (n,) row weights
    (dataset sizes — any positive scaling; the law is scale-invariant) ->
    (d,) fp32 sparse/dropout-aware mean: each coordinate is averaged over
    the LIVE rows that actually sent it (coord != 0), weighted by
    ``(coord_sent) * w``.  Absent rows never vote — there is no
    imputation (a dropped-out coordinate is information-free, unlike a
    straggler's stale full row)."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    w_blk = block_d(d, interpret)
    out = pl.pallas_call(
        _sparse_wmean_kernel,
        grid=(pl.cdiv(d, w_blk),),
        in_specs=[
            pl.BlockSpec((n, w_blk), lambda i: (0, i)),
            _row_spec(n), _row_spec(n),
        ],
        out_specs=pl.BlockSpec((1, w_blk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(g, mask.astype(jnp.float32).reshape(1, n),
      w.astype(jnp.float32).reshape(1, n))
    return out[0]


def _scaled_sparse_wmean_kernel(g_ref, sc_ref, mask_ref, w_ref, out_ref):
    # quantized codes: code == 0 iff the dequantized coordinate == 0
    # (scales are strictly positive), so the sent-pattern survives
    # quantization and the in-tile dequant feeds the same law
    sc = sc_ref[...][0]
    live = mask_ref[...][0] > 0.5
    w = jnp.where(live, w_ref[...][0].astype(jnp.float32), 0.0)
    xf = g_ref[...].astype(jnp.float32) * sc[:, None]
    out_ref[...] = ref.sparse_mean_law(xf, w)[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def scaled_sparse_masked_weighted_mean(g, scale, mask, w, *,
                                       interpret: bool | None = None):
    """Sparse mean over a quantized arena: in-tile dequant (per-row fp32
    scale sidecar), then :func:`sparse_masked_weighted_mean`'s law."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    w_blk = block_d(d, interpret)
    out = pl.pallas_call(
        _scaled_sparse_wmean_kernel,
        grid=(pl.cdiv(d, w_blk),),
        in_specs=[
            pl.BlockSpec((n, w_blk), lambda i: (0, i)),
            _row_spec(n), _row_spec(n), _row_spec(n),
        ],
        out_specs=pl.BlockSpec((1, w_blk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(g, scale.astype(jnp.float32).reshape(1, n),
      mask.astype(jnp.float32).reshape(1, n),
      w.astype(jnp.float32).reshape(1, n))
    return out[0]


def _accumulate_rows(rows, *, chain, div, true_div, exact):
    """Summation + division stage shared by the ordered applications.

    ``chain`` picks between the dense references' two summation shapes
    (reduce over a gather vs an unrolled add loop).  ``true_div`` mirrors
    the reference's DIVISION compilation: XLA strength-reduces division
    by a compile-time constant into a reciprocal multiply (~1 ulp off for
    non-power-of-2 divisors).  A ``jnp.mean``-based reference compiles
    sum+div as one composite and GETS that rewrite — leave the constant
    visible (true_div=False) so the kernel gets it too; an explicit
    ``out / m`` reference dispatches a standalone true division — pin the
    divisor behind a barrier (true_div=True) so the rewrite cannot see
    the constant."""
    if chain:
        acc = jnp.zeros_like(rows[0])
        for row in rows:
            acc = acc + row
        out = acc
    else:
        stk = jnp.stack(rows, axis=0)
        if exact:
            stk = jax.lax.optimization_barrier(stk)
        out = jnp.sum(stk, axis=0)
    if div is not None:
        den = jnp.float32(div)
        if true_div and exact:
            den = jax.lax.optimization_barrier(den)
        out = out / den
    return out


def _ordered_apply_kernel(ord_ref, g_ref, out_ref, *, k, chain, div,
                          true_div, exact):
    order = ord_ref[...]                           # (n, 1) int32 column
    x = g_ref[...].astype(jnp.float32)             # (n, T)
    rows = [jnp.sum(jnp.where(order == r, x, 0.0), axis=0)
            for r in range(k)]
    out_ref[...] = _accumulate_rows(rows, chain=chain, div=div,
                                    true_div=true_div, exact=exact)[None]


def _masked_ordered_apply_kernel(ord_ref, g_ref, mask_ref, mean_ref,
                                 out_ref, *, k, chain, div, true_div,
                                 exact):
    """Ordered application over the VIRTUALLY imputed stack: each rank is
    at most one row — a live rank contributes its raw row (exact one-hot
    extract + literal-zero mean term), a ghost rank contributes exactly
    the precomputed mean's bits — so no imputed tile is ever built and
    parity with the impute-then-extract arithmetic is bitwise."""
    order = ord_ref[...]                           # (n, 1) int32 column
    x = g_ref[...].astype(jnp.float32)
    live = mask_ref[...] > 0.5                     # (n, 1)
    mean = mean_ref[...][0].astype(jnp.float32)
    rows = []
    for r in range(k):
        sel = order == r
        row = jnp.sum(jnp.where(sel & live, x, 0.0), axis=0)
        ghost = jnp.sum((sel & ~live).astype(jnp.float32)) > 0.0
        rows.append(row + jnp.where(ghost, mean, 0.0))
    out_ref[...] = _accumulate_rows(rows, chain=chain, div=div,
                                    true_div=true_div, exact=exact)[None]


@functools.partial(jax.jit,
                   static_argnames=("k", "chain", "div", "true_div",
                                    "interpret"))
def ordered_apply(order, g, k: int, *, chain: bool = False,
                  div: float | None = None, true_div: bool = True,
                  interpret: bool | None = None):
    """order: (n,) int32 pick order (sentinel >= k ignored), g: (n, d) ->
    (d,) fp32: the k picked rows summed in pick order, divided by ``div``
    (None = no division; ``true_div`` picks the reference's division
    compilation — see _accumulate_rows)."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    w_blk = block_d(d, interpret)
    out = pl.pallas_call(
        functools.partial(_ordered_apply_kernel, k=k, chain=chain, div=div,
                          true_div=true_div, exact=interpret),
        grid=(pl.cdiv(d, w_blk),),
        in_specs=[
            _col_spec(n),
            pl.BlockSpec((n, w_blk), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, w_blk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(order.reshape(n, 1).astype(jnp.int32), g)
    return out[0]


@functools.partial(jax.jit,
                   static_argnames=("k", "chain", "div", "true_div",
                                    "interpret"))
def masked_ordered_apply(order, g, mask, mean, k: int, *,
                         chain: bool = False, div: float | None = None,
                         true_div: bool = True,
                         interpret: bool | None = None):
    """Imputation-fused :func:`ordered_apply`: g stays native dtype and
    absent rows are imputed inside the tile from the precomputed (d,)
    ``mean`` (mask/mean are traced operands)."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    w_blk = block_d(d, interpret)
    out = pl.pallas_call(
        functools.partial(_masked_ordered_apply_kernel, k=k, chain=chain,
                          div=div, true_div=true_div, exact=interpret),
        grid=(pl.cdiv(d, w_blk),),
        in_specs=[
            _col_spec(n),
            pl.BlockSpec((n, w_blk), lambda i: (0, i)),
            _col_spec(n),
            pl.BlockSpec((1, w_blk), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, w_blk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(order.reshape(n, 1).astype(jnp.int32), g,
      mask.astype(jnp.float32).reshape(n, 1), mean.reshape(1, d))
    return out[0]
