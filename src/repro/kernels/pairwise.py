"""Pallas TPU kernel: Gram matrix of the agent-gradient stack.

Krum / multi-Krum / MDA / Bulyan need all pairwise squared distances
||g_i - g_j||^2 = ||g_i||^2 + ||g_j||^2 - 2 <g_i, g_j>.  On GPU the surveyed
systems loop over pairs; on TPU the inner products are one MXU matmul
(n x d)(d x n) — the kernel tiles the huge d axis into VMEM blocks and
accumulates the (n, n) Gram in fp32 across grid steps (output block pinned
at (0, 0), revisited every step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import block_d, resolve_interpret


def _ragged(d: int, w: int):
    """d when the last of the w-wide blocks is ragged, else None."""
    return d if d % w else None


def _valid_cols(x, i, d):
    """Zero the columns of block i past the true width d: a ragged last
    block reads undefined values there, which would enter the Gram sum
    (d=None: the grid tiles d exactly)."""
    if d is None:
        return x
    t = x.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) + i * t
    return jnp.where(col < d, x, 0.0)


def _gram_kernel(g_ref, out_ref, *, d):
    i = pl.program_id(0)
    x = _valid_cols(g_ref[...].astype(jnp.float32), i, d)   # (n, T)
    part = jax.lax.dot_general(
        x, x, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (n, n) on the MXU

    @pl.when(i == 0)
    def _init():
        out_ref[...] = part

    @pl.when(i > 0)
    def _acc():
        out_ref[...] += part


@functools.partial(jax.jit, static_argnames=("interpret",))
def gram(g, *, interpret: bool | None = None):
    """g: (n, d) -> (n, n) fp32 Gram (any d)."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    w = block_d(d, interpret)
    return pl.pallas_call(
        functools.partial(_gram_kernel, d=_ragged(d, w)),
        grid=(pl.cdiv(d, w),),
        in_specs=[pl.BlockSpec((n, w), lambda i: (0, i))],
        out_specs=pl.BlockSpec((n, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
    )(g)


def imputed_mean(g, wn):
    """(d,) imputation value of the masked pairwise family: fp32 weighted
    mean of the arrived rows (wn zero elsewhere), round-tripped through
    the stack's native dtype — THE one copy of the arithmetic, identical
    to the tree-level engine's.  Output-sized (d,), so sharing it across
    the Gram / selection / application kernels keeps the path
    imputation-free (no (n, d) copy) while computing the mean once."""
    from repro.kernels.ref import weighted_row_sum
    return weighted_row_sum(g, wn).astype(g.dtype)


def _masked_gram_kernel(g_ref, mask_ref, mean_ref, out_ref, *, d):
    """Gram of the MEAN-IMPUTED stack, imputation fused into the tile
    (the kernels/masked.py trick applied to the pairwise path): absent
    rows are replaced inside the tile by the precomputed (T,) mean slice,
    so the (n, d) imputed copy never exists and mask/weights stay traced
    operands (fault schedules never recompile)."""
    i = pl.program_id(0)
    x = g_ref[...]                                   # (n, T) native dtype
    m = mask_ref[...][0]                             # (n,) f32, 1 = arrived
    mean = mean_ref[...][0]                          # (T,) native dtype
    xi = jnp.where(m[:, None] > 0.5, x, mean[None]).astype(jnp.float32)
    xi = _valid_cols(xi, i, d)
    part = jax.lax.dot_general(
        xi, xi, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (n, n) on the MXU

    @pl.when(i == 0)
    def _init():
        out_ref[...] = part

    @pl.when(i > 0)
    def _acc():
        out_ref[...] += part


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_gram(g, mask, wn, mean=None, *, interpret: bool | None = None):
    """g: (n, d) any dtype, mask: (n,) {0,1} f32, wn: (n,) f32 normalized
    weights -> (n, n) fp32 Gram of the mean-imputed stack.  ``mean``: the
    (d,) :func:`imputed_mean` (computed here when None — pass it in to
    share one mean across a kernel pipeline)."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    if mean is None:
        mean = imputed_mean(g, wn)
    w = block_d(d, interpret)
    return pl.pallas_call(
        functools.partial(_masked_gram_kernel, d=_ragged(d, w)),
        grid=(pl.cdiv(d, w),),
        in_specs=[
            pl.BlockSpec((n, w), lambda i: (0, i)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, w), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
    )(g, mask.astype(jnp.float32).reshape(1, n), mean.reshape(1, d))
