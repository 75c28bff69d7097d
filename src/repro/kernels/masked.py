"""Pallas TPU kernel: masked / weighted coordinate order statistics.

The async training loop aggregates over a *varying subset* of agents every
server step (quorum masks from the fault simulator) with per-agent staleness
discounts.  The engine's masked semantics for coordinate-wise rules
(:func:`repro.core.aggregators._masked_aggregate`) are: the order statistic
over the ARRIVED rows only — absent rows enter the per-coordinate sort as
+inf sentinels and the kept rank window is computed from the traced arrived
count (:func:`repro.kernels.ref.arrived_stat_from_sorted`), then the result
is scaled by the mean arrived weight.  Mean-imputing the absent rows (the
pre-PR-9 law, still used by the pairwise Gram kernels) is NOT robust: the
delivered mean is attack-contaminated, so the imputed ghost rows land
inside the trim window and a single straggler lets a large_value attack
straight through trimmed_mean/coordinate_median.  The sentinel law keeps
everything the old one bought — one fused VMEM pass per sort tile, no
(n, d) copy, mask/weights as traced operands so a fault schedule never
recompiles — while restoring the f-of-arrived breakdown bound.

Arithmetic is shared with the tree-level engine path (fp32 sentinel select
-> fp32 sort -> arrived-window reduce, one helper in kernels/ref.py), so
fp32 results are bit-for-bit with the ``impl="gather"`` reference —
tests/test_kernels_parity.py is the proof.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.coord_stats import _sort_network, stat_from_sorted
from repro.kernels.tiling import block_d, resolve_interpret


def _masked_stat_kernel(g_ref, mask_ref, wn_ref, out_ref, *, stat, b,
                        exact):
    del wn_ref                                       # weights scale outside
    x = g_ref[...]                                   # (n, T) native dtype
    m = mask_ref[...][0]                             # (n,) f32, 1 = arrived
    # absent rows become +inf sort sentinels: they occupy the top ranks of
    # every column and the arrived-count window below never reaches them
    sent = jnp.where(m[:, None] > 0.5, x.astype(jnp.float32), jnp.inf)
    s = _sort_network(sent)
    if exact:
        # see coord_stats._coord_stat_kernel: pin the reduce order so the
        # fp32 result is bit-for-bit with the tree-level sentinel path
        s = jax.lax.optimization_barrier(s)
    from repro.kernels import ref
    out_ref[...] = ref.arrived_stat_from_sorted(s, m, stat, b)[None]


def _sign_vote_kernel(g_ref, out_ref):
    # sign-compress + majority vote in one pass: the per-coordinate sum of
    # signs is exact in fp32 for any realistic n (integers < 2^24), so the
    # vote is bitwise identical across impls by construction
    s = jnp.sign(g_ref[...].astype(jnp.float32))     # (n, T) in {-1, 0, 1}
    out_ref[...] = jnp.sign(jnp.sum(s, axis=0))[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def sign_vote(g, *, interpret: bool | None = None):
    """g: (n, d) any dtype (fp32 arena or int8/fp8 codes — sign is
    invariant under the positive per-row dequant scale) -> (d,) fp32
    majority vote."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    w = block_d(d, interpret)
    out = pl.pallas_call(
        _sign_vote_kernel,
        grid=(pl.cdiv(d, w),),
        in_specs=[pl.BlockSpec((n, w), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, w), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(g)
    return out[0]


def _masked_sign_vote_kernel(g_ref, mask_ref, wn_ref, out_ref):
    del wn_ref                                       # weights scale outside
    m = mask_ref[...][0]
    # arrived rows vote, absent rows cast NO vote — an imputed ghost vote
    # would carry the sign of the (attack-contaminated) delivered mean
    s = jnp.sign(g_ref[...].astype(jnp.float32)) * m[:, None]
    out_ref[...] = jnp.sign(jnp.sum(s, axis=0))[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_sign_vote(g, mask, wn, *, interpret: bool | None = None):
    """Majority vote over the arrived rows only (the engine's masked law
    for the sign family), fused per tile."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    w = block_d(d, interpret)
    out = pl.pallas_call(
        _masked_sign_vote_kernel,
        grid=(pl.cdiv(d, w),),
        in_specs=[
            pl.BlockSpec((n, w), lambda i: (0, i)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, w), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(g, mask.astype(jnp.float32).reshape(1, n),
      wn.astype(jnp.float32).reshape(1, n))
    return out[0]


# ---------------------------------------------------------------------------
# scaled variants: the arena holds int8/fp8 CODES plus a per-row fp32 scale
# sidecar (core.flat.quantize_rows); dequantization happens INSIDE the tile
# (codes.astype(f32) * scale[:, None] per VMEM block — exactly
# core.flat.dequantize_rows' arithmetic, so parity vs the engine-level
# dequant copy is bitwise) and the dequantized (n, d) stack never exists
# outside VMEM.  The masked variants use the same arrived-window sentinel
# law as the plain kernels above: dequantize, push absent rows to +inf,
# one sort, one count-windowed reduce.


def _scaled_stat_kernel(g_ref, sc_ref, out_ref, *, stat, b, exact):
    sc = sc_ref[...][0]                              # (n,) f32
    xf = g_ref[...].astype(jnp.float32) * sc[:, None]
    s = _sort_network(xf)
    if exact:
        s = jax.lax.optimization_barrier(s)
    out_ref[...] = stat_from_sorted(s, stat, b)[None]


@functools.partial(jax.jit, static_argnames=("stat", "b", "interpret"))
def scaled_coord_stat(g, scale, stat: str, b: int = 0, *,
                      interpret: bool | None = None):
    """g: (n, d) quantized codes, scale: (n,) fp32 per-row dequant scale
    -> (d,) fp32 order statistic over the dequantized stack, dequant fused
    into the sort tile."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    w = block_d(d, interpret)
    out = pl.pallas_call(
        functools.partial(_scaled_stat_kernel, stat=stat, b=b,
                          exact=interpret),
        grid=(pl.cdiv(d, w),),
        in_specs=[
            pl.BlockSpec((n, w), lambda i: (0, i)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, w), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(g, scale.astype(jnp.float32).reshape(1, n))
    return out[0]


def _scaled_masked_stat_kernel(g_ref, sc_ref, mask_ref, wn_ref, out_ref, *,
                               stat, b, exact):
    del wn_ref                                       # weights scale outside
    sc = sc_ref[...][0]
    m = mask_ref[...][0]
    xf = g_ref[...].astype(jnp.float32) * sc[:, None]
    sent = jnp.where(m[:, None] > 0.5, xf, jnp.inf)
    s = _sort_network(sent)
    if exact:
        s = jax.lax.optimization_barrier(s)
    from repro.kernels import ref
    out_ref[...] = ref.arrived_stat_from_sorted(s, m, stat, b)[None]


@functools.partial(jax.jit, static_argnames=("stat", "b", "interpret"))
def scaled_masked_coord_stat(g, scale, mask, wn, stat: str, b: int = 0, *,
                             interpret: bool | None = None):
    """Masked order statistic over a quantized arena: in-tile dequant,
    +inf sentinels for absent rows, fused sort + arrived-window reduce."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    w = block_d(d, interpret)
    out = pl.pallas_call(
        functools.partial(_scaled_masked_stat_kernel, stat=stat, b=b,
                          exact=interpret),
        grid=(pl.cdiv(d, w),),
        in_specs=[
            pl.BlockSpec((n, w), lambda i: (0, i)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, w), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(g, scale.astype(jnp.float32).reshape(1, n),
      mask.astype(jnp.float32).reshape(1, n),
      wn.astype(jnp.float32).reshape(1, n))
    return out[0]


def _scaled_masked_sign_kernel(g_ref, sc_ref, mask_ref, wn_ref, out_ref):
    del wn_ref                                       # weights scale outside
    sc = sc_ref[...][0]
    m = mask_ref[...][0]
    xf = g_ref[...].astype(jnp.float32) * sc[:, None]
    s = jnp.sign(xf) * m[:, None]
    out_ref[...] = jnp.sign(jnp.sum(s, axis=0))[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def scaled_masked_sign_vote(g, scale, mask, wn, *, interpret: bool | None = None):
    """Masked majority vote over a quantized arena: arrived rows vote,
    absent rows cast none.  The per-row dequant scale is sign-neutral
    (scales are non-negative), but the dequant is kept so the kernel's
    arithmetic matches the engine's dequantized fp32 reference
    bit-for-bit."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    w = block_d(d, interpret)
    out = pl.pallas_call(
        _scaled_masked_sign_kernel,
        grid=(pl.cdiv(d, w),),
        in_specs=[
            pl.BlockSpec((n, w), lambda i: (0, i)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, w), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(g, scale.astype(jnp.float32).reshape(1, n),
      mask.astype(jnp.float32).reshape(1, n),
      wn.astype(jnp.float32).reshape(1, n))
    return out[0]


@functools.partial(jax.jit, static_argnames=("stat", "b", "interpret"))
def masked_coord_stat(g, mask, wn, stat: str, b: int = 0, *,
                      interpret: bool | None = None):
    """g: (n, d) any dtype, mask: (n,) {0,1} f32, wn: (n,) f32 normalized
    weights -> (d,) fp32 statistic over the arrived rows."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    w = block_d(d, interpret)
    out = pl.pallas_call(
        functools.partial(_masked_stat_kernel, stat=stat, b=b,
                          exact=interpret),
        grid=(pl.cdiv(d, w),),
        in_specs=[
            pl.BlockSpec((n, w), lambda i: (0, i)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, w), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(g, mask.astype(jnp.float32).reshape(1, n),
      wn.astype(jnp.float32).reshape(1, n))
    return out[0]
