"""Pallas TPU kernel: coordinate-wise order statistics over the agent axis.

The aggregation hot-spot of median-family gradient filters (survey: "the
median-based aggregation still dominates the training time in large-scale
settings" [18]).  Aspect ratio is extreme — n ~ 16-64 agents vs d ~ 1e8-1e11
coordinates — so the kernel tiles d into VMEM-resident (n, TILE_D) blocks and
runs an odd-even transposition sorting NETWORK along the (small, static) agent
axis: n fully-vectorized compare-exchange passes on (TILE_D,)-lane vectors.
This is the TPU-native replacement for the GPU thread-per-coordinate sort.

Two entry points:

:func:`coord_sort`
    Materializes the full sorted (n, d) stack — the historical kernel, kept
    for tests and for callers that derive several statistics from one sort.

:func:`coord_stat`
    The dispatch-path kernel: derives the order statistic (median or
    b-trimmed mean) INSIDE the tile and writes only the (1, TILE_D) result,
    so a model with d > 1e6 parameters never materializes an (n, d) sorted
    copy in HBM — the sorted stack lives and dies in VMEM, one tile at a
    time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import block_d, resolve_interpret


def _sort_network(x):
    """Odd-even transposition sort along axis 0 of (n, t).  n static."""
    n = x.shape[0]
    rows = [x[i] for i in range(n)]
    for p in range(n):
        start = p % 2
        for i in range(start, n - 1, 2):
            lo = jnp.minimum(rows[i], rows[i + 1])
            hi = jnp.maximum(rows[i], rows[i + 1])
            rows[i], rows[i + 1] = lo, hi
    return jnp.stack(rows, axis=0)


def _coord_sort_kernel(g_ref, out_ref):
    out_ref[...] = _sort_network(g_ref[...].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def coord_sort(g, *, interpret: bool | None = None):
    """g: (n, d) -> sorted-per-coordinate (n, d) fp32."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    w = block_d(d, interpret)
    return pl.pallas_call(
        _coord_sort_kernel,
        grid=(pl.cdiv(d, w),),
        in_specs=[pl.BlockSpec((n, w), lambda i: (0, i))],
        out_specs=pl.BlockSpec((n, w), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        interpret=interpret,
    )(g)


def stat_from_sorted(s, stat: str, b: int = 0):
    """Order statistic from a per-coordinate-sorted (n, t) block —
    delegates to the ref.py oracles so the kernel body and the parity
    oracle are literally ONE copy of the load-bearing arithmetic
    (0.5*(lo+hi) median, jnp.mean over the kept slice: bit-for-bit with
    ``repro.core.filters.dense``)."""
    from repro.kernels import ref
    if stat == "median":
        return ref.median_from_sorted(s)
    if stat == "trimmed_mean":
        return ref.trimmed_mean_from_sorted(s, b)
    raise KeyError(stat)


def _coord_stat_kernel(g_ref, out_ref, *, stat, b, exact):
    s = _sort_network(g_ref[...].astype(jnp.float32))
    if exact:
        # interpret mode: stop XLA from reassociating the mean reduce
        # through the stacked sort-network rows — with the barrier the
        # reduce compiles exactly like the dense reference's
        # slice-of-sorted mean, making fp32 results bit-for-bit
        s = jax.lax.optimization_barrier(s)
    out_ref[...] = stat_from_sorted(s, stat, b)[None]


@functools.partial(jax.jit, static_argnames=("stat", "b", "interpret"))
def coord_stat(g, stat: str, b: int = 0, *, interpret: bool | None = None):
    """g: (n, d) -> (d,) fp32 order statistic (``median`` |
    ``trimmed_mean`` with per-side trim ``b``), fused sort+reduce per tile:
    the sorted stack never leaves VMEM."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    w = block_d(d, interpret)
    out = pl.pallas_call(
        functools.partial(_coord_stat_kernel, stat=stat, b=b,
                          exact=interpret),
        grid=(pl.cdiv(d, w),),
        in_specs=[pl.BlockSpec((n, w), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, w), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(g)
    return out[0]
