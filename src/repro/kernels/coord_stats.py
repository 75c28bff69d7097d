"""Pallas TPU kernel: coordinate-wise order statistics over the agent axis.

The aggregation hot-spot of median-family gradient filters (survey: "the
median-based aggregation still dominates the training time in large-scale
settings" [18]).  Aspect ratio is extreme — n ~ 16-64 agents vs d ~ 1e8-1e11
coordinates — so the kernels tile d into VMEM-resident blocks and run an
odd-even transposition sorting NETWORK along the (small, static) agent
axis: n fully-vectorized compare-exchange passes.  This is the TPU-native
replacement for the GPU thread-per-coordinate sort.

Two entry points:

:func:`coord_sort`
    Materializes the full sorted (n, d) stack — the historical kernel, kept
    for tests and for callers that derive several statistics from one sort.
    It tiles d into (n, TILE_D) blocks (:mod:`repro.kernels.tiling`).

:func:`coord_stat`
    The dispatch-path kernel: derives the order statistic (median or
    b-trimmed mean) INSIDE the block and writes only the result, so a model
    with d > 1e6 parameters never materializes an (n, d) sorted copy in
    HBM — the sorted stack lives and dies in VMEM, one block at a time.
    It picks its tiling from the input's shape and dtype:

    * lane-dense, for an fp32 stack with n % 8 == 0 and d % 128 == 0 (the
      training arena): an fp32 (n, d) array tiled T(8, 128) is, byte for
      byte, an (n/8, d/128 * 8, 128) array whose row r*8 + i holds
      coordinates r*128 ... r*128 + 127 of agent a*8 + i.  The kernel reads
      that view (a bitcast: no relayout copy), gathers each agent's
      (rows, 128) slab with a sublane-strided load, and runs the network on
      full vregs over strips of STRIP rows, in blocks of about
      LANE_BLOCK_BYTES;
    * tiled, for every other stack: (n, TILE_D) blocks, one agent per
      sublane.

    :mod:`repro.obs.counters` counts each trace of either path
    (``kernels.coord_stat.lane_dense``, ``kernels.coord_stat.tiled``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import block_d, resolve_interpret
from repro.obs.counters import count_trace

LANE = 128
# an input block of the lane-dense path: 1,024 rows per agent at n = 8
LANE_BLOCK_BYTES = 4 << 20
# rows per agent in one sort-network pass: n x STRIP x 128 fp32 stays in vregs
STRIP = 16


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


def lane_dense(g) -> bool:
    """True where the (n, d) stack's own T(8, 128) layout is the
    lane-dense view: fp32, n % 8 == 0, d % 128 == 0."""
    n, d = g.shape
    return g.dtype == jnp.float32 and n % 8 == 0 and d % LANE == 0


def _lane_view(g):
    """(n, d) -> (n/8, d/128 * 8, 128): the same bytes under T(8, 128)."""
    n, d = g.shape
    r = d // LANE
    return (g.reshape(n // 8, 8, r, LANE).transpose(0, 2, 1, 3)
            .reshape(n // 8, r * 8, LANE))


def _lane_rows(n: int, r: int) -> int:
    """Rows of 128 lanes per agent in one block: LANE_BLOCK_BYTES of input,
    a STRIP multiple, or all r rows where fewer."""
    rows = LANE_BLOCK_BYTES // (n * LANE * 4) // STRIP * STRIP
    return min(max(rows, STRIP), r)


def _lane_stat_kernel(g_ref, out_ref, *, stat, b, strip, exact):
    n = g_ref.shape[0] * 8
    rows = out_ref.shape[0]

    def run(r0, size):
        # agent i of the strip: sublane i % 8 of every 8th row, (size, 128)
        x = jnp.stack([g_ref[i // 8, pl.ds(r0 * 8 + i % 8, size, stride=8), :]
                       for i in range(n)])
        s = _sort_network(x)
        if exact:
            s = jax.lax.optimization_barrier(s)   # see _coord_stat_kernel
        out_ref[pl.ds(r0, size), :] = stat_from_sorted(s, stat, b)

    full, tail = divmod(rows, strip)

    def body(k, carry):
        run(pl.multiple_of(k * strip, strip), strip)
        return carry

    jax.lax.fori_loop(0, full, body, 0)
    if tail:
        run(full * strip, tail)


def _coord_stat_lane_dense(g, stat, b, *, rows, strip, interpret):
    """Lane-dense coord_stat in blocks of ``rows`` rows of 128 lanes per
    agent, the network run over strips of ``strip`` rows."""
    n, d = g.shape
    r = d // LANE
    out = pl.pallas_call(
        functools.partial(_lane_stat_kernel, stat=stat, b=b, strip=strip,
                          exact=interpret),
        grid=(pl.cdiv(r, rows),),
        in_specs=[pl.BlockSpec((n // 8, rows * 8, LANE),
                               lambda j: (0, j, 0))],
        out_specs=pl.BlockSpec((rows, LANE), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((r, LANE), jnp.float32),
        interpret=interpret,
        name="coord_stat",
    )(_lane_view(g))
    return out.reshape(d)


@functools.partial(jax.jit, static_argnames=("stat", "b", "interpret"))
def coord_stat(g, stat: str, b: int = 0, *, interpret: bool | None = None):
    """g: (n, d) -> (d,) fp32 order statistic (``median`` |
    ``trimmed_mean`` with per-side trim ``b``), fused sort+reduce per
    block: the sorted stack never leaves VMEM.  Lane-dense blocks where
    :func:`lane_dense` holds, (n, TILE_D) blocks otherwise."""
    interpret = resolve_interpret(interpret)
    n, d = g.shape
    if lane_dense(g):
        count_trace("kernels.coord_stat.lane_dense")
        # interpret mode: one block, one strip (see repro.kernels.tiling)
        rows = d // LANE if interpret else _lane_rows(n, d // LANE)
        strip = rows if interpret else min(STRIP, rows)
        return _coord_stat_lane_dense(g, stat, b, rows=rows, strip=strip,
                                      interpret=interpret)
    count_trace("kernels.coord_stat.tiled")
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
