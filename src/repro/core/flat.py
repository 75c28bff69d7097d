"""FlatPlan: the precomputed ravel/unravel plan of the zero-copy flat
aggregation pipeline.

Every dense aggregation path (``impl="gather"``, ``impl="pallas"``) works on
the raveled (n, P) gradient stack, but the legacy engine rebuilt the
flattening *inside every aggregation call*: ``tree_stack_ravel`` re-derived
each leaf's size and re-concatenated the model-sized stack per call, and
``tree_unravel_like`` recomputed ``np.prod`` offsets per call inside traced
code.  At model scale that is pure memory traffic and trace-time overhead on
the hottest path in the system (the survey's per-step aggregation tax).

A :class:`FlatPlan` hoists all of that to plan time:

* leaf offsets / trailing shapes / dtypes are computed ONCE per tree
  structure (cached on ``(treedef, shapes, dtypes)`` — a dict probe on
  every later call, including calls inside jit traces);
* :meth:`FlatPlan.ravel` builds the (n, P) arena with one concatenate —
  the training loops call it once per step at gradient-production time and
  thread the arena through the jitted step (donated on TPU backends);
* :meth:`FlatPlan.unravel` splits the aggregate back into the parameter
  tree exactly once, at optimizer-apply — never inside the aggregation
  dispatch.

The arena keeps each leaf's dtype.  A tree of one dtype gets one (n, P)
array of that dtype (so ``agg_dtype`` exchange-compression survives the
flattening), bit-for-bit with the per-call ravel it replaces.  A tree
whose leaves mix dtypes (Mamba-2's float32 ``A_log``/``dt_bias``/``D``
beside bf16 matrices, an fp32 MoE router) gets a *segmented* arena: a
tuple of (n, P_k) arrays, one per dtype in order of first appearance,
each laid out exactly as a uniform arena of that dtype.  No coordinate
is widened into or narrowed by another leaf's dtype, and the arena takes
the bytes its leaves take.  A single array is always in tree order and a
tuple always in segment order; :meth:`FlatPlan.unravel` reads either.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.counters import inc


@dataclass(frozen=True)
class FlatPlan:
    """Ravel/unravel plan for a pytree with a leading agent axis.

    ``shapes``/``dtypes`` describe the per-leaf TRAILING dims (agent axis
    stripped); ``offsets[i]:offsets[i] + sizes[i]`` is leaf i's slice of
    the (n, P) arena; ``total`` is P.  ``segments`` lists, per dtype in
    order of first appearance, ``(dtype name, leaf indices)``;
    ``seg_offsets[i]`` is leaf i's offset inside its segment.  Frozen and
    hashable, so plans pass freely through jit closures as statics."""
    treedef: Any
    shapes: tuple
    dtypes: tuple
    offsets: tuple
    sizes: tuple
    total: int
    uniform_dtype: Optional[Any]
    segments: tuple
    seg_offsets: tuple

    @staticmethod
    def for_tree(tree) -> "FlatPlan":
        """The (cached) plan of ``tree``, whose leaves carry a leading
        agent axis.  Works on tracers — only shapes/dtypes are read."""
        leaves, treedef = jax.tree.flatten(tree)
        return _plan(treedef,
                     tuple(tuple(l.shape[1:]) for l in leaves),
                     tuple(jnp.dtype(l.dtype).name for l in leaves))

    @staticmethod
    def for_proto(proto) -> "FlatPlan":
        """The plan of a SINGLE-AGENT prototype (no leading agent axis)."""
        leaves, treedef = jax.tree.flatten(proto)
        return _plan(treedef,
                     tuple(tuple(l.shape) for l in leaves),
                     tuple(jnp.dtype(l.dtype).name for l in leaves))

    @property
    def arena_dtype(self):
        """Dtype of the (n, P) arena :meth:`ravel` builds: the uniform
        leaf dtype, or None for a tree of mixed dtypes, whose arena is one
        segment per dtype (:attr:`segments`)."""
        return jnp.dtype(self.uniform_dtype) if self.uniform_dtype else None

    def ravel(self, tree, dtype=None):
        """(pytree with leading n) -> its arena: one (n, P) array in
        ``dtype`` (default: the uniform leaf dtype) with ONE concatenate,
        or, for a mixed-dtype tree and no ``dtype``, the tuple of its
        (n, P_k) segments, one concatenate each."""
        leaves = jax.tree.leaves(tree)
        n = leaves[0].shape[0]
        if dtype is None and self.uniform_dtype is None:
            inc("core.flat.segments", len(self.segments))
            return tuple(
                jnp.concatenate([leaves[i].reshape(n, -1) for i in idx],
                                axis=1)
                for _, idx in self.segments)
        inc("core.flat.segments")
        dt = jnp.dtype(dtype) if dtype is not None else self.arena_dtype
        return jnp.concatenate(
            [l.reshape(n, -1).astype(dt) for l in leaves], axis=1)

    def _slices(self, arena):
        """(array, offset, size) of each leaf along ``arena``'s last axis:
        tree-order offsets for one array, segment offsets for a tuple."""
        if not isinstance(arena, tuple):
            return [(arena, o, s) for o, s in zip(self.offsets, self.sizes)]
        seg_of = {i: k for k, (_, idx) in enumerate(self.segments)
                  for i in idx}
        return [(arena[seg_of[i]], self.seg_offsets[i], s)
                for i, s in enumerate(self.sizes)]

    def unravel(self, vec):
        """(P,) or a tuple of per-segment (P_k,) -> single-agent pytree
        (leaf dtypes restored)."""
        out = [jax.lax.slice(v, (o,), (o + s,)).reshape(shp).astype(dt)
               for (v, o, s), shp, dt in zip(self._slices(vec), self.shapes,
                                             self.dtypes)]
        return jax.tree.unflatten(self.treedef, out)

    def unravel_stack(self, arena):
        """(n, P) or a tuple of (n, P_k) segments -> pytree with leading n
        (leaf dtypes restored)."""
        out = [jax.lax.slice(a, (0, o), (a.shape[0], o + s))
               .reshape((a.shape[0],) + shp).astype(dt)
               for (a, o, s), shp, dt in zip(self._slices(arena),
                                             self.shapes, self.dtypes)]
        return jax.tree.unflatten(self.treedef, out)


# ---------------------------------------------------------------------------
# wire quantization: per-row symmetric codes + fp32 scale sidecar
#
# The compressed-exchange path (ByzantineConfig.agg_dtype in QUANT_DTYPES)
# quantizes the fp32 arena right after ravel — each agent row gets its own
# scale (rows are per-agent messages; one outlier agent must not crush
# everyone else's resolution) — and the kernels dequantize INSIDE the tile,
# so the (n, P) dequantized copy is never materialized (jaxpr-gated in
# tests/test_kernels_parity.py).  qmax is the symmetric code range: 127 for
# int8, 448 for float8_e4m3fn (its largest finite value).

QUANT_DTYPES = {"int8": 127.0}
if hasattr(jnp, "float8_e4m3fn"):
    QUANT_DTYPES["float8_e4m3fn"] = 448.0


def quantize_rows(x, dtype):
    """fp32 (n, P) -> (codes (n, P) ``dtype``, scale (n,) fp32), per-row
    symmetric: ``scale_i = amax_i / qmax`` (1.0 for an all-zero row, so
    dequantization never divides by zero), ``codes = x / scale`` rounded
    (integer dtypes) or cast (fp8).  ``dequantize_rows(codes, scale)``
    reconstructs within one code step."""
    name = jnp.dtype(dtype).name
    qmax = QUANT_DTYPES[name]
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=1)
    scale = jnp.where(amax > 0.0, amax / qmax, 1.0)
    codes = xf / scale[:, None]
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        codes = jnp.clip(jnp.round(codes), -qmax, qmax)
    return codes.astype(dtype), scale


def dequantize_rows(codes, scale):
    """(codes (n, P), scale (n,) fp32) -> fp32 (n, P).  The reference
    arithmetic for the in-tile dequantization — the scaled kernels compute
    exactly ``codes.astype(f32) * scale[:, None]`` per VMEM block, so this
    host-visible version is the bit-for-bit parity oracle."""
    return codes.astype(jnp.float32) * scale[:, None]


def fake_quantize(x, dtype):
    """Quantize-dequantize round trip: the fp32 stack every NON-flat
    consumer (tree fallbacks, telemetry weights on gather) sees, so the
    compressed run's semantics do not depend on which path executed."""
    codes, scale = quantize_rows(x, dtype)
    return dequantize_rows(codes, scale)


@functools.lru_cache(maxsize=None)
def _plan(treedef, shapes, dtypes) -> FlatPlan:
    sizes = tuple(int(np.prod(s, dtype=np.int64)) for s in shapes)
    offsets = tuple(int(o) for o in np.cumsum((0,) + sizes)[:-1])
    uniform = dtypes[0] if len(set(dtypes)) == 1 else None
    by_dt: dict = {}
    for i, dt in enumerate(dtypes):
        by_dt.setdefault(dt, []).append(i)
    segments = tuple((dt, tuple(idx)) for dt, idx in by_dt.items())
    seg_offsets = [0] * len(sizes)
    for _, idx in segments:
        at = 0
        for i in idx:
            seg_offsets[i], at = at, at + sizes[i]
    return FlatPlan(treedef=treedef, shapes=shapes, dtypes=dtypes,
                    offsets=offsets, sizes=sizes,
                    total=int(sum(sizes)), uniform_dtype=uniform,
                    segments=segments, seg_offsets=tuple(seg_offsets))


def arena_rows(arena, idx):
    """Rows ``idx`` of an arena, one array or a tuple of segments."""
    return jax.tree.map(lambda a: a[idx], arena)


__all__ = ["FlatPlan", "QUANT_DTYPES", "arena_rows", "quantize_rows",
           "dequantize_rows", "fake_quantize"]
