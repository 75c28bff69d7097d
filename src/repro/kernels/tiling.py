"""Shared tile-width and interpret-mode policy for the aggregation kernels.

Compiled TPU kernels tile the huge d axis into VMEM-resident TILE_D-lane
blocks (n sublanes x 512 lanes, fp32): the Gram, weighted-sum, selection,
masked, scaled, sign-vote and Bulyan kernels, coord_sort, and coord_stat
on stacks that are not lane-dense.  coord_stat on an fp32 stack with
n % 8 == 0 and d % 128 == 0 reads the arena's own T(8, 128) layout in
blocks of its own (repro.kernels.coord_stats).  Interpret mode has no VMEM to
respect, but it DOES pay the interpreter's per-grid-step dispatch cost
(~10 ms/step): at model scale (d ~ 1e6 -> thousands of tiles) a tiled
grid turns one aggregation into tens of seconds on CPU.  So off-TPU the
kernels run the SAME kernel body over one coarse block — identical code
path and arithmetic (the parity suite pins fp32 bit-for-bit), CPU cost
back to the plain-XLA ballpark.  The compiled tiling is checked by
compiling for a described v5e (tests/test_tpu_compile.py), which interpret
mode cannot stand in for.

Every kernel entry point takes ``interpret=None`` by default and resolves
it here: compiled Mosaic kernels on a TPU backend, the interpreter
everywhere else.
"""
from __future__ import annotations

import jax

TILE_D = 512

_INTERPRET = None


def default_interpret() -> bool:
    """True (interpret mode) unless running on a real TPU backend."""
    global _INTERPRET
    if _INTERPRET is None:
        _INTERPRET = jax.default_backend() != "tpu"
    return _INTERPRET


def resolve_interpret(interpret: bool | None) -> bool:
    """An explicit ``interpret`` wins; None means :func:`default_interpret`."""
    return default_interpret() if interpret is None else bool(interpret)


def block_d(d: int, interpret: bool) -> int:
    """Block width along d.  Compiled grids take ``pl.cdiv(d, TILE_D)``
    blocks: a ragged last block reads undefined columns past d, which
    only the Gram kernels (a reduction over d) must mask; every other
    kernel's output column depends on its own input column alone, and
    writes past d are dropped.  Interpret mode runs one block over the
    stack, which repro.kernels.ops._pad_d zero-pads to a TILE_D multiple
    first."""
    return d if interpret else TILE_D
