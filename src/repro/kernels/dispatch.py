"""Caps-driven kernel dispatch: rule name -> Pallas implementation.

The bridge between the :class:`~repro.core.aggregators.AggregatorSpec`
engine and the kernel layer.  A Table-2 rule is *kernelizable* when its
declared capabilities are coordinate-wise (per-coordinate order statistics
-> :mod:`repro.kernels.coord_stats` / :mod:`repro.kernels.masked`) or
Gram-derivable (pairwise distances / norms -> :mod:`repro.kernels.pairwise`
+ :mod:`repro.kernels.select` + :mod:`repro.kernels.wsum`).  The tables
below are the single source of truth the spec builder queries at
``make_spec`` time to auto-select ``impl="pallas"``.

Every entry has the same contract as the dense gather path it replaces:
input is the fp32 (n, P) raveled gradient stack (masked variants take the
native-dtype stack plus traced mask/weights), output is the (P,) fp32
aggregate, numerically interchangeable with ``impl="gather"`` —
bit-for-bit for the order-statistic and single-selection rules, selection-
identical with ulp-level application rounding for averaged selections
(CGE) — proven case by case in tests/test_kernels_parity.py.

``interpret`` resolution (:func:`repro.kernels.tiling.default_interpret`):
kernels compile to real Mosaic kernels on TPU backends and run in
interpret mode (pure-jax evaluation of the SAME kernel bodies, one coarse
block) everywhere else.  Interpret mode checks the arithmetic, not the
compiled tiling or what Mosaic accepts: tests/test_tpu_compile.py compiles
every kernel here for a described v5e.  Override per call for debugging.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.coord_stats import coord_stat
from repro.kernels.masked import (masked_coord_stat, masked_sign_vote,
                                  scaled_coord_stat,
                                  scaled_masked_coord_stat,
                                  scaled_masked_sign_vote, sign_vote)
from repro.kernels.ops import (_pad_d, kernel_bulyan, kernel_bulyan_masked,
                               kernel_cge, kernel_cge_masked, kernel_krum,
                               kernel_krum_masked, kernel_m_krum,
                               kernel_m_krum_masked, kernel_mda,
                               kernel_mda_masked, kernel_multi_krum,
                               kernel_multi_krum_masked)
from repro.kernels.wsum import (scaled_sparse_masked_weighted_mean,
                                sparse_masked_weighted_mean)

def _trim_b(n: int, f: int, hyper: dict) -> int:
    from repro.core.aggregators import trim_count          # lazy: no cycle
    return trim_count(n, f, hyper.get("beta"))


# ---------------------------------------------------------------------------
# synchronous rules: (stack fp32 (n, P), f, hyper, interpret) -> (P,) fp32


def _median(stack, f, hyper, interpret):
    gp, d = _pad_d(stack, interpret)
    return coord_stat(gp, "median", interpret=interpret)[:d]


def _trimmed_mean(stack, f, hyper, interpret):
    gp, d = _pad_d(stack, interpret)
    b = _trim_b(stack.shape[0], f, hyper)
    return coord_stat(gp, "trimmed_mean", b=b, interpret=interpret)[:d]


def _krum(stack, f, hyper, interpret):
    # gram -> fused selection -> one-hot weighted sum (exactly the
    # selected row's bits); ops.kernel_krum is THE one pipeline copy
    return kernel_krum(stack, f, interpret=interpret)


def _cge(stack, f, hyper, interpret):
    return kernel_cge(stack, f, normalize=hyper.get("normalize", True),
                      interpret=interpret)


def _multi_krum(stack, f, hyper, interpret):
    return kernel_multi_krum(stack, f, m=hyper.get("m", 2),
                             interpret=interpret)


def _m_krum(stack, f, hyper, interpret):
    return kernel_m_krum(stack, f, m=hyper.get("m", 2), interpret=interpret)


def _mda(stack, f, hyper, interpret):
    return kernel_mda(stack, f, interpret=interpret)


def _bulyan(stack, f, hyper, interpret):
    # only the classic krum base is Gram-derivable; make_spec gates the
    # pallas impl on hyper, so a non-krum base never reaches this table
    assert hyper.get("base", "krum") == "krum", hyper
    return kernel_bulyan(stack, f, interpret=interpret)


def _sign_sgd(stack, f, hyper, interpret):
    gp, d = _pad_d(stack, interpret)
    return sign_vote(gp, interpret=interpret)[:d]


def _sparse_mean(stack, f, hyper, interpret):
    # plain = every row live with unit weight; padded columns are all-zero
    # (nobody "sent" them) and slice away
    n = stack.shape[0]
    gp, d = _pad_d(stack, interpret)
    ones = jnp.ones((n,), jnp.float32)
    return sparse_masked_weighted_mean(gp, ones, ones,
                                       interpret=interpret)[:d]


PALLAS_RULES = {
    "coordinate_median": _median,
    "trimmed_mean": _trimmed_mean,
    "krum": _krum,
    "cge": _cge,
    "multi_krum": _multi_krum,
    "m_krum": _m_krum,
    "mda": _mda,
    "bulyan": _bulyan,
    "sign_sgd": _sign_sgd,
    "sparse_mean": _sparse_mean,
}

# rules whose flat_fn fuses its own kernel stages instead of fitting the
# stateless (stack, f, hyper) contract above: centered_clip's fixed-point
# loop carries the server center across iterations, so only its
# model-sized multiply-accumulate rides a kernel
# (wsum.clipped_weighted_sum) — requested with an explicit
# ``impl="pallas"`` (``auto`` keeps the dense flat body: the kernel
# changes the reduce association, so opting in is a numerics decision)
FLAT_SELF_KERNELED = {"centered_clip"}


# ---------------------------------------------------------------------------
# masked / weighted rules: fused masked variants (async quorums) —
# the coordinate statistics impute inside the sort tile, the selection
# family inside the Gram/application tiles (no masked (n, d) copy is ever
# materialized; the coordinate-wise kernels use the arrived-window
# sentinel law, the Gram kernels the mean-imputed law — see
# kernels/masked.py)


def _masked_median(stack, mask, wn, f, hyper, interpret):
    gp, d = _pad_d(stack, interpret)
    return masked_coord_stat(gp, mask, wn, "median",
                             interpret=interpret)[:d]


def _masked_trimmed_mean(stack, mask, wn, f, hyper, interpret):
    gp, d = _pad_d(stack, interpret)
    b = _trim_b(stack.shape[0], f, hyper)
    return masked_coord_stat(gp, mask, wn, "trimmed_mean", b=b,
                             interpret=interpret)[:d]


def _masked_krum(stack, mask, wn, f, hyper, interpret):
    return kernel_krum_masked(stack, mask, wn, f, interpret=interpret)


def _masked_cge(stack, mask, wn, f, hyper, interpret):
    return kernel_cge_masked(stack, mask, wn, f,
                             normalize=hyper.get("normalize", True),
                             interpret=interpret)


def _masked_multi_krum(stack, mask, wn, f, hyper, interpret):
    return kernel_multi_krum_masked(stack, mask, wn, f,
                                    m=hyper.get("m", 2),
                                    interpret=interpret)


def _masked_m_krum(stack, mask, wn, f, hyper, interpret):
    return kernel_m_krum_masked(stack, mask, wn, f, m=hyper.get("m", 2),
                                interpret=interpret)


def _masked_mda(stack, mask, wn, f, hyper, interpret):
    return kernel_mda_masked(stack, mask, wn, f, interpret=interpret)


def _masked_bulyan(stack, mask, wn, f, hyper, interpret):
    assert hyper.get("base", "krum") == "krum", hyper
    return kernel_bulyan_masked(stack, mask, wn, f, interpret=interpret)


def _masked_sign_sgd(stack, mask, wn, f, hyper, interpret):
    gp, d = _pad_d(stack, interpret)
    return masked_sign_vote(gp, mask, wn, interpret=interpret)[:d]


def _masked_sparse_mean(stack, mask, wn, f, hyper, interpret):
    # the wn slot carries RAW mask-folded row weights (dataset sizes), not
    # the normalized w/tot the imputing rules take — sparse_mean's law is
    # invariant under global weight scaling, so both conventions agree
    gp, d = _pad_d(stack, interpret)
    return sparse_masked_weighted_mean(gp, mask, wn,
                                       interpret=interpret)[:d]


PALLAS_MASKED_RULES = {
    "coordinate_median": _masked_median,
    "trimmed_mean": _masked_trimmed_mean,
    "krum": _masked_krum,
    "cge": _masked_cge,
    "multi_krum": _masked_multi_krum,
    "m_krum": _masked_m_krum,
    "mda": _masked_mda,
    "bulyan": _masked_bulyan,
    "sign_sgd": _masked_sign_sgd,
    "sparse_mean": _masked_sparse_mean,
}


# ---------------------------------------------------------------------------
# scaled rules: the arena holds int8/fp8 codes + a per-row fp32 dequant
# scale sidecar (core.flat.quantize_rows); these kernels dequantize INSIDE
# the tile, so no dequantized (n, P) copy is ever materialized (jaxpr-gated
# in tests/test_kernels_parity.py).  Rules without an entry here pay an
# engine-level dequant copy (aggregators._flat_dequant warns once).


def _scaled_median(stack, qs, f, hyper, interpret):
    gp, d = _pad_d(stack, interpret)
    return scaled_coord_stat(gp, qs, "median", interpret=interpret)[:d]


def _scaled_trimmed_mean(stack, qs, f, hyper, interpret):
    gp, d = _pad_d(stack, interpret)
    b = _trim_b(stack.shape[0], f, hyper)
    return scaled_coord_stat(gp, qs, "trimmed_mean", b=b,
                             interpret=interpret)[:d]


def _scaled_sign_sgd(stack, qs, f, hyper, interpret):
    # sign(code * scale) == sign(code): scales are strictly positive, so
    # the plain vote kernel reads the codes directly — zero dequant cost
    gp, d = _pad_d(stack, interpret)
    return sign_vote(gp, interpret=interpret)[:d]


def _scaled_sparse_mean(stack, qs, f, hyper, interpret):
    n = stack.shape[0]
    gp, d = _pad_d(stack, interpret)
    ones = jnp.ones((n,), jnp.float32)
    return scaled_sparse_masked_weighted_mean(gp, qs, ones, ones,
                                              interpret=interpret)[:d]


PALLAS_SCALED_RULES = {
    "coordinate_median": _scaled_median,
    "trimmed_mean": _scaled_trimmed_mean,
    "sign_sgd": _scaled_sign_sgd,
    "sparse_mean": _scaled_sparse_mean,
}


def _scaled_masked_median(stack, qs, mask, wn, f, hyper, interpret):
    gp, d = _pad_d(stack, interpret)
    return scaled_masked_coord_stat(gp, qs, mask, wn, "median",
                                    interpret=interpret)[:d]


def _scaled_masked_trimmed_mean(stack, qs, mask, wn, f, hyper, interpret):
    gp, d = _pad_d(stack, interpret)
    b = _trim_b(stack.shape[0], f, hyper)
    return scaled_masked_coord_stat(gp, qs, mask, wn, "trimmed_mean", b=b,
                                    interpret=interpret)[:d]


def _scaled_masked_sign_sgd(stack, qs, mask, wn, f, hyper, interpret):
    gp, d = _pad_d(stack, interpret)
    return scaled_masked_sign_vote(gp, qs, mask, wn,
                                   interpret=interpret)[:d]


def _scaled_masked_sparse_mean(stack, qs, mask, wn, f, hyper, interpret):
    gp, d = _pad_d(stack, interpret)
    return scaled_sparse_masked_weighted_mean(gp, qs, mask, wn,
                                              interpret=interpret)[:d]


PALLAS_SCALED_MASKED_RULES = {
    "coordinate_median": _scaled_masked_median,
    "trimmed_mean": _scaled_masked_trimmed_mean,
    "sign_sgd": _scaled_masked_sign_sgd,
    "sparse_mean": _scaled_masked_sparse_mean,
}


# ---------------------------------------------------------------------------
# entry points the spec engine calls


def pallas_supported(name: str) -> bool:
    return name in PALLAS_RULES


def pallas_masked_supported(name: str) -> bool:
    return name in PALLAS_MASKED_RULES


def pallas_scaled_supported(name: str) -> bool:
    """True iff ``name`` dequantizes a quantized (codes + per-row scale)
    arena inside its kernel tiles (both sync and masked variants)."""
    return (name in PALLAS_SCALED_RULES
            and name in PALLAS_SCALED_MASKED_RULES)


@functools.partial(jax.jit,
                   static_argnames=("name", "f", "hyper", "interpret"))
def pallas_aggregate(name: str, stack, f: int, hyper: tuple = (), *,
                     interpret: bool | None = None):
    """stack: fp32 (n, P) -> (P,) fp32 via the rule's Pallas kernels.
    ``hyper`` is the spec's sorted static hyper tuple."""
    return PALLAS_RULES[name](stack, f, dict(hyper), interpret)


@functools.partial(jax.jit,
                   static_argnames=("name", "f", "hyper", "interpret"))
def pallas_masked_aggregate(name: str, stack, mask, wn, f: int,
                            hyper: tuple = (), *,
                            interpret: bool | None = None):
    """Mean-imputed masked statistic; mask/wn are TRACED (n,) operands, so
    per-step fault masks never retrigger compilation."""
    return PALLAS_MASKED_RULES[name](stack, mask, wn, f, dict(hyper),
                                     interpret)


@functools.partial(jax.jit,
                   static_argnames=("name", "f", "hyper", "interpret"))
def pallas_scaled_aggregate(name: str, stack, qscale, f: int,
                            hyper: tuple = (), *,
                            interpret: bool | None = None):
    """stack: quantized (n, P) codes, qscale: (n,) fp32 per-row dequant
    scale -> (P,) fp32 aggregate, dequantization fused into the tiles."""
    return PALLAS_SCALED_RULES[name](stack, qscale, f, dict(hyper),
                                     interpret)


@functools.partial(jax.jit,
                   static_argnames=("name", "f", "hyper", "interpret"))
def pallas_scaled_masked_aggregate(name: str, stack, qscale, mask, wn,
                                   f: int, hyper: tuple = (), *,
                                   interpret: bool | None = None):
    """Masked variant of :func:`pallas_scaled_aggregate` — qscale, mask
    and wn are all TRACED (n,) operands (fault masks and per-step scales
    never retrigger compilation)."""
    return PALLAS_SCALED_MASKED_RULES[name](stack, qscale, mask, wn, f,
                                            dict(hyper), interpret)
