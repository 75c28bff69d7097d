"""Gradient coding / algorithmic redundancy — survey §3.3.3.

Draco [18]: the parallel setting — the server assigns the SAME data shard to
r agents (repetition / fractional-repetition code).  With <= (r-1)/2 Byzantine
agents per group, a majority vote over each group recovers the exact gradient
(linear-time decode).  We implement the repetition code with a distance-based
majority (floating-point-safe plurality).

DETOX [86]: hierarchical — (1) Draco-style majority vote inside groups of r,
(2) partition the n/r voted gradients into buckets and average, (3) a robust
aggregation (any gradient filter) over bucket means.  Trades redundancy for
both speed and robustness.

Decode paths.  There is ONE copy of the vote law,
:func:`coded_vote_weights`: (n, n) Gram -> (n,) one-hot-per-group decode
weights.  :func:`flat_draco_aggregate` runs it over the zero-copy (n, P)
arena on the Pallas primitives (``kernels.pairwise.gram`` for the vote,
``kernels.wsum.masked_weighted_sum`` for the application — which also
where-zeroes non-winning rows, so a rejected Byzantine row carrying
±inf/NaN cannot leak 0*inf = NaN into the decode).
:func:`tree_draco_aggregate` ravels uniform-dtype pytrees through their
cached :class:`~repro.core.flat.FlatPlan` into that same arena path
(bit-for-bit: the tree entry point IS the arena path), and keeps a
leaf-wise Gram fallback only for mixed-dtype trees.

Roster-aware grouping.  :func:`coding_groups` is the lru-cached per-(n, r)
group table — the same build-time-cache trick as the trim tables
(``aggregators.trim_count``).  Under elastic membership the training loops
re-derive it per bucket capacity when the bucket's step function is built
(respecialize time), grouping the packed LIVE rows positionally.  In the
parallel regime every agent computes the same full-shard gradient, so
regrouping live agents per bucket preserves exact recovery.  A bucket
capacity not divisible by r carries a smaller trailing group (with a
proportionally lower per-group vote tolerance); the *static* entry points
require ``n % r == 0`` and raise :class:`ValueError` otherwise.

Vote tolerance.  Agreement is ``d2 <= tol * scale_g`` where ``scale_g`` is
the per-group MEDIAN delivered row sq-norm.  The historical global
``max(sq)`` scale was attacker-inflatable: one large-value Byzantine row
anywhere in the stack raised every group's tolerance until genuinely
disagreeing rows counted as "agreeing" and the argmax tie-break became
steerable (tests/test_coding.py pins the exploit).  With a delivered
majority of honest rows per group, the median norm is an honest row's
norm, so the steering budget collapses from sqrt(tol)·max-norm to
sqrt(tol)·honest-norm.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.filters import dense as D


@functools.lru_cache(maxsize=None)
def coding_groups(n: int, r: int, allow_ragged: bool = False):
    """The static per-(n, r) group-id table: slot i belongs to group
    ``i // r``.  Cached (lru) and returned read-only — the elastic loops
    call this once per bucket capacity at step-build time and bake the
    table into the bucket's traced step, exactly the trick the trim
    tables use, so churn costs at most one compile per bucket.

    ``allow_ragged`` (elastic buckets only): a capacity not divisible by
    r keeps a smaller trailing group instead of raising."""
    if r <= 0:
        raise ValueError(
            f"gradient coding needs a positive repetition group size: "
            f"got r={r} (n={n})")
    if not allow_ragged and n % r:
        raise ValueError(
            f"draco repetition code needs the group size to divide the "
            f"agent count: got n={n}, r={r} (n % r == {n % r})")
    groups = (np.arange(n, dtype=np.int64) // r)
    groups.setflags(write=False)
    return groups


def draco_assignment(n: int, r: int):
    """Fractional repetition assignment: group g = agents [g*r, (g+1)*r).
    Returns (num_groups, group_of_agent index array).  Raises
    :class:`ValueError` (with the shapes) unless ``r`` divides ``n``."""
    return n // r, jnp.asarray(coding_groups(n, r))


def majority_vote(g, tol: float = 1e-6):
    """Plurality vector among rows of g: (r, d) -> (d,).

    Counts, for each row, how many rows lie within ``tol`` relative to the
    MEDIAN row sq-norm — returns the row with the highest count.  Exact-
    agreement majority in fp arithmetic; the median scale keeps a single
    large-value Byzantine row from inflating the tolerance."""
    d2 = D.pairwise_sq_dists(g)
    sq = jnp.sum(jnp.square(g), axis=-1)
    scale = jnp.maximum(jnp.median(sq), 1e-30)
    votes = jnp.sum(d2 <= tol * scale, axis=-1)
    return g[jnp.argmax(votes)]


def coded_vote_weights(gram, r: int, tol: float = 1e-6, mask=None,
                       groups=None):
    """THE vote law: (n, n) fp32 Gram -> (n,) decode weights (one-hot per
    surviving group, normalized over surviving groups).

    ``mask`` (n,) bool restricts the vote to *delivered* rows: absent rows
    neither vote nor win, groups with no delivery get zero weight, and the
    average renormalizes over the surviving groups.  ``groups`` is a HOST
    (numpy) group-id table from :func:`coding_groups` — static, so the
    group one-hots fold into the trace as constants.

    Agreement tolerance is per group: ``d2 <= tol * median(sq_delivered)``
    of that group — see the module docstring for why not ``max(sq)``."""
    n = gram.shape[0]
    if groups is None:
        groups = coding_groups(n, r)
    groups = np.asarray(groups)
    k = int(groups.max()) + 1
    onehot = groups[None, :] == np.arange(k)[:, None]         # (k, n) static
    same = groups[:, None] == groups[None, :]                 # (n, n) static

    sq = jnp.diag(gram)
    d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * gram, 0.0)
    m = jnp.ones((n,), bool) if mask is None else mask.astype(bool)
    in_group = onehot & m[None, :]                            # (k, n)
    cnt = jnp.sum(in_group, axis=-1)                          # (k,) delivered
    # per-group lower-median delivered sq-norm: with <= (cnt-1)//2
    # Byzantine rows delivered per group, the element at sorted index
    # (cnt-1)//2 is an honest row's norm whatever the attacker sends
    sq_rows = jnp.where(in_group, sq[None, :], jnp.inf)
    mid = jnp.clip((cnt - 1) // 2, 0, n - 1)
    med = jnp.take_along_axis(jnp.sort(sq_rows, axis=-1),
                              mid[:, None], axis=-1)[:, 0]
    scale = jnp.maximum(jnp.where(cnt > 0, med, 0.0), 1e-30)  # (k,)

    agree = (d2 <= tol * scale[jnp.asarray(groups)][:, None]) & same
    votes = jnp.where(m, jnp.sum(agree & m[None, :], axis=-1), -1)
    # winner per group: argmax over the group's slots (-2 outside keeps
    # the historical first-max-in-slot-order tie-break; a delivered row
    # always self-agrees, so it outranks the -1 absent rows)
    win = jnp.argmax(jnp.where(onehot, votes[None, :], -2), axis=-1)
    group_ok = cnt > 0
    group_w = jnp.where(group_ok, 1.0, 0.0) / jnp.maximum(
        jnp.sum(group_ok), 1)
    return jnp.zeros((n,)).at[win].set(group_w)


def flat_draco_aggregate(x, r: int, tol: float = 1e-6, mask=None,
                         groups=None, interpret: bool | None = None):
    """Draco decode over the (n, P) arena: (n, P) -> (P,) fp32.

    The vote rides ``kernels.pairwise.gram`` (one MXU matmul per tile) and
    the application ``kernels.wsum.masked_weighted_sum`` (one-hot winner
    weights are non-negative, satisfying its precondition; non-winning
    rows are where-zeroed, so Byzantine ±inf never leaks).  In interpret
    mode columns are zero-padded to the kernels' TILE_D multiple (zero
    columns change neither the Gram nor the weighted sum) and the pad is
    sliced off; compiled kernels tile a ragged last block in place."""
    from repro.kernels.ops import _pad_d
    from repro.kernels.pairwise import gram
    from repro.kernels.wsum import masked_weighted_sum
    n, p = x.shape
    if groups is None:
        groups = coding_groups(n, r)
    xp, _ = _pad_d(x, interpret)
    w = coded_vote_weights(gram(xp, interpret=interpret), r, tol=tol,
                           mask=mask, groups=groups)
    m = jnp.ones((n,), bool) if mask is None else mask.astype(bool)
    out = masked_weighted_sum(w, xp, m,
                              jnp.zeros((xp.shape[1],), jnp.float32),
                              interpret=interpret)
    return out[:p]


def draco_aggregate(g, r: int, tol: float = 1e-6):
    """g: (n, d) with groups of r computing identical tasks.
    Returns the mean (over groups) majority gradient — exact when each
    group has at most (r-1)//2 Byzantine members."""
    draco_assignment(g.shape[0], r)               # validates n % r == 0
    return flat_draco_aggregate(g, r, tol=tol).astype(g.dtype)


def detox_aggregate(g, r: int, f: int = 0, buckets: int = 0,
                    filter_name: str = "geometric_median",
                    tol: float = 1e-6):
    """DETOX: vote -> bucket-average -> robust aggregate.

    The bucket stage tolerates ``f`` vote-overwhelmed groups only if the
    robust filter sees a strict honest majority of bucket means, i.e.
    ``b >= 2f + 1`` buckets survive the divisibility shrink; otherwise
    the filter silently degrades (at ``b = 1`` it collapses to a plain
    average — zero breakdown), so we raise instead."""
    n, d = g.shape
    k, _ = draco_assignment(n, r)
    voted = jax.vmap(lambda grp: majority_vote(grp, tol))(
        g.reshape(k, r, d))
    b = buckets if buckets else max(1, k // max(2 * f + 1, 1))
    while k % b:
        b -= 1
    if b < 2 * f + 1:
        raise ValueError(
            f"detox: k={k} voted gradients (n={n}, r={r}) admit only "
            f"b={b} equal buckets — cannot hold 2f+1={2 * f + 1} bucket "
            f"means for f={f}; pick n/r with more groups or a lower f")
    means = jnp.mean(voted.reshape(b, k // b, d), axis=1)
    return D.FILTERS[filter_name](means, min(f, max((b - 1) // 2, 0)))


def tree_draco_aggregate(grads, r: int, tol: float = 1e-6, mask=None,
                         groups=None):
    """Draco on pytree gradient stacks.

    Uniform-dtype trees ravel through their cached
    :class:`~repro.core.flat.FlatPlan` into the (n, P) arena and decode
    with :func:`flat_draco_aggregate` — the tree entry point IS the arena
    path, bit-for-bit.  Mixed-dtype trees split into per-dtype sub-arenas:
    the full-row Gram is additive over column segments, so the segment
    Grams (each on the arena ``kernels.pairwise.gram`` primitive) sum into
    ONE Gram feeding ONE vote, and the winner weights apply per segment
    through ``kernels.wsum.masked_weighted_sum`` — same vote law, same
    where-zeroed Byzantine-row hygiene as the uniform path.  The split is
    announced once via :func:`~repro.core.aggregators.warn_once` with the
    offending dtypes (a uniform exchange dtype restores the single arena).

    ``mask`` (n,) bool restricts the vote to *delivered* gradients (the
    async simulator's straggler fallback): absent agents neither vote nor
    win, groups with no delivery are excluded, and the average
    renormalizes over the surviving groups.  ``groups`` (host array from
    :func:`coding_groups`) overrides the static ``i // r`` table — the
    elastic loops pass their bucket's (possibly ragged) table here."""
    from repro.core.aggregators import warn_once
    from repro.core.flat import FlatPlan
    from repro.kernels.ops import _pad_d
    from repro.kernels.pairwise import gram
    from repro.kernels.wsum import masked_weighted_sum
    n = jax.tree.leaves(grads)[0].shape[0]
    if groups is None:
        groups = coding_groups(n, r)
    plan = FlatPlan.for_tree(grads)
    if plan.uniform_dtype is not None:
        vec = flat_draco_aggregate(plan.ravel(grads), r, tol=tol,
                                   mask=mask, groups=groups)
        return plan.unravel(vec)
    # mixed-dtype tree: per-dtype sub-arenas.  Gram(full row) is the sum of
    # the segment Grams (column blocks are disjoint), so the vote sees the
    # SAME (n, n) Gram the single-arena path would — one vote, applied per
    # segment with the arena weighted-sum kernel (winner one-hots are
    # non-negative; losing rows are where-zeroed, so Byzantine ±inf in a
    # rejected row never leaks into the decode).
    leaves, treedef = jax.tree.flatten(grads)
    by_dtype = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.dtype(leaf.dtype), []).append(i)
    warn_once(
        ("draco-mixed-dtype", tuple(sorted(str(d) for d in by_dtype))),
        "tree_draco_aggregate: mixed-dtype gradient tree "
        f"({', '.join(sorted(str(d) for d in by_dtype))}) decodes through "
        "per-dtype sub-arenas instead of one flat arena; set a uniform "
        "exchange dtype (e.g. agg_dtype) to restore the single-ravel path")
    m = jnp.ones((n,), bool) if mask is None else mask.astype(bool)
    segs = {}
    total_gram = None
    for dt, idxs in by_dtype.items():
        seg = jnp.concatenate(
            [leaves[i].reshape(n, -1) for i in idxs], axis=1)
        segp, _ = _pad_d(seg)
        segs[dt] = (idxs, seg.shape[1], segp)
        g = gram(segp)
        total_gram = g if total_gram is None else total_gram + g
    w = coded_vote_weights(total_gram, r, tol=tol, mask=mask, groups=groups)
    out = [None] * len(leaves)
    for dt, (idxs, p, segp) in segs.items():
        vec = masked_weighted_sum(
            w, segp, m, jnp.zeros((segp.shape[1],), jnp.float32))[:p]
        off = 0
        for i in idxs:
            size = leaves[i][0].size
            out[i] = vec[off:off + size].reshape(
                leaves[i].shape[1:]).astype(leaves[i].dtype)
            off += size
    return jax.tree.unflatten(treedef, out)
