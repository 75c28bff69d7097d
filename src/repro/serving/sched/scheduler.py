"""Continuous-batching scheduler for replicated Byzantine-tolerant decode.

:func:`~repro.serving.engine.generate_replicated` is f-of-r fault-
tolerant for ONE request; this is the control plane that runs MANY
concurrent generation streams through the same replicated vote.  The
three moving parts:

**Continuous batching over a padded slot batch.**  Active requests live
in slots of a fixed-capacity batch; streams join and retire mid-decode.
The batch capacity is drawn from ``slot_buckets`` (the PR 4 elastic-
bucket trick applied to the batch dimension B): the jitted replicated
decode step is compiled once per bucket — request churn costs at most
``len(slot_buckets)`` compilations EVER, counted by ``obs.counters``
site ``sched_decode`` — and requests at different depths coexist in one
batch via per-row decode positions (``cache["pos"]`` as a (B,) vector —
see :func:`repro.models.attention.decode_attention`).  Joining requests
are prefilled at their exact prompt length (one ``sched_prefill``
compile per distinct length; padding a prompt would change its bits)
and their cache rows spliced into the slot slab; retired slots are
repacked out when the active set fits a smaller bucket.  Slot rows are
bit-independent, so every stream's tokens are EXACTLY the tokens
``generate_replicated`` emits for that request alone — the conformance
contract ``tests/test_serving_chaos.py`` pins.

**SLO-aware early commit.**  Replicas finish a decode step at different
virtual times (``delays``, e.g. a :class:`~repro.simulator.faults.
FaultTrace` delay matrix).  Instead of always waiting for the slowest
live replica and running the full robust aggregation, a slot's token is
committed as soon as the first ``f + 1`` live replicas AGREE BITWISE on
the argmax — by the approximate-consensus bound (Liu, Gupta & Vaidya,
arXiv:2101.09337), any f+1 agreeing replicas contain an honest one, and
honest replicas are deterministic, so the early token equals the full-
quorum token whenever at most f replicas are corrupt.  A slot that
cannot reach f+1 consistency by ``deadline`` virtual seconds falls back
to the full masked-aggregation vote over all live replicas (the exact
:class:`~repro.serving.agreement.Agreement` program the engine runs —
never a third copy), committing at the slowest live arrival.  Both paths
are bit-identical to ``generate_replicated`` under <= f corruption;
beyond f, f+1 COLLUDING replicas that answer fastest can steer an early
commit — the tolerance bound is tight, and the chaos suite demonstrates
the break.

**Suspicion-driven roster policy.**  With a
:class:`~repro.serving.sched.policy.SuspicionPolicy` attached, every
step's (r,) per-replica selection weights are streamed through the
recorder (:meth:`~repro.obs.recorder.Recorder.subscribe`) into the
policy, which evicts replicas whose selection rate pins at zero and
folds cooled-off standbys back in; evicted replicas keep decoding as
warm standbys (their caches advance with the agreed tokens) so
reinstatement is instantly consistent.  On steps where every slot
commits early the aggregation never runs — the telemetry row is then
the argmax-agreement share (the fraction of slots whose committed token
the replica reproduced), which pins corrupted replicas at zero just the
same.

Virtual-time accounting (latency percentiles, throughput, early-commit
fraction) lands in :class:`~repro.serving.sched.metrics.ServingMetrics`;
the load benchmark (``benchmarks/bench_serving.py``) sweeps Poisson
offered load x fault rate over this scheduler.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import decode_step, init_cache, prefill
from repro.obs.counters import count_trace
from repro.obs.spans import (SCHED_ADMIT, SCHED_COMMIT, SCHED_DECODE,
                             SCHED_PREFILL, SCHED_QUEUED, SCHED_RESIZE,
                             SCHED_SPLICE, SCHED_STEP, SCHED_TO_HOST,
                             SCHED_VOTE, close_span, open_span, span)
from repro.serving.agreement import Agreement
from repro.serving.sched.metrics import ServingMetrics
from repro.serving.sched.queue import Request, RequestQueue


# ---------------------------------------------------------------------------
# slot-slab cache helpers: per-row decode positions + batch-axis surgery


def _is_pos(path) -> bool:
    return getattr(path[-1], "key", None) == "pos"


def vectorize_cache_pos(cache, batch: int):
    """Turn every scalar ``pos`` leaf into a per-row vector.

    Appends a (batch,) axis to each ``pos`` leaf (top-level and the
    per-layer stacks alike), broadcasting the current value — the form
    :func:`repro.models.attention.decode_attention` treats as per-row
    decode positions.  Non-``pos`` leaves pass through untouched."""
    def fn(path, leaf):
        if _is_pos(path):
            return jnp.broadcast_to(leaf[..., None], leaf.shape + (batch,))
        return leaf
    return jax.tree_util.tree_map_with_path(fn, cache)


def slot_axes(make_cache):
    """Locate the slot (batch) axis of every cache leaf.

    ``make_cache(B)`` builds the (possibly replica-stacked) vectorized
    cache for B slots; comparing the B=1 and B=2 shape trees finds, per
    leaf, the single axis that scales with B — family-agnostic (KV
    rings, SSM states, conv tails and pos vectors all resolve without
    naming them)."""
    s1 = jax.eval_shape(lambda: make_cache(1))
    s2 = jax.eval_shape(lambda: make_cache(2))

    def ax(a, b):
        d = [i for i, (x, y) in enumerate(zip(a.shape, b.shape)) if x != y]
        if len(d) != 1:
            raise ValueError(
                f"cannot locate slot axis: shapes {a.shape} vs {b.shape}")
        return d[0]
    return jax.tree.map(ax, s1, s2)


def slab_grow(slab, axes, extra: int):
    """Append ``extra`` zero slots along each leaf's slot axis."""
    def pad(a, ax):
        pw = [(0, 0)] * a.ndim
        pw[ax] = (0, extra)
        return jnp.pad(a, pw)
    return jax.tree.map(pad, slab, axes)


def slab_take(slab, axes, idx):
    """Reorder/shrink: keep slot rows ``idx`` (exact copies, bit-safe)."""
    idx = jnp.asarray(np.asarray(idx, np.int32))
    return jax.tree.map(lambda a, ax: jnp.take(a, idx, axis=ax),
                        slab, axes)


def slab_write(slab, axes, rows, slots):
    """Splice ``rows`` (a cache with len(slots) slot rows) into ``slab``
    at slot indices ``slots`` (exact copies, bit-safe)."""
    slots = jnp.asarray(np.asarray(slots, np.int32))

    def w(a, r, ax):
        ix = (slice(None),) * ax + (slots,)
        return a.at[ix].set(r)
    return jax.tree.map(w, slab, rows, axes)


# ---------------------------------------------------------------------------
# the scheduler


class ReplicatedScheduler:
    """Continuous batching of replicated decode streams (see module doc).

    ``cfg``/``params_stack``: arch + replica-stacked params ((r, ...)
    leaves, as for ``generate_replicated``).  ``aggregator``: the
    :class:`~repro.core.aggregators.AggregatorSpec` voting each step
    (static, or elastic over replica rosters).  ``slot_buckets``:
    ascending batch capacities; the largest bounds concurrent streams.
    ``seq_capacity``: per-slot cache capacity (prompt + budget of every
    admitted request must fit).  ``early_commit``/``deadline``: the SLO
    policy — commit on first f+1 bitwise-consistent live replicas,
    falling back to the full vote when consistency is not reached within
    ``deadline`` virtual seconds (None = wait as long as it takes).
    ``delays``: per-replica decode-step latencies — an (steps, r) array
    (e.g. ``FaultTrace.delay``) or ``fn(step) -> (r,)``; default: every
    replica takes ``base_step_time``.  ``fault_hook(step, logits)``:
    the replica-boundary corruption point, same contract as the engine's.
    ``policy``: a :class:`SuspicionPolicy` driving the voting roster;
    ``recorder``/``telemetry``: flight-recorder hooks (a policy without
    a recorder gets an in-memory one).
    """

    def __init__(self, cfg, params_stack, aggregator, *,
                 slot_buckets=(2, 4, 8), seq_capacity: int = 64,
                 early_commit: bool = True, deadline: float | None = None,
                 delays=None, base_step_time: float = 1.0,
                 fault_hook=None, policy=None, recorder=None,
                 telemetry: bool | None = None, jit: bool = True,
                 queue: RequestQueue | None = None):
        if getattr(cfg, "is_encdec", False) or getattr(
                cfg, "frontend", "none") not in (None, "none", "text"):
            raise NotImplementedError(
                "the scheduler serves token-frontend decoder-only archs; "
                "encoder-decoder / vision / audio requests carry per-"
                "request encoder state the slot slab does not hold yet")
        buckets = tuple(int(b) for b in slot_buckets)
        if not buckets or list(buckets) != sorted(set(buckets)) \
                or buckets[0] < 1:
            raise ValueError(
                f"slot_buckets must be ascending positive ints, "
                f"got {slot_buckets}")
        self.cfg = cfg
        self.params_stack = params_stack
        self.spec = aggregator
        self.buckets = buckets
        self.seq_capacity = int(seq_capacity)
        self.early_commit = bool(early_commit)
        self.deadline = deadline
        self.delays = delays
        self.base_step_time = float(base_step_time)
        self.fault_hook = fault_hook
        self.jit = bool(jit)
        self.r = jax.tree.leaves(params_stack)[0].shape[0]

        el = getattr(aggregator, "elastic_n", None)
        if el is not None and el.n_max != self.r:
            raise ValueError(
                f"elastic aggregator {aggregator.describe()} was built for "
                f"n_max={el.n_max} but params_stack has {self.r} replicas")

        self.policy = policy
        if policy is not None and recorder is None:
            from repro.obs.recorder import Recorder
            recorder = Recorder()                 # in-memory event bus
        self.recorder = recorder
        if telemetry is None:
            telemetry = recorder is not None or policy is not None
        self.telemetry = bool(telemetry)
        if policy is not None:
            policy.attach(recorder)
        self.agreement = Agreement(aggregator, telemetry=self.telemetry,
                                   jit=self.jit, site="sched_agree")

        self.queue = queue if queue is not None else RequestQueue()
        self._queued: dict = {}           # id(request) -> its open span
        self.metrics = ServingMetrics()
        self.clock = 0.0
        self.step_idx = 0
        self.bucket = buckets[0]
        self.slots: list[Request | None] = [None] * self.bucket
        self.cur_token = np.zeros(self.bucket, np.int32)
        self._dec: dict = {}
        self._pre: dict = {}
        self._axes = slot_axes(self._make_slab)
        self.slab = self._make_slab(self.bucket)
        if recorder is not None:
            from repro.obs.telemetry import dispatch_record
            recorder.emit("run", engine="sched", replicas=self.r,
                          slot_buckets=list(buckets),
                          seq_capacity=self.seq_capacity,
                          early_commit=self.early_commit,
                          deadline=self.deadline,
                          dispatch=dispatch_record(aggregator))

    # -- slab / program construction ------------------------------------
    def _make_slab(self, B: int):
        def one(p):
            return init_cache(self.cfg, p, B, self.seq_capacity,
                              {"tokens": jnp.zeros((B, 1), jnp.int32)})
        return vectorize_cache_pos(jax.vmap(one)(self.params_stack), B)

    def _decode_fn(self, B: int):
        if B not in self._dec:
            def dec(pstack, token, slab):
                count_trace("sched_decode")

                def one(p, c):
                    return decode_step(self.cfg, p, token, c)
                return jax.vmap(one)(pstack, slab)
            self._dec[B] = jax.jit(dec) if self.jit else dec
        return self._dec[B]

    def _prefill_fn(self, T: int):
        if T not in self._pre:
            def pf(pstack, tokens):               # tokens (1, T) int32
                count_trace("sched_prefill")
                batch = {"tokens": tokens}

                def one(p):
                    c = init_cache(self.cfg, p, 1, self.seq_capacity, batch)
                    return prefill(self.cfg, p, batch, c)
                return jax.vmap(one)(pstack)
            self._pre[T] = jax.jit(pf) if self.jit else pf
        return self._pre[T]

    # -- roster / timing helpers ----------------------------------------
    def _live(self) -> np.ndarray:
        if self.policy is not None:
            return np.asarray(self.policy.roster, bool).copy()
        return np.ones(self.r, bool)

    def _step_delays(self, step: int) -> np.ndarray:
        if self.delays is None:
            d = np.full(self.r, self.base_step_time)
        elif callable(self.delays):
            d = np.asarray(self.delays(step), np.float64)
        else:
            arr = np.asarray(self.delays, np.float64)
            d = arr[min(step, len(arr) - 1)]
        d = np.asarray(d, np.float64).copy()
        # omission faults ride the roster/policy, not infinite delays —
        # clamp so the full-vote wait stays finite
        bad = ~np.isfinite(d)
        if bad.any():
            d[bad] = max(1.0, np.max(d[~bad], initial=1.0)) * 100.0
        return d

    def _f_eff(self, n_live: int) -> int:
        if self.agreement.elastic is not None:
            return int(self.spec.respecialize(n_live).f)
        return int(self.spec.f)

    def _commit_walk(self, amax: np.ndarray, live: np.ndarray,
                     d: np.ndarray, q: int):
        """Earliest f+1 bitwise-consistent commit per slot.

        ``amax`` (r, B) per-replica fp32 argmax tokens; walks live
        replicas in arrival order ((delay, id) — same-instant ties pin
        to replica id, as in the simulator's event queue).  Returns
        ``(t_star, tok_star)``: per slot, the virtual delay at which
        some token value reached ``q`` consistent supporters (inf when
        consistency is never reached) and that token."""
        B = amax.shape[1]
        t_star = np.full(B, np.inf)
        tok_star = np.full(B, -1, np.int64)
        if q < 1:
            return t_star, tok_star
        counts: list[dict] = [{} for _ in range(B)]
        remaining = set(range(B))
        order = sorted(np.flatnonzero(live), key=lambda i: (d[i], i))
        for i in order:
            for b in list(remaining):
                tk = int(amax[i, b])
                c = counts[b]
                c[tk] = c.get(tk, 0) + 1
                if c[tk] >= q:
                    t_star[b] = d[i]
                    tok_star[b] = tk
                    remaining.discard(b)
            if not remaining:
                break
        return t_star, tok_star

    # -- request intake --------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Admission-controlled submit (False = rejected at the door)."""
        if req.prompt_len + req.max_new_tokens > self.seq_capacity:
            raise ValueError(
                f"request {req.rid}: prompt {req.prompt_len} + budget "
                f"{req.max_new_tokens} exceeds seq_capacity "
                f"{self.seq_capacity}")
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.rid}: empty decode budget")
        if not self.queue.submit(req):
            return False
        self._queued[id(req)] = open_span(SCHED_QUEUED, rid=req.rid)
        return True

    def submit_all(self, reqs) -> int:
        return sum(1 for r in reqs if self.submit(r))

    # -- slot management -------------------------------------------------
    def _active_ids(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s is not None]

    def _resize_to(self, n_needed: int) -> None:
        """Move the slab to the smallest bucket holding ``n_needed``."""
        target = next(b for b in self.buckets if b >= n_needed)
        if target == self.bucket:
            return
        with span(SCHED_RESIZE, **{"from": self.bucket, "to": target}):
            if target > self.bucket:
                extra = target - self.bucket
                self.slab = slab_grow(self.slab, self._axes, extra)
                self.slots += [None] * extra
                self.cur_token = np.concatenate(
                    [self.cur_token, np.zeros(extra, np.int32)])
            else:                                 # repack actives, shrink
                keep = self._active_ids()
                free = [i for i in range(self.bucket)
                        if self.slots[i] is None]
                idx = (keep + free)[:target]
                self.slab = slab_take(self.slab, self._axes, idx)
                self.slots = [self.slots[i] for i in idx]
                self.cur_token = self.cur_token[np.asarray(idx, int)]
            self.bucket = target

    def _commit_tokens(self, logits, active: list[int], now: float,
                       phase: str):
        """Agree on this step's token for every ``active`` slot.

        Returns (per-slot token dict, latest commit time, telemetry row).
        ``logits`` is the post-fault-hook (r, B, V) stack; ``phase`` only
        labels the recorder event ("decode" | "prefill")."""
        with span(SCHED_TO_HOST, shape=tuple(logits.shape)):
            la = np.asarray(logits, np.float32)
        with span(SCHED_COMMIT, n_slots=len(active)):
            live = self._live()
            d = self._step_delays(self.step_idx)
            amax = la.argmax(axis=-1)             # (r, B) fp32 argmax
            n_live = int(live.sum())
            q = self._f_eff(n_live) + 1
            t_star, tok_star = self._commit_walk(amax, live, d, q)
            limit = (np.inf if self.deadline is None
                     else float(self.deadline))
            full_wait = float(d[live].max()) if n_live else 0.0

            tokens: dict[int, int] = {}
            times: dict[int, float] = {}
            early: dict[int, bool] = {}
            fallback = [b for b in active
                        if not (self.early_commit and t_star[b] <= limit)]
            vote_tok, vote_telem = None, None
            if fallback:
                with span(SCHED_VOTE):
                    out = self.agreement.vote(
                        logits, live if self.policy is not None else None)
                    if self.telemetry:
                        vote_tok, vote_telem = out
                    else:
                        vote_tok = out
                    vote_tok = np.asarray(vote_tok)
            for b in active:
                if b in fallback:
                    tokens[b] = int(vote_tok[b])
                    times[b] = now + full_wait
                    early[b] = False
                else:
                    tokens[b] = int(tok_star[b])
                    times[b] = now + float(t_star[b])
                    early[b] = True

            telem = None
            if self.telemetry:
                if vote_telem is not None:
                    telem = {k: np.asarray(v)
                             for k, v in vote_telem.items()}
                else:
                    # all-early step: the vote never ran — replica shares
                    # are argmax agreement over the committed slots
                    agree_frac = np.zeros(self.r, np.float64)
                    if active:
                        hits = np.stack([amax[:, b] == tokens[b]
                                         for b in active], axis=1)
                        agree_frac = np.where(live, hits.mean(axis=1), 0.0)
                    telem = {"sel_w": agree_frac.astype(np.float32),
                             "mask": live,
                             "contrib_w": live.astype(np.float32)}
            return tokens, times, early, telem, live

    # -- the step --------------------------------------------------------
    def step(self) -> bool:
        """One scheduler step: decode actives, then admit arrivals.

        Returns False when there was nothing to do AND nothing is queued
        (the drain condition)."""
        active = self._active_ids()
        with span(SCHED_STEP, step=self.step_idx, active=len(active),
                  bucket=self.bucket, queued=len(self.queue)):
            return self._step(active)

    def _step(self, active: list[int]) -> bool:
        now = self.clock
        if not active and len(self.queue) == 0:
            return False
        if not active:
            nxt = self.queue.peek_arrival()
            if nxt is None:
                return False
            now = max(now, float(nxt))            # idle: fast-forward

        t_end = now
        telem, live = None, self._live()
        if active:
            with span(SCHED_DECODE, bucket=self.bucket):
                dec = self._decode_fn(self.bucket)
                tok = jnp.asarray(self.cur_token[:, None])
                logits, self.slab = dec(self.params_stack, tok, self.slab)
                if self.fault_hook is not None:
                    logits = self.fault_hook(self.step_idx, logits)
                jax.block_until_ready(logits)
            tokens, times, early, telem, live = self._commit_tokens(
                logits, active, now, "decode")
            for b in active:
                req = self.slots[b]
                req.out.append(tokens[b])
                self.cur_token[b] = tokens[b]
                self.metrics.commit(req, times[b], times[b] - now,
                                    early[b])
                t_end = max(t_end, times[b])
                if req.done:
                    self.metrics.finish(req, times[b])
                    self.slots[b] = None
                    self.cur_token[b] = 0
        else:
            t_end = now + 0.0

        # admissions: arrivals by ``now`` join during this step (their
        # prefill overlaps the decode), decode from the NEXT step on
        n_active = len(self._active_ids())
        staged = self.queue.poll(now, limit=self.buckets[-1] - n_active)
        if staged or n_active != len(active):
            self._resize_to(max(n_active + len(staged), 1))
        for req in staged:
            slot = self.slots.index(None)
            t_first = self._admit(req, slot, now)
            t_end = max(t_end, t_first)

        if self.recorder is not None:
            m = {"active": len(self._active_ids()),
                 "queued": len(self.queue), "bucket": self.bucket,
                 "clock": self.clock,
                 "n_live": int(np.asarray(live).sum())}
            self.recorder.step(self.step_idx, metrics=m, telemetry=telem,
                               roster=live)
        self.clock = max(self.clock, t_end,
                         now + (self.base_step_time if active else 0.0))
        self.step_idx += 1
        return True

    def _admit(self, req: Request, slot: int, now: float) -> float:
        """Prefill ``req`` into ``slot`` and commit its first token."""
        queued = self._queued.pop(id(req), None)
        if queued is not None:
            close_span(queued)
        with span(SCHED_ADMIT, rid=req.rid, prompt_len=req.prompt_len):
            with span(SCHED_PREFILL):
                pf = self._prefill_fn(req.prompt_len)
                tokens = jnp.asarray(
                    np.asarray(req.tokens, np.int32)[None, :])
                logits, rows = pf(self.params_stack, tokens)  # (r, 1, V)
                if self.fault_hook is not None:
                    logits = self.fault_hook(self.step_idx, logits)
                jax.block_until_ready(logits)
            tokens_d, times_d, early_d, _, _ = self._commit_tokens(
                logits, [0], now, "prefill")
            tok0 = tokens_d[0]
            self.metrics.admit(req, now)
            req.out.append(tok0)
            self.metrics.commit(req, times_d[0], times_d[0] - now,
                                early_d[0])
            self.slots[slot] = req
            self.cur_token[slot] = tok0
            with span(SCHED_SPLICE):
                self.slab = slab_write(self.slab, self._axes,
                                       vectorize_cache_pos(rows, 1), [slot])
            if req.done:                           # budget of exactly 1
                self.metrics.finish(req, times_d[0])
                self.slots[slot] = None
                self.cur_token[slot] = 0
            return times_d[0]

    # -- driving ---------------------------------------------------------
    def run(self, max_steps: int | None = None) -> ServingMetrics:
        """Step until the queue and slot table drain (or ``max_steps``)."""
        steps = 0
        while max_steps is None or steps < max_steps:
            if not self.step():
                break
            steps += 1
        if self.recorder is not None:
            self.recorder.emit("note", message="sched drained",
                               steps=self.step_idx,
                               **{k: v for k, v in
                                  self.metrics.summary().items()
                                  if isinstance(v, (int, float))})
        return self.metrics


__all__ = ["ReplicatedScheduler", "vectorize_cache_pos", "slot_axes",
           "slab_grow", "slab_take", "slab_write"]
