"""Shared arithmetic of the per-layer metric readers (``metrics/``).

A reader gets the run's namespace: ``kind`` ("train" | "serve"), the
trace's plain form ``plain``, ``busy_s`` and ``trace_window_s``, the
chip's ``peak``, the configuration's ``model`` block and what the run
measured; a training run also carries ``seq`` and ``flops_per_token``
(the configuration's reference module counts it).  It returns None
where it finds nothing to read."""
from __future__ import annotations

import re

from chipbench import trace

# device operations of the aggregation layer: on a TPU an op's trace
# name is its HLO instruction, and a Pallas kernel's instruction is named
# after the kernel's entry in kernels/ (coord_stat, gram, ...).  Only the
# instruction's own name counts, at the start of the text: the ops that
# merely read a kernel's output (casts, the optimizer's fusions) name it
# among their operands and are not aggregation.
AGG_OPS = re.compile(
    r"^%(?:masked_|scaled_|scaled_masked_)?(?:coord_stat|coord_sort|gram|"
    r"weighted_sum|clipped_weighted_sum|sparse_masked_weighted_mean|"
    r"sign_vote|ordered_apply|krum_select|cge_select|multi_krum_order|"
    r"iterative_order|bulyan_coord)(?:\.\d+)?\s")


def idle_share(run, kind: str):
    if run.kind != kind or not run.busy_s or not run.trace_window_s:
        return None
    return 100.0 * (1.0 - run.busy_s / run.trace_window_s)


def is_agg(ev) -> bool:
    return bool(AGG_OPS.match(ev[0]))


def agg_seconds_per_step(run):
    if run.kind != "train" or not run.steps:
        return None
    s = trace.op_seconds(run.plain, is_agg)
    return s / run.steps if s > 0 else None
