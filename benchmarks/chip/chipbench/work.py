"""The chip's peaks and the work a kernel must do, counted from shapes.
These counts are the benchmark's, not the program's: they stay the same
whatever implements the rule.  A training step's FLOPs per token depend
on the model, and are counted by the configuration's reference module
(``train_flops_per_token``)."""
from __future__ import annotations

import json
import os

from chipbench.spec import BENCH_DIR


def peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    """Published peaks of one chip of ``device_kind``; a kind that is not
    in ``peaks.json`` is an error, never a default."""
    with open(os.path.join(bench_dir, "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]


# the rules whose least work includes the pairwise Gram matrix
GRAM_RULES = ("krum", "multi_krum", "m_krum", "mda", "bulyan", "cge")


def agg_min_work(rule: str, n: int, p: int, elem_bytes: int,
                 masked: bool = False) -> tuple[float, float]:
    """(FLOPs, bytes) the rule must do at least on an (n, p) arena whose
    elements take ``elem_bytes``: read the arena (and the (n,) mask and
    weights) once, write the (p,) result once; a Gram rule adds the
    2 n^2 p FLOPs of its pairwise products."""
    nbytes = (n + 1) * p * elem_bytes + (2 * n * 4 if masked else 0)
    flops = 2.0 * n * n * p if rule in GRAM_RULES else 0.0
    return flops, float(nbytes)


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Least seconds for the work on the chip, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
