"""The chip's peaks and the work a step or a kernel must do, counted from
shapes.  These counts are the benchmark's, not the program's: they stay
the same whatever implements the step or the rule."""
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


def matmul_params(model: dict) -> int:
    """Weights that enter a matmul per token: every block's projections
    and the output head; the input embedding (a gather) and the norms
    are left out."""
    d, hd = model["d_model"], model.get("head_dim") or (
        model["d_model"] // model["num_heads"])
    h, k, ff = model["num_heads"], model["num_kv_heads"], model["d_ff"]
    attn = d * h * hd + 2 * d * k * hd + h * hd * d
    mlp = 3 * d * ff if model.get("act", "silu") == "silu" else 2 * d * ff
    return model["num_layers"] * (attn + mlp) + d * model["vocab_size"]


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward and backward FLOPs per token, recompute not counted:
    6 N + 12 L H hd T (PaLM, arXiv:2204.02311, App. B)."""
    hd = model.get("head_dim") or model["d_model"] // model["num_heads"]
    return (6.0 * matmul_params(model)
            + 12.0 * model["num_layers"] * model["num_heads"] * hd * seq)


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
