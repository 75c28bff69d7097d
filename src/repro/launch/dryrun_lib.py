"""Dry-run machinery: lower + compile every (arch x shape x mesh) combination
and extract the roofline terms from the compiled artifact.

Separated from dryrun.py so tests can import it under a small host-device
count; dryrun.py (the production entry point) pins XLA_FLAGS to 512 devices
as its first two lines.
"""
from __future__ import annotations

import json
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import active_params, get_config, num_params
from repro.distributed.sharding import (agent_axes, batch_pspec, cache_pspecs,
                                        grads_pspecs, param_pspecs)
from repro.launch import mesh as mesh_lib
from repro.launch.input_specs import (SHAPES, input_specs, params_specs,
                                      shape_supported)
from repro.models import decode_step, prefill
from repro.optim import diminishing, sgd
from repro.training.step import ByzantineConfig, make_train_step

FSDP_THRESHOLD = 20e9


def sharding_mode(cfg) -> str:
    return "fsdp" if num_params(cfg) >= FSDP_THRESHOLD else "ddp"


def _ns(mesh, tree_pspecs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def _opt_pspecs(opt_sds, params_ps):
    """Optimizer-state specs: momentum/adam moments mirror the param specs."""
    def walk(sub):
        if isinstance(sub, dict):
            return {k: (params_ps if k in ("mu", "m", "v") else walk(v))
                    for k, v in sub.items()}
        return P()
    return walk(opt_sds)


# ---------------------------------------------------------------------------
# lowering per kind


def lower_train(cfg, mesh, multi_pod: bool, bz: ByzantineConfig,
                mode: str | None = None):
    mode = mode or sharding_mode(cfg)
    kind, specs = input_specs(cfg, "train_4k", n_agents=bz.n_agents)
    params_sds = params_specs(cfg)
    opt = sgd(diminishing(0.1))          # paper-faithful DGD/BGD server step
    opt_sds = jax.eval_shape(opt.init, params_sds)
    key_sds = jax.eval_shape(lambda: jax.random.PRNGKey(0))

    params_ps = param_pspecs(params_sds, mode, mesh)
    opt_ps = _opt_pspecs(opt_sds, params_ps)
    batch_ps = jax.tree.map(
        lambda l: batch_pspec(multi_pod, extra_dims=l.ndim - 1),
        specs["batch"])

    step = make_train_step(cfg, bz, opt, mesh_sizes=dict(mesh.shape))
    metrics_ps = {"loss": P(), "loss_all": P(), "grad_norm": P()}
    jitted = jax.jit(
        step,
        in_shardings=(_ns(mesh, params_ps), _ns(mesh, opt_ps), None,
                      _ns(mesh, batch_ps), NamedSharding(mesh, P())),
        out_shardings=(_ns(mesh, params_ps), _ns(mesh, opt_ps), None,
                       _ns(mesh, metrics_ps)),
    )
    with mesh:
        lowered = jitted.lower(params_sds, opt_sds, None, specs["batch"],
                               key_sds)
    return lowered


def _dispatch_ctx(cfg, mesh, multi_pod: bool, enabled: bool):
    """MoE dispatch sharding hint (§Perf pair C)."""
    import contextlib

    from repro.distributed.context import moe_dispatch_sharding
    if not enabled or not cfg.num_experts:
        return contextlib.nullcontext()
    ax = agent_axes(multi_pod)
    ax = ax[0] if len(ax) == 1 else ax
    ep = cfg.num_experts % mesh.shape["model"] == 0
    return moe_dispatch_sharding(ax, ep, dict(mesh.shape))


def lower_prefill(cfg, mesh, multi_pod: bool, moe_dispatch: bool = False):
    _, specs = input_specs(cfg, "prefill_32k")
    params_sds = params_specs(cfg)
    params_ps = param_pspecs(params_sds, "ddp", mesh)
    ax = agent_axes(multi_pod)
    ax = ax[0] if len(ax) == 1 else ax
    batch_ps = jax.tree.map(
        lambda l: P(ax, *([None] * (l.ndim - 1))), specs["batch"])
    cache_ps = cache_pspecs(specs["cache"], multi_pod, mesh)

    def step(params, batch, cache):
        return prefill(cfg, params, batch, cache)

    vocab_ax = "model" if cfg.vocab_size % mesh.shape["model"] == 0 else None
    logits_ps = P(ax, vocab_ax)
    jitted = jax.jit(
        step,
        in_shardings=(_ns(mesh, params_ps), _ns(mesh, batch_ps),
                      _ns(mesh, cache_ps)),
        out_shardings=(NamedSharding(mesh, logits_ps), _ns(mesh, cache_ps)),
    )
    with mesh, _dispatch_ctx(cfg, mesh, multi_pod, moe_dispatch):
        lowered = jitted.lower(params_sds, specs["batch"], specs["cache"])
    return lowered


def lower_decode(cfg, mesh, multi_pod: bool, shape_name: str,
                 cache_layout: str = "headdim"):
    _, specs = input_specs(cfg, shape_name)
    params_sds = params_specs(cfg)
    params_ps = param_pspecs(params_sds, "ddp", mesh)
    ax = agent_axes(multi_pod)
    ax = ax[0] if len(ax) == 1 else ax
    B = specs["token"].shape[0]
    tok_ps = P(ax if B > 1 else None, None)
    cache_ps = cache_pspecs(specs["cache"], multi_pod, mesh,
                            layout=cache_layout)

    def step(params, token, cache):
        return decode_step(cfg, params, token, cache)

    vocab_ax = "model" if cfg.vocab_size % mesh.shape["model"] == 0 else None
    logits_ps = P(ax if B > 1 else None, vocab_ax)
    jitted = jax.jit(
        step,
        in_shardings=(_ns(mesh, params_ps), NamedSharding(mesh, tok_ps),
                      _ns(mesh, cache_ps)),
        out_shardings=(NamedSharding(mesh, logits_ps), _ns(mesh, cache_ps)),
    )
    with mesh:
        lowered = jitted.lower(params_sds, specs["token"], specs["cache"])
    return lowered


def lower_combo(cfg, shape_name: str, mesh, multi_pod: bool,
                bz: ByzantineConfig | None = None, mode: str | None = None,
                cache_layout: str = "headdim", moe_dispatch: bool = False):
    kind = SHAPES[shape_name]["kind"]
    if kind == "train":
        n_default = 32 if multi_pod else 16
        bz = bz or ByzantineConfig(n_agents=n_default,
                                   f=(n_default - 1) // 4)
        return lower_train(cfg, mesh, multi_pod, bz, mode)
    if kind == "prefill":
        return lower_prefill(cfg, mesh, multi_pod, moe_dispatch=moe_dispatch)
    return lower_decode(cfg, mesh, multi_pod, shape_name,
                        cache_layout=cache_layout)


# ---------------------------------------------------------------------------
# compiled-artifact analysis

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(segment: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(segment):
        if dt not in _DTYPE_BYTES:
            continue
        cnt = 1
        if dims:
            for d in dims.split(","):
                cnt *= int(d)
        total += cnt * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str):
    """Sum RESULT-shape bytes of every collective op in the optimized HLO
    (async *-start counted once; *-done skipped)."""
    out = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if " = " not in s:
            continue
        lhs, rhs = s.split(" = ", 1)
        m = re.match(r"(\([^)]*\)|\S+)\s+(%?[\w-]+)\(", rhs)
        if not m:
            continue
        shape_seg, opname = m.group(1), m.group(2).lstrip("%")
        base = None
        for c in _COLLECTIVES:
            if opname == c or opname == c + "-start":
                base = c
                break
        if base is None:
            continue
        out[base] += _shape_bytes(shape_seg)
        counts[base] += 1
    return out, counts


def analyze(lowered, compiled, wall: dict):
    """Primary metrics come from the trip-count-aware HLO analyzer
    (repro.launch.hlo_cost) — XLA's cost_analysis counts while bodies once,
    under-reporting scanned-layer programs by ~num_layers.  The raw XLA
    numbers are kept under *_xla for reference."""
    from repro.launch.hlo_cost import analyze_hlo_text
    cost = dict(compiled.cost_analysis() or {})
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                mem[attr] = int(v)
    except Exception as e:              # CPU backend may not support it
        mem["error"] = str(e)
    text = compiled.as_text()
    hlo = analyze_hlo_text(text)
    return {
        "flops": float(hlo["flops"]),
        "bytes_accessed": float(hlo["result_bytes"]),
        "collective_bytes": hlo["collective_bytes"],
        "collective_counts": hlo["collective_counts"],
        "collective_bytes_total": float(hlo["collective_bytes_total"]),
        "bytes_by_op": hlo.get("bytes_by_op", {}),
        "flops_xla": float(cost.get("flops", -1.0)),
        "bytes_accessed_xla": float(cost.get("bytes accessed", -1.0)),
        "memory": mem,
        "hlo_chars": len(text),
        **wall,
    }


def model_flops(cfg, shape_name: str) -> float:
    """6·N·D (train) / 2·N_active per generated token (decode) /
    2·N_active·tokens (prefill)."""
    info = SHAPES[shape_name]
    n_act = active_params(cfg)
    tokens = info["global_batch"] * info["seq_len"]
    if info["kind"] == "train":
        return 6.0 * n_act * tokens
    if info["kind"] == "prefill":
        return 2.0 * n_act * tokens
    return 2.0 * n_act * info["global_batch"]       # one token per request


def roofline_terms(record, n_chips: int):
    """Three roofline terms in seconds from a dry-run record.

    flops / bytes from cost_analysis are for the PER-DEVICE partitioned
    module; collective bytes likewise.  Terms:
      compute    = flops_per_device / peak
      memory     = bytes_per_device / HBM_bw
      collective = collective_bytes_per_device / (3 links * ICI_bw)
    """
    comp = record["flops"] / mesh_lib.PEAK_FLOPS_BF16
    memt = record["bytes_accessed"] / mesh_lib.HBM_BW
    coll = record["collective_bytes_total"] / (3 * mesh_lib.ICI_BW)
    terms = {"compute_s": comp, "memory_s": memt, "collective_s": coll}
    dom = max(terms, key=terms.get)
    return {**terms, "dominant": dom}


# ---------------------------------------------------------------------------
# the full run


def run_combo(arch: str, shape_name: str, multi_pod: bool,
              out_dir: str = "artifacts/dryrun", mode: str | None = None,
              bz: ByzantineConfig | None = None, mesh=None,
              tag: str = "", verbose: bool = True,
              skip_existing: bool = False, cache_layout: str = "headdim",
              moe_dispatch: bool = False):
    cfg = get_config(arch)
    ok, why = shape_supported(cfg, shape_name)
    mesh_name = "pod512" if multi_pod else "pod256"
    name = f"{arch}_{shape_name}_{mesh_name}" + (f"_{tag}" if tag else "")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, name + ".json")
    if skip_existing and os.path.exists(path):
        with open(path) as fh:
            rec = json.load(fh)
        if "error" not in rec:
            if verbose:
                print(f"[dryrun] cached {name}")
            return rec
    if not ok:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "skipped": why}
        with open(path, "w") as fh:
            json.dump(rec, fh, indent=1)
        if verbose:
            print(f"[dryrun] SKIP {name}: {why}")
        return rec

    if mesh is None:
        mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))

    t0 = time.time()
    lowered = lower_combo(cfg, shape_name, mesh, multi_pod, bz=bz, mode=mode,
                          cache_layout=cache_layout,
                          moe_dispatch=moe_dispatch)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    rec = analyze(lowered, compiled,
                  {"lower_s": t1 - t0, "compile_s": t2 - t1})
    rec.update(arch=arch, shape=shape_name, mesh=mesh_name,
               n_chips=n_chips, kind=SHAPES[shape_name]["kind"],
               params=num_params(cfg), active_params=active_params(cfg),
               model_flops=model_flops(cfg, shape_name),
               sharding_mode=mode or sharding_mode(cfg), tag=tag)
    rec["roofline"] = roofline_terms(rec, n_chips)
    rec["useful_flops_ratio"] = (
        rec["model_flops"] / n_chips / rec["flops"]
        if rec["flops"] > 0 else None)
    with open(path, "w") as fh:
        json.dump(rec, fh, indent=1)
    if verbose:
        r = rec["roofline"]
        print(f"[dryrun] {name}: compile {rec['compile_s']:.1f}s  "
              f"flops/dev {rec['flops']:.3e}  "
              f"coll {rec['collective_bytes_total']/1e6:.1f}MB  "
              f"dominant {r['dominant']}")
    return rec
