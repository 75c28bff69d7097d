"""CPU tests of the ``train.sync.trimmed.mamba2`` cell and its plain
reference (``benchmarks/chip/refs/mamba2.py``).

The tiny model (``tiny/ssm.json``, chunk 8) runs at the tiny seq of 32,
so the program's chunked SSD crosses three chunk boundaries.  The check
has to pass the program as it is and fail the fp8 control and each
planted fault that the benchmark's weights can show.
"""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from test_chip_benchmark import REPO, _fresh_jit_caches  # noqa: F401
from test_chip_benchmark import run_cell, tiny_root

from chipbench import spec  # noqa: E402

CELL = "train.sync.trimmed.mamba2"


def _cell():
    return spec.load_cell(str(REPO), CELL)


def test_cell_resolves_to_the_mamba2_configuration_and_reference():
    cell = _cell()
    assert cell.chips == 1 and cell.config_name == "mamba2-130m.silo8"
    assert cell.config["model"]["family"] == "ssm"
    assert Path(cell.reference.__file__).name == "mamba2.py"
    assert cell.traffic["seq"] == 2048 and cell.traffic["per_agent_batch"] == 1
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (entry,) = [c for c in bench["configs"] if c["name"] == cell.config_name]
    assert entry["reduced"] == []
    reported = {m["name"] for m in cell.per_layer}
    assert reported == {"device_idle_share.train", "train_step_mfu",
                        "agg_kernel_ms", "agg_roofline"}


def test_flops_per_token_hand_count():
    ref, m = _cell().reference, _cell().config["model"]
    per_layer = 768 * (2 * 1536 + 2 * 128 + 24) + 1536 * 768
    assert per_layer == 2_574_336 + 1_179_648
    assert ref.matmul_params(m) == 24 * per_layer + 768 * 50280
    assert ref.matmul_params(m) == 128_710_656
    ssd = 24 * (2 * 256 * 128 * 1 + 2 * 256 * 24 * 64 + 4 * 24 * 64 * 128)
    assert ref.train_flops_per_token(m, 2048) == 6 * 128_710_656 + 3 * ssd
    assert ref.train_flops_per_token(m, 2048) == 890_228_736


def test_parameter_tree_mixes_dtypes_as_configured():
    """The program's tree at the configured widths: 128,983,488
    parameters, of which the 1,728 of A_log, dt_bias and D in float32 and
    the rest, a multiple of 128, in bfloat16."""
    import jax
    import numpy as np
    from chipbench import weights
    from repro.configs.base import ArchConfig
    shapes = weights.param_shapes(ArchConfig(name="m",
                                             **_cell().config["model"]))
    by = {}
    for leaf in jax.tree.leaves(shapes):
        name = np.dtype(leaf.dtype).name
        by[name] = by.get(name, 0) + int(np.prod(leaf.shape))
    assert by == {"bfloat16": 128_981_760, "float32": 1_728}
    assert by["bfloat16"] % 128 == 0


def _control_exceeds(res, err):
    limits = {k: v["limit"] for k, v in res["checks"].items()}
    control = {line.split()[0]: float(line.split()[1]) for line in
               err.split("control fp8: ")[1].splitlines()[0].split("  ")}
    return any(control[k] > limits[k] for k in limits), control, limits


def test_tiny_cell_is_correct_and_its_control_is_not(tmp_path, capsys,
                                                     monkeypatch):
    res, err = run_cell(tiny_root(tmp_path), CELL, capsys, monkeypatch,
                        control="fp8")
    assert res["correct"] is True, err[-2000:]
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    bad, control, limits = _control_exceeds(res, err)
    assert bad, (control, limits)


def _mamba_block_without_gate(cfg, params, x):
    """The program's block with the gate y * SiLU(z) left out."""
    import jax
    import jax.numpy as jnp
    from repro.models import ssm
    b, t, _ = x.shape
    di, g, n = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
    h, p = cfg.ssm_num_heads, cfg.ssm_head_dim
    _, xbc, dt = ssm._split_proj(cfg, x @ params["in_proj"])
    xbc = ssm.causal_conv(xbc, params["conv_w"], params["conv_b"])
    xs = xbc[..., :di].reshape(b, t, h, p)
    bm = xbc[..., di:di + g * n].reshape(b, t, g, n)
    cm = xbc[..., di + g * n:].reshape(b, t, g, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["A_log"])
    y, state = ssm.ssd_chunked(xs * dt[..., None].astype(xs.dtype), dt * a,
                               bm, cm, cfg.ssm_chunk)
    y = y + (params["D_skip"][:, None] * xs.astype(jnp.float32)).astype(
        y.dtype)
    y = ssm.rms_norm(y.reshape(b, t, di), params["norm_scale"], cfg.norm_eps)
    return y @ params["out_proj"], state


def _noncausal_conv(x, w, b):
    """The convolution reading the positions after t instead of before."""
    import jax
    import jax.numpy as jnp
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (0, k - 1), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k))
    return jax.nn.silu(out + b)


def _plant(fault, monkeypatch):
    import jax.numpy as jnp
    from repro.models import ssm
    if fault == "gate_dropped":
        monkeypatch.setattr(ssm, "mamba_block", _mamba_block_without_gate)
    elif fault == "conv_noncausal":
        monkeypatch.setattr(ssm, "causal_conv", _noncausal_conv)
    else:
        # every decay inside a chunk set to 1, the causal mask kept; the
        # carry between chunks (its segment sum has c + 1 = 5 entries at
        # seq 32) keeps its decay
        seg = ssm._segsum
        chunk = json.loads((Path(__file__).parent / "tiny" / "ssm.json")
                           .read_text())["ssm_chunk"]
        monkeypatch.setattr(ssm, "_segsum", lambda x: jnp.where(
            jnp.isfinite(seg(x)), 0.0, -jnp.inf) if x.shape[-1] == chunk
            else seg(x))


@pytest.mark.parametrize("fault", ["gate_dropped", "conv_noncausal",
                                   "no_intra_chunk_decay"])
def test_tiny_cell_check_fails_on_planted_fault(fault, tmp_path, capsys,
                                                monkeypatch):
    _plant(fault, monkeypatch)
    res, err = run_cell(tiny_root(tmp_path), CELL, capsys, monkeypatch)
    assert res["correct"] is False, err[-2000:]
