"""chip_smoke.py's control flow on the CPU: every phase's gates at the
paper-100m-smoke size, and the device gate that keeps the script from
reporting a CPU run as a chip run."""
import importlib.util
import os
import subprocess
import sys

import pytest

from repro.configs import get_config

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    return get_config("paper-100m-smoke")


def test_phase_train(smoke, cfg):
    out = smoke.phase_train(cfg, seq=32, steps=3)
    assert len(out["losses"]) == 3 and len(out["step_s"]) == 2


def test_phase_async_runs_masked_steps(smoke, cfg):
    out = smoke.phase_async(cfg, seq=32, steps=4)
    assert len(out["losses"]) == 4
    assert min(out["arrived"]) < 8          # the quorum path ran


def test_phase_kernels(smoke):
    out = smoke.phase_kernels(3000)          # not a TILE_D multiple
    assert set(out) == {f"{r}/{m}" for r in ("coordinate_median",
                                             "trimmed_mean", "multi_krum")
                        for m in ("plain", "masked")}
    assert all(v["rel"] <= 1e-6 for v in out.values())


def test_phase_serve(smoke, cfg):
    out = smoke.phase_serve(cfg, prompt_lens=(4, 8), new_tokens=5,
                            n_requests=3)
    assert len(out["prompt_lens"]) == 3


def test_gate_raises(smoke):
    with pytest.raises(RuntimeError, match="gate failed"):
        smoke._gate(False, "forced")


def test_main_refuses_the_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_script_exits_nonzero_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert run.returncode != 0
    assert '"ok": true' not in run.stdout
