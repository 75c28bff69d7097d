"""CPU tests of the chip benchmark (``benchmarks/chip``).

They cover what a CPU can show: cells, mixes and metrics found by name,
the traffic generator, the work formulas, the trace reduction on a
recorded trace, the refusal of a device that is not a TPU, and whole
runs at a tiny size with the chip gate skipped, where the output check
has to pass on the program as it is and fail on a control in lower
precision and on each fault planted under the timed path.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"
TINY = Path(__file__).resolve().parent / "tiny"
sys.path.insert(0, str(BENCH))

from chipbench import cli, spec, trace, traffic, work  # noqa: E402

TRAIN, SERVE = "train.sync.trimmed", "serve.steady"
ASYNC = "train.async.krum"


@pytest.fixture(autouse=True, scope="module")
def _fresh_jit_caches():
    """Start and end with empty jit caches: a kernel entry point traced in
    this process with the interpret default steered to the chip's (as the
    described-chip compile tests do) would otherwise be reused here and
    fail to lower for the CPU, and this module's interpret-mode traces
    would be reused by such a compile."""
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


def _bench():
    with open(REPO / "BENCHMARK.json") as fh:
        return json.load(fh)


def tiny_model(family: str) -> dict:
    """The tiny model of ``family`` (``tiny/<family>.json``) that the CPU
    runs put in a configuration's place, its family named."""
    path = TINY / f"{family}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no tiny model of family {family!r}: "
                                f"{path} is missing")
    return {"family": family, **json.loads(path.read_text())}


def tiny_root(tmp_path) -> Path:
    """A checkout holding BENCHMARK.json and a copy of the benchmark, with
    the real cells' configurations cut to the tiny model of their own
    family and their mixes to tiny sizes; the limits stay the real
    cells' own."""
    root = tmp_path / "checkout"
    bench = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = _bench()
    for c in b["configs"]:
        path = root / c["file"]
        cfg = json.loads(path.read_text())
        cfg["model"] = tiny_model(cfg["model"]["family"])
        if "seq_capacity" in cfg["deployment"]:
            cfg["deployment"].update(slot_buckets=[2, 4], seq_capacity=40)
        path.write_text(json.dumps(cfg))
    for w in b["workloads"]:
        path = bench / "traffic" / f"{w['traffic']}.json"
        tr = json.loads(path.read_text())
        if tr["kind"] == "train":
            tr.update(seq=32, warmup_steps=10, trace_seconds=1)
        else:
            tr.update(rate_per_s=8.0, prompt_lens=[8, 16],
                      prompt_weights=[0.5, 0.5], new_tokens=[4, 8, 16],
                      new_token_weights=[0.4, 0.4, 0.2], drain_seconds=30)
            tr["check"]["tokens"] = 40
        path.write_text(json.dumps(tr))
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


def run_cell(root, name, capsys, monkeypatch, seed=2147483659,
             seconds=2.0, control=None):
    """One whole run in this process, the chip gate skipped and the
    compile cache left as the test process has it."""
    import jax
    from chipbench import clock
    monkeypatch.setattr(clock, "enable_cache", lambda root: "")
    argv = ["--workload", name, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", "0"]
    if control:
        argv += ["--control", control]
    capsys.readouterr()
    rc = cli.main(argv, root=str(root), t_start=time.perf_counter(),
                  gate=lambda chips: jax.devices(),
                  bench_dir=str(root / "benchmarks" / "chip"))
    out, err = capsys.readouterr()
    assert rc == 0, err[-3000:]
    return json.loads(out.strip().splitlines()[-1]), err


# ---------------------------------------------------------------------------
# finding cells, mixes and metrics by name


def test_every_cell_resolves_to_its_files():
    b = _bench()
    for w in b["workloads"]:
        cell = spec.load_cell(str(REPO), w["name"])
        assert cell.traffic["kind"] in ("train", "serve")
        assert cell.config["model"]["d_model"] == 768
        assert cell.config["model"]["family"] in spec.known_families()
        ref = cell.reference
        assert (Path(ref.__file__).resolve()
                == (REPO / cell.config["reference"]).resolve())
        assert callable(ref.loss) and callable(ref.train_flops_per_token)
        if cell.traffic["kind"] == "serve":
            assert callable(ref.logits)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))


def test_new_mix_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench_dir = str(root / "benchmarks" / "chip")
    mix = json.loads((BENCH / "traffic" / f"{SERVE}.json").read_text())
    mix["rate_per_s"] = 1.0
    (root / "benchmarks" / "chip" / "traffic" / "serve.slow.json"
     ).write_text(json.dumps(mix))
    (root / "benchmarks" / "chip" / "metrics" / "steps_seen.py"
     ).write_text("def read(run):\n    return len(run.steps) or None\n")
    b = _bench()
    b["workloads"].append({"name": "serve.slow", "config":
                           "paper-100m.replica5", "traffic": "serve.slow",
                           "chips": 1, "why": "a test cell"})
    for m in b["end_to_end"]:
        if SERVE in m.get("workloads", ()):
            m["workloads"].append("serve.slow")
    b["per_layer"].append({"name": "steps_seen", "unit": "steps",
                           "better": "higher", "source": "program_counter",
                           "layer": "serving scheduler",
                           "moves": "serve_tpot_p95_ms",
                           "workloads": ["serve.slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.load_cell(str(root), "serve.slow", bench_dir)
    assert cell.traffic["rate_per_s"] == 1.0
    assert [m["name"] for m in cell.per_layer] == ["steps_seen"]
    got = spec.read_per_layer(cell, type("Run", (), {"steps": [1, 2, 3]}))
    assert got == {"steps_seen": {"value": 3.0, "unit": "steps"}}
    # the old cell is untouched
    assert (spec.load_cell(str(root), SERVE, bench_dir).traffic
            == json.loads((BENCH / "traffic" / f"{SERVE}.json").read_text()))
    with pytest.raises(LookupError):
        spec.load_cell(str(root), "no.such.cell", bench_dir)


def _set_config(root, name, edit):
    """Rewrite configuration ``name`` of the checkout at ``root``."""
    (entry,) = [c for c in _bench()["configs"] if c["name"] == name]
    path = root / entry["file"]
    cfg = json.loads(path.read_text())
    edit(cfg)
    path.write_text(json.dumps(cfg))


def _no_family(cfg):
    del cfg["model"]["family"]


def _unknown_family(cfg):
    cfg["model"]["family"] = "no_such_family"


def _no_reference_file(cfg):
    cfg["reference"] = "benchmarks/chip/refs/no_such_module.py"


def _reference_without_loss(cfg):
    cfg["reference"] = "benchmarks/chip/metrics/train_step_mfu.py"


@pytest.mark.parametrize("edit, error", [
    (_no_family, LookupError), (_unknown_family, ValueError),
    (_no_reference_file, FileNotFoundError),
    (_reference_without_loss, LookupError)])
def test_configuration_without_family_or_reference_is_refused(
        edit, error, tmp_path, capsys):
    """``load_cell`` refuses the configuration before any device work,
    naming its file, and the run prints no result line."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    _set_config(root, "paper-100m.silo8", edit)
    bench_dir = str(root / "benchmarks" / "chip")
    with pytest.raises(error, match="paper-100m.silo8.json"):
        spec.load_cell(str(root), TRAIN, bench_dir)
    # the serving cell's configuration is untouched, and still loads
    assert spec.load_cell(str(root), SERVE, bench_dir).reference.logits
    capsys.readouterr()
    rc = cli.main(["--workload", TRAIN, "--seed", "1", "--seconds", "1"],
                  root=str(root), t_start=time.perf_counter(),
                  gate=lambda chips: pytest.fail("the gate was reached"),
                  bench_dir=bench_dir)
    out, err = capsys.readouterr()
    assert rc != 0 and out == "" and "paper-100m.silo8.json" in err


def test_tiny_root_cuts_each_configuration_to_its_familys_tiny_model(
        tmp_path):
    root = tiny_root(tmp_path)
    for c in _bench()["configs"]:
        cfg = json.loads((root / c["file"]).read_text())
        real = json.loads((REPO / c["file"]).read_text())["model"]
        want = json.loads((TINY / f"{real['family']}.json").read_text())
        assert cfg["model"] == {"family": real["family"], **want}
        assert cfg["reference"] == json.loads(
            (REPO / c["file"]).read_text())["reference"]


def test_family_without_tiny_model_names_the_missing_file():
    with pytest.raises(FileNotFoundError,
                       match=str(TINY / "no_such_family.json")):
        tiny_model("no_such_family")


# ---------------------------------------------------------------------------
# the traffic generator


def _mix():
    return json.loads((BENCH / "traffic" / f"{SERVE}.json").read_text())


def test_serve_traffic_is_deterministic_per_seed():
    a = traffic.serve_requests(_mix(), 2147483659, 30.0, 32000)
    b = traffic.serve_requests(_mix(), 2147483659, 30.0, 32000)
    assert len(a) == len(b) == round(_mix()["rate_per_s"] * 30)
    for x, y in zip(a, b):
        assert x["due"] == y["due"] and x["max_new"] == y["max_new"]
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
    c = traffic.serve_requests(_mix(), 5, 30.0, 32000)
    assert any(not np.array_equal(x["tokens"], y["tokens"])
               for x, y in zip(a, c))


def test_every_seed_gets_the_same_work():
    mix = _mix()
    runs = [traffic.serve_requests(mix, s, 30.0, 32000) for s in (1, 2, 3)]
    # the same sizes at the same arrival times, in the same order
    sizes = [[(len(q["tokens"]), q["max_new"], q["due"]) for q in r]
             for r in runs]
    assert sizes[0] == sizes[1] == sizes[2]
    due = [q["due"] for q in runs[0]]
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 30.0
    n = len(runs[0])
    counts = traffic.exact_counts(mix["prompt_weights"], n)
    assert counts.sum() == n
    np.testing.assert_allclose(counts / n, mix["prompt_weights"], atol=1 / n)


def test_train_feed_rows_all_differ_and_repeat_per_seed():
    tr = json.loads((BENCH / "traffic" / f"{TRAIN}.json").read_text())
    feed = traffic.train_feed(dict(tr, seq=16), 8, 32000, 2147483659)
    again = traffic.train_feed(dict(tr, seq=16), 8, 32000, 2147483659)
    rows = []
    for step in range(3):
        b = feed(step)
        np.testing.assert_array_equal(b["tokens"], again(step)["tokens"])
        assert b["tokens"].shape == (8, tr["per_agent_batch"], 16)
        np.testing.assert_array_equal(b["tokens"][..., 1:],
                                      b["labels"][..., :-1])
        rows += [tuple(r) for r in np.asarray(b["tokens"]).reshape(-1, 16)]
    assert len(set(rows)) == len(rows)


def test_reference_delivery_plan_is_the_programs():
    """The reference writes the async server's schedule out on its own;
    for the async cell's stragglers it has to be the program's."""
    from chipbench import reference
    from repro.simulator import SimConfig, Straggler, plan_arrivals
    tr = json.loads((BENCH / "traffic" / f"{ASYNC}.json").read_text())
    (fault,) = tr["faults"]
    delays = np.ones(8)
    delays[fault["agents"]] = fault["scale"]
    refresh, contrib, stale = reference.delivery_plan(delays, tr["quorum"],
                                                      30)
    sim = SimConfig(faults=(Straggler(dist="constant", scale=fault["scale"],
                                      agents=tuple(fault["agents"])),),
                    quorum=tr["quorum"], seed=2147483659)
    want = plan_arrivals(sim, 8, 30)
    np.testing.assert_array_equal(refresh, want.refresh)
    np.testing.assert_array_equal(contrib, want.contrib)
    np.testing.assert_array_equal(stale, want.staleness)
    assert list(contrib.sum(1)[:6]) == [6, 6, 8, 6, 6, 8]
    sync = reference.delivery_plan(np.ones(8), None, 4)
    assert sync[0].all() and sync[1].all() and sync[2].max() == 0


def test_multi_krum_selection_hand_made():
    from chipbench import reference
    x = np.array([[0.0], [0.1], [0.2], [5.0], [5.0]])
    d2 = (x - x.T) ** 2
    # f=1: each row's score sums its 2 nearest; the two 5.0 rows sit at
    # distance 0 from each other but 4.8^2 from the rest
    assert list(reference.multi_krum_select(d2, 1, 2)) == [1, 0]


# ---------------------------------------------------------------------------
# work formulas and peaks


def test_flops_per_token_hand_count():
    cell = spec.load_cell(str(REPO), TRAIN)
    dense, m = cell.reference, cell.config["model"]
    assert Path(dense.__file__).name == "dense_decoder.py"
    per_layer = (768 * 768 * 2 + 768 * 256 * 2 + 3 * 768 * 2048)
    assert dense.matmul_params(m) == 12 * per_layer + 768 * 32000
    assert dense.matmul_params(m) == 100_073_472
    assert dense.train_flops_per_token(m, 256) == (
        6 * 100_073_472 + 12 * 12 * 12 * 64 * 256)
    assert dense.train_flops_per_token(m, 512) == (
        6 * 100_073_472 + 12 * 12 * 12 * 64 * 512)


def test_step_mfu_reads_the_runs_flops_per_token():
    """``train_step_mfu`` takes the FLOPs per token that the harness
    counted through the configuration's reference, whatever the model."""
    read = spec.metric_reader("train_step_mfu")
    peak = work.peaks("TPU v5 lite")
    run = SimpleNamespace(kind="train", tokens_per_s=47_500.0,
                          flops_per_token=1.0e9, peak=peak,
                          model={"family": "ssm"}, seq=256)
    assert read(run) == 100.0 * (1.0e9 * 47_500.0) / 197e12
    assert read(SimpleNamespace(**dict(vars(run), kind="serve"))) is None
    assert not hasattr(work, "train_flops_per_token")


def test_aggregation_least_work_hand_count():
    p = 124_668_672
    flops, nbytes = work.agg_min_work("trimmed_mean", 8, p, 2)
    assert (flops, nbytes) == (0.0, 9 * p * 2)
    flops, nbytes = work.agg_min_work("multi_krum", 8, p, 4, masked=True)
    assert flops == 2 * 64 * p and nbytes == 9 * p * 4 + 64
    peak = work.peaks("TPU v5 lite")
    t, bound = work.roofline_s(0.0, 9 * p * 2, peak)
    assert bound == "memory" and t == pytest.approx(9 * p * 2 / 819e9)
    t, bound = work.roofline_s(1e15, 1.0, peak)
    assert bound == "compute" and t == pytest.approx(1e15 / 197e12)


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


# ---------------------------------------------------------------------------
# trace reduction


def _ev(name, start, dur, **stats):
    return [name, float(start), float(dur), stats]


def test_trace_reduction_hand_made():
    plain = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                _ev("jit_dec(1)", 0, 100), _ev("jit_pf(2)", 200, 50)]},
            {"name": "XLA Ops", "events": [
                _ev("fusion.1", 0, 40), _ev("fusion.2", 30, 40),
                _ev("custom-call.3", 100, 10, tf_op="jit(pallas_aggregate)"),
                _ev("fusion.1", 200, 20)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [_ev("outer", 0, 1000),
                                          _ev("np.asarray", 75, 20)]}]}]}
    assert trace.busy_s(plain) == pytest.approx(100e-9)
    assert trace.op_seconds(plain, lambda ev: "pallas" in
                            ev[3].get("tf_op", "")) == pytest.approx(10e-9)
    assert trace.module_runs(plain, r"^jit_dec\b") == [pytest.approx(1e-7)]
    assert trace.top_ops(plain)[0] == ["fusion.1", pytest.approx(60e-9)]
    gaps = trace.idle_gaps(plain)
    assert gaps[0] == ["host: outer", pytest.approx(90e-9)]
    assert gaps[1] == ["host: np.asarray", pytest.approx(30e-9)]


def test_trace_reduction_on_recorded_chip_trace():
    """A trace recorded on a TPU v5e (a few training steps, cut to keep
    it small): the reduction finds the device, its operations and the
    aggregation kernels by name."""
    from chipbench import readers
    plain = trace.load(str(BENCH / "testdata" / "train_trace.json.gz"))
    busy = trace.busy_s(plain)
    assert busy is not None and busy > 0
    ops = trace.top_ops(plain)
    assert ops and all(s > 0 for _, s in ops)
    assert ops == sorted(ops, key=lambda x: -x[1])
    agg = trace.op_seconds(plain, readers.is_agg)
    assert 0 < agg < busy
    # only the kernel's own instruction, not the ops that read its output
    named = {ev[0].split(" ")[0] for pl in plain["planes"]
             for ln in pl["lines"] for ev in ln["events"]
             if ln["name"] == "XLA Ops" and readers.is_agg(ev)}
    assert named == {"%coord_stat.1"}
    readers_of_it = [ev for pl in plain["planes"] for ln in pl["lines"]
                     for ev in ln["events"] if ln["name"] == "XLA Ops"
                     and "%coord_stat.1" in ev[0]
                     and not ev[0].startswith("%coord_stat.1 ")]
    assert readers_of_it and not any(readers.is_agg(ev)
                                     for ev in readers_of_it)
    assert trace.idle_gaps(plain)


# ---------------------------------------------------------------------------
# the chip gate


def test_non_tpu_device_is_refused(capsys):
    rc = cli.main(["--workload", TRAIN, "--seed", "1", "--seconds", "1"],
                  root=str(REPO), t_start=time.perf_counter())
    out, err = capsys.readouterr()
    assert rc != 0 and out == "" and "no TPU" in err


def test_checkout_without_the_program_prints_no_result(tmp_path):
    root = tmp_path / "bare"
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", TRAIN, "--seed", "3", "--seconds",
                        "1", "--trace", "0"], cwd=root, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


# ---------------------------------------------------------------------------
# whole runs at a tiny size: the check passes the program and fails the
# control and every planted fault


def _train_run_and_control(cell, tmp_path, capsys, monkeypatch):
    res, err = run_cell(tiny_root(tmp_path), cell, capsys, monkeypatch,
                        control="fp8")
    assert res["correct"] is True, err[-2000:]
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["attempted"] > 0 and res["device"]["count"] >= 1
    limits = {k: v["limit"] for k, v in res["checks"].items()}
    control = {line.split()[0]: float(line.split()[1]) for line in
               err.split("control fp8: ")[1].splitlines()[0].split("  ")}
    assert any(control[k] > limits[k] for k in limits), (control, limits)


def test_train_run_is_correct_and_its_control_is_not(tmp_path, capsys,
                                                      monkeypatch):
    _train_run_and_control(TRAIN, tmp_path, capsys, monkeypatch)


def test_reference_is_the_module_the_configuration_names(
        tmp_path, capsys, monkeypatch):
    """A copy of the dense reference under another path, the original
    gone: the training cell runs through the copy and reads correct."""
    root = tiny_root(tmp_path)
    refs = root / "benchmarks" / "chip" / "refs"
    copy = root / "benchmarks" / "chip" / "other_refs" / "dense_copy.py"
    copy.parent.mkdir()
    shutil.move(refs / "dense_decoder.py", copy)
    _set_config(root, "paper-100m.silo8", lambda cfg: cfg.update(
        reference="benchmarks/chip/other_refs/dense_copy.py"))
    calls = []
    load = spec.load_reference

    def recording(*args, **kw):
        mod = load(*args, **kw)
        for name in ("loss", "train_flops_per_token"):
            def wrapped(*a, _f=getattr(mod, name), _n=name, **k):
                calls.append((mod.__file__, _n))
                return _f(*a, **k)
            setattr(mod, name, wrapped)
        return mod
    monkeypatch.setattr(spec, "load_reference", recording)
    res, err = run_cell(root, TRAIN, capsys, monkeypatch)
    assert res["correct"] is True, err[-2000:]
    assert {n for _, n in calls} == {"loss", "train_flops_per_token"}
    assert {Path(f).resolve() for f, _ in calls} == {copy.resolve()}


def test_async_train_run_is_correct_and_its_control_is_not(
        tmp_path, capsys, monkeypatch):
    _train_run_and_control(ASYNC, tmp_path, capsys, monkeypatch)


def _plant_train_fault(fault, monkeypatch):
    # the synchronous step and the general async step each import these
    import repro.simulator.async_loop as async_loop
    import repro.training.step as step
    for mod in (step, async_loop):
        if fault == "state_unchanged":
            monkeypatch.setattr(mod, "apply_updates", lambda p, u: p)
        else:
            orig = mod.loss_fn
            monkeypatch.setattr(mod, "loss_fn", lambda cfg, p, b, o=orig: o(
                cfg, p, {k: v[:v.shape[0] // 2] for k, v in b.items()}))


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_check_fails_on_planted_fault(fault, tmp_path, capsys,
                                            monkeypatch):
    _plant_train_fault(fault, monkeypatch)
    res, err = run_cell(tiny_root(tmp_path), TRAIN, capsys, monkeypatch)
    assert res["correct"] is False, err[-2000:]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_async_train_check_fails_on_planted_fault(fault, tmp_path, capsys,
                                                  monkeypatch):
    _plant_train_fault(fault, monkeypatch)
    res, err = run_cell(tiny_root(tmp_path), ASYNC, capsys, monkeypatch)
    assert res["correct"] is False, err[-2000:]


def test_serve_run_is_correct_and_its_control_is_not(tmp_path, capsys,
                                                      monkeypatch):
    res, err = run_cell(tiny_root(tmp_path), SERVE, capsys, monkeypatch,
                        seconds=3.0, control="fp8")
    assert res["correct"] is True, err[-2000:]
    assert set(res["metrics"]) == {"serve_ttft_p95_ms", "serve_tpot_p95_ms",
                                   "serve_tokens_per_s", "setup_s"}
    assert res["failed"] == 0 and res["attempted"] == 24
    control = float(err.split("control fp8: logit_gap ")[1].split()[0])
    assert control > res["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("fault", ["token_altered", "cache_unchanged"])
def test_serve_check_fails_on_planted_fault(fault, tmp_path, capsys,
                                            monkeypatch):
    import repro.serving.sched.scheduler as sch
    if fault == "token_altered":
        orig = sch.ReplicatedScheduler._commit_tokens

        def commit(self, logits, active, now, phase):
            out = orig(self, logits, active, now, phase)
            if phase == "decode":
                out[0].update({b: (t + 1) % self.cfg.vocab_size
                               for b, t in out[0].items()})
            return out
        monkeypatch.setattr(sch.ReplicatedScheduler, "_commit_tokens",
                            commit)
    else:
        orig = sch.decode_step
        monkeypatch.setattr(sch, "decode_step", lambda cfg, p, tok, c: (
            orig(cfg, p, tok, c)[0], c))
    res, err = run_cell(tiny_root(tmp_path), SERVE, capsys, monkeypatch,
                        seconds=3.0)
    assert res["correct"] is False, err[-2000:]
