"""The serving metrics that read the program's own spans
(``benchmarks/chip/metrics/serve_*``, ``chipbench.spans``): on a
hand-made trace with known answers, on a trace of a program that opens
no spans (they find nothing and do not raise), and on a ``serve.steady``
window recorded on a TPU v5e."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

from chipbench import spans, spec, trace  # noqa: E402

READERS = ("serve_sched_step_ms", "serve_to_host_ms", "serve_commit_ms",
           "serve_admit_ms", "serve_queue_wait_p75_ms",
           "serve_idle_commit_share")


def _ev(name, start, end):
    return [name, float(start), float(end - start), {}]


def _hand_made():
    """Three steps on the host (times in ns): A and C only decode, B also
    admits one request; four requests waited in the queue.  The device
    runs the decodes, the prefill, the splice and a sliver inside C's
    pull; the window is 400 ns."""
    host = [
        _ev("sched.step", 0, 100), _ev("sched.decode", 0, 40),
        _ev("sched.to_host", 40, 60), _ev("sched.commit", 60, 70),
        _ev("sched.step", 100, 300), _ev("sched.decode", 100, 140),
        _ev("sched.to_host", 140, 150), _ev("sched.commit", 150, 160),
        _ev("sched.admit", 170, 290), _ev("sched.prefill", 170, 200),
        _ev("sched.to_host", 200, 210), _ev("sched.commit", 210, 220),
        _ev("sched.splice", 220, 230),
        _ev("sched.step", 300, 380), _ev("sched.decode", 300, 340),
        _ev("sched.to_host", 340, 360), _ev("sched.commit", 360, 370),
        _ev("sched.queued", 50, 170), _ev("sched.queued", 0, 10),
        _ev("sched.queued", 0, 20), _ev("sched.queued", 0, 30),
        _ev("$scheduler.py:1 step", 0, 380)]        # a Python frame
    ops = [_ev("fusion.1", 0, 40), _ev("fusion.1", 100, 140),
           _ev("fusion.2", 170, 200), _ev("copy.3", 220, 230),
           _ev("fusion.1", 300, 340), _ev("copy.4", 345, 350)]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
                                             "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python",
                                         "events": host}]}]}


def _run(plain, window_s=400e-9, kind="serve"):
    return SimpleNamespace(kind=kind, plain=plain, trace_window_s=window_s)


def _read(name, run):
    return spec.metric_reader(name, str(BENCH))(run)


def test_span_helpers_hand_made():
    plain = _hand_made()
    steps = spans.find(plain, spans.STEP)
    assert steps == [[0, 100], [100, 300], [300, 380]]
    admits = spans.find(plain, spans.ADMIT)
    pulls = spans.find(plain, spans.TO_HOST)
    assert spans.outside(pulls, admits) == [[40, 60], [140, 150],
                                            [340, 360]]
    assert spans.holding(steps, admits) == [[100, 300]]
    busy = spans.device_busy(plain)
    assert busy == [[0, 40], [100, 140], [170, 200], [220, 230],
                    [300, 340], [345, 350]]
    # [40, 70] and [340, 370] less the op at [345, 350]
    assert spans.idle_ns([[40, 70], [60, 70], [340, 370]], busy) == 55.0


@pytest.mark.parametrize("name,want", [
    ("serve_sched_step_ms", 90e-6),            # A and C: 100 and 80 ns
    ("serve_to_host_ms", 20e-6),               # 20, 10, 20 outside admit
    ("serve_commit_ms", 10e-6),                # 10, 10, 10
    ("serve_admit_ms", 120e-6),
    ("serve_queue_wait_p75_ms", 52.5e-6),      # 10, 20, 30, 120
    # idle 400 - 165 = 235 ns; pulls and commits hold 100 ns, 5 busy
    ("serve_idle_commit_share", 100.0 * 95 / 235),
])
def test_reader_hand_made(name, want):
    assert _read(name, _run(_hand_made())) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_spans(name):
    """A program that opens no spans (and a training run) reads None."""
    plain = trace.load(str(BENCH / "testdata" / "train_trace.json.gz"))
    assert _read(name, _run(plain, 8.0)) is None
    assert _read(name, _run(_hand_made(), kind="train")) is None


def _recorded():
    """27 whole scheduler steps (5 admissions) of a ``serve.steady``
    window traced on a TPU v5e: the program's spans and the device's
    ops and program runs, the op names cut to instruction and opcode."""
    plain = trace.load(str(BENCH / "testdata" / "serve_trace.json.gz"))
    t0, t1 = plain["window_ns"]
    return _run(plain, (t1 - t0) * 1e-9)


def test_readers_on_recorded_serve_trace():
    run = _recorded()
    got = {name: _read(name, run) for name in READERS}
    assert all(v is not None for v in got.values()), got
    # a decode-only step holds the decode program, the pull and the commit
    decode = _read("serve_decode_device_ms", run)
    assert (decode + got["serve_to_host_ms"] + got["serve_commit_ms"]
            < got["serve_sched_step_ms"])
    # an admission holds its prefill program
    assert got["serve_admit_ms"] > _read("serve_prefill_device_ms", run)
    assert 0 < got["serve_idle_commit_share"] < 100
    assert got["serve_queue_wait_p75_ms"] > 0


def test_step_children_hold_the_idle_of_recorded_steps():
    """Where the device waits inside a scheduler step, a child span of
    the step (decode, pull, commit, admission, resize) says why: at
    least 90% of that idle time lies inside one."""
    plain = _recorded().plain
    busy = spans.device_busy(plain)
    children = [sp for name in (spans.DECODE, spans.TO_HOST, spans.COMMIT,
                                spans.ADMIT, "sched.resize")
                for sp in spans.find(plain, name)]
    in_steps = spans.idle_ns(spans.find(plain, spans.STEP), busy)
    assert in_steps > 0
    assert spans.idle_ns(children, busy) >= 0.9 * in_steps
