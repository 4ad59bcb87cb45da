"""The trace reduction, on a recorded trace of one kernel-path query (one
v5e; the device's ops and programs and the host's query annotation) and
on a hand-made one."""
import json
from pathlib import Path

import pytest

from bench_tiny import REPO  # noqa: F401  (puts the repository on sys.path)
from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data" / "kernel_query_trace.json"


@pytest.fixture(scope="module")
def planes():
    return json.loads(DATA.read_text())


def test_recorded_trace_window_and_busy(planes):
    t0, t1 = tr.find_event(planes, "bench.query")
    win = tr.reduce_window(planes, t0, t1)
    assert win.n_devices == 1
    assert win.window_s == pytest.approx(6.235587229, rel=1e-12)
    # busy by brute force: the union of the ops' intervals in the window
    ops = next(ln for pl in planes if pl["name"].startswith("/device:")
               for ln in pl["lines"] if ln["name"] == "XLA Ops")["events"]
    cuts = sorted({x for _, a, d in ops for x in (max(a, t0),
                                                  min(a + d, t1))})
    busy = sum(b - a for a, b in zip(cuts, cuts[1:])
               if any(s <= a and b <= s + d for _, s, d in ops))
    assert win.busy_s == pytest.approx(busy * 1e-9, rel=1e-12)
    idle = sum(b - a for a, b in win.gaps) * 1e-9
    assert idle + win.busy_s == pytest.approx(win.window_s, rel=1e-12)


def test_recorded_trace_kernel_time(planes):
    t0, t1 = tr.find_event(planes, "bench.query")
    win = tr.reduce_window(planes, t0, t1)
    kernel = sum(d for pl in planes if pl["name"].startswith("/device:")
                 for ln in pl["lines"] if ln["name"] == "XLA Ops"
                 for n, _, d in ln["events"]
                 if n.startswith("%mapspace_eval_multi"))
    assert win.time_matching("mapspace_eval") == pytest.approx(
        kernel * 1e-9, rel=1e-12)
    assert win.time_matching("evaluate_batch_multi") is None
    assert tr.top_ops(win)[0][0] == "%mapspace_eval_multi.1"


def _plane(name, lines):
    return {"name": name, "lines": [{"name": k, "events": v}
                                    for k, v in lines.items()]}


def test_hand_made_trace_gaps_and_labels():
    planes = [
        _plane("/host:CPU", {"main": [["bench.query", 1000.0, 9000.0]]}),
        _plane("/device:TPU:0", {
            "XLA Modules": [["jit_evaluate_batch_multi(123)", 2000.0,
                             3000.0]],
            "XLA Ops": [["%fusion.1 = f32[8] fusion(x)", 2000.0, 2000.0],
                        ["%fusion.2 = f32[8] fusion(y)", 3000.0, 2000.0],
                        ["%copy = f32[8] copy(z)", 500.0, 1000.0],
                        ["%fusion.1 = f32[8] fusion(x)", 8000.0, 4000.0]]}),
    ]
    t0, t1 = tr.find_event(planes, "bench.query")
    win = tr.reduce_window(planes, t0, t1)
    # clipped to [1000, 10000]: busy [1000,1500] + [2000,5000] + [8000,10000]
    assert win.busy_s == pytest.approx(5500e-9)
    assert win.gaps == [(1500.0, 2000.0), (5000.0, 8000.0)]
    assert win.op_s["%fusion.1"] == pytest.approx(4000e-9)
    assert win.module_s == {"jit_evaluate_batch_multi": pytest.approx(3e-6)}
    assert win.time_matching("evaluate_batch_multi") == pytest.approx(3e-6)
    labels = tr.label_gaps(win.gaps, [("pack", 1000.0, 1700.0),
                                      ("pack", 4000.0, 9000.0),
                                      ("score", 6000.0, 7000.0)])
    # gap [1500, 2000]: pack until 1700, then nothing; gap [5000, 8000]:
    # pack throughout, score open in [6000, 7000]
    assert labels == [["pack", pytest.approx(2200e-9)],
                      ["pack+score", pytest.approx(1000e-9)],
                      ["no-phase", pytest.approx(300e-9)]]


def test_no_device_plane_reads_nothing():
    planes = [_plane("/host:CPU", {"main": [["bench.query", 0.0, 10.0]]})]
    win = tr.reduce_window(planes, 0.0, 10.0)
    assert win.n_devices == 0 and win.busy_s == 0.0
    assert win.time_matching("mapspace_eval") is None
