"""The reduction from profiler trace to per-layer numbers: on a hand-made
trace with known answers, and on a trace recorded on a TPU v5e (one chip,
cifar10-k2-t64 under the offline mix, a 50 ms window of 7 ticks and the
tick that drained the queue)."""
import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def hand_made():
    # window [0, 100) ns; device 0 busy [10, 30) u [25, 40) u [60, 70)
    ops = {0: [(10, 30, "acam_match_serve.1 (f32[64,2048]"),
               (25, 40, "copy.1 f32[1024,2,784]"),
               (60, 70, "all-gather.2 f32[4,64]"),
               (150, 160, "acam_match_serve.1 (f32[64,2048]")],  # outside
           1: [(0, 50, "acam_match_serve.1 (f32[64,2048]")]}
    host = sorted([(0, 100, tr.WINDOW), (0, 45, "bench.step"),
                   (2, 8, "acam_fused_dispatch"), (40, 58, "bench.submit"),
                   (70, 95, "bench.step"), (75, 90, "np.asarray")])
    return tr.Trace(window=(0, 100), ops=ops, host=host)


def test_busy_idle_kernel_and_collective_time():
    t = hand_made()
    assert t.window_s == pytest.approx(100e-9)
    assert tr.busy_s(t, 0) == pytest.approx(40e-9)  # 30 + 10
    assert tr.idle_share(t, 0) == pytest.approx(0.6)
    assert tr.busy_s(t, 1) == pytest.approx(50e-9)
    assert tr.op_events(t, tr.SERVE_KERNEL) == [20]
    assert tr.op_events(t, tr.COLLECTIVE) == [10]
    assert tr.host_spans(t, "acam_fused_dispatch") == [6]
    assert tr.top_ops(t)[0] == ["acam_match_serve.1 (f32[64,2048]", 20e-9]


def test_idle_gaps_by_innermost_host_span():
    # gaps: [0,10) mid 5 -> dispatch; [40,60) mid 50 -> submit;
    # [70,100) mid 85 -> np.asarray
    gaps = dict(tr.idle_gaps(hand_made()))
    assert gaps == pytest.approx({"acam_fused_dispatch": 10e-9,
                                  "bench.submit": 20e-9,
                                  "np.asarray": 30e-9})


def test_op_names():
    assert tr.op_name("%copy.1 = f32[8,2]{1,0:T(8,128)} copy(f32[8,2] %x)") \
        == "copy.1 f32[8,2]"
    assert tr.SERVE_KERNEL.search(tr.op_name(
        "%acam_match_serve.1 = (f32[64,2048]{1,0}, s32[64,128]) "
        "custom-call(...)"))


def test_recorded_chip_trace():
    t = tr.load(os.path.join(DATA, "t64-offline.xplane.pb"))
    assert list(t.ops) == [0]
    assert t.window_s == pytest.approx(0.057889001)
    busy = tr.busy_s(t)
    assert 0 < busy < t.window_s
    kernel = tr.op_events(t, tr.SERVE_KERNEL)
    # one serve kernel per tick, one acam_fused_dispatch span per tick
    assert len(kernel) == 8 == len(tr.host_spans(t, "acam_fused_dispatch"))
    assert sum(kernel) == pytest.approx(57193, rel=1e-9)
    assert tr.op_events(t, tr.COLLECTIVE) == []
    top = tr.top_ops(t)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    assert top[0][0] == "copy.1 f32[1024,2,784]"
    gaps = tr.idle_gaps(t, n=100)
    assert sum(s for _, s in gaps) == pytest.approx(t.window_s - busy)
    assert gaps[0][0] == "np.asarray(jax.Array)"
