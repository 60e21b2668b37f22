"""The metric readers on a made-up run record and trace summary."""

import pytest

from bench import harness, loops, ops


class FakeCell:
    def __init__(self, batch, layers):
        self.batch, self.layers, self.chips = batch, layers, 1


def make_run(calls, spans=(), trace=None, batch=2, seconds=10.0, profiler=None):
    rec = loops.Record(seconds)
    rec.t0, rec.end = 100.0, 100.0 + seconds
    rec.calls = calls
    rec.spans.rows = list(spans)
    rec.profiler = profiler
    layers = [{"kind": "conv", "k": 3, "cin": 8, "cout": 8,
               "in_hw": (4, 4), "out_hw": (4, 4)}]
    peaks = {"int8_ops": 1e9, "hbm_bytes_per_s": 1e9}
    return harness.Run(FakeCell(batch, layers), rec, 12.5, peaks, trace)


def read(name, run):
    return harness.reader(name)(run)


def test_fps_counts_frames_answered_inside_the_window():
    calls = [{"n": 2, "ok": True, "done": 101.0},
             {"n": 2, "ok": True, "done": 109.9},
             {"n": 2, "ok": False, "done": 105.0},
             {"n": 2, "ok": True, "done": 110.5}]
    assert read("fps", make_run(calls)) == pytest.approx(4 / 10)


def test_latency_percentiles_over_all_frames():
    calls = [{"n": 1, "ok": True, "due": 100.0 + i, "done": 100.0 + i + 0.01 * (i + 1)}
             for i in range(100)]
    run = make_run(calls)
    assert read("p50_ms", run) == pytest.approx(505.0)
    assert read("p90_ms", run) == pytest.approx(901.0)
    assert read("p50_ms", make_run([{"n": 1, "ok": True, "done": 1}])) is None


def test_setup_s():
    assert read("setup_s", make_run([])) == 12.5


def test_dispatch_leaves_out_the_profiled_part():
    prof = loops.Profiler("unused", 0, 0)
    prof.on, prof.off = 104.0, 106.0
    spans = [("dispatch", 101.0, 101.002), ("fetch", 101.002, 101.1),
             ("dispatch", 105.0, 105.5), ("dispatch", 107.0, 107.004),
             ("dispatch", 99.0, 99.5)]
    run = make_run([], spans, profiler=prof)
    assert read("dispatch_ms.offline", run) == pytest.approx(3.0)
    assert read("dispatch_ms.stream", make_run([])) is None


SUMMARY = {"window_s": 2.0, "busy_s": 0.5, "conv_s": 0.1, "chips": 1,
           "fetches": 10}


def profiled_run(trace=None):
    """10 s window, profiled from 104 to 106 s; 2 frames a call, 3 calls
    answered outside the profiled part (one after the close), 2 inside."""
    prof = loops.Profiler("unused", 0, 0)
    prof.on, prof.off = 104.0, 106.0
    calls = [{"n": 2, "ok": True, "done": d}
             for d in (101.0, 104.5, 105.5, 108.0, 109.0, 110.5)]
    calls.append({"n": 2, "ok": False, "done": 102.0})
    return make_run(calls, trace=trace, profiler=prof)


def test_untraced_rate_leaves_out_the_profiled_part():
    from bench.metrics import _untraced
    run = profiled_run(dict(SUMMARY))
    assert _untraced.rate(run) == pytest.approx(6 / 8)
    assert _untraced.traced_rate(run) == pytest.approx(20 / 2.0)
    assert _untraced.slowdown(run) == pytest.approx(10 / 0.75)
    assert _untraced.rate(make_run([])) is None


def test_device_metrics_from_the_trace():
    run = profiled_run(dict(SUMMARY))
    layers = run.cell.layers
    # 0.5 s busy over 20 frames, at 0.75 frames/s outside the profile
    assert read("idle_share.offline", run) == pytest.approx(
        100 * (1 - 0.5 / 20 * 0.75))
    assert read("device_ms_per_frame.stream", run) == pytest.approx(25.0)
    mfu = ops.frame_ops(layers) * 0.75 / 1e9 * 100
    assert read("step_mfu.offline", run) == pytest.approx(mfu)
    least = ops.least_seconds(layers, 2, 1e9, 1e9)["seconds"]
    assert read("conv_roofline.offline", run) == pytest.approx(
        100 * least * 10 / 0.1)


@pytest.mark.parametrize("name", ["idle_share.offline",
                                  "conv_roofline.offline",
                                  "device_ms_per_frame.stream"])
def test_device_metrics_are_silent_without_a_trace(name):
    assert read(name, profiled_run()) is None


def test_step_mfu_is_silent_without_answers():
    assert read("step_mfu.offline", make_run([])) is None


def test_roofline_is_silent_without_conv_ops():
    run = make_run([], trace=dict(SUMMARY, conv_s=0.0))
    assert read("conv_roofline.offline", run) is None
