"""The trace reduction, on a small trace recorded on a TPU v5 lite
(``record_trace.py``: half a second of ResNet-8 under the stream_resnet8
mix) and on made-up intervals."""

from pathlib import Path

import numpy as np
import pytest

from bench import xplane

DATA = Path(__file__).resolve().parent / "data" / "resnet8_stream.xplane.pb"


@pytest.fixture(scope="module")
def profile():
    return xplane.read(DATA)


@pytest.fixture(scope="module")
def summary(profile):
    return xplane.summarize(profile)


def test_window_and_busy(summary):
    assert summary["chips"] == 1
    assert 0.5 < summary["window_s"] < 0.55
    assert 0 < summary["conv_s"] < summary["busy_s"] < summary["window_s"]
    # every idle second is put down to some span
    idle = sum(v for _, v in summary["idle_gaps"])
    assert idle == pytest.approx(summary["window_s"] - summary["busy_s"])


def test_pinned_readings(summary):
    # the reduction is deterministic: these change only with its code
    assert summary["window_s"] == pytest.approx(0.522742005)
    assert summary["busy_s"] == pytest.approx(0.001633204)
    assert summary["conv_s"] == pytest.approx(0.000290384)
    assert summary["fetches"] == 7


def test_one_conv_op_per_conv_layer_and_frame(summary):
    # ResNet-8 has 9 convs; each eager conv program runs one conv fusion
    assert summary["conv_ops"] == 9 * summary["fetches"]


def test_busy_is_the_union_of_the_ops(profile, summary):
    spans = xplane.host_spans(profile)
    lo, hi = spans[0][1], max(e for _, _, e in spans)
    ops = xplane.device_events(profile)[0]["ops"]
    # brute force on a 1 us grid
    grid = np.zeros((hi - lo) // 1000 + 1, bool)
    for s, e, _ in ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            grid[(s - lo) // 1000:(e - lo) // 1000] = True
    assert grid.sum() * 1e-6 == pytest.approx(summary["busy_s"], rel=0.05)


def test_spans_found(profile):
    names = {n for n, _, _ in xplane.host_spans(profile)}
    assert names <= set(xplane.SPAN_NAMES)
    assert {"dispatch", "fetch", "record"} <= names


def test_device_ops_are_named_by_program(summary):
    labels = [k for k, _ in summary["device_ops"]]
    assert labels[0].startswith("jit_conv_general_dilated/")
    assert all("/" in k for k in labels)


PATTERNS = {"instruction_patterns": ["convolution", "imc_conv2d"],
            "fusion_kinds": ["kOutput"]}


@pytest.mark.parametrize("text,conv", [
    ("%fusion = s32[256,32,32,32]{0} fusion(s32[...] %copy-done), "
     "kind=kOutput, calls=%fused_computation", True),
    ("%convolution_select_fusion = s32[32,8,5,16]{3} fusion(...), "
     "kind=kOutput, calls=%fused_computation", True),
    ("%convolution.3 = s32[1,8,8,64] convolution(s32[...] %a, s32[...] %b)", True),
    ("%imc_conv2d.1 = s32[1,8,8,64] custom-call(...)", True),
    ("%copy = s32[1,32,32,16] copy(s32[1,32,32,16] %convolution.3)", False),
    ("%round.1 = f32[1,32,32,16] round-nearest-even(f32[...] %a.1)", False),
    ("%broadcast_add_fusion = f32[1,32,32,16] fusion(...), kind=kLoop", False),
])
def test_is_conv(text, conv):
    assert xplane.is_conv(text, PATTERNS) is conv


def test_conv_spec_file_matches_the_patterns_tested():
    assert xplane.conv_patterns() == PATTERNS


def test_op_label():
    text = ("%fusion.4 = s32[32,8,5,16]{3,1,2,0:T(8,128)S(1)} fusion(...), "
            "kind=kOutput")
    assert xplane.op_label(text, "jit_conv_general_dilated(1698)") == \
        "jit_conv_general_dilated/fusion s32[32,8,5,16]"


def test_union_and_merge():
    iv = [(0, 10), (5, 12), (20, 25), (24, 30), (40, 41)]
    assert xplane.union_length(iv) == 12 + 10 + 1
    assert xplane.merged(iv) == [(0, 12), (20, 30), (40, 41)]
    assert xplane.union_length([]) == 0


def test_span_at():
    spans = [("dispatch", 0, 10), ("fetch", 10, 20), ("record", 25, 30)]
    assert xplane.span_at(spans, 5) == "dispatch"
    assert xplane.span_at(spans, 15) == "fetch"
    assert xplane.span_at(spans, 22) == "between_spans"


def test_no_spans_no_summary(tmp_path):
    class Empty:
        planes = []
    assert xplane.summarize(Empty()) is None
