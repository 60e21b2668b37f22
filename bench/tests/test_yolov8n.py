"""The YOLOv8n cell on the CPU at 64x64: its configuration, its reference,
a run that is correct, and a classifier broken under boxes that pass."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import ROOT, cpu_run, small_cell

from bench import check, harness, ops
from bench.models import yolov8n

CELL = "yolov8n.stream"
HW = [64, 64]


def small(frames=8):
    """The cell at 64x64, 8 frames in the pool, all of them compared."""
    cell = small_cell(CELL, frames=frames)
    cell.cfg = dict(cell.cfg, image_hw=HW)
    cell.layers = cell.model.layers(cell.cfg)
    return cell


def config():
    return json.loads((ROOT / "bench" / "configs" / "yolov8n.json").read_text())


def test_published_sizes():
    cfg = config()
    assert yolov8n.widths(cfg) == ([16, 32, 64, 128, 256], [1, 2, 2, 1], 1)
    assert yolov8n.head_widths(cfg, 64) == (64, 80)
    layers = yolov8n.layers(cfg)
    assert len(layers) == 63
    assert ops.frame_macs(layers) == 4_371_456_000
    n = sum(c["k"] ** 2 * c["cin"] * c["cout"] + c["cout"] for c in layers)
    assert 3.15e6 <= n <= 3.16e6
    least = ops.least_seconds(layers, 1, 393e12, 819e9)
    assert least["seconds"] == pytest.approx(48.36e-6, rel=1e-3)
    assert cfg["check"]["parts"] == {"box": [0, 4], "cls": [4, 84]}


def test_reference_matches_the_program_in_float():
    # the benchmark's own YOLOv8n and the program's yolo.forward, written
    # apart, agree on the same weights within float32 rounding
    from repro.models.cnn import yolo
    cfg = dict(config(), image_hw=HW)
    params = yolov8n.init_params(jax.random.key(3), cfg)
    x = jax.random.normal(jax.random.key(4), (3, 64, 64, 3))
    ref = np.asarray(yolov8n.reference_fn(cfg)(params, x))
    assert ref.shape == (3, 8 * 8 + 4 * 4 + 2 * 2, 84)
    np.testing.assert_allclose(ref, np.asarray(yolo.forward(params, x)),
                               rtol=1e-5, atol=1e-4)


def test_cpu_run_is_correct():
    res = cpu_run(small(), seed=2**31 + 77)
    assert res["correct"] is True, res["check"]
    assert set(res["check"]) == {"box.rel_l2", "box.worst_frame",
                                 "cls.rel_l2", "cls.worst_frame", "failed",
                                 "lowered_in_window"}
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"p50_ms", "p90_ms", "setup_s"}


def dead(y):                    # every class score 0.5
    return y.at[..., 4:].set(0.5)


def inverted(y):                # each class score p as 1 - p
    return y.at[..., 4:].set(1.0 - y[..., 4:])


@pytest.mark.parametrize("fault", [dead, inverted], ids=["dead", "inverted"])
def test_broken_classifier_fails_on_its_part_alone(monkeypatch, fault):
    deploy = yolov8n.deploy

    def faulty_deploy(*args, **kwargs):
        serve = deploy(*args, **kwargs)
        return lambda frames: fault(serve(frames))

    monkeypatch.setattr(yolov8n, "deploy", faulty_deploy)
    res = cpu_run(small(), seed=11)
    table = res["check"]
    assert res["correct"] is False
    for n in ("box.rel_l2", "box.worst_frame"):
        assert table[n]["value"] <= table[n]["limit"], (n, table)
    assert any(table[n]["value"] > table[n]["limit"]
               for n in ("cls.rel_l2", "cls.worst_frame")), table


def test_control_fails_its_limits():
    cell = small()
    cfg = cell.cfg
    params = yolov8n.init_params(jax.random.key(5), cfg)
    g = np.random.default_rng(5)
    calib = jnp.asarray(g.standard_normal((16, 64, 64, 3), np.float32))
    x = g.standard_normal((8, 64, 64, 3), np.float32)
    want = check.in_blocks(
        lambda b: yolov8n.reference_fn(cfg)(params, b), x)
    got = check.in_blocks(
        lambda b: yolov8n.control_fn(cfg, cfg["check"]["control_qmax"])(
            params, calib, b), x)
    verdict = check.verdict(check.numbers(got, want, cfg["check"]["parts"]),
                            cfg["check"]["limits"])
    assert verdict["correct"] is False


def test_nonconv_ms_per_frame_reads_the_summary():
    from test_metrics import make_run
    trace = {"busy_s": 0.5, "conv_s": 0.2, "fetches": 100, "window_s": 4.0}
    run = make_run([], trace=trace, batch=1)
    assert harness.reader("nonconv_ms_per_frame.stream")(run) == \
        pytest.approx(1e3 * 0.3 / 100)
    assert harness.reader("nonconv_ms_per_frame.stream")(
        make_run([], trace=None)) is None
    assert harness.reader("nonconv_ms_per_frame.stream")(
        make_run([], trace=dict(trace, fetches=0))) is None
