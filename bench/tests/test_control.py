"""The control, the reference at the precision below the configuration's
(int4 for an int8 deployment), fails the limits that the program passes.

At a size a test run holds: 48 frames per seed, three seeds, both
configurations.  The readings on the chip at the cells' own sizes are in
PERF.md.
"""

import jax
import numpy as np
import pytest

from bench import check, harness, traffic


@pytest.mark.parametrize("name", ["resnet8.offline", "resnet18_cifar.offline"])
def test_control_fails_and_program_passes(name):
    cell = harness.Cell(name)
    cfg = cell.cfg
    limits = cfg["check"]["limits"]
    ref = cell.model.reference_fn(cfg)
    ctl = cell.model.control_fn(cfg, cfg["check"]["control_qmax"])
    for seed in (1, 2, 3):
        g = traffic.rngs(seed)
        key = jax.random.key(int(g["weights"].integers(2**32)))
        params = cell.model.init_params(key, cfg)
        calib = traffic.frames(g["calibration"], 16, cfg["image_hw"])
        x = traffic.frames(g["frames"], 48, cfg["image_hw"])
        serve = cell.model.deploy(params, cfg, jax.numpy.asarray(calib), {})
        got = np.concatenate([np.asarray(serve(x[i:i + 16]))
                              for i in range(0, 48, 16)])
        want = np.asarray(ref(params, x))
        prog = check.verdict(check.numbers(got, want), limits)
        control = check.verdict(
            check.numbers(np.asarray(ctl(params, calib, x)), want), limits)
        assert prog["correct"], prog
        assert not control["correct"], control


def test_control_at_the_program_precision_passes():
    # at int8 the control passes, as the program does: it is the same
    # network at another precision, not another network
    cell = harness.Cell("resnet8.offline")
    cfg = cell.cfg
    g = traffic.rngs(4)
    params = cell.model.init_params(
        jax.random.key(int(g["weights"].integers(2**32))), cfg)
    calib = traffic.frames(g["calibration"], 16, cfg["image_hw"])
    x = traffic.frames(g["frames"], 16, cfg["image_hw"])
    want = np.asarray(cell.model.reference_fn(cfg)(params, x))
    int8 = np.asarray(cell.model.control_fn(cfg, 127)(params, calib, x))
    assert check.verdict(check.numbers(int8, want),
                         cfg["check"]["limits"])["correct"]


def test_control_reads_each_declared_part(tmp_path):
    # bench/control.py reports harness.compare's numbers: with parts
    # declared, the program's and the control's readings of each part
    from conftest import PART_LIMITS, parts_root, small_cell
    cell = small_cell("resnet8.offline", root=parts_root(tmp_path))
    dep = harness.Deployment(cell, 7)
    rec = harness.window(dep, 1.0)
    dep.serve = None
    cmp = harness.compare(dep, rec, control=True)
    assert list(cmp["numbers"]) == list(cmp["control"]) == list(PART_LIMITS)
    assert check.verdict(cmp["numbers"], PART_LIMITS)["correct"]
    assert not check.verdict(cmp["control"], PART_LIMITS)["correct"]
