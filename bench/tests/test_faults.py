"""A run with the timed path broken underneath comes out not correct.

The harness's look for a chip is skipped (``conftest.cpu_run``); the rest
of a run is driven as on the chip, with the program's ``serve`` wrapped so
that each answer is broken where it is produced.
"""

import jax.numpy as jnp
import pytest
from conftest import PARTS, cpu_run, parts_root, small_cell

from bench.models import resnet


def alter_one(y):              # one answer altered
    return y.at[0].set(-y[0])


def swap_frames(y):            # answers handed to the wrong frames
    return jnp.roll(y, 1, axis=0)


def half_batch(y):             # half of the batch left out, the rest copied
    half = max(1, y.shape[0] // 2)
    return jnp.concatenate([y[:half]] * (y.shape[0] // half))[:y.shape[0]]


def not_finite(y):
    return y.at[0, 0].set(jnp.nan)


def wrong_shape(y):
    return y[:, :-1]


class Stale:                   # the first answer, returned again and again
    def __init__(self):
        self.first = None

    def __call__(self, y):
        if self.first is None or self.first.shape != y.shape:
            self.first = y
        return self.first


def raising(y):
    raise RuntimeError("planted fault")


def lowers_each_call(y):       # right answers, from a program compiled anew
    import jax
    return jax.jit(lambda v: v * 1.0)(y)


FAULTS = {"alter_one": alter_one, "swap_frames": swap_frames,
          "half_batch": half_batch, "not_finite": not_finite,
          "wrong_shape": wrong_shape, "stale": Stale, "raising": raising,
          "lowers_each_call": lowers_each_call}


def broken(monkeypatch, fault):
    deploy = resnet.deploy
    armed = {"on": False}

    def faulty_deploy(*args, **kwargs):
        serve = deploy(*args, **kwargs)

        def serve_broken(frames):
            y = serve(frames)
            return fault(y) if armed["on"] else y
        return serve_broken

    monkeypatch.setattr(resnet, "deploy", faulty_deploy)
    return armed


BATCH_FAULTS = ("swap_frames", "half_batch")    # need more than one frame a call
CASES = [("resnet8.offline", f) for f in sorted(FAULTS)] + \
    [("resnet18_cifar.stream", f) for f in sorted(FAULTS) if f not in BATCH_FAULTS]


@pytest.mark.parametrize("cell_name,fault", CASES)
def test_fault_is_not_correct(monkeypatch, cell_name, fault):
    make = FAULTS[fault]
    armed = broken(monkeypatch, make() if fault == "stale" else make)
    cell = small_cell(cell_name)
    from bench import harness
    orig_window = harness.window

    def window(dep, *a, **k):
        armed["on"] = True             # broken in the timed window only
        return orig_window(dep, *a, **k)

    monkeypatch.setattr(harness, "window", window)
    res = cpu_run(cell)
    assert res["correct"] is False, res["check"]


def dead_last_part(y):         # the last declared part returns a constant
    return y.at[..., PARTS["last"][0]:PARTS["last"][1]].set(0.0)


def test_fault_in_one_part_is_not_correct(monkeypatch, tmp_path):
    broken(monkeypatch, dead_last_part)["on"] = True
    cell = small_cell("resnet8.offline", root=parts_root(tmp_path))
    res = cpu_run(cell)
    assert res["correct"] is False, res["check"]
    assert res["check"]["head.rel_l2"]["value"] <= 0.8
    assert res["check"]["last.rel_l2"]["value"] > 0.8


def test_unbroken_control_run_is_correct(monkeypatch):
    armed = broken(monkeypatch, alter_one)
    assert not armed["on"]
    assert cpu_run(small_cell("resnet8.offline"))["correct"] is True
