"""The generator: the same work for every seed, inputs fixed by the seed."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"


@pytest.mark.parametrize("rate,seconds", [(11.5, 30), (5.4, 30), (100, 2.5)])
def test_arrivals_count_and_rate(rate, seconds):
    off = traffic.arrivals(np.random.default_rng(0), rate, seconds)
    assert len(off) == round(rate * seconds)
    assert off[0] == 0 and np.all(np.diff(off) > 0)
    # n gaps of mean 1 / rate; the last is after the last arrival
    assert off[-1] < seconds


def test_arrivals_same_gaps_in_another_order():
    a = traffic.arrivals(np.random.default_rng(1), 10, 30)
    b = traffic.arrivals(np.random.default_rng(2), 10, 30)
    assert not np.allclose(a, b)
    g = traffic.gaps(10, 30)
    assert g.sum() == pytest.approx(30)
    for off in (a, b):
        nearest = np.abs(np.diff(off)[:, None] - g[None, :]).min(axis=1)
        assert nearest.max() < 1e-9


@pytest.mark.parametrize("period,share", [(2.0, 0.25), (0.5, 0.5), (3.0, 1.0)])
def test_bursts_keep_the_arrivals_inside_on_phases(period, share):
    bursts = {"period_s": period, "on_share": share}
    off = traffic.arrivals(np.random.default_rng(4), 20, 30, bursts)
    assert len(off) == 600 and np.all(np.diff(off) >= 0)
    assert np.all(np.mod(off, period) <= period * share + 1e-9)
    assert off[-1] < 30
    # the same gaps, packed: the on-time offsets are a plain stream's
    plain = traffic.arrivals(np.random.default_rng(4), 20 / share, 30 * share)
    on_s = period * share
    np.testing.assert_allclose(np.floor(off / period) * on_s
                               + np.mod(off, period), plain, atol=1e-9)


def test_arrivals_gaps_are_exponential():
    off = traffic.arrivals(np.random.default_rng(3), 50, 200)
    gaps = np.diff(off)
    assert gaps.mean() == pytest.approx(1 / 50, rel=0.01)
    assert np.median(gaps) == pytest.approx(np.log(2) / 50, rel=0.02)


@pytest.mark.parametrize("seed", [0, 2**31 + 17, 2**40, -5])
def test_frames_follow_the_seed(seed):
    a = traffic.frames(traffic.rngs(seed)["frames"], 4, (32, 32))
    b = traffic.frames(traffic.rngs(seed)["frames"], 4, (32, 32))
    c = traffic.frames(traffic.rngs(seed + 1)["frames"], 4, (32, 32))
    assert a.shape == (4, 32, 32, 3) and a.dtype == np.float32
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_uses_of_one_seed_are_independent():
    g = traffic.rngs(9)
    assert g["frames"].random() != g["calibration"].random()


@pytest.mark.parametrize("path", sorted(MIXES.glob("*.json")), ids=lambda p: p.stem)
def test_mix_files_load(path):
    mix = traffic.load(path)
    assert mix["pool_frames"] % mix["batch"] == 0
    assert traffic.kind(mix["kind"]).window


@pytest.mark.parametrize("mix", [
    {"kind": "burst", "batch": 1, "pool_frames": 1},
    {"kind": "../run", "batch": 1, "pool_frames": 1},
    {"kind": "offline", "batch": 4, "outstanding": 0, "pool_frames": 8},
    {"kind": "offline", "batch": 4, "outstanding": 2, "pool_frames": 6},
    {"kind": "stream", "batch": 2, "rate_fps": 1, "schedule_seed": 1,
     "pool_frames": 2},
    {"kind": "stream", "batch": 1, "rate_fps": 1, "schedule_seed": 1,
     "pool_frames": 2, "bursts": {"period_s": 1, "on_share": 0}},
    {"kind": "stream", "batch": 1, "pool_frames": 2},
], ids=["unknown_kind", "path_kind", "no_outstanding", "ragged_pool",
        "stream_batch", "empty_bursts", "no_rate"])
def test_bad_mix_is_refused(tmp_path, mix):
    p = tmp_path / "m.json"
    p.write_text(json.dumps(mix))
    with pytest.raises(ValueError):
        traffic.load(p)
