"""The numbers that decide ``correct`` (``bench/check.py``), on seeded
synthetic answers."""

import numpy as np
import pytest

from bench import check

WHOLE_LIMITS = {"rel_l2": 0.8, "worst_frame": 1.4}


def whole_answer_numbers(got, want):
    """The comparison before answers had parts, kept as the reference."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    diff = np.linalg.norm(got - want, axis=1)
    norm = np.linalg.norm(want - want.mean(axis=0), axis=1)
    return {"rel_l2": float(np.sqrt((diff ** 2).sum() / (norm ** 2).sum())),
            "worst_frame": float(diff.max() / np.sqrt((norm ** 2).mean()))}


@pytest.mark.parametrize("seed,frames,width,dtype", [
    (1, 2048, 10, np.float32), (2, 7, 10, np.float32),
    (3, 256, 1000, np.float64), (4, 2, 3, np.float32)])
def test_two_dimensional_answer_without_parts_reads_as_before(
        seed, frames, width, dtype):
    rng = np.random.default_rng(seed)
    want = (rng.standard_normal((frames, width)) * 3 + 1).astype(dtype)
    got = (want + 0.2 * rng.standard_normal((frames, width))).astype(dtype)
    assert check.numbers(got, want) == whole_answer_numbers(got, want)


def test_worst_frame_is_per_frame_for_answers_of_any_rank():
    rng = np.random.default_rng(5)
    want = rng.standard_normal((6, 50, 4))
    got = want.copy()
    got[2] += 0.1                     # one frame off, all over it
    flat = check.numbers(got.reshape(6, -1), want.reshape(6, -1))
    assert check.numbers(got, want) == flat
    # all of the difference in one frame: the worst frame holds all of it
    assert flat["worst_frame"] == pytest.approx(np.sqrt(6) * flat["rel_l2"],
                                                rel=1e-12)


def detector_answer(rng, frames=6, anchors=8400, classes=80):
    """A YOLOv8-shaped answer, (frames, anchors, 4 + classes): box numbers
    in pixels around fixed anchor positions, then sigmoid class scores of
    seeded weights, near 0.43 and spread by about 0.1.  The class scores
    carry about 1% of the answer's centred energy."""
    base = rng.uniform(0, 640, (1, anchors, 4))
    box = base + 4.2 * rng.standard_normal((frames, anchors, 4))
    logit = -0.3 + 0.4 * rng.standard_normal((frames, anchors, classes))
    return np.concatenate([box, 1 / (1 + np.exp(-logit))], axis=-1)


DETECTOR_PARTS = {"box": [0, 4], "cls": [4, 84]}
DETECTOR_LIMITS = {"box.rel_l2": 0.8, "box.worst_frame": 1.4,
                   "cls.rel_l2": 0.8, "cls.worst_frame": 1.4}


@pytest.mark.parametrize("fault", ["dead", "inverted"])
def test_class_part_fault_hides_in_the_whole_and_shows_in_its_part(fault):
    rng = np.random.default_rng(6)
    want = detector_answer(rng)
    centred = want - want.mean(axis=0)
    share = (centred[..., 4:] ** 2).sum() / (centred ** 2).sum()
    assert 0.005 < share < 0.02
    # the program's rounding: a tenth of each part's own spread
    got = want + 0.1 * centred.std(axis=(0, 1)) * rng.standard_normal(
        want.shape)
    sound = check.numbers(got, want, DETECTOR_PARTS)
    assert check.verdict(sound, DETECTOR_LIMITS)["correct"], sound

    cls = want[..., 4:]
    got[..., 4:] = 0.5 if fault == "dead" else 1 - cls
    whole = check.numbers(got.reshape(len(got), -1),
                          want.reshape(len(want), -1))
    assert check.verdict(whole, WHOLE_LIMITS)["correct"], whole
    parts = check.numbers(got, want, DETECTOR_PARTS)
    assert parts["box.rel_l2"] == sound["box.rel_l2"]
    # a constant part reads 1 at the least, however it is centred
    assert parts["cls.rel_l2"] > max(1.0, 8 * sound["cls.rel_l2"])
    assert parts["cls.worst_frame"] > max(1.0, 8 * sound["cls.worst_frame"])
    assert not check.verdict(parts, DETECTOR_LIMITS)["correct"]


def test_each_part_is_centred_on_its_own_reference_mean():
    rng = np.random.default_rng(7)
    want = rng.standard_normal((32, 10))
    want[:, 5:] += 100.0               # an offset only the second part has
    got = want + 0.05 * rng.standard_normal(want.shape)
    parts = {"a": [0, 5], "b": [5, 10]}
    out = check.numbers(got, want, parts)
    for name, (start, stop) in parts.items():
        alone = check.numbers(got[:, start:stop], want[:, start:stop])
        assert out[f"{name}.rel_l2"] == alone["rel_l2"]
        assert out[f"{name}.worst_frame"] == alone["worst_frame"]


def test_wrong_shape_reads_infinite_for_every_number():
    out = check.numbers(np.zeros((4, 9)), np.zeros((4, 10)),
                        {"a": [0, 5], "b": [5, 10]})
    assert set(out) == {"a.rel_l2", "a.worst_frame", "b.rel_l2",
                        "b.worst_frame"}
    assert all(v == float("inf") for v in out.values())
    assert not check.verdict(out, {k: 1.0 for k in out})["correct"]


def test_part_past_the_answer_raises():
    with pytest.raises(ValueError, match="past the answer"):
        check.numbers(np.ones((4, 10)), np.ones((4, 10)), {"a": [5, 11]})


@pytest.mark.parametrize("parts,limits", [
    (None, {"rel_l2": 0.8}),                                   # a number without a limit
    (None, {"rel_l2": 0.8, "worst_frame": 1.4, "extra": 1}),   # a limit without a number
    ({"a": [0, 5], "b": [5, 10]}, {"rel_l2": 0.8, "worst_frame": 1.4}),
    ({"a": [0, 5], "b": [5, 10]},
     {"a.rel_l2": 0.8, "a.worst_frame": 1.4, "b.rel_l2": 0.8}),
    ({"a": [0, 5]}, {"a.rel_l2": 0.8, "a.worst_frame": 1.4,
                     "b.rel_l2": 0.8, "b.worst_frame": 1.4}),
    ({"a": [5, 5]}, {"a.rel_l2": 0.8, "a.worst_frame": 1.4}),  # empty part
    ({"a": [-1, 5]}, {"a.rel_l2": 0.8, "a.worst_frame": 1.4}),
    ({"a": [0, 5.0]}, {"a.rel_l2": 0.8, "a.worst_frame": 1.4}),
])
def test_limits_that_do_not_match_the_parts_raise(parts, limits):
    spec = {"limits": limits}
    if parts is not None:
        spec["parts"] = parts
    with pytest.raises(ValueError):
        check.validate(spec)


@pytest.mark.parametrize("parts", [None, {"a": [0, 5], "b": [5, 10]}])
def test_matching_limits_validate(parts):
    spec = {"limits": {n: 1.0 for n in check.names(parts)}}
    if parts is not None:
        spec["parts"] = parts
    check.validate(spec)
