"""The harness end to end on the CPU at a small size, and BENCHMARK.json
against the rules the benchmark is held to."""

import json
import re
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest
from conftest import PART_LIMITS, cpu_run, parts_root, small_cell

from bench import check, harness, loops

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_spec_has_exactly_the_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert SPEC["command"] == ["python3", "bench/run.py"]


def test_names_and_units():
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += CELLS + [c["name"] for c in SPEC["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")


def test_text_fields():
    for e in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for m in SPEC["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200
    allowed = {"name", "unit", "better", "bound", "source", "workloads",
               "layer", "moves"}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert set(m) <= allowed


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    setup = [m for m in SPEC["end_to_end"] if m["name"] == "setup_s"]
    assert setup and "workloads" not in setup[0]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_reports_what_it_must(name):
    cell = harness.Cell(name)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert harness.reader(m["name"])
    for m in cell.end_to_end:
        assert harness.reader(m["name"])
    assert cell.chips == 1


def test_config_files():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and c["reduced"] == []
        assert c["file"].startswith("bench/configs/")


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        harness.Cell("no_such.cell")


@pytest.mark.parametrize("name", ["resnet8.offline", "resnet18_cifar.stream"])
def test_cpu_run_is_correct(name):
    res = cpu_run(small_cell(name))
    assert res["correct"] is True, res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    cell = harness.Cell(name)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(res)[-1] == "check"
    assert res["check"]["failed"] == {"value": 0, "limit": 0}
    for v in res["metrics"].values():
        assert v["value"] > 0


def test_cpu_run_judges_each_declared_part(tmp_path):
    res = cpu_run(small_cell("resnet8.offline", root=parts_root(tmp_path)))
    assert res["correct"] is True, res["check"]
    assert list(res["check"]) == list(PART_LIMITS) + ["failed",
                                                      "lowered_in_window"]
    for name, limit in PART_LIMITS.items():
        assert res["check"][name]["limit"] == limit
        assert 0 < res["check"][name]["value"] <= limit


@pytest.mark.parametrize("limits", [
    {"rel_l2": 0.8, "worst_frame": 1.4},               # limits of no part
    dict(list(PART_LIMITS.items())[:3]),               # a part without one
    dict(PART_LIMITS, **{"other.rel_l2": 0.8}),        # a limit of no part
])
def test_cell_whose_limits_miss_its_parts_is_refused(tmp_path, limits):
    root = parts_root(tmp_path, limits=limits)
    with pytest.raises(ValueError, match="check.limits"):
        harness.Cell("resnet8.offline", root)


class RecordingModel:
    """A reference that answers from the frames it is given, and keeps
    them."""

    def __init__(self):
        self.seen = []

    def reference_fn(self, cfg):
        def ref(params, x):
            self.seen.append(np.array(x))
            return x.reshape(len(x), -1)[:, :10] * 2.0 + params
        return ref


def synthetic(after_close_counts, seed=3):
    """A deployment and a window of 14 calls of 1 to 5 frames over a pool
    of 64, with calls that failed and calls answered after the close."""
    rng = np.random.default_rng(11)
    kind = SimpleNamespace(ANSWERS_AFTER_CLOSE_COUNT=after_close_counts)
    cell = SimpleNamespace(cfg={"check": {}}, kind=kind, model=RecordingModel(),
                           mix={"sample_frames": 25})
    dep = SimpleNamespace(cell=cell, params=0.5,
                          pool=rng.standard_normal((64, 2, 2, 3)),
                          sample_rng=np.random.default_rng(seed))
    rec = loops.Record(1.0)
    rec.t0, rec.end = 0.0, 10.0
    for i in range(14):
        n = 1 + i % 5
        call = {"index": i, "first": (7 * i) % (64 - n), "n": n,
                "done": 12.0 if i in (4, 9) else 1.0}
        rec.calls.append(call)
        if i in (2, 11):
            call["ok"] = False
            continue
        rec.logits[i] = rng.standard_normal((n, 10)).astype(np.float32)
        call["ok"] = True
    return dep, rec


def concatenating_compare(dep, rec):
    """The comparison as it was before the sampled answers were gathered:
    every answer concatenated, then the sample taken."""
    rows, logits = [], []
    in_window = not dep.cell.kind.ANSWERS_AFTER_CLOSE_COUNT
    for call in rec.calls:
        if not call.get("ok") or (in_window and call["done"] > rec.end):
            continue
        rows.append(np.arange(call["first"], call["first"] + call["n"]))
        logits.append(rec.logits[call["index"]])
    rows, logits = np.concatenate(rows), np.concatenate(logits)
    pick = harness.sample(dep, rows)
    ref = dep.cell.model.reference_fn(dep.cell.cfg)
    want = check.in_blocks(lambda x: ref(dep.params, x), dep.pool[rows[pick]])
    return rows[pick], logits[pick], check.numbers(logits[pick], want)


@pytest.mark.parametrize("after_close_counts", [False, True])
def test_compare_gathers_the_sampled_answers(after_close_counts):
    dep, rec = synthetic(after_close_counts)
    old_rows, old_answers, old_numbers = concatenating_compare(dep, rec)
    old_frames = np.concatenate(dep.cell.model.seen)[:len(old_rows)]

    dep, rec = synthetic(after_close_counts)
    rows, where = harness.answered(dep, rec)
    pick = harness.sample(dep, rows)
    assert np.array_equal(rows[pick], old_rows)
    answers = harness.gather(rec, where[pick])
    assert answers.dtype == old_answers.dtype
    assert np.array_equal(answers, old_answers)

    dep, rec = synthetic(after_close_counts)
    out = harness.compare(dep, rec)
    assert out["frames"] == len(old_rows)
    assert out["numbers"] == old_numbers
    frames = np.concatenate(dep.cell.model.seen)[:len(old_rows)]
    assert np.array_equal(frames, old_frames)


def test_traced_cpu_run_reads_host_metrics():
    # on the CPU the trace has no device plane: device metrics stay silent
    res = cpu_run(small_cell("resnet8.offline"), traced=True, seconds=2.0)
    assert res["correct"] is True
    assert "dispatch_ms.offline" in res["metrics"]
    assert "idle_share.offline" not in res["metrics"]


def test_run_without_accelerator_prints_nothing():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "resnet18_cifar.stream", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 2
    assert proc.stdout == ""
    assert "no accelerator" in proc.stderr


def test_run_without_the_program_fails(tmp_path):
    import shutil
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path[:0] = ['.', 'src'];"
         "from bench.models import resnet; import jax;"
         "resnet.deploy(None, {'deployment': {}}, None, {})"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0 and proc.stdout == ""
    assert "No module named 'repro'" in proc.stderr
