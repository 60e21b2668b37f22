"""One run of one cell: set-up, the measured window, metrics, comparison.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Everything that
belongs to it is found by name: its configuration in the ``file`` that
``BENCHMARK.json`` gives it (whose ``model`` names the module under
``bench/models`` that makes its weights and reference and deploys it, and
whose ``check`` the cell is judged by, ``bench/check.py``), its
traffic mix in ``bench/traffic/<traffic>.json`` (whose ``kind`` names the
loop in ``bench/kinds/<kind>.py`` that drives the window), and each of its metrics
in ``bench/metrics/<name>.py``, or ``<first part of the name>.py`` where
metrics such as ``dispatch_ms.offline`` and ``dispatch_ms.stream`` share a
reader.  A reader's ``read(run)`` returns a number, or None where it found
nothing to read.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, loops, traffic, xplane
from bench.metrics import _untraced as untraced

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
WARMUP_CALLS = 3        # calls of the cell's own shape before the window
TRACE_SECONDS = 4.0     # length of the profiled part of a --trace 1 window
# Fired once for each program JAX lowers, also where the persistent cache
# then supplies the compiled code: a program the window lowers was not warm.
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def spec(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


class Cell:
    """A workload of ``BENCHMARK.json`` with its configuration, traffic mix,
    model module and metrics."""

    def __init__(self, name: str, root: Path = ROOT):
        bench = spec(root)
        entries = [w for w in bench["workloads"] if w["name"] == name]
        if not entries:
            known = ", ".join(w["name"] for w in bench["workloads"])
            raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
        entry = entries[0]
        self.name, self.chips = name, entry["chips"]
        configs = {c["name"]: c for c in bench["configs"]}
        path = root / configs[entry["config"]]["file"]
        self.cfg = json.loads(path.read_text())
        try:
            check.validate(self.cfg["check"])
        except (KeyError, ValueError) as e:
            raise ValueError(f"{path}: {e}") from None
        self.mix = traffic.load(HERE / "traffic" / f"{entry['traffic']}.json")
        self.kind = traffic.kind(self.mix["kind"])
        self.model = importlib.import_module(f"bench.models.{self.cfg['model']}")
        self.layers = self.model.layers(self.cfg)
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        e2e_names = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if (name in m["workloads"] if "workloads" in m
                              else m["moves"] in e2e_names)]

    @property
    def batch(self) -> int:
        return self.mix["batch"]


def reader(name: str) -> Callable:
    """``read`` of ``bench/metrics/<name>.py``, or of the file named by the
    part of ``name`` before its first dot."""
    for stem in (name, name.split(".")[0]):
        path = HERE / "metrics" / f"{stem}.py"
        if path.exists():
            mod_name = "bench_metric_" + stem.replace(".", "_")
            s = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(s)
            s.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} in bench/metrics")


class Run:
    """What the metric readers read."""

    def __init__(self, cell: Cell, record: loops.Record, setup_s: float,
                 peaks: Dict, summary: Optional[Dict]):
        self.cell, self.record, self.setup_s = cell, record, setup_s
        self.peaks, self.trace = peaks, summary


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

class Deployment:
    """The served model and the cell's inputs, made from one seed."""

    def __init__(self, cell: Cell, seed: int):
        self.cell = cell
        self.phases: Dict[str, float] = {}
        cfg, mix = cell.cfg, cell.mix
        g = traffic.rngs(seed)
        t = loops.now()
        key = jax.random.key(int(g["weights"].integers(2**32)))
        self.params = jax.block_until_ready(cell.model.init_params(key, cfg))
        self.phases["weights_s"] = loops.now() - t
        t = loops.now()
        hw = cfg["image_hw"]
        calib = traffic.frames(g["calibration"],
                               cfg["deployment"]["calibration_frames"], hw)
        self.calib = jax.block_until_ready(jnp.asarray(calib))
        self.pool = traffic.frames(g["frames"], mix["pool_frames"], hw)
        self.sample_rng = g["sample"]
        self.phases["frames_s"] = loops.now() - t
        self.serve = cell.model.deploy(self.params, cfg, self.calib,
                                       self.phases)
        t = loops.now()
        for _ in range(WARMUP_CALLS):
            np.asarray(self.serve(self.pool[:cell.batch]))
        # What set-up left on the heap is never garbage: keep the
        # collector's full passes in the window off it.
        gc.collect()
        gc.freeze()
        self.phases["warmup_s"] = loops.now() - t


def window(dep: Deployment, seconds: float,
           profiler: Optional[loops.Profiler] = None,
           rate: Optional[float] = None) -> loops.Record:
    """The measured window of the cell's traffic, driven by its kind's loop
    (``rate`` overrides a stream's offered rate)."""
    rec = loops.Record(seconds)
    rec.profiler = profiler
    dep.cell.kind.window(dep, seconds, rec, rate)
    if profiler is not None:
        profiler.stop(rec.spans)
    return rec


class Watch:
    """Programs lowered and full collections of Python's collector, with
    their host times, while it is on."""

    def __init__(self):
        self.lowered: List[float] = []
        self.full_gc: List[tuple] = []
        self.on = False
        self._gc_start = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kwargs):
        if self.on and event == LOWERING_EVENT:
            self.lowered.append(loops.now())

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_start = loops.now()
        elif info["generation"] == 2:
            self.full_gc.append((self._gc_start, loops.now()))

    def __enter__(self):
        self.on = True
        gc.callbacks.append(self._gc)
        return self

    def __exit__(self, *exc):
        self.on = False
        gc.callbacks.remove(self._gc)


_watch: Optional[Watch] = None


def watch() -> Watch:
    """The process's one ``Watch`` (JAX's listeners cannot be removed)."""
    global _watch
    if _watch is None:
        _watch = Watch()
    _watch.lowered.clear()
    _watch.full_gc.clear()
    return _watch


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def answered(dep: Deployment, rec: loops.Record):
    """Pool rows of the frames answered in the window (for a stream: all of
    its frames that were answered), and where each one's answer lies in
    ``rec.logits``: its call's index and its offset in that call."""
    rows, where = [], []
    in_window = not dep.cell.kind.ANSWERS_AFTER_CLOSE_COUNT
    for call in rec.calls:
        if not call.get("ok") or (in_window and call["done"] > rec.end):
            continue
        offsets = np.arange(call["n"])
        rows.append(call["first"] + offsets)
        where.append(np.stack([np.full(call["n"], call["index"]), offsets], 1))
    if not rows:
        return np.zeros(0, int), np.zeros((0, 2), int)
    return np.concatenate(rows), np.concatenate(where)


def sample(dep: Deployment, rows: np.ndarray):
    """Indices into the answered frames of the sample to compare, drawn
    from the seed."""
    k = min(dep.cell.mix["sample_frames"], len(rows))
    return np.sort(dep.sample_rng.choice(len(rows), size=k, replace=False))


def gather(rec: loops.Record, where: np.ndarray) -> np.ndarray:
    """The answers at ``where`` (rows of call index and offset), copied out
    of ``rec.logits`` one by one: no copy of the window's other answers."""
    return np.stack([rec.logits[call][offset] for call, offset in where])


def compare(dep: Deployment, rec: loops.Record, control: bool = False) -> Dict:
    """The numbers compared, against the reference, for the sampled
    answers; with ``control`` also the control's numbers on the same
    frames."""
    cell = dep.cell
    parts = cell.cfg["check"].get("parts")
    rows, where = answered(dep, rec)
    if not len(rows):
        return {"numbers": {}, "frames": 0}
    pick = sample(dep, rows)
    frames = dep.pool[rows[pick]]
    ref = cell.model.reference_fn(cell.cfg)
    want = check.in_blocks(lambda x: ref(dep.params, x), frames)
    out = {"numbers": check.numbers(gather(rec, where[pick]), want, parts),
           "frames": len(pick)}
    if control:
        qmax = cell.cfg["check"]["control_qmax"]
        ctl = cell.model.control_fn(cell.cfg, qmax)
        got = check.in_blocks(lambda x: ctl(dep.params, dep.calib, x), frames)
        out["control"] = check.numbers(got, want, parts)
    return out


# ---------------------------------------------------------------------------
# a whole run
# ---------------------------------------------------------------------------

def frames_counts(rec: loops.Record):
    attempted = sum(c["n"] for c in rec.calls)
    failed = sum(c["n"] for c in rec.calls if not c.get("ok"))
    return attempted, failed


def window_health(rec: loops.Record, w: Watch) -> Dict:
    """What else happened between the window's start and its last answer:
    programs lowered (none, where set-up warmed every shape), full passes
    of Python's collector, and the slowest call with its wall and CPU
    seconds (a stall the host worked through, or one it waited out)."""
    closed = max([rec.end] + [c["done"] for c in rec.calls if "done" in c])
    inside = [(s, e) for s, e in w.full_gc if rec.t0 <= s <= closed]
    out = {"lowered": sum(1 for t in w.lowered if rec.t0 <= t <= closed),
           "full_gc": [len(inside), sum(e - s for s, e in inside)]}
    timed = [c for c in rec.calls if "dispatch_s" in c]
    if timed:
        c = max(timed, key=lambda c: c["dispatch_s"] + c.get("fetch_s", 0.0))
        out["slowest_call"] = {
            "at_s": c["dispatch"] - rec.t0, "dispatch_s": c["dispatch_s"],
            "dispatch_cpu_s": c["dispatch_cpu_s"],
            "fetch_s": c.get("fetch_s")}
    return out


def memory_peak(devices: List) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def read_metrics(run: Run, metrics: List[Dict]) -> Dict:
    out = {}
    for m in metrics:
        value = reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_start: float, devices: List, peaks: Dict) -> Dict:
    """Set up, measure, read the metrics, compare; returns the result."""
    dep = Deployment(cell, seed)
    tmp = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    try:
        profiler = None
        if traced:
            length = min(TRACE_SECONDS, seconds / 2)
            profiler = loops.Profiler(tmp, (seconds - length) / 2, length)
        with watch() as w:
            rec = window(dep, seconds, profiler)
        health = window_health(rec, w)
        setup_s = rec.t0 - t_start
        mem = memory_peak(devices[:cell.chips])
        summary = None
        if traced:
            files = sorted(Path(tmp).rglob("*.xplane.pb"))
            if files:
                summary = xplane.summarize(xplane.read(files[-1]))
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    run = Run(cell, rec, setup_s, peaks, summary)
    metrics = read_metrics(run, cell.per_layer if traced else cell.end_to_end)
    extra = {"window": health}
    if traced:
        extra["end_to_end_of_traced_run"] = read_metrics(run, cell.end_to_end)
        extra["traced_rate_over_untraced"] = untraced.slowdown(run)
    loops.report_errors(rec)
    attempted, failed = frames_counts(rec)
    dep.serve = None            # the program's state goes before the reference
    cmp = compare(dep, rec)
    verdict = check.verdict(cmp["numbers"], cell.cfg["check"]["limits"])
    table = dict(verdict["numbers"])
    table["failed"] = {"value": failed, "limit": 0}
    table["lowered_in_window"] = {"value": health["lowered"], "limit": 0}
    correct = verdict["correct"] and failed == 0 and health["lowered"] == 0
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if traced and summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["setup_phases_s"] = dict(dep.phases)
    result["sample_frames"] = cmp["frames"]
    result.update(extra)
    result["check"] = table
    for name, row in table.items():
        print(f"check {name} {row['value']} limit {row['limit']}",
              file=sys.stderr)
    return result
