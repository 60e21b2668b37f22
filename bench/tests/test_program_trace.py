"""The run-id link between program spans and device programs
(``program_trace.py``), on a made-up trace and on a trace recorded on a TPU
v5 lite with the program's spans on (``profile_program.py --workload
resnet8.offline --traffic stream_resnet8 --seed 7 --seconds 1
--trace-seconds 0.5``)."""

from pathlib import Path

import pytest
from repro.obs import SPAN_NAMES

from bench import program_trace, xplane

DATA = Path(__file__).resolve().parent / "data"


class Ev:
    def __init__(self, name, start, end, **stats):
        self.name, self.start_ns, self.duration_ns = name, start, end - start
        self.stats = list(stats.items())


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class Trace:
    def __init__(self, planes):
        self.planes = planes


def made_up():
    """One call of two nodes: node a launches from quant.weight (run 11)
    and int8.acc (run 12), node b from its own span (run 13, enqueued on
    the launching thread); the device clock reads 100-105 ns behind."""
    python = Line("python3", [
        Ev("dispatch", 0, 1000), Ev("fetch", 1000, 1100),
        Ev("execute", 10, 950),
        Ev("node", 20, 500, node="a"),
        Ev("quant.weight", 30, 200, node="a"),
        Ev("DevicePut", 40, 45),
        Ev("int8.acc", 200, 400, node="a"),
        Ev("node", 500, 890, node="b"),
        Ev("DevicePut", 600, 601),
    ])
    main = Line("main", [
        Ev("PJRT_LoadedExecutable_Execute", 50, 60),
        Ev("tpu::System::Execute", 55, 58, _p=1),
        Ev("PJRT_LoadedExecutable_Execute", 250, 260),
        Ev("tpu::System::Execute", 255, 258, _p=2),
        Ev("PJRT_LoadedExecutable_Execute", 690, 710),
        Ev("DoEnqueueProgram", 700, 705, run_id=13),
    ])
    worker = Line("worker", [
        Ev("tpu::System::Execute=>IssueSequencedEvent", 300, 320, _c=1),
        Ev("DoEnqueueProgram", 305, 310, run_id=11),
        Ev("tpu::System::Execute=>IssueSequencedEvent", 330, 340, _c=2),
        Ev("DoEnqueueProgram", 332, 336, run_id=12),
    ])
    device = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_a", 200, 260, run_id=11),
                             Ev("jit_b", 262, 300, run_id=12),
                             Ev("jit_c", 600, 640, run_id=13)]),
        Line("XLA Ops", [Ev("%x = f32[1] add(a, b)", 200, 260),
                         Ev("%y = s32[1] convolution(a, b)", 262, 300),
                         Ev("%z = f32[1] add(a, b)", 600, 640),
                         Ev("%w = f32[1] copy(a)", 950, 960)]),
    ])
    return Trace([Plane("/host:CPU", [python, main, worker]), device])


@pytest.fixture(scope="module")
def fake():
    return program_trace.summarize(made_up(), SPAN_NAMES)


def test_launches_and_puts_inside_calls(fake):
    assert (fake["launches"], fake["puts"], fake["execute_spans"]) == (3, 2, 1)
    assert dict(fake["launches_by_phase"]) == {"quant.weight": 1, "int8.acc": 1,
                                               "node": 1}
    assert dict(fake["puts_by_phase"]) == {"quant.weight": 1, "node": 1}


def test_clock_offset_puts_modules_after_their_enqueue(fake):
    # leads of the enqueues over the modules: 105, 70 and 100 ns
    assert fake["clock_offset_us"] == pytest.approx(0.105)
    assert fake["modules_linked"] == 1.0


def test_device_time_follows_the_launch_not_the_clock(fake):
    # run 11 was enqueued while int8.acc ran, but launched from quant.weight
    assert dict(fake["device_by_phase"]) == pytest.approx(
        {"quant.weight": 60e-9, "int8.acc": 38e-9, "node": 40e-9,
         "unlinked": 10e-9})
    assert dict(fake["device_by_node"]) == pytest.approx(
        {"a": 98e-9, "b": 40e-9, "unlinked": 10e-9})
    busy = xplane.summarize(made_up())["busy_s"]
    assert sum(v for _, v in fake["device_by_phase"]) == pytest.approx(busy)


def test_idle_gaps_on_the_corrected_clock(fake):
    # device ops shifted by 105 ns: gaps at 0-305, 365-367, 405-705,
    # 745-1055 and 1065-1100, put down by their middles
    idle = {k.split(":")[0]: v for k, v in fake["idle_by_phase"]}
    assert idle == pytest.approx({"quant.weight": 305e-9, "int8.acc": 2e-9,
                                  "node": 300e-9, "execute": 310e-9,
                                  "fetch": 35e-9})


def test_innermost_span():
    spans = program_trace.Spans([("execute", 0, 100, None),
                                 ("node", 10, 50, "a"),
                                 ("quant.act", 12, 20, "a"),
                                 ("node", 60, 90, "b")])
    name = [r[0] for r in spans.rows]
    assert name[spans.innermost(15)] == "quant.act"
    assert name[spans.innermost(30)] == "node"
    assert name[spans.innermost(55)] == "execute"
    assert spans.innermost(150) == -1
    assert name[spans.outermost(15)] == "execute"


def test_no_program_spans_no_summary():
    old = xplane.read(DATA / "resnet8_stream.xplane.pb")
    assert xplane.summarize(old) is not None
    assert program_trace.summarize(old, SPAN_NAMES) is None
    assert program_trace.summarize(Trace([]), SPAN_NAMES) is None


@pytest.fixture(scope="module")
def recorded():
    pd = xplane.read(DATA / "resnet8_stream_spans.xplane.pb")
    return pd, xplane.summarize(pd), program_trace.summarize(pd, SPAN_NAMES)


def test_recorded_modules_are_linked(recorded):
    _, _, p = recorded
    assert p["modules_linked"] >= 0.99


def test_recorded_offset_puts_99_percent_after_their_enqueue(recorded):
    pd, s, p = recorded
    at = program_trace.launch_times(program_trace.host_events(pd, SPAN_NAMES))
    spans = xplane.host_spans(pd)
    lo, hi = spans[0][1], max(e for _, _, e in spans)
    mods = [(st, r) for c in program_trace.device_modules(pd).values()
            for st, _, r in c if lo <= st <= hi and r in at]
    offset = p["clock_offset_us"] * 1e3
    assert offset > 0

    def after(shift):
        return sum(st + shift >= at[r][1] for st, r in mods) / len(mods)

    assert after(0) < 0.5                   # uncorrected, most read early
    assert after(offset) >= 0.99
    assert after(offset - 1) < 0.99         # and no less shift does


def test_recorded_device_time_is_all_put_down(recorded):
    _, s, p = recorded
    by_phase = dict(p["device_by_phase"])
    assert sum(by_phase.values()) == pytest.approx(s["busy_s"], rel=0.01)
    assert set(by_phase) <= set(SPAN_NAMES)
    idle = sum(v for _, v in p["idle_by_phase"])
    assert idle + s["busy_s"] == pytest.approx(s["window_s"], rel=0.01)
    nodes = [k for k, _ in p["device_by_node"]]
    assert nodes[:3] == ["s0b0.conv1", "s0b0.conv2", "stem"]


def test_recorded_launches_per_call(recorded):
    pd, s, p = recorded
    # ResNet-8 at batch 1: 224 programs and 72 host-to-device puts a call,
    # in every call the trace holds whole
    assert p["execute_spans"] == s["fetches"] == 5
    assert p["launches"] == 224 * p["execute_spans"]
    assert p["puts"] == 72 * p["execute_spans"]
    ev = program_trace.host_events(pd, SPAN_NAMES)
    calls = [(a, b) for n, a, b, _ in ev["spans"] if n == "execute"]
    assert [sum(a <= t <= b for t in ev["launches"]) for a, b in calls] == \
        [224] * len(calls)
    assert dict(p["launches_by_phase"]) == {
        "quant.weight": 90, "quant.act": 53, "dequant": 40, "int8.acc": 30,
        "node": 11}
    assert dict(p["puts_by_phase"]) == {"quant.weight": 40, "quant.act": 32}


def test_recorded_pinned_readings(recorded):
    # the reduction is deterministic: these change only with its code
    _, _, p = recorded
    assert p["clock_offset_us"] == pytest.approx(1200.367)
    assert dict(p["device_by_phase"])["quant.weight"] == \
        pytest.approx(0.000315041)


def test_profile_on_the_cpu_records_the_program_spans():
    # the CPU trace has no device plane: only the host readings remain
    from conftest import small_cell

    from bench import profile_program
    res = profile_program.profile(small_cell("resnet8.offline"), seed=5,
                                  seconds=2.0, trace_seconds=0.5, pairs=1,
                                  pair_seconds=0.5)
    assert [p["recording"] for p in res["pairs"]] == [False, True]
    assert all(p["dispatch_ms"] > 0 for p in res["pairs"])
    # a warm window runs the compiled program: nothing is traced, and the
    # weights are re-quantised inside it, where no counter sees them
    assert res["per_call"] == {"execute.frames": 4.0, "execute.traces": 0.0,
                               "quant.weight.tensors": 0.0}
    assert res["execute_spans"] > 0
    share = res["host_share_untraced"]
    assert set(share) == set(SPAN_NAMES)
    assert sum(share.values()) == pytest.approx(100.0, rel=1e-6)
    assert res["slowest_span"]["wall_s"] > 0
    assert res["window"]["lowered"] == 0
    assert res["trace"] is None and res["program_trace"] is None
