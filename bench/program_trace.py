"""The program's own spans in a profiler trace, tied to the device programs
they launched by run id.

The program (``repro.obs``) writes each span it records into the trace as a
``TraceAnnotation`` named after the span, with the graph node as the stat
``node``.  The runtime's host events tie every device program to the host
code that launched it:

* on the device plane, each ``XLA Modules`` event carries a ``run_id``;
* on a host thread, ``DoEnqueueProgram`` carries the same ``run_id``, inside
  a ``tpu::System::Execute=>IssueSequencedEvent`` whose flow id (``_c``)
  is that (``_p``) of the ``tpu::System::Execute`` made on the launching
  thread, inside its ``PJRT_LoadedExecutable_Execute``.

The launch is put at that ``tpu::System::Execute`` (the enqueue itself may
run later, on a worker thread), or at the enqueue where the flow is
missing.  The innermost program span open at the launch owns the program's
device time.

Host and device clocks disagree by more than one eager launch: a module
can read as starting before its own enqueue.  ``clock_offset_us`` is the
shift of device times that puts at least 99% of linked modules at or after
the start of their enqueue; idle gaps are placed on that corrected clock.
Device busy time is placed by run id and needs no correction: it is
computed on the device's own clock, as ``xplane.summarize`` computes it, so
``device_by_phase`` sums to its ``busy_s``.

The names of the program's spans come from the program (``obs.SPAN_NAMES``),
passed in by the caller; this module keeps no list of its own.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import xplane

LAUNCH = "PJRT_LoadedExecutable_Execute"
PUT = "DevicePut"
ENQUEUE = "DoEnqueueProgram"
ISSUE = "tpu::System::Execute=>IssueSequencedEvent"
SYSTEM_EXECUTE = "tpu::System::Execute"
CALL_SPAN = "execute"           # the program's span around one call
OUTSIDE = "outside_program"     # launched outside every program span
UNLINKED = "unlinked"           # no enqueue in the trace, or no module
OFFSET_SHARE = 0.99


class Spans:
    """Nested host spans ``(name, start, end, node)``, ns, with the
    innermost one open at a time found by bisection."""

    def __init__(self, rows: List[Tuple[str, int, int, Optional[str]]]):
        self.rows = sorted(rows, key=lambda r: (r[1], -r[2]))
        self.starts = [r[1] for r in self.rows]
        self.parent = []
        stack: List[int] = []
        for i, (_, s, e, _) in enumerate(self.rows):
            while stack and self.rows[stack[-1]][2] < s:
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            stack.append(i)

    def innermost(self, t: int) -> int:
        """Index of the innermost span open at ``t``, or -1."""
        j = bisect.bisect_right(self.starts, t) - 1
        while j >= 0 and self.rows[j][2] < t:
            j = self.parent[j]
        return j

    def outermost(self, t: int) -> int:
        j = self.innermost(t)
        while j >= 0 and self.parent[j] >= 0:
            j = self.parent[j]
        return j


def _stats(ev) -> Dict:
    return dict(ev.stats)


def host_events(pd, span_names: Sequence[str]) -> Dict:
    """The host events the link needs, in one pass over the host planes."""
    names = set(span_names)
    out = {"spans": [], "launches": [], "puts": [], "enqueues": [],
           "issues": defaultdict(list), "system": {}}
    for pi, plane in enumerate(pd.planes):
        if not plane.name.startswith("/host"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                n = ev.name
                if n in names:
                    s = int(ev.start_ns)
                    out["spans"].append((n, s, s + int(ev.duration_ns),
                                         _stats(ev).get("node")))
                elif n == LAUNCH:
                    out["launches"].append(int(ev.start_ns))
                elif n == PUT:
                    out["puts"].append(int(ev.start_ns))
                elif n == ENQUEUE:
                    st = _stats(ev)
                    out["enqueues"].append((st.get("run_id"), int(ev.start_ns),
                                            (pi, li)))
                elif n == ISSUE:
                    s = int(ev.start_ns)
                    out["issues"][(pi, li)].append(
                        (s, s + int(ev.duration_ns), _stats(ev).get("_c")))
                elif n == SYSTEM_EXECUTE:
                    st = _stats(ev)
                    if "_p" in st:
                        out["system"][st["_p"]] = int(ev.start_ns)
    return out


def launch_times(ev: Dict) -> Dict[int, Tuple[int, int]]:
    """``run_id -> (launch, enqueue start)`` on the host clock, ns."""
    issues = {k: sorted(v) for k, v in ev["issues"].items()}
    starts = {k: [s for s, _, _ in v] for k, v in issues.items()}
    out = {}
    for run_id, t, line in ev["enqueues"]:
        launch = t
        rows = issues.get(line)
        if rows:
            j = bisect.bisect_right(starts[line], t) - 1
            if j >= 0 and rows[j][1] >= t and rows[j][2] in ev["system"]:
                launch = ev["system"][rows[j][2]]
        out[run_id] = (launch, t)
    return out


def device_modules(pd) -> Dict[int, List[Tuple[int, int, Optional[int]]]]:
    """Per chip, ``XLA Modules`` events as ``(start, end, run_id)``, ns."""
    out: Dict[int, list] = {}
    for plane in pd.planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        mods = out.setdefault(int(m.group(1)), [])
        for line in plane.lines:
            if line.name != xplane.MODULES_LINE:
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                mods.append((s, s + int(ev.duration_ns),
                             _stats(ev).get("run_id")))
        mods.sort()
    return out


def clock_offset(mods, launches) -> Tuple[int, int, int]:
    """``(offset ns, modules linked, modules)``: the offset is the least
    shift of device times that puts ``OFFSET_SHARE`` of the linked modules
    at or after the start of their enqueue."""
    lead = [launches[r][1] - s for s, _, r in mods if r in launches]
    if not lead:
        return 0, 0, len(mods)
    offset = int(np.quantile(np.array(lead), OFFSET_SHARE, method="higher"))
    return offset, len(lead), len(mods)


def _rows(d: Dict[str, float], top: Optional[int] = None) -> List[list]:
    rows = sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])
    return rows[:top] if top else rows


def summarize(pd, span_names: Sequence[str], top: int = 10) -> Optional[Dict]:
    """Launches and puts per call, the clock offset, and device busy and
    idle time by program span, over ``xplane.summarize``'s window; None
    where the trace holds no benchmark span, no device operation or no
    program span named in ``span_names``."""
    bench_spans = xplane.host_spans(pd)
    chips = xplane.device_events(pd)
    ev = host_events(pd, span_names)
    if not bench_spans or not ev["spans"] or not chips \
            or not any(c["ops"] for c in chips.values()):
        return None
    lo = min(s for _, s, _ in bench_spans)
    hi = max(e for _, _, e in bench_spans)
    spans = Spans(ev["spans"])
    calls = [i for i, r in enumerate(spans.rows) if r[0] == CALL_SPAN]

    def in_call(t):
        j = spans.outermost(t)
        return j >= 0 and spans.rows[j][0] == CALL_SPAN

    def phase_of(t):
        j = spans.innermost(t)
        return (spans.rows[j][0], spans.rows[j][3]) if j >= 0 else (OUTSIDE, None)

    launches = [t for t in ev["launches"] if in_call(t)]
    puts = [t for t in ev["puts"] if in_call(t)]
    launch_phase: Dict[str, int] = defaultdict(int)
    put_phase: Dict[str, int] = defaultdict(int)
    for t in launches:
        launch_phase[phase_of(t)[0]] += 1
    for t in puts:
        put_phase[phase_of(t)[0]] += 1

    linked_at = launch_times(ev)
    modules = device_modules(pd)
    in_window = [m for c in modules.values() for m in c if lo <= m[0] <= hi]
    offset, linked, n_mods = clock_offset(in_window, linked_at)

    by_phase: Dict[str, float] = defaultdict(float)
    by_node: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[int, int]] = []
    for chip, c in chips.items():
        mods, mi, covered = modules.get(chip, []), 0, lo
        ops = [(max(s, lo), min(e, hi)) for s, e, _ in c["ops"]
               if e > lo and s < hi]
        for s, e in ops:
            while mi < len(mods) and mods[mi][1] < s:
                mi += 1
            phase = node = UNLINKED
            if mi < len(mods) and mods[mi][0] <= s and mods[mi][2] in linked_at:
                phase, node = phase_of(linked_at[mods[mi][2]][0])
                node = node or phase
            part = e - max(s, covered)
            if part > 0:
                by_phase[phase] += part * 1e-9 / len(chips)
                by_node[node] += part * 1e-9 / len(chips)
            covered = max(covered, e)
        shifted = [(max(s + offset, lo), min(e + offset, hi))
                   for s, e, _ in c["ops"] if e + offset > lo and s + offset < hi]
        prev = lo
        for s, e in xplane.merged(shifted):
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if hi > prev:
            gaps.append((prev, hi))

    idle: Dict[str, List[float]] = defaultdict(list)
    for s, e in gaps:
        mid = (s + e) // 2
        j = spans.innermost(mid)
        name = spans.rows[j][0] if j >= 0 else xplane.span_at(bench_spans, mid)
        idle[name].append((e - s) * 1e-9 / len(chips))
    idle_rows = _rows({f"{k}: {len(v)} gaps, longest {max(v):.6f} s": sum(v)
                       for k, v in idle.items()}, top)
    n_calls = max(len(calls), 1)
    return {
        "launches": len(launches),
        "puts": len(puts),
        "execute_spans": len(calls),
        "launches_by_phase": _rows({k: v / n_calls
                                    for k, v in launch_phase.items()}),
        "puts_by_phase": _rows({k: v / n_calls for k, v in put_phase.items()}),
        "clock_offset_us": offset * 1e-3,
        "modules_linked": linked / n_mods if n_mods else 0.0,
        "device_by_phase": _rows(by_phase),
        "device_by_node": _rows(by_node, top),
        "idle_by_phase": idle_rows,
    }
