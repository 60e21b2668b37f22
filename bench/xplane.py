"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

On a TPU the trace has one plane per chip, ``/device:TPU:<n>``, whose line
``XLA Ops`` holds one event per HLO operation run on the TensorCore (its
name is the operation's HLO text) and whose line ``XLA Modules`` holds one
event per program run.  The host planes hold the benchmark's own spans,
written with ``jax.profiler.TraceAnnotation`` (see ``loops.py``), on the
same clock.

The traced window runs from the start of the first of those spans to the
end of the last.  Within it:

* busy time is the union of the intervals of the device's operations,
  averaged over the chips used;
* conv time is the summed duration of the operations that
  ``conv_ops.json`` counts as convolutions;
* idle gaps are the stretches between busy intervals, each put down to the
  benchmark span that covers its middle; they are reported summed by span,
  with their number and the longest, since an eager program leaves one
  short gap per operation it launches.
"""

from __future__ import annotations

import json
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_NAMES = ("wait_arrival", "dispatch", "fetch", "record",
              "trace_start", "trace_stop")


def conv_patterns() -> Dict[str, List[str]]:
    spec = json.loads((HERE / "conv_ops.json").read_text())
    return {k: spec[k] for k in ("instruction_patterns", "fusion_kinds")}


def is_conv(op_text: str, patterns: Dict[str, List[str]]) -> bool:
    """Whether an ``XLA Ops`` event computes a convolution, by its HLO
    instruction's name or its fusion kind."""
    head = op_text.partition(" = ")[0]
    if any(p in head for p in patterns["instruction_patterns"]):
        return True
    kind = re.search(r"kind=(\w+)", op_text)
    return bool(kind) and kind.group(1) in patterns["fusion_kinds"]


def op_label(op_text: str, module: str) -> str:
    """A stable short name: the program, the HLO instruction without its
    number, and its result type, e.g.
    ``jit_conv_general_dilated/fusion s32[256,32,32,32]``."""
    head, _, rest = op_text.partition(" = ")
    inst = re.sub(r"\.\d+$", "", head.lstrip("%"))
    result = rest.split("{")[0].split(" ")[0] if rest else ""
    prog = re.sub(r"\(\d+\)$", "", module)
    return f"{prog}/{inst} {result}".strip()


def union_length(intervals: List[Tuple[int, int]]) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def merged(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def read(path: Path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def host_spans(pd) -> List[Tuple[str, int, int]]:
    """The benchmark's own spans in the trace, ``(name, start, end)`` ns."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in SPAN_NAMES:
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns)))
    return sorted(out, key=lambda r: r[1])


def device_events(pd) -> Dict[int, Dict[str, list]]:
    """Per chip: ``ops`` as ``(start, end, text)`` and ``modules`` as
    ``(start, end, name)``, in ns."""
    out: Dict[int, Dict[str, list]] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        chip = out.setdefault(int(m.group(1)), {"ops": [], "modules": []})
        for line in plane.lines:
            key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
            if key is None:
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                chip[key].append((s, s + int(ev.duration_ns), ev.name))
    for chip in out.values():
        chip["ops"].sort()
        chip["modules"].sort()
    return out


def summarize(pd, patterns: Optional[Dict[str, List[str]]] = None,
              top: int = 10) -> Optional[Dict]:
    """Busy, conv and idle time of the traced window, or None where the
    trace holds no benchmark span or no device operation."""
    patterns = conv_patterns() if patterns is None else patterns
    spans = host_spans(pd)
    chips = device_events(pd)
    if not spans or not chips or not any(c["ops"] for c in chips.values()):
        return None
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    window = hi - lo
    busy, conv, conv_n = [], 0, 0
    by_label: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[int, int]] = []
    for chip in chips.values():
        ops = [(max(s, lo), min(e, hi), t) for s, e, t in chip["ops"]
               if e > lo and s < hi]
        busy.append(union_length([(s, e) for s, e, _ in ops]))
        mods, mi = chip["modules"], 0
        for s, e, text in ops:
            while mi < len(mods) and mods[mi][1] < s:
                mi += 1
            module = mods[mi][2] if mi < len(mods) and mods[mi][0] <= s else ""
            by_label[op_label(text, module)] += (e - s) * 1e-9
            if is_conv(text, patterns):
                conv += e - s
                conv_n += 1
        prev = lo
        for s, e in merged([(s, e) for s, e, _ in ops]):
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if hi > prev:
            gaps.append((prev, hi))
    n_chips = len(chips)
    idle: Dict[str, List[float]] = defaultdict(list)
    for s, e in gaps:
        idle[span_at(spans, (s + e) // 2)].append((e - s) * 1e-9)
    idle_rows = sorted(([f"{k}: {len(v)} gaps, longest {max(v):.6f} s",
                         sum(v) / n_chips] for k, v in idle.items()),
                       key=lambda kv: -kv[1])
    fetches = sum(1 for name, _, e in spans if name == "fetch" and lo <= e <= hi)
    return {
        "window_s": window * 1e-9,
        "busy_s": sum(busy) / n_chips * 1e-9,
        "conv_s": conv / n_chips * 1e-9,
        "conv_ops": conv_n,
        "chips": n_chips,
        "fetches": fetches,
        "device_ops": sorted(([k, v] for k, v in by_label.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": idle_rows[:top],
    }


def span_at(spans: List[Tuple[str, int, int]], t: int) -> str:
    """The name of the last benchmark span that started at or before ``t``
    and ends after it, else ``between_spans``."""
    name = "between_spans"
    for n, s, e in spans:
        if s > t:
            break
        if e >= t:
            name = n
    return name
