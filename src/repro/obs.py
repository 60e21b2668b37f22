"""Spans and counters of the served program, recorded only when asked.

Off is the default and the served path's state: ``span`` then returns one
shared null context after a single check of the module's flag, and
``count`` returns after the same check.  Nothing is recorded and nothing is
allocated.  Only ``recording()`` turns them on, for the duration of its
``with`` block::

    with obs.recording() as rec:
        executor.execute(graph, params, x, mode="int8", act_scales=scales)
    rec.rows()          # one row per span
    rec.counters        # {"execute.frames": ..., "quant.weight.tensors": ...}

While on, each span keeps its id, its parent's id, the id of the
``execute`` span it belongs to, its name, node, kind and batch, and its
start and end on ``time.perf_counter``.  An ``execute`` span also keeps
its start and end on ``time.thread_time``, so that a call's waiting can be
told from its work; a phase lasts about a millisecond or less, below what
a thread clock that ticks every 10 ms (as on a TPU v5e host) can tell,
and reading that clock is a system call.  While a profiler records, each
span also enters ``jax.profiler.TraceAnnotation`` with its name and node,
so that it sits on the trace's host plane, on the device trace's clock,
where the device programs it launched can be tied to it by run id.  Rows
live in numeric columns (``array.array``), not in one Python object per
span, so a long recording creates no objects for the collector to scan.

The names a span may take are ``SPAN_NAMES``; the counters are
``COUNTER_NAMES``.  Spans record the Python that runs: inside a function
that ``jax.jit`` is tracing they time the tracing, once, not the compiled
program, so only an eager call is measured by them.  Spans nest on one
thread; record one thread at a time.
"""

from __future__ import annotations

import contextlib
import time
from array import array
from typing import Dict, Iterator, List, Optional

import numpy as np

#: span names, outermost first: one ``execute`` per executor call, one
#: ``node`` per graph node, and the phases of an int8 conv or dense node;
#: then ``calibrate``, one per ``quant.calibrate_graph`` (set-up)
SPAN_NAMES = ("execute", "node", "quant.act", "quant.weight", "int8.acc",
              "dequant", "calibrate")
#: frames served, executor programs traced, and weight tensors quantised
#: (per trace, inside a jitted program); activation scales calibrated; the
#: bytes of the executor's inputs and results; executor calls whose host
#: frames were put as a flat [rows, 128] view, and those of them laid out
#: channel planar in int8; grouped int8 convs and products of two
#: activations traced into a program; summed over the recording
COUNTER_NAMES = ("execute.frames", "execute.traces", "quant.weight.tensors",
                 "calibrate.scales", "execute.bytes_in", "execute.bytes_out",
                 "execute.flat_puts", "execute.planar_puts",
                 "quant.grouped_convs", "execute.act_products")

_NULL = contextlib.nullcontext()
_NAN = float("nan")
_active: Optional["Recording"] = None


class Recording:
    """Spans and counters recorded by one ``recording()`` block.

    Columns, one entry per span in the order the spans were entered:
    ``name`` (index into ``SPAN_NAMES``), ``parent`` and ``call`` (span
    ids, -1 for none), ``node`` and ``kind`` (indices into ``strings``,
    -1 for none), ``batch``, ``t0``/``t1`` (``time.perf_counter`` s) and,
    for ``execute`` spans, ``c0``/``c1`` (``time.thread_time`` s; NaN for
    the others).
    """

    INT_COLUMNS = ("name", "parent", "call", "node", "kind", "batch")
    FLOAT_COLUMNS = ("t0", "t1", "c0", "c1")

    def __init__(self):
        import jax.profiler
        self._annotation = jax.profiler.TraceAnnotation
        self.strings: List[str] = []
        self._codes: Dict[str, int] = {}
        self.counters: Dict[str, int] = dict.fromkeys(COUNTER_NAMES, 0)
        self._cols: Dict[str, array] = {k: array("q") for k in self.INT_COLUMNS}
        self._cols.update((k, array("d")) for k in self.FLOAT_COLUMNS)
        self._stack: List[int] = []         # open spans, innermost last
        self._anns: List = []               # their annotations, or None
        self._call = -1
        # what the next __enter__ records: set by span(), no tuple a span
        self._name, self._node, self._kind, self._batch = "", None, None, 0

    @property
    def n(self) -> int:
        """Spans recorded."""
        return len(self._cols["t0"])

    def _code(self, s: Optional[str]) -> int:
        if s is None:
            return -1
        code = self._codes.get(s)
        if code is None:
            code = self._codes[s] = len(self.strings)
            self.strings.append(s)
        return code

    # -- the span context: one object, reused by every span ---------------

    def __enter__(self):
        name, node, kind, batch = self._name, self._node, self._kind, self._batch
        code = SPAN_NAMES.index(name)
        c, stack = self._cols, self._stack
        i = len(c["t0"])
        parent = stack[-1] if stack else -1
        if node is None and parent >= 0:
            node = c["node"][parent]        # a phase belongs to its node
        else:
            node = self._code(node)
        cpu = _NAN
        if code == 0:
            self._call = i
            cpu = time.thread_time()
        c["name"].append(code)
        c["parent"].append(parent)
        c["call"].append(self._call)
        c["node"].append(node)
        c["kind"].append(self._code(kind))
        c["batch"].append(batch)
        c["c0"].append(cpu)
        c["c1"].append(_NAN)
        c["t1"].append(_NAN)
        stack.append(i)
        ann = None
        if self._annotation.is_enabled():   # a profiler is recording
            ann = self._annotation(name, node=self.strings[node]) \
                if node >= 0 else self._annotation(name)
            ann.__enter__()
        self._anns.append(ann)
        c["t0"].append(time.perf_counter())

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        i = self._stack.pop()
        ann = self._anns.pop()
        if ann is not None:
            ann.__exit__(*exc)
        c = self._cols
        c["t1"][i] = t1
        if i == self._call:
            c["c1"][i] = time.thread_time()
        if not self._stack:
            self._call = -1
        return False

    # -- reading -----------------------------------------------------------

    def rows(self) -> Dict[str, np.ndarray]:
        """The recorded columns as arrays, plus ``id``, ``dur`` (wall s),
        ``cpu`` (thread-CPU s of ``execute`` spans, NaN for the others)
        and ``self`` (wall s less the wall s of the span's children)."""
        out = {k: np.array(v, np.int64 if v.typecode == "q" else np.float64)
               for k, v in self._cols.items()}
        n = len(out["t0"])
        out["id"] = np.arange(n)
        out["dur"] = out["t1"] - out["t0"]
        out["cpu"] = out["c1"] - out["c0"]
        child = out["parent"] >= 0
        children = np.bincount(out["parent"][child],
                               weights=out["dur"][child], minlength=n)
        out["self"] = out["dur"] - children[:n]
        return out

    def label(self, code: int) -> Optional[str]:
        """The string of a ``node`` or ``kind`` code."""
        return self.strings[code] if code >= 0 else None


def span(name: str, node: Optional[str] = None, kind: Optional[str] = None,
         batch: int = 0):
    """A context manager timing the block as span ``name``.

    ``node`` is the graph node the span belongs to (a phase span inherits
    its enclosing span's), ``kind`` the node's kind (for ``execute``: the
    arithmetic mode), ``batch`` the frames of an ``execute`` call.  A
    ``calibrate`` span gives the graph's name as ``node``, the number of
    nodes it scales as ``kind`` ("<n> nodes") and its frames as ``batch``.
    """
    rec = _active
    if rec is None:
        return _NULL
    rec._name, rec._node, rec._kind, rec._batch = name, node, kind, batch
    return rec


def on() -> bool:
    """Whether a ``recording()`` is on: lets a caller skip work whose only
    use is a counter."""
    return _active is not None


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` while recording."""
    rec = _active
    if rec is not None:
        rec.counters[name] += n


@contextlib.contextmanager
def recording() -> Iterator[Recording]:
    """Turn spans and counters on for the block; yields what they record."""
    global _active
    if _active is not None:
        raise RuntimeError("obs.recording() is already on")
    rec = Recording()
    _active = rec
    try:
        yield rec
    finally:
        _active = None
