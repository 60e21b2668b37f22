"""What the measured window records: its calls, answers and host spans.

The loop of each traffic kind (``bench/kinds/<kind>.py``) calls
``serve(frames)`` (the program's entry) through ``Record.dispatch`` and
fetches the logits to the host with ``np.asarray`` through
``Record.finish``.  Around every step it records a host span, named

* ``wait_arrival``: sleeping until a stream frame's scheduled arrival;
* ``dispatch``: the ``serve`` call, until it returns (the enqueue);
* ``fetch``: from then until the logits are on the host;
* ``record``: checking and storing them;
* ``trace_start`` / ``trace_stop``: turning the profiler on and off.

The spans go to a list on the host clock, and, while the profiler is on, to
its trace as well, so that device idle time can be put down to them.
"""

from __future__ import annotations

import sys
import time
import traceback
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

now = time.perf_counter


class Spans:
    """Host spans ``(name, start, end)`` on ``time.perf_counter``."""

    def __init__(self):
        self.rows: List[tuple] = []
        self.annotate = False       # also write each span into the trace

    def __call__(self, name: str):
        return _Span(self, name)


class _Span:
    __slots__ = ("spans", "name", "t", "ann")

    def __init__(self, spans, name):
        self.spans, self.name = spans, name

    def __enter__(self):
        self.ann = None
        if self.spans.annotate:
            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
        self.t = now()

    def __exit__(self, *exc):
        end = now()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.spans.rows.append((self.name, self.t, end))


class Profiler:
    """Turns the profiler on ``lead`` seconds into the window and records
    ``length`` seconds.  ``on``/``off`` are the host times from the call
    that starts it to the return of the one that stops it: the part of the
    window the profiler slowed, stopping included (collecting the trace
    holds the loop for seconds)."""

    def __init__(self, log_dir: str, lead: float, length: float):
        self.log_dir, self.lead, self.length = log_dir, lead, length
        self.on: Optional[float] = None
        self.off: Optional[float] = None
        self._recording = 0.0

    def step(self, t0: float, spans: Spans) -> None:
        t = now()
        if self.on is None and t >= t0 + self.lead:
            self.on = t
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # Python calls: costly, not read
            opts.host_tracer_level = 1      # the benchmark's spans, not the runtime's
            with spans("trace_start"):
                jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self._recording = now()
            spans.annotate = True
        elif self.on is not None and self.off is None \
                and t >= self._recording + self.length:
            self.stop(spans)

    def stop(self, spans: Spans) -> None:
        if self.on is not None and self.off is None:
            spans.annotate = False
            with spans("trace_stop"):
                jax.profiler.stop_trace()
            self.off = now()


class Record:
    """What one window did: its calls, their answers and its spans."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.t0 = self.end = 0.0
        self.spans = Spans()
        self.calls: List[Dict] = []     # one per serve call, in order
        self.logits: Dict[int, np.ndarray] = {}   # call index -> logits
        self.errors: List[str] = []
        self.profiler: Optional[Profiler] = None

    def step_profiler(self):
        if self.profiler is not None:
            self.profiler.step(self.t0, self.spans)

    def finish(self, call: Dict, out) -> None:
        """Fetch a call's logits to the host and check that they are whole."""
        spans = self.spans
        try:
            t = now()
            with spans("fetch"):
                logits = np.asarray(out)
            call["done"] = now()
            call["fetch_s"] = call["done"] - t
            with spans("record"):
                ok = (logits.shape[0] == call["n"]
                      and bool(np.isfinite(logits).all()))
                if ok:
                    self.logits[call["index"]] = logits
                else:
                    self.errors.append(
                        f"call {call['index']}: logits of shape "
                        f"{logits.shape} or not finite")
        except Exception:  # noqa: BLE001 -- a failed call is counted, not fatal
            call["done"] = now()
            self.errors.append(traceback.format_exc())
        call["ok"] = call["index"] in self.logits

    def dispatch(self, serve: Callable, call: Dict, frames: np.ndarray):
        """Call ``serve``; ``call`` gets the host's wall and this thread's
        CPU seconds for it, which tell work on the host from waiting."""
        call["dispatch"] = now()
        cpu = time.thread_time()
        try:
            with self.spans("dispatch"):
                out = serve(frames)
        except Exception:  # noqa: BLE001
            self.errors.append(traceback.format_exc())
            out = None
        call["dispatch_s"] = now() - call["dispatch"]
        call["dispatch_cpu_s"] = time.thread_time() - cpu
        self.calls.append(call)
        return out

    def fail(self, call: Dict) -> None:
        call["done"], call["ok"] = now(), False


def report_errors(rec: Record, limit: int = 3) -> None:
    for err in rec.errors[:limit]:
        print(err, file=sys.stderr)
