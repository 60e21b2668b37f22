"""The frame rate of the part of the window the profiler did not touch.

The profiler slows the host while it records, and stopping it holds the
loop while the trace is collected, so rates that stand for the program
are taken outside ``Profiler.on``..``off``; device times per frame are
taken inside it.
"""


def rate(run):
    """Frames answered inside the window but outside the profiled part,
    over the seconds of the window outside it; None where none were."""
    rec = run.record
    prof = rec.profiler
    on = off = rec.end + 1.0            # nothing profiled
    if prof is not None and prof.on is not None:
        on, off = prof.on, prof.off
    frames = sum(c["n"] for c in rec.calls
                 if c.get("ok") and rec.t0 <= c["done"] <= rec.end
                 and not on <= c["done"] <= off)
    seconds = rec.seconds - max(0.0, min(off, rec.end) - min(on, rec.end))
    if not frames or seconds <= 0:
        return None
    return frames / seconds


def traced_rate(run):
    """Frames answered in the profiled part over its seconds."""
    t = run.trace
    if not t or not t["fetches"] or t["window_s"] <= 0:
        return None
    return t["fetches"] * run.cell.batch / t["window_s"]


def slowdown(run):
    """The traced rate as a share of the untraced one."""
    a, b = traced_rate(run), rate(run)
    return None if a is None or b is None else a / b
