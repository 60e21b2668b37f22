"""dispatch_ms.<mix>: mean host time of one ``serve`` call until it returns
(the enqueue, before the fetch), over the window's calls outside its
profiled part (host clock)."""


def read(run):
    rec = run.record
    prof = rec.profiler
    on = prof.on if prof and prof.on is not None else float("inf")
    off = prof.off if prof and prof.off is not None else float("inf")
    times = [e - s for name, s, e in rec.spans.rows
             if name == "dispatch" and rec.t0 <= s <= rec.end
             and not on <= s <= off]
    if not times:
        return None
    return 1e3 * sum(times) / len(times)
