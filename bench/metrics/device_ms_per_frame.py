"""device_ms_per_frame.<mix>: device busy time in the profiled window over
the frames answered there, in milliseconds."""


def read(run):
    t = run.trace
    if not t or not t["fetches"]:
        return None
    return 1e3 * t["busy_s"] / (t["fetches"] * run.cell.batch)
