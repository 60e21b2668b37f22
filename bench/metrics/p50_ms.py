"""p50_ms: median latency of a stream's frames, in milliseconds."""

from bench.metrics._latency import percentile_ms


def read(run):
    return percentile_ms(run, 50)
