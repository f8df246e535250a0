"""Median time a decode step waited in its server's queue, submit to
dequeue (``server.queue`` spans of phase ``decode`` that start in the
window outside the profiler's trace, ``spans_io``)."""

from metrics_io import pct
from spans_io import durations_ms


def read(run):
    d = durations_ms(run, "server.queue", phase="decode")
    return pct(d, 50) if d else None
