"""95th percentile of the time a job blocked waiting for a decode slot
before its prefill was submitted (``job.slot_wait`` spans that start in the
window outside the profiler's trace, ``spans_io``)."""

from metrics_io import pct
from spans_io import durations_ms


def read(run):
    d = durations_ms(run, "job.slot_wait")
    return pct(d, 95) if d else None
