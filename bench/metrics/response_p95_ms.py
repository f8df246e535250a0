"""95th percentile over every job released in the window of its
response: from its due release to the return of its ``generate`` call,
on the benchmark's own clock.  Slot waits, queueing, prefill and every
decode step count; a job that never finished counts in ``failed``."""

from metrics_io import pct


def read(run):
    return pct(run.response_ms(), 95)
