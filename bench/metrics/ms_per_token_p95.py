"""95th percentile over every job released in the window of its
response (due release to the return of its ``generate`` call) over the
tokens it served: the streams whose steps waited for others'."""

from metrics_io import pct


def read(run):
    return pct(run.ms_per_token(), 95)
