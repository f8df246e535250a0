"""95th percentile of the servers' queue wait (submit to dequeue) of
every request in the window (ServerStats ``wakeup_latencies``)."""

from metrics_io import pct


def read(run):
    return pct([w * 1e3 for w in run.delta["queue_waits_s"]], 95)
