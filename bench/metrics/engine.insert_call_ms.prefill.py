"""Mean length of the server calls that copy a prefill's rows into the
paged block pools (``server.call`` spans of phase ``insert`` that start in
the window outside the profiler's trace, ``spans_io``): dequeue to the
client woken."""

from spans_io import durations_ms


def read(run):
    d = durations_ms(run, "server.call", phase="insert")
    return sum(d) / len(d) if d else None
