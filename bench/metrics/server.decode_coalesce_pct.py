"""Of the jobs in their decode phase on a server when one of its decode
calls was dequeued (the call's ``ready``), the share whose step that call
served (its ``rows``), summed over the decode calls that start in the
window outside the profiler's trace (``spans_io``): 100 when every ready
stream rides every call, lower when streams take turns.  It says nothing of
turns when no call found two streams ready (``spans_io.decode_ready``)."""

from spans_io import spans


def read(run):
    calls = spans(run, "server.call", phase="decode")
    ready = sum(c["attrs"].get("ready", 0) for c in calls or ())
    if not ready:
        return None
    return 100.0 * sum(c["attrs"]["rows"] for c in calls) / ready
