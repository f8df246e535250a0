"""Mean host time of a decode server call outside its device call: the
``server.call`` span less its ``engine.device`` child (staging, the logits
copy, notifying the clients), over the decode calls that start in the
window outside the profiler's trace (``spans_io``)."""

from spans_io import spans


def read(run):
    calls = spans(run, "server.call", phase="decode")
    if not calls:
        return None
    device = {s["parent"]: s["end"] - s["start"]
              for s in spans(run, "engine.device", every=True)}
    host = [c["end"] - c["start"] - device[c["id"]] for c in calls
            if c["id"] in device]
    return sum(host) / len(host) * 1e3 if host else None
