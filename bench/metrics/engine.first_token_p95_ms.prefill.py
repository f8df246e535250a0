"""95th percentile, over the jobs whose ``generate`` call began in the
window outside the profiler's trace (``spans_io``), of the time from that
call to its first token as the program stamps it (``job`` span start to its
``job.first_token``): slot wait, queue wait, prefill, insert and the greedy
pick."""

from metrics_io import pct
from spans_io import spans


def read(run):
    jobs = spans(run, "job")
    if not jobs:
        return None
    first = {s["job"]: s["start"]
             for s in spans(run, "job.first_token", every=True)}
    return pct([(first[j["job"]] - j["start"]) * 1e3 for j in jobs
                if j["job"] in first], 95)
