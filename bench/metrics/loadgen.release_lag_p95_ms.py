"""How late the load generator called ``generate`` against each job's
due release (95th percentile over the window's jobs): a starved client
thread, or a job waiting for its stream's previous job."""

from metrics_io import pct


def read(run):
    return pct([(j.start - j.due) * 1e3 for j in run.jobs if j.start], 95)
