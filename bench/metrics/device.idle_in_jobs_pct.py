"""Of the traced window's time in which at least one job is open (a
``job`` span: ``generate`` called, not yet returned), the share in which no
operation runs on the device: the host, not the releases, keeps the chip
waiting then.  The spans are put on the trace's clock through the
window's anchor."""

from spans_io import idle_in_jobs


def read(run):
    r = idle_in_jobs(run)
    return 100.0 * r["idle_s"] / r["jobs_s"] if r else None
