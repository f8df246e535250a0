"""Mean host-clock time of one bucketed prefill call (ServerStats
per-cell seconds over calls)."""


def read(run):
    p = run.phase("prefill")
    return p["seconds"] / p["timed"] * 1e3 if p and p["timed"] else None
