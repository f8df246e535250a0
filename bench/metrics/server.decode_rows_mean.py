"""Live rows per paged decode call over the window (ServerStats deltas):
how many streams' steps shared one device call."""


def read(run):
    p = run.phase("decode")
    return p["rows"] / p["calls"] if p else None
