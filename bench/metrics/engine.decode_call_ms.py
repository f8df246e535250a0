"""Mean host-clock time of one paged decode call, staging and the
logits copy included (ServerStats per-cell seconds over calls)."""


def read(run):
    p = run.phase("decode")
    return p["seconds"] / p["timed"] * 1e3 if p and p["timed"] else None
