"""The program's spans in a run, as the span readers see them.

A run with the program's recorder on (``ServeEngine.enable_tracing``) over
its window carries the spans recorded there as ``run.delta["spans"]``:
tuples ``(name, id, parent, job, start, end, attrs)`` on ``time.monotonic()``
(``repro.core.spans``).

The host-clock readers take the spans that start in the window outside
the profiler's trace: all of them in an untraced run, the seconds after the
trace in a traced one (the profiler slows the host's work while it runs).
The device readers take the traced window alone.  There the reduced trace
carries the device's busy intervals, merged over the devices, on the
trace's clock (``busy_intervals``, ns), and the window's anchor ties the two
clocks: the ``bench.sync`` span starts at ``trace["t0_ns"]`` on the trace's
clock and at ``trace["sync_monotonic"]`` on the monotonic one.

A reader of a run without spans reads None.
"""

from __future__ import annotations

from trace_reduce import _clip, union

FIELDS = ("name", "id", "parent", "job", "start", "end", "attrs")

# spans with no child on the served path (a server call's own host work is
# its duration less its ``engine.*`` children)
LEAVES = ("server.idle", "server.queue", "engine.stage", "engine.device",
          "engine.fetch", "job.slot_wait", "job.turnaround")


def _traced(run, t: float) -> bool:
    return run.trace is not None and run.in_trace(t)


def spans(run, name: str, *, every: bool = False, **attrs) -> list | None:
    """Spans called ``name`` whose attributes hold ``attrs``, as dicts:
    those that start outside the traced window, or with ``every`` all."""
    raw = run.delta.get("spans")
    if raw is None:
        return None
    out = []
    for s in raw:
        if s[0] != name:
            continue
        d = dict(zip(FIELDS, s))
        if not every and _traced(run, d["start"]):
            continue
        if all(d["attrs"].get(k) == v for k, v in attrs.items()):
            out.append(d)
    return out


def durations_ms(run, name: str, **attrs) -> list[float] | None:
    got = spans(run, name, **attrs)
    return None if got is None else [(s["end"] - s["start"]) * 1e3
                                     for s in got]


def to_trace_ns(run, t: float) -> float:
    return run.trace["t0_ns"] + (t - run.trace["sync_monotonic"]) * 1e9


def intersect(x, y) -> list[tuple[float, float]]:
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if b > a:
            out.append((a, b))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(x, y) -> list[tuple[float, float]]:
    """``x`` less ``y``, both merged and sorted."""
    out, j = [], 0
    for a, b in x:
        while j < len(y) and y[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(y) and y[k][0] < b:
            if y[k][0] > cur:
                out.append((cur, y[k][0]))
            cur = max(cur, y[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def measure(intervals) -> float:
    return sum(b - a for a, b in intervals)


def mapped(run, name: str) -> list[tuple[float, float]]:
    """Every span called ``name`` clipped to the traced window, on the
    trace's clock, merged."""
    w0 = run.trace["t0_ns"]
    events = ((name, to_trace_ns(run, s["start"]),
               (s["end"] - s["start"]) * 1e9)
              for s in spans(run, name, every=True))
    return union((a, b) for _, a, b in
                 _clip(events, w0, w0 + run.trace["window_s"] * 1e9))


def idle_in_jobs(run) -> dict | None:
    """Of the traced window's time in which at least one ``job`` span is
    open, the time with no operation on the device, and how much of that
    each leaf span covers (seconds; leaves on different threads overlap)."""
    busy = (run.trace or {}).get("busy_intervals")
    if busy is None or run.delta.get("spans") is None:
        return None
    jobs = mapped(run, "job")
    if not jobs:
        return None
    idle = subtract(jobs, union(busy))
    covered = {name: measure(intersect(idle, mapped(run, name))) / 1e9
               for name in LEAVES}
    leaves = union(iv for name in LEAVES for iv in mapped(run, name))
    covered["none"] = measure(subtract(idle, leaves)) / 1e9
    return {"jobs_s": measure(jobs) / 1e9, "idle_s": measure(idle) / 1e9,
            "covered_s": covered}


def summary(run, *, traced: bool = False) -> dict:
    """Count and mean length (ms) of the spans by name and phase, of those
    the host-clock readers take, or with ``traced`` of those that start in
    the traced window: where a step's host time goes."""
    acc: dict = {}
    for s in run.delta.get("spans") or ():
        d = dict(zip(FIELDS, s))
        if _traced(run, d["start"]) != traced:
            continue
        key = d["name"] + ("/" + d["attrs"]["phase"]
                           if "phase" in d["attrs"] else "")
        n, total = acc.get(key, (0, 0.0))
        acc[key] = (n + 1, total + d["end"] - d["start"])
    return {k: [n, total / n * 1e3] for k, (n, total) in sorted(acc.items())}


def decode_ready(run) -> dict[int, int]:
    """How many of the decode calls the host-clock readers take found each
    number of jobs ready (``ready``): 1 alone means no two streams were in
    their decode phase at once on a server when a call was made."""
    out: dict[int, int] = {}
    for c in spans(run, "server.call", phase="decode") or ():
        k = c["attrs"].get("ready", 0)
        out[k] = out.get(k, 0) + 1
    return dict(sorted(out.items()))
