"""``run.py`` with the program's span recorder on, and the span metrics
in its result line.

    python3 bench/run_spans.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--spans <0|1>]

``run.py`` does not yet turn the recorder on nor hand its spans to the
readers; this file goes once a ``benchmark`` PR makes those edits to
``run.py`` (PERF.md, Open questions).  Until then it runs a cell exactly as
``run.py`` does.  A traced run, or an untraced one with ``--spans 1``, runs
with these hooks:

- ``run.run_clients``: the window's call (not the warm-up's) turns
  ``ServeEngine.enable_tracing()`` on as the clients start, after the
  traced window's anchor;
- ``jax.profiler.stop_trace``: turns it off while the profiler stops, so
  that a server idle at the traced window's end closes its ``server.idle``
  span inside the trace (a span still open at the stop is not in it);
- ``run.stats_delta``: turns it off once every job of the window has
  returned, and adds the spans recorded to the window's delta
  (``delta["spans"]``, read by ``spans_io``);
- ``trace_reduce.reduce``: adds the device's merged busy intervals
  (``busy_intervals``).

The result line also holds the span metrics of ``span_metrics.json`` listed
for the cell that find something to read: the host-clock ones over the
window outside the profiler's trace, and in a traced run the device ones
over the traced window.  An untraced run's end-to-end metrics then show what
the recorder costs.  Standard error gets each span's count and mean length,
how many decode calls found how many jobs ready, and in a traced run how
``device.idle_in_jobs_pct`` divides among the leaf spans that cover it.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402  (stamps the process's start time)
import trace_reduce  # noqa: E402

ENTRIES = json.loads((BENCH / "span_metrics.json").read_text())

# far more than a 51 s window records (the decode cell: ~7 spans a step)
CAPACITY = 1 << 18


def busy_intervals(tree: dict, w0: float, window_s: float) -> list:
    """The device's busy time in the window as merged (start, end) ns,
    over every device plane."""
    w1 = w0 + window_s * 1e9
    return trace_reduce.union(
        (a, b) for name, p in tree.items()
        if name.startswith("/device:") and "XLA Ops" in p
        for _, a, b in trace_reduce._clip(p["XLA Ops"], w0, w1))


@contextlib.contextmanager
def hooked():
    """Inside: ``run.serve`` runs with the hooks above.  Yields a dict
    that ends up holding the window's ``delta`` and, traced, its reduced
    ``trace``."""
    import jax

    from repro.core.spans import Recorder

    state: dict = {}
    run_clients, stats_delta = run.run_clients, run.stats_delta
    reduce, stop_trace = trace_reduce.reduce, jax.profiler.stop_trace

    def clients(engine, jobs):
        if jobs and all(j.index >= 0 for j in jobs):  # the window's jobs
            state["engine"] = engine
            state["rec"] = engine.enable_tracing(Recorder(CAPACITY))
        return run_clients(engine, jobs)

    def stop(*a, **k):
        engine = state.get("engine")
        if engine is not None:
            engine.disable_tracing()
            time.sleep(0.01)  # each idle server closes its span meanwhile
        stop_trace(*a, **k)
        if engine is not None:
            engine.enable_tracing(state["rec"])

    def delta(engine, before):
        engine.disable_tracing()
        out = stats_delta(engine, before)
        if "rec" in state:
            out["spans"] = list(state["rec"].spans)
        state["delta"] = out
        return out

    def reduced(tree, window_s, **k):
        out = state["trace"] = reduce(tree, window_s, **k)
        out["busy_intervals"] = busy_intervals(tree, out["t0_ns"], window_s)
        return out

    run.run_clients, run.stats_delta = clients, delta
    trace_reduce.reduce, jax.profiler.stop_trace = reduced, stop
    try:
        yield state
    finally:
        run.run_clients, run.stats_delta = run_clients, stats_delta
        trace_reduce.reduce, jax.profiler.stop_trace = reduce, stop_trace


def report(cell, state: dict) -> None:
    """What the spans show beyond the result line, to standard error."""
    import metrics_io
    import spans_io

    if "delta" not in state:
        return
    r = metrics_io.Run(cell=cell, jobs=[], bounds={}, delta=state["delta"],
                       trace=state.get("trace"), setup_s=0.0, device_kind="")
    kept = r.delta.get("spans") or ()
    if len(kept) >= CAPACITY:
        print("span ring full: the oldest of the window's spans are lost",
              file=sys.stderr)
    print("spans: " + json.dumps(spans_io.summary(r)), file=sys.stderr)
    print("decode calls by jobs ready: "
          + json.dumps(spans_io.decode_ready(r)), file=sys.stderr)
    if r.trace is not None:
        print("programs: " + json.dumps(r.trace["programs"]), file=sys.stderr)
        print("spans in the traced window: "
              + json.dumps(spans_io.summary(r, traced=True)), file=sys.stderr)
        split = spans_io.idle_in_jobs(r)
        if split:
            print("idle in jobs: " + json.dumps(split), file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--spans", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (args.trace or args.spans):
        return run.main(["--workload", args.workload, "--seed",
                         str(args.seed), "--seconds", str(args.seconds)])

    cell = run.Cell.load(run.ROOT, args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    run.use_compile_cache()
    devices = run.require_chips(cell.chips)
    mine = [m for m in ENTRIES if cell.name in m["workloads"]]
    (cell.per_layer if args.trace else cell.end_to_end).extend(mine)
    with hooked() as state:
        result = run.serve(cell, args.seed, args.seconds, bool(args.trace),
                           devices)
    report(cell, state)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
