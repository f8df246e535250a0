"""Chip benchmark: admitted real-time streams served by the program's
batched, paged ServeEngine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its model configuration in ``bench/configs/<config>.json`` (with the plain
reference ``bench/models/<reference>.py`` it names), its traffic mix in
``bench/traffic/<traffic>.json``, its declared costs and period scale in
``bench/cells/<cell>.json``, and each metric's reader in
``bench/metrics/<metric>.py``.

A run builds the model with random bfloat16 weights from the seed on the
device, admits the mix's streams through the program's admission (a
refused stream fails every job it would have released), compiles only the
shape cells this traffic can hit, warms up until every stream has
completed one job, then releases jobs open loop for ``--seconds``: one
client thread per stream calls ``ServeEngine.generate`` at each job's due
release time, and every latency is taken from that due time.  After the
window it waits for every released job, reads the device's peak memory,
frees the program's state and compares a sample of the served tokens,
drawn from the seed, with the float32 reference.  The last line of
standard output is one JSON object; without a TPU, or with fewer chips
than the cell asks for, the run exits non-zero and prints none.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import loadgen  # noqa: E402
from check import REFUSED  # noqa: E402

# how far past the window's close a released job may still finish before
# it counts as never having come
DRAIN_S = 60.0


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace("-", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One benchmark cell with everything it names, read from disk."""

    name: str
    chips: int
    conf: dict
    traffic: dict
    declared: dict
    end_to_end: list
    per_layer: list

    @classmethod
    def load(cls, root: Path, name: str) -> "Cell":
        bench = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
        w = cells[name]

        def mine(metrics):
            return [m for m in metrics
                    if "workloads" not in m or name in m["workloads"]]

        read = lambda *p: json.loads(root.joinpath(*p).read_text())
        return cls(name=name, chips=w["chips"],
                   conf=read("bench", "configs", w["config"] + ".json"),
                   traffic=read("bench", "traffic", w["traffic"] + ".json"),
                   declared=read("bench", "cells", name + ".json"),
                   end_to_end=mine(bench["end_to_end"]),
                   per_layer=mine(bench["per_layer"]))


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``.jax_cache/`` at the checkout's root (the fixed path
    the program's own entry points use).  Every program is cached, however
    quickly it compiled, so that a warm run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(n: int):
    """The TPU devices of this run; exits non-zero without enough."""
    import jax

    devs = [d for d in jax.devices() if d.platform == "tpu"]
    if len(devs) < n:
        print(f"need {n} TPU chip(s), JAX sees {jax.devices()}",
              file=sys.stderr)
        raise SystemExit(3)
    return devs[:n]


@dataclasses.dataclass
class JobRecord:
    stream: str
    index: int
    due: float  # monotonic release time
    prompt_len: int
    steps: int
    prompt: object = None
    start: float = 0.0  # generate() called
    end: float = 0.0  # generate() returned: last token out
    first_token: int | None = None
    tokens: list = dataclasses.field(default_factory=list)
    error: str | None = None
    deadline_ms: float = 0.0

    @property
    def done(self) -> bool:
        return self.error is None and self.end > 0.0

    @property
    def missed(self) -> bool:
        return (self.end - self.due) * 1e3 > self.deadline_ms


def _serve_job(engine, rec: JobRecord) -> None:
    import jax

    rec.start = time.monotonic()
    try:
        with jax.profiler.TraceAnnotation(f"bench.generate.{rec.stream}"):
            res = engine.generate(rec.stream, rec.prompt, steps=rec.steps)
    except Exception as e:  # noqa: BLE001 - a failed job is counted, not fatal
        rec.error = f"{type(e).__name__}: {e}"
        return
    rec.end = time.monotonic()
    rec.first_token = res.first_token
    rec.tokens = list(res.tokens)


def run_clients(engine, jobs: list[JobRecord]) -> list[threading.Thread]:
    """One client thread per stream, each serving its jobs in release
    order at their due times (a job whose predecessor is still running
    starts late, and its latencies count the wait)."""
    by_stream: dict[str, list[JobRecord]] = {}
    for j in jobs:
        by_stream.setdefault(j.stream, []).append(j)

    def client(recs):
        for rec in recs:
            wait = rec.due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            _serve_job(engine, rec)

    threads = [threading.Thread(target=client, args=(recs,),
                                name=f"client-{name}", daemon=True)
               for name, recs in by_stream.items()]
    for t in threads:
        t.start()
    return threads


def stats_snapshot(engine) -> dict:
    """Counters of every server: what ``metrics`` readers take deltas of."""
    out = []
    for server in engine.pool.servers:
        st = server.stats
        cells = {}
        for key, c in st.cell_stats.items():
            cells[key] = (c.calls, c.rows, c.timed, c.mean_s * c.timed)
        out.append({"completed": st.completed, "batches": st.batches,
                    "batch_sizes": len(st.batch_sizes),
                    "wakeups": len(st.wakeup_latencies), "cells": cells})
    return {"servers": out, "migrations": engine.migrations_completed}


def stats_delta(engine, before: dict) -> dict:
    """Per phase (``decode``, ``prefill``, ``migrate``), summed over the
    servers: calls, true rows and timed seconds since ``before``; and the
    queue waits and batch sizes recorded since then."""
    phases: dict[str, dict] = {}
    waits, sizes = [], []
    for server, b in zip(engine.pool.servers, before["servers"]):
        st = server.stats
        waits += list(st.wakeup_latencies[b["wakeups"]:])
        sizes += list(st.batch_sizes[b["batch_sizes"]:])
        for key, c in st.cell_stats.items():
            calls0, rows0, timed0, sec0 = b["cells"].get(key, (0, 0, 0, 0.0))
            phase = key[0].split("@", 1)[0]
            p = phases.setdefault(phase, {"calls": 0, "rows": 0, "timed": 0,
                                          "seconds": 0.0})
            p["calls"] += c.calls - calls0
            p["rows"] += c.rows - rows0
            p["timed"] += c.timed - timed0
            p["seconds"] += c.mean_s * c.timed - sec0
    return {"phases": phases, "queue_waits_s": waits, "batch_sizes": sizes,
            "migrations": engine.migrations_completed - before["migrations"]}


def build_engine(cell: Cell, cfg, weights, devices):
    """A batched, paged engine at the mix's sizes.  Each server's block
    pool holds one whole-``max_seq`` reservation for every stream of the
    mix (plus the scratch block), so an admitted job never finds the pool
    empty; jobs beyond ``max_batch`` wait for a decode slot."""
    from repro.serving.engine import ServeEngine

    eng = cell.traffic["engine"]
    streams = sum(g["streams"] for g in cell.traffic["groups"])
    engine = ServeEngine(cfg, weights, max_seq=eng["max_seq"],
                         max_batch=eng["max_batch"],
                         kv_block_size=eng["kv_block_size"],
                         kv_blocks=streams * eng["max_seq"]
                         // eng["kv_block_size"] + 1,
                         num_servers=eng["servers"], batching=True,
                         paged=True, ordering="priority")
    used = {str(engine.device_of(i)) for i in range(eng["servers"])}
    if used != {str(d) for d in devices[: eng["servers"]]}:
        raise RuntimeError(f"servers on {used}, expected {devices}")
    if eng["work_stealing"]:
        engine.enable_work_stealing()
    return engine


def admit(cell: Cell, engine, streams) -> tuple[set, dict]:
    """Admit every stream; returns (refused names, {name: analysis bound
    in ms}) where the bound is the response time the analysis proved for
    the stream in the final admitted set on its device."""
    from repro.serving.engine import StreamSpec

    refused, last = set(), {}
    for s in streams:
        group = s.name.rstrip("0123456789")
        cost = cell.declared["declared_ms"][group]
        decision = engine.admit(StreamSpec(
            name=s.name, priority=s.priority, period_ms=s.period_ms,
            deadline_ms=s.deadline_ms, prefill_ms=cost["prefill"],
            decode_ms=cost["decode"], decode_steps=s.max_steps))
        if decision.admitted:
            last[engine.pool.server_of(s.name)] = decision.response_times
        else:
            refused.add(s.name)
            print(f"admission refused {s.name}: {decision.reason}",
                  file=sys.stderr)
    bounds = {n: r for rts in last.values() for n, r in rts.items()}
    return refused, bounds


def precompile(cell: Cell, engine, streams) -> int:
    """Tune buckets to every size any seed can offer, and compile exactly
    the cells that traffic can hit (and each phase's fallback)."""
    sizes = loadgen.all_sizes(cell.traffic, len(streams))
    eng = cell.traffic["engine"]
    engine.tune_buckets([n for n, _ in sizes],
                        steps_hint=max(s for _, s in sizes),
                        max_buckets=eng["max_buckets"])
    per_server = -(-len(streams) // eng["servers"])
    cells = engine.traffic_cells(
        sizes, concurrency=min(len(streams), per_server if not
                               eng["work_stealing"] else len(streams),
                               engine.max_batch))
    report = engine.precompile(tuple(n for n, _ in sizes), traffic=cells)
    return report.compiled


def warm_up(engine, streams, refused: set, traffic, vocab: int) -> None:
    """Every admitted stream serves its smallest job once, all released
    at once."""
    import numpy as np

    sizes, _, _ = loadgen.layout(traffic, len(streams))
    rng = np.random.default_rng(0)
    recs = []
    for s, sz in zip(streams, sizes):
        if s.name in refused:
            continue
        n, steps = min(sz)
        recs.append(JobRecord(s.name, -1, time.monotonic(), n, steps,
                              prompt=rng.integers(0, vocab, (1, n),
                                                  dtype=np.int32),
                              deadline_ms=s.deadline_ms))
    for t in run_clients(engine, recs):
        t.join()
    bad = [r for r in recs if not r.done]
    if bad:
        raise RuntimeError(f"warm-up failed: {bad[0].stream}: {bad[0].error}")


def peak_memory(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def serve(cell: Cell, seed: int, seconds: float, trace: bool,
          devices, *, control: bool = False, calls: dict | None = None
          ) -> dict:
    """One run of ``cell`` on ``devices``: the result line's object.
    ``control`` adds the control's reading to the checks (``control.py``);
    a ``calls`` dict is filled with the call seconds of each shape cell
    in the window, from the servers' rings of recent calls."""
    import jax
    import numpy as np

    import check
    import metrics_io
    import stalls

    model = load_module(BENCH / "models" / f"{cell.conf['reference']}.py")
    from repro.configs.registry import get_config

    cfg = dataclasses.replace(get_config(cell.conf["arch"]),
                              **model.program_fields(cell.conf))
    with jax.default_device(devices[0]):
        weights = jax.block_until_ready(model.weights(cell.conf, seed))
    engine = build_engine(cell, cfg, weights, devices)
    n_servers = cell.traffic["engine"]["servers"]
    try:
        streams = loadgen.streams(cell.traffic,
                                  cell.declared["period_scale_ms"])
        refused, bounds = admit(cell, engine, streams)
        programs = precompile(cell, engine, streams)
        print(f"precompile: {programs} programs", file=sys.stderr)
        warm_up(engine, streams, refused, cell.traffic, cfg.vocab_size)

        rng = np.random.default_rng(seed)
        deadline = {s.name: s.deadline_ms for s in streams}
        jobs = [JobRecord(j.stream, j.index, j.due_s, j.prompt_len, j.steps,
                          deadline_ms=deadline[j.stream],
                          prompt=rng.integers(0, cfg.vocab_size,
                                              (1, j.prompt_len),
                                              dtype=np.int32),
                          error=REFUSED if j.stream in refused else None)
                for j in loadgen.schedule(cell.traffic, streams, seconds)]
        before = stats_snapshot(engine)
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        if trace_dir:
            import trace_reduce

            jax.profiler.start_trace(trace_dir,
                                     profiler_options=trace_reduce.options())
            with jax.profiler.TraceAnnotation(trace_reduce.SYNC):
                t_sync = time.monotonic()
        t0 = time.monotonic() + 0.1
        for rec in jobs:
            rec.due += t0
        setup_s = t0 - T_START
        with stalls.Watch() as watch:
            threads = run_clients(engine,
                                  [j for j in jobs if j.error is None])
            if trace_dir:
                t_stop = t_sync + cell.traffic["trace_seconds"]
                time.sleep(max(0.0, t_stop - time.monotonic()))
                trace_s = time.monotonic() - t_sync
                jax.profiler.stop_trace()
            for t in threads:
                t.join(max(0.0, t0 + seconds + DRAIN_S - time.monotonic()))
        hung = any(t.is_alive() for t in threads)
        delta = stats_delta(engine, before)
        if calls is not None:
            n_new = sum(p["calls"] for p in delta["phases"].values())
            for server in engine.pool.servers:
                for m in list(server.stats.batch_meta)[-n_new:]:
                    key = "%s:%sx%s" % (m["kind"], m["padded"],
                                        m.get("bucket", m.get("width")))
                    calls.setdefault(key, []).append(m["seconds"])
        memory = peak_memory(devices[:n_servers])
    finally:
        engine.close()
    if hung:
        for rec in jobs:
            if not rec.done and rec.error is None:
                rec.error = "no answer within the drain time"
    del engine
    reduced = None
    if trace_dir:
        try:
            tree = trace_reduce.planes(trace_reduce.find_xplane(trace_dir))
            reduced = trace_reduce.reduce(tree, trace_s)
            reduced["sync_monotonic"] = t_sync
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    checks = check.served_tokens(cell, model, weights, jobs, seed,
                                 control=control)
    run = metrics_io.Run(cell=cell, jobs=jobs, bounds=bounds, delta=delta,
                         trace=reduced, setup_s=setup_s,
                         device_kind=devices[0].device_kind)
    result = {
        "correct": checks.correct,
        "attempted": len(jobs),
        "failed": sum(1 for j in jobs if not j.done or j.missed),
        "metrics": metrics_io.read_all(
            BENCH / "metrics", cell.per_layer if trace else cell.end_to_end,
            run),
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices),
                   "memory_peak_bytes": memory},
    }
    if reduced is not None:
        result["device"].update(busy_s=reduced["busy_s"],
                                window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if control:
        result["control_correct"] = checks.control_correct
    result["window_events"] = watch.summary()
    print("window events: " + json.dumps(result["window_events"]),
          file=sys.stderr)
    result["checks"] = checks.numbers
    for line in checks.lines():
        print(line, file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell.load(ROOT, args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    use_compile_cache()
    devices = require_chips(cell.chips)
    result = serve(cell, args.seed, args.seconds, bool(args.trace), devices)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
