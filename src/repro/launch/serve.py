"""Serving launcher: the paper's server-based access control, live.

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2_1_8b \
        --streams 3 --requests 2 --seed 0 [--reduced]

Builds the model at its published widths with random bf16 weights drawn
from ``--seed`` (``--reduced``: the tiny same-family variant the CPU
examples and tests use), starts a batched, paged ServeEngine, admits N
prioritised streams, precompiles exactly the shape cells their requests can
hit, runs the requests from client threads (which suspend between segments
— never busy-wait), and reports time to first token, the gap between output
tokens and each server's ServerStats.  ``chip_smoke.py`` drives the same
functions on the chip.
"""

from __future__ import annotations

import argparse
import threading
import time
from dataclasses import dataclass

import jax
import numpy as np

from repro.configs.registry import get_config
from repro.models import model as M
from repro.serving.engine import ServeEngine, StreamSpec


@dataclass(frozen=True)
class Sizes:
    """Engine and request sizes of one launcher profile; prompt lengths and
    decode steps are drawn per stream from the inclusive ranges."""

    max_seq: int
    max_batch: int
    kv_block_size: int
    prompt_len: tuple[int, int]
    steps: tuple[int, int]


FULL = Sizes(max_seq=1024, max_batch=4, kv_block_size=16,
             prompt_len=(64, 512), steps=(16, 32))
REDUCED = Sizes(max_seq=64, max_batch=4, kv_block_size=8,
                prompt_len=(4, 24), steps=(4, 8))


@dataclass
class Workload:
    """Admission specs plus each stream's prompts, one (1, L) int32 array
    per request; a stream's prompts share one length."""

    specs: list[StreamSpec]
    prompts: dict[str, list[np.ndarray]]

    def requests(self) -> list[tuple[int, int]]:
        """(prompt_length, decode_steps) of every request."""
        return [(p.shape[1], s.decode_steps) for s in self.specs
                for p in self.prompts[s.name]]


def init_model(arch: str, *, reduced: bool, seed: int):
    """(cfg, params): random weights from ``seed``, built on the default
    device in the config's dtype."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    params = jax.jit(M.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))
    return cfg, params


def build_engine(cfg, params, sizes: Sizes, *, num_servers: int = 1,
                 batching: bool = True, ordering: str = "priority"
                 ) -> ServeEngine:
    """A ServeEngine at ``sizes``: batched and paged, or the unbatched
    path with ``batching=False``."""
    return ServeEngine(cfg, params, max_seq=sizes.max_seq,
                       max_batch=sizes.max_batch,
                       kv_block_size=sizes.kv_block_size,
                       num_servers=num_servers, batching=batching,
                       paged=batching, ordering=ordering)


def make_workload(cfg, sizes: Sizes, *, streams: int, requests: int,
                  seed: int) -> Workload:
    """``streams`` prioritised streams (stream0 highest), each with one
    prompt length and decode-step count drawn from ``sizes`` and
    ``requests`` random prompts of that length."""
    rng = np.random.default_rng(seed)
    specs, prompts = [], {}
    for i in range(streams):
        length = int(rng.integers(sizes.prompt_len[0],
                                  sizes.prompt_len[1] + 1))
        steps = int(rng.integers(sizes.steps[0], sizes.steps[1] + 1))
        name = f"stream{i}"
        # 4 s: four FULL-size streams still pass admission on one server,
        # which the smoke's one-server reference needs
        specs.append(StreamSpec(name=name, priority=streams - i,
                                period_ms=4000.0, deadline_ms=4000.0,
                                prefill_ms=40.0, decode_ms=10.0,
                                decode_steps=steps))
        prompts[name] = [rng.integers(0, cfg.vocab_size, (1, length),
                                      dtype=np.int32)
                         for _ in range(requests)]
    return Workload(specs, prompts)


def admit(engine: ServeEngine, workload: Workload) -> list[str]:
    """Admit every stream; returns the admitted names (rejections are
    printed with the analysis' reason)."""
    admitted = []
    for spec in workload.specs:
        decision = engine.admit(spec)
        if decision.admitted:
            admitted.append(spec.name)
        else:
            print(f"{spec.name}: REJECTED ({decision.reason})")
    return admitted


def precompile(engine: ServeEngine, workload: Workload):
    """Tune the buckets to the workload and compile exactly the cells its
    requests can hit (plus each phase's fallback).  Returns (seconds,
    PrecompileReport)."""
    reqs = workload.requests()
    lengths = [length for length, _ in reqs]
    engine.tune_buckets(lengths, steps_hint=max(s for _, s in reqs),
                        max_buckets=2)
    cells = engine.traffic_cells(
        reqs, concurrency=min(len(workload.specs), engine.max_batch))
    t0 = time.monotonic()
    report = engine.precompile(tuple(lengths), traffic=cells)
    return time.monotonic() - t0, report


def run_clients(engine: ServeEngine, workload: Workload, names=None
                ) -> dict[str, list]:
    """Run each stream's requests in order from its own client thread;
    returns {stream: [GenerationResult]} and re-raises the first client
    error."""
    names = [s.name for s in workload.specs] if names is None else names
    steps = {s.name: s.decode_steps for s in workload.specs}
    results: dict[str, list] = {}
    errors: list[BaseException] = []

    def work(name: str) -> None:
        try:
            results[name] = [engine.generate(name, p, steps=steps[name])
                             for p in workload.prompts[name]]
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    threads = [threading.Thread(target=work, args=(n,), name=f"client-{n}")
               for n in names]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return results


def latency_report(results: dict[str, list]) -> dict:
    """Requests served, time to first token and the gap between output
    tokens (host clock, ms) over every request."""
    runs = [r for rs in results.values() for r in rs]
    ttft = [r.prefill_latency_s * 1e3 for r in runs]
    gaps = [d * 1e3 for r in runs for d in r.decode_latencies_s]
    return {"requests": len(runs),
            "tokens": sum(1 + len(r.tokens) for r in runs),
            "ttft_ms_p50": float(np.percentile(ttft, 50)),
            "ttft_ms_p99": float(np.percentile(ttft, 99)),
            "itl_ms_p50": float(np.percentile(gaps, 50)),
            "itl_ms_p99": float(np.percentile(gaps, 99))}


def server_stats(engine: ServeEngine) -> list[dict]:
    """Each server's device and ServerStats summary, with its per-cell
    device-call counts and mean seconds."""
    out = []
    for si, server in enumerate(engine.pool.servers):
        st = server.stats
        out.append({
            "server": si, "device": str(engine.device_of(si)),
            "completed": st.completed, "batches": st.batches,
            "mean_batch": (float(np.mean(st.batch_sizes))
                           if st.batch_sizes else 0.0),
            "max_queue_len": st.max_queue_len,
            "cells": {"%s:%dx%d" % key: [c.calls, c.mean_s]
                      for key, c in sorted(st.cell_stats.items())}})
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2_1_8b")
    ap.add_argument("--streams", type=int, default=3)
    ap.add_argument("--requests", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ordering", default="priority",
                    choices=["priority", "fifo", "edf"])
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config and sizes (CPU)")
    args = ap.parse_args(argv)

    sizes = REDUCED if args.reduced else FULL
    cfg, params = init_model(args.arch, reduced=args.reduced, seed=args.seed)
    workload = make_workload(cfg, sizes, streams=args.streams,
                             requests=args.requests, seed=args.seed)
    engine = build_engine(cfg, params, sizes, ordering=args.ordering)
    try:
        admitted = admit(engine, workload)
        compile_s, rep = precompile(engine, workload)
        print(f"precompile: {rep.compiled} programs in {compile_s:.3f}s")
        results = run_clients(engine, workload, admitted)
        stats = server_stats(engine)
    finally:
        engine.close()
    report = latency_report(results)
    print(f"{report['requests']} requests: ttft p50 "
          f"{report['ttft_ms_p50']:.1f}ms p99 {report['ttft_ms_p99']:.1f}ms"
          f"  inter-token p50 {report['itl_ms_p50']:.1f}ms p99 "
          f"{report['itl_ms_p99']:.1f}ms")
    for s in stats:
        print(f"server {s['server']} on {s['device']}: {s['completed']} "
              f"requests in {s['batches']} batches, max queue "
              f"{s['max_queue_len']}")
    return report


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
