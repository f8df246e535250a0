"""Cost-model calibration: measured batch_meta cells -> fitted surface ->
calibrated admission, end to end on a live paged engine.

Drives a paged ServeEngine (CPU backend, reduced model) across a grid of
occupancies and prompt lengths so traffic lands in several (rows, width)
decode cells and (rows, bucket) prefill cells, then:

  1. ingests the pool-wide per-cell timing aggregates into a
     ``StepCostModel`` and fits the per-phase roofline surface;
  2. scores the surface against the measured means per cell
     (predicted-vs-measured relative error — the interpolation quality the
     calibrated admission bound leans on);
  3. runs the admission capacity experiment: identical streams declared at
     the conservative full-width worst case (2x the costliest measured
     cell — what a profiler would declare) are admitted one by one until
     the Eqs (1)-(6) check rejects; calibrated admission re-prices each
     stream at the bucket its traffic actually hits and must admit
     STRICTLY more streams.

Writes BENCH_cost_model.json (tracked artifact).  Exits nonzero when the
median relative error exceeds a generous threshold (the surface is a
2-feature linear fit over noisy CPU timings; 1.0 catches only a broken
fit, not an imprecise one) or when calibrated admission fails to beat the
worst-case declaration.  ``--smoke`` shrinks repeats for CI.
"""

from __future__ import annotations

import json
import sys
import threading
from pathlib import Path

import numpy as np

MEDIAN_REL_ERR_MAX = 1.0
MAX_STREAMS = 64


def _spec(name: str, steps: int):
    from repro.serving.engine import StreamSpec

    return StreamSpec(name=name, priority=1, period_ms=60_000.0,
                      deadline_ms=60_000.0, prefill_ms=100.0, decode_ms=50.0,
                      decode_steps=steps)


def _drive(engine, num_streams: int, *, steps: int, prompt_len: int) -> None:
    prompt = np.arange(1, prompt_len + 1, dtype=np.int32)[None, :] % 100
    names = [f"s{i}" for i in range(num_streams)]
    for n in names:
        decision = engine.admit(_spec(n, steps))
        assert decision.admitted, (n, decision.reason)
    threads = [threading.Thread(
        target=lambda n=n: engine.generate(n, prompt, steps=steps))
        for n in names]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for n in names:
        engine.remove(n)


def _admission_capacity(ctl, *, declared_ms: float, eta: int, period_ms: float,
                        cell=None) -> int:
    """Admit identical streams until the analysis rejects one."""
    from repro.core.task_model import GpuSegment, Task

    seg = GpuSegment(e=declared_ms * 0.9, m=declared_ms * 0.1)
    for i in range(MAX_STREAMS):
        task = Task(name=f"cap{i}", C=0.1, T=period_ms, D=period_ms,
                    segments=(seg,) * eta, priority=1)
        if not ctl.try_admit(task, cell=cell).admitted:
            return i
    return MAX_STREAMS


def main(*, smoke: bool = False) -> dict:
    import jax

    from repro.analysis.cost_model import StepCostModel, TrafficModel
    from repro.configs.registry import get_config
    from repro.core.admission import AdmissionController
    from repro.models import model as M
    from repro.serving.engine import ServeEngine

    cfg = get_config("internlm2_1_8b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    max_batch, max_seq, block = 4, 64, 16  # widths {1,2,4}, rows {1,2,4}
    engine = ServeEngine(cfg, params, max_seq=max_seq, ordering="fifo",
                         num_servers=1, batching=True, max_batch=max_batch,
                         paged=True, kv_block_size=block)
    steps = 12  # long prompts cross a block boundary mid-generation
    repeats = 1 if smoke else 3
    try:
        rep = engine.precompile(prompt_buckets=(4, 32))
        print(f"precompile: {rep.compiled} traces, {rep.skipped} skipped")
        # occupancy x prompt-length grid: low/full rows, narrow/wide gathers
        for _ in range(repeats):
            for streams, plen in ((1, 4), (2, 4), (4, 4), (1, 24), (4, 24)):
                _drive(engine, streams, steps=steps, prompt_len=plen)
        cell_stats = engine.pool.cell_stats()
        traffic = TrafficModel.from_stats(cell_stats)
    finally:
        engine.close()

    model = StepCostModel()
    n_cells = model.ingest(cell_stats)
    coeffs = model.fit()
    err = model.error_report()
    print(f"{n_cells} measured cells, median rel err "
          f"{err['median_rel_err']:.3f}, dispatch overhead "
          f"{model.dispatch_overhead_s() * 1e3:.3f} ms")

    # -- calibrated admission capacity vs worst-case declaration ----------
    decode_cells = [k for k in cell_stats if k[0] == "decode"]
    small = min(decode_cells, key=lambda k: k[1] * k[2])
    worst = max(decode_cells, key=lambda k: k[1] * k[2])
    declared_ms = 2.0 * model.predict(*worst) * 1e3  # profiler's margin
    calibrated_ms = model.safety * model.predict(*small) * 1e3
    eta = 4
    period_ms = max(20.0, 8 * eta * calibrated_ms)
    declared_n = _admission_capacity(
        AdmissionController(2, epsilon_ms=0.05),
        declared_ms=declared_ms, eta=eta, period_ms=period_ms)
    calibrated_n = _admission_capacity(
        AdmissionController(2, epsilon_ms=0.05, cost_model=model),
        declared_ms=declared_ms, eta=eta, period_ms=period_ms, cell=small)
    print(f"admission capacity: declared {declared_n} streams -> "
          f"calibrated {calibrated_n} streams "
          f"(declared {declared_ms:.2f} ms/step, calibrated "
          f"{calibrated_ms:.2f} ms/step in cell {small})")

    report = {
        "model": cfg.name,
        "max_batch": max_batch, "max_seq": max_seq, "block_size": block,
        "n_cells": n_cells,
        "median_rel_err": err["median_rel_err"],
        "median_rel_err_max": MEDIAN_REL_ERR_MAX,
        "cells": err["cells"],
        "coeffs": coeffs,
        "dispatch_overhead_ms": model.dispatch_overhead_s() * 1e3,
        "hot_cells": sorted(map(list, traffic.hot_cells(min_share=0.1))),
        "admission": {
            "eta": eta, "period_ms": period_ms,
            "declared_ms_per_step": declared_ms,
            "calibrated_ms_per_step": calibrated_ms,
            "calibrated_cell": list(small),
            "declared_streams": declared_n,
            "calibrated_streams": calibrated_n,
        },
    }
    # the smoke grid must not clobber the committed full-grid artifact
    name = "BENCH_cost_model_smoke.json" if smoke else "BENCH_cost_model.json"
    out = Path(__file__).parent / name
    out.write_text(json.dumps(report, indent=2))
    print(f"wrote {out}")

    failures = []
    if not err["median_rel_err"] <= MEDIAN_REL_ERR_MAX:
        failures.append(f"median rel err {err['median_rel_err']:.3f} > "
                        f"{MEDIAN_REL_ERR_MAX}")
    if not calibrated_n > declared_n:
        failures.append(f"calibrated admission ({calibrated_n}) did not beat "
                        f"worst-case declaration ({declared_n})")
    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
    if failures:
        raise SystemExit(1)
    return report


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main(smoke="--smoke" in sys.argv)
