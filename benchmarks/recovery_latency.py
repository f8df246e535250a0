"""Recovery latency + degraded-mode throughput under mid-traffic failure.

Kills one server of an N-server pool while every stream is decoding (a
deterministic ``FaultInjector`` schedule) and measures the fault-tolerance
story end to end:

  * detection -> resume latency: from the injected device death
    (``FaultInjector.events`` timestamp) to the first token a recovered
    stream appends after re-prefilling its retained prefix on a survivor;
  * degraded throughput: decode tokens/s of the same workload on the full
    pool vs the post-failure pool, swept over pool size — the price of
    losing a device, with degraded-mode admission re-placing (never
    silently overloading) the displaced streams;
  * correctness alongside: every recovered stream's tokens must equal the
    failure-free run's (the chaos suite asserts this per scenario; here it
    guards the numbers being reported).

Writes BENCH_recovery.json next to this file.  ``--smoke`` shrinks the
sweep for CI.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

STEPS = 24
PROMPT_LEN = 4


def _spec(name: str, prio: int, steps: int = STEPS):
    from repro.serving.engine import StreamSpec

    return StreamSpec(name=name, priority=prio, period_ms=30_000.0,
                      deadline_ms=30_000.0, prefill_ms=50.0, decode_ms=5.0,
                      decode_steps=steps)


def _make_engine(cfg, params, *, num_servers: int, max_batch: int = 4):
    from repro.serving.engine import ServeEngine

    eng = ServeEngine(cfg, params, max_seq=64, ordering="fifo",
                      num_servers=num_servers, batching=True,
                      max_batch=max_batch, paged=True, kv_block_size=16)
    eng.enable_fault_tolerance(heartbeat_timeout_s=30.0)
    return eng


def _run(eng, names, prompt, *, steps: int = STEPS):
    results: dict[str, object] = {}

    def worker(n):
        try:
            results[n] = eng.generate(n, prompt, steps=steps)
        except Exception as e:  # noqa: BLE001 - shed streams are reported
            results[n] = e

    threads = [threading.Thread(target=worker, args=(n,)) for n in names]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results, time.perf_counter() - t0


def _throughput(results, wall: float) -> float:
    tokens = sum(len(r.tokens) for r in results.values()
                 if not isinstance(r, Exception))
    return tokens / wall if wall > 0 else 0.0


def bench_pool(cfg, params, num_servers: int, *, streams_per_server: int,
               steps: int) -> dict:
    from repro.runtime.faultinject import FaultInjector, ServerFault

    prompt = np.arange(1, PROMPT_LEN + 1, dtype=np.int32)[None, :] % 100
    num_streams = num_servers * streams_per_server
    names = [f"s{i}" for i in range(num_streams)]

    # failure-free reference: tokens (correctness guard) + throughput
    eng = _make_engine(cfg, params, num_servers=num_servers)
    for i, n in enumerate(names):
        assert eng.admit(_spec(n, num_streams - i, steps)).admitted
    baseline, wall = _run(eng, names, prompt, steps=steps)
    want = {n: baseline[n].tokens for n in names}
    healthy_tps = _throughput(baseline, wall)
    eng.close()

    # faulted run: same workload, one server dies mid-decode
    eng = _make_engine(cfg, params, num_servers=num_servers)
    for i, n in enumerate(names):
        assert eng.admit(_spec(n, num_streams - i, steps)).admitted
    victim = eng.pool.server_of(names[0])
    # land the death well inside the decode phase of the victim's streams
    at_call = 2 * streams_per_server + 3
    inj = FaultInjector([ServerFault(server=victim, at_call=at_call,
                                     kind="die")])
    eng.pool.attach_fault_injector(inj)
    faulted, wall = _run(eng, names, prompt, steps=steps)
    degraded_tps = _throughput(faulted, wall)

    recovered = [n for n in names
                 if not isinstance(faulted[n], Exception)
                 and faulted[n].recoveries > 0]
    mismatches = [n for n in names
                  if not isinstance(faulted[n], Exception)
                  and faulted[n].tokens != want[n]]
    assert not mismatches, f"recovered tokens diverged: {mismatches}"
    assert recovered, "fault did not hit any decoding stream"

    # detection -> resume latency: injected-death timestamp (the server
    # thread raises DeviceLostError at that instant, so detection is
    # immediate for the die kind) to each recovered stream's resume point —
    # the retained prefix re-established on a survivor, ready to decode
    death_t = inj.events[0].at_monotonic
    resume_ms = [1e3 * (faulted[n].resumed_at_monotonic[0] - death_t)
                 for n in recovered]

    shed = [n for n in names if isinstance(faulted[n], Exception)]
    eng.close()
    return {
        "num_servers": num_servers,
        "num_streams": num_streams,
        "steps": steps,
        "victim": victim,
        "recovered_streams": len(recovered),
        "shed_streams": len(shed),
        "healthy_tokens_per_s": round(healthy_tps, 2),
        "degraded_tokens_per_s": round(degraded_tps, 2),
        "degraded_fraction": round(degraded_tps / healthy_tps, 4)
        if healthy_tps else None,
        "detect_to_resume_ms": {
            "mean": round(float(np.mean(resume_ms)), 3),
            "max": round(float(np.max(resume_ms)), 3),
        },
        "death_at_monotonic": death_t,
    }


def main() -> None:
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
    smoke = "--smoke" in sys.argv

    import jax

    from repro.configs.registry import get_config
    from repro.models import model as M

    cfg = get_config("internlm2_1_8b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    pool_sizes = (2,) if smoke else (2, 3, 4)
    steps = 12 if smoke else STEPS
    rows = [bench_pool(cfg, params, n, streams_per_server=2, steps=steps)
            for n in pool_sizes]

    out = {
        "config": "internlm2_1_8b.reduced",
        "mode": "smoke" if smoke else "full",
        "pools": rows,
    }
    path = Path(__file__).resolve().parent / "BENCH_recovery.json"
    path.write_text(json.dumps(out, indent=2) + "\n")
    print(json.dumps(out, indent=2))
    print(f"wrote {path}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
