"""Live migration economics: steal win, move latency, elastic ramp.

Three measurements over the paged multi-server engine:

  * steal win — an adversarially imbalanced workload (every stream pinned
    onto server 0, arrivals in MMPP-style bursts) served with pinned
    routing vs with work stealing enabled; reports the tokens/s ratio.
    The rebalancer should recover most of the idle servers' capacity —
    the acceptance line is >= 1.3x on a 4-device pool.  (A server thread
    serializes its own Python-side dispatch with its XLA steps, so
    spreading a pinned burst wins wall-clock even single-core.)
  * migration latency vs blocks moved — wall time of the two-phase
    gather -> host hop -> scatter for growing sequence lengths, on the
    precompiled pow2-bucketed migrate cells (no mid-traffic traces).
  * elastic ramp — tokens/s of a fixed workload at each target of a
    ``LoadTrajectory`` as the ``ElasticPoolController`` scales the pool
    up and back down, with correctness guarded bit-exactly throughout.

Writes BENCH_migration.json next to this file.  ``--smoke`` shrinks the
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


def _make_engine(cfg, params, *, num_servers: int, max_batch: int = 4,
                 kv_block_size: int = 16):
    from repro.serving.engine import ServeEngine

    eng = ServeEngine(cfg, params, max_seq=64, ordering="fifo",
                      num_servers=num_servers, batching=True,
                      max_batch=max_batch, paged=True,
                      kv_block_size=kv_block_size)
    eng.enable_fault_tolerance(heartbeat_timeout_s=30.0)
    return eng


def _burst_offsets(num_streams: int, seed: int = 20260808) -> list[float]:
    """MMPP-style start offsets (seconds): bursts of back-to-back arrivals
    separated by idle dwells — the imbalanced-arrival shape the stealer
    is priced against."""
    rng = np.random.default_rng(seed)
    offsets, t, bursty = [], 0.0, True
    for _ in range(num_streams):
        offsets.append(t)
        t += rng.uniform(0.001, 0.004) if bursty else rng.uniform(0.05, 0.12)
        if rng.random() < (0.3 if bursty else 0.5):
            bursty = not bursty
    return offsets


def _run(eng, names, prompt, *, steps: int = STEPS, offsets=None):
    results: dict[str, object] = {}

    def worker(n, delay):
        if delay:
            time.sleep(delay)
        try:
            results[n] = eng.generate(n, prompt, steps=steps)
        except Exception as e:  # noqa: BLE001 - recorded, asserted by caller
            results[n] = e

    offsets = offsets or [0.0] * len(names)
    threads = [threading.Thread(target=worker, args=(n, d))
               for n, d in zip(names, offsets)]
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


def _pin_all(eng, names, si: int = 0) -> None:
    """Adversarial placement: force every stream onto one server, in both
    the admission partition and the pool routing."""
    for n in names:
        if eng.admission.device_of(n) != si:
            eng.admission.migrate(n, si)
        eng.pool.reassign(n, si, priority=eng._streams[n].priority)


def bench_steal_win(cfg, params, *, num_servers: int, streams: int,
                    steps: int) -> dict:
    prompt = np.arange(1, PROMPT_LEN + 1, dtype=np.int32)[None, :] % 100
    names = [f"s{i}" for i in range(streams)]
    offsets = _burst_offsets(streams)

    runs = {}
    for mode in ("pinned", "stealing"):
        eng = _make_engine(cfg, params, num_servers=num_servers)
        try:
            for i, n in enumerate(names):
                assert eng.admit(_spec(n, streams - i, steps)).admitted
            _pin_all(eng, names, 0)
            # warmup pass: compile every cell both modes will touch, so the
            # timed run compares routing policy, not trace cache state
            warm, _ = _run(eng, names, prompt, steps=steps)
            assert not any(isinstance(r, Exception) for r in warm.values())
            _pin_all(eng, names, 0)
            if mode == "stealing":
                eng.enable_work_stealing(interval_s=0.01)
            results, wall = _run(eng, names, prompt, steps=steps,
                                 offsets=offsets)
            bad = [n for n in names if isinstance(results[n], Exception)]
            assert not bad, f"{mode}: streams failed: {bad}"
            runs[mode] = {
                "tokens_per_s": _throughput(results, wall),
                "wall_s": wall,
                "migrations": eng.migrations_completed,
                "tokens": {n: results[n].tokens for n in names},
            }
            assert eng.kv_blocks_in_use() == 0
        finally:
            eng.close()

    mism = [n for n in names
            if runs["pinned"]["tokens"][n] != runs["stealing"]["tokens"][n]]
    assert not mism, f"stealing changed tokens: {mism}"
    assert runs["stealing"]["migrations"] >= 1, "no steal fired"
    win = runs["stealing"]["tokens_per_s"] / runs["pinned"]["tokens_per_s"]
    return {
        "num_servers": num_servers,
        "num_streams": streams,
        "steps": steps,
        "pinned_tokens_per_s": round(runs["pinned"]["tokens_per_s"], 2),
        "stealing_tokens_per_s": round(runs["stealing"]["tokens_per_s"], 2),
        "steals_completed": runs["stealing"]["migrations"],
        "steal_win": round(win, 4),
    }


def bench_migration_latency(cfg, params, *, lengths, reps: int) -> dict:
    from repro.models import model as M

    eng = _make_engine(cfg, params, num_servers=2, kv_block_size=8)
    rows = []
    try:
        for tokens in lengths:
            assert eng.admit(_spec("mv0", 1, 4)).admitted
            samples = []
            blocks = None
            for rep in range(reps + 1):  # rep 0 is an untimed warmup
                seq_id, _, _, _ = eng._paged_reserve(0, "mv0", tokens, 0, 8)
                src = eng._paged[0]
                if src.pools is None:
                    src.pools = M.init_paged_cache(cfg, src.mgr.num_blocks,
                                                   src.mgr.block_size)
                blocks = len(src.mgr.seqs[seq_id].blocks)
                t0 = time.perf_counter()
                eng._execute_migration("mv0", seq_id, 0, 1, 0)
                if rep:
                    samples.append(1e3 * (time.perf_counter() - t0))
                eng._paged_release(1, seq_id)
            eng.remove("mv0")
            assert eng.kv_blocks_in_use() == 0
            rows.append({
                "tokens": tokens,
                "blocks_moved": blocks,
                "latency_ms": {
                    "min": round(min(samples), 3),
                    "mean": round(float(np.mean(samples)), 3),
                    "max": round(max(samples), 3),
                },
            })
    finally:
        eng.close()
    return {"kv_block_size": 8, "reps": reps, "points": rows}


def bench_elastic_ramp(cfg, params, *, steps: int) -> dict:
    from repro.runtime.elastic import ElasticPoolController, LoadTrajectory

    prompt = np.arange(1, PROMPT_LEN + 1, dtype=np.int32)[None, :] % 100
    names = [f"s{i}" for i in range(4)]
    traj = LoadTrajectory(((0.0, 1), (1.0, 3), (2.0, 1)))

    eng = _make_engine(cfg, params, num_servers=1)
    phases = []
    want = None
    try:
        for i, n in enumerate(names):
            assert eng.admit(_spec(n, len(names) - i, steps)).admitted
        ctl = ElasticPoolController(eng, min_servers=1, max_servers=4)
        warm, _ = _run(eng, names, prompt, steps=steps)  # compile warmup
        assert not any(isinstance(r, Exception) for r in warm.values())
        for t in (0.0, 1.0, 2.0):
            ctl.scale_to(traj.target_at(t))
            results, wall = _run(eng, names, prompt, steps=steps)
            bad = [n for n in names if isinstance(results[n], Exception)]
            assert not bad, f"ramp t={t}: streams failed: {bad}"
            got = {n: results[n].tokens for n in names}
            if want is None:
                want = got
            else:
                assert got == want, f"ramp t={t}: tokens diverged"
            phases.append({
                "t_s": t,
                "target_servers": traj.target_at(t),
                "live_servers": len(ctl.live()),
                "tokens_per_s": round(_throughput(results, wall), 2),
            })
        assert eng.kv_blocks_in_use() == 0
    finally:
        eng.close()
    return {"num_streams": len(names), "steps": steps,
            "trajectory": [list(p) for p in traj.points], "phases": phases}


def main() -> None:
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
    smoke = "--smoke" in sys.argv

    import jax

    from repro.configs.registry import get_config
    from repro.models import model as M

    cfg = get_config("internlm2_1_8b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    steps = 12 if smoke else STEPS
    streams = 4 if smoke else 6
    # tokens per point; capped by max_seq=64 (kv_block_size=8 -> <=8 blocks)
    lengths = (8, 32) if smoke else (8, 16, 32, 64)
    reps = 3 if smoke else 10

    out = {
        "config": "internlm2_1_8b.reduced",
        "mode": "smoke" if smoke else "full",
        "steal": bench_steal_win(cfg, params, num_servers=4,
                                 streams=streams, steps=steps),
        "latency": bench_migration_latency(cfg, params, lengths=lengths,
                                           reps=reps),
        "elastic": bench_elastic_ramp(cfg, params, steps=steps),
    }
    path = Path(__file__).resolve().parent / "BENCH_migration.json"
    path.write_text(json.dumps(out, indent=2) + "\n")
    print(json.dumps(out, indent=2))
    print(f"wrote {path}")
    if out["steal"]["steal_win"] < 1.3:
        print(f"WARNING: steal win {out['steal']['steal_win']} < 1.3x",
              file=sys.stderr)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
