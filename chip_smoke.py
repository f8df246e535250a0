"""Chip smoke test: the served path at full width on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four one-chip servers, live migration

One chip: internlm2-1.8b at its published widths (random bf16 weights from
``--seed``) is served through admission -> ServerPool -> the batched,
paged ServeEngine, using the launcher's own functions
(``repro.launch.serve``); every greedy token is checked against the
engine's unbatched path on the same chip.  ``--chips 4``: four one-chip
servers behind the pool router, one stream migrated live between chips,
every token checked against one server.  The timings printed are smoke
timings, not benchmark numbers.  The last line of standard output is one
JSON object, ``{"ok": true, "device": {...}}``; without a TPU, or when any
phase fails, the script exits non-zero without printing it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model as M  # noqa: E402

ARCH = "internlm2_1_8b"
# a divergence counts as bf16 rounding only when both paths' tokens are
# near-ties in a plain forward over the shared prefix: each within this
# many standard deviations of that logit row below its top logit
TIE_SIGMAS = 0.125
RULE = ("greedy tokens must equal the reference path's; at a request's "
        "first divergent step both tokens must lie within "
        f"{TIE_SIGMAS} std of the top logit of a plain forward over the "
        "shared prefix (a bf16 near-tie), else the smoke fails")


def compare(cfg, params, sizes, workload, got, want) -> dict:
    """Check ``got`` against ``want`` ({stream: [GenerationResult]} for the
    same prompts) under RULE; every token must be in range.  Returns counts
    and raises AssertionError on a failure."""
    forward = jax.jit(lambda p, t: M.apply(cfg, p, {"tokens": t},
                                           mode="train")[0][0])
    steps = {s.name: s.decode_steps for s in workload.specs}
    exact = diverged = 0
    failures = []
    for name, prompts in workload.prompts.items():
        for j, prompt in enumerate(prompts):
            a, b = got[name][j], want[name][j]
            seq_a = [a.first_token, *a.tokens]
            seq_b = [b.first_token, *b.tokens]
            for seq in (seq_a, seq_b):
                assert len(seq) == steps[name] + 1, (name, j, len(seq))
                assert all(0 <= t < cfg.vocab_size for t in seq), (name, j)
            k = next((i for i, (x, y) in enumerate(zip(seq_a, seq_b))
                      if x != y), None)
            if k is None:
                exact += 1
                continue
            diverged += 1
            ctx = np.concatenate([prompt[0], np.asarray(seq_b[:k], np.int32)])
            toks = np.zeros((1, sizes.max_seq), np.int32)
            toks[0, : len(ctx)] = ctx
            z = np.asarray(forward(params, toks)[len(ctx) - 1], np.float64)
            top2 = np.sort(z)[-2:]
            gap = float(z.max() - min(z[seq_a[k]], z[seq_b[k]]))
            limit = float(TIE_SIGMAS * z.std())
            verdict = "rounding" if gap <= limit else "FAIL"
            print(f"divergence {name}#{j} at step {k}: {seq_a[k]} vs "
                  f"reference {seq_b[k]}; top-2 margin "
                  f"{float(top2[1] - top2[0])!r}, gap below top {gap!r}, limit "
                  f"{limit!r} -> {verdict}")
            if verdict == "FAIL":
                failures.append((name, j, k))
    print(f"compare: {exact} requests token-identical, {diverged} diverged "
          "within rounding" if not failures else
          f"compare: {len(failures)} divergences beyond rounding")
    assert not failures, failures
    return {"exact": exact, "diverged": diverged}


def _print_served(label: str, engine, results, compile_s, report) -> None:
    lat = serve.latency_report(results)
    print(f"smoke timings, not benchmark numbers ({label}): "
          f"{lat['requests']} requests, {lat['tokens']} tokens; ttft p50 "
          f"{lat['ttft_ms_p50']!r} ms p99 {lat['ttft_ms_p99']!r} ms; "
          f"inter-token p50 {lat['itl_ms_p50']!r} ms p99 "
          f"{lat['itl_ms_p99']!r} ms")
    print(f"compile_s={compile_s!r} ({report.compiled} programs)")
    for stats in serve.server_stats(engine):
        print(f"server stats: {json.dumps(stats)}")


def one_chip(cfg, params, sizes, *, seed: int, streams: int = 3,
             requests: int = 2) -> dict:
    """Serve ``streams`` prioritised streams on one batched, paged server
    and compare with the unbatched path."""
    workload = serve.make_workload(cfg, sizes, streams=streams,
                                   requests=requests, seed=seed)
    engine = serve.build_engine(cfg, params, sizes)
    try:
        assert len(serve.admit(engine, workload)) == streams
        compile_s, report = serve.precompile(engine, workload)
        got = serve.run_clients(engine, workload)
        _print_served("batched paged, 1 server", engine, got, compile_s,
                      report)
    finally:
        engine.close()
    ref = serve.build_engine(cfg, params, sizes, batching=False)
    try:
        assert len(serve.admit(ref, workload)) == streams
        want = serve.run_clients(ref, workload)
    finally:
        ref.close()
    print(f"compare rule: {RULE}")
    return compare(cfg, params, sizes, workload, got, want)


def four_chips(cfg, params, sizes, *, seed: int, streams: int = 4,
               requests: int = 2) -> dict:
    """Four one-chip servers behind the pool router, stream0 migrated live
    to another chip during its first request; tokens compared with the
    same workload on one server."""
    workload = serve.make_workload(cfg, sizes, streams=streams,
                                   requests=requests, seed=seed)
    engine = serve.build_engine(cfg, params, sizes, num_servers=4)
    try:
        assert len(serve.admit(engine, workload)) == streams
        compile_s, report = serve.precompile(engine, workload)
        pool_devices = [
            {d for leaf in jax.tree.leaves(st.pools) for d in leaf.devices()}
            for st in engine._paged]
        print(f"server pools on: {[sorted(map(str, d)) for d in pool_devices]}")
        assert all(len(d) == 1 for d in pool_devices), pool_devices
        assert len(set().union(*pool_devices)) == 4, pool_devices
        name = workload.specs[0].name
        src = engine.pool.server_of(name)
        dst = (src + 1) % 4
        decision, placed = engine.admission.migrate(name, dst)
        assert decision.admitted and placed == dst, decision
        assert engine.pool.request_migration(name, dst)
        got = serve.run_clients(engine, workload)
        assert engine.migrations_completed >= 1
        assert engine.pool.server_of(name) == dst
        print(f"migrated {name} live from server {src} on "
              f"{engine.device_of(src)} to server {dst} on "
              f"{engine.device_of(dst)}; migrations completed: "
              f"{engine.migrations_completed}")
        usage = engine.kv_usage()
        assert not any(usage.values()), usage
        _print_served("batched paged, 4 servers", engine, got, compile_s,
                      report)
    finally:
        engine.close()
    single = serve.build_engine(cfg, params, sizes)
    try:
        assert len(serve.admit(single, workload)) == streams
        serve.precompile(single, workload)
        want = serve.run_clients(single, workload)
    finally:
        single.close()
    print(f"compare rule (reference: one server): {RULE}")
    return compare(cfg, params, sizes, workload, got, want)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {dev.platform})")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: needs {args.chips} chips, JAX found "
                         f"{len(devices)}")
    print(f"device_kind: {dev.device_kind}; devices: {len(devices)}")
    cfg, params = serve.init_model(ARCH, reduced=False, seed=args.seed)
    print(f"model: {cfg.name}, {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}, {M.param_count(cfg)} "
          f"parameters in {cfg.dtype}")
    phase = four_chips if args.chips == 4 else one_chip
    phase(cfg, params, serve.FULL, seed=args.seed)
    for d in devices[: args.chips]:
        print(f"peak_bytes_in_use {d}: "
              f"{d.memory_stats()['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
