"""Continuous-batching throughput: batched vs unbatched decode dispatch.

Drives two ServeEngines over the same reduced model on the CPU backend —
one with plain per-request dispatch (the paper's server, one device call
per decode step) and one with the BatchingServer (same-shape decode steps
from all concurrent streams coalesced into one masked device call) — and
reports decode tokens/s at 1/2/4/8 concurrent streams.

This is the GCAPS/RTGPU observation made concrete: the paper's server
bounds *access*, batching closes the *throughput* gap — per-request
dispatch pays the full device-call overhead (the runtime analogue of
Lemma 1's 2*eps) once per token, batching pays it once per batch.

Both engines run FIFO ordering so streams interleave fairly (priority
ordering would serialize the streams and hide the batching effect behind
starvation).  Writes BENCH_batching.json next to this file.

``--paged-sweep`` additionally compares the PAGED block-pool decode layout
against the masked-dense slot cache across occupancy (live streams out of
``max_batch`` slots) and context length (short prompts vs prompts near
max_seq): the masked-dense path pays the full (max_batch, max_seq) buffer
every step; the paged path's device call shrinks with slot compaction and
the block-table gather width, so the gap is widest exactly where central
knowledge says the work is small.  Writes BENCH_paged_decode.json.
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


def _make_engine(cfg, params, *, batching: bool, max_batch: int,
                 paged: bool = False, max_seq: int = 64):
    from repro.serving.engine import ServeEngine

    return ServeEngine(cfg, params, max_seq=max_seq, ordering="fifo",
                       num_servers=1, batching=batching, max_batch=max_batch,
                       paged=paged, kv_block_size=16)


def _spec(name: str, prio: int, steps: int = STEPS):
    from repro.serving.engine import StreamSpec

    return StreamSpec(name=name, priority=prio, period_ms=30_000.0,
                      deadline_ms=30_000.0, prefill_ms=50.0, decode_ms=5.0,
                      decode_steps=steps)


def _run(engine, num_streams: int, *, steps: int = STEPS,
         prompt_len: int = PROMPT_LEN) -> dict:
    prompt = np.arange(1, prompt_len + 1, dtype=np.int32)[None, :] % 100
    names = [f"s{i}" for i in range(num_streams)]
    for i, n in enumerate(names):
        decision = engine.admit(_spec(n, num_streams - i, steps))
        assert decision.admitted, (n, decision.reason)
    results: dict[str, object] = {}

    def worker(n):
        results[n] = engine.generate(n, prompt, steps=steps)

    threads = [threading.Thread(target=worker, args=(n,)) for n in names]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    for n in names:
        engine.remove(n)
    tokens = sum(len(results[n].tokens) for n in names)
    # decode-phase throughput: all streams prefill first (one bucketed call
    # when batched), so wall minus the slowest prefill is decode-dominated
    prefill_s = max(results[n].prefill_latency_s for n in names)
    decode_wall = max(wall - prefill_s, 1e-9)
    server = engine.pool.servers[0]
    sizes = server.stats.batch_sizes
    return {
        "tokens": tokens,
        "wall_s": wall,
        "tokens_per_s": tokens / wall,
        "decode_tokens_per_s": tokens / decode_wall,
        "mean_batch": (sum(sizes) / len(sizes)) if sizes else 1.0,
    }


def _best_of(engine, num_streams: int, *, repeats: int = 3,
             key: str = "tokens_per_s", **kw) -> dict:
    """Best-of-N measurement: one scheduler hiccup or GC pause in a ~100ms
    run swings tokens/s by 2x, and 'fastest clean run' is the number that
    reflects the dispatch path being measured.  ``key`` picks the metric
    the comparison cares about (the paged sweep reports decode rates)."""
    runs = [_run(engine, num_streams, **kw) for _ in range(repeats)]
    return max(runs, key=lambda r: r[key])


def main() -> dict:
    import jax

    from repro.configs.registry import get_config
    from repro.models import model as M

    cfg = get_config("internlm2_1_8b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    report: dict = {"model": cfg.name, "steps": STEPS, "streams": {}}
    for num_streams in (1, 2, 4, 8):
        row: dict = {}
        for mode, batching in (("unbatched", False), ("batched", True)):
            engine = _make_engine(cfg, params, batching=batching,
                                  max_batch=max(num_streams, 1))
            try:
                # compile every decode/prefill shape bucket, then one
                # warm-up run — prefill coalescing widths are timing-
                # dependent, so only precompile makes them deterministic
                if batching:
                    engine.precompile(prompt_buckets=(PROMPT_LEN,))
                _run(engine, num_streams)
                row[mode] = _best_of(engine, num_streams)
            finally:
                engine.close()
        row["speedup"] = (row["batched"]["tokens_per_s"]
                          / row["unbatched"]["tokens_per_s"])
        report["streams"][str(num_streams)] = row
        print(f"{num_streams} streams: unbatched "
              f"{row['unbatched']['tokens_per_s']:8.1f} tok/s | batched "
              f"{row['batched']['tokens_per_s']:8.1f} tok/s "
              f"(mean batch {row['batched']['mean_batch']:.2f}) | "
              f"speedup {row['speedup']:.2f}x")

    out = Path(__file__).parent / "BENCH_batching.json"
    out.write_text(json.dumps(report, indent=2))
    print(f"wrote {out}")
    return report


def paged_sweep(*, smoke: bool = False) -> dict:
    """Paged block-pool vs masked-dense decode across occupancy and context
    length.  ``smoke`` shrinks the grid/steps for a CI-sized run."""
    import jax

    from repro.configs.registry import get_config
    from repro.models import model as M

    cfg = get_config("internlm2_1_8b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    max_batch = 8
    max_seq = 1024  # the masked-dense path pays this buffer every step
    steps = 32
    occupancies = (1, 2) if smoke else (1, 2, 4, 8)
    contexts = {"short": 4}
    if not smoke:
        contexts["long"] = max_seq - steps - 8  # prompts near max_seq
    report: dict = {"model": cfg.name, "max_batch": max_batch,
                    "max_seq": max_seq, "steps": steps, "cells": []}

    for ctx_name, prompt_len in contexts.items():
        for occ in occupancies:
            cell: dict = {"context": ctx_name, "prompt_len": prompt_len,
                          "occupancy": f"{occ}/{max_batch}"}
            for mode, paged in (("masked_dense", False), ("paged", True)):
                engine = _make_engine(cfg, params, batching=True,
                                      max_batch=max_batch, paged=paged,
                                      max_seq=max_seq)
                try:
                    # compile every decode/prefill shape bucket, then one
                    # warm-up run — nothing compiles inside the clock
                    bucket = 1 << (prompt_len - 1).bit_length()
                    engine.precompile(
                        prompt_buckets=(min(bucket, max_seq),))
                    _run(engine, occ, steps=steps, prompt_len=prompt_len)
                    cell[mode] = _best_of(engine, occ, steps=steps,
                                          prompt_len=prompt_len,
                                          key="decode_tokens_per_s")
                finally:
                    engine.close()
            cell["speedup"] = (cell["paged"]["decode_tokens_per_s"]
                               / cell["masked_dense"]["decode_tokens_per_s"])
            report["cells"].append(cell)
            print(f"{ctx_name:>5} ctx, {occ}/{max_batch} live: masked "
                  f"{cell['masked_dense']['decode_tokens_per_s']:8.1f} tok/s"
                  f" | paged {cell['paged']['decode_tokens_per_s']:8.1f} "
                  f"tok/s | speedup {cell['speedup']:.2f}x")

    # the smoke grid must not clobber the committed full-grid artifact
    name = "BENCH_paged_decode_smoke.json" if smoke else "BENCH_paged_decode.json"
    out = Path(__file__).parent / name
    out.write_text(json.dumps(report, indent=2))
    print(f"wrote {out}")
    return report


FAMILY_ARCHS = (
    ("internlm2_1_8b", "gqa"),
    ("deepseek_v2_lite_16b", "mla"),
    ("mamba2_780m", "ssm"),
    ("zamba2_7b", "hybrid"),
    ("whisper_medium", "encdec"),
)


def family_sweep(*, smoke: bool = False) -> dict:
    """One paged-vs-masked-dense cell per CACHE FAMILY (the same serving
    engine, five pool layouts: GQA KV blocks, MLA latent blocks, SSM state
    slabs, hybrid block+slab, enc-dec shared cross segments), plus the
    MLA latent pool's block-size sensitivity — the latent rows are narrow
    (r + rope, not n_kv*hd), so the gather-width/bucket-waste tradeoff
    sits at a different block size than plain GQA."""
    import jax

    from repro.configs.registry import get_config
    from repro.models import model as M

    max_batch = 4
    max_seq = 64
    steps = 12 if smoke else 24
    occ = 2
    repeats = 2 if smoke else 3
    report: dict = {"max_batch": max_batch, "max_seq": max_seq,
                    "steps": steps, "occupancy": occ, "families": {},
                    "mla_block_size": []}

    for arch, family in FAMILY_ARCHS:
        cfg = get_config(arch).reduced()
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        cell: dict = {"arch": arch}
        for mode, paged in (("masked_dense", False), ("paged", True)):
            engine = _make_engine(cfg, params, batching=True,
                                  max_batch=max_batch, paged=paged,
                                  max_seq=max_seq)
            try:
                engine.precompile(prompt_buckets=(PROMPT_LEN,))
                _run(engine, occ, steps=steps)
                cell[mode] = _best_of(engine, occ, steps=steps,
                                      repeats=repeats,
                                      key="decode_tokens_per_s")
            finally:
                engine.close()
        cell["speedup"] = (cell["paged"]["decode_tokens_per_s"]
                           / cell["masked_dense"]["decode_tokens_per_s"])
        report["families"][family] = cell
        print(f"{family:>7}: masked "
              f"{cell['masked_dense']['decode_tokens_per_s']:8.1f} tok/s | "
              f"paged {cell['paged']['decode_tokens_per_s']:8.1f} tok/s | "
              f"speedup {cell['speedup']:.2f}x")

    cfg = get_config("deepseek_v2_lite_16b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    for bs in ((8, 32) if smoke else (8, 16, 32)):
        from repro.serving.engine import ServeEngine

        engine = ServeEngine(cfg, params, max_seq=max_seq, ordering="fifo",
                             num_servers=1, batching=True,
                             max_batch=max_batch, paged=True,
                             kv_block_size=bs)
        try:
            engine.precompile(prompt_buckets=(PROMPT_LEN,))
            _run(engine, occ, steps=steps)
            r = _best_of(engine, occ, steps=steps, repeats=repeats,
                         key="decode_tokens_per_s")
        finally:
            engine.close()
        report["mla_block_size"].append(
            {"block_size": bs,
             "decode_tokens_per_s": r["decode_tokens_per_s"]})
        print(f"mla bs={bs:3d}: {r['decode_tokens_per_s']:8.1f} tok/s")

    out = Path(__file__).parent / "BENCH_paged_families.json"
    out.write_text(json.dumps(report, indent=2))
    print(f"wrote {out}")
    return report


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--paged-sweep" in sys.argv:
        paged_sweep(smoke="--smoke" in sys.argv)
        family_sweep(smoke="--smoke" in sys.argv)
    else:
        main()
