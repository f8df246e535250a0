"""Priority serving example: two streams, one latency-critical and one
batch, sharing a single accelerator through the paper's server.

Shows the paper's core claim operationally: with priority-queue arbitration
(+ suspension instead of busy-wait), the high-priority stream's latency is
protected from the low-priority stream's load.

Run:  PYTHONPATH=src python examples/serve_priority.py
"""

import threading

import numpy as np

from repro.launch import serve
from repro.serving.engine import StreamSpec


def main() -> None:
    cfg, params = serve.init_model("internlm2_1_8b", reduced=True, seed=3)
    # the unbatched server: one request per device call, priority-ordered
    engine = serve.build_engine(cfg, params, serve.REDUCED, batching=False)

    assert engine.admit(StreamSpec("interactive", priority=10, period_ms=400,
                                   deadline_ms=400, prefill_ms=30,
                                   decode_ms=8, decode_steps=4)).admitted
    assert engine.admit(StreamSpec("batch", priority=1, period_ms=2000,
                                   deadline_ms=2000, prefill_ms=60,
                                   decode_ms=8, decode_steps=16)).admitted

    lat: dict[str, list] = {"interactive": [], "batch": []}

    def batch_worker():
        rng = np.random.RandomState(0)
        for _ in range(4):
            prompt = rng.randint(0, cfg.vocab_size, (1, 16)).astype(np.int32)
            r = engine.generate("batch", prompt, steps=16)
            lat["batch"].extend(r.decode_latencies_s)

    def interactive_worker():
        rng = np.random.RandomState(1)
        for _ in range(8):
            prompt = rng.randint(0, cfg.vocab_size, (1, 4)).astype(np.int32)
            r = engine.generate("interactive", prompt, steps=4)
            lat["interactive"].extend(r.decode_latencies_s)

    threads = [threading.Thread(target=batch_worker),
               threading.Thread(target=interactive_worker)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    for name, xs in lat.items():
        ms = np.asarray(xs) * 1e3
        print(f"{name:12s} decode p50 {np.percentile(ms, 50):6.1f} ms  "
              f"p99 {np.percentile(ms, 99):6.1f} ms  n={len(ms)}")
    print(f"server handled {engine.server.stats.completed} requests, "
          f"max queue {engine.server.stats.max_queue_len}")
    engine.close()


if __name__ == "__main__":
    main()
