"""Record the small trace ``test_trace_reduce.py`` checks the reduction
on, on a TPU:

    python3 bench/tests/record_trace.py <out_dir>

Three known programs run with known gaps between them: a step named like
the paged decode step, a lambda (as the prefill is), and one that matches
no program.  Between them the host sleeps 20 ms and 10 ms.  The ``.xplane.pb`` is left under ``<out_dir>``.
"""

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import trace_reduce  # noqa: E402


class Engine:
    def _decode_paged_impl(self, x):
        return jnp.tanh(x @ x) @ x


def unlisted(x, y):
    return x + y


def main(out_dir: str) -> None:
    decode = jax.jit(Engine()._decode_paged_impl)
    prefill = jax.jit(lambda x: jnp.sin(x) @ x.T)
    other = jax.jit(unlisted)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    for f in (decode, prefill):
        f(x).block_until_ready()
    other(x, x).block_until_ready()
    jax.profiler.start_trace(out_dir, profiler_options=trace_reduce.options())
    with jax.profiler.TraceAnnotation(trace_reduce.SYNC):
        t0 = time.monotonic()
    time.sleep(0.01)  # the device clock may run up to ~1 ms ahead
    for _ in range(3):
        decode(x).block_until_ready()
        time.sleep(0.02)
        prefill(x).block_until_ready()
        time.sleep(0.01)
    other(x, x).block_until_ready()
    print(f"window_s {time.monotonic() - t0!r}")
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
