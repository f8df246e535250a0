"""What held up the host inside the timed window: garbage collections,
JAX tracing, compiling or loading a program, and the delays of a thread
that asked to sleep ``TICK_S``.  Every run prints it on standard error
and in its result line; no metric reads it.  Nothing should compile or
load inside the window: a count here names the cause of a far-off run.

For the worst delay it keeps when it began (seconds after the window
opened) and the CPU time the whole process spent meanwhile: about the
delay when another thread held the interpreter, about nothing when the
process as a whole was not running.
"""

from __future__ import annotations

import gc
import threading
import time

TICK_S = 0.02


class Watch:
    def __init__(self):
        self.gc: dict[int, list] = {}  # generation -> [count, max s, sum s]
        self.jax: dict[str, list] = {}  # event -> [count, max s, sum s]
        self.worst = (0.0, 0.0, 0.0)  # late s, began at s, process CPU s
        self.late_over_50ms = 0
        self._t0 = 0.0
        self._gc_t0 = 0.0
        self._stop = threading.Event()
        self._ticker = threading.Thread(target=self._tick, daemon=True,
                                        name="stall-ticker")

    @staticmethod
    def _add(table, key, seconds):
        n = table.setdefault(key, [0, 0.0, 0.0])
        n[0] += 1
        n[1] = max(n[1], seconds)
        n[2] += seconds

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self._add(self.gc, info["generation"],
                      time.perf_counter() - self._gc_t0)

    def _on_jax(self, event, seconds, **_):
        if event.startswith(("/jax/core/compile", "/jax/compilation_cache")):
            self._add(self.jax, event.rsplit("/", 1)[-1], seconds)

    def _tick(self):
        while True:
            t, cpu = time.perf_counter(), time.process_time()
            if self._stop.wait(TICK_S):
                return
            late = time.perf_counter() - t - TICK_S
            self.late_over_50ms += late > 0.05
            if late > self.worst[0]:
                self.worst = (late, t - self._t0,
                              time.process_time() - cpu)

    def __enter__(self):
        import jax.monitoring

        gc.callbacks.append(self._on_gc)
        jax.monitoring.register_event_duration_secs_listener(self._on_jax)
        self._t0 = time.perf_counter()
        self._ticker.start()
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        self._stop.set()
        self._ticker.join()
        gc.callbacks.remove(self._on_gc)
        jax.monitoring.unregister_event_duration_listener(self._on_jax)

    def summary(self) -> dict:
        ms = lambda t: {str(k): {"n": v[0], "max_ms": v[1] * 1e3,
                                 "sum_ms": v[2] * 1e3}
                        for k, v in sorted(t.items())}
        late, at, cpu = self.worst
        return {"gc_by_generation": ms(self.gc), "jax": ms(self.jax),
                "tick_late_max_ms": late * 1e3, "tick_late_max_at_s": at,
                "tick_late_max_cpu_ms": cpu * 1e3,
                "ticks_late_over_50ms": self.late_over_50ms}
