"""The run a metric reader sees, and the loop that asks every reader.

Each metric of ``BENCHMARK.json`` has a reader ``metrics/<name>.py`` with
one function, ``read(run) -> float | None``.  A reader that finds nothing
to read returns None, and the metric is left out of the result line.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

import work

PEAKS = json.loads((Path(__file__).parent / "peaks.json").read_text())


def pct(values, q: float):
    return float(np.percentile(np.asarray(values, float), q)) if values else None


@dataclass
class Run:
    cell: object
    jobs: list
    bounds: dict  # stream -> analysis response-time bound, ms
    delta: dict  # ServerStats deltas over the window (run.stats_delta)
    trace: dict | None  # trace_reduce.reduce output, traced runs only
    setup_s: float
    device_kind: str

    @property
    def done(self) -> list:
        return [j for j in self.jobs if j.done]

    @property
    def peak(self) -> dict:
        if self.device_kind not in PEAKS:
            raise KeyError(f"no peaks for device kind {self.device_kind!r}")
        return PEAKS[self.device_kind]

    def response_ms(self) -> list[float]:
        """Each finished job's response, from its due release to the
        return of its ``generate`` call (its last token), on the
        benchmark's own clock."""
        return [(j.end - j.due) * 1e3 for j in self.done]

    def ms_per_token(self) -> list[float]:
        """Each finished job's response over the tokens it served: one
        host-clock reading spanning the whole job, never a single step."""
        return [(j.end - j.due) * 1e3 / (j.steps + 1) for j in self.done]

    def phase(self, name: str) -> dict | None:
        p = self.delta["phases"].get(name)
        return p if p and p["calls"] else None

    def in_trace(self, t: float) -> bool:
        t0 = self.trace["sync_monotonic"]
        return t0 <= t < t0 + self.trace["window_s"]

    def decode_tokens_in_trace(self):
        """(context length) of every decode token produced in the traced
        window; a job's tokens are spread evenly over its ``generate``
        call, the first (the prefill's) included."""
        out = []
        for j in self.done:
            span = (j.end - j.start) / (j.steps + 1)
            for k in range(1, j.steps + 1):
                if self.in_trace(j.start + span * (k + 1)):
                    out.append(j.prompt_len + k)
        return out

    def program(self, name: str) -> dict | None:
        """Device calls and seconds of program ``name`` in the traced
        window, or None when it made none there.  A traced run whose whole
        trace holds no module of a program that a metric of its cell reads
        is a fault of ``programs.json``, not a reading of nothing: it
        raises."""
        if self.trace is None:
            return None
        if name not in self.trace["matched"]:
            raise LookupError(
                f"no module of program {name!r} in the trace; modules "
                f"matching no program: {self.trace['unmatched']}")
        return self.trace["programs"].get(name)

    def decode_work(self):
        """(useful FLOPs, least bytes, device seconds) of the decode calls
        in the traced window, or None."""
        prog = self.program("decode")
        ctx = self.decode_tokens_in_trace()
        if prog is None or not ctx:
            return None
        conf = self.cell.conf
        flops = sum(work.decode_token_flops(conf, c) for c in ctx)
        rows = len(ctx) / prog["calls"]
        per_call = work.decode_call_bytes(conf, [0] * max(1, round(rows)))
        kv = work.kv_bytes_per_token(conf) * sum(ctx)
        return flops, prog["calls"] * per_call + kv, prog["seconds"]


def load_reader(directory: Path, name: str):
    path = directory / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_all(directory: Path, metrics: list, run: Run) -> dict:
    out = {}
    for m in metrics:
        value = load_reader(directory, m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
