"""Release schedules for admitted real-time streams (open loop).

Each stream is a sporadic task: job k+1 is released ``T * (1 + jitter)``
after job k, with the jitter drawn from an exponential law, so consecutive
releases are never closer than the minimum inter-arrival ``T`` that
admission proved.  This is the ``sporadic`` model of the program's
``scenarios/arrivals.py`` with an exponential slack instead of a uniform
one, copied here so that the yardstick cannot change with the program.

What a run's seed may change and what it may not: a mix's job sizes,
jitters and phases are drawn once from its own ``layout_seed``, so every
seed offers the same jobs at the same release times; the run's seed draws
the prompt tokens and the weights.  Which jobs overlap sets the tails, so
a seed that dealt the sizes out in another order would change the work.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Job:
    stream: str
    index: int
    due_s: float  # release, seconds after the window opens
    prompt_len: int
    steps: int


@dataclass(frozen=True)
class Stream:
    name: str
    priority: int  # larger is served first (rate monotonic)
    period_ms: float
    deadline_ms: float
    max_prompt: int
    max_steps: int


def streams(traffic: dict, period_scale_ms: float) -> list[Stream]:
    """The mix's streams with periods ``period_scale_ms * multiplier``,
    priorities rate monotonic (the shortest period first)."""
    out = []
    groups = traffic["groups"]
    n_all = sum(g["streams"] for g in groups)
    i = 0
    for g in groups:
        for _ in range(g["streams"]):
            period = period_scale_ms * (1.0 + traffic["period_spread"] * i)
            out.append(Stream(
                name=f"{g['name']}{i}", priority=n_all - i, period_ms=period,
                deadline_ms=period * traffic["deadline_over_period"],
                max_prompt=g["prompt_len"][1], max_steps=g["steps"][1]))
            i += 1
    return out


def _group_of(traffic: dict, index: int) -> dict:
    for g in traffic["groups"]:
        if index < g["streams"]:
            return g
        index -= g["streams"]
    raise IndexError(index)


def layout(traffic: dict, n_streams: int):
    """The fixed set each seed deals out: per stream, ``jobs_per_stream``
    (prompt_len, steps) pairs and exponential jitters (as multiples of
    the period), and one phase fraction per stream."""
    rng = np.random.default_rng(traffic["layout_seed"])
    k = traffic["jobs_per_stream"]
    sizes, jitters = [], []
    for i in range(n_streams):
        g = _group_of(traffic, i)
        lo, hi = g["prompt_len"]
        slo, shi = g["steps"]
        sizes.append(list(zip(rng.integers(lo, hi + 1, k).tolist(),
                              rng.integers(slo, shi + 1, k).tolist())))
        jitters.append(rng.exponential(traffic["jitter_mean"], k).tolist())
    phases = rng.uniform(0.0, 1.0, n_streams).tolist()
    return sizes, jitters, phases


def schedule(traffic: dict, streams_: list[Stream],
             seconds: float) -> list[Job]:
    """Every job released in ``[0, seconds)``, in release order: the
    mix's own sizes and release times, the same for every seed."""
    sizes, jitters, phases = layout(traffic, len(streams_))
    jobs = []
    for i, s in enumerate(streams_):
        t = phases[i] * s.period_ms / 1e3
        for k, (length, steps) in enumerate(sizes[i]):
            if t >= seconds:
                break
            jobs.append(Job(s.name, k, t, length, steps))
            t += s.period_ms / 1e3 * (1.0 + jitters[i][k])
        else:
            raise ValueError(f"{s.name}: jobs_per_stream too small for "
                             f"{seconds} s at period {s.period_ms} ms")
    return sorted(jobs, key=lambda j: j.due_s)


def all_sizes(traffic: dict, n_streams: int) -> list[tuple[int, int]]:
    """Every (prompt_len, steps) pair any seed can offer: the set the
    engine's buckets and compiled programs are chosen from."""
    sizes, _, _ = layout(traffic, n_streams)
    return [p for s in sizes for p in s]
