"""Reduce a JAX profiler trace (``*.xplane.pb``) to the numbers the
benchmark reports: device busy time, per-program device time and calls,
the device operations that took most time (named ``<program>:<op>``), and
the longest idle gaps labelled by what the host was doing in them.

The window is set by the benchmark: a host span named ``SYNC`` marks its
start (and ties the host clock to the trace clock), and its length is
given in seconds.  Device events are clipped to it.  Programs are the
jitted step functions of the program under test, matched by name
fragments listed in ``programs.json`` beside this file.
"""

from __future__ import annotations

import bisect
import glob
import json
from pathlib import Path

SYNC = "bench.sync"
PROGRAMS = json.loads((Path(__file__).parent / "programs.json").read_text())


def options():
    """Profiler options of a traced run: host spans and device activity,
    without the Python function tracer (its cost lands on the host threads
    that feed the chip) and without HLO protos (trace size)."""
    import jax

    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.enable_hlo_proto = False
    return o


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line):
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def planes(path: str) -> dict:
    """{plane name: {line name: [(event, start_ns, dur_ns), ...]}}; lines
    of one name (host threads often share one) are merged."""
    from jax.profiler import ProfileData

    out: dict = {}
    for p in ProfileData.from_file(path).planes:
        lines = out.setdefault(p.name, {})
        for ln in p.lines:
            lines.setdefault(ln.name, []).extend(_events(ln))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events, w0, w1):
    for name, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            yield name, a, b


def program_of(module: str) -> str | None:
    for prog, fragments in PROGRAMS.items():
        if any(f in module for f in fragments):
            return prog
    return None


def _label(gap, host):
    """The host event that overlaps ``gap`` most, outside the
    benchmark's own spans, with the share of the gap it covers."""
    g0, g1 = gap
    best, best_ov = "no host event", 0.0
    for name, s, e in host:
        ov = min(e, g1) - max(s, g0)
        if ov > best_ov:
            best, best_ov = name, ov
    return f"{best} ({100.0 * best_ov / (g1 - g0):.0f}% of the gap)"


def _op_name(event: str) -> str:
    return event.split(" = ", 1)[0]


def reduce(tree: dict, window_s: float, *, top: int = 10) -> dict:
    """Numbers of one traced window from ``planes()`` output."""
    sync = [s for p in tree.values() for evs in p.values()
            for n, s, _ in evs if n == SYNC]
    if not sync:
        raise ValueError(f"no {SYNC!r} span in the trace")
    w0 = min(sync)
    w1 = w0 + window_s * 1e9
    devices = {n: p for n, p in tree.items()
               if n.startswith("/device:") and "XLA Ops" in p}
    if not devices:
        raise ValueError("no device plane with XLA ops in the trace")
    host = [(n, s, s + d) for pname, p in tree.items()
            if pname.startswith("/host:CPU") for evs in p.values()
            for n, s, d in evs if d > 0 and not n.startswith("bench.")]
    busy, ops, progs, gaps = [], {}, {}, []
    seen = {n for p in devices.values()
            for n, _, _ in p.get("XLA Modules", [])}
    for p in devices.values():
        mods = []
        for name, a, b in _clip(p.get("XLA Modules", []), w0, w1):
            prog = program_of(name)
            mods.append((a, b, prog or "other"))
            if prog is None:
                continue
            c = progs.setdefault(prog, {"calls": 0, "seconds": 0.0})
            c["calls"] += 1
            c["seconds"] += (b - a) / 1e9
        mods.sort()
        starts = [m[0] for m in mods]
        op_iv = []
        for name, a, b in _clip(p["XLA Ops"], w0, w1):
            op_iv.append((a, b))
            i = bisect.bisect_right(starts, a) - 1
            prog = mods[i][2] if i >= 0 and a < mods[i][1] else "other"
            key = f"{prog}:{_op_name(name)}"
            ops[key] = ops.get(key, 0.0) + (b - a) / 1e9
        merged = union(op_iv)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        edges = [w0, *[x for iv in merged for x in iv], w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_label(g, host), (g[1] - g[0]) / 1e9] for g in gaps[:top]]
    return {
        "t0_ns": w0,
        "window_s": window_s,
        "devices": len(devices),
        "busy_s": sum(busy) / len(busy),
        "programs": progs,
        # programs with a module anywhere in the trace, and the modules
        # that match no program: a program missing here is a name fault
        "matched": sorted({program_of(n) for n in seen} - {None}),
        "unmatched": sorted(n for n in seen if program_of(n) is None)[:20],
        "device_ops": sorted(([n, s] for n, s in ops.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": idle,
    }
