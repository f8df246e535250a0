"""The benchmark's data: every name in ``BENCHMARK.json`` resolves to its
files, and the load generator offers every seed the same work."""

import json
import re
from collections import Counter

import loadgen
import run

BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_name_resolves():
    for w in BENCH["workloads"]:
        cell = run.Cell.load(run.ROOT, w["name"])
        assert (run.BENCH / "models" / f"{cell.conf['reference']}.py").exists()
        assert cell.end_to_end and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert (run.BENCH / "metrics" / f"{m['name']}.py").exists(), m
        for g in cell.traffic["groups"]:
            assert g["name"] in cell.declared["declared_ms"]
    for c in BENCH["configs"]:
        assert (run.ROOT / c["file"]).exists()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))


def test_moves_are_reported_where_listed():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        target = e2e[m["moves"]]
        for c in m.get("workloads", cells):
            assert c in target.get("workloads", cells), (m["name"], c)


def test_seeds_get_the_same_work():
    """Sizes and release times are the mix's own; the seed draws only the
    prompt tokens (and the weights)."""
    seconds = BENCH["run_seconds"]
    for w in BENCH["workloads"]:
        cell = run.Cell.load(run.ROOT, w["name"])
        streams = loadgen.streams(cell.traffic,
                                  cell.declared["period_scale_ms"])
        jobs = loadgen.schedule(cell.traffic, streams, seconds)
        sizes = Counter((j.prompt_len, j.steps) for j in jobs)
        assert len(sizes) > len(jobs) // 2  # the mix's spread of sizes
        for s in streams:
            dues = [j.due_s for j in jobs if j.stream == s.name]
            assert dues and max(dues) < seconds
            assert min(y - x for x, y in zip(dues, dues[1:] or
                                             [dues[0] + 1e9])) >= (
                s.period_ms / 1e3 * (1 - 1e-9))
