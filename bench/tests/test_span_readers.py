"""The span readers (``metrics/<name>.py`` of ``span_metrics.json``) on
spans made by hand; the readers the benchmark already has, unchanged on the
recorded v5e trace; and a CPU rehearsal of ``run_spans.py``'s traced run."""

import json

import jax
import pytest

import metrics_io
import run
import run_spans
import spans_io
import trace_reduce
from test_harness_cpu import cell
from test_readers import fake_run, job, read
from test_trace_reduce import FIXTURE, WINDOW_S

ENTRIES = json.loads((run.BENCH / "span_metrics.json").read_text())
BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())


def test_entries_are_readers_for_the_cells():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    known = {m["name"] for m in BENCH["per_layer"]}
    for m in ENTRIES:
        assert (run.BENCH / "metrics" / f"{m['name']}.py").exists(), m
        assert m["name"] not in known
        assert set(m["workloads"]) <= cells
        assert set(m["workloads"]) <= set(e2e[m["moves"]]["workloads"])


def span(name, sid, start, end, *, parent=0, job=0, **attrs):
    return (name, sid, parent, job, start, end, attrs)


def spanned_run():
    """A 1 s window (anchor at monotonic 10.0, trace clock 5e9 ns) with two
    jobs, one prefill and insert for the first, two decode calls, one of
    them with a second job ready, and the device busy for 0.3 s."""
    trace = {"sync_monotonic": 10.0, "window_s": 1.0, "t0_ns": 5e9,
             "busy_s": 0.3, "busy_intervals": [(5.1e9, 5.3e9),
                                                (5.5e9, 5.6e9)]}
    r = fake_run(trace, [job(10, 3, 10.0, 10.9)])
    r.delta["spans"] = [
        span("job", 1, 10.0, 10.6, job=1),
        span("job", 2, 10.5, 10.8, job=2),
        span("job", 3, 9.0, 9.5, job=3),  # before the window
        span("job.slot_wait", 4, 10.0, 10.01, parent=1, job=1),
        span("job.slot_wait", 5, 10.5, 10.53, parent=2, job=2),
        span("server.queue", 6, 10.01, 10.02, parent=1, job=1,
             phase="prefill"),
        span("server.call", 7, 10.02, 10.1, job=1, phase="prefill", rows=1,
             ready=0),
        span("server.call", 8, 10.1, 10.104, job=1, phase="insert", rows=1,
             ready=0),
        span("job.first_token", 9, 10.105, 10.105, parent=1, job=1),
        span("server.queue", 10, 10.11, 10.1102, parent=1, job=1,
             phase="decode"),
        span("server.call", 11, 10.2, 10.31, job=1, phase="decode", rows=1,
             ready=1),
        span("engine.device", 12, 10.202, 10.3, parent=11, job=1),
        span("server.call", 13, 10.55, 10.61, job=2, phase="decode", rows=1,
             ready=2),
        span("engine.device", 14, 10.551, 10.6, parent=13, job=2),
        span("server.queue", 15, 10.54, 10.5404, parent=2, job=2,
             phase="decode"),
        span("job.turnaround", 16, 10.31, 10.35, parent=1, job=1),
        span("job.first_token", 17, 10.52, 10.52, parent=2, job=2),
        span("server.idle", 18, 10.4, 10.5),
    ]
    return r


def test_span_readers_on_spans_by_hand():
    r = spanned_run()
    r.trace = None  # untraced: the host-clock readers take every span
    assert read("engine.slot_wait_p95_ms", r) == pytest.approx(
        10 + 0.95 * 20)
    assert read("engine.slot_wait_p95_ms.prefill", r) == read(
        "engine.slot_wait_p95_ms", r)
    assert read("engine.first_token_p95_ms.prefill", r) == pytest.approx(
        20 + 0.95 * (105 - 20))
    assert read("engine.insert_call_ms.prefill", r) == pytest.approx(4.0)
    assert read("server.decode_queue_wait_p50_ms", r) == pytest.approx(0.3)
    assert read("server.decode_coalesce_pct", r) == pytest.approx(
        100 * 2 / 3)
    assert read("engine.decode_host_ms", r) == pytest.approx(
        (0.11 - 0.098 + 0.06 - 0.049) / 2 * 1e3)
    assert read("device.idle_in_jobs_pct", r) is None
    assert spans_io.decode_ready(r) == {1: 1, 2: 1}
    summ = spans_io.summary(r)
    assert summ["server.call/decode"] == [2, pytest.approx(85.0)]
    assert summ["job"] == [3, pytest.approx(1400 / 3)]
    assert spans_io.summary(r, traced=True) == {}


def test_device_readers_take_the_traced_window():
    r = spanned_run()
    # jobs open 10.0-10.8 on the monotonic clock: 0.8 s, of which the
    # device ran 0.1-0.3 and 0.5-0.6 s into the window
    split = spans_io.idle_in_jobs(r)
    assert split["jobs_s"] == pytest.approx(0.8)
    assert split["idle_s"] == pytest.approx(0.5)
    assert read("device.idle_in_jobs_pct", r) == pytest.approx(62.5)
    assert read("device.idle_in_jobs_pct.prefill", r) == pytest.approx(62.5)
    summ = spans_io.summary(r, traced=True)
    assert summ["server.call/decode"] == [2, pytest.approx(85.0)]
    assert summ["job"] == [2, pytest.approx(450.0)]  # job 3 began before
    assert spans_io.summary(r) == {"job": [1, pytest.approx(500.0)]}
    # every other span starts in the traced window: no host-clock reading
    for m in ENTRIES:
        if not m["name"].startswith("device."):
            assert read(m["name"], r) is None, m["name"]
    cov = split["covered_s"]
    assert cov["server.idle"] == pytest.approx(0.1)
    assert cov["job.turnaround"] == pytest.approx(0.04)
    assert cov["job.slot_wait"] == pytest.approx(0.01)
    assert cov["engine.device"] == pytest.approx(0.0)


def test_host_readers_leave_out_the_traced_window():
    """With the profiler on for the window's first half second, the
    host-clock readers read only what starts after it: the second job."""
    r = spanned_run()
    r.trace["window_s"] = 0.5
    assert read("engine.slot_wait_p95_ms", r) == pytest.approx(30.0)
    assert read("server.decode_coalesce_pct", r) == pytest.approx(50.0)
    assert read("engine.decode_host_ms", r) == pytest.approx(11.0)
    assert read("server.decode_queue_wait_p50_ms", r) == pytest.approx(0.4)
    assert read("engine.first_token_p95_ms.prefill", r) == pytest.approx(20.0)
    assert read("engine.insert_call_ms.prefill", r) is None
    assert spans_io.decode_ready(r) == {2: 1}


def test_span_readers_without_spans_read_none():
    for trace in (None, {"sync_monotonic": 0.0, "window_s": 1.0,
                         "t0_ns": 0.0, "busy_s": 0.5}):
        r = fake_run(trace, [job(300, 10, 0.1, 0.5)])
        for m in ENTRIES:
            assert read(m["name"], r) is None, m["name"]
    r = spanned_run()
    del r.trace["busy_intervals"]
    assert read("device.idle_in_jobs_pct", r) is None


def test_interval_arithmetic():
    x = [(0, 10), (20, 30)]
    y = [(5, 25)]
    assert spans_io.intersect(x, y) == [(5, 10), (20, 25)]
    assert spans_io.subtract(x, y) == [(0, 5), (25, 30)]
    assert spans_io.subtract(x, []) == x
    assert spans_io.measure(x) == 20


def test_existing_readers_read_the_fixture_as_before():
    """The readers the benchmark already has, on the recorded v5e trace:
    the values they read when the span readers were added."""
    reduced = trace_reduce.reduce(trace_reduce.planes(str(FIXTURE)),
                                  WINDOW_S)
    assert "busy_intervals" not in reduced
    reduced["sync_monotonic"] = 0.0
    jobs = [job(300, 100, start=-0.001, end=WINDOW_S - 1e-9),
            job(2000, 3, start=0.0, end=WINDOW_S / 2)]
    r = fake_run(reduced, jobs)
    got = {n: read(n, r) for n in ("device.idle_pct", "model.decode_mfu_pct",
                                   "decode_paged_roofline",
                                   "model.prefill_mfu_pct")}
    want = json.loads((run.BENCH / "tests" / "data"
                       / "fixture_readings.json").read_text())
    assert got == pytest.approx(want, rel=1e-9)


def _device_from_host(monkeypatch):
    """The CPU trace has no device plane: make one of the host's
    ``engine.device`` annotations, so the device counts as busy while the
    engine waits on it."""
    planes = trace_reduce.planes

    def with_device(path):
        tree = planes(path)
        dev = [e for line in tree.get("/host:CPU", {}).values()
               for e in line if e[0] == "engine.device"]
        tree["/device:CPU:0"] = {"XLA Ops": dev, "XLA Modules": []}
        return tree

    monkeypatch.setattr(trace_reduce, "planes", with_device)


@pytest.mark.parametrize("traced", [True, False])
def test_run_on_cpu_reads_the_span_metrics(monkeypatch, traced):
    """``run.serve`` on the CPU under ``run_spans.hooked``, as
    ``run_spans.py`` runs it, traced or with ``--spans 1``: the recorder is
    on for the window alone, and the result line holds every span metric
    that the run can read."""
    _device_from_host(monkeypatch)
    c = cell(trace_seconds=2)
    (c.per_layer if traced else c.end_to_end).extend(ENTRIES)
    with run_spans.hooked() as state:
        res = run.serve(c, 3, 5.0, traced, jax.devices()[:1])
    assert res["correct"], res["checks"]
    assert (run.run_clients.__name__, run.stats_delta.__name__,
            trace_reduce.reduce.__name__, jax.profiler.stop_trace.__name__) \
        == ("run_clients", "stats_delta", "reduce", "stop_trace")
    host = {m["name"] for m in ENTRIES if not m["name"].startswith("device.")}
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(got) >= host
    assert 0 < got["server.decode_coalesce_pct"] <= 100
    assert got["engine.insert_call_ms.prefill"] > 0
    assert got["engine.decode_host_ms"] > 0
    # one ``job`` span for each of the window's jobs: none of the warm-up
    assert sum(s[0] == "job" for s in state["delta"]["spans"]) \
        == res["attempted"]
    if traced:
        assert 0 <= got["device.idle_in_jobs_pct"] < 100
        # the gaps of 20 ms and more, the window's last one too, lie in a
        # server's idle span
        gaps = res["breakdown"]["idle_gaps"]
        assert all(label.startswith("server.idle") for label, s in gaps
                   if s >= 0.02), gaps
    else:
        assert "device.idle_in_jobs_pct" not in got
        assert "trace" not in state
