"""Per-layer readers on a run made by hand: the shares of the peak stay
under 100% when the device time is what the work needs, and a reader with
nothing to read returns None."""

import types

import pytest

import metrics_io
import run
from test_harness_cpu import cell

CONF = dict(run.Cell.load(run.ROOT, "internlm2-rt-decode").conf)


def job(prompt_len, steps, start, end):
    return run.JobRecord("rt0", 0, start - 0.01, prompt_len, steps,
                         start=start, end=end, first_token=1,
                         tokens=[1] * steps, deadline_ms=1e4)


def fake_run(trace, jobs):
    c = cell()
    c.conf = CONF
    return metrics_io.Run(
        cell=c, jobs=jobs, bounds={"rt0": 1000.0},
        delta={"phases": {"decode": {"calls": 10, "rows": 15, "timed": 10,
                                     "seconds": 0.07}},
               "queue_waits_s": [0.001, 0.002], "batch_sizes": [1, 2],
               "migrations": 0},
        trace=trace, setup_s=10.0, device_kind="TPU v5 lite")


def read(name, r):
    return metrics_io.load_reader(run.BENCH / "metrics", name)(r)


def test_decode_shares_from_shapes():
    # 100 one-row decode calls of 300-context tokens in 1 s of trace: the
    # least time is the weights and cache read once per call at 819 GB/s
    # 101 tokens over the job, the prefill's first: its 100 decode tokens
    # come out in the traced second
    j = job(300, 100, start=-1.0 / 100, end=1.0 - 1e-9)
    bytes_per_call = 2 * (1_889_110_016 - 189_530_112 + 2048) + 98_304 * 301
    device_s = 100 * bytes_per_call / 819e9 / 0.8  # 80% of the roofline
    trace = {"sync_monotonic": 0.0, "window_s": 1.0, "busy_s": device_s,
             "programs": {"decode": {"calls": 100, "seconds": device_s}},
             "matched": ["decode"], "unmatched": []}
    r = fake_run(trace, [j])
    assert read("decode_paged_roofline", r) == pytest.approx(80.0, rel=0.02)
    assert 0 < read("model.decode_mfu_pct", r) < 5
    assert read("device.idle_pct", r) == pytest.approx(
        100 * (1 - device_s), rel=1e-6)


def test_counter_readers():
    # due 0.99, generate returned at 1.2: 210 ms over 11 served tokens
    r = fake_run(None, [job(300, 10, 1.0, 1.2)])
    assert read("server.decode_rows_mean", r) == 1.5
    assert read("engine.decode_call_ms", r) == pytest.approx(7.0)
    assert read("response_p95_ms", r) == pytest.approx(210.0)
    assert read("ms_per_token_p50", r) == pytest.approx(210.0 / 11)
    assert read("ms_per_token_p95", r) == pytest.approx(210.0 / 11)
    assert read("admission.resp_over_bound_max", r) == pytest.approx(0.21)
    assert read("server.queue_wait_p95_ms.prefill", r) == pytest.approx(1.95)
    assert read("loadgen.release_lag_p95_ms", r) == pytest.approx(10.0)


def test_nothing_to_read_is_none():
    r = fake_run(None, [])
    for name in ("decode_paged_roofline", "model.decode_mfu_pct",
                 "model.prefill_mfu_pct", "device.idle_pct",
                 "engine.prefill_call_ms",
                 "response_p95_ms", "ms_per_token_p50", "ms_per_token_p95"):
        assert read(name, r) is None, name


def test_program_missing_from_the_trace_raises():
    # the decode program ran, but no module of the prefill program is in
    # the whole trace: its name fragments in programs.json match nothing
    trace = {"sync_monotonic": 0.0, "window_s": 1.0, "busy_s": 0.5,
             "programs": {"decode": {"calls": 3, "seconds": 0.02}},
             "matched": ["decode"], "unmatched": ["jit_renamed_prefill"]}
    r = fake_run(trace, [job(300, 10, 0.1, 0.5)])
    with pytest.raises(LookupError, match="jit_renamed_prefill"):
        read("model.prefill_mfu_pct", r)
    # a program in the trace that made no call inside the window reads None
    trace["matched"] = ["decode", "prefill"]
    assert read("model.prefill_mfu_pct", r) is None
