"""Spans and counters of the served path (``core.spans``): how the
recorder nests and bounds them, what the servers and the paged engine
record, that nothing is recorded while tracing is off, and that the spans
sit on the profiler's clock."""

import glob
import threading
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs.registry import get_config
from repro.core.dispatch.batching import BatchingServer
from repro.core.spans import FIELDS, Recorder
from repro.models import model as M
from repro.serving.engine import ServeEngine, StreamSpec

STEPS = 4
SYNC = "spans.sync"


def named(spans, name, **attrs):
    out = [dict(zip(FIELDS, s)) for s in spans if s[0] == name]
    return [s for s in out
            if all(s["attrs"].get(k) == v for k, v in attrs.items())]


# -- the recorder alone -----------------------------------------------------
def test_spans_nest_and_share_the_job_id():
    rec = Recorder()
    job = rec.begin_job(prompt_len=4, steps=2)
    a = rec.begin("a")
    b = rec.begin("b", rows=1)
    rec.tag(padded=2)
    rec.end(b)
    rec.record("instant", 1.0, 1.0)
    rec.end(a)
    assert rec.current_job() == job[1]
    rec.end(job)
    got = {s["name"]: s for s in (dict(zip(FIELDS, s)) for s in rec.spans)}
    j = got["job"]
    assert j["job"] == j["id"] and j["parent"] == 0
    assert got["a"]["parent"] == j["id"]
    assert got["b"]["parent"] == got["a"]["id"]
    assert got["instant"]["parent"] == got["a"]["id"]
    assert {s["job"] for s in got.values()} == {j["id"]}
    assert got["b"]["attrs"] == {"rows": 1, "padded": 2}
    assert j["attrs"] == {"prompt_len": 4, "steps": 2}
    assert j["start"] <= got["a"]["start"] <= got["b"]["start"]
    assert got["b"]["end"] <= got["a"]["end"] <= j["end"]
    assert rec.current_job() == 0


def test_threads_keep_their_own_nesting():
    rec = Recorder()
    job = rec.begin_job()
    seen = {}

    def other():
        s = rec.begin("server.call", job=job[1])
        inner = rec.begin("engine.device")
        seen["job"] = rec.current_job()
        rec.end(inner)
        rec.end(s)

    t = threading.Thread(target=other)
    t.start()
    t.join(10)
    assert not t.is_alive()
    rec.end(job)
    call = named(rec.spans, "server.call")[0]
    dev = named(rec.spans, "engine.device")[0]
    assert call["parent"] == 0 and call["job"] == job[1]
    assert dev["parent"] == call["id"] and dev["job"] == job[1]
    assert seen["job"] == job[1]


def test_end_closes_spans_an_exception_left_open():
    rec = Recorder()
    job = rec.begin_job()
    rec.begin("job.turnaround")  # never ended: its job failed
    rec.end(job)
    assert [s[0] for s in rec.spans] == ["job"]
    assert rec.current_job() == 0
    rec.end(job)  # no longer open: nothing more is kept
    assert len(rec.spans) == 1
    rec.end(rec.begin("after"))
    after = named(rec.spans, "after")[0]
    assert after["parent"] == 0 and after["job"] == 0


def test_ring_stays_bounded():
    rec = Recorder(capacity=8)
    for i in range(100):
        rec.record("s", float(i), float(i), i=i)
    assert len(rec.spans) == 8
    assert [s[6]["i"] for s in rec.spans] == list(range(92, 100))


def test_no_annotation_without_a_profiler_trace(monkeypatch):
    """With no profiler trace running, a span makes no annotation; with
    one running, each ``begin`` enters one and ``end`` leaves it."""
    made = []

    class Note:
        is_enabled = staticmethod(lambda: tracing)

        def __init__(self, name):
            made.append([name, 0])

        def __enter__(self):
            made[-1][1] += 1

        def __exit__(self, *exc):
            made[-1][1] -= 1

    monkeypatch.setattr("repro.core.spans.TraceAnnotation", Note)
    rec = Recorder()
    tracing = False
    rec.end(rec.begin("off"))
    assert made == [] and named(rec.spans, "off")
    tracing = True
    rec.end(rec.begin("on"))
    assert made == [["on", 0]]


def test_counters():
    rec = Recorder()
    rec.add("srv.ready")
    rec.add("srv.ready", 2)
    rec.add("srv.ready", -1)
    assert rec.count("srv.ready") == 2 and rec.count("other") == 0


# -- the batching server ----------------------------------------------------
def test_batching_server_spans():
    """A blocker holds the server while three same-key requests queue; they
    then ride one call.  One ``server.queue`` per request, one
    ``server.call`` per device call."""
    rec = Recorder()
    server = BatchingServer(max_batch=4, name="srv")
    server.set_recorder(rec)
    rec.add("srv.ready", 3)
    gate = threading.Event()
    try:
        blocker = server.submit(lambda: gate.wait(10), job=7,
                                phase="prefill")
        time.sleep(0.05)  # the blocker is in flight
        reqs = [server.submit_batch(i, run_batch=lambda ps: [p * 10
                                                             for p in ps],
                                    batch_key="k", job=i + 1,
                                    phase="decode")
                for i in range(3)]
        gate.set()
        blocker.wait(10)
        assert [r.wait(10) for r in reqs] == [0, 10, 20]
    finally:
        server.shutdown()
    queue = named(rec.spans, "server.queue")
    assert sorted(q["job"] for q in queue) == [1, 2, 3, 7]
    assert all(q["parent"] == q["job"] for q in queue)
    assert {q["attrs"]["phase"] for q in queue if q["job"] != 7} == {"decode"}
    calls = named(rec.spans, "server.call")
    assert len(calls) == 2
    pre = named(rec.spans, "server.call", phase="prefill")[0]
    dec = named(rec.spans, "server.call", phase="decode")[0]
    assert pre["attrs"]["rows"] == 1 and pre["job"] == 7
    assert dec["attrs"]["rows"] == 3 and dec["attrs"]["ready"] == 3
    assert dec["attrs"]["jobs"] == (1, 2, 3)
    for q in queue:
        call = pre if q["job"] == 7 else dec
        assert q["start"] <= q["end"] <= call["end"]
        assert q["end"] >= call["start"] - 1e-3
    assert named(rec.spans, "server.idle")


def test_an_idle_server_traces_from_the_switch():
    """Idle since before tracing was turned on, a server opens its
    ``server.idle`` span at the switch, and closes it at the switch off."""
    rec = Recorder()
    server = BatchingServer(name="srv")
    try:
        time.sleep(0.02)
        t_on = time.monotonic()
        server.set_recorder(rec)
        time.sleep(0.05)
        server.set_recorder(None)
        t_off = time.monotonic()
        deadline = time.monotonic() + 10
        while not rec.spans and time.monotonic() < deadline:
            time.sleep(0.001)
    finally:
        server.shutdown()
    (idle,) = named(rec.spans, "server.idle")
    assert t_on <= idle["start"] < t_on + 0.02
    assert t_off - 0.02 < idle["end"] < t_off + 0.02


# -- the paged engine, traced under the profiler -----------------------------
def _spec(name, prio):
    return StreamSpec(name=name, priority=prio, period_ms=8000.0,
                      deadline_ms=8000.0, prefill_ms=50.0, decode_ms=5.0,
                      decode_steps=STEPS)


def _serve_two(eng, prompt):
    results = {}

    def worker(n):
        results[n] = eng.generate(n, prompt, steps=STEPS)

    threads = [threading.Thread(target=worker, args=(n,))
               for n in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads)
    return results


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Two streams on one paged server with one decode slot, served once
    (untraced, to compile) and once with the recorder on inside a CPU
    profiler trace."""
    cfg = get_config("internlm2_1_8b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    eng = ServeEngine(cfg, params, max_seq=32, batching=True, paged=True,
                      max_batch=1)
    prompt = np.array([[1, 2, 3, 4, 5]], np.int32)
    try:
        for i, n in enumerate(("a", "b")):
            assert eng.admit(_spec(n, 2 - i)).admitted
        want = _serve_two(eng, prompt)
        out = str(tmp_path_factory.mktemp("trace"))
        jax.profiler.start_trace(out)
        try:
            with TraceAnnotation(SYNC):
                t_sync = time.monotonic()
            rec = eng.enable_tracing()
            results = _serve_two(eng, prompt)
            eng.disable_tracing()
            spans = list(rec.spans)
        finally:
            jax.profiler.stop_trace()
        path = sorted(glob.glob(f"{out}/**/*.xplane.pb", recursive=True))[-1]
        events = [(e.name, e.start_ns, e.duration_ns)
                  for p in ProfileData.from_file(path).planes
                  if p.name.startswith("/host:CPU")
                  for ln in p.lines for e in ln.events]
        yield dict(engine=eng, prompt=prompt, want=want, results=results,
                   rec=rec, spans=spans, t_sync=t_sync, events=events)
    finally:
        eng.close()


def test_traced_tokens_unchanged(served):
    for n in ("a", "b"):
        assert served["results"][n].tokens == served["want"][n].tokens


def test_one_slot_makes_a_job_wait(served):
    waits = named(served["spans"], "job.slot_wait")
    assert len(waits) == 2
    assert max(w["end"] - w["start"] for w in waits) > 1e-3
    res = served["results"].values()
    assert max(r.slot_wait_s for r in res) > 1e-3
    jobs = {j["id"]: j for j in named(served["spans"], "job")}
    for w in waits:
        assert w["parent"] == w["job"] and w["job"] in jobs


def test_a_jobs_spans_share_its_id(served):
    spans = served["spans"]
    jobs = named(spans, "job")
    assert len(jobs) == 2
    for j in jobs:
        assert j["attrs"] == {"prompt_len": 5, "steps": STEPS}
        mine = [s for s in map(lambda t: dict(zip(FIELDS, t)), spans)
                if s["job"] == j["id"]]
        phases = [s["attrs"]["phase"] for s in mine
                  if s["name"] == "server.queue"]
        assert sorted(phases) == sorted(["prefill", "insert"]
                                        + ["decode"] * STEPS)
        assert sum(s["name"] == "job.turnaround" for s in mine) == STEPS + 1
        for s in mine:
            assert j["start"] <= s["start"] and s["end"] <= j["end"] + 1e-3
    calls = named(spans, "server.call", phase="decode")
    assert len(calls) == 2 * STEPS  # one slot: no decode call is shared
    assert all(c["attrs"]["rows"] == 1 and c["attrs"]["padded"] == 1
               and 1 <= c["attrs"]["ready"] <= 2 for c in calls)
    ids = {c["id"] for c in calls}
    for name in ("engine.stage", "engine.device", "engine.fetch"):
        assert len([s for s in named(spans, name) if s["parent"] in ids]) \
            == 2 * STEPS
    assert served["rec"].counters == {served["engine"].pool.servers[0].name
                                      + ".ready": 0}


def test_first_token_between_prefill_and_first_decode_submit(served):
    spans = served["spans"]
    by_job = {j["id"]: j for j in named(spans, "job")}
    prefill_calls = {c["id"]: c for c in named(spans, "server.call",
                                               phase="prefill")}
    fetched = {prefill_calls[f["parent"]]["job"]: f["end"]
               for f in named(spans, "engine.fetch")
               if f["parent"] in prefill_calls}
    stamps = sorted(r.first_token_at for r in served["results"].values())
    instants = named(spans, "job.first_token")
    assert sorted(i["start"] for i in instants) == stamps
    for i in instants:
        assert i["job"] in by_job and i["start"] == i["end"]
        first_decode = min(q["start"] for q in named(spans, "server.queue",
                                                     phase="decode")
                           if q["job"] == i["job"])
        assert fetched[i["job"]] <= i["start"] <= first_decode


def test_off_records_nothing_and_never_calls_the_recorder(served,
                                                          monkeypatch):
    eng, rec = served["engine"], served["rec"]
    assert eng.recorder is None
    assert all(s.recorder is None for s in eng.pool.servers)
    # a server idle since before tracing was turned off closes that span
    # when it next wakes; from then on it records nothing
    _serve_two(eng, served["prompt"])
    kept = len(rec.spans)

    def boom(*a, **k):
        raise AssertionError("recorder called while tracing is off")

    for method in ("begin", "begin_job", "end", "tag", "record",
                   "current_job", "add", "count"):
        monkeypatch.setattr(Recorder, method, boom)
    results = _serve_two(eng, served["prompt"])
    assert len(rec.spans) == kept
    for n in ("a", "b"):
        assert results[n].tokens == served["want"][n].tokens
        assert results[n].first_token_at is not None


def test_mirrored_annotations_agree_with_the_spans(served):
    """Through the anchor (a profiler span stamped with the monotonic
    clock), each span begun on the served path lies within 1 ms of its
    profiler annotation."""
    events = served["events"]
    w0 = min(s for n, s, _ in events if n == SYNC)
    t_sync = served["t_sync"]
    mirrored = ("server.call", "server.idle", "engine.stage",
                "engine.device", "engine.fetch", "job.slot_wait",
                "job.turnaround")
    checked = 0
    for s in map(lambda t: dict(zip(FIELDS, t)), served["spans"]):
        if s["name"] not in mirrored:
            continue
        a = w0 + (s["start"] - t_sync) * 1e9
        b = w0 + (s["end"] - t_sync) * 1e9
        near = min((e for e in events if e[0] == s["name"]),
                   key=lambda e: abs(e[1] - a))
        assert abs(near[1] - a) < 1e6, s
        assert abs(near[1] + near[2] - b) < 1e6, s
        checked += 1
    assert checked >= 8 * STEPS
    assert not [e for e in events if e[0] in ("job", "server.queue",
                                              "job.first_token")]


def test_moe_engine_tags_whether_a_call_reads_only_routed_experts(served):
    """A MoE engine tags each device call ``moe_routed``: a one-row decode
    call selects 2 of the reduced config's 4 experts and does, a prefill of
    the whole prompt does not; a GQA engine tags none."""
    assert not any("moe_routed" in s["attrs"]
                   for s in named(served["spans"], "engine.device"))
    cfg = get_config("deepseek_v2_lite_16b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    eng = ServeEngine(cfg, params, max_seq=32, batching=True, paged=True,
                      max_batch=1)
    try:
        assert eng.admit(_spec("m", 1)).admitted
        rec = eng.enable_tracing()
        eng.generate("m", served["prompt"], steps=STEPS)
        eng.disable_tracing()
        spans = list(rec.spans)
    finally:
        eng.close()
    tags = {}
    for phase in ("prefill", "decode"):
        calls = {c["id"] for c in named(spans, "server.call", phase=phase)}
        tags[phase] = [s["attrs"].get("moe_routed")
                       for s in named(spans, "engine.device")
                       if s["parent"] in calls]
    assert tags == {"prefill": [False], "decode": [True] * STEPS}
