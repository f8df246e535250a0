"""``trace_reduce`` on a small trace recorded on a TPU v5e by
``record_trace.py``: three decode-named calls, three lambda calls and one
unlisted call, with host sleeps of 20 ms and 10 ms between them."""

from pathlib import Path

import pytest

import trace_reduce as tr

FIXTURE = Path(__file__).parent / "data" / "v5e_fixture.xplane.pb"
WINDOW_S = 0.10110578500000145  # as record_trace.py printed it


@pytest.fixture(scope="module")
def tree():
    return tr.planes(str(FIXTURE))


@pytest.fixture(scope="module")
def reduced(tree):
    return tr.reduce(tree, WINDOW_S)


def window(tree):
    w0 = min(s for p in tree.values() for evs in p.values()
             for n, s, _ in evs if n == tr.SYNC)
    return w0, w0 + WINDOW_S * 1e9


def test_busy_is_the_union_of_device_ops(tree, reduced):
    w0, w1 = window(tree)
    ops = sorted((max(s, w0), min(s + d, w1))
                 for _, s, d in tree["/device:TPU:0"]["XLA Ops"]
                 if s + d > w0 and s < w1)
    busy, end = 0.0, w0
    for a, b in ops:  # sweep: count each instant once
        if b > end:
            busy += b - max(a, end)
            end = b
    assert reduced["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert 0 < reduced["busy_s"] < reduced["window_s"] == WINDOW_S
    assert reduced["devices"] == 1


def test_programs_by_name(tree, reduced):
    w0, w1 = window(tree)
    mods = tree["/device:TPU:0"]["XLA Modules"]
    for prog, frag in (("decode", "_decode_paged_impl"),
                       ("prefill", "_lambda")):
        inside = [d for n, s, d in mods if frag in n and w0 <= s
                  and s + d <= w1]
        got = reduced["programs"][prog]
        assert got["calls"] == len(inside) >= 2
        assert got["seconds"] == pytest.approx(sum(inside) / 1e9)
    assert reduced["programs"]["prefill"]["calls"] == 3
    assert "unlisted" not in str(reduced["programs"])
    assert reduced["matched"] == ["decode", "prefill"]
    assert any("unlisted" in m for m in reduced["unmatched"])
    assert tr.program_of("jit__insert_paged_impl(1)") == "insert"


def test_ops_per_program_and_gaps(reduced):
    ops = dict(reduced["device_ops"])
    assert all(":" in k and " = " not in k for k in ops)
    assert sum(v for k, v in ops.items() if k.startswith("prefill:")) == (
        pytest.approx(reduced["programs"]["prefill"]["seconds"], rel=1e-3))
    assert any(k.startswith("other:") for k in ops)
    gaps = [g for _, g in reduced["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    # the host slept 20 ms after each decode call: the three longest gaps
    assert all(0.019 < g < 0.03 for g in gaps[:3])
    assert all("% of the gap)" in label for label, _ in reduced["idle_gaps"])


def test_same_named_host_lines_are_merged(tree):
    host = tree["/host:CPU"]
    names = [n for evs in host.values() for n, _, _ in evs]
    assert tr.SYNC in names and "PjitFunction(_decode_paged_impl)" in names


def test_union():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                             (3, 4)]
