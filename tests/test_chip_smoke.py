"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
phases pass when driven from here at a tiny size on CPU devices."""

import copy
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.launch import serve

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load_smoke()


@pytest.fixture(scope="module")
def tiny():
    return serve.init_model("internlm2_1_8b", reduced=True, seed=0)


def test_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "no TPU" in res.stderr


def test_compile_cache_dir(monkeypatch):
    """The cache follows JAX_COMPILATION_CACHE_DIR (left to JAX) and is
    otherwise the fixed .jax_cache/ at the repository root."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *kv: updates.append(kv))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
    assert enable_compile_cache() == "/cache/from/env"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert enable_compile_cache() == str(ROOT / ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))]


def test_full_size_streams_admit_on_one_server(tiny):
    """The four-chip phase's reference admits all four full-size streams on
    one server (admission prices the declared costs, not the model)."""
    cfg, params = tiny
    wl = serve.make_workload(cfg, serve.FULL, streams=4, requests=2, seed=0)
    eng = serve.build_engine(cfg, params, serve.REDUCED)
    try:
        assert len(serve.admit(eng, wl)) == 4
    finally:
        eng.close()


def test_one_chip_phase_tiny(smoke, tiny, capsys):
    cfg, params = tiny
    summary = smoke.one_chip(cfg, params, serve.REDUCED, seed=0)
    out = capsys.readouterr().out
    # on the CPU both paths run the same arithmetic: no divergence at all
    assert summary == {"exact": 6, "diverged": 0}
    assert "smoke timings, not benchmark numbers" in out
    assert "compile_s=" in out and "compare rule:" in out


def test_compare_judges_divergences(smoke, tiny):
    """A token far from the reference's top logit fails the comparison; the
    same divergence passes once the near-tie limit admits it."""
    cfg, params = tiny
    wl = serve.make_workload(cfg, serve.REDUCED, streams=1, requests=1,
                             seed=1)
    ref = serve.build_engine(cfg, params, serve.REDUCED, batching=False)
    try:
        assert serve.admit(ref, wl) == ["stream0"]
        want = serve.run_clients(ref, wl)
    finally:
        ref.close()
    got = copy.deepcopy(want)
    r = got["stream0"][0]
    r.tokens[2] = (r.tokens[2] + 1) % cfg.vocab_size
    assert smoke.compare(cfg, params, serve.REDUCED, wl, want, want) == {
        "exact": 1, "diverged": 0}
    with pytest.raises(AssertionError):
        smoke.compare(cfg, params, serve.REDUCED, wl, got, want)
    loose = _load_smoke()
    loose.TIE_SIGMAS = 1e9
    assert loose.compare(cfg, params, serve.REDUCED, wl, got, want) == {
        "exact": 0, "diverged": 1}
    r.tokens[0] = cfg.vocab_size  # out of range: always a failure
    with pytest.raises(AssertionError):
        loose.compare(cfg, params, serve.REDUCED, wl, got, want)


FOUR_CHIP = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import importlib.util
    from repro.launch import serve
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    cfg, params = serve.init_model("internlm2_1_8b", reduced=True, seed=0)
    summary = smoke.four_chips(cfg, params, serve.REDUCED, seed=0)
    assert summary == {"exact": 8, "diverged": 0}, summary
    print("FOUR-OK")
""")


def test_four_chip_phase_tiny():
    env = dict(os.environ, PYTHONPATH="src")
    res = subprocess.run([sys.executable, "-c", FOUR_CHIP], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "FOUR-OK" in res.stdout
    assert "migrated stream0 live" in res.stdout
