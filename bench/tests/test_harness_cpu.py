"""CPU rehearsal of a whole benchmark run at a tiny size.

``run.serve`` is the harness without its look for a chip: it is driven
here on the CPU with a reduced GQA configuration and a short mix, through
admission, precompile, warm-up, the open-loop window, the comparison with
the reference and every end-to-end reader.  Then the timed path is broken
underneath, once per fault a served cell can have, and ``correct`` has to
come out false.  The command itself still refuses the CPU.
"""

import copy
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import run
from repro.serving.engine import ServeEngine

CONF = {
    "arch": "internlm2_1_8b", "reference": "gqa", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 128,
    "rms_norm_eps": 1e-5, "rope_theta": 1000000, "tie_word_embeddings": False,
}
TRAFFIC = {
    "groups": [{"name": "rt", "streams": 3, "prompt_len": [6, 20],
                "steps": [4, 8]}],
    "period_spread": 0.05, "deadline_over_period": 1.0, "jitter_mean": 0.1,
    "layout_seed": 7, "jobs_per_stream": 8,
    "engine": {"max_seq": 64, "max_batch": 4, "kv_block_size": 8,
               "servers": 1, "work_stealing": False, "max_buckets": 2},
    "check_tokens": 30, "trace_seconds": 1,
}
# set from readings at this size, as the cells' limits are: over seeds
# 1-8 of ``dense_cell`` sound bfloat16 runs read at most 0.0219 and the
# float8 control at least 0.193 (test_control); a broken path reads more
LIMIT = 0.06


def cell(declared=None, **traffic):
    e2e = [{"name": n, "unit": u} for n, u in
           (("setup_s", "s"), ("response_p95_ms", "ms"),
            ("ms_per_token_p50", "ms"))]
    t = copy.deepcopy(TRAFFIC)
    t.update(traffic)
    return run.Cell(name="cpu-rehearsal", chips=1, conf=dict(CONF),
                    traffic=t,
                    declared=declared or {
                        "declared_ms": {"rt": {"prefill": 20.0,
                                               "decode": 10.0}},
                        "period_scale_ms": 1500.0,
                        "limits": {"max_logit_gap": LIMIT}},
                    end_to_end=e2e, per_layer=[])


def serve(c=None, seed=3, seconds=4.0):
    return run.serve(c or cell(), seed, seconds, False, jax.devices()[:1])


def dense_cell():
    """Long jobs on short periods, so that decode steps of several streams
    share device calls, and every served token is checked."""
    return cell(
        declared={"declared_ms": {"rt": {"prefill": 2.0, "decode": 1.0}},
                  "period_scale_ms": 250.0,
                  "limits": {"max_logit_gap": LIMIT}},
        groups=[{"name": "rt", "streams": 4, "prompt_len": [6, 12],
                 "steps": [24, 40]}],
        jobs_per_stream=40, check_tokens=100000)


def test_sound_run_is_correct():
    res = serve()
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 6 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "response_p95_ms",
                                   "ms_per_token_p50"}
    assert list(res)[-1] == "checks"
    assert res["window_events"]["tick_late_max_ms"] >= 0
    assert res["checks"]["max_logit_gap"]["value"] < LIMIT / 3


def _decode_paged(fault):
    real = ServeEngine._decode_paged_impl

    def broken(self, params, packed, pools):
        logits, new_pools = real(self, params, packed, pools)
        return fault(logits, pools, new_pools)

    return broken


FAULTS = {
    # the step returns the cache it was given: no token's KV is written
    "state_unchanged": lambda lg, old, new: (lg, old),
    # the second half of each decode batch gets the first row's answer
    "half_batch_left_out": lambda lg, old, new: (
        lg.at[lg.shape[0] // 2:].set(lg[0]) if lg.shape[0] > 1 else lg, new),
    # every row's token is altered where it is produced
    "token_altered": lambda lg, old, new: (jnp.roll(lg, 1, axis=-1), new),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_path_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(ServeEngine, "_decode_paged_impl",
                        _decode_paged(FAULTS[fault]))
    res = serve(dense_cell(), seed=4)
    assert not res["correct"], res["checks"]


def test_dense_run_batches_and_is_correct():
    res = serve(dense_cell(), seed=5)
    assert res["correct"], res["checks"]


def test_command_refuses_the_cpu(tmp_path):
    out = subprocess.run(
        [sys.executable, str(run.BENCH / "run.py"), "--workload",
         "internlm2-rt-decode", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env={"JAX_PLATFORMS": "cpu",
                                             "PATH": "/usr/bin:/bin",
                                             "HOME": str(tmp_path)},
        cwd=tmp_path, timeout=300)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
