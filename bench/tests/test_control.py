"""The control at a size a test run can hold: the float8 reference, put
in the program's place on the same served tokens, reads a far wider
logit gap than the bfloat16 program, and the rule that decides
``correct`` finds it not correct (the chip readings at the cells' own
sizes are in PERF.md)."""

import jax

import run
from test_harness_cpu import dense_cell


def test_control_is_not_correct():
    calls: dict = {}
    res = run.serve(dense_cell(), 6, 4.0, False, jax.devices()[:1],
                    control=True, calls=calls)
    prog = res["checks"]["max_logit_gap"]["value"]
    ctl = res["checks"]["control_max_logit_gap"]["value"]
    assert calls and res["correct"]
    assert res["control_correct"] is False, (prog, ctl)
    assert ctl > 3 * max(prog, 1e-3), (prog, ctl)
