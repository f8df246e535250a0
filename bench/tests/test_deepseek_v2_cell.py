"""The ``deepseek-v2-lite`` configuration file against what the older
tests hold inline, and the DeepSeek-V2 reference's control at a CPU size.

``test_work.py`` counts work on an inline copy of the cut (``DSV2_CUT``),
and ``test_v5e_compile.py`` compiles the registry config cut to 7 layers:
both must be what ``bench/configs/deepseek-v2-lite.json`` states, so a
change to the file cannot leave them checking another model.
"""

import dataclasses
import json

import jax
import pytest

import run
import work
from repro.configs.registry import get_config
from test_harness_cpu import dense_cell
from test_work import DSV2_CUT

CONF = json.loads((run.BENCH / "configs" / "deepseek-v2-lite.json")
                  .read_text())
REF = run.load_module(run.BENCH / "models" / "deepseek_v2.py")

# the published configuration at CPU widths
CPU_CONF = {**CONF, "hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "kv_lora_rank": 32,
            "qk_rope_head_dim": 8, "qk_nope_head_dim": 16, "v_head_dim": 16,
            "n_routed_experts": 16, "n_shared_experts": 2,
            "moe_intermediate_size": 32, "num_hidden_layers": 3,
            "vocab_size": 128}
# set from readings at this size as the cells' limits are: over seeds 1-8
# of this cell sound bfloat16 runs read at most 0.0989 and the float8
# control at least 0.345
CPU_LIMIT = 0.2


def test_work_counts_the_file():
    assert {k: CONF[k] for k in DSV2_CUT} == DSV2_CUT
    assert work.total_params(CONF) == 4_009_526_784


def test_compiled_cut_is_the_file():
    cfg = dataclasses.replace(get_config(CONF["arch"]),
                              **REF.program_fields(CONF))
    assert cfg == dataclasses.replace(get_config("deepseek_v2_lite_16b"),
                                      num_layers=7)


@pytest.mark.parametrize("key,value", [("routed_scaling_factor", 16.0),
                                       ("n_group", 8), ("q_lora_rank", 1536)])
def test_reference_refuses_what_it_does_not_implement(key, value):
    with pytest.raises(ValueError, match=key):
        REF.program_fields({**CONF, key: value})


def test_control_is_not_correct():
    cell = dense_cell()
    cell.conf = CPU_CONF
    cell.declared["limits"]["max_logit_gap"] = CPU_LIMIT
    calls: dict = {}
    res = run.serve(cell, 6, 4.0, False, jax.devices()[:1],
                    control=True, calls=calls)
    prog = res["checks"]["max_logit_gap"]["value"]
    ctl = res["checks"]["control_max_logit_gap"]["value"]
    assert calls and res["correct"]
    assert res["control_correct"] is False, (prog, ctl)
    assert ctl > 3 * max(prog, 1e-3), (prog, ctl)
