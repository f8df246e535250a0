"""DeepSeek-V2-Lite in the program against the plain reference
(``bench/models/deepseek_v2.py``, loaded by path: it imports nothing of
the program).

At ``reduced()`` widths in float32, the program's prefill and then its
paged decode, through the step functions ``ServeEngine`` jits, give the
reference's full-forward logits at every position.  Each published
detail the program implements is shown necessary: reverting it alone in
the program takes the comparison past the tolerance.  The published
values themselves are pinned against the benchmark's configuration file.
"""

import dataclasses
import importlib.util
import json
import math
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.models import layers as L
from repro.models import model as M
from repro.models import moe as MOE
from repro.serving.engine import ServeEngine

BENCH = Path(__file__).resolve().parents[1] / "bench"
PUBLISHED = json.loads((BENCH / "configs" / "deepseek-v2-lite.json").read_text())

# Both sides compute in float32 from the same weights; they differ only in
# the order of their sums (absorbed against expanded latent attention, a
# scan over experts against one einsum), which moves logits of magnitude
# ~3.4 by ~2e-6 here.  Every reverted detail below moves them by 0.09 or
# more (the YaRN frequencies least: at 48 positions only the slow pairs
# differ).
TOL = 1e-4
PROMPT, STEPS, MAX_SEQ, BLOCK = 20, 28, 64, 8


def _load(name):
    spec = importlib.util.spec_from_file_location(name, BENCH / "models"
                                                  / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("deepseek_v2")
CFG = get_config("deepseek_v2_lite_16b").reduced()
# the published configuration at the reduced config's widths
CONF = {**PUBLISHED, "hidden_size": CFG.d_model, "intermediate_size": CFG.d_ff,
        "num_attention_heads": CFG.num_heads,
        "kv_lora_rank": CFG.kv_lora_rank,
        "qk_rope_head_dim": CFG.qk_rope_head_dim,
        "qk_nope_head_dim": CFG.qk_nope_head_dim,
        "v_head_dim": CFG.v_head_dim, "n_routed_experts": CFG.num_experts,
        "num_experts_per_tok": CFG.num_experts_per_tok,
        "n_shared_experts": CFG.num_shared_experts,
        "moe_intermediate_size": CFG.moe_d_ff,
        "num_hidden_layers": CFG.num_layers, "vocab_size": CFG.vocab_size}


@pytest.fixture(scope="module")
def setup():
    weights = REF.weights(CONF, 7)
    tokens = np.random.default_rng(7).integers(
        0, CFG.vocab_size, PROMPT + STEPS).astype(np.int32)
    want = np.asarray(REF.logits(CONF, weights, tokens, 0, PROMPT + STEPS))
    return jax.tree.map(lambda a: a.astype(jnp.float32), weights), tokens, want


def served_logits(cfg, params, tokens) -> np.ndarray:
    """Logits of every position: the engine's prefill over the prompt, its
    insert into the block pools, then one paged decode step per further
    token (fed the given tokens, not its own picks)."""
    eng = ServeEngine(cfg, params, max_seq=MAX_SEQ, batching=True,
                      max_batch=2, paged=True, kv_block_size=BLOCK)
    try:
        state = eng._paged[0]
        p = eng._params_on(0)
        logits, cache, _ = eng._prefill(
            p, eng._put(0, eng._prefill_batch(tokens[None, :PROMPT])))
        out = list(np.asarray(logits[0]))
        blocks = [b for b in range(state.mgr.num_blocks)
                  if b != state.scratch_block][: state.nb_max]
        table = np.asarray(blocks, np.int32)
        pools = eng._insert_paged_jit(
            eng._make_pools(0), cache,
            *eng._put(0, (np.int32(0), table, np.int32(state.scratch_slab),
                          np.int32(state.scratch_seg))))
        decode = jax.jit(eng._decode_paged_impl)
        for pos in range(PROMPT, len(tokens)):
            pack = np.concatenate([[tokens[pos], pos, state.scratch_slab,
                                    state.scratch_seg], table]).astype(np.int32)
            logits, pools = decode(p, eng._put(0, pack[None]), pools)
            out.append(np.asarray(logits[0, -1]))
        return np.stack(out)
    finally:
        eng.close()


def gap(cfg, setup) -> float:
    params, tokens, want = setup
    return float(np.abs(served_logits(cfg, params, tokens) - want).max())


def test_reduced_config_keeps_the_published_values():
    fields = REF.program_fields(CONF)
    for k, v in fields.items():
        # MLA reads the nope, rope and v widths, never these two
        if k not in ("head_dim", "num_kv_heads"):
            assert getattr(CFG, k) == v, k


def test_full_config_is_the_published_model():
    """Every program field of the published file, eps among them, against
    the registry's config (the file cuts only the depth)."""
    cfg = get_config("deepseek_v2_lite_16b")
    fields = REF.program_fields({**PUBLISHED, "num_hidden_layers": 27})
    assert {k: getattr(cfg, k) for k in fields} == fields
    assert cfg.norm_eps == 1e-6
    assert M.param_count(cfg) == 15_706_484_224
    assert M.param_count(dataclasses.replace(cfg, num_layers=7)) == 4_009_526_784


def test_program_prefill_and_paged_decode_match_the_reference(setup):
    cfg = dataclasses.replace(CFG, dtype="float32")
    assert gap(cfg, setup) < TOL


def _no_latent_norm(monkeypatch):
    real = L.rms_norm
    monkeypatch.setattr(L, "rms_norm", lambda x, scale, eps: (
        x if scale.shape[-1] == CFG.kv_lora_rank else real(x, scale, eps)))


REVERTS = {
    "latent_norm": _no_latent_norm,
    "yarn_frequencies": lambda mp: mp.setattr(
        L, "yarn_inv_freq", lambda cfg, dim: L._rope_freqs(dim, cfg.rope_theta)),
    "yarn_mscale_squared": lambda mp: mp.setattr(
        L, "yarn_get_mscale", lambda scale, mscale: 1.0),
    "adjacent_pairs": lambda mp: mp.setattr(L, "deinterleave", lambda x: x),
    "no_topk_renormalisation": None,  # a config field, set below
}


@pytest.mark.parametrize("fix", sorted(REVERTS))
def test_each_fix_is_needed(fix, setup, monkeypatch):
    cfg = dataclasses.replace(CFG, dtype="float32")
    if REVERTS[fix] is None:
        cfg = dataclasses.replace(cfg, norm_topk_prob=True)
    else:
        REVERTS[fix](monkeypatch)
    assert gap(cfg, setup) > 100 * TOL


def test_yarn_constants_at_published_widths():
    cfg = get_config("deepseek_v2_lite_16b")
    assert L.yarn_correction_range(cfg, 64) == (10, 23)
    rs = PUBLISHED["rope_scaling"]
    assert REF.yarn_find_correction_range(
        rs["beta_fast"], rs["beta_slow"], 64, PUBLISHED["rope_theta"],
        rs["original_max_position_embeddings"]) == (10, 23)
    m2 = L.mla_softmax_scale(cfg) * math.sqrt(192)
    assert m2 == pytest.approx(1.5896, abs=5e-5)
    assert REF.softmax_scale(PUBLISHED) == pytest.approx(L.mla_softmax_scale(cfg))
    plain = np.asarray(L._rope_freqs(64, 10000.0))
    yarn = np.asarray(L.yarn_inv_freq(cfg, 64))
    np.testing.assert_allclose(yarn[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(yarn[23:], plain[23:] / 40, rtol=1e-6)
    assert np.all((yarn[11:23] < plain[11:23]) & (yarn[11:23] > plain[11:23] / 40))
    # cos and sin keep a factor of 1 (mscale / mscale_all_dim)
    cos, sin = L.mla_rope_cos_sin(cfg, jnp.arange(3)[None], 64)
    np.testing.assert_allclose(np.asarray(cos[0, 0]), 1.0)


def test_new_defaults_leave_other_models_as_they_were():
    """Qwen3-MoE still renormalises its top-k weights, and internlm2 (GQA,
    plain rope) still gives its reference's logits."""
    cfg = get_config("qwen3_moe_235b_a22b").reduced()
    assert cfg.norm_topk_prob
    p = MOE.init_moe(cfg, jax.random.PRNGKey(1), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (16, cfg.d_model))
    _, w, _ = MOE.router_topk(cfg, p, x)
    top = jax.lax.top_k(jax.nn.softmax(x @ p["router"], axis=-1),
                        cfg.num_experts_per_tok)[0]
    np.testing.assert_allclose(np.asarray(w), np.asarray(
        top / jnp.sum(top, axis=-1, keepdims=True)), rtol=1e-6)

    gqa = _load("gqa")
    conf = json.loads((BENCH / "configs" / "internlm2-1.8b.json").read_text())
    cfg = get_config("internlm2_1_8b").reduced()
    assert cfg.rope_scaling_factor == 0.0
    conf.update(hidden_size=cfg.d_model, intermediate_size=cfg.d_ff,
                num_attention_heads=cfg.num_heads,
                num_key_value_heads=cfg.num_kv_heads,
                num_hidden_layers=cfg.num_layers, vocab_size=cfg.vocab_size)
    cfg = dataclasses.replace(cfg, **gqa.program_fields(conf), dtype="float32")
    weights = gqa.weights(conf, 3)
    tokens = np.arange(24, dtype=np.int32) * 5 % cfg.vocab_size
    want = np.asarray(gqa.logits(conf, weights, tokens, 0, 24))
    got, _, _ = M.apply(cfg, jax.tree.map(lambda a: a.astype(jnp.float32),
                                          weights),
                        {"tokens": jnp.asarray(tokens)[None]}, mode="train")
    assert np.abs(np.asarray(got[0]) - want).max() < TOL
