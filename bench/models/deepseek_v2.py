"""Plain reference for DeepSeek-V2 (DeepSeek-V2-Lite): multi-head latent
attention with YaRN rope, and a mixture of experts with shared experts.

This file imports nothing of the program under test.  Like ``gqa.py`` it
holds three things a configuration of this family needs:

* ``weights(conf, seed)``: random bfloat16 weights from the seed, made on
  the device in one jitted call, in the program's parameter pytree: the
  leading dense layers as a list ``first_layers``, the MoE layers stacked
  in ``layers`` (router, ``experts``, ``shared``), MLA's ``kv_norm`` on the
  latent;
* ``program_fields(conf)``: the program's config fields, from the
  published keys of the configuration file;
* ``logits(conf, w, tokens, start, n, fp8=False)``: the float32 forward
  pass at the highest matmul precision, returning the logits of ``n``
  positions from ``start``.  ``fp8=True`` is the control: every matmul
  input (weights per output channel, activations per token, the router
  too) rounded to float8 e4m3, the next precision below the bfloat16 the
  configuration states.

It follows the published modelling code (``modeling_deepseek.py`` beside
the config): MLA without query compression, the latent RMS-normalised
(``kv_a_layernorm``) and expanded by ``kv_b_proj`` into per-head K_nope
and V (not the absorbed form the program decodes with); the rotary parts
de-interleaved, then rotated half against half with YaRN's cos and sin;
the softmax scale times YaRN's mscale(all_dim) squared; softmax gating in
float32, greedy top-k, the top-k weights renormalised only under
``norm_topk_prob``; the
shared experts as one SwiGLU of their summed width.  Departures:

* every routed expert is computed on every position (a scan over the
  experts) and weighted by its gate, zero outside the top-k, where the
  published code gathers each expert's tokens: the same sum;
* ``kv_a_layernorm`` takes ``rms_norm_eps`` (the published module keeps
  its default 1e-6, the same value in DeepSeek-V2-Lite);
* no auxiliary loss, no cache, no query compression (``q_lora_rank``
  must be null), no grouped top-k (``n_group`` 1), no scaling of the
  routed weights (``routed_scaling_factor`` 1).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _check(conf: dict) -> None:
    """Refuse published settings this reference does not implement."""
    want = {"q_lora_rank": None, "scoring_func": "softmax",
            "topk_method": "greedy", "n_group": 1, "topk_group": 1,
            "moe_layer_freq": 1, "hidden_act": "silu",
            "routed_scaling_factor": 1}
    bad = {k: conf[k] for k, v in want.items() if conf.get(k, v) != v}
    if conf["rope_scaling"].get("type") != "yarn":
        bad["rope_scaling.type"] = conf["rope_scaling"].get("type")
    if bad:
        raise ValueError(f"not implemented by this reference: {bad}")


def program_fields(conf: dict) -> dict:
    _check(conf)
    rs = conf["rope_scaling"]
    n = conf["num_attention_heads"]
    return {
        "num_layers": conf["num_hidden_layers"],
        "d_model": conf["hidden_size"],
        "num_heads": n,
        "num_kv_heads": n,
        "head_dim": conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"],
        "d_ff": conf["intermediate_size"],
        "vocab_size": conf["vocab_size"],
        "attn_type": "mla",
        "rope_theta": float(conf["rope_theta"]),
        "kv_lora_rank": conf["kv_lora_rank"],
        "qk_rope_head_dim": conf["qk_rope_head_dim"],
        "qk_nope_head_dim": conf["qk_nope_head_dim"],
        "v_head_dim": conf["v_head_dim"],
        "rope_scaling_factor": float(rs["factor"]),
        "rope_original_max_position": rs["original_max_position_embeddings"],
        "rope_beta_fast": float(rs["beta_fast"]),
        "rope_beta_slow": float(rs["beta_slow"]),
        "rope_mscale": float(rs["mscale"]),
        "rope_mscale_all_dim": float(rs["mscale_all_dim"]),
        "num_experts": conf["n_routed_experts"],
        "num_experts_per_tok": conf["num_experts_per_tok"],
        "num_shared_experts": conf["n_shared_experts"],
        "moe_d_ff": conf["moe_intermediate_size"],
        "first_dense_layers": conf["first_k_dense_replace"],
        "norm_topk_prob": bool(conf["norm_topk_prob"]),
        "norm_eps": float(conf["rms_norm_eps"]),
        "tie_embeddings": bool(conf["tie_word_embeddings"]),
        "mlp_type": "swiglu",
        "family": "moe",
        "cache_family": "mla",
    }


def weights(conf: dict, seed: int):
    """Random bfloat16 weights: matrices N(0, 1/fan_in) (the router too),
    norm scales 1 + N(0, 0.1^2), the embedding N(0, 1)."""
    _check(conf)
    d, v = conf["hidden_size"], conf["vocab_size"]
    n, r = conf["num_attention_heads"], conf["kv_lora_rank"]
    pr, pn = conf["qk_rope_head_dim"], conf["qk_nope_head_dim"]
    hv = conf["v_head_dim"]
    e, fe = conf["n_routed_experts"], conf["moe_intermediate_size"]
    fs = conf["n_shared_experts"] * fe
    n_dense = conf["first_k_dense_replace"]
    n_moe = conf["num_hidden_layers"] - n_dense
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             seed >> 32)

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 64))
        bf = jnp.bfloat16

        def mat(shape, fan_in):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    / math.sqrt(fan_in)).astype(bf)

        def norm(shape):
            return {"scale": (1.0 + 0.1 * jax.random.normal(
                next(ks), shape, jnp.float32)).astype(bf)}

        def block(lead, mixer):
            return {
                "ln1": norm((*lead, d)),
                "attn": {"wq": mat((*lead, d, n, pn + pr), d),
                         "w_dkv": mat((*lead, d, r + pr), d),
                         "w_uk": mat((*lead, r, n, pn), r),
                         "w_uv": mat((*lead, r, n, hv), r),
                         "wo": mat((*lead, n, hv, d), n * hv),
                         "kv_norm": norm((*lead, r))},
                "ln2": norm((*lead, d)),
                **mixer(lead),
            }

        def dense(lead):
            f = conf["intermediate_size"]
            return {"mlp": {"w_gate": mat((*lead, d, f), d),
                            "w_up": mat((*lead, d, f), d),
                            "w_down": mat((*lead, f, d), f)}}

        def moe(lead):
            return {"moe": {
                "router": mat((*lead, d, e), d),
                "experts": {"w_gate": mat((*lead, e, d, fe), d),
                            "w_up": mat((*lead, e, d, fe), d),
                            "w_down": mat((*lead, e, fe, d), fe)},
                "shared": {"w_gate": mat((*lead, d, fs), d),
                           "w_up": mat((*lead, d, fs), d),
                           "w_down": mat((*lead, fs, d), fs)}}}

        w = {
            "embed": jax.random.normal(next(ks), (v, d), bf),
            "first_layers": [block((), dense) for _ in range(n_dense)],
            "layers": block((n_moe,), moe),
            "final_norm": norm((d,)),
        }
        if not conf["tie_word_embeddings"]:
            w["lm_head"] = mat((d, v), d)
        return w

    return make(key)


def _q8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along
    ``axis`` (the reduction axis of the matmul it feeds)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _mm(spec, a, b, fp8, a_axis, b_axis):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if fp8:
        a, b = _q8(a, a_axis), _q8(b, b_axis)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale["scale"].astype(jnp.float32)


def _swiglu(x, w, fp8):
    gate = _mm("td,df->tf", x, w["w_gate"], fp8, -1, 0)
    up = _mm("td,df->tf", x, w["w_up"], fp8, -1, 0)
    return _mm("tf,fd->td", jax.nn.silu(gate) * up, w["w_down"], fp8, -1, 0)


# -- YaRN, as DeepseekV2YarnRotaryEmbedding computes it ----------------------


def yarn_get_mscale(scale: float = 1, mscale: float = 1) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_find_correction_range(low_rot, high_rot, dim, base, max_pos):
    def dim_at(rot):
        return (dim * math.log(max_pos / (rot * 2 * math.pi))) / (
            2 * math.log(base))

    return (max(math.floor(dim_at(low_rot)), 0),
            min(math.ceil(dim_at(high_rot)), dim - 1))


def _yarn_cos_sin(conf, t):
    """cos, sin (T, rope dim) at positions 0..T-1."""
    rs = conf["rope_scaling"]
    dim, base, factor = conf["qk_rope_head_dim"], conf["rope_theta"], rs["factor"]
    exps = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    freq_extra = 1.0 / (base ** exps)
    freq_inter = 1.0 / (factor * base ** exps)
    low, high = yarn_find_correction_range(
        rs["beta_fast"], rs["beta_slow"], dim, base,
        rs["original_max_position_embeddings"])
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    freqs = jnp.outer(jnp.arange(t, dtype=jnp.float32), inv_freq)
    mscale = (yarn_get_mscale(factor, rs["mscale"])
              / yarn_get_mscale(factor, rs["mscale_all_dim"]))
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb) * mscale, jnp.sin(emb) * mscale


def softmax_scale(conf) -> float:
    rs = conf["rope_scaling"]
    scale = (conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]) ** -0.5
    if rs.get("mscale_all_dim"):
        m = yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])
        scale = scale * m * m
    return scale


def _rotate_half(x):
    h = x.shape[-1] // 2
    return jnp.concatenate([-x[..., h:], x[..., :h]], axis=-1)


def _rope(x, cos, sin):
    """x (T, N, d): de-interleave the adjacent pairs, then rotate half."""
    t, nh, d = x.shape
    x = x.reshape(t, nh, d // 2, 2).transpose(0, 1, 3, 2).reshape(t, nh, d)
    return x * cos[:, None, :] + _rotate_half(x) * sin[:, None, :]


# -- the layers ---------------------------------------------------------------


def _attn(conf, fp8, x, aw):
    t = x.shape[0]
    n, r = conf["num_attention_heads"], conf["kv_lora_rank"]
    pn = conf["qk_nope_head_dim"]
    q = _mm("td,dnh->tnh", x, aw["wq"], fp8, -1, 0)
    q_nope, q_pe = q[..., :pn], q[..., pn:]
    kv_a = _mm("td,dr->tr", x, aw["w_dkv"], fp8, -1, 0)
    c_kv = _rms(kv_a[:, :r], aw["kv_norm"], conf["rms_norm_eps"])
    k_pe = kv_a[:, None, r:]  # one rotary key shared by every head
    k_nope = _mm("tr,rnh->tnh", c_kv, aw["w_uk"], fp8, -1, 0)
    v = _mm("tr,rnh->tnh", c_kv, aw["w_uv"], fp8, -1, 0)
    cos, sin = _yarn_cos_sin(conf, t)
    q_pe, k_pe = _rope(q_pe, cos, sin), _rope(k_pe, cos, sin)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (t, n, k_pe.shape[-1]))], axis=-1)
    s = jnp.einsum("tnh,snh->nts", q, k, precision=HIGHEST) * softmax_scale(conf)
    causal = jnp.arange(t)[None, :, None] >= jnp.arange(t)[None, None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("nts,snh->tnh", p, v, precision=HIGHEST)
    return _mm("tnh,nhd->td", o, aw["wo"], fp8, (1, 2), (0, 1))


def _moe(conf, fp8, x, mw):
    t = x.shape[0]
    logits = _mm("td,de->te", x, mw["router"], fp8, -1, 0)
    scores = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(scores, conf["num_experts_per_tok"])
    if conf["norm_topk_prob"]:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    gates = jnp.zeros_like(scores).at[jnp.arange(t)[:, None], top_i].set(top_w)

    def expert(acc, ew):
        w, g = ew
        return acc + _swiglu(x, w, fp8) * g[:, None], None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                             (mw["experts"], gates.T))
    return routed + _swiglu(x, mw["shared"], fp8)


def _layer(conf, fp8, x, lw, moe: bool):
    eps = conf["rms_norm_eps"]
    x = x + _attn(conf, fp8, _rms(x, lw["ln1"], eps), lw["attn"])
    a = _rms(x, lw["ln2"], eps)
    return x + (_moe(conf, fp8, a, lw["moe"]) if moe
                else _swiglu(a, lw["mlp"], fp8))


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _forward(conf_json, w, tokens, start, n, fp8):
    conf = json.loads(conf_json)
    x = w["embed"][tokens].astype(jnp.float32)
    for lw in w["first_layers"]:
        x = _layer(conf, fp8, x, lw, moe=False)

    def body(x, lw):
        return _layer(conf, fp8, x, lw, moe=True), None

    x, _ = jax.lax.scan(body, x, w["layers"])
    x = jax.lax.dynamic_slice_in_dim(x, start, n, axis=0)
    x = _rms(x, w["final_norm"], conf["rms_norm_eps"])
    table = w["embed"].T if conf["tie_word_embeddings"] else w["lm_head"]
    return _mm("td,dv->tv", x, table, fp8, -1, 0)


def logits(conf: dict, w, tokens, start: int, n: int, *, fp8: bool = False):
    """Float32 logits (n, V) at positions ``start`` .. ``start + n - 1`` of
    ``tokens``.  ``tokens`` (1-D) is padded to a size fixed for the cell,
    and ``n`` is fixed too, so one compiled program serves every request:
    causal attention keeps the padding out of every real position, and
    routing is per position."""
    return _forward(json.dumps(conf, sort_keys=True), w,
                    jnp.asarray(tokens, jnp.int32), jnp.int32(start), n, fp8)
