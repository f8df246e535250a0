"""Plain reference for decoder-only GQA transformers (InternLM2, Llama
style): RMSNorm, rotate-half RoPE, grouped-query causal attention, SwiGLU.

This file imports nothing of the program under test.  It holds three
things a configuration of this family needs:

* ``weights(conf, seed)``: random weights from the seed, made on the
  device in one jitted call, in the dtype they are served in and in the
  nested-dict layout the program's parameter pytree uses (the program is
  handed these arrays; it makes none of its own);
* ``program_fields(conf)``: the program's config fields, from the
  published keys of the configuration file;
* ``logits(conf, w, tokens, start, n, fp8=False)``: the float32 forward
  pass at the highest matmul precision, layer by layer (a scan that
  widens one layer's weights at a time), returning the logits of ``n``
  positions from ``start``.  ``fp8=True`` is the control: every matmul
  input (weights per output channel, activations per token) rounded to
  float8 e4m3, the next precision below the bfloat16 the configuration
  states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def program_fields(conf: dict) -> dict:
    return {
        "num_layers": conf["num_hidden_layers"],
        "d_model": conf["hidden_size"],
        "num_heads": conf["num_attention_heads"],
        "num_kv_heads": conf["num_key_value_heads"],
        "head_dim": conf["hidden_size"] // conf["num_attention_heads"],
        "d_ff": conf["intermediate_size"],
        "vocab_size": conf["vocab_size"],
        "rope_theta": float(conf["rope_theta"]),
        "norm_eps": float(conf["rms_norm_eps"]),
        "tie_embeddings": bool(conf["tie_word_embeddings"]),
        "mlp_type": "swiglu",
        "attn_type": "gqa",
        "family": "dense",
    }


def _dims(conf):
    d = conf["hidden_size"]
    nq = conf["num_attention_heads"]
    return (conf["num_hidden_layers"], d, nq, conf["num_key_value_heads"],
            d // nq, conf["intermediate_size"], conf["vocab_size"])


def weights(conf: dict, seed: int):
    """Random bfloat16 weights: matrices N(0, 1/fan_in), norm scales
    1 + N(0, 0.1^2), the embedding N(0, 1)."""
    n, d, nq, nkv, h, f, v = _dims(conf)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             seed >> 32)

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 16))
        bf = jnp.bfloat16

        def mat(shape, fan_in):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    / math.sqrt(fan_in)).astype(bf)

        def norm(shape):
            return (1.0 + 0.1 * jax.random.normal(next(ks), shape,
                                                  jnp.float32)).astype(bf)

        w = {
            "embed": jax.random.normal(next(ks), (v, d), bf),
            "layers": {
                "ln1": {"scale": norm((n, d))},
                "attn": {"wq": mat((n, d, nq, h), d),
                         "wk": mat((n, d, nkv, h), d),
                         "wv": mat((n, d, nkv, h), d),
                         "wo": mat((n, nq, h, d), nq * h)},
                "ln2": {"scale": norm((n, d))},
                "mlp": {"w_gate": mat((n, d, f), d),
                        "w_up": mat((n, d, f), d),
                        "w_down": mat((n, f, d), f)},
            },
            "final_norm": {"scale": norm((d,))},
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
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x (T, N, H): rotate-half rotary embedding at positions 0..T-1."""
    t, _, h = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, h, 2, dtype=jnp.float32) / h)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : h // 2], x[..., h // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(conf, fp8, x, lw):
    _, d, nq, nkv, h, _, _ = _dims(conf)
    eps = conf["rms_norm_eps"]
    theta = float(conf["rope_theta"])
    t = x.shape[0]
    a = _rms(x, lw["ln1"]["scale"], eps)
    q = _rope(_mm("td,dnh->tnh", a, lw["attn"]["wq"], fp8, -1, 0), theta)
    k = _rope(_mm("td,dnh->tnh", a, lw["attn"]["wk"], fp8, -1, 0), theta)
    v = _mm("td,dnh->tnh", a, lw["attn"]["wv"], fp8, -1, 0)
    g = nq // nkv
    k = jnp.repeat(k, g, axis=1)  # query head n reads kv head n // g
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("tnh,snh->nts", q, k, precision=HIGHEST) / math.sqrt(h)
    causal = jnp.arange(t)[None, :, None] >= jnp.arange(t)[None, None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("nts,snh->tnh", p, v, precision=HIGHEST)
    x = x + _mm("tnh,nhd->td", o, lw["attn"]["wo"], fp8, (1, 2), (0, 1))
    a = _rms(x, lw["ln2"]["scale"], eps)
    gate = _mm("td,df->tf", a, lw["mlp"]["w_gate"], fp8, -1, 0)
    up = _mm("td,df->tf", a, lw["mlp"]["w_up"], fp8, -1, 0)
    return x + _mm("tf,fd->td", jax.nn.silu(gate) * up, lw["mlp"]["w_down"],
                   fp8, -1, 0)


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _forward(conf_items, w, tokens, start, n, fp8):
    conf = dict(conf_items)
    x = w["embed"][tokens].astype(jnp.float32)

    def body(x, lw):
        return _layer(conf, fp8, x, lw), None

    x, _ = jax.lax.scan(body, x, w["layers"])
    x = jax.lax.dynamic_slice_in_dim(x, start, n, axis=0)
    x = _rms(x, w["final_norm"]["scale"], conf["rms_norm_eps"])
    table = w["embed"].T if conf["tie_word_embeddings"] else w["lm_head"]
    return _mm("td,dv->tv", x, table, fp8, -1, 0)


def _hashable(conf):
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "num_hidden_layers", "intermediate_size", "vocab_size",
            "rms_norm_eps", "rope_theta", "tie_word_embeddings")
    return tuple((k, conf[k]) for k in keys)


def logits(conf: dict, w, tokens, start: int, n: int, *, fp8: bool = False):
    """Float32 logits (n, V) at positions ``start`` .. ``start + n - 1`` of
    ``tokens``.  ``tokens`` (1-D) is padded to a size fixed for the cell,
    and ``n`` is fixed too, so one compiled program serves every request:
    causal attention keeps the padding out of every real position."""
    return _forward(_hashable(conf), w, jnp.asarray(tokens, jnp.int32),
                    jnp.int32(start), n, fp8)
