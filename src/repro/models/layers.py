"""Shared model layers: norms, rotary embeddings (incl. M-RoPE), attention
(GQA/MQA and MLA with absorbed decode), and MLPs.

All functions are pure; parameters are plain dicts of jnp arrays.  Matmul
accumulations that feed softmax/normalization run in fp32
(``preferred_element_type``); activations stay in the config dtype.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed import sharding as shd

Params = dict[str, Any]

# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------


def dense_init(key, shape, dtype, *, scale: float | None = None):
    fan_in = shape[0] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------


def rms_norm(x, scale, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt) * scale


def init_rms_norm(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


# --------------------------------------------------------------------------
# rotary embeddings
# --------------------------------------------------------------------------


def _rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def rope_cos_sin(positions, head_dim: int, theta: float):
    """positions (..., S) -> cos/sin (..., S, head_dim/2) in fp32."""
    freqs = _rope_freqs(head_dim, theta)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(ang), jnp.sin(ang)


def mrope_cos_sin(positions, head_dim: int, theta: float, sections):
    """Qwen2-VL multimodal rotary: ``positions`` (3, B, S) carries the
    temporal/height/width streams; rotary pairs are split into ``sections``
    (summing to head_dim/2), each driven by its own stream."""
    assert positions.shape[0] == 3, "M-RoPE needs (3, B, S) positions"
    cos, sin = rope_cos_sin(positions, head_dim, theta)  # (3, B, S, hd/2)
    sec_ids = jnp.repeat(
        jnp.arange(3), jnp.array(sections), total_repeat_length=head_dim // 2
    )  # static
    take = jax.nn.one_hot(sec_ids, 3, dtype=cos.dtype)  # (hd/2, 3)
    cos = jnp.einsum("tbsd,dt->bsd", cos, take)
    sin = jnp.einsum("tbsd,dt->bsd", sin, take)
    return cos, sin


def apply_rope(x, cos, sin):
    """x (B, S, N, H); cos/sin (B, S, H/2).  Llama-style rotate-half."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dt)


def yarn_get_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention temperature for a context stretched ``scale``-fold."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_correction_range(cfg, dim: int) -> tuple[int, int]:
    """The rotary pair indices between which YaRN ramps from the original
    frequencies (below ``low``) to the interpolated ones (above ``high``):
    where a pair turns ``beta_fast`` and ``beta_slow`` times over the
    original context."""

    def dim_at(rotations):
        return (dim * math.log(cfg.rope_original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    return (max(math.floor(dim_at(cfg.rope_beta_fast)), 0),
            min(math.ceil(dim_at(cfg.rope_beta_slow)), dim - 1))


def yarn_inv_freq(cfg, dim: int):
    """YaRN rotary frequencies (dim/2,) in fp32, as DeepSeek-V2 computes
    them: the original ones below the correction range, those divided by
    the scaling factor above it, and a linear ramp between."""
    exps = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / cfg.rope_theta ** exps
    inter = 1.0 / (cfg.rope_scaling_factor * cfg.rope_theta ** exps)
    low, high = yarn_correction_range(cfg, dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / ((high - low) or 0.001), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def mla_rope_cos_sin(cfg, positions, dim: int):
    """MLA's rotary cos/sin (..., S, dim/2) under the config's YaRN scaling:
    ``yarn_inv_freq``, and cos and sin times mscale / mscale_all_dim."""
    ang = positions[..., None].astype(jnp.float32) * yarn_inv_freq(cfg, dim)
    m = (yarn_get_mscale(cfg.rope_scaling_factor, cfg.rope_mscale)
         / yarn_get_mscale(cfg.rope_scaling_factor, cfg.rope_mscale_all_dim))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def deinterleave(x):
    """(..., H) -> the even features, then the odd ones.  DeepSeek-V2
    rotates adjacent feature pairs: it de-interleaves before a rotate-half
    rope (and the rotated vector stays in that order)."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def sinusoidal_positions(positions, d_model: int):
    """Whisper-style absolute sinusoidal embeddings, (..., S) -> (..., S, D)."""
    half = d_model // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / (half - 1))
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------------
# attention (GQA / MQA)
# --------------------------------------------------------------------------


def init_attention(cfg, key, dtype) -> Params:
    d, nq, nkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, nq, hd), dtype),
        "wk": dense_init(ks[1], (d, nkv, hd), dtype),
        "wv": dense_init(ks[2], (d, nkv, hd), dtype),
        "wo": dense_init(ks[3], (nq, hd, d), dtype, scale=1.0 / math.sqrt(nq * hd)),
    }


def _sdpa(q, k, v, *, mask, scale: float):
    """q (B,Sq,Nq,H); k/v (B,Sk,Nkv,H); grouped heads; fp32 softmax.

    This is also the pure-jnp oracle the Pallas flash kernel is verified
    against (kernels/ref.py re-exports it)."""
    b, sq, nq, h = q.shape
    nkv = k.shape[2]
    hv = v.shape[-1]  # may differ from h (MLA: qk dim != v dim)
    g = nq // nkv
    q = q.reshape(b, sq, nkv, g, h)
    logits = jnp.einsum("bskgh,btkh->bkgst", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, nq, hv).astype(v.dtype)


def attention(cfg, p: Params, x, *, positions, cache=None, layer_cache=None,
              mrope_positions=None):
    """GQA attention.

    Training/prefill: ``layer_cache is None`` -> causal self-attention; if
    ``cache == 'build'`` also returns the (k, v) for cache construction.
    Decode: ``layer_cache = (k_cache, v_cache, pos)`` with x of seq-len 1;
    returns (out, (k_cache', v_cache')).
    Paged decode: ``layer_cache = (k_stack, v_stack, lidx, block_tables,
    pos)`` with the full layer-stacked pools (L, num_blocks, block_size,
    Nkv, H) shared across rows, ``lidx`` this layer's index into the stack,
    and ``block_tables`` (B, W) int32 the per-row indirection; returns
    (out, (k_stack', v_stack')).
    """
    b, s, d = x.shape
    hd = cfg.head_dim
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", x, p["wv"])
    q = shd.shard_heads(q)

    if cfg.rope_theta:
        if cfg.mrope and mrope_positions is not None:
            cos, sin = mrope_cos_sin(mrope_positions, hd, cfg.rope_theta,
                                     cfg.mrope_sections)
        else:
            cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    else:
        # absolute positions are added at the embedding layer (whisper)
        pass

    scale = 1.0 / math.sqrt(hd)
    if layer_cache is None:
        # causal self-attention over the full sequence
        idx = jnp.arange(s)
        mask = (idx[None, :] <= idx[:, None])[None, None, None, :, :]
        out = _sdpa(q, k, v, mask=mask, scale=scale)
        new_cache = (k, v) if cache == "build" else None
    elif len(layer_cache) == 5:
        # paged decode: the cache is a block POOL shared by all rows, each
        # row addressing its own blocks through ``tables``.  The pool rides
        # the layer scan as CARRY — the full (L, NB, BS, Nkv, H) stacks,
        # indexed by ``lidx`` — so the only per-step data movement is the
        # one-row scatter of the new token and the gather of the W live
        # blocks: cost tracks actual work, never pool capacity (the dense
        # path copies its whole (max_batch, max_seq) cache every step).
        # Rows never share a tail block (the paged KV manager copy-on-
        # write-forks shared tails), so scatters are row-disjoint and no
        # masked merge is needed.
        k_stack, v_stack, lidx, tables, pos = layer_cache
        bs_blk = k_stack.shape[2]  # (L, NB, BS, Nkv, H), (B, W), (B,)
        bidx = jnp.arange(b)
        blk = tables[bidx, pos // bs_blk]
        off = pos % bs_blk
        k_stack = k_stack.at[lidx, blk, off].set(k[:, 0].astype(k_stack.dtype))
        v_stack = v_stack.at[lidx, blk, off].set(v[:, 0].astype(v_stack.dtype))
        w = tables.shape[1]
        k_seq = k_stack[lidx, tables].reshape(b, w * bs_blk, *k_stack.shape[3:])
        v_seq = v_stack[lidx, tables].reshape(b, w * bs_blk, *v_stack.shape[3:])
        valid = jnp.arange(w * bs_blk)[None, :] <= pos[:, None]
        mask = valid[:, None, None, None, :]
        out = _sdpa(q, k_seq, v_seq, mask=mask, scale=scale)
        new_cache = (k_stack, v_stack)
    else:
        k_cache, v_cache, pos = layer_cache  # (B, Smax, Nkv, H), pos (B,)
        # write the new token at its position per batch element
        from repro.models import perf

        if perf.current().cache_update == "scatter":
            bidx = jnp.arange(b)
            k_cache = k_cache.at[bidx, pos].set(k[:, 0].astype(k_cache.dtype))
            v_cache = v_cache.at[bidx, pos].set(v[:, 0].astype(v_cache.dtype))
        else:  # naive baseline: full-cache select
            upd = jnp.arange(k_cache.shape[1])[None, :] == pos[:, None]
            k_cache = jnp.where(upd[..., None, None], k.astype(k_cache.dtype),
                                k_cache)
            v_cache = jnp.where(upd[..., None, None], v.astype(v_cache.dtype),
                                v_cache)
        valid = (jnp.arange(k_cache.shape[1])[None, :] <= pos[:, None])
        mask = valid[:, None, None, None, :]
        out = _sdpa(q, k_cache, v_cache, mask=mask, scale=scale)
        new_cache = (k_cache, v_cache)

    out = jnp.einsum("bsnh,nhd->bsd", out, p["wo"])
    return shd.shard_hidden(out), new_cache


# --------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# --------------------------------------------------------------------------


def init_mla(cfg, key, dtype) -> Params:
    d, n = cfg.d_model, cfg.num_heads
    r, pr, pn, hv = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.qk_nope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)
    return {
        "wq": dense_init(ks[0], (d, n, pn + pr), dtype),
        "w_dkv": dense_init(ks[1], (d, r + pr), dtype),  # latent + shared rope key
        "w_uk": dense_init(ks[2], (r, n, pn), dtype),
        "w_uv": dense_init(ks[3], (r, n, hv), dtype),
        "wo": dense_init(ks[4], (n, hv, d), dtype, scale=1.0 / math.sqrt(n * hv)),
        "kv_norm": init_rms_norm(r, dtype),  # on the latent, before caching
    }


def mla_softmax_scale(cfg) -> float:
    """1/sqrt(qk head dim) times YaRN's mscale(all_dim)^2 (DeepSeek-V2)."""
    m = yarn_get_mscale(cfg.rope_scaling_factor, cfg.rope_mscale_all_dim)
    return m * m / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


@jax.named_scope("mla")
def mla_attention(cfg, p: Params, x, *, positions, cache=None, layer_cache=None):
    """MLA: KV compressed to a ``kv_lora_rank`` latent + one shared rotary
    key.  The latent is RMS-normalised (``kv_norm``) before it is cached or
    expanded, so the cache stores only the normalised (c_kv, k_rope) — the
    paper-accurate memory win.  Decode uses the absorbed formulation
    (queries projected into the latent space; no per-step K/V
    decompression).  The rotary parts follow DeepSeek-V2: adjacent pairs
    (``deinterleave``), YaRN frequencies and softmax scale.

    Paged decode: ``layer_cache = (ckv_stack, krope_stack, lidx, tables,
    pos)`` — latent block pools (L, NB, BS, r) / (L, NB, BS, pr) shared
    across rows, the same block-table indirection as GQA but with much
    smaller rows (r + pr vs 2 * n_kv * head_dim per token), which is why
    MLA paging has its own block-size sensitivity."""
    b, s, d = x.shape
    n = cfg.num_heads
    r, pr, pn, hv = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.qk_nope_head_dim, cfg.v_head_dim
    scale = mla_softmax_scale(cfg)

    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])  # (B,S,N,pn+pr)
    q_nope, q_rope = q[..., :pn], q[..., pn:]
    ckv_full = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])  # (B,S,r+pr)
    c_kv, k_rope = ckv_full[..., :r], ckv_full[..., r:]
    c_kv = rms_norm(c_kv, p["kv_norm"]["scale"], cfg.norm_eps)

    cos, sin = mla_rope_cos_sin(cfg, positions, pr)
    q_rope = apply_rope(deinterleave(q_rope), cos, sin)
    k_rope = apply_rope(deinterleave(k_rope)[:, :, None, :], cos,
                        sin)[:, :, 0, :]  # shared head

    if layer_cache is None:
        k_nope = jnp.einsum("bsr,rnh->bsnh", c_kv, p["w_uk"])
        v = jnp.einsum("bsr,rnh->bsnh", c_kv, p["w_uv"])
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope[:, :, None, :], (b, s, n, pr))], axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        idx = jnp.arange(s)
        mask = (idx[None, :] <= idx[:, None])[None, None, None, :, :]
        out = _sdpa(qq, k, v, mask=mask, scale=scale)
        new_cache = (c_kv, k_rope) if cache == "build" else None
    elif len(layer_cache) == 5:
        # paged decode over latent block pools: scatter the new (c_kv,
        # k_rope) row into this stream's tail block, gather the W live
        # blocks through the table, then the same absorbed math as the
        # dense branch.  Rows never share a tail block (COW fork), so the
        # scatters are row-disjoint exactly as in GQA paged decode.
        ckv_stack, krope_stack, lidx, tables, pos = layer_cache
        bs_blk = ckv_stack.shape[2]  # (L, NB, BS, r), (B, W), (B,)
        bidx = jnp.arange(b)
        blk = tables[bidx, pos // bs_blk]
        off = pos % bs_blk
        ckv_stack = ckv_stack.at[lidx, blk, off].set(
            c_kv[:, 0].astype(ckv_stack.dtype))
        krope_stack = krope_stack.at[lidx, blk, off].set(
            k_rope[:, 0].astype(krope_stack.dtype))
        w = tables.shape[1]
        ckv_seq = ckv_stack[lidx, tables].reshape(b, w * bs_blk, r)
        krope_seq = krope_stack[lidx, tables].reshape(b, w * bs_blk, pr)
        q_lat = jnp.einsum("bsnh,rnh->bsnr", q_nope, p["w_uk"])
        logits = (
            jnp.einsum("bsnr,btr->bnst", q_lat, ckv_seq,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bsnh,bth->bnst", q_rope, krope_seq,
                         preferred_element_type=jnp.float32)
        ) * scale
        valid = (jnp.arange(w * bs_blk)[None, :] <= pos[:, None])
        logits = jnp.where(valid[:, None, None, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        o_lat = jnp.einsum("bnst,btr->bsnr", probs,
                           ckv_seq.astype(jnp.float32)).astype(x.dtype)
        out = jnp.einsum("bsnr,rnh->bsnh", o_lat, p["w_uv"])
        new_cache = (ckv_stack, krope_stack)
    else:
        ckv_cache, krope_cache, pos = layer_cache  # (B,Smax,r), (B,Smax,pr)
        t = ckv_cache.shape[1]
        from repro.models import perf

        if perf.current().cache_update == "scatter":
            bidx = jnp.arange(b)
            ckv_cache = ckv_cache.at[bidx, pos].set(
                c_kv[:, 0].astype(ckv_cache.dtype))
            krope_cache = krope_cache.at[bidx, pos].set(
                k_rope[:, 0].astype(krope_cache.dtype))
        else:  # naive baseline: full-cache select
            upd = jnp.arange(t)[None, :] == pos[:, None]
            ckv_cache = jnp.where(upd[..., None], c_kv.astype(ckv_cache.dtype),
                                  ckv_cache)
            krope_cache = jnp.where(upd[..., None],
                                    k_rope.astype(krope_cache.dtype), krope_cache)
        # absorbed decode: q_nope' = q_nope @ w_uk  -> latent space
        q_lat = jnp.einsum("bsnh,rnh->bsnr", q_nope, p["w_uk"])
        logits = (
            jnp.einsum("bsnr,btr->bnst", q_lat, ckv_cache,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bsnh,bth->bnst", q_rope, krope_cache,
                         preferred_element_type=jnp.float32)
        ) * scale
        valid = (jnp.arange(t)[None, :] <= pos[:, None])[:, None, None, :]
        logits = jnp.where(valid, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        o_lat = jnp.einsum("bnst,btr->bsnr", probs,
                           ckv_cache.astype(jnp.float32)).astype(x.dtype)
        out = jnp.einsum("bsnr,rnh->bsnh", o_lat, p["w_uv"])
        new_cache = (ckv_cache, krope_cache)

    out = jnp.einsum("bsnh,nhd->bsd", out, p["wo"])
    return shd.shard_hidden(out), new_cache


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------


def init_mlp(cfg, key, dtype, d_ff: int | None = None) -> Params:
    d = cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_type == "swiglu":
        return {
            "w_gate": dense_init(ks[0], (d, f), dtype),
            "w_up": dense_init(ks[1], (d, f), dtype),
            "w_down": dense_init(ks[2], (f, d), dtype),
        }
    return {  # gelu
        "w_up": dense_init(ks[1], (d, f), dtype),
        "w_down": dense_init(ks[2], (f, d), dtype),
    }


def mlp(cfg, p: Params, x):
    if "w_gate" in p:
        h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["w_gate"]))
        h = h * jnp.einsum("bsd,df->bsf", x, p["w_up"])
    else:
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["w_up"]))
    h = shd.shard_ffn(h)
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])
