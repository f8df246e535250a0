"""Unified model API over all families.

    init_params(cfg, key)                      -> params pytree
    init_cache(cfg, batch, max_seq)            -> decode cache pytree
    init_paged_cache(cfg, blocks, block_size)  -> block-pool decode cache
    supports_paged(cfg)                        -> paged decode available?
    apply(cfg, params, batch, mode=...)        -> (logits, cache, aux)
    loss_fn(cfg, params, batch, ...)           -> (loss, metrics)
    param_count(cfg)                           -> analytical N (for rooflines)
    input_specs(cfg, shape)                    -> ShapeDtypeStruct batch stand-ins
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models import encdec, hybrid, ssm, transformer

Params = Any


def _family_mod(cfg):
    if cfg.family == "hybrid":
        return hybrid
    if cfg.family == "encdec":
        return encdec
    return transformer  # dense | moe | ssm | vlm


def init_params(cfg, key) -> Params:
    return _family_mod(cfg).init_params(cfg, key)


def init_cache(cfg, batch: int, max_seq: int, dtype=None):
    return _family_mod(cfg).init_cache(cfg, batch, max_seq, dtype)


def cache_family(cfg) -> str | None:
    """Resolve the cache family (a ``serving.kvcache.FAMILIES`` key) this
    config pages under, or None when nothing resolves.  A declared
    ``cfg.cache_family`` always wins; only plain GQA-shaped stacks derive
    one implicitly — there is NO silent dense fallback for the rest."""
    mod = _family_mod(cfg)
    return getattr(mod, "cache_family", lambda _cfg: None)(cfg)


def supports_paged(cfg) -> bool:
    """True when the family can run its decode cache in pooled form
    (``init_paged_cache`` + a block-table / slab-id decode cache)."""
    mod = _family_mod(cfg)
    return getattr(mod, "supports_paged", lambda _cfg: False)(cfg)


def init_paged_cache(cfg, num_blocks: int, block_size: int, dtype=None, *,
                     num_slabs: int = 0, num_segments: int = 0):
    """Pooled decode cache for the resolved cache family: block pools
    (num_blocks, block_size, ...) for attention KV, state-slab pools
    (num_slabs, ...) for SSM layers, and shared read-only segment pools
    (num_segments, ...) for enc-dec cross KV; the caller owns block
    tables, slab/segment ids, and lengths (see serving/kvcache.py)."""
    return _family_mod(cfg).init_paged_cache(
        cfg, num_blocks, block_size, dtype, num_slabs=num_slabs,
        num_segments=num_segments)


def paged_pool_kinds(cfg) -> dict[str, str]:
    """Pools-dict key -> "block" | "slab" | "segment" — the engine's map
    for generic staging, export/import, and the per-kind leak probe."""
    return _family_mod(cfg).paged_pool_kinds(cfg)


def paged_insert_views(cfg, prefill_cache) -> dict:
    """Prefill-cache leaves rearranged to match the ``init_paged_cache``
    pools structure ((Laxis, B, ...) per leaf) for the engine's generic
    insert scatter."""
    mod = _family_mod(cfg)
    if hasattr(mod, "paged_insert_views"):
        return mod.paged_insert_views(cfg, prefill_cache)
    views = {"layers": prefill_cache["layers"]}
    if "first_layers" in prefill_cache:
        views["first_layers"] = jax.tree.map(
            lambda *xs: jnp.stack(xs), *prefill_cache["first_layers"])
    return views


def apply(cfg, params, batch, *, mode: str, cache=None, remat: bool = False,
          remat_policy=None):
    return _family_mod(cfg).forward(cfg, params, batch, mode=mode, cache=cache,
                                    remat=remat, remat_policy=remat_policy)


def cross_entropy(logits, labels, *, z_loss: float = 0.0):
    """Stable CE in fp32; labels < 0 are masked out."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def loss_fn(cfg, params, batch, *, remat: bool = True, remat_policy=None,
            aux_weight: float = 0.01):
    logits, _, aux = apply(cfg, params, batch, mode="train", remat=remat,
                           remat_policy=remat_policy)
    ce = cross_entropy(logits, batch["labels"])
    loss = ce + aux_weight * aux
    return loss, {"ce": ce, "aux": aux}


# --------------------------------------------------------------------------
# analytical parameter counts (roofline MODEL_FLOPS = 6*N*D or 6*N_active*D)
# --------------------------------------------------------------------------


def _attn_params(cfg) -> int:
    d = cfg.d_model
    if cfg.attn_type == "mla":
        r, pr, pn, hv, n = (cfg.kv_lora_rank, cfg.qk_rope_head_dim,
                            cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.num_heads)
        return (d * n * (pn + pr) + d * (r + pr) + r + r * n * pn
                + r * n * hv + n * hv * d)  # r: the latent's norm
    nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return d * hd * (nq + 2 * nkv) + nq * hd * d


def _mlp_params(d: int, f: int, mlp_type: str) -> int:
    return (3 if mlp_type == "swiglu" else 2) * d * f


def _mamba_params(cfg) -> int:
    d, din, h = cfg.d_model, cfg.d_inner, cfg.ssm_nheads
    cdim = din + 2 * cfg.ssm_ngroups * cfg.ssm_state_dim
    return (d * (din + cdim + h) + cfg.conv_width * cdim + cdim
            + 3 * h + din + din * d)


def param_count(cfg, *, active_only: bool = False) -> int:
    d, v = cfg.d_model, cfg.vocab_size
    total = v * d + d  # embedding + final norm
    if not cfg.tie_embeddings:
        total += d * v  # lm_head

    if cfg.family == "hybrid":
        g = cfg.num_layers // cfg.attn_every
        n_mamba = cfg.num_layers - g
        total += n_mamba * (_mamba_params(cfg) + d)
        total += _attn_params(cfg) + _mlp_params(d, cfg.d_ff, cfg.mlp_type) + 2 * d
        return total

    if cfg.family == "encdec":
        enc = cfg.encoder_layers * (_attn_params(cfg)
                                    + _mlp_params(d, cfg.d_ff, cfg.mlp_type) + 2 * d)
        dec = cfg.num_layers * (2 * _attn_params(cfg)
                                + _mlp_params(d, cfg.d_ff, cfg.mlp_type) + 3 * d)
        return total + enc + dec + d  # + enc_norm

    if cfg.family == "ssm":
        return total + cfg.num_layers * (_mamba_params(cfg) + d)

    # dense / moe / vlm decoder
    for i in range(cfg.num_layers):
        total += _attn_params(cfg) + 2 * d
        if cfg.is_moe and i >= cfg.first_dense_layers:
            routed = cfg.num_experts_per_tok if active_only else cfg.num_experts
            total += routed * _mlp_params(d, cfg.moe_d_ff, "swiglu")
            total += cfg.num_shared_experts * _mlp_params(d, cfg.moe_d_ff, "swiglu")
            total += d * cfg.num_experts  # router
        else:
            total += _mlp_params(d, cfg.d_ff, cfg.mlp_type)
    return total


# --------------------------------------------------------------------------
# input stand-ins for the dry-run (ShapeDtypeStruct: no allocation)
# --------------------------------------------------------------------------


def input_specs(cfg, shape, *, for_train: bool | None = None) -> dict:
    """Batch stand-ins for one step of the given ShapeSpec.

    train:   tokens+labels (B,S)   [+frames/embeds/mrope per family]
    prefill: tokens (B,S)          [+...]
    decode:  tokens (B,1), cache supplied separately by the launcher
    """
    b, s = shape.global_batch, shape.seq_len
    f32 = jnp.float32
    i32 = jnp.int32
    dt = jnp.dtype(cfg.dtype)
    sds = jax.ShapeDtypeStruct
    kind = shape.kind if for_train is None else ("train" if for_train else shape.kind)

    if kind == "train":
        batch = {"tokens": sds((b, s), i32), "labels": sds((b, s), i32)}
        seq = (b, s)
    elif kind == "prefill":
        batch = {"tokens": sds((b, s), i32)}
        seq = (b, s)
    else:  # decode: one new token against a cache of length s
        batch = {"tokens": sds((b, 1), i32)}
        seq = (b, 1)

    if cfg.family == "vlm":
        # frontend stub: merged text+vision embeddings and M-RoPE positions
        # replace raw tokens entirely
        batch.pop("tokens", None)
        batch["embeds"] = sds((*seq, cfg.d_model), dt)
        batch["mrope_positions"] = sds((3, *seq), i32)
    if cfg.family == "encdec" and kind != "decode":
        # frontend stub: precomputed encoder frame embeddings
        batch["frames"] = sds((b, cfg.encoder_seq, cfg.d_model), dt)
    return batch
