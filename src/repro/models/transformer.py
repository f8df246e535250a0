"""Decoder-only transformer stack (dense / MoE / VLM / pure-SSM families).

Layers are stacked along a leading axis and executed with ``jax.lax.scan``
so the HLO stays O(1) in depth (mandatory for compiling 94/126-layer models
in the 512-device dry-run, and the production-correct choice anyway).
Non-uniform prefixes (e.g. DeepSeek's first dense layer) run as plain Python
loops before the scan.

Modes:
  train   — causal forward, logits for all positions, no cache
  prefill — causal forward + returns the decode cache
  decode  — single-token step against the cache
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.distributed import sharding as shd
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S

# --------------------------------------------------------------------------
# per-layer init
# --------------------------------------------------------------------------


def _mixer_kind(cfg) -> str:
    if cfg.family == "ssm":
        return "mamba"
    return cfg.attn_type  # gqa | mla


def init_layer(cfg, key, dtype, *, use_moe: bool, d_ff: int | None = None):
    ks = jax.random.split(key, 3)
    kind = _mixer_kind(cfg)
    p: dict = {"ln1": L.init_rms_norm(cfg.d_model, dtype)}
    if kind == "mamba":
        p["mamba"] = S.init_mamba2(cfg, ks[0], dtype)
        return p  # SSM blocks: mixer only, no separate MLP
    if kind == "mla":
        p["attn"] = L.init_mla(cfg, ks[0], dtype)
    else:
        p["attn"] = L.init_attention(cfg, ks[0], dtype)
    p["ln2"] = L.init_rms_norm(cfg.d_model, dtype)
    if use_moe:
        p["moe"] = M.init_moe(cfg, ks[1], dtype)
    else:
        p["mlp"] = L.init_mlp(cfg, ks[1], dtype, d_ff=d_ff)
    return p


def init_params(cfg, key):
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    n_first = cfg.first_dense_layers if cfg.is_moe else 0
    n_scan = cfg.num_layers - n_first

    params: dict = {}
    params["embed"] = L.embed_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype)

    if n_first:
        params["first_layers"] = [
            init_layer(cfg, jax.random.fold_in(ks[1], i), dtype,
                       use_moe=False, d_ff=cfg.d_ff)
            for i in range(n_first)
        ]

    layer_keys = jax.random.split(ks[2], n_scan)
    params["layers"] = jax.vmap(
        lambda k: init_layer(cfg, k, dtype, use_moe=cfg.is_moe,
                             d_ff=cfg.moe_d_ff if cfg.is_moe else cfg.d_ff)
    )(layer_keys)

    params["final_norm"] = L.init_rms_norm(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(ks[3], (cfg.d_model, cfg.vocab_size), dtype)
    return params


# --------------------------------------------------------------------------
# block
# --------------------------------------------------------------------------


def block(cfg, p, x, *, positions, mrope_positions=None, mode: str,
          layer_cache=None, use_moe: bool, lengths=None, layer=None):
    """One transformer block.  Returns (x, new_layer_cache, aux_loss).
    ``lengths`` (B,) are the true per-row prompt lengths of a padded
    (bucketed) prefill — only the SSM prefill needs them (its recurrent
    state is polluted by pad positions unless dt is masked).  ``layer``:
    the block's index into ``p["moe"]["experts"]`` where those hold every
    layer's experts (``moe.moe_layer``)."""
    kind = _mixer_kind(cfg)
    aux = jnp.zeros((), jnp.float32)
    h = L.rms_norm(x, p["ln1"]["scale"], cfg.norm_eps)
    if kind == "mamba":
        if mode == "prefill":
            out, new_cache = S.prefill_mamba_cache(cfg, p["mamba"], h,
                                                   lengths=lengths)
        else:
            out, new_cache = S.mamba2_block(cfg, p["mamba"], h,
                                            layer_cache=layer_cache)
        return x + out, new_cache, aux

    cache_flag = "build" if mode == "prefill" else None
    if kind == "mla":
        out, new_cache = L.mla_attention(cfg, p["attn"], h, positions=positions,
                                         cache=cache_flag, layer_cache=layer_cache)
    else:
        out, new_cache = L.attention(cfg, p["attn"], h, positions=positions,
                                     cache=cache_flag, layer_cache=layer_cache,
                                     mrope_positions=mrope_positions)
    x = x + out
    h = L.rms_norm(x, p["ln2"]["scale"], cfg.norm_eps)
    if use_moe:
        out, aux = M.moe_layer(cfg, p["moe"], h, layer)
    else:
        out = L.mlp(cfg, p["mlp"], h)
    return x + out, new_cache, aux


def _split_experts(cfg, layers):
    """(the layer stack without its routed experts, the experts stacked
    whole, or None): each scanned block then slices its own from the whole
    stack (``moe.moe_layer``'s ``layer``)."""
    if not cfg.is_moe:
        return layers, None
    moe = dict(layers["moe"])
    experts = moe.pop("experts")
    return {**layers, "moe": moe}, experts


def _with_experts(lp, experts):
    """A scanned block's params with the whole expert stack put back."""
    if experts is None:
        return lp
    return {**lp, "moe": {**lp["moe"], "experts": experts}}


# --------------------------------------------------------------------------
# cache construction
# --------------------------------------------------------------------------


def init_cache(cfg, batch: int, max_seq: int, dtype=None):
    """Zero decode cache for the scanned stack (leading L axis) plus any
    prefix layers and the position counter."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    n_first = cfg.first_dense_layers if cfg.is_moe else 0
    n_scan = cfg.num_layers - n_first
    kind = _mixer_kind(cfg)

    def one_layer():
        if kind == "mamba":
            return (
                jnp.zeros((batch, cfg.conv_width - 1, S.conv_dim(cfg)), dtype),
                jnp.zeros((batch, cfg.ssm_nheads, cfg.ssm_head_dim,
                           cfg.ssm_state_dim), jnp.float32),
            )
        if kind == "mla":
            return (
                jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
                jnp.zeros((batch, max_seq, cfg.qk_rope_head_dim), dtype),
            )
        return (
            jnp.zeros((batch, max_seq, cfg.num_kv_heads, cfg.head_dim), dtype),
            jnp.zeros((batch, max_seq, cfg.num_kv_heads, cfg.head_dim), dtype),
        )

    stack = jax.tree.map(lambda a: jnp.broadcast_to(a, (n_scan, *a.shape)), one_layer())
    cache = {"layers": stack, "pos": jnp.zeros((batch,), jnp.int32)}
    if n_first:
        cache["first_layers"] = [one_layer() for _ in range(n_first)]
    return cache


def cache_family(cfg) -> str | None:
    """Resolve the cache family this stack pages under (a key into
    ``serving.kvcache.FAMILIES``).  A declared ``cfg.cache_family`` wins;
    otherwise only plain GQA-shaped stacks (gqa/vlm attention) derive a
    family — everything else must declare or gets None (NO silent dense
    fallback: the engine refuses paged mode rather than guessing)."""
    if getattr(cfg, "cache_family", ""):
        return cfg.cache_family
    if cfg.family in ("encdec", "hybrid"):
        return None
    return "gqa" if _mixer_kind(cfg) == "gqa" else None


def supports_paged(cfg) -> bool:
    """Stacks whose decode cache can run in pooled form: GQA k/v block
    pools, MLA latent block pools (smaller rows, same tables), and SSM
    state-slab pools.  Non-uniform MoE prefix layers ride along as an
    extra pool with their own leading axis."""
    return cache_family(cfg) in ("gqa", "mla", "ssm")


def init_paged_cache(cfg, num_blocks: int, block_size: int, dtype=None, *,
                     num_slabs: int = 0, num_segments: int = 0):
    """Zero pooled decode cache, keyed to match :func:`paged_pool_kinds`:

      gqa  — ``layers``: (k, v) pools (L, NB, BS, n_kv, head_dim)
      mla  — ``layers``: (c_kv, k_rope) pools (L, NB, BS, r) / (L, NB, BS,
             rope_dim); MoE prefix layers add ``first_layers`` with their
             own leading axis
      ssm  — ``layers``: (conv, state) SLAB pools (L, NS, W-1, C) /
             (L, NS, H, P, N) fp32 — constant-size, one slab per stream

    Block tables / slab ids and per-row lengths are NOT part of this
    pytree — the serving engine passes them per decode call (they change
    every step; the pool doesn't)."""
    fam = cache_family(cfg)
    if not supports_paged(cfg):
        raise NotImplementedError(
            f"paged decode cache unsupported for family={cfg.family!r} "
            f"attn_type={cfg.attn_type!r}")
    dtype = dtype or jnp.dtype(cfg.dtype)
    n_first = cfg.first_dense_layers if cfg.is_moe else 0
    n_scan = cfg.num_layers - n_first

    def one_layer():
        if fam == "ssm":
            return (
                jnp.zeros((num_slabs, cfg.conv_width - 1, S.conv_dim(cfg)),
                          dtype),
                jnp.zeros((num_slabs, cfg.ssm_nheads, cfg.ssm_head_dim,
                           cfg.ssm_state_dim), jnp.float32),
            )
        if fam == "mla":
            return (
                jnp.zeros((num_blocks, block_size, cfg.kv_lora_rank), dtype),
                jnp.zeros((num_blocks, block_size, cfg.qk_rope_head_dim),
                          dtype),
            )
        shape = (num_blocks, block_size, cfg.num_kv_heads, cfg.head_dim)
        return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))

    def stack(n):
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n, *a.shape)),
                            one_layer())

    pools = {"layers": stack(n_scan)}
    if n_first:
        pools["first_layers"] = stack(n_first)
    return pools


def paged_pool_kinds(cfg) -> dict[str, str]:
    """Pool-kind map for the engine's generic staging/migration: pools-dict
    key -> "block" | "slab" | "segment"."""
    kind = "slab" if cache_family(cfg) == "ssm" else "block"
    kinds = {"layers": kind}
    if cfg.is_moe and cfg.first_dense_layers:
        kinds["first_layers"] = kind
    return kinds


def _shard_cache(cfg, cache):
    kind = _mixer_kind(cfg)
    if kind == "mamba":
        return cache  # state caches: small, head-sharded via params

    def f(x):
        # stacked leaves are (L, B, S, ...): shard batch + sequence
        if x.ndim >= 3:
            return shd.shard_cache_seq(x, batch_axis=1, seq_axis=2)
        return x

    cache = dict(cache)
    cache["layers"] = jax.tree.map(f, cache["layers"])
    return cache


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def embed_tokens(cfg, params, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    return x * 1.0  # keep dtype


def unembed(cfg, params, x):
    table = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, table,
                        preferred_element_type=jnp.float32)
    return shd.shard_logits(logits)


def forward(cfg, params, batch, *, mode: str, cache=None, remat: bool = False,
            remat_policy=None):
    """batch: dict with 'tokens' (B,S) or 'embeds' (B,S,D); optional
    'positions' ((B,S) or (3,B,S) for M-RoPE).  Returns (logits, new_cache,
    aux_loss)."""
    if cfg.embed_inputs and "embeds" in batch:
        x = batch["embeds"]
    else:
        x = embed_tokens(cfg, params, batch["tokens"])
    x = shd.shard_hidden(x)
    b, s, _ = x.shape

    if mode == "decode":
        pos = cache["pos"]  # (B,)
        positions = pos[:, None]
        mrope_positions = batch.get("mrope_positions")  # (3,B,1) or None
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        mrope_positions = batch.get("mrope_positions")
    if cfg.rope_theta == 0.0:  # absolute sinusoidal (whisper-style)
        x = x + L.sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)

    n_first = cfg.first_dense_layers if cfg.is_moe else 0
    aux_total = jnp.zeros((), jnp.float32)
    new_cache = {"pos": None} if mode != "train" else None

    paged = mode == "decode" and cache is not None and (
        "block_tables" in cache or "slab_ids" in cache)
    prefill_lengths = None
    if mode == "prefill" and batch.get("lengths") is not None:
        prefill_lengths = jnp.asarray(batch["lengths"], jnp.int32)

    # -- prefix (non-scanned) layers ------------------------------------
    first_caches = []
    first_pools = cache.get("first_layers") if paged else None
    for i in range(n_first):
        if paged:
            # prefix pools carry their own leading axis; lidx selects it
            lc = first_pools + (jnp.int32(i), cache["block_tables"],
                                cache["pos"])
        elif mode == "decode":
            lc = cache["first_layers"][i] + (cache["pos"],)
        else:
            lc = None
        x, c, aux = block(cfg, params["first_layers"][i], x,
                          positions=positions, mrope_positions=mrope_positions,
                          mode=mode, layer_cache=lc, use_moe=False,
                          lengths=prefill_lengths)
        aux_total += aux
        if paged:
            first_pools = c
        else:
            first_caches.append(c)

    # -- scanned stack ---------------------------------------------------
    # Serving passes the routed experts whole, for the routed path to read
    # just the selected slabs; training keeps them sliced by the scan, where
    # the gradient of a stack the scan closes over is summed whole a layer.
    layers, experts = params["layers"], None
    if mode != "train":
        layers, experts = _split_experts(cfg, layers)
    if paged:
        # the pool stacks ride the scan as CARRY (not xs/ys): each layer
        # scatters one row (or slab) and gathers its live window in place,
        # so the scan never materializes a copy of the whole pool —
        # per-step cost tracks the live rows' work, not pool capacity
        kind = _mixer_kind(cfg)

        def paged_body(carry, lp):
            x, aux_acc, p0, p1, lidx = carry
            if kind == "mamba":
                lc = (p0, p1, lidx, cache["slab_ids"])
            else:  # gqa / mla block pools share the table indirection
                lc = (p0, p1, lidx, cache["block_tables"], cache["pos"])
            x, (p0, p1), aux = block(
                cfg, _with_experts(lp, experts), x, positions=positions,
                mrope_positions=mrope_positions, mode=mode, layer_cache=lc,
                use_moe=cfg.is_moe, layer=lidx)
            return (x, aux_acc + aux, p0, p1, lidx + 1), None

        p0, p1 = cache["layers"]
        carry = (x, aux_total, p0, p1, jnp.int32(0))
        (x, aux_total, p0, p1, _), _ = jax.lax.scan(paged_body, carry, layers)
        layer_caches = (p0, p1)
    else:
        def body(carry, inp):
            x, aux_acc, lidx = carry
            if mode == "decode":
                lp, lc = inp
                lc = lc + (cache["pos"],)
            else:
                lp, lc = inp, None
            x, c, aux = block(cfg, _with_experts(lp, experts), x,
                              positions=positions,
                              mrope_positions=mrope_positions, mode=mode,
                              layer_cache=lc, use_moe=cfg.is_moe,
                              lengths=prefill_lengths,
                              layer=None if experts is None else lidx)
            return (x, aux_acc + aux, lidx + 1), c

        body_fn = body
        if remat:
            body_fn = jax.checkpoint(body, policy=remat_policy)

        xs = (layers, cache["layers"]) if mode == "decode" else layers
        (x, aux_total, _), layer_caches = jax.lax.scan(
            body_fn, (x, aux_total, jnp.int32(0)), xs)

    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = unembed(cfg, params, x)

    if mode == "train":
        return logits, None, aux_total
    out_cache = {"layers": layer_caches, "pos": None}
    if n_first:
        out_cache["first_layers"] = first_pools if paged else first_caches
    if mode == "prefill":
        # per-row true lengths: bucketed prefill batching pads same-bucket
        # prompts to a common length; rows past ``lengths[b]`` hold padding
        # KV that decode masks (and progressively overwrites)
        out_cache["pos"] = (prefill_lengths if prefill_lengths is not None
                            else jnp.full((b,), s, jnp.int32))
        kind = _mixer_kind(cfg)
        if kind in ("gqa", "mla"):
            out_cache = _pad_prefill_cache(cfg, out_cache, batch.get("max_seq", s))
    else:
        out_cache["pos"] = cache["pos"] + 1
        if paged:
            # pools are not (L,B,S,...)-shaped; sharding rules don't apply
            for k in ("block_tables", "slab_ids"):
                if k in cache:
                    out_cache[k] = cache[k]
            return logits, out_cache, aux_total
    return logits, _shard_cache(cfg, out_cache), aux_total


def _pad_prefill_cache(cfg, cache, max_seq: int):
    """Grow prefill caches to max_seq along the sequence axis: axis 2 for
    the scanned stack (L,B,S,...), axis 1 for unstacked prefix layers
    (B,S,...)."""

    def pad_axis(axis):
        def pad(x):
            if x.ndim > axis and x.shape[axis] < max_seq:
                pads = [(0, 0)] * x.ndim
                pads[axis] = (0, max_seq - x.shape[axis])
                return jnp.pad(x, pads)
            return x

        return pad

    cache = dict(cache)
    cache["layers"] = jax.tree.map(pad_axis(2), cache["layers"])
    if "first_layers" in cache:
        cache["first_layers"] = jax.tree.map(pad_axis(1), cache["first_layers"])
    return cache
