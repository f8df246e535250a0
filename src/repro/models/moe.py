"""Mixture-of-Experts layer.

Four execution paths, all numerically equivalent (up to capacity drops):

  * dense      — every expert runs on every token, combined by routing
                 weights.  Exact (dropless); the one-chip path when a call's
                 selections can cover every expert (prefill, training), and
                 the reference oracle for the other paths.
  * routed     — the dense path's arithmetic restricted to the experts the
                 router selected: each is read once, by index, from the
                 stacked weights.  Exact (dropless); the one-chip path when a
                 call selects fewer experts than there are (decode; see
                 ``routes_sparsely``).
  * ep_a2a     — expert parallelism over the 'model' mesh axis via
                 shard_map: tokens are dispatched into per-expert capacity
                 buffers locally, exchanged with a single all_to_all,
                 computed on the expert-owning shard, and returned with a
                 second all_to_all.  Used for train/prefill (seq divisible
                 by the model axis).
  * ep_replicated — tokens replicated over the model axis; each shard
                 computes only its local experts and partial outputs are
                 psum-combined.  Used for decode (seq length 1).

Routing: top-k over softmax(router logits) in float32.  With
``norm_topk_prob`` (Qwen3-MoE) the top-k weights are renormalised to sum to
1; without it (DeepSeek-V2) they are the raw probabilities.  Plus the
standard load-balance auxiliary loss.

Named scopes ``moe.router``, ``moe.experts`` and ``moe.shared`` label the
device ops of each part in traces (on the distributed paths the router
runs inside ``moe.experts``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro.models.layers import Params, dense_init, mlp


def init_moe(cfg, key, dtype) -> Params:
    e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p: Params = {
        "router": dense_init(ks[0], (d, e), jnp.float32, scale=0.02),
        "experts": {
            "w_gate": dense_init(ks[1], (e, d, f), dtype, scale=1.0 / math.sqrt(d)),
            "w_up": dense_init(ks[2], (e, d, f), dtype, scale=1.0 / math.sqrt(d)),
            "w_down": dense_init(ks[3], (e, f, d), dtype, scale=1.0 / math.sqrt(f)),
        },
    }
    if cfg.num_shared_experts:
        shared_f = cfg.moe_d_ff * cfg.num_shared_experts
        p["shared"] = {
            "w_gate": dense_init(ks[4], (d, shared_f), dtype),
            "w_up": dense_init(jax.random.fold_in(ks[4], 1), (d, shared_f), dtype),
            "w_down": dense_init(jax.random.fold_in(ks[4], 2), (shared_f, d), dtype),
        }
    return p


@jax.named_scope("moe.router")
def router_topk(cfg, p: Params, x):
    """x (T, D) -> (idx (T,K), weights (T,K), aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    k = cfg.num_experts_per_tok
    top_p, idx = jax.lax.top_k(probs, k)
    weights = top_p
    if cfg.norm_topk_prob:
        weights = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # load-balance aux loss (Switch-style): E * sum_e f_e * P_e
    e = cfg.num_experts
    occupancy = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    f_e = occupancy / idx.size
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e)
    return idx, weights.astype(x.dtype), aux


def _expert_ffn(experts: Params, h):
    """h (E, C, D) -> (E, C, D), batched swiglu over experts."""
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, experts["w_gate"]))
    u = jnp.einsum("ecd,edf->ecf", h, experts["w_up"])
    return jnp.einsum("ecf,efd->ecd", g * u, experts["w_down"])


def _dispatch(tokens, idx, weights, e: int, capacity: int):
    """Scatter tokens into per-expert capacity buffers.

    tokens (T, D); idx/weights (T, K).  Returns (buf (E*C, D), slot (T*K,),
    keep (T*K,)).  Slot assignment is in token order (first-come
    first-served within each expert), overflow tokens are dropped.
    """
    t, k = idx.shape
    flat_e = idx.reshape(-1)  # (T*K,)
    oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # (T*K, E)
    pos = jnp.cumsum(oh, axis=0) - 1  # running count per expert
    safe_e = jnp.minimum(flat_e, e - 1)
    pos_in_e = jnp.take_along_axis(pos, safe_e[:, None], axis=1)[:, 0]
    # flat_e may carry the sentinel value `e` (non-local expert): always drop
    keep = (pos_in_e < capacity) & (flat_e < e)
    slot = jnp.where(keep, flat_e * capacity + pos_in_e, e * capacity)
    src = jnp.repeat(tokens, k, axis=0)  # (T*K, D)
    buf = jnp.zeros((e * capacity + 1, tokens.shape[-1]), tokens.dtype)
    buf = buf.at[slot].add(src * keep[:, None].astype(tokens.dtype))
    return buf[:-1], slot, keep


def _combine(buf_out, slot, keep, weights, t: int, k: int):
    """Gather expert outputs back to tokens and mix with routing weights."""
    d = buf_out.shape[-1]
    padded = jnp.concatenate([buf_out, jnp.zeros((1, d), buf_out.dtype)], axis=0)
    safe_slot = jnp.where(keep, slot, buf_out.shape[0])
    y = padded[safe_slot]  # (T*K, D)
    y = y.reshape(t, k, d) * weights[..., None]
    return jnp.sum(y, axis=1)


def moe_dense(cfg, p: Params, x):
    """Reference path: all experts on all tokens (exact, dropless)."""
    b, s, d = x.shape
    tokens = x.reshape(-1, d)
    idx, weights, aux = router_topk(cfg, p, tokens)
    with jax.named_scope("moe.experts"):
        # (E, T, D): every expert everywhere
        g = jax.nn.silu(jnp.einsum("td,edf->etf", tokens,
                                   p["experts"]["w_gate"]))
        u = jnp.einsum("td,edf->etf", tokens, p["experts"]["w_up"])
        y_all = jnp.einsum("etf,efd->etd", g * u, p["experts"]["w_down"])
        combine = jnp.zeros((tokens.shape[0], cfg.num_experts), x.dtype)
        tk = jnp.arange(tokens.shape[0])[:, None]
        combine = combine.at[tk, idx].add(weights)
        out = jnp.einsum("te,etd->td", combine, y_all)
    return out.reshape(b, s, d), aux


def routes_sparsely(cfg, n_tokens: int) -> bool:
    """Whether a call of ``n_tokens`` tokens selects fewer experts than
    there are, so that the routed path reads fewer than the dense one."""
    return n_tokens * cfg.num_experts_per_tok < cfg.num_experts


def moe_routed(cfg, p: Params, x, layer=None):
    """Routed-experts path (exact, dropless): each distinct expert the
    router selected runs its SwiGLU on every token of the call, added under
    its column of the combine matrix (zero for rows that did not route
    there).  Its weights are sliced by index straight from the stacked
    arrays (``layer`` as in ``moe_layer``), so only the selected slabs are
    read, each once."""
    b, s, d = x.shape
    tokens = x.reshape(-1, d)
    t = tokens.shape[0]
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    idx, weights, aux = router_topk(cfg, p, tokens)
    experts = p["experts"]
    with jax.named_scope("moe.experts"):
        combine = jnp.zeros((t, e), x.dtype)
        combine = combine.at[jnp.arange(t)[:, None], idx].add(weights)
        picked = jnp.zeros((e,), bool).at[idx.reshape(-1)].set(True)
        slots = min(e, t * k)
        (ids,) = jnp.nonzero(picked, size=slots, fill_value=0)
        count = jnp.sum(picked)

        def slab(name, eid):
            w = experts[name]
            if layer is None:
                return jax.lax.dynamic_index_in_dim(w, eid, keepdims=False)
            return jax.lax.dynamic_slice(
                w, (layer, eid, 0, 0), (1, 1, *w.shape[2:]))[0, 0]

        def add_expert(eid, out):
            g = jax.nn.silu(jnp.einsum("td,df->tf", tokens,
                                       slab("w_gate", eid)))
            u = jnp.einsum("td,df->tf", tokens, slab("w_up", eid))
            y = jnp.einsum("tf,fd->td", g * u, slab("w_down", eid))
            c = jax.lax.dynamic_index_in_dim(combine, eid, axis=1)
            return out + c.astype(jnp.float32) * y.astype(jnp.float32)

        out = jnp.zeros((t, d), jnp.float32)
        for j in range(slots):
            if j < k:  # row 0 alone selects k distinct experts
                out = add_expert(ids[j], out)
            else:  # a slot past the distinct count reads nothing
                out = jax.lax.cond(j < count, partial(add_expert, ids[j]),
                                   lambda o: o, out)
    return out.astype(x.dtype).reshape(b, s, d), aux


@jax.named_scope("moe.experts")
def _moe_local(cfg, router, experts, tokens, *, capacity: int, e_local: int,
               axis: str | None):
    """Per-shard MoE body (runs inside shard_map, or standalone if axis None
    with e_local == num_experts)."""
    t, d = tokens.shape
    k = cfg.num_experts_per_tok
    e = cfg.num_experts
    idx, weights, aux = router_topk(cfg, {"router": router}, tokens)
    buf, slot, keep = _dispatch(tokens, idx, weights, e, capacity)

    if axis is not None:
        n = jax.lax.psum(1, axis)
        # (E, C, D) -> exchange so each shard holds its local experts' tokens
        buf = buf.reshape(e, capacity, d)
        buf = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=1, tiled=True)
        # (E_local, n*C, D)
        y = _expert_ffn(experts, buf)
        y = jax.lax.all_to_all(y, axis, split_axis=1, concat_axis=0, tiled=True)
        y = y.reshape(e * capacity, d)
    else:
        y = _expert_ffn(experts, buf.reshape(e, capacity, d)).reshape(e * capacity, d)

    out = _combine(y, slot, keep, weights, t, k)
    return out, aux


@jax.named_scope("moe.experts")
def _moe_replicated_body(cfg, router, experts, tokens, *, capacity: int, axis: str):
    """Decode path: tokens replicated over the model axis; each shard runs
    its local experts only and partial results are psum-combined."""
    t, d = tokens.shape
    k = cfg.num_experts_per_tok
    e = cfg.num_experts
    n = jax.lax.psum(1, axis)
    e_local = e // n
    shard = jax.lax.axis_index(axis)
    idx, weights, aux = router_topk(cfg, {"router": router}, tokens)
    # mask to experts owned by this shard, re-indexed locally
    local = (idx // e_local) == shard
    local_idx = jnp.where(local, idx % e_local, e_local)  # e_local = drop
    w_local = jnp.where(local, weights, 0.0)
    buf, slot, keep = _dispatch(tokens, local_idx, w_local, e_local, capacity)
    y = _expert_ffn(experts, buf.reshape(e_local, capacity, d)).reshape(-1, d)
    out = _combine(y, slot, keep, w_local, t, k)
    return jax.lax.psum(out, axis), aux


@jax.named_scope("moe.experts")
def _moe_decode_tpdata(cfg, rules, p: Params, x):
    """§Perf decode path: expert FFN width sharded over the DP axes.

    Instead of FSDP-gathering ~GBs of expert weights per layer to process a
    few hundred tokens, gather the TOKENS (all_gather over DP: ~MBs),
    compute each (expert-shard x FFN-slice) locally, and combine with
    psum over the model axis (expert partials) + psum_scatter over the DP
    axes (FFN partials + return each shard its own batch slice)."""
    import math as _math

    mesh, axis = rules.mesh, rules.model_axis
    batch = rules.batch()
    n_model = mesh.shape[axis]
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    e_local = e // n_model
    b, s, d = x.shape
    t_all = b * s
    capacity = max(int(_math.ceil(t_all * k / e * cfg.capacity_factor)), 4)

    def body(router, wg, wu, wd, xx):
        xl = xx.reshape(-1, d)
        if rules.shard_batch:
            # tokens sharded over DP: gather them (MBs, vs GBs of weights)
            xa = jax.lax.all_gather(xl, rules.batch_axes, axis=0, tiled=True)
        else:
            xa = xl  # serve_2d: tokens already replicated over DP
        idx, weights, aux = router_topk(cfg, {"router": router}, xa)
        shard = jax.lax.axis_index(axis)
        local = (idx // e_local) == shard
        local_idx = jnp.where(local, idx % e_local, e_local)
        w_local = jnp.where(local, weights, 0.0)
        buf, slot, keep = _dispatch(xa, local_idx, w_local, e_local, capacity)
        hbuf = buf.reshape(e_local, capacity, d)
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", hbuf, wg))
        u = jnp.einsum("ecd,edf->ecf", hbuf, wu)
        y = jnp.einsum("ecf,efd->ecd", g * u, wd).reshape(-1, d)
        out = _combine(y, slot, keep, w_local, t_all, k)  # (T, D)
        out = jax.lax.psum(out, axis)  # sum expert partials over TP
        if rules.shard_batch:
            # sum FFN-width partials over DP + return each shard its tokens
            out = jax.lax.psum_scatter(out, rules.batch_axes,
                                       scatter_dimension=0, tiled=True)
            # aux is identical on every DP shard post-gather, but the VMA
            # system can't infer that through all_gather: pmean to prove it
            aux = jax.lax.pmean(aux, rules.batch_axes)
        else:
            out = jax.lax.psum(out, rules.batch_axes)  # FFN partials only
        return out.reshape(xx.shape), aux

    dp = (tuple(rules.batch_axes) if len(rules.batch_axes) > 1
          else rules.batch_axes[0])
    x_spec = P(batch, None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(axis, None, dp), P(axis, None, dp), P(axis, dp, None),
                  x_spec),
        out_specs=(x_spec, P()),
    )(p["router"], p["experts"]["w_gate"], p["experts"]["w_up"],
      p["experts"]["w_down"], x)


def moe_layer(cfg, p: Params, x, layer=None):
    """Dispatching MoE layer: picks the execution path from the active
    sharding rules and, with no mesh, from the call's shape
    (``routes_sparsely``).  Returns (out (B,S,D), aux_loss).

    With ``layer`` given, ``p["experts"]`` holds the experts of every
    layer stacked on a leading axis, and ``layer`` indexes this one.  Layer
    scans pass them so: a branch of the routed path then slices just its
    expert's slabs from the stack, where a layer's slice handed to it would
    be copied whole by the compiler."""
    b, s, d = x.shape
    rules = shd.current_rules()
    k = cfg.num_experts_per_tok
    e = cfg.num_experts

    one_chip = (rules is None or rules.mesh is None
                or rules.mesh.shape[rules.model_axis] == 1)
    routed = one_chip and routes_sparsely(cfg, b * s)
    if layer is not None and not routed:
        p = {**p, "experts": jax.tree.map(lambda w: w[layer], p["experts"])}
    if routed:
        out, aux = moe_routed(cfg, p, x, layer)
    elif one_chip:
        out, aux = moe_dense(cfg, p, x)
    else:
        mesh = rules.mesh
        axis = rules.model_axis
        n = mesh.shape[axis]
        batch = rules.batch()
        if e % n == 0 and s % n == 0 and s > 1:
            # EP with all_to_all: tokens seq-sharded over the model axis
            t_loc = (b * s) // (n * math.prod(mesh.shape[a] for a in rules.batch_axes))
            capacity = max(_ceil_mult(t_loc * k / e * cfg.capacity_factor, 1), 4)

            all_axes = (*rules.batch_axes, axis)

            def body(router, experts, xx):
                bb, ss, dd = xx.shape
                out, aux = _moe_local(cfg, router, experts, xx.reshape(-1, dd),
                                      capacity=capacity, e_local=e // n, axis=axis)
                return out.reshape(bb, ss, dd), jax.lax.pmean(aux, all_axes)

            out, aux = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(axis, None, None), P(batch, axis, None)),
                out_specs=(P(batch, axis, None), P()),
            )(p["router"], p["experts"], x)
        elif e % n == 0 and rules.expert_ff_fsdp:
            from repro.models import perf

            assert perf.current().moe_decode == "tp_data"
            out, aux = _moe_decode_tpdata(cfg, rules, p, x)
        elif e % n == 0:
            # decode: tokens replicated over model, partial psum combine
            t_loc = (b * s) // math.prod(mesh.shape[a] for a in rules.batch_axes)
            capacity = max(_ceil_mult(t_loc * k / e * cfg.capacity_factor, 1), 4)

            def body(router, experts, xx):
                bb, ss, dd = xx.shape
                out, aux = _moe_replicated_body(
                    cfg, router, experts, xx.reshape(-1, dd),
                    capacity=capacity, axis=axis)
                # aux is computed on model-replicated tokens: it only varies
                # over the DP axes, so average over those alone
                return out.reshape(bb, ss, dd), jax.lax.pmean(aux, rules.batch_axes)

            out, aux = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(axis, None, None), P(batch, None, None)),
                out_specs=(P(batch, None, None), P()),
            )(p["router"], p["experts"], x)
        else:
            out, aux = moe_dense(cfg, p, x)

    if "shared" in p:
        with jax.named_scope("moe.shared"):
            out = out + mlp(cfg, p["shared"], x)
    return shd.shard_hidden(out), aux


def _ceil_mult(x: float, m: int) -> int:
    return int(math.ceil(x / m) * m)
