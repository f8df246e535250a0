"""Pure-jnp oracles for every Pallas kernel in this package.

These are THE reference semantics: kernel tests sweep shapes/dtypes and
assert allclose against these functions (interpret=True on CPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True, scale: float | None = None):
    """q (B,Nq,S,H); k/v (B,Nkv,S,H) -> (B,Nq,S,H).  Grouped (GQA) heads."""
    b, nq, s, h = q.shape
    nkv = k.shape[1]
    g = nq // nkv
    scale = scale if scale is not None else h ** -0.5
    qg = q.reshape(b, nkv, g, s, h)
    logits = jnp.einsum("bkgsh,bkth->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        idx = jnp.arange(s)
        mask = idx[None, :] <= idx[:, None]
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,bkth->bkgsh", probs, v.astype(jnp.float32))
    return out.reshape(b, nq, s, h).astype(q.dtype)


def decode_attention_ref(q, k, v, lengths, *, scale: float | None = None):
    """q (B,Nq,H); k/v (B,Nkv,S,H); lengths (B,) -> (B,Nq,H).

    Attends to positions < lengths[b] (a KV cache of logical length
    lengths[b] inside a max_seq buffer)."""
    b, nq, h = q.shape
    nkv, s = k.shape[1], k.shape[2]
    g = nq // nkv
    scale = scale if scale is not None else h ** -0.5
    qg = q.reshape(b, nkv, g, h)
    logits = jnp.einsum("bkgh,bkth->bkgt", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    valid = jnp.arange(s)[None, :] < lengths[:, None]  # (B,S)
    logits = jnp.where(valid[:, None, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,bkth->bkgh", probs, v.astype(jnp.float32))
    return out.reshape(b, nq, h).astype(q.dtype)


def paged_decode_attention_ref(q, k_pool, v_pool, block_tables, lengths, *,
                               scale: float | None = None):
    """q (B,Nq,H); k/v pools (NB,BS,Nkv,H); block_tables (B,W) int32;
    lengths (B,) -> (B,Nq,H).

    Gathers each row's blocks into a logically contiguous (W*BS) cache view
    and defers to :func:`decode_attention_ref` — the paged kernel must be
    exactly 'dense decode attention over the gathered view'."""
    bs = k_pool.shape[1]
    b, w = block_tables.shape

    def gather(pool):
        # (B, W, BS, Nkv, H) -> (B, Nkv, W*BS, H)
        seq = pool[block_tables].reshape(b, w * bs, *pool.shape[2:])
        return jnp.swapaxes(seq, 1, 2)

    return decode_attention_ref(q, gather(k_pool), gather(v_pool), lengths,
                                scale=scale)


def paged_mla_decode_attention_ref(q_lat, q_rope, ckv_pool, krope_pool,
                                   block_tables, lengths, *,
                                   scale: float):
    """q_lat (B,Nq,R); q_rope (B,Nq,PR); pools ckv (NB,BS,R) /
    k_rope (NB,BS,PR); block_tables (B,W); lengths (B,) -> o_lat (B,Nq,R).

    Absorbed MLA over the gathered latent view: key = concat(c_kv, k_rope),
    value = c_kv itself."""
    f32 = jnp.float32
    bs = ckv_pool.shape[1]
    b, w = block_tables.shape
    r, pr = q_lat.shape[-1], q_rope.shape[-1]
    ckv = ckv_pool[block_tables].reshape(b, w * bs, r).astype(f32)
    krope = krope_pool[block_tables].reshape(b, w * bs, pr).astype(f32)
    logits = (jnp.einsum("bnr,btr->bnt", q_lat.astype(f32), ckv)
              + jnp.einsum("bnp,btp->bnt", q_rope.astype(f32), krope)) * scale
    valid = jnp.arange(w * bs)[None, :] < lengths[:, None]  # (B, W*BS)
    logits = jnp.where(valid[:, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bnt,btr->bnr", probs, ckv).astype(q_lat.dtype)


def ssd_slab_decode_ref(state_pool, slab_ids, x, dt, A, B, C):
    """state_pool (NS,H,P,N) fp32; slab_ids (B,); x (B,H,P); dt (B,H);
    A (H,); B/C (B,G,N) -> (y (B,H,P), states (B,H,P,N) fp32).

    One SSD recurrent step over each row's gathered slab (same math as
    models.ssm.ssd_decode_step, state addressed through the pool)."""
    f32 = jnp.float32
    h = x.shape[1]
    hg = h // B.shape[1]
    state = state_pool[slab_ids].astype(f32)
    dtf = dt.astype(f32)
    dec = jnp.exp(dtf * A)  # (B,H)
    Bh = jnp.repeat(B, hg, axis=1).astype(f32)  # (B,H,N)
    Ch = jnp.repeat(C, hg, axis=1).astype(f32)
    upd = jnp.einsum("bh,bhp,bhn->bhpn", dtf, x.astype(f32), Bh)
    state = dec[:, :, None, None] * state + upd
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch)
    return y.astype(x.dtype), state


def ssd_intra_ref(x, dt, dA, B, C):
    """Intra-chunk SSD + chunk-state summary (one chunk per leading index).

    x  (M, H, Q, P)   inputs (M = batch*num_chunks)
    dt (M, H, Q)      positive step sizes
    dA (M, H, Q)      dt * A  (negative)
    B  (M, Q, N)      input projection (shared across heads; G=1)
    C  (M, Q, N)      output projection
    returns y (M, H, Q, P) = intra-chunk output,
            s (M, H, N, P) = end-of-chunk state contribution
    """
    f32 = jnp.float32
    seg = jnp.cumsum(dA.astype(f32), axis=-1)  # (M,H,Q)
    q = x.shape[2]
    idx = jnp.arange(q)
    causal = idx[:, None] >= idx[None, :]
    L = jnp.exp(jnp.where(causal[None, None], seg[..., :, None] - seg[..., None, :],
                          -1e30))
    cb = jnp.einsum("min,mjn->mij", C.astype(f32), B.astype(f32))  # (M,Q,Q)
    w = cb[:, None] * L  # (M,H,Q,Q)
    xdt = x.astype(f32) * dt.astype(f32)[..., None]
    y = jnp.einsum("mhij,mhjp->mhip", w, xdt)
    dte = jnp.exp(seg[..., -1:] - seg) * dt.astype(f32)  # (M,H,Q)
    s = jnp.einsum("mhq,mqn,mhqp->mhnp", dte, B.astype(f32), x.astype(f32))
    return y.astype(x.dtype), s.astype(f32)
