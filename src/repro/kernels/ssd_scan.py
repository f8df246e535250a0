"""Pallas TPU kernel for the Mamba-2 SSD intra-chunk computation.

The chunked SSD algorithm (models/ssm.py) splits the sequence into chunks;
the O(Q^2) intra-chunk part and the (N x P) chunk-state summary are the
compute hot-spot and live here.  The O(num_chunks) inter-chunk recurrence is
tiny and stays in jnp (lax.scan).

Per grid step (m = batch*chunk index, h = head):
    seg   = cumsum(dA_h)                      (Q,)
    L     = exp(seg_i - seg_j) . causal       (Q, Q)
    w     = (C B^T) * L                       (Q, Q)   <- MXU matmul
    y     = w (x * dt)                        (Q, P)   <- MXU matmul
    s_c   = B^T diag(exp(seg_Q - seg) dt) x   (N, P)   <- MXU matmul
B and C are shared across heads (ngroups=1), so their tiles are fetched
once per (m, *) sweep and reused across the head dimension, which is the
innermost ("arbitrary") grid axis.

VMEM per step (Q=256, N=128, P=64, fp32): L+w 2*256KiB, B/C 2*128KiB,
x 64KiB, outputs <96KiB -> ~1MiB, comfortably inside VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _ssd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, y_ref, s_ref):
    f32 = jnp.float32
    q = x_ref.shape[2]
    x = x_ref[0, 0].astype(f32)      # (Q, P)
    dt = dt_ref[0, 0].astype(f32)    # (Q,)
    da = da_ref[0, 0].astype(f32)    # (Q,)
    bb = b_ref[0].astype(f32)        # (Q, N)
    cc = c_ref[0].astype(f32)        # (Q, N)

    seg = jnp.cumsum(da)             # (Q,)
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    diff = seg[:, None] - seg[None, :]
    L = jnp.exp(jnp.where(rows >= cols, diff, NEG_INF))

    cb = jax.lax.dot_general(cc, bb, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)  # (Q, Q)
    w = cb * L
    xdt = x * dt[:, None]
    y = jax.lax.dot_general(w, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=f32)   # (Q, P)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    dte = jnp.exp(seg[-1] - seg) * dt                     # (Q,)
    s = jax.lax.dot_general(bb * dte[:, None], x, (((0,), (0,)), ((), ())),
                            preferred_element_type=f32)   # (N, P)
    s_ref[0, 0] = s


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra(x, dt, dA, B, C, *, interpret: bool = False):
    """x (M,H,Q,P); dt/dA (M,H,Q); B/C (M,Q,N) ->
    y (M,H,Q,P), s (M,H,N,P) fp32."""
    m, h, q, p = x.shape
    n = B.shape[-1]
    grid = (m, h)
    y, s = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, q), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, q), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, q, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, q, n), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, n, p), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, h, q, p), x.dtype),
            jax.ShapeDtypeStruct((m, h, n, p), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, dA, B, C)
    return y, s


# --------------------------------------------------------------------------
# slab-indexed decode step: per-row SSM state gathered from a SLAB POOL
# --------------------------------------------------------------------------


def _slab_decode_kernel(slab_ref, x_ref, dt_ref, a_ref, b_ref, c_ref,
                        st_ref, y_ref, out_ref):
    f32 = jnp.float32
    st = st_ref[0].astype(f32)    # (H, P, N): this row's slab
    x = x_ref[0].astype(f32)      # (H, P)
    dt = dt_ref[0].astype(f32)    # (H,)
    a = a_ref[...].astype(f32)    # (H,)
    bb = b_ref[0].astype(f32)     # (H, N) head-expanded
    cc = c_ref[0].astype(f32)     # (H, N)

    dec = jnp.exp(dt * a)
    st = dec[:, None, None] * st + (dt[:, None] * x)[..., None] * bb[:, None, :]
    out_ref[0] = st
    y_ref[0] = jnp.sum(st * cc[:, None, :], axis=-1).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_slab_decode(state_pool, slab_ids, x, dt, A, B, C, *,
                    interpret: bool = False):
    """One recurrent SSD step with per-row state addressed THROUGH a slab
    pool: state_pool (NS,H,P,N) fp32, slab_ids (B,) int32, x (B,H,P),
    dt (B,H), A (H,), B/C (B,G,N) -> (y (B,H,P), states (B,H,P,N) fp32).

    ``slab_ids`` rides as a scalar-prefetch operand and the state's index
    map reads ``s[i]`` — the slab gather IS the addressing, mirroring how
    paged_decode_attention addresses KV blocks.  The updated per-row states
    come back gathered; the caller scatters them with
    ``state_pool.at[slab_ids].set(states)`` (slabs are unshared, so the
    scatter cannot race between live rows)."""
    bsz, h, p = x.shape
    n = B.shape[-1]
    hg = h // B.shape[1]
    Bh = jnp.repeat(B, hg, axis=1)  # (B,H,N)
    Ch = jnp.repeat(C, hg, axis=1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # slab_ids
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, h, p), lambda i, s: (i, 0, 0)),
            pl.BlockSpec((1, h), lambda i, s: (i, 0)),
            pl.BlockSpec((h,), lambda i, s: (0,)),
            pl.BlockSpec((1, h, n), lambda i, s: (i, 0, 0)),
            pl.BlockSpec((1, h, n), lambda i, s: (i, 0, 0)),
            pl.BlockSpec((1, h, p, n), lambda i, s: (s[i], 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, p), lambda i, s: (i, 0, 0)),
            pl.BlockSpec((1, h, p, n), lambda i, s: (i, 0, 0, 0)),
        ],
    )
    y, states = pl.pallas_call(
        _slab_decode_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(slab_ids, x, dt, A, Bh, Ch, state_pool)
    return y, states
