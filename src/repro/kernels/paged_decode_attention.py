"""Pallas TPU paged decode attention: one query token against a BLOCK-POOL
KV cache addressed through a block table (vLLM-style paging, TPU-shaped).

The cache is a dense pool k/v (num_blocks, block_size, Nkv, H); each batch
row owns an ordered list of pool blocks given by ``block_tables`` (B, W)
int32, and ``lengths`` (B,) gives the logical token count.  Block j of row b
holds cache positions [j*block_size, (j+1)*block_size).

Grid: (B, Nq, W), the block-table dimension sequential.  The block table and
lengths ride as scalar-prefetch operands (``PrefetchScalarGridSpec``): the
index map reads ``tables[b, j]`` to DMA exactly the tile the row needs —
the gather IS the addressing, no materialized contiguous copy.  Tiles wholly
past ``lengths[b]`` are skipped, so the sweep cost tracks each row's true
cache length (the server's central knowledge of per-stream lengths, pushed
down into the device loop).

The online-softmax recurrence is shared with the masked-dense kernel
(``decode_attention.online_softmax_*``) — the two paths differ only in tile
addressing, so they stay numerically interchangeable.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (online_softmax_block,
                                            online_softmax_finalize,
                                            online_softmax_init)


def _paged_kernel(tables_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float, bs: int):
    j = pl.program_id(2)
    nb = pl.num_programs(2)
    length = len_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        online_softmax_init(m_ref, l_ref, acc_ref)

    @pl.when(j * bs < length)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)     # (1, H)
        k = k_ref[0, :, 0].astype(jnp.float32)  # (bs, H): one pool block
        v = v_ref[0, :, 0].astype(jnp.float32)
        cols = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        online_softmax_block(q, k, v, cols, length, scale, m_ref, l_ref,
                             acc_ref)

    @pl.when(j == nb - 1)
    def _finalize():
        o_ref[0, 0] = online_softmax_finalize(l_ref, acc_ref).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           scale: float | None = None,
                           interpret: bool = False):
    """q (B,Nq,H); k/v pools (NB,BS,Nkv,H); block_tables (B,W) int32;
    lengths (B,) -> (B,Nq,H).

    ``W * BS`` must cover ``max(lengths)``; table entries past a row's live
    blocks may point anywhere (their tiles are skipped or fully masked).
    """
    b, nq, h = q.shape
    bs, nkv = k_pool.shape[1], k_pool.shape[2]
    g = nq // nkv
    w = block_tables.shape[1]
    scale = scale if scale is not None else h ** -0.5

    kernel = functools.partial(_paged_kernel, scale=scale, bs=bs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, lengths
        grid=(b, nq, w),
        in_specs=[
            pl.BlockSpec((1, 1, 1, h), lambda b_, n, j, t, l: (b_, n, 0, 0)),
            pl.BlockSpec((1, bs, 1, h),
                         lambda b_, n, j, t, l: (t[b_, j], 0, n // g, 0)),
            pl.BlockSpec((1, bs, 1, h),
                         lambda b_, n, j, t, l: (t[b_, j], 0, n // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, h),
                               lambda b_, n, j, t, l: (b_, n, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, h), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nq, 1, h), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, lengths, q[:, :, None, :], k_pool, v_pool)
    return out[:, :, 0, :]


# --------------------------------------------------------------------------
# MLA variant: absorbed decode over latent block pools
# --------------------------------------------------------------------------


def _paged_mla_kernel(tables_ref, len_ref, q_ref, ckv_ref, krope_ref, o_ref,
                      m_ref, l_ref, acc_ref, *, scale: float, bs: int,
                      r: int):
    j = pl.program_id(2)
    nb = pl.num_programs(2)
    length = len_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        online_softmax_init(m_ref, l_ref, acc_ref)

    @pl.when(j * bs < length)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)        # (1, R+PR) absorbed query
        ckv = ckv_ref[0].astype(jnp.float32)       # (bs, R): one pool block
        krope = krope_ref[0].astype(jnp.float32)   # (bs, PR)
        # MLA's key IS (latent ‖ rope-key) and its value IS the latent:
        # the shared online-softmax core handles k/v of different widths
        # (acc is sized by v), so the only MLA-specific work is the concat
        k = jnp.concatenate([ckv, krope], axis=-1)  # (bs, R+PR)
        cols = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        online_softmax_block(q, k, ckv, cols, length, scale, m_ref, l_ref,
                             acc_ref)

    @pl.when(j == nb - 1)
    def _finalize():
        o_ref[0, 0] = online_softmax_finalize(l_ref, acc_ref).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_mla_decode_attention(q_lat, q_rope, ckv_pool, krope_pool,
                               block_tables, lengths, *, scale: float,
                               interpret: bool = False):
    """Absorbed-MLA paged decode: q_lat (B,Nq,R) latent-projected queries,
    q_rope (B,Nq,PR); pools ckv (NB,BS,R), k_rope (NB,BS,PR);
    block_tables (B,W) int32; lengths (B,) -> o_lat (B,Nq,R).

    Per position the key is concat(c_kv, k_rope) and the VALUE is c_kv
    itself, so the kernel is the GQA paged sweep with a different tile
    addressing — the caller applies w_uv to the returned latent output.
    ``scale`` is the model's softmax scale, ``layers.mla_softmax_scale(cfg)``
    (1/sqrt(qk_nope + qk_rope) times YaRN's mscale^2); NOTE the pools carry no
    head axis (the latent is shared by every head — MLA's memory win), so
    each of the Nq sweeps re-reads the same blocks.
    """
    b, nq, r = q_lat.shape
    pr = q_rope.shape[-1]
    bs = ckv_pool.shape[1]
    w = block_tables.shape[1]
    q = jnp.concatenate([q_lat, q_rope], axis=-1)[:, :, None, :]

    kernel = functools.partial(_paged_mla_kernel, scale=scale, bs=bs, r=r)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, lengths
        grid=(b, nq, w),
        in_specs=[
            pl.BlockSpec((1, 1, 1, r + pr),
                         lambda b_, n, j, t, l: (b_, n, 0, 0)),
            pl.BlockSpec((1, bs, r), lambda b_, n, j, t, l: (t[b_, j], 0, 0)),
            pl.BlockSpec((1, bs, pr), lambda b_, n, j, t, l: (t[b_, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, r),
                               lambda b_, n, j, t, l: (b_, n, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, r), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nq, 1, r), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, lengths, q, ckv_pool, krope_pool)
    return out[:, :, 0, :]
