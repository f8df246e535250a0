"""Pallas TPU decode attention: one query token against a KV cache.

q (B, Nq, H); k/v caches (B, Nkv, Smax, H); lengths (B,) gives the logical
cache length per sequence (positions >= lengths[b] are masked).

Grid: (B, Nq, Smax/bk), KV dimension sequential, online softmax in VMEM
scratch (same recurrence as the prefill kernel, with a single query row).
Blocks wholly beyond lengths[b] are skipped — for ragged batches the sweep
cost tracks the true cache length, not the buffer size.

The query row is tiny (1, H); we keep it in VMEM and rely on the (bk, H)
cache tile reads being the bandwidth term — decode attention is memory-bound
and the point of the kernel is to stream the cache exactly once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


# -- online-softmax core ----------------------------------------------------
# Shared by the masked-dense kernel below and the paged kernel in
# paged_decode_attention.py: both sweep KV one (bk, H) tile at a time and
# differ only in how the tile is addressed (contiguous slab vs block-table
# indirection).  The recurrence state lives in VMEM scratch:
#   m (1,)  running max,  l (1,)  running denominator,  acc (1, H) numerator.


def online_softmax_init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def online_softmax_block(q, k, v, cols, length, scale, m_ref, l_ref, acc_ref):
    """Fold one KV tile into the recurrence.  q (1,H); k/v (bk,H) fp32;
    ``cols`` (1,bk) are the tile's global cache positions — positions >=
    ``length`` are masked, so callers only need tile-granular early exit."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(cols < length, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def online_softmax_finalize(l_ref, acc_ref):
    denom = jnp.maximum(l_ref[...], 1e-30)
    return acc_ref[...] / denom[:, None]


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, scale: float, bk: int):
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    length = len_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        online_softmax_init(m_ref, l_ref, acc_ref)

    @pl.when(j * bk < length)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)  # (1, H)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, H)
        v = v_ref[0, 0].astype(jnp.float32)
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        online_softmax_block(q, k, v, cols, length, scale, m_ref, l_ref,
                             acc_ref)

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0, 0] = online_softmax_finalize(l_ref, acc_ref).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def decode_attention(q, k, v, lengths, *, scale: float | None = None,
                     block_k: int = 512, interpret: bool = False):
    """q (B,Nq,H); k/v (B,Nkv,Smax,H); lengths (B,) -> (B,Nq,H)."""
    b, nq, h = q.shape
    nkv, smax = k.shape[1], k.shape[2]
    g = nq // nkv
    scale = scale if scale is not None else h ** -0.5
    bk = min(block_k, smax)
    assert smax % bk == 0, (smax, bk)

    grid = (b, nq, smax // bk)
    kernel = functools.partial(_decode_kernel, scale=scale, bk=bk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # lengths, scalar-prefetch style
            pl.BlockSpec((1, 1, 1, h), lambda b_, n, j: (b_, n, 0, 0)),
            pl.BlockSpec((1, 1, bk, h), lambda b_, n, j: (b_, n // g, j, 0)),
            pl.BlockSpec((1, 1, bk, h), lambda b_, n, j: (b_, n // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, h), lambda b_, n, j: (b_, n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nq, 1, h), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, h), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, q[:, :, None, :], k, v)
    return out[:, :, 0, :]
