"""Pallas TPU flash attention (prefill, causal, GQA).

Layout: q (B, Nq, S, H); k/v (B, Nkv, S, H) — heads-major so the (S, H)
tile is contiguous and MXU-aligned (H and the block sizes are multiples of
128 at production scale; the wrapper pads smaller test shapes).

Grid: (B, Nq, S/bq, S/bk) with the last (KV) dimension sequential
("arbitrary") — the online-softmax running max/denominator/accumulator live
in VMEM scratch across the KV sweep and the output block is written once on
the final visited KV block.  Causal blocks with j > i are skipped entirely
(their iterations early-out), halving the work versus a dense sweep.

VMEM budget per step (bq=bk=256, H=128, fp32 scratch):
  q/k/v tiles 3*256*128*2B = 192KiB, logits 256*256*4B = 256KiB,
  acc 256*128*4B = 128KiB  -> well under the ~16MiB VMEM/core.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, bq: int, bk: int):
    i = pl.program_id(2)  # query block
    j = pl.program_id(3)  # kv block
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: block is relevant iff any query row can see any kv column
    run = (not causal) or (j * bk <= i * bq + bq - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, H)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, H)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool = False):
    """q (B,Nq,S,H); k/v (B,Nkv,S,H) -> (B,Nq,S,H)."""
    b, nq, s, h = q.shape
    nkv = k.shape[1]
    g = nq // nkv
    scale = scale if scale is not None else h ** -0.5
    bq = min(block_q, s)
    bk = min(block_k, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)

    grid = (b, nq, s // bq, s // bk)
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, h), lambda b_, n, i, j: (b_, n, i, 0)),
            pl.BlockSpec((1, 1, bk, h), lambda b_, n, i, j: (b_, n // g, j, 0)),
            pl.BlockSpec((1, 1, bk, h), lambda b_, n, i, j: (b_, n // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, h), lambda b_, n, i, j: (b_, n, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, h), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
