"""Pallas TPU fused RMSNorm.

Why a kernel: the XLA path (models/layers.rms_norm) upcasts the (B,S,D)
activation to f32, reduces, rescales, and casts back — on the dry-run
profile this f32 round-trip of the residual stream is a top-5 HBM
contributor on every train cell (EXPERIMENTS.md §Perf diagnosis).  The
fused kernel reads the bf16 row once, keeps the f32 math in VMEM, writes
the bf16 row once: 2 x D bytes per row instead of ~6 x.

Grid: one program per row block (rows = flattened batch*seq).  D stays
whole per block (d_model <= 16k -> a (block_rows, D) bf16 tile plus f32
scratch fits VMEM comfortably: 256 x 16384 x 2B = 8 MiB at the largest).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (rows, D)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, w, *, eps: float = 1e-5, block_rows: int = 256,
            interpret: bool = False):
    """x (..., D); w (D,) -> same shape/dtype as x.

    Row counts that are not a multiple of ``block_rows`` are zero-padded up
    to the next block boundary (each row normalizes independently, so the
    pad rows are dead work, discarded on the way out) — keeping the block
    size large instead of shrinking it to a divisor of the row count.
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    br = min(block_rows, rows)
    rows_p = -(-rows // br) * br
    if rows_p != rows:
        x2 = jnp.pad(x2, ((0, rows_p - rows), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rows_p // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2, w)
    return out[:rows].reshape(orig_shape)
