"""Gradient compression for data-parallel reduction (distributed-optimization
trick): 8-bit quantization with error feedback.

Scheme (per leaf, inside shard_map over the DP axes):
  1. shared scale: pmax of the local absmax over the DP axes (tiny scalar
     collective), scale = absmax / 127;
  2. q = round((g + err)/scale), clipped to [-127, 127] — int8 payload,
     carried as int16 on the wire so the psum accumulation cannot overflow
     (|sum| <= n*127, safe for n <= 257 shards);
  3. mean = psum(q) * scale / n;
  4. err' = (g + err) - q*scale  (error feedback: quantization error is
     re-injected next step — the Seide/Karimireddy condition that keeps
     compressed SGD convergent).

The wire format is 2 bytes/element vs 4 for fp32 — the win targets the
``pod`` axis (DCN) where gradient all-reduce bandwidth is the multi-pod
bottleneck.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize_int8(x):
    """x fp -> (q int8, scale fp32).  Symmetric per-tensor scaling."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def init_error_state(grads):
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def _psum_compressed_leaf(g, e, axes, n: int):
    corrected = g.astype(jnp.float32) + e
    absmax = jax.lax.pmax(jnp.max(jnp.abs(corrected)), axes)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(corrected / scale), -127, 127)
    total = jax.lax.psum(q.astype(jnp.int16), axes)
    mean = total.astype(jnp.float32) * scale / n
    new_err = corrected - q * scale
    return mean.astype(g.dtype), new_err


def compressed_psum_mean(grads, err, mesh, axes: tuple[str, ...]):
    """All-reduce-mean grads over ``axes`` with int8 compression + error
    feedback.  grads/err leaves must be replicated (or identically sharded)
    over ``axes``; leaves keep whatever sharding they have on other axes.

    Returns (mean_grads, new_err)."""
    n = math.prod(mesh.shape[a] for a in axes)
    if n > 257:
        raise ValueError(f"int16 wire overflows beyond 257 shards, got {n}")

    def body(g, e):
        return jax.tree.map(
            lambda gl, el: _psum_compressed_leaf(gl, el, axes, n), g, e)

    # treat every leaf as fully local per shard on `axes`; other mesh axes
    # pass through unsharded specs (caller reshards around this op)
    specs = jax.tree.map(lambda _: P(), grads)
    out = jax.shard_map(body, mesh=mesh, in_specs=(specs, specs),
                        out_specs=jax.tree.map(lambda _: (P(), P()), grads))
    pairs = out(grads, err)
    mean = jax.tree.map(lambda t: t[0], pairs,
                        is_leaf=lambda t: isinstance(t, tuple))
    new_err = jax.tree.map(lambda t: t[1], pairs,
                           is_leaf=lambda t: isinstance(t, tuple))
    return mean, new_err
