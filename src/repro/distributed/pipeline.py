"""Pipeline parallelism over a mesh axis (GPipe schedule, shard_map +
collective_permute).

Intended use at fleet scale: stage the layer stack over the ``pod`` axis so
only activations (MBs) cross the DCN boundary instead of gradient
all-reduces (GBs) — the multi-pod alternative to pod-level DP.

Mechanics (the standard JAX collective pipeline):
  * each pipeline rank holds ``layers_per_stage`` consecutive layers
    (weights sharded on the stacked-layer axis via shard_map in_specs);
  * the schedule runs ``num_microbatches + num_stages - 1`` ticks; at each
    tick every rank applies its stage to its current activation, then the
    activations rotate one rank forward via ppermute;
  * rank 0 injects a fresh microbatch each tick (while any remain), rank
    P-1 emits a finished microbatch per tick after the fill phase;
  * bubble fraction = (P-1)/(M+P-1), the usual GPipe cost.

``pipeline_apply`` is differentiable (ppermute transposes to the reverse
permutation), so it drops into the training loss unchanged.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn, params, x_mb, *, mesh, axis: str, out_like=None):
    """Run a GPipe pipeline over mesh axis ``axis``.

    stage_fn(stage_params, x) -> y  applies ONE stage (its slice of
    layers).  ``params`` leaves must be stacked with a leading
    ``num_stages`` axis (shard_map shards them so each rank sees its
    stage's slice, with the leading axis collapsed to size 1).
    ``x_mb`` is (num_microbatches, mb_size, ...) and the result has the
    same shape.
    """
    n_stages = mesh.shape[axis]
    n_mb = x_mb.shape[0]
    ticks = n_mb + n_stages - 1

    def run(local_params, xs):
        # local_params leaves: (1, ...) stage slice; drop the stage axis
        sparams = jax.tree.map(lambda a: a[0], local_params)
        rank = jax.lax.axis_index(axis)
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        # pad the microbatch stream through the drain phase
        pad = jnp.zeros((n_stages - 1, *xs.shape[1:]), xs.dtype)
        stream = jnp.concatenate([xs, pad], axis=0)

        def tick(state, x_in):
            # inject at stage 0, everyone computes, rotate forward
            state = jnp.where(rank == 0, x_in, state)
            out = stage_fn(sparams, state)
            emitted = out  # meaningful on the last rank only
            state = jax.lax.ppermute(out, axis, perm)
            return state, emitted

        state0 = jnp.zeros_like(xs[0])
        # the carry becomes rank-varying after the first ppermute: mark it
        # so for the varying-axes checker
        state0 = jax.lax.pcast(state0, (axis,), to="varying")
        _, emitted = jax.lax.scan(tick, state0, stream)
        # finished microbatch m leaves the last rank at tick m + P - 1
        outs = emitted[n_stages - 1:]
        # replicate the last rank's outputs (masked psum proves replication
        # to the varying-axes checker, unlike a broadcast ppermute)
        mask = (rank == n_stages - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, axis)

    pspec = jax.tree.map(lambda _: P(axis), params)
    return jax.shard_map(
        run, mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
    )(params, x_mb)


def stack_stages(layer_params, n_stages: int):
    """(L, ...) stacked layer params -> (n_stages, L/n_stages, ...)."""

    def f(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return a.reshape(n_stages, l // n_stages, *a.shape[1:])

    return jax.tree.map(f, layer_params)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
