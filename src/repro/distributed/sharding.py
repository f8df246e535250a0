"""Sharding rules: logical activation/parameter shardings for the production
mesh, applied via a thread-local context so model code stays mesh-agnostic
(no-ops on CPU smoke tests).

Mesh axes (launch/mesh.py):
  single-pod : (data=16, model=16)
  multi-pod  : (pod=2, data=16, model=16)   # pod extends the data dimension

Parallelism mapping:
  * batch            -> ('pod','data')  (DP; pod axis is DP across DCN)
  * sequence (long)  -> 'model'         (SP for prefill/decode caches)
  * attention heads / FFN columns / experts / vocab -> 'model'   (TP/EP)
  * parameters       -> TP axis over 'model'; optionally FSDP over 'data'
"""

from __future__ import annotations

import re
import threading
from dataclasses import dataclass, field
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_CTX = threading.local()


@dataclass
class ShardingRules:
    mesh: Mesh | None = None
    batch_axes: tuple[str, ...] = ("data",)  # ('pod','data') multi-pod
    model_axis: str = "model"
    fsdp: bool = True  # shard the non-TP param axis over 'data'
    shard_seq: bool = False  # sequence-parallel activations/caches
    # decode long-context: shard cache sequence over (data+model)
    seq_axes: tuple[str, ...] = ("model",)
    # serving/§Perf: shard expert FFN width over the DP axes so MoE decode
    # gathers tokens instead of expert weights (models/moe._moe_decode_tpdata)
    expert_ff_fsdp: bool = False
    # serving/§Perf: 2D tensor parallelism for decode — weights stay fully
    # sharded over (data x model), activations are replicated over the batch
    # axes (psum-combined), the KV cache shards its sequence over both axes.
    # Removes the per-layer FSDP weight all-gathers that dominate decode.
    shard_batch: bool = True

    def batch(self) -> Any:
        if not self.shard_batch:
            return None
        return tuple(self.batch_axes) if len(self.batch_axes) > 1 else self.batch_axes[0]

    def fsdp_axis(self):
        return "data" if self.fsdp else None


def set_rules(rules: ShardingRules | None) -> None:
    _CTX.rules = rules


def current_rules() -> ShardingRules | None:
    return getattr(_CTX, "rules", None)


class use_rules:
    """Context manager: ``with use_rules(rules): ...``"""

    def __init__(self, rules: ShardingRules | None):
        self.rules = rules

    def __enter__(self):
        self.prev = current_rules()
        set_rules(self.rules)
        return self.rules

    def __exit__(self, *exc):
        set_rules(self.prev)


def _constrain(x, spec: P):
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))


# -- activation shardings ----------------------------------------------------


def shard_tokens(x):
    """(B, S) int tokens."""
    r = current_rules()
    if r is None:
        return x
    seq = r.model_axis if r.shard_seq else None
    return _constrain(x, P(r.batch(), seq))


def shard_hidden(x):
    """(B, S, D) activations: batch over DP; seq over model when SP is on."""
    r = current_rules()
    if r is None:
        return x
    seq = r.model_axis if r.shard_seq else None
    return _constrain(x, P(r.batch(), seq, None))


def shard_heads(x):
    """(B, S, N, H) per-head activations: heads over the model axis."""
    r = current_rules()
    if r is None:
        return x
    return _constrain(x, P(r.batch(), None, r.model_axis, None))


def shard_logits(x):
    """(B, S, V) logits: vocab over the model axis."""
    r = current_rules()
    if r is None:
        return x
    return _constrain(x, P(r.batch(), None, r.model_axis))


def shard_ffn(x):
    """(B, S, F) FFN activations: columns over the model axis."""
    r = current_rules()
    if r is None:
        return x
    return _constrain(x, P(r.batch(), None, r.model_axis))


def shard_cache_seq(x, *, batch_axis: int, seq_axis: int):
    """KV/conv caches: shard batch over DP and the sequence axis over the
    model axis (sequence parallelism for long contexts).  When batch is 1
    (long_500k), the sequence is spread over every mesh axis instead."""
    r = current_rules()
    if r is None or r.mesh is None:
        return x
    spec = [None] * x.ndim
    if x.shape[batch_axis] == 1:
        spec[seq_axis] = (*r.batch_axes, r.model_axis)
    else:
        spec[batch_axis] = r.batch()
        spec[seq_axis] = r.seq_axes if len(r.seq_axes) > 1 else r.seq_axes[0]
    return _constrain(x, P(*spec))


# -- parameter shardings -----------------------------------------------------

# leaf-name-pattern -> spec builder; {tp} is the model axis, {fsdp} the
# optional data axis.  Layer-stacked leaves get a leading None inserted by
# param_specs().  Patterns are matched against the '/'-joined tree path.
_PARAM_RULES: list[tuple[str, tuple]] = [
    (r"embed$", ("tp", "fsdp")),             # (V, D): vocab over TP
    (r"pos_embed$", (None, None)),
    (r"lm_head$", ("fsdp", "tp")),           # (D, V)
    (r"w_dkv$", ("fsdp", None)),             # (D, R+Pr): replicated latent
    (r"(wq|wq_a|wq_b)$", ("fsdp", "tp", None)),  # (D, N, H)
    (r"(wk|wv)$", ("fsdp", "tp", None)),
    (r"wo$", ("tp", None, "fsdp")),          # (N, H, D)
    (r"(w_uk|w_uv)$", (None, "tp", None)),   # (R, N, H): heads over TP
    (r"w_krope$", ("fsdp", None)),
    (r"experts/(w_gate|w_up)$", ("tp", "fsdp", None)),  # (E, D, F): EP
    (r"experts/w_down$", ("tp", None, "fsdp")),         # (E, F, D)
    (r"(w_gate|w_up)$", ("fsdp", "tp")),     # (D, F)
    (r"w_down$", ("tp", "fsdp")),            # (F, D)
    (r"router$", ("fsdp", None)),            # (D, E)
    (r"in_proj$", ("fsdp", "tp")),           # SSM in projection (D, inner)
    (r"(z_proj|xbc_proj|dt_proj)$", ("fsdp", "tp")),  # split SSM projections
    (r"out_proj$", ("tp", "fsdp")),          # SSM out projection (inner, D)
    (r"conv_w$", (None, "tp")),              # (width, conv_dim)
    (r"(A_log|dt_bias|ssm_D)$", ("tp",)),    # per-head SSM params
    (r"(norm|scale|bias|b)$", (None,)),      # norms & small vectors
]


def _spec_for(path: str, shape: tuple[int, ...], rules: ShardingRules) -> P:
    tp = rules.model_axis
    fsdp = rules.fsdp_axis()
    if rules.expert_ff_fsdp and re.search(r"experts/", path):
        # serving layout: experts over TP, FFN width over the DP axes
        dp = rules.batch_axes if len(rules.batch_axes) > 1 else rules.batch_axes[0]
        pad = [None] * (len(shape) - 3)
        if re.search(r"experts/(w_gate|w_up)$", path):  # (E, D, F)
            return P(*pad, tp, None, dp)
        if re.search(r"experts/w_down$", path):  # (E, F, D)
            return P(*pad, tp, dp, None)
    for pat, proto in _PARAM_RULES:
        if re.search(pat, path):
            if len(proto) > len(shape):
                proto = proto[-len(shape):]
            axes = []
            for i, a in enumerate(proto):
                name = {"tp": tp, "fsdp": fsdp}.get(a, a) if isinstance(a, str) else a
                # never shard an axis that isn't divisible by the mesh axis
                if name is not None and rules.mesh is not None:
                    size = rules.mesh.shape[name] if not isinstance(name, tuple) else 1
                    if shape[i + (len(shape) - len(proto))] % max(size, 1) != 0:
                        name = None
                axes.append(name)
            pad = [None] * (len(shape) - len(proto))
            return P(*pad, *axes)
    return P(*([None] * len(shape)))


def param_specs(params_shape, rules: ShardingRules, *, stacked_prefix: int = 0):
    """Build a PartitionSpec pytree matching ``params_shape`` (a pytree of
    ShapeDtypeStruct, e.g. from jax.eval_shape(init_params, ...))."""

    def build(path, leaf):
        pathstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        return _spec_for(pathstr, leaf.shape, rules)

    return jax.tree_util.tree_map_with_path(build, params_shape)


def named(params_specs, rules: ShardingRules):
    """PartitionSpec pytree -> NamedSharding pytree."""
    return jax.tree.map(
        lambda s: NamedSharding(rules.mesh, s), params_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
