"""The routed-experts MoE path against the dense oracle.

``moe_routed`` reads only the experts the router selected; it must give
``moe_dense``'s output, aux loss and gradients (the same arithmetic, summed
in another order).  Cases run at the published expert counts and top-k of
DeepSeek-V2-Lite (64, top-6, raw probabilities) and Qwen3-MoE (128, top-8,
renormalised) at the reduced widths, so that every case selects fewer
experts than there are.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import moe as MOE

ARCHS = ["deepseek_v2_lite_16b", "qwen3_moe_235b_a22b"]


def _cfg(arch, dtype="float32"):
    full = get_config(arch)
    return dataclasses.replace(full.reduced(), dtype=dtype,
                               num_experts=full.num_experts,
                               num_experts_per_tok=full.num_experts_per_tok)


def _tokens(cfg, t, dtype=jnp.float32):
    """(1, t, D) rows as a served decode call pads them: row 1 a near copy
    of row 0 (they share experts) and, past 2 rows, the last row a copy of
    row 0 (the padding ``_run_paged_decode`` adds)."""
    x = jax.random.normal(jax.random.PRNGKey(t), (t, cfg.d_model), jnp.float32)
    if t >= 2:
        x = x.at[1].set(x[0] + 0.01 * x[1])
    if t >= 3:
        x = x.at[-1].set(x[0])
    return x.astype(dtype)[None]


def _params(cfg, seed=0):
    return MOE.init_moe(cfg, jax.random.PRNGKey(seed), jnp.dtype(cfg.dtype))


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("t", [1, 2, 3, 8])
def test_routed_equals_dense(arch, t):
    cfg = _cfg(arch)
    p, x = _params(cfg), _tokens(cfg, t)
    want, aux_want = MOE.moe_dense(cfg, p, x)
    got, aux = jax.jit(lambda pp, xx: MOE.moe_routed(cfg, pp, xx))(p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert float(aux) == float(aux_want)
    if t >= 2:  # shared experts leave slots past the distinct count
        idx, _, _ = MOE.router_topk(cfg, p, x[0])
        assert len(np.unique(np.asarray(idx))) < idx.size


@pytest.mark.parametrize("arch", ARCHS)
def test_routed_equals_dense_in_bfloat16(arch):
    """Each expert's output rounds to bfloat16 on both paths; they differ in
    how the weighted outputs are summed: within two bfloat16 steps."""
    cfg = _cfg(arch, "bfloat16")
    p, x = _params(cfg), _tokens(cfg, 8, jnp.bfloat16)
    want, _ = jax.jit(lambda pp, xx: MOE.moe_dense(cfg, pp, xx))(p, x)
    got, _ = jax.jit(lambda pp, xx: MOE.moe_routed(cfg, pp, xx))(p, x)
    want = np.asarray(want, np.float32)
    step = 2.0 ** -7 * np.abs(want).max()
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=2 * step)


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("t", [1, 3])
def test_routed_gradients_equal_dense(arch, t):
    cfg = _cfg(arch)
    p, x = _params(cfg), _tokens(cfg, t)
    r = jax.random.normal(jax.random.PRNGKey(7), x.shape)

    def loss(path):
        def f(pp, xx):
            out, aux = path(cfg, pp, xx)
            return jnp.sum(out * r) + 0.01 * aux
        return jax.jit(jax.grad(f, argnums=(0, 1)))(p, x)

    got, want = loss(MOE.moe_routed), loss(MOE.moe_dense)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("arch", ARCHS)
def test_routed_reads_its_layer_of_a_stack(arch):
    """A layer scan hands the experts of every layer stacked, with the
    layer's index: the same output as that layer's experts alone."""
    cfg = _cfg(arch)
    p0, p1, x = _params(cfg, 0), _params(cfg, 1), _tokens(cfg, 3)
    stacked = {**p1, "experts": jax.tree.map(lambda a, b: jnp.stack([a, b]),
                                             p0["experts"], p1["experts"])}
    want, _ = MOE.moe_dense(cfg, p1, x)
    got, _ = jax.jit(lambda pp, xx, i: MOE.moe_routed(cfg, pp, xx, i))(
        stacked, x, jnp.int32(1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_dsv2lite_routes_decode_sparsely_not_prefill():
    """The dsv2lite-rt-decode cell's calls: decode rows route sparsely at
    every row bucket (8 x 6 < 64), its prefills (one row of 128-512) not."""
    cfg = get_config("deepseek_v2_lite_16b")
    assert all(MOE.routes_sparsely(cfg, rows) for rows in (1, 2, 4, 8))
    assert not any(MOE.routes_sparsely(cfg, bucket)
                   for bucket in (128, 256, 512))
    assert MOE.routes_sparsely(cfg, 10) and not MOE.routes_sparsely(cfg, 11)
