"""Per-architecture smoke tests: instantiate the REDUCED config of each
family, run forward/train/prefill/decode on CPU, assert shapes + finiteness.
The FULL configs are exercised only via the dry-run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCH_IDS, get_config
from repro.models import model as M

jax.config.update("jax_platform_name", "cpu")


def _batch_for(cfg, b=2, s=16, key=None):
    key = key or jax.random.PRNGKey(0)
    batch = {
        "tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
    }
    if cfg.family == "vlm":
        del batch["tokens"]
        batch["embeds"] = jax.random.normal(key, (b, s, cfg.d_model),
                                            jnp.float32).astype(cfg.dtype)
        pos = jnp.broadcast_to(jnp.arange(s)[None, None, :], (3, b, s))
        batch["mrope_positions"] = pos
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            key, (b, cfg.encoder_seq, cfg.d_model), jnp.float32).astype(cfg.dtype)
    return batch


@pytest.fixture(scope="module")
def built():
    """Init each reduced arch once per test session."""
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_config(arch).reduced()
            params = M.init_params(cfg, jax.random.PRNGKey(42))
            cache[arch] = (cfg, params)
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_forward(arch, built):
    cfg, params = built(arch)
    batch = _batch_for(cfg)
    logits, _, aux = M.apply(cfg, params, batch, mode="train")
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_gradients(arch, built):
    cfg, params = built(arch)
    batch = _batch_for(cfg)

    def loss(p):
        return M.loss_fn(cfg, p, batch, remat=True)[0]

    val, grads = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(val))
    leaves = jax.tree.leaves(grads)
    assert leaves, "no gradients"
    for g in leaves:
        assert np.isfinite(np.asarray(g, np.float32)).all()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_then_decode(arch, built):
    cfg, params = built(arch)
    b, s, max_seq = 2, 8, 16
    batch = _batch_for(cfg, b, s)
    batch["max_seq"] = max_seq
    logits, cache, _ = M.apply(cfg, params, batch, mode="prefill")
    assert logits.shape == (b, s, cfg.vocab_size)
    assert cache is not None
    assert int(cache["pos"][0]) == s

    step = {"tokens": jnp.array([[1], [2]], jnp.int32)}
    if cfg.family == "vlm":
        del step["tokens"]
        step["embeds"] = jnp.ones((b, 1, cfg.d_model), cfg.dtype)
        step["mrope_positions"] = jnp.full((3, b, 1), s, jnp.int32)
    logits2, cache2, _ = M.apply(cfg, params, step, mode="decode", cache=cache)
    assert logits2.shape == (b, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits2, np.float32)).all()
    assert int(cache2["pos"][0]) == s + 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_full_forward(arch, built):
    """Teacher-forced decode must reproduce the full-sequence forward
    logits (the KV/state caches are exact, not approximations).  Run in
    fp32: the property is cache exactness — in bf16 the absorbed-MLA and
    SSD decode paths reorder reductions and differ by ~1e-2, which is
    precision, not logic (verified fp32 max diff <= 4e-6)."""
    import dataclasses

    cfg, _ = built(arch)
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(42))
    b, s = 1, 8
    batch = _batch_for(cfg, b, s)
    full_logits, _, _ = M.apply(cfg, params, batch, mode="train")

    pre = {k: (v[:, :4] if k in ("tokens", "labels") else v)
           for k, v in batch.items()}
    if cfg.family == "vlm":
        pre["embeds"] = batch["embeds"][:, :4]
        pre["mrope_positions"] = batch["mrope_positions"][:, :, :4]
    pre["max_seq"] = s
    _, cache, _ = M.apply(cfg, params, pre, mode="prefill")

    outs = []
    for t in range(4, s):
        step = {"tokens": batch["tokens"][:, t:t + 1]} if cfg.family != "vlm" else {}
        if cfg.family == "vlm":
            step["embeds"] = batch["embeds"][:, t:t + 1]
            step["mrope_positions"] = batch["mrope_positions"][:, :, t:t + 1]
        if cfg.family == "encdec":
            step["tokens"] = batch["tokens"][:, t:t + 1]
        lg, cache, _ = M.apply(cfg, params, step, mode="decode", cache=cache)
        outs.append(np.asarray(lg[:, 0], np.float32))

    want = np.asarray(full_logits[:, 4:s], np.float32)
    got = np.stack(outs, axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_cache_family_resolves(arch):
    """Every registry entry must resolve to a paged cache family — either
    declared (cfg.cache_family) or derived (plain GQA stacks only).  A
    None here would mean the arch silently loses the paged serving path
    and falls back to dense, which the serving layer forbids."""
    from repro.serving.kvcache import FAMILIES

    cfg = get_config(arch).reduced()
    fam = M.cache_family(cfg)
    assert fam is not None, f"{arch}: no cache family (silent dense fallback)"
    assert fam in FAMILIES, f"{arch}: unknown family {fam!r}"
    assert M.supports_paged(cfg), arch
    # the declaration (when present) is what resolution honors
    if cfg.cache_family:
        assert fam == cfg.cache_family


@pytest.mark.parametrize("arch", ["llama3_405b", "qwen3_moe_235b_a22b",
                                  "deepseek_v2_lite_16b", "mamba2_780m",
                                  "zamba2_7b", "whisper_medium"])
def test_param_count_matches_init(arch):
    """Analytical param_count (used for roofline MODEL_FLOPS) must match the
    actual initialized tree of the reduced config."""
    cfg = get_config(arch).reduced()
    shapes = jax.eval_shape(lambda k: M.init_params(cfg, k), jax.random.PRNGKey(0))
    actual = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    expected = M.param_count(cfg)
    assert actual == expected, f"{arch}: init {actual} vs analytical {expected}"


def test_full_config_param_counts():
    """Sanity-check the FULL configs' analytical sizes (billions)."""
    expect = {
        "llama3_405b": (390e9, 420e9),
        "granite_34b": (32e9, 38e9),
        "internlm2_20b": (17e9, 22e9),
        "internlm2_1_8b": (1.6e9, 2.1e9),
        "qwen3_moe_235b_a22b": (225e9, 245e9),
        "deepseek_v2_lite_16b": (13e9, 17e9),
        "mamba2_780m": (0.6e9, 0.9e9),
        "qwen2_vl_2b": (1.2e9, 2.3e9),
        # whisper-medium is 769M (enc+dec, tied unembedding)
        "whisper_medium": (0.70e9, 0.85e9),
        # zamba2-7b minus the per-use LoRA deltas on the shared block
        # (omitted; DESIGN.md §5) lands at ~5.7B
        "zamba2_7b": (5e9, 9e9),
    }
    for arch, (lo, hi) in expect.items():
        n = M.param_count(get_config(arch))
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B outside [{lo/1e9},{hi/1e9}]"
