"""The greedy pick runs on the device: the batched engine's prefill and
decode programs return token ids, and each id must be the one ``np.argmax``
takes over the same float logits on the host — the first maximum on ties,
``-inf`` never above a finite value, and the first NaN where a row has one.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.models import model as M
from repro.serving.engine import ServeEngine, _greedy

V = 16


def _row(case: str) -> np.ndarray:
    row = np.linspace(-1.0, 1.0, V, dtype=np.float32)[::-1].copy()
    if case == "tie":
        row[[3, 7, 11]] = 5.0
    elif case == "neg_inf":
        row[:] = -np.inf
        row[9] = -1e30
    elif case == "all_neg_inf":
        row[:] = -np.inf
    elif case == "nan":
        row[[2, 6]] = np.nan
        row[4] = 9.0
    elif case == "all_nan":
        row[:] = np.nan
    return row


CASES = ["plain", "tie", "neg_inf", "all_neg_inf", "nan", "all_nan"]


def _logits(case: str, rows: int, seq: int = 1) -> np.ndarray:
    """``rows`` rows of (seq, V) logits: row 0 carries the case at every
    position; further rows are the engine's padding (copies of row 0) but
    for row 1, a plain row, so that rows are not all alike."""
    out = np.repeat(_row(case)[None, None], seq, axis=1)
    out = np.repeat(out, rows, axis=0)
    if rows > 1:
        out[1] = np.roll(_row("plain"), 5)
    return out


@pytest.mark.parametrize("rows", [1, 4], ids=["one_row", "padded_rows"])
@pytest.mark.parametrize("case", CASES)
def test_decode_pick_matches_numpy_argmax(case, rows):
    logits = _logits(case, rows)
    ids = np.asarray(jax.jit(_greedy)(jnp.asarray(logits)))
    assert ids.dtype == np.int32 and ids.shape == (rows,)
    np.testing.assert_array_equal(ids, np.argmax(logits[:, -1], axis=-1))


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("internlm2_1_8b").reduced()
    eng = ServeEngine(cfg, M.init_params(cfg, jax.random.PRNGKey(0)),
                      max_seq=32, batching=True)
    yield eng
    eng.close()


@pytest.mark.parametrize("rows", [1, 4], ids=["one_row", "padded_rows"])
@pytest.mark.parametrize("case", CASES)
def test_prefill_pick_matches_numpy_argmax(engine, case, rows):
    """The prefill pick reads each row at its own true last position."""
    logits = _logits(case, rows, seq=8)
    lens = np.array([8, 3, 8, 8][:rows], np.int32)
    if rows > 1:  # row 1's true last position holds the case's row
        logits[1, 2] = _row(case)
    ids = np.asarray(engine._last_token(jnp.asarray(logits),
                                        jnp.asarray(lens)))
    want = np.argmax(logits[np.arange(rows), lens - 1], axis=-1)
    assert ids.dtype == np.int32
    np.testing.assert_array_equal(ids, want)


@pytest.mark.parametrize("arch,family", [("internlm2_1_8b", "gqa"),
                                         ("deepseek_v2_lite_16b", "mla")])
def test_paged_decode_ids_are_argmax_of_its_logits(arch, family):
    """``_decode_paged`` (the served program) returns, for the same pack
    and pools, the argmax of the logits ``_decode_paged_impl`` computes —
    every live row and every padding row."""
    cfg = get_config(arch).reduced()
    assert M.cache_family(cfg) == family
    params = M.init_params(cfg, jax.random.PRNGKey(2))
    eng = ServeEngine(cfg, params, max_seq=32, batching=True, max_batch=4,
                      paged=True, kv_block_size=8)
    try:
        pools = eng._make_pools(0)
        keys = iter(jax.random.split(jax.random.PRNGKey(5), 64))
        pools = jax.tree.map(
            lambda x: (jax.random.normal(next(keys), x.shape, x.dtype)
                       if jnp.issubdtype(x.dtype, jnp.floating) else x),
            pools)
        state = eng._paged[0]
        # rows: two live sequences of 5 and 13 tokens, padded to 4 rows by
        # copies of row 0, as _run_paged_decode pads
        pack = np.zeros((4, 4 + 2), np.int32)
        pack[0] = [7, 5, state.scratch_slab, state.scratch_seg, 1, 2]
        pack[1] = [11, 13, state.scratch_slab, state.scratch_seg, 3, 4]
        pack[2:] = pack[0]
        params = eng._params_on(0)
        packed = eng._put(0, pack)
        logits, _ = jax.jit(eng._decode_paged_impl)(params, packed, pools)
        want = np.argmax(np.asarray(logits, np.float32)[:, -1], axis=-1)
        ids, _ = eng._decode_paged(params, packed, pools)  # donates pools
        ids = np.asarray(ids)
        assert ids.dtype == np.int32 and ids.shape == (4,)
        np.testing.assert_array_equal(ids, want)
    finally:
        eng.close()
