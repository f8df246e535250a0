"""Compile step programs of the cells' configurations for a described TPU
v5e, with no chip: what the chip's compiler would refuse (a program that
does not fit, an unaligned tile) is refused here.

* ``internlm2-rt-prefill``'s largest prefill: at 4 rows of 4096 it needs
  more than the chip holds beside the weights and the pool, at 2 rows it
  fits; the mix runs at 2 rows (``max_batch`` 2).
* the ``deepseek-v2-lite`` cut (1 dense + 6 MoE layers, every width as
  published, as the program's registry config holds it): its decode,
  prefill and insert programs at the decode mix's sizes.

The topology is described inside a fixture, never at import (only one
process at a time may load the TPU library).
"""

import dataclasses
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.models import model as M
from repro.serving.engine import ServeEngine

HBM = 16e9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def need(engine, cell, sharding) -> float:
    """Bytes the cell's programs need at once, by memory analysis."""
    out = 0.0
    for lowered in engine.lower_cells([cell], sharding=sharding)[cell]:
        m = lowered.compile().memory_analysis()
        out = max(out, m.argument_size_in_bytes + m.output_size_in_bytes
                  + m.temp_size_in_bytes - m.alias_size_in_bytes)
    return out


def engine_for(cfg, **sizes):
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    return ServeEngine(cfg, params, batching=True, paged=True, **sizes)


def test_prefill_mix_fits_at_two_rows_not_four(one_chip):
    cfg = get_config("internlm2_1_8b")
    pool = 2 * 4096 // 16 * 16 * 98_304  # the 2-row mix's KV pool
    e4 = engine_for(cfg, max_seq=4096, max_batch=4, kv_block_size=16)
    e2 = engine_for(cfg, max_seq=4096, max_batch=2, kv_block_size=16)
    try:
        assert need(e4, ("prefill", 4, 4096), one_chip) + pool > HBM
        assert need(e2, ("prefill", 2, 4096), one_chip) + pool < HBM
    finally:
        e4.close()
        e2.close()


@pytest.mark.parametrize("cell", [
    ("decode@mla", 8, 32), ("decode@mla", 1, 64), ("prefill@mla", 8, 512),
    ("insert@mla", 8, 512)])
def test_deepseek_cut_compiles(one_chip, cell):
    cfg = dataclasses.replace(get_config("deepseek_v2_lite_16b"),
                              num_layers=7)
    eng = engine_for(cfg, max_seq=1024, max_batch=8, kv_block_size=16)
    try:
        assert 0 < need(eng, cell, one_chip) < HBM
    finally:
        eng.close()
