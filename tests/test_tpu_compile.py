"""Compile the served path's step programs for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is only described, and refuses what the chip would
refuse (unaligned tiles, too much fast memory, a program that does not fit
the device).  The programs are the ServeEngine's own jitted prefill,
paged-insert, paged-decode and migration steps at internlm2-1.8b's
published widths, lowered from shapes alone by ``ServeEngine.lower_cells``;
``num_layers`` is cut to 2 because the layers run as one scan, so depth
adds nothing the compiler checks.  DeepSeek-V2-Lite's one-row paged decode
(its first 7 layers) is compiled too, to see that the routed experts are
read in place rather than copied.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every xdist worker imports
this file.  Keep all such compiles in this one file.
"""

import dataclasses
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.models import model as M
from repro.serving.engine import ServeEngine

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def engine():
    """The smoke's engine sizes at full width, 2 layers, params as shapes
    only (nothing is allocated)."""
    cfg = dataclasses.replace(get_config("internlm2_1_8b"), num_layers=2)
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    eng = ServeEngine(cfg, params, max_seq=1024, batching=True, paged=True,
                      max_batch=4, kv_block_size=16)
    yield eng
    eng.close()


@pytest.mark.parametrize("cell", [
    ("prefill", 4, 512),
    ("insert", 4, 512),
    ("decode", 1, 32),
    ("decode", 4, 64),
    ("migrate", 64, 16),
])
def test_engine_step_compiles_for_v5e(engine, one_chip, cell):
    programs = engine.lower_cells([cell], sharding=one_chip)[cell]
    assert programs
    for lowered in programs:
        mem = lowered.compile().memory_analysis()
        need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        assert 0 < need < V5E_HBM_BYTES, (cell, need)


def test_moe_decode_reads_routed_experts_in_place(one_chip):
    """The one-row decode of DeepSeek-V2-Lite's first 7 layers (1 dense +
    6 MoE) takes the routed-experts path.  The branches that read each
    selected expert's slabs must slice them from the stacked weights: a copy
    of even one slab (2048 x 1408 bf16, 5.8 MB) would pass the 21 MB of
    scratch that the dense program needs (15.4 MB), and a copy of a layer's
    experts would need over 1 GB."""
    cfg = dataclasses.replace(get_config("deepseek_v2_lite_16b"), num_layers=7)
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    eng = ServeEngine(cfg, params, max_seq=1024, batching=True, paged=True,
                      max_batch=8, kv_block_size=16)
    try:
        cell = ("decode@mla", 1, 64)
        (lowered,) = eng.lower_cells([cell], sharding=one_chip)[cell]
        mem = lowered.compile().memory_analysis()
    finally:
        eng.close()
    assert 0 < mem.temp_size_in_bytes < 21 * 10**6, mem.temp_size_in_bytes
