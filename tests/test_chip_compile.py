"""Compile the device path for a described TPU v5e chip, without a chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and refuses what the chip would
refuse (tiles the kernel cannot take, more VMEM than it may use, a
program that does not fit HBM).  These tests compile the bid kernels and
the fused migrate program at the widths of the 2048-GPU cluster
(512 nodes x 4 GPUs), check that the kernels lower to ``tpu_custom_call``
(compiled, not interpreted) and that the temporaries fit one chip's
16 GB.  Nothing runs, so they say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest-xdist
worker imports this file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.fused import lower_fused_round
from repro.kernels import lap_bid
from tests.test_fused_decide import index_ops_by_scope

HBM_BYTES = 16 * 10**9  # one v5e chip
KC, KL, PMAX = 512, 4, 2  # 2048 GPUs
N_WEIGHTS = 4096 + 2  # weight table of a 4096-job trace (max_id + 2)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            return topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler, or its library is held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check(compiled, kernel: bool):
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == kernel
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES


@pytest.mark.usefixtures("no_compile_cache")
def test_lap_bid_compiles_at_2048(one_chip):
    fn = jax.jit(functools.partial(lap_bid.lap_bid_pallas, interpret=False))
    n = 2048
    _check(fn.lower(_spec((n, n), one_chip), _spec((n,), one_chip)).compile(), True)


@pytest.mark.parametrize("n", [4, 512])
@pytest.mark.usefixtures("no_compile_cache")
def test_lap_bid_fused_compiles(one_chip, n):
    fn = jax.jit(
        functools.partial(lap_bid.lap_bid_fused_pallas, tb_scale=2.0**-9, interpret=False)
    )
    _check(fn.lower(_spec((n, n), one_chip), _spec((n,), one_chip)).compile(), True)


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
@pytest.mark.usefixtures("no_compile_cache")
def test_fused_round_compiles_at_2048_gpus(one_chip, monkeypatch, use_kernel):
    # the kernel path resolves interpret mode from the default backend,
    # which is the CPU here; compile what the chip would run instead
    monkeypatch.setattr(lap_bid, "_resolve_interpret", lambda interpret: False)
    compiled = lower_fused_round(
        KC, KL, PMAX, N_WEIGHTS, use_kernel=use_kernel, sharding=one_chip
    ).compile()
    _check(compiled, use_kernel)
    # the 4x4 pair auctions and the 512-node match take the dense bid
    # round: the node match keeps only the gathers of the picked pair
    # totals and of the matching cost, outside its bid loop
    ops = index_ops_by_scope(compiled.as_text())
    assert "pair_auction" not in ops, ops
    assert ops.get("node_match") == 2, ops


@pytest.mark.usefixtures("no_compile_cache")
def test_typed_fused_round_compiles_at_2048_gpus(one_chip):
    """The program of a cluster of several GPU types (256 A100 + 256 V100
    nodes): the type-feasible node match adds no kernel and no gather or
    scatter to the bid loop."""
    compiled = lower_fused_round(
        KC, KL, PMAX, N_WEIGHTS, typed=True, sharding=one_chip
    ).compile()
    _check(compiled, False)
    ops = index_ops_by_scope(compiled.as_text())
    assert "pair_auction" not in ops, ops
    assert ops.get("node_match") == 2, ops
