"""Compile the main path's Pallas kernels for a TPU v5e at real sizes.

Nothing runs here. The TPU compiler installed with JAX compiles for a
``v5e:2x2`` topology that is described, not attached, and refuses what the
chip would refuse: primitives Mosaic cannot lower, slices that do not fit
the tiling, kernels that overrun fast memory. Shapes are the paper's largest
owner, Dbpedia (Tab. 2): 491,078 entities at d=100, 128 queries per batch.

The topology is described inside a fixture, never while a module is
imported, so every pytest worker collects the same tests and only the
worker running this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.sparse_update.ops import _fused_sparse_step_jit
from repro.kernels.triple_score.ops import (
    _pairwise_scores_jit,
    fused_ranks_pallas_graph,
)

E, R, D, B = 491_078, 14_085, 100, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: an argument shape placed on one chip of a
    described v5e:2x2. The persistent compilation cache is off meanwhile:
    what is compiled for a described chip cannot be read back without
    one."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip
    )
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def test_owner_mesh_keeps_device_order(topo):
    """Group assembly puts owner entry ``k`` on ``jax.devices()[k]``; the
    owner mesh must hold the chips in that order (``jax.make_mesh`` would
    lay a v5e 2x2 out as the ring 0, 1, 3, 2 and swap two owners)."""
    from repro.core.distributed import owner_mesh_over

    mesh = owner_mesh_over(topo.devices)
    assert [d.id for d in mesh.devices.flat] == [d.id for d in topo.devices]


def _assert_kernel_compiles(fn, *args) -> None:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # a Mosaic kernel, not XLA ops


@pytest.mark.parametrize("filter_width", [4, 256])
@pytest.mark.parametrize("mode", ["l1", "l2", "dot"])
def test_fused_rank_kernel_compiles(spec, mode, filter_width):
    """The backtrack-scoring and serving rank kernel at Dbpedia's size."""
    _assert_kernel_compiles(
        lambda q, e, g, f: fused_ranks_pallas_graph(
            q, e, g, f, mode=mode, block_q=8, block_e=512, interpret=False
        ),
        spec((B, D)), spec((E, D)), spec((B,)),
        spec((B, filter_width), jnp.int32),
    )


@pytest.mark.parametrize("mode", ["l1", "dot"])
def test_pairwise_score_kernel_compiles(spec, mode):
    _assert_kernel_compiles(
        lambda q, e: _pairwise_scores_jit(
            q, e, mode=mode, block_q=8, block_e=256, interpret=False
        ),
        spec((B, D)), spec((E, D)),
    )


@pytest.mark.xfail(
    strict=True, raises=ValueError,
    reason="Mosaic refuses the [re | im] half-width lane slices "
           "(\"Shape mismatch in input, indices and output\"); dispatch "
           "sends cl1 to the XLA scan",
)
def test_fused_rank_kernel_cl1_compiles(spec):
    jax.jit(
        lambda q, e, g, f: fused_ranks_pallas_graph(
            q, e, g, f, mode="cl1", block_q=8, block_e=512, interpret=False
        )
    ).lower(
        spec((B, D)), spec((E, D)), spec((B,)), spec((B, 4), jnp.int32)
    ).compile()


@pytest.mark.xfail(
    strict=True, raises=NotImplementedError,
    reason="Mosaic has no lowering for the in-kernel row scatters "
           "(\"Unimplemented primitive ...: scatter\"); dispatch trains "
           "with the XLA sparse step until the kernel keeps its tables in "
           "pl.ANY with row DMA",
)
def test_sparse_update_kernel_compiles(spec):
    b = 100  # the paper's minibatch
    _fused_sparse_step_jit.lower(
        spec((E, D)), spec((R, D)),
        spec((b, 3), jnp.int32), spec((b, 3), jnp.int32), spec(()),
        mode="l1", margin=2.0, interpret=False,
        unique_e=3 * b, unique_r=b,
    ).compile()

