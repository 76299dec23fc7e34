"""Ahead-of-time compiles of the session's main-path programs for a
described TPU v5e, at the sizes ``chip_smoke.py`` runs on the chip.

Nothing runs: the TPU compiler refuses here what the chip would refuse
(block tiling, on-chip memory, shapes that do not fit), which the CPU
executors and the Pallas interpreter never check. The topology is
described inside a module fixture, so collecting this file loads no TPU
library, and the fixture skips where the library cannot describe one.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.distributed import make_cluster_step
from repro.core.halo_gnn import abstract_dist_graph
from repro.core.partition_state import PartitionState
from repro.core.repartitioner import adapt_jit
from repro.graph.structure import Graph
from repro.kernels.migration_kernels import pallas_score_select

# the 1M-vertex R-MAT session of chip_smoke.py (avg degree 8; e_cap is the
# generated live edges plus the session's 25% streaming head-room)
RMAT_N = 1_000_000
RMAT_E_CAP = 4_876_851


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _state(n_cap, k, sharding):
    return PartitionState(
        assignment=_sds((n_cap,), jnp.int32, sharding),
        pending=_sds((n_cap,), jnp.int32, sharding),
        capacity=_sds((k,), jnp.int32, sharding),
        rng=_sds((2,), jnp.uint32, sharding),
        iteration=_sds((), jnp.int32, sharding),
        last_moves=_sds((), jnp.int32, sharding))


@pytest.mark.parametrize("blk", [64, 128])
def test_fused_scorer_compiles_at_fem64(one_chip, blk):
    """The Mosaic kernel at chip_smoke's kernel phase: a 64^3 FEM cube,
    k=9. A tile row touches at most five tile columns (itself and its y/z
    neighbours; the blk=64 pack holds 20,224 tiles)."""
    k = 9
    n_blocks = 64 ** 3 // blk
    nnzb = 5 * n_blocks
    n_pad = n_blocks * blk
    args = (_sds((nnzb, blk, blk), jnp.float32, one_chip),
            _sds((nnzb,), jnp.int32, one_chip),
            _sds((n_blocks + 1,), jnp.int32, one_chip),
            _sds((n_pad,), jnp.int32, one_chip),
            _sds((n_pad,), jnp.bool_, one_chip),
            _sds((n_pad, k), jnp.float32, one_chip),
            _sds((n_pad,), jnp.bool_, one_chip))
    for tie_break in ("random", "stay"):
        compiled = pallas_score_select.lower(
            *args, k=k, max_per_row=5, tie_break=tie_break).compile()
        assert "tpu_custom_call" in compiled.as_text()


def test_streaming_adapt_step_compiles_at_1m_rmat(one_chip):
    """The local session's per-superstep adapt program (fused flat scorer,
    4 rounds, k=8) at the 1M-vertex R-MAT session's shapes."""
    k = 8
    graph = Graph(src=_sds((RMAT_E_CAP,), jnp.int32, one_chip),
                  dst=_sds((RMAT_E_CAP,), jnp.int32, one_chip),
                  node_mask=_sds((RMAT_N,), jnp.bool_, one_chip),
                  edge_mask=_sds((RMAT_E_CAP,), jnp.bool_, one_chip))
    step = jax.jit(partial(adapt_jit, s=0.5, iters=4, tie_break="random",
                           backend="pallas"))
    compiled = step.lower(graph, _state(RMAT_N, k, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def test_cluster_step_compiles_on_four_chips(topo):
    """The sharded backend's migration step over a 4-chip mesh at k=4,
    with bucket shapes of the 1M-vertex R-MAT session."""
    k = P = 4
    mesh = Mesh(np.asarray(topo.devices[:P]), ("nodes",))
    shard = NamedSharding(mesh, PartitionSpec("nodes"))
    repl = NamedSharding(mesh, PartitionSpec())
    n_blk, e_blk, halo = 327_680, 2_621_440, 131_072
    dg = jax.tree.map(lambda s: _sds(s.shape, s.dtype, shard),
                      abstract_dist_graph(P, n_blk, e_blk, halo))
    st = _state(RMAT_N, k, repl)
    step = make_cluster_step(mesh, k=k, n_cap=RMAT_N).jitted
    compiled = step.lower(
        st.assignment, st.pending, st.rng, st.capacity,
        _sds((), jnp.float32, repl), dg,
        _sds((P * n_blk,), jnp.bool_, shard),
        _sds((P * n_blk,), jnp.int32, shard),
        _sds((RMAT_N,), jnp.int32, repl),
        _sds((RMAT_N,), jnp.bool_, repl)).compile()
    text = compiled.as_text()
    assert "all-gather" in text
    assert "all-reduce" in text
