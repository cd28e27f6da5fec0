"""Compile the solver's main-path programs for a described TPU v5e.

The only file that compiles for a chip. Nothing runs: the TPU compiler
refuses here, at no chip time, what it would refuse on the machine with
the chip (unsupported ops, programs that overflow device memory, sharded
programs the partitioner rejects). The topology is described inside a
module fixture, never at import: only the worker that runs this file loads
the TPU library, and every worker collects the same tests.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, SingleDeviceSharding

from kube_batch_tpu.solver import kernels, select_device, sharding, spmd

HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices[:4]), (sharding.NODE_AXIS,))


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A described-chip compile cannot be read back without the chip, so
    keep it out of any persistent cache this worker may have turned on."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def packed_spec(sharding_of, T, N, R=2, Q=4, C=32, K=64):
    """ShapeDtypeStructs of a tensorize PackedInputs bundle."""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding_of)

    return kernels.PackedInputs(
        task_f32=s((2, T, R), jnp.float32),
        task_i32=s((6, T), jnp.int32),
        node_f32=s((3, N, R), jnp.float32),
        node_i32=s((3, N), jnp.int32),
        group_feas=s((1, N), jnp.bool_),
        pair_idx=s((0,), jnp.int32),
        pair_feas=s((0, N), jnp.bool_),
        score_idx=s((0,), jnp.int32),
        score_rows=s((0, N), jnp.float32),
        queue_f32=s((2, Q, R), jnp.float32),
        misc=s((R + 2,), jnp.float32),
        cand_idx=s((C, K), jnp.int32),
        cand_static=s((C, K), jnp.float32),
        cand_info=s((3, C), jnp.int32),
    )


def fits_one_chip(compiled):
    m = compiled.memory_analysis()
    total = (m.temp_size_in_bytes + m.argument_size_in_bytes
             + m.output_size_in_bytes)
    assert 0 < total < HBM_BYTES, total


def test_topk_selection_compiles(one_chip):
    # The 50k x 5k headline's selection: 25 classes bucketed to 32 rows,
    # 5,000 nodes padded to 5,120, K = 64; int64 tie keys need x64.
    keys = jax.ShapeDtypeStruct((32, 5120), jnp.int32, sharding=one_chip)
    with jax.enable_x64(True):
        compiled = select_device._topk_jit(64, 5000).lower(keys).compile()
    fits_one_chip(compiled)


def test_sparse_solve_compiles(one_chip):
    # 10k x 1k snapshot shapes: the sparse program tensorize hands
    # solve_jit (slab rounds + the compacted dense tail).
    spec = packed_spec(one_chip, T=10240, N=1024)
    compiled = kernels.solve_jit.lower(spec, max_rounds=256).compile()
    fits_one_chip(compiled)


def test_flat_sparse_shard_step_compiles(mesh4):
    # The task-sharded sparse solve on a 2x2 mesh: inputs replicated,
    # slab rows sharded inside the shard_map body, commits exchanged
    # through collectives.
    shardings = spmd.sparse_spmd_shardings_for(
        packed_spec(None, T=8192, N=1024), mesh4
    )
    spec = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        packed_spec(None, T=8192, N=1024), shardings,
    )
    step = spmd._spmd_sparse_step(mesh4, 256, 3072, False)
    compiled = step.lower(spec).compile()
    text = compiled.as_text()
    assert "all-reduce" in text or "all-gather" in text
    fits_one_chip(compiled)


def test_rack_perm_orders_by_chip_coords(mesh4):
    # Two-level racks follow the physical (slice, coords) order that a
    # real TPU reports; CPU meshes have no coords and keep identity.
    devs = list(np.asarray(mesh4.devices).flat)
    perm = sharding.rack_perm(mesh4)
    assert sorted(perm.tolist()) == [0, 1, 2, 3]
    by_rack = sorted(range(4), key=lambda i: perm[i])
    coords = [tuple(devs[i].coords) for i in by_rack]
    assert coords == sorted(coords)
