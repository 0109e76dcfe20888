"""Device-plane tests on the virtual 8-device CPU mesh (SURVEY §4.4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.parallel import (
    DEFAULT_RULES, MeshSpec, allgather, allreduce, alltoall, build_mesh,
    broadcast, local_mesh, logical_sharding, pgroup, reducescatter, send,
    slice_topology,
)
from jax import shard_map


def test_mesh_spec_factor():
    s = MeshSpec.for_devices(8, tp=2)
    assert s.tp == 2 and s.fsdp == 4 and s.dp == 1 and s.size == 8
    s = MeshSpec.for_devices(8, tp=2, fsdp=2)
    assert s.dp == 2 and s.size == 8
    with pytest.raises(ValueError):
        MeshSpec.for_devices(8, tp=3)


def test_build_mesh(cpu_mesh8):
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2), cpu_mesh8)
    assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 2
    assert mesh.devices.size == 8
    topo = slice_topology(cpu_mesh8)
    assert topo["n_devices"] == 8


def test_logical_sharding(cpu_mesh8):
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2), cpu_mesh8)
    s = logical_sharding(mesh, ("batch", "seq", "embed"))
    # batch -> (dp, fsdp); embed -> fsdp already used, drops to replicated.
    assert s.spec == P(("dp", "fsdp"))
    s2 = logical_sharding(mesh, ("embed", "mlp"))
    assert s2.spec == P("fsdp", "tp")
    # Size-1 axes vanish from specs.
    mesh_dp = build_mesh(MeshSpec(dp=8), cpu_mesh8)
    s3 = logical_sharding(mesh_dp, ("embed", "mlp"))
    assert s3.spec == P()


def test_collectives_in_shard_map(cpu_mesh8):
    mesh = build_mesh(MeshSpec(dp=4, tp=2), cpu_mesh8)

    def f(x):
        a = allreduce(x, "tp")
        g = allgather(x, "dp")
        return a, g

    x = jnp.arange(8.0).reshape(8, 1)
    out_a, out_g = jax.jit(shard_map(
        f, mesh=mesh, in_specs=P(("dp", "tp")),
        out_specs=(P(("dp", "tp")), P((), None)), check_vma=False))(x)
    assert out_a.shape == (8, 1)
    # tp pairs (0,1),(2,3)... summed
    np.testing.assert_allclose(np.asarray(out_a)[:4, 0], [1, 1, 5, 5])


def test_pgroup_eager(cpu_mesh8):
    mesh = build_mesh(MeshSpec(dp=8), cpu_mesh8)
    g = pgroup(mesh, "dp")
    assert g.size == 8
    x = jnp.arange(8.0)
    out = g.allreduce(x)
    np.testing.assert_allclose(np.asarray(out), [28.0] * 8)
    b = g.broadcast(jnp.arange(8.0), root=3)
    np.testing.assert_allclose(np.asarray(b), [3.0] * 8)
    sh = g.shift(jnp.arange(8.0), shift=1)
    np.testing.assert_allclose(np.asarray(sh), np.roll(np.arange(8.0), 1))
    g.barrier()


def test_pgroup_reducescatter_per_rank(cpu_mesh8):
    """Leading-axis-is-rank: rank i contributes x[i] and receives the sum
    of every rank's i-th chunk (ref: collective.py:482 semantics)."""
    mesh = build_mesh(MeshSpec(dp=4), cpu_mesh8[:4])
    g = pgroup(mesh, "dp")
    # 4 ranks, each contributing a (4,) vector: rank r contributes
    # r * [1,1,1,1]; reduce-scatter leaves rank i with sum_r x_r[i] = 6.
    x = jnp.broadcast_to(jnp.arange(4.0)[:, None], (4, 4)).reshape(16)
    out = g.reducescatter(x.reshape(16, 1))
    np.testing.assert_allclose(np.asarray(out), np.full((4, 1), 6.0))


def test_reducescatter_and_alltoall(cpu_mesh8):
    mesh = build_mesh(MeshSpec(dp=8), cpu_mesh8)

    def rs(x):
        return reducescatter(x, "dp", scatter_axis=0)

    x = jnp.ones((8, 8))
    out = jax.jit(shard_map(rs, mesh=mesh, in_specs=P(),
                            out_specs=P("dp"), check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 8), 8.0))

    def a2a(x):
        return alltoall(x, "dp", split_axis=1, concat_axis=0)

    # Rank i starts with row i; after a2a rank j holds column j. Reassembling
    # shards as columns must reproduce the original matrix exactly.
    x = jnp.arange(64.0).reshape(8, 8)
    out = jax.jit(shard_map(a2a, mesh=mesh, in_specs=P("dp"),
                            out_specs=P(None, "dp"), check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))
