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


def _qkv(batch=4, seq=64, heads=8, kv_heads=4, head_dim=16):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (batch, seq, heads, head_dim), jnp.float32)
    k = jax.random.normal(ks[1], (batch, seq, kv_heads, head_dim),
                          jnp.float32)
    v = jax.random.normal(ks[2], (batch, seq, kv_heads, head_dim),
                          jnp.float32)
    return q, k, v


@pytest.mark.parametrize("spec", [
    MeshSpec(fsdp=2, tp=2),           # the four-chip train mesh
    MeshSpec(dp=2, fsdp=2, tp=2),
    MeshSpec(tp=4),                   # one kv head per device
])
def test_sharded_attention_matches_unsharded(cpu_mesh8, spec):
    """The per-device attention the multi-chip TPU path runs under
    shard_map (there with the flash kernels, here with the plain path):
    batch rows and GQA head groups land on the device that holds their kv
    heads, forward and backward."""
    from ray_tpu.models.llama import sharded_attention
    from ray_tpu.ops.attention import attention

    mesh = build_mesh(spec, cpu_mesh8[:spec.size])
    q, k, v = _qkv()

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    def sharded(q, k, v):
        return sharded_attention(q, k, v, mesh, use_pallas=False)

    def plain(q, k, v):
        return attention(q, k, v, causal=True, use_pallas=False)

    np.testing.assert_allclose(jax.jit(sharded)(q, k, v), plain(q, k, v),
                               rtol=1e-5, atol=1e-5)
    got = jax.jit(jax.grad(loss(sharded), argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("spec,shape,match", [
    (MeshSpec(tp=8), {}, "k/v dimension 2"),           # 4 kv heads over 8
    (MeshSpec(fsdp=4, tp=2), {"batch": 2}, "q dimension 0"),
])
def test_sharded_attention_names_what_does_not_divide(cpu_mesh8, spec, shape,
                                                      match):
    from ray_tpu.models.llama import sharded_attention

    mesh = build_mesh(spec, cpu_mesh8)
    with pytest.raises(ValueError, match=match):
        sharded_attention(*_qkv(**shape), mesh, use_pallas=False)


def test_sharded_attention_refuses_a_sharded_sequence(cpu_mesh8):
    from ray_tpu.models.llama import sharded_attention

    mesh = build_mesh(MeshSpec(fsdp=2, tp=2), cpu_mesh8[:4])
    rules = tuple(("seq", "fsdp") if name == "seq" else (name, axes)
                  for name, axes in DEFAULT_RULES if name != "batch")
    with pytest.raises(ValueError, match="keeps the sequence whole"):
        sharded_attention(*_qkv(), mesh, rules, use_pallas=False)
