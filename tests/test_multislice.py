"""Multi-slice (two-level ICI/DCN) tests on the virtual 8-device mesh.

Covers: slice grouping/mesh construction, two-level collectives equal
their flat forms, the 2-slice train step matching the single-mesh
oracle, and slice-per-stage pipelining (SURVEY §5.8, §7.4)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel import (MeshSpec, build_mesh, build_multislice_mesh,
                              group_devices_by_slice, multislice_rules,
                              pipeline_apply, split_stages,
                              two_level_pmean, two_level_psum)


@pytest.fixture
def devices(cpu_mesh8):
    return cpu_mesh8


def test_build_multislice_mesh_shape(devices):
    mesh = build_multislice_mesh({"dp": 2, "tp": 2}, n_slices=2,
                                 devices=devices)
    assert mesh.axis_names == ("dcn", "dp", "tp")
    assert mesh.devices.shape == (2, 2, 2)
    # slice 0 devices all precede slice 1 devices (chunked grouping)
    ids = [d.id for d in mesh.devices[0].flat]
    ids2 = [d.id for d in mesh.devices[1].flat]
    assert max(ids) < min(ids2)


def test_group_devices_by_slice_cpu_collapses(devices):
    groups = group_devices_by_slice(devices)
    assert sum(len(g) for g in groups) == len(devices)


def test_two_level_psum_equals_flat(devices):
    mesh = build_multislice_mesh({"dp": 4}, n_slices=2, devices=devices)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16))

    out = jax.jit(shard_map(
        lambda a: two_level_psum(a, intra_axis="dp"),
        mesh=mesh, in_specs=P(("dcn", "dp")), out_specs=P(("dcn", "dp")),
        check_vma=False))(x)
    want = np.broadcast_to(np.asarray(x).sum(0), x.shape)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5)

    out = jax.jit(shard_map(
        lambda a: two_level_pmean(a, intra_axis="dp"),
        mesh=mesh, in_specs=P(("dcn", "dp")), out_specs=P(("dcn", "dp")),
        check_vma=False))(x)
    want = np.broadcast_to(np.asarray(x).mean(0), x.shape)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5)


def test_multislice_train_step_matches_single_mesh(devices):
    import optax

    from ray_tpu.models import (LLAMA_CONFIGS, init_params, lm_loss,
                                param_logical_axes)
    from ray_tpu.train import make_train_step

    cfg = LLAMA_CONFIGS["tiny"]
    base = init_params(jax.random.PRNGKey(0), cfg)
    # each branch gets its own param copies: device_put may ALIAS a
    # replicated leaf's buffer, and the donated train step would delete
    # it out from under the other branch
    fresh = lambda: jax.tree.map(jnp.array, base)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                cfg.vocab, jnp.int32)

    ms_mesh = build_multislice_mesh({"dp": 2, "fsdp": 1, "tp": 2},
                                    n_slices=2, devices=devices)
    rules = multislice_rules()
    init_fn, step_fn, place = make_train_step(
        lambda p, b: lm_loss(p, b, cfg, mesh=ms_mesh, rules=rules),
        optax.adamw(1e-3), ms_mesh, param_logical_axes(cfg), rules=rules)
    _, ms_metrics = step_fn(init_fn(fresh()), place({"tokens": tokens}))

    o_mesh = build_mesh(MeshSpec(dp=8), devices)
    o_init, o_step, o_place = make_train_step(
        lambda p, b: lm_loss(p, b, cfg, mesh=o_mesh),
        optax.adamw(1e-3), o_mesh, param_logical_axes(cfg))
    _, o_metrics = o_step(o_init(fresh()), o_place({"tokens": tokens}))

    np.testing.assert_allclose(float(ms_metrics["loss"]),
                               float(o_metrics["loss"]), rtol=1e-5)


def test_slice_per_stage_pipeline(devices):
    pp_mesh = build_multislice_mesh({"dp": 4}, n_slices=2,
                                    devices=devices, dcn_axis_name="pp")
    L, D = 4, 16
    keys = jax.random.split(jax.random.PRNGKey(5), L)
    params = {"w": jnp.stack(
        [jax.random.normal(k, (D, D)) * (D ** -0.5) for k in keys])}

    def stage_fn(sp, x):
        def body(c, lp):
            return jnp.tanh(c @ lp["w"]), None
        out, _ = jax.lax.scan(body, x, sp)
        return out

    x = jax.random.normal(jax.random.PRNGKey(6), (8, D))
    got = pipeline_apply(pp_mesh, stage_fn, split_stages(params, 2), x,
                         microbatches=4)
    want = x
    for i in range(L):
        want = jnp.tanh(want @ params["w"][i])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
