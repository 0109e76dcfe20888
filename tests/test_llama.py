"""Llama model tests on the CPU mesh (SURVEY §4.4 device-count-free path)."""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (
    LLAMA_CONFIGS, forward, init_params, lm_loss, param_logical_axes,
)
from ray_tpu.models.llama import REMAT_POLICIES
from ray_tpu.parallel import MeshSpec, build_mesh, shard_pytree

CFG = LLAMA_CONFIGS["tiny"]


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def test_forward_shapes(params):
    tokens = jnp.zeros((2, 32), jnp.int32)
    logits = forward(params, tokens, CFG)
    assert logits.shape == (2, 32, CFG.vocab)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()


def test_param_axes_match_structure(params):
    axes = param_logical_axes(CFG)
    jax.tree.map(lambda *_: None, params, axes,
                 is_leaf=lambda x: isinstance(x, tuple))  # raises on mismatch


def test_causality(params):
    """Changing a future token must not affect earlier logits."""
    t1 = jnp.zeros((1, 16), jnp.int32)
    t2 = t1.at[0, 10].set(7)
    l1 = forward(params, t1, CFG)
    l2 = forward(params, t2, CFG)
    np.testing.assert_allclose(np.asarray(l1[0, :10]), np.asarray(l2[0, :10]),
                               rtol=1e-5)
    assert not np.allclose(np.asarray(l1[0, 10:]), np.asarray(l2[0, 10:]))


def test_loss_and_grad(params):
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32),
                                          0, CFG.vocab)}
    loss, grads = jax.value_and_grad(
        lambda p: lm_loss(p, batch, CFG))(params)
    assert np.isfinite(float(loss))
    norms = jax.tree.map(lambda g: float(jnp.abs(g).max()), grads)
    flat = jax.tree.leaves(norms)
    assert all(np.isfinite(n) for n in flat)
    assert any(n > 0 for n in flat)


def test_sharded_forward_all_layouts(cpu_mesh8, params):
    """Same logits under dp/fsdp/tp/sp layouts (GSPMD + ring attention)."""
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, CFG.vocab)
    ref = forward(params, tokens, CFG)
    for spec in (MeshSpec(dp=8), MeshSpec(fsdp=4, tp=2),
                 MeshSpec(dp=2, fsdp=2, tp=2), MeshSpec(sp=4, tp=2)):
        mesh = build_mesh(spec, cpu_mesh8)
        shardings = shard_pytree(params, param_logical_axes(CFG), mesh)
        p_sharded = jax.device_put(params, shardings)
        out = jax.jit(
            lambda p, t: forward(p, t, CFG, mesh=mesh))(p_sharded, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"layout {spec}")


def _named_products(jaxpr, name) -> int:
    """``dot_general``s of ``jaxpr`` (and of every jaxpr inside it) whose
    name stack holds ``name``: an einsum's own, its recomputation under a
    checkpoint and its two transposes all carry the einsum's string."""
    n = 0
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "dot_general"
                and name in str(eqn.source_info.name_stack)):
            n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _named_products(sub, name)
    return n


@pytest.mark.parametrize("policy", sorted(set(REMAT_POLICIES) - {"full"}))
def test_remat_policy_keeps_the_loss_and_every_gradient(cpu_mesh8, policy):
    """What a layer's checkpoint keeps is the value the forward pass
    computed: on the fsdp=2 x tp=2 CPU mesh (float32, the blockwise
    attention, which has no LSE to name) the loss and every gradient
    leaf equal those of ``"full"``, which keeps nothing."""
    kept = dataclasses.replace(CFG, remat=True, remat_policy=policy)
    full = dataclasses.replace(kept, remat_policy="full")
    mesh = build_mesh(MeshSpec(fsdp=2, tp=2), cpu_mesh8[:4])
    params = init_params(jax.random.PRNGKey(0), kept)
    params = jax.device_put(
        params, shard_pytree(params, param_logical_axes(kept), mesh))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32),
                                          0, kept.vocab)}

    def loss_and_grads(cfg):
        return jax.jit(jax.value_and_grad(
            lambda p: lm_loss(p, batch, cfg, mesh=mesh)))(params)

    loss, grads = loss_and_grads(kept)
    loss_full, grads_full = loss_and_grads(full)
    assert float(loss) == float(loss_full)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), grads, grads_full)


def _gradient_products(params, name, policy=None) -> int:
    """The products named ``name`` in the gradient's jaxpr where a layer's
    checkpoint keeps ``policy`` (None: ``LlamaConfig``'s default)."""
    cfg = dataclasses.replace(
        CFG, remat=True, **({"remat_policy": policy} if policy else {}))
    batch = {"tokens": jnp.zeros((2, 32), jnp.int32)}
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: lm_loss(p, batch, cfg)))(params).jaxpr
    return _named_products(jaxpr, name)


def test_default_remat_policy_runs_the_output_product_once(params):
    """``remat=True`` by default keeps the attention output product's
    result: the gradient's jaxpr holds that product in the forward scan
    and its two transposes, and ``"full"`` a fourth, the recomputation."""
    products = partial(_gradient_products, params, "bshk,hkd->bsd")
    assert products() == products("attn") == 3
    assert products("full") == 4
    with pytest.raises(ValueError, match="remat_policy"):
        dataclasses.replace(CFG, remat_policy="nothing")


def test_default_remat_policy_runs_the_up_product_once(params):
    """The default, ``"attn_up"``, keeps the up product's result beside
    what ``"attn"`` keeps. Gate and up are each a ``bsd,dm->bsm`` in the
    forward scan, a recomputation and two transposes, eight products
    with ``"attn"``; the default's backward body recomputes gate alone.
    q, k and v (``bsd,dhk->bshk``, twelve) are recomputed by both."""
    assert CFG.remat_policy == "attn_up"
    wide = partial(_gradient_products, params, "bsd,dm->bsm")
    assert (wide("full"), wide("attn"), wide()) == (8, 8, 7)
    heads = partial(_gradient_products, params, "bsd,dhk->bshk")
    assert (heads("full"), heads("attn"), heads()) == (12, 12, 12)
