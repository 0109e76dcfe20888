"""Pipeline parallelism over the "pp" mesh axis.

GPipe-style microbatch pipelining expressed as a single SPMD program:
``shard_map`` over the pp axis gives each device its stage's parameters
(leading "stage" dim sharded), and a ``lax.scan`` over M + P - 1 ticks
moves activations one stage forward per tick via single-hop ``ppermute``
(ICI neighbours). The bubble is the standard (P-1)/(M+P-1) fraction.

The reference has no pipeline engine of its own (SURVEY §2.3: PP is a
vLLM flag pass-through; aDAG supplies only the substrate) — this is the
TPU-native schedule, compiled by XLA end-to-end (fwd AND bwd pipeline
for free via autodiff through the scan/ppermute).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def split_stages(params: Any, n_stages: int) -> Any:
    """Reshape layer-stacked params (L, ...) into (n_stages, L//n_stages,
    ...): the leading stage axis is what shard_map partitions over pp."""
    def leaf(p):
        L = p.shape[0]
        assert L % n_stages == 0, f"layers {L} not divisible by {n_stages} stages"
        return p.reshape(n_stages, L // n_stages, *p.shape[1:])

    return jax.tree.map(leaf, params)


def pipeline_apply(mesh: Mesh, stage_fn: Callable, stage_params: Any,
                   x: jnp.ndarray, *, microbatches: int,
                   axis: str = "pp") -> jnp.ndarray:
    """Run ``stage_fn`` as a P-stage pipeline over ``x``.

    stage_fn(stage_local_params, activations) -> activations: one stage's
    compute (its share of layers); stage_local_params have the leading
    per-stage layer dim (stage axis already stripped).
    stage_params: pytree with leading stage axis of size mesh.shape[axis]
    (see split_stages). x: (B, ...) with B divisible by ``microbatches``.
    """
    n_stages = mesh.shape[axis]
    B = x.shape[0]
    assert B % microbatches == 0, "batch not divisible by microbatches"
    mb = B // microbatches
    xm = x.reshape(microbatches, mb, *x.shape[1:])
    M = microbatches
    ticks = M + n_stages - 1

    def per_device(params_local, xm_local):
        # params_local leaves: (1, layers_per_stage, ...) — strip stage dim
        params_here = jax.tree.map(lambda p: p[0], params_local)
        s = jax.lax.axis_index(axis)
        state = jnp.zeros_like(xm_local[0], dtype=xm_local.dtype)
        outputs = jnp.zeros_like(xm_local)

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t; later stages consume what the
            # previous tick's ppermute delivered
            feed = xm_local[jnp.clip(t, 0, M - 1)]
            inp = jnp.where(s == 0, feed, state)
            y = stage_fn(params_here, inp)
            # my microbatch index this tick; inactive ticks emit zeros so
            # the psum-combine at the end stays exact
            idx = t - s
            active = (idx >= 0) & (idx < M)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage records its finished microbatch
            is_last = s == n_stages - 1
            out_idx = jnp.clip(idx, 0, M - 1)
            outputs = jax.lax.cond(
                active & is_last,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y.astype(o.dtype), out_idx, 0),
                lambda o: o,
                outputs)
            # shift activations one stage forward on the ring
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            state = jax.lax.ppermute(y, axis, perm)
            return (state, outputs), None

        (state, outputs), _ = jax.lax.scan(
            tick, (state, outputs), jnp.arange(ticks))
        # only the last stage ever wrote into outputs (the cond above);
        # every other stage's buffer is still zero, so psum replicates
        # the last stage's results to all stages
        return jax.lax.psum(outputs, axis)

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    out = shard_map(
        per_device, mesh=mesh,
        in_specs=(spec_params, P()), out_specs=P(),
        check_vma=False,
    )(stage_params, xm)
    return out.reshape(B, *x.shape[1:])
