"""Compile a configuration's real-size programs for a *described*
TPU v5e 2x2 (no chip needed) and print what each needs of a chip's
memory. Run by hand before a chip call, whenever a configuration or an
engine setting changes:

    JAX_PLATFORMS=cpu python3 benchmarks/aot_fit.py <config name> [layers]

A compile that passes is not a chip run: nothing executes, so this says
nothing about results or speed. It counts one program at a time, not
what else the process keeps on the device.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

V5E_HBM = 15.75 * 1024**3      # what a v5e chip leaves a program (PR 21)


def _report(label: str, compiled) -> None:
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    print(json.dumps({
        "program": label, "arguments_gb": m.argument_size_in_bytes / 1e9,
        "temporaries_gb": m.temp_size_in_bytes / 1e9,
        "outputs_gb": m.output_size_in_bytes / 1e9,
        "aliased_gb": m.alias_size_in_bytes / 1e9, "live_gb": live / 1e9,
        "fits": live < V5E_HBM,
        "tpu_custom_calls": compiled.as_text().count("tpu_custom_call")}),
        flush=True)


def serve(config: dict, topo) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness.families import family_of
    from ray_tpu.llm.runner import decode_burst, prefill_sample
    from ray_tpu.ops import rope_frequencies

    family = family_of(config)
    cfg = family.program_config(config)
    e = config["engine"]
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def on_chip(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: family.served_params(jax.random.PRNGKey(0), config)))
    cos, sin = on_chip(jax.eval_shape(lambda: rope_frequencies(
        cfg.head_dim, cfg.max_seq, cfg.rope_theta)))
    cache = sds((cfg.n_layers, e["num_pages"], e["page_size"],
                 cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
    B = e["max_num_seqs"]
    pages = -(-e["max_seq_len"] // e["page_size"])
    i32, f32 = sds((B,), jnp.int32), sds((B,), jnp.float32)
    _report(f"decode_burst {B} slots x {pages} pages x {e['decode_burst']}",
            decode_burst.lower(
                params, cache, cache, i32, i32, sds((B, pages), jnp.int32),
                sds((B,), jnp.bool_), cos, sin, 0, f32, i32, f32, None,
                cfg=cfg, n_steps=e["decode_burst"], paged_kernel=False,
                greedy=True).compile())
    one_i, one_f = sds((1,), jnp.int32), sds((1,), jnp.float32)
    _report(f"prefill_sample bucket {e['max_seq_len']}",
            prefill_sample.lower(
                params, cache, cache, sds((1, e["max_seq_len"]), jnp.int32),
                one_i, sds((1, pages), jnp.int32), cos, sin, 0, one_f,
                one_i, one_f, None, cfg=cfg, greedy=True).compile())


def train(config: dict, topo, batch: int, seq: int) -> None:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.harness.families import family_of
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import DEFAULT_RULES, shard_pytree
    from ray_tpu.train import make_train_step
    from ray_tpu.train.step import (TrainState, _batch_sharding,
                                    opt_state_shardings)

    family = family_of(config)
    cfg = family.program_config(config)
    make_params, loss_of, logical_axes = family.training()
    mesh = build_mesh(MeshSpec(**config["mesh"]), topo.devices)
    optimizer = optax.adamw(3e-4, weight_decay=0.1)
    axes = logical_axes(cfg)
    _init, step_fn, _place = make_train_step(
        lambda p, b: loss_of(p, b, cfg, mesh=mesh), optimizer, mesh, axes)

    def placed(tree, shardings):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree, shardings)

    params = jax.eval_shape(lambda: make_params(jax.random.PRNGKey(0), cfg))
    param_sh = shard_pytree(params, axes, mesh, DEFAULT_RULES)
    state = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32,
                                  sharding=NamedSharding(mesh, P())),
        params=placed(params, param_sh),
        opt_state=placed(jax.eval_shape(optimizer.init, params),
                         opt_state_shardings(optimizer, params, param_sh,
                                             mesh)))
    tokens = {"tokens": jax.ShapeDtypeStruct(
        (batch, seq), jnp.int32,
        sharding=_batch_sharding(mesh, DEFAULT_RULES))}
    _report(f"train step {cfg.n_layers} layers, {batch} x {seq}, mesh "
            f"{config['mesh']} (per device)",
            step_fn.lower(state, tokens).compile())


def main() -> None:
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with open(os.path.join(HERE, "configs", sys.argv[1] + ".json")) as f:
        config = json.load(f)
    if len(sys.argv) > 2:
        config["num_hidden_layers"] = int(sys.argv[2])
    # a compile for a described chip cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    if config["kind"] == "serve":
        serve(config, topo)
    else:
        train(config, topo, 4, 2048)


if __name__ == "__main__":
    main()
