"""``aot_fit.py`` for a serve configuration with latent attention (ONE
pool of rows, no V pool): compile its real-size ``decode_burst`` (the
block tables at their full span) and ``prefill_sample`` (the largest
bucket) for a *described* TPU v5e, no chip needed, and print what each
needs of a chip's memory and how many kernels it holds.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_fit_latent.py <config name> [layers]

``aot_fit.py`` hands the programs a K and a V pool of heads; this hands
them the one pool ``llm/cache.py`` makes for such a configuration. A
compile that passes is not a chip run.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import aot_fit  # noqa: E402  (sets TPU_LOG_DIR; ``_report``)


def serve(config: dict, topo) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness.families import family_of
    from ray_tpu.llm.cache import init_kv_cache
    from ray_tpu.llm.runner import decode_burst, prefill_sample
    from ray_tpu.ops import rope_frequencies

    # ``attention`` asks the default backend whether it is a TPU, and
    # here that is the CPU: this compile IS for a TPU, so say so (else
    # prefill takes the plain-jax attention and its float32 scores)
    sys.modules["ray_tpu.ops.attention"]._on_tpu = lambda x: True
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
        cfg.rope_dim, cfg.max_seq, cfg.rope_theta,
        scaling=cfg.rope_scaling)))
    B, page = e["max_num_seqs"], e["page_size"]
    width = -(-e["max_seq_len"] // page)
    pool, v_pool = jax.eval_shape(lambda: (lambda c: (c.k, c.v))(
        init_kv_cache(cfg, e["num_pages"], page)))
    pool = on_chip(pool)
    print(json.dumps({
        "pool": list(pool.shape), "v_pool": v_pool,
        "pool_gb": pool.size * 2 / 1e9,
        "weights_gb": sum(a.size * a.dtype.itemsize for a in
                          jax.tree.leaves(params)) / 1e9}), flush=True)
    i32, f32 = sds((B,), jnp.int32), sds((B,), jnp.float32)
    table = sds((B, width), jnp.int32)
    aot_fit._report(
        f"decode_burst {B} slots, tables of {width} pages x "
        f"{e['decode_burst']}",
        decode_burst.lower(
            params, pool, None, i32, i32, table, sds((B,), jnp.bool_),
            cos, sin, 0, f32, i32, f32, None, table, sds((), jnp.int32),
            cfg=cfg, n_steps=e["decode_burst"], greedy=True).compile())
    one_i, one_f = sds((1,), jnp.int32), sds((1,), jnp.float32)
    aot_fit._report(
        f"prefill_sample bucket {e['max_seq_len']}",
        prefill_sample.lower(
            params, pool, None, sds((1, e["max_seq_len"]), jnp.int32),
            one_i, sds((1, width), jnp.int32), cos, sin, 0, one_f, one_i,
            one_f, None, cfg=cfg, greedy=True).compile())


def main() -> None:
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with open(os.path.join(HERE, "configs", sys.argv[1] + ".json")) as f:
        config = json.load(f)
    if len(sys.argv) > 2:
        config["num_hidden_layers"] = int(sys.argv[2])
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    serve(config, topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2"))


if __name__ == "__main__":
    main()
