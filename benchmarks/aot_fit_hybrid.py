"""``aot_fit.py`` for a serve configuration with state layers (linear
attention beside the paged keys and values of the sparse layers):
compile its real-size ``decode_burst`` (the block tables at their full
span) and ``prefill_sample`` (the largest bucket, or the one given) for
a *described* TPU v5e, no chip needed, and print what each needs of a
chip's memory and how many kernels it holds.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_fit_hybrid.py <config name> [bucket]

``aot_fit.py`` hands the programs a K and a V pool; this hands them the
four pools ``llm/cache.py`` makes for such a configuration (K and V of
the sparse layers, the sums of strides, the state a slot). A compile
that passes is not a chip run.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import aot_fit  # noqa: E402  (sets TPU_LOG_DIR; ``_report``)


def serve(config: dict, topo, bucket=None) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness.families import family_of
    from ray_tpu.llm.cache import init_kv_cache
    from ray_tpu.llm.runner import decode_burst, prefill_sample
    from ray_tpu.ops import rope_frequencies

    # ``attention`` asks the default backend whether it is a TPU, and
    # here that is the CPU: this compile IS for a TPU, so say so (the
    # other kernels ask ``lax.platform_dependent`` and need no telling)
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
    bucket = bucket or e["max_seq_len"]
    width = -(-e["max_seq_len"] // page)
    k_pool, v_pool, c_pool, s_pool = on_chip(jax.eval_shape(
        lambda: (lambda c: (c.k, c.v, c.c, c.s))(
            init_kv_cache(cfg, e["num_pages"], page, slots=B))))
    print(json.dumps({
        "k_pool": list(k_pool.shape), "sums_pool": list(c_pool.shape),
        "state_pool": list(s_pool.shape),
        "pools_gb": (2 * k_pool.size * 2 + c_pool.size * 4
                     + s_pool.size * 4) / 1e9,
        "weights_gb": sum(a.size * a.dtype.itemsize for a in
                          jax.tree.leaves(params)) / 1e9}), flush=True)
    i32, f32 = sds((B,), jnp.int32), sds((B,), jnp.float32)
    table = sds((B, width), jnp.int32)
    aot_fit._report(
        f"decode_burst {B} slots, tables of {width} pages x "
        f"{e['decode_burst']}",
        decode_burst.lower(
            params, k_pool, v_pool, i32, i32, table, sds((B,), jnp.bool_),
            cos, sin, 0, f32, i32, f32, None, table, sds((), jnp.int32),
            None, c_pool, s_pool, cfg=cfg, n_steps=e["decode_burst"],
            greedy=True).compile())
    one_i, one_f = sds((1,), jnp.int32), sds((1,), jnp.float32)
    aot_fit._report(
        f"prefill_sample bucket {bucket}",
        prefill_sample.lower(
            params, k_pool, v_pool, sds((1, bucket), jnp.int32),
            one_i, sds((1, width), jnp.int32), cos, sin, 0, one_f, one_i,
            one_f, None, None, c_pool, s_pool, one_i, cfg=cfg,
            greedy=True).compile())


def main() -> None:
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with open(os.path.join(HERE, "configs", sys.argv[1] + ".json")) as f:
        config = json.load(f)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    serve(config, topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2"),
          int(sys.argv[2]) if len(sys.argv) > 2 else None)


if __name__ == "__main__":
    main()
