"""``aot_fit.py`` for a serve configuration whose page cache has several
layer groups (full and window layers side by side): compile its
real-size ``decode_burst`` (every page list at its top bucket) and
``prefill_sample`` (the largest bucket) for a *described* TPU v5e, no
chip needed, and print what each needs of a chip's memory.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_fit_groups.py <config name> [layers]

``aot_fit.py`` hands the programs one pool and one table; this hands them
one a group, sized as the engine sizes them (``EngineConfig.num_pages``
for the layers that see the whole sequence, ``cache.window_group_pages``
for a window group). A compile that passes is not a chip run.
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
    from ray_tpu.llm.cache import window_group_pages
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
    B, page = e["max_num_seqs"], e["page_size"]
    width = -(-e["max_seq_len"] // page)
    pools, tables, lists = [], [], []
    for g, window in enumerate(cfg.kv_groups):
        pages = e["num_pages"] if window is None else window_group_pages(
            B, window, page, e["decode_burst"])
        per_slot = width if window is None else min(
            width, -(-window // page) + 1)
        pools.append(sds((cfg.group_layers(g), pages, page, cfg.n_kv_heads,
                          cfg.head_dim), cfg.dtype))
        tables.append(sds((B, width), jnp.int32))
        lists.append(sds((3, min(B * per_slot, pages - 1)), jnp.int32))
        print(json.dumps({"group": "full" if window is None else "window",
                          "layers": cfg.group_layers(g), "pages": pages,
                          "pool_gib": 2 * cfg.group_layers(g) * pages * page
                          * cfg.n_kv_heads * cfg.head_dim * 2 / 2**30,
                          "list_top": lists[-1].shape[1]}), flush=True)
    pools, tables, lists = tuple(pools), tuple(tables), tuple(lists)
    i32, f32 = sds((B,), jnp.int32), sds((B,), jnp.float32)
    aot_fit._report(
        f"decode_burst {B} slots, lists {[l.shape[1] for l in lists]} "
        f"pages x {e['decode_burst']}",
        decode_burst.lower(
            params, pools, pools, i32, i32, tables, sds((B,), jnp.bool_),
            cos, sin, 0, f32, i32, f32, None, lists, sds((), jnp.int32),
            cfg=cfg, n_steps=e["decode_burst"], greedy=True).compile())
    one_i, one_f = sds((1,), jnp.int32), sds((1,), jnp.float32)
    rows = tuple(sds((1, width), jnp.int32) for _ in tables)
    aot_fit._report(
        f"prefill_sample bucket {e['max_seq_len']}",
        prefill_sample.lower(
            params, pools, pools, sds((1, e["max_seq_len"]), jnp.int32),
            one_i, rows, cos, sin, 0, one_f, one_i, one_f, None, cfg=cfg,
            greedy=True).compile())


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
