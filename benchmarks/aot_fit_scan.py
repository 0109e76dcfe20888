"""``aot_fit.py`` for a serve configuration with scan layers (a
decoder-hybrid-decoder stack: a state a slot beside a window group and
ONE full layer's pages): compile its real-size ``decode_burst`` (the
full group's table at its whole width, the window group a row of its
window's pages a slot) and ``prefill_sample`` (the
largest bucket, or the one given) for a *described* TPU v5e, no chip
needed, and print what each needs of a chip's memory and how many
kernels it holds.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_fit_scan.py <config name> [bucket] [slots]

``aot_fit_groups.py`` hands the programs a K and a V pool a group; this
hands them those (a page's rows are ``n_kv_heads / 2`` of ``2
head_dim``) and the state pool, and tells ``prefill_sample`` the slot. A
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


def programs(config: dict, topo, bucket=None):
    """-> (label, lowered) of ``decode_burst`` and ``prefill_sample`` at
    the configuration's engine settings, for ``topo``'s first device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness.families import family_of
    from ray_tpu.llm.cache import init_kv_cache, window_group_pages
    from ray_tpu.llm.runner import decode_burst, prefill_sample
    from ray_tpu.ops import rope_frequencies

    # ``attention`` asks the default backend whether it is a TPU, and
    # here that is the CPU: this compile IS for a TPU, so say so (the
    # scan kernels ask ``lax.platform_dependent`` and need no telling)
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
        cfg.rope_dim, cfg.max_seq, cfg.rope_theta)))
    B, page = e["max_num_seqs"], e["page_size"]
    bucket = bucket or e["max_seq_len"]
    width = -(-e["max_seq_len"] // page)
    pages = [e["num_pages"] if w is None else window_group_pages(
        B, w, page, e["decode_burst"]) for w in cfg.kv_groups]
    k_pools, v_pools, s_pool = on_chip(jax.eval_shape(
        lambda: (lambda c: (c.k, c.v, c.s))(
            init_kv_cache(cfg, pages, page, slots=B))))
    # the full group through the slots' own tables at their whole width,
    # the window group a row a slot (its first position, its pages)
    lists = (sds((B, width), jnp.int32), *(
        (sds((B,), jnp.int32),
         sds((B, min(width, -(-w // page) + 1)), jnp.int32))
        for w in cfg.kv_groups[1:]))
    print(json.dumps({
        "k_pools": [list(p.shape) for p in k_pools],
        "state_pool": list(s_pool.shape),
        "list_tops": [l.shape[1] for l in jax.tree.leaves(lists)
                      if l.ndim == 2],
        "pools_gb": (2 * sum(p.size for p in k_pools) * 2
                     + s_pool.size * 4) / 1e9,
        "weights_gb": sum(a.size * a.dtype.itemsize for a in
                          jax.tree.leaves(params)) / 1e9}), flush=True)
    i32, f32 = sds((B,), jnp.int32), sds((B,), jnp.float32)
    tables = tuple(sds((B, width), jnp.int32) for _ in pages)
    yield (f"decode_burst {B} slots, a table of {width} and rows of "
           f"{lists[1][1].shape[1]} pages x {e['decode_burst']}"
           ), decode_burst.lower(
        params, k_pools, v_pools, i32, i32, tables, sds((B,), jnp.bool_),
        cos, sin, 0, f32, i32, f32, None, lists, sds((), jnp.int32), None,
        None, s_pool, cfg=cfg, n_steps=e["decode_burst"], greedy=True)
    one_i, one_f = sds((1,), jnp.int32), sds((1,), jnp.float32)
    yield f"prefill_sample bucket {bucket}", prefill_sample.lower(
        params, k_pools, v_pools, sds((1, bucket), jnp.int32), one_i,
        tuple(sds((1, width), jnp.int32) for _ in pages), cos, sin, 0,
        one_f, one_i, one_f, None, None, None, s_pool, one_i, cfg=cfg,
        greedy=True)


def main() -> None:
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with open(os.path.join(HERE, "configs", sys.argv[1] + ".json")) as f:
        config = json.load(f)
    if len(sys.argv) > 3:
        # another number of slots, the full pool sized for them
        e = config["engine"]
        e["max_num_seqs"] = int(sys.argv[3])
        e["num_pages"] = e["max_num_seqs"] * -(
            -e["max_seq_len"] // e["page_size"]) + 1
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for label, lowered in programs(
            config, topo, int(sys.argv[2]) if len(sys.argv) > 2 else None):
        try:
            aot_fit._report(label, lowered.compile())
        except Exception as e:      # the compiler refuses what cannot fit
            print(json.dumps({"program": label, "fits": False,
                              "refused": str(e).split("\n")[0][:300]}),
                  flush=True)


if __name__ == "__main__":
    main()
