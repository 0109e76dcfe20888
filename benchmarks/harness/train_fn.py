"""What the train cells hand to ``Trainer.fit``: the function a user
would write, with the benchmark's clock round its steps.

Runs in the gang worker, which holds the cell's chips: weights made on
the mesh in one jitted call from the seed, the reference check on a
probe batch, warm steps, then the window. Every step is a user's step:
a fresh batch made on the host, ``place_batch``, the instrumented
``step_fn`` (telemetry on, so it waits for the device), the loss read
as a number, ``train.report``.
"""

from __future__ import annotations

import os
import time


def train_fn(config: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu import get_tpu_chip_ids, train
    from ray_tpu._private import device_plane
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import DEFAULT_RULES, shard_pytree
    from ray_tpu.train import make_train_step
    from ray_tpu.train.step import make_eval_step

    from . import families, trace

    model, mix, seed = config["model"], config["mix"], config["seed"]
    phases = {"train_fn": time.time()}   # unix times, for set-up's notes
    devices = jax.devices()
    phases["devices"] = time.time()
    report = {"phases": phases, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "pid": os.getpid(),
        "chip_ids": get_tpu_chip_ids(),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir}}
    family = families.family_of(model)
    cfg, vocab = family.program_config(model), int(model["vocab_size"])
    make_params, loss_of, logical_axes = family.training()
    mesh = build_mesh(MeshSpec(**model["mesh"]), devices)
    axes = logical_axes(cfg)
    init_fn, step_fn, place_batch = make_train_step(
        lambda p, b: loss_of(p, b, cfg, mesh=mesh),
        optax.adamw(3e-4, weight_decay=0.1), mesh, axes)

    # weights straight onto the mesh, in the type they are trained in
    shardings = shard_pytree(
        jax.eval_shape(lambda: make_params(jax.random.PRNGKey(0), cfg)),
        axes, mesh, DEFAULT_RULES)
    params = jax.jit(lambda key: make_params(key, cfg),
                     out_shardings=shardings)(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    phases["weights"] = time.time()

    # the reference's verdict on the loss at the initial parameters
    probe = np.random.default_rng(seed + 1).integers(
        0, vocab, (mix["probe"]["batch"], mix["probe"]["seq"]),
        dtype=np.int32)
    t_ref = time.perf_counter()
    loss_sys = float(make_eval_step(
        lambda p, b: loss_of(p, b, cfg, mesh=mesh))(
            params, place_batch({"tokens": probe})))
    loss_ref = float(family.next_token_loss(
        params, jnp.asarray(probe), model, z_loss=1e-4))
    report["probe"] = {"loss": loss_sys, "reference": loss_ref,
                       "seconds": time.perf_counter() - t_ref}

    phases["probe"] = time.time()
    state = init_fn(params)
    del params

    def one_step(i: int):
        tokens = np.random.default_rng((seed + 2, i)).integers(
            0, vocab, (mix["batch"], mix["seq"]), dtype=np.int32)
        nonlocal state
        state, metrics = step_fn(state, place_batch({"tokens": tokens}))
        loss = float(metrics["loss"])       # ends the step: a value read
        train.report({"step": i + 1, "loss": loss})
        return loss

    warm = [one_step(i) for i in range(int(mix.get("warm_steps", 2)))]
    phases["warm"] = time.time()
    report["warm"] = {"losses": warm,
                      "compile_cache": device_plane.compilation_cache_stats()}

    # ------------------------------------------------------- the window
    window_s = float(config["seconds"])
    trace_dir, trace_steps = config.get("trace_dir"), 3
    compiles_before = sum(device_plane.compilation_cache_stats().values())
    steps, traced = [], {}
    t0 = time.perf_counter()
    report["window_opened_unix"] = time.time()
    i = len(warm)
    while True:
        tracing = bool(trace_dir) and len(steps) < trace_steps
        if tracing and not steps:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            traced["t0"] = time.perf_counter() - t0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        begun = time.perf_counter() - t0
        if begun >= window_s:
            break
        loss = one_step(i)
        ended = time.perf_counter() - t0
        steps.append({"begun": begun, "ended": ended, "loss": loss,
                      "traced": tracing})
        i += 1
        if trace_dir and len(steps) == trace_steps:
            traced["t1"] = time.perf_counter() - t0
            jax.profiler.stop_trace()
            traced.update(trace.reduce_directory(trace_dir))
            traced["resumed"] = time.perf_counter() - t0
    report["steps"] = steps
    report["trace"] = traced
    report["window_compiles"] = sum(
        device_plane.compilation_cache_stats().values()) - compiles_before
    report["tokens_per_step"] = int(mix["batch"]) * int(mix["seq"])
    report["memory_peak_bytes"] = max(
        ((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
         for d in jax.local_devices()), default=0)
    train.report({**report, "step": i + 1, "final": True})
