"""Driver benchmark: Llama train-step throughput on the real chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

What it measures: tokens/sec of a full pjit train step (fwd + bwd + adamw
update, donated buffers) on the flagship Llama config that fits the chip,
plus achieved MFU against the chip's peak bf16 FLOPs. It needs a TPU: no
chip, an unknown chip or a failing phase ends the run with a non-zero
exit — it never benchmarks another backend or a smaller model instead.
(The kernels' on-device parity gate lives in chip_smoke.py.)

``vs_baseline``: the reference repo publishes no tokens/s number for its
training path (BASELINE.md: torch-DDP parity "within 2.5%" is its only
training claim, and BASELINE.json's 7B tokens/s/chip metric has no
published value). We therefore report achieved MFU / 0.40 — 40% MFU being
the publicly accepted "good" llama-pretraining efficiency mark that a
torch-DDP-parity system would need to hit on comparable hardware.
"""

import json
import os
import sys
import time

# Peak dense bf16 FLOP/s of one chip, keyed by the device_kind jax reports
# (Google Cloud TPU documentation, per-generation system architecture
# pages). A device that is not here is an error, not a default.
_PEAK_FLOPS = {
    "TPU v6 lite": 918e12,  # Trillium
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v4": 275e12,
    "TPU v3": 123e12,
    "TPU v2": 45e12,
}


def _tpu_device():
    """The chip this process benchmarks, or a non-zero exit."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py measures the chip and found {dev.platform!r} "
                 f"({dev.device_kind}); it does not fall back")
    if dev.device_kind not in _PEAK_FLOPS:
        sys.exit(f"no peak FLOP/s on record for device_kind "
                 f"{dev.device_kind!r}: add it to _PEAK_FLOPS with its "
                 f"source")
    return dev


def _model_flops_per_token(cfg, seq: int) -> float:
    """fwd+bwd matmul FLOPs per token: 6*N params + causal attention."""
    n = cfg.n_params()
    # attention scores+values: 2 matmuls of S*S*d per head-group, causal
    # halves them; x3 for backward.
    attn = 6 * cfg.n_layers * seq * cfg.dim
    return 6.0 * n + attn


def _bench_serving(name: str, *, quantize: bool = False, B: int = 16,
                   prefix: str = "serve", max_seq_cap: int = 1024):
    """Continuous-batching decode throughput + TTFT on the chip (the
    BASELINE.json Serve north-star: req/s + p50 TTFT have no published
    reference value; we report tokens/s/chip and TTFT directly).

    ``quantize``: native per-output-channel int8 weights (ops/quant.py)
    — the path that puts the 7B-class BASELINE model on ONE 16 GB v5e
    (8B bf16 params are 16.1 GB; int8 is 8.0 GB). The reference only
    reaches quantized serving by passing engine kwargs to vLLM
    (vllm_models.py:59); this engine owns it natively."""
    import numpy as np
    import jax

    from ray_tpu.llm import EngineConfig, LLMEngine, SamplingParams
    from ray_tpu.models import LLAMA_CONFIGS, init_params

    cfg = LLAMA_CONFIGS[name]
    if quantize:
        from ray_tpu.ops.quant import init_params_quantized

        params = init_params_quantized(jax.random.PRNGKey(7), cfg)
        # barrier: 8 GB of init must not still be in flight (holding its
        # transients) when the first prefill lands
        jax.block_until_ready(params)
    else:
        params = init_params(jax.random.PRNGKey(7), cfg)
    max_seq = min(max_seq_cap, cfg.max_seq)
    page = 64 if max_seq >= 512 else 16
    engine = LLMEngine(params, cfg, EngineConfig(
        max_num_seqs=B, page_size=page,
        num_pages=1 + B * ((max_seq + page - 1) // page),
        max_seq_len=max_seq,
        # a deep burst amortizes the per-dispatch host round trip; the
        # depth a locally attached chip wants is not re-measured yet
        # (ROADMAP D5)
        decode_burst=32))
    rng = np.random.default_rng(0)
    plen = max_seq // 2 - 1
    greedy = SamplingParams(temperature=0.0, max_tokens=max_seq // 2)

    def prompt(n):
        return [int(t) for t in rng.integers(1, cfg.vocab, n)]

    # warmup: compiles the prefill bucket and BOTH decode-burst widths
    # (full burst while budget lasts, then the 1-step tail)
    engine.add_request(prompt(plen), SamplingParams(
        temperature=0.0, max_tokens=engine.ecfg.decode_burst + 2))
    while engine.has_unfinished():
        engine.step()

    # host<->device round trip: a trivial dispatch + value fetch,
    # reported separately so TTFT decomposes into dispatch vs compute
    one = jax.jit(lambda x: x + 1)
    float(one(jax.numpy.float32(0)))  # compile
    rtts = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(one(jax.numpy.float32(0)))
        rtts.append(time.perf_counter() - t0)
    rtt_ms = 1e3 * min(rtts)

    # TTFT: time from arrival to first sampled token (prefill only —
    # step(skip_decode=True) stops once the first token is out)
    t0 = time.perf_counter()
    rid = engine.add_request(prompt(plen), greedy)
    outs = engine.step(skip_decode=True)
    assert any(o.request_id == rid for o in outs)
    ttft_ms = 1e3 * (time.perf_counter() - t0)

    # decode throughput: all slots busy, timed decode-only rounds;
    # each round emits decode_burst tokens per slot (count the outputs,
    # don't assume)
    for _ in range(B - 1):
        engine.add_request(prompt(plen // 4), greedy)
    for _ in range(B):   # drain prefills (one admission per step)
        engine.step()
    steps = 16
    t0 = time.perf_counter()
    n_tokens = 0
    for _ in range(steps):
        n_tokens += len(engine.step())
    dt = time.perf_counter() - t0
    out = {
        "model": name + ("-int8" if quantize else ""),
        "decode_tokens_per_sec": round(n_tokens / dt, 1),
        # prefill compute: wall TTFT less one host<->device round trip
        "ttft_compute_ms": round(max(0.0, ttft_ms - rtt_ms), 2),
        "ttft_wall_ms": round(ttft_ms, 2),
        "link_rtt_ms": round(rtt_ms, 2),
        "latency_primary": f"{prefix}_ttft_compute_ms",
        "batch": B,
        "decode_burst": engine.ecfg.decode_burst,
    }
    if quantize:
        out["weight_bytes"] = int(cfg.n_params())  # int8: 1 B/param
    return {f"{prefix}_{k}": v for k, v in out.items()}


def _bench_long_context(name: str):
    """Long-context decode: continuous batching at 8k max_seq with ~3.5k
    token prompts (the regime ring attention / paged KV exist for). The
    reference serves this through vLLM; here it is the native engine on
    the gather-burst path (measured faster than both our Pallas paged
    kernel and jax's at every context length on v5e — see
    config.llm_paged_kernel)."""
    import dataclasses
    import numpy as np
    import jax

    from ray_tpu.llm import EngineConfig, LLMEngine, SamplingParams
    from ray_tpu.models import LLAMA_CONFIGS, init_params

    cfg = dataclasses.replace(LLAMA_CONFIGS[name], max_seq=8192)
    params = init_params(jax.random.PRNGKey(7), cfg)
    # ctx fills ≥93% of the 8k window (512 decode tokens fit after it):
    # the metric's name promises 8k-context serving, so the KV must
    # actually be ~8k deep (VERDICT r3 weak #3 — 3584 measured a
    # half-filled window)
    B, page, ctx = 4, 64, 7650
    engine = LLMEngine(params, cfg, EngineConfig(
        max_num_seqs=B, page_size=page,
        num_pages=1 + B * (8192 // page), max_seq_len=8192,
        decode_burst=32))
    rng = np.random.default_rng(1)

    def prompt(n):
        return [int(t) for t in rng.integers(1, cfg.vocab, n)]

    greedy = SamplingParams(temperature=0.0, max_tokens=512)
    for _ in range(B):
        engine.add_request(prompt(ctx), greedy)
    for _ in range(B):   # drain prefills (one admission per step)
        engine.step(skip_decode=True)
    engine.step()        # compile + first burst
    steps = 8
    t0 = time.perf_counter()
    n_tokens = 0
    for _ in range(steps):
        n_tokens += len(engine.step())
    dt = time.perf_counter() - t0
    return {
        "serve_8k_model": name,
        "serve_8k_decode_tokens_per_sec": round(n_tokens / dt, 1),
        "serve_8k_ctx": ctx,
        "serve_8k_batch": B,
        # attention regime at 8k: the once-per-burst contiguous gather
        # (measured r4 at true 8k occupancy: 486 tok/s gathered vs 127
        # paged on v5e — see config.llm_paged_kernel for the full curve)
        "serve_8k_kernel": "gathered-burst",
    }


def _bench_8b_subprocess():
    """The Llama-3-8B int8 family in its OWN process, run BEFORE this
    process touches the chip (a chip belongs to one process at a time).
    8B int8 weights (8.0 GiB) leave the least headroom of any family, so
    they get a device nothing else has allocated on. A failure of the
    child fails the run."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--serve-8b-only"],
        stdout=subprocess.PIPE, text=True, timeout=1200, check=True)
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _serve_8b_main():
    """Child entry: run ONLY the 8B int8 family, print one JSON line.
    B=8 measured best on the v5e in the records older than PR 0 (227
    tok/s vs 110 at B=4 and 208 at B=16); not re-measured on a locally
    attached chip."""
    _tpu_device()
    print(json.dumps(_bench_serving("8b", quantize=True, B=8,
                                    prefix="serve_8b_int8",
                                    max_seq_cap=512)))


def _bench_core_summary():
    """Control-plane microbenchmarks (tasks/s, actor calls/s) folded
    into the bench line — the framework's own speed, not the model's
    (ref: python/ray/_private/ray_perf.py families; full suite in
    bench_core.py)."""
    import ray_tpu as ray

    @ray.remote
    def _nop():
        return None

    @ray.remote
    class _Ctr:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

    ray.init(num_cpus=8, object_store_memory=1 << 29)
    try:
        ray.get(_nop.remote(), timeout=60)
        t0 = time.perf_counter()
        ray.get([_nop.remote() for _ in range(2000)], timeout=120)
        tasks_per_s = 2000 / (time.perf_counter() - t0)
        a = _Ctr.remote()
        ray.get(a.inc.remote(), timeout=60)
        t0 = time.perf_counter()
        ray.get([a.inc.remote() for _ in range(2000)], timeout=120)
        actor_per_s = 2000 / (time.perf_counter() - t0)
    finally:
        ray.shutdown()
    return {
        "core_tasks_per_sec": round(tasks_per_s, 1),
        "core_actor_calls_per_sec": round(actor_per_s, 1),
    }


def _bench_train(name: str, batch: int, seq: int, steps: int, dev):
    """One config's full train-step throughput (fwd+bwd+adamw, donated
    buffers) -> (tokens/s, mfu, step_ms, loss)."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import (
        LLAMA_CONFIGS, init_params, lm_loss, param_logical_axes)
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.train import make_train_step

    cfg = LLAMA_CONFIGS[name]
    mesh = build_mesh(MeshSpec(), [dev])
    optimizer = optax.adamw(3e-4, weight_decay=0.1)
    init_fn, step_fn, place_batch = make_train_step(
        lambda p, b: lm_loss(p, b, cfg, mesh=mesh),
        optimizer, mesh, param_logical_axes(cfg))

    params = init_params(jax.random.PRNGKey(0), cfg)
    state = init_fn(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq),
                                0, cfg.vocab, jnp.int32)
    data = place_batch({"tokens": tokens})

    # Warmup (compile) then timed steps. Sync on a metric VALUE, which
    # cannot arrive before the steps it depends on have run.
    for _ in range(2):
        state, metrics = step_fn(state, data)
    float(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step_fn(state, data)
    float(metrics["loss"])
    dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * steps / dt
    mfu = (tokens_per_sec * _model_flops_per_token(cfg, seq)
           / _PEAK_FLOPS[dev.device_kind])
    return {
        "tokens_per_sec": round(tokens_per_sec, 1),
        "mfu": round(mfu, 4),
        "step_ms": round(1e3 * dt / steps, 2),
        "loss": round(float(metrics["loss"]), 4),
        "batch": batch, "seq": seq, "n_params": cfg.n_params(),
    }


def main():
    from ray_tpu._private.device_plane import enable_compilation_cache

    enable_compilation_cache()
    if "--serve-8b-only" in sys.argv:
        return _serve_8b_main()
    # 8B first, in a child, BEFORE this process claims the chip
    serve_8b = _bench_8b_subprocess()
    dev = _tpu_device()
    # headline: the LARGEST config one 16 GB v5e trains — "1b" (1.53 B
    # params, adamw state included). Llama-3-8B itself is out of reach
    # for a single chip by arithmetic alone (16.1 GB of bf16 params
    # before optimizer state or activations); the multi-chip shardings
    # that train it are exercised by __graft_entry__.dryrun_multichip.
    # Batch 8 at seq 2048 needs 21.4 G for 1b — batch 4 is the fit.
    name, batch, seq, steps = "1b", 4, 2048, 6
    secondary = ("400m", 8, 2048, 10)
    train = _bench_train(name, batch, seq, steps, dev)
    sec = _bench_train(*secondary, dev)
    extras = {f"llama_{secondary[0]}_train_{k}": v for k, v in sec.items()
              if k in ("tokens_per_sec", "mfu", "step_ms")}
    serve_metrics = _bench_serving(name)
    serve_metrics.update(_bench_long_context("400m"))
    serve_metrics.update(serve_8b)   # ran first, in a child
    core_metrics = _bench_core_summary()

    print(json.dumps({
        "metric": f"llama_{name}_train_tokens_per_sec_per_chip",
        "value": train["tokens_per_sec"],
        "unit": "tokens/s",
        "vs_baseline": round(train["mfu"] / 0.40, 4),
        "mfu": train["mfu"],
        "step_ms": train["step_ms"],
        "device": dev.device_kind,
        "platform": dev.platform,
        "n_params": train["n_params"],
        "batch": train["batch"],
        "seq": train["seq"],
        # vs_baseline is a PROXY: the reference publishes no tokens/s
        # for its training path (BASELINE.md), so this is achieved MFU
        # over the 40%-MFU public yardstick — see module docstring
        "vs_baseline_kind": "proxy_mfu_over_0.40",
        "loss": train["loss"],
        "note_8b": ("Llama-3-8B bf16 params alone (16.1 GB) exceed one "
                    "16 GB v5e; the TRAIN headline stays the 1b config "
                    "(8b/70b shardings run in dryrun_multichip), but 8B "
                    "SERVES on this chip via native int8 weights — see "
                    "serve_8b_int8_* metrics"),
        **extras,
        **serve_metrics,
        **core_metrics,
    }))


if __name__ == "__main__":
    sys.exit(main())
