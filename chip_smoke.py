"""The quickest proof that ray_tpu still starts on the chip.

    python chip_smoke.py             # one chip: runtime, serve, train
    python chip_smoke.py --chips 4   # one four-chip host: the two paths
                                     # that exist only across chips

Everything goes through the entry points a user calls: ``ray_tpu.init``,
``serve.run`` + the deployment handle + the HTTP proxy, ``Trainer.fit``.
This process never imports jax: a chip belongs to one process at a time,
and here that process is the serve replica, then the train gang worker.
Whatever the smoke compares with is computed by the process that holds
the chip.

Each phase prints one JSON line as it ends; the first failure ends the
run with a non-zero exit. Timings on those lines are for the reader, not
metrics. The last line is ``{"ok": true, "device": {...}}`` with the
device as the chip's holder reported it; without a TPU there is no such
line and the exit code is not 0.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import os
import shutil
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
RUNS_DIR = os.path.join(HERE, ".smoke_runs")   # Trainer storage; removed at exit

VOCAB = 128256   # Llama-3's vocabulary, shared by the 8b and 1b configs

# --- one chip ---------------------------------------------------------------
# Llama-3-8B at its published widths, int8 weights (8.0 GB on a 16 GB v5e)
SERVE = dict(
    model="8b", quantize="int8",
    # the page pool covers every slot at max_seq_len: 8 x 1024 / 64 + dump page
    engine_config=dict(max_num_seqs=8, page_size=64, num_pages=129,
                       max_seq_len=1024, decode_burst=8))
# every prompt pads to the 512 prefill bucket: one prefill program, and a
# request's first token (a prefill's, alone in its dispatch) is the same
# whatever else the replica serves. The tokens after it need not be: since
# PR 32 a decode burst scores ONE list of every decoding slot's live
# pages, so a slot's sums run over its own keys in an order that depends
# on where its pages sit in that list, and with random weights two logits
# tie within bf16 rounding every few tokens. So the two routes have to
# agree to the token on a request that decodes ALONE both times and on
# every first token; and EVERY token of the requests decoded together, by
# either route, is judged by the harness's plain float32 reference on the
# replica's own weights (``judge_tokens``): a slot that scored another
# slot's keys, or missed its own, picks tokens the reference scores like
# any other, deviations below its first choice
PROMPT_LENS = (301, 333, 365, 397, 429, 448)
MAX_TOKENS = 25
# benchmarks/harness/serve_cell.py's MARGIN_LIMIT and its argument: bf16
# activations through 32 layers move a logit by a few hundredths of a
# position's logit deviation; a wrong key, page or position by about 4
MARGIN_LIMIT = 0.15
# the largest config one 16 GB chip trains with adamw state (batch 8 needs 21 GB)
TRAIN = dict(model="1b", batch=4, seq=2048, steps=4, kernel_parity=True)
# ... and it stands at the chip's limit: with a layer's input kept alone
# the compiler counts 15.28 of 15.75 GiB, with the saves of ``"attn"``
# 16.04 (``LlamaConfig``'s default keeps more) and refuses the step. The
# fsdp x tp mesh keeps the default (5.3 of 15.75 GiB).
ONE_CHIP_REMAT = "full"

# --- four chips (--chips 4) ---------------------------------------------------
SHARDED = dict(model="1b", batch=4, seq=2048, steps=4, mesh=dict(fsdp=2, tp=2))
REPLICAS = dict(
    model="1b", quantize=None, num_replicas=4,
    engine_config=dict(max_num_seqs=8, page_size=64, num_pages=129,
                       max_seq_len=1024, decode_burst=8))


def _hbm(device_stats, key: str):
    """One counter of ``device.memory_stats()`` (None where the backend
    keeps no such statistics; the TPU does)."""
    return (device_stats or {}).get(key)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def seeded_prompt(seed: int, index: int, length: int) -> list:
    import random

    rng = random.Random(seed * 1000 + index)
    return [rng.randrange(1, VOCAB) for _ in range(length)]


# ------------------------------------------------------------------ runtime
def phase_runtime(chips: int) -> None:
    """Build the C++ core from the sources git has, start the runtime,
    and hold its chip detection to the machine."""
    shutil.rmtree(os.path.join(HERE, "ray_tpu", "_native", "build"),
                  ignore_errors=True)
    t0 = time.time()
    import ray_tpu
    from ray_tpu import _native

    if _native.get_lib() is None:
        raise SystemExit("native core unavailable: "
                         f"{_native.native_unavailable_reason()}")
    build_s = time.time() - t0
    ray_tpu.init()
    detected = ray_tpu.cluster_resources().get("TPU", 0.0)
    if detected != float(chips):
        raise SystemExit(
            f"ray_tpu.init() reports TPU={detected} but this run needs "
            f"exactly {chips} local chip(s)")
    emit("runtime", native_build_s=round(build_s, 2), tpu=detected,
         cpu=ray_tpu.cluster_resources().get("CPU"))


# -------------------------------------------------------------------- serve
def _completion(out: dict) -> list:
    return out["choices"][0]["token_ids"]


def _http_completion(port: int, name: str, payload: dict) -> list:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/{name}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=180) as resp:
        if not payload.get("stream"):
            return _completion(json.loads(resp.read())["result"])
        tokens, finished = [], False
        for line in resp:            # SSE-style "data: {...}" chunks
            line = line.strip()
            if line.startswith(b"data:"):
                chunk = json.loads(line[len(b"data:"):])
                tokens.append(chunk["token"])
                finished = chunk["finished"]
        if not finished:
            raise AssertionError("stream ended without a finished chunk")
        return tokens


def _replica_call(replica, method: str, *args):
    """One call to ONE replica (the deployment handle would pick any)."""
    import ray_tpu

    return ray_tpu.get(replica.handle.remote(method, args, {}), timeout=900)


def _replicas_of(name: str) -> list:
    import ray_tpu
    from ray_tpu.serve.controller import CONTROLLER_NAME

    _version, replicas = ray_tpu.get(
        ray_tpu.get_actor(CONTROLLER_NAME).get_replicas.remote(name),
        timeout=60)
    return replicas


def _wait_gone(pids, timeout_s: float = 120.0) -> float:
    """The chip is free again only once its holder's process is gone."""
    from ray_tpu._private.device_plane import process_alive

    t0 = time.time()
    while any(process_alive(p) for p in pids):
        if time.time() - t0 > timeout_s:
            raise AssertionError(f"replica processes {pids} still alive "
                                 f"{timeout_s}s after serve.shutdown()")
        time.sleep(0.1)
    return time.time() - t0


def _check_tokens(tokens: list) -> None:
    if len(tokens) != MAX_TOKENS or not all(
            isinstance(t, int) and 0 <= t < VOCAB for t in tokens):
        raise AssertionError(f"want {MAX_TOKENS} token ids in [0, {VOCAB}), "
                             f"got {tokens}")


def _check_device(info: dict, count: int = 1) -> None:
    """The chip's holder is another process than this one, and jax there
    reports ``count`` TPU devices: its lease's chips and no others."""
    if info["platform"] != "tpu" or info["device_count"] != count:
        raise AssertionError(f"not on {count} TPU chip(s): {info}")
    if info["pid"] == os.getpid():
        raise AssertionError("the driver holds the chip")


def _check_distinct(infos: list) -> None:
    """Each replica is a process of its own holding a chip of its own."""
    if len({i["pid"] for i in infos}) != len(infos) \
            or len({tuple(i["chip_ids"]) for i in infos}) != len(infos):
        raise AssertionError(f"replicas share a process or a chip: {infos}")


def judge_tokens(config: dict) -> dict:
    """Runs under a chip lease once the replica has let go of the chip:
    the replica's weights again from its seed, and for every (prompt,
    answer) the plain float32 reference's verdict on every token of the
    answer, teacher-forced: how far the chosen token's reference logit
    lies below that position's largest, in that position's logit
    deviations (``benchmarks/harness/reference.py``, which shares no code
    with ``ray_tpu``; 0 where the reference agrees). Sequences are padded
    on the right to one length, which a causal model never sees."""
    sys.path.insert(0, config["root"])
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import reference
    from ray_tpu.models import LLAMA_CONFIGS
    from ray_tpu.ops.quant import init_params_quantized

    cfg = LLAMA_CONFIGS[config["model"]]
    params = init_params_quantized(jax.random.PRNGKey(config["seed"]), cfg)
    published = dict(num_hidden_layers=cfg.n_layers,
                     num_attention_heads=cfg.n_heads,
                     num_key_value_heads=cfg.n_kv_heads,
                     rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps)
    width = max(len(p) + len(a) for p, a in config["pairs"])
    worst = []
    for prompt, answer in config["pairs"]:
        tokens = prompt + answer + [0] * (width - len(prompt) - len(answer))
        logits = reference.forward_logits(
            params, jnp.asarray([tokens], jnp.int32), published)[0]
        at = logits[len(prompt) - 1:len(prompt) + len(answer) - 1]
        chosen = jnp.take_along_axis(
            at, jnp.asarray(answer)[:, None], -1)[:, 0]
        worst.append(float(np.asarray(
            (at.max(-1) - chosen) / at.std(-1)).max()))
    return {"device": _device_report(), "margin_worst": worst}


def phase_serve(seed: int, spec: dict) -> dict:
    """Llama-3-8B int8 behind serve.run: the same greedy requests through
    the deployment handle and through the HTTP proxy, several in flight,
    and the reference's verdict on every token they were given."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_deployment

    name = "llm"
    t0 = time.time()
    handle = serve.run(build_llm_deployment(
        spec["model"], name=name, quantize=spec["quantize"], init="random",
        seed=seed, engine_config=spec["engine_config"]))
    completions = handle.options(method_name="completions")
    port = serve.start()
    payloads = [{"prompt_ids": seeded_prompt(seed, i, n),
                 "temperature": 0.0, "max_tokens": MAX_TOKENS}
                for i, n in enumerate(PROMPT_LENS)]

    # the first request waits for the replica: weights made on the chip,
    # then the prefill and decode programs compiled (or read from the cache)
    first = _completion(ray_tpu.get(completions.remote(payloads[0]),
                                    timeout=600))
    cold_s = time.time() - t0

    t1 = time.time()
    refs = [completions.remote(p) for p in payloads]     # all in flight
    stats = handle.options(method_name="stats")
    max_running, pending = 0, refs
    while pending:
        max_running = max(max_running, ray_tpu.get(
            stats.remote(), timeout=60)["running"])
        _done, pending = ray_tpu.wait(refs, num_returns=len(refs),
                                      timeout=0.02)
    by_handle = [_completion(o) for o in ray_tpu.get(refs, timeout=600)]
    handle_s = time.time() - t1

    t2 = time.time()
    with concurrent.futures.ThreadPoolExecutor(len(payloads)) as pool:
        by_http = list(pool.map(
            lambda ip: _http_completion(
                port, name, {**ip[1], "stream": ip[0] % 2 == 0}),
            enumerate(payloads)))
    http_s = time.time() - t2

    # alone again, through the proxy: the same batch of one, the same bits
    alone_http = _http_completion(port, name, {**payloads[0], "stream": True})
    for tokens in by_handle + by_http + [alone_http]:
        _check_tokens(tokens)
    if first != alone_http:
        raise AssertionError("handle and HTTP routes disagree on a request "
                             f"served alone: {first} vs {alone_http}")
    firsts = [[tokens[0] for tokens in route]
              for route in (by_handle, by_http)]
    if firsts[0] != firsts[1] or firsts[0][0] != first[0]:
        raise AssertionError("a request's first token changed with its "
                             f"route or its company: {first[0]}, {firsts}")
    counters = ray_tpu.get(stats.remote(), timeout=60)["counters"]
    if max_running < 2 or (counters["active_slot_steps"]
                           <= counters["decode_steps"]):
        raise AssertionError(
            "requests never shared a decode batch (max running slots "
            f"seen: {max_running}; slot-steps {counters['active_slot_steps']}"
            f" in {counters['decode_steps']} steps)")

    # what the prefill these prompts ran was compiled to, read from the
    # program's text by the replica (every prompt pads to one bucket)
    t3 = time.time()
    info = ray_tpu.get(handle.options(method_name="device_info").remote(
        {"prompt_len": max(PROMPT_LENS)}), timeout=600)
    info_s = time.time() - t3
    _check_device(info)
    serve.shutdown()
    gone_s = _wait_gone([info["pid"]])

    # the chip is free: the reference takes it, on the replica's weights
    t4 = time.time()
    prompts = [p["prompt_ids"] for p in payloads]
    judged = ray_tpu.get(ray_tpu.remote(judge_tokens).options(
        num_tpus=1).remote(dict(
            root=HERE, model=spec["model"], seed=seed,
            pairs=list(zip(prompts * 2 + prompts[:1],
                           by_handle + by_http + [first])))), timeout=900)
    _check_device(judged["device"])
    margins = judged["margin_worst"]
    if max(margins) > MARGIN_LIMIT:
        raise AssertionError(
            "a token of a request decoded beside others lies "
            f"{max(margins):.3f} deviations below the reference's first "
            f"choice (limit {MARGIN_LIMIT}); by request, handle then HTTP "
            f"then alone: {margins}")
    together = by_handle + by_http
    agree = [sum(a == b for a, b in zip(x, y))
             for x, y in zip(by_handle, by_http)]
    n_tokens = MAX_TOKENS * len(payloads)
    emit("serve", model=spec["model"], quantize=spec["quantize"],
         platform=info["platform"], device_kind=info["device_kind"],
         replica_pid=info["pid"], chip_ids=info["chip_ids"],
         peak_hbm_bytes=_hbm(info["memory_stats"], "peak_bytes_in_use"),
         hbm_limit_bytes=_hbm(info["memory_stats"], "bytes_limit"),
         prefill_attention=info["prefill_attention"],
         prefill_read_s=round(info_s, 2),
         compile_cache_dir=info["compile_cache_dir"],
         compile_cache=info["compile_cache"],
         first_request_s=round(cold_s, 1), max_running=max_running,
         slots_a_decode_step=round(counters["active_slot_steps"]
                                   / counters["decode_steps"], 2),
         page_list_rounds=counters["gather_hist"],
         handle_round_s=round(handle_s, 2), http_round_s=round(http_s, 2),
         tokens_per_round=n_tokens, replica_exit_s=round(gone_s, 2),
         margin_worst=round(max(margins), 4), margin_limit=MARGIN_LIMIT,
         margin_worst_together=round(max(margins[:len(together)]), 4),
         tokens_routes_agree=agree, reference_s=round(time.time() - t4, 1))
    return info


# -------------------------------------------------------------------- train
def _kernel_parity(seed: int) -> dict:
    """Flash kernels against the blockwise oracle on the device, forward
    and backward (GQA 8/4 heads of 128, causal, bf16). Runs in the gang
    worker, which already holds the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import attention, blockwise_attention

    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (1, 512, 8, 128), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 512, 4, 128), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 512, 4, 128), jnp.bfloat16)
    g = jax.random.normal(ks[3], (1, 512, 8, 128), jnp.float32)

    def run(fn):
        def loss(q, k, v):
            return (fn(q, k, v).astype(jnp.float32) * g).sum()

        out = jax.jit(fn)(q, k, v)
        grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        return [np.asarray(x, np.float32) for x in (out, *grads)]

    got = run(lambda q, k, v: attention(q, k, v, causal=True,
                                        use_pallas=True))
    want = run(lambda q, k, v: blockwise_attention(q, k, v, causal=True))
    errors = {}
    for label, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        if not np.isfinite(a).all():
            raise AssertionError(f"kernel {label} is not finite")
        # bf16 carries 8 bits of mantissa; errors are held to a few ulp of
        # the largest value in play
        errors[label] = float(np.abs(a - b).max() / np.abs(b).max())
        if errors[label] > 3e-2:
            raise AssertionError(
                f"flash {label} is off the oracle by {errors[label]:.3g} "
                f"of its range")
    return errors


def _expert_kernel_parity(seed: int) -> dict:
    """The grouped expert kernel against ``lax.ragged_dot`` on the device
    at SmallThinker's widths (64 int8 experts of 768 on 2,560: gate/up
    and down), with a decode step's 48 rows and the longest prefill
    bucket's 12,288 x 6, groups as a uniform router fills them and the
    last rows behind every group. Both round one float32 sum to bf16, so
    they may differ by one bf16 step at the output's largest value and
    by no more. Runs in the gang worker, which already holds the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import moe

    experts, hidden, width, top_k = 64, 2560, 768, 6
    rng = np.random.default_rng(seed)
    report = {}
    for k, n in ((hidden, width), (width, hidden)):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), k)
        w = {"q": jax.random.randint(key, (2, experts, k, n), -127, 128,
                                     jnp.int8),
             "s": (0.5 + jax.random.uniform(key, (2, experts, n)))
             / (127 * k ** 0.5)}
        for tokens in (8, 12288):
            m = tokens * top_k
            chosen = np.argsort(rng.random((tokens, experts)))[:, :top_k]
            chosen[tokens - tokens // 16:] = experts    # rows of no token
            row_expert = jnp.asarray(np.sort(chosen.reshape(m)), jnp.int32)
            sizes = jnp.bincount(row_expert, length=experts + 1)[:experts]
            total = int(sizes.sum())
            lhs = jax.random.normal(jax.random.fold_in(key, m), (m, k),
                                    jnp.bfloat16)
            got = jax.jit(moe.grouped_matmul)(
                lhs, w, row_expert, sizes, jnp.int32(1))[:total]
            want = jax.jit(moe._gmm_xla, static_argnums=6)(
                lhs, w["q"], w["s"], 1, row_expert, sizes,
                jnp.bfloat16)[:total]
            tiles = moe.chosen_tiles(hidden, width)[f"{m}x{k}x{n}:int8"]
            if tiles != [min(m, 128), k, n]:
                raise AssertionError(f"{m}x{k}x{n}: tiles {tiles}, not "
                                     f"the whole widths")
            got, want = (np.asarray(x, np.float32) for x in (got, want))
            if not np.isfinite(got).all():
                raise AssertionError(f"{m}x{k}x{n}: not finite")
            largest = float(np.abs(want).max())
            step = 2.0 ** (np.floor(np.log2(largest)) - 7)
            diff = float(np.abs(got - want).max())
            if diff > step:
                raise AssertionError(
                    f"{m}x{k}x{n}: the kernel is {diff} off lax.ragged_dot, "
                    f"over one bf16 step ({step}) at {largest}")
            report[f"{m}x{k}x{n}"] = {"max_diff": diff,
                                      "largest_value": largest,
                                      "bf16_step": step, "tiles": tiles}
    return report


def _sparse_kernel_parity(seed: int) -> dict:
    """The indexer's four kernels (``ops/sparse_attention.py``) against
    their plain-jax twins on the device at the published widths: 512
    queries of 16 heads against 8,192 keys as wide as the pool's slot,
    the 2,048 best of each row, and the product over that mask at 32
    query and 4 KV heads of 128; then a decode step's 8 rows with the
    burst's own rows behind a gap, and the decode product over their
    choice through a block table; then the choice over a 22,372-token
    prompt's limits in 24,576 rows (``_select_long``). The scores are
    float32 sums of the same bf16 products, the choice is exact (the
    same mask), the product rounds one float32 sum to bf16. Runs in the
    gang worker."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import sparse_attention as sparse

    T, S, top = 512, 8192, 2048
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    qi = jax.random.normal(ks[0], (1, T, 16, 128), jnp.bfloat16)
    w = jax.random.normal(ks[1], (1, T, 16), jnp.float32)
    ki = jax.random.normal(ks[2], (1, S, 128), jnp.bfloat16)
    q = jax.random.normal(ks[3], (T, 32, 128), jnp.bfloat16)
    kt, vt = (jax.random.normal(k, (4, S, 128), jnp.bfloat16)
              for k in ks[4:])
    last = (S - T + jnp.arange(T) + 1).astype(jnp.int32)
    seen = np.arange(S)[None] < np.asarray(last)[:, None]
    scores = jax.jit(sparse.index_scores_tpu)(qi, w, ki, last[None])[0]
    want = sparse.index_scores_xla(qi, w, ki)[0]
    report = {"scores_max_diff": float(np.abs(np.where(
        seen, np.asarray(scores) - np.asarray(want), 0.0)).max())}
    lim = jnp.stack([last, jnp.zeros_like(last)], -1)
    mask = jax.jit(lambda s, l: sparse.choose_tpu(
        s, l, k=top, start_b=None))(scores, lim)
    report["masks_differ"] = int((np.asarray(mask) != np.asarray(
        sparse.choose_xla(scores, lim, k=top, start_b=None))).sum())
    report["chosen_a_row"] = int(np.asarray(mask)[-1].sum())
    out = jax.jit(lambda *a: sparse.masked_attention_tpu(
        *a, scale=128 ** -0.5))(q, kt, vt, mask, last)
    twin = sparse.masked_attention_xla(q[:128], kt, vt, mask[:128],
                                       scale=128 ** -0.5)
    report["product_max_diff"] = float(np.abs(
        np.asarray(out[:128], np.float32) - np.asarray(twin, np.float32)
    ).max())
    # a decode step: 8 slots, 4,096 cached keys and 3 of the burst's
    lengths = jnp.asarray([4096, 3000, 2049, 100, 0, 4000, 2048, 1],
                          jnp.int32)
    rows = jax.random.normal(ks[0], (8, 4096 + 128), jnp.float32)
    lim = jnp.stack([lengths, jnp.full_like(lengths, 3)], -1)
    step = jax.jit(lambda s, l: sparse.choose_tpu(
        s, l, k=top, start_b=4096))(rows, lim)
    report["decode_masks_differ"] = int((np.asarray(step) != np.asarray(
        sparse.choose_xla(rows, lim, k=top, start_b=4096))).sum())
    # the product over that choice, each slot's own pages of 64 where
    # they lie (listed out of order) in the second layer of two
    pages = 1 + 8 * 64
    pool_k, pool_v = (jax.random.normal(k, (2, pages, 64, 4, 128),
                                        jnp.bfloat16) for k in ks[4:])
    tables = jax.random.permutation(ks[1], jnp.arange(
        1, pages, dtype=jnp.int32)).reshape(8, 64)
    operands = (q[:8], pool_k, pool_v, jnp.int32(1), tables, lengths,
                step[:, :4096])
    o, lse = jax.jit(lambda *a: sparse.decode_attention_tpu(
        *a, scale=128 ** -0.5))(*operands)
    o_twin, lse_twin = sparse.decode_attention_xla(*operands,
                                                   scale=128 ** -0.5)
    report["decode_product_max_diff"] = float(max(
        np.abs(np.asarray(o) - np.asarray(o_twin)).max(),
        np.abs(np.asarray(lse) - np.asarray(lse_twin))[
            np.asarray(lengths) > 0].max()))
    report.update(_select_long(seed, sparse, top))
    if (report["masks_differ"] or report["decode_masks_differ"]
            or report["long_masks_differ"]
            or report["chosen_a_row"] != top
            or report["scores_max_diff"] > 1e-3
            or report["product_max_diff"] > 2.0 ** -6
            or report["decode_product_max_diff"] > 2.0 ** -6):
        raise AssertionError(f"sparse kernels off their twins: {report}")
    return report


def _select_long(seed: int, sparse, top: int, prompt: int = 22372,
                 S: int = 24576, timed=(2048, 8192, 22016)) -> dict:
    """``choose_tpu`` against ``choose_xla`` a tile of 512 rows, as
    ``attend`` hands them over, for a ``prompt``-token prompt's limits
    in a row of ``S`` columns: ``long_masks_differ`` over every tile
    (the rows past the prompt see nothing; every other tile's scores
    are rounded to quarters, so that hundreds tie across the ``top``-th
    place; the key blocks of 2,048 that no row of a tile sees, which
    the scores' kernel leaves unwritten, hold NaN), and ``select_ms``:
    the kernel's milliseconds for the tile that starts at each of
    ``timed``, by the device's trace (the price at equal lengths,
    outside any cell)."""
    import statistics
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    tile = sparse.QUERY_TILE
    kernel = jax.jit(lambda s, l: sparse.choose_tpu(
        s, l, k=top, start_b=None).astype(jnp.int8))
    differ = jax.jit(lambda s, l: (kernel(s, l) != sparse.choose_xla(
        s, l, k=top, start_b=None)).sum())

    def operands(t0):
        at = t0 + jnp.arange(tile)
        lim = jnp.where(at < prompt, at + 1, 0).astype(jnp.int32)
        scores = jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(seed), t0), (tile, S),
            jnp.float32)
        if t0 // tile % 2:
            scores = jnp.round(scores * 4) / 4
        unwritten = jnp.arange(S) >= -(-lim.max() // 2048) * 2048
        return (jnp.where(unwritten[None], jnp.nan, scores),
                jnp.stack([lim, jnp.zeros_like(lim)], -1))

    report = {"long_masks_differ": sum(
        int(differ(*operands(t0)))
        for t0 in range(0, -(-prompt // tile) * tile, tile))}
    # by the device's own clock: a call's dispatch costs more than the
    # kernel does
    from benchmarks.harness import trace

    runs, args = 10, [operands(t0) for t0 in timed]
    jax.block_until_ready(args)
    with tempfile.TemporaryDirectory() as where:
        with jax.profiler.trace(where):
            for operand in args:
                for _ in range(runs):
                    out = kernel(*operand)
                out.block_until_ready()
        ops = trace.load_xplane(trace.find_xplane(where))["devices"][0]["ops"]
    took = [e[2] for e in sorted(ops, key=lambda e: e[1])
            if sparse.SELECT_KERNEL in e[0]]
    if len(took) != runs * len(timed):
        raise AssertionError(
            f"{len(took)} events of {sparse.SELECT_KERNEL} in the trace, "
            f"not {runs * len(timed)}")
    report["select_ms"] = {
        str(t0): round(statistics.median(
            took[i * runs:(i + 1) * runs]) / 1e6, 4)
        for i, t0 in enumerate(timed)}
    return report


# (prompt tokens, the buckets it is run in: a rung of
# ``runner.PREFILL_RUNGS``, the power of two above it and, where the chip
# holds it, the next, the configurations whose first token is compared)
BUCKET_CASES = (
    (12000, (12288, 16384, 32768), ("minicpm-sala-int8-12l",)),
    (22372, (24576, 32768), ("minicpm-sala-int8-12l",
                             "keye-vl-2.0-30b-a3b-ep4-int8-16l")))


def _bucket_kernels(seed: int, prompt: int, rows, dense_len: int,
                    top: int) -> dict:
    """The entries of a prompt's own rows that differ between each bucket
    of ``rows`` and the next, for the five prefill kernels that have rows
    to pad, at the served widths: ``rt_linear_prefill`` (32 heads of 128,
    and the state), ``flash_block_sparse_fwd`` with ``rt_sparse_select``'s
    choice of 64 blocks of 64 for the queries past ``dense_len`` (32 heads
    over 2 KV heads), ``flash_sparse_fwd`` with ``rt_sparse_select``'s
    choice of ``top`` keys (16 indexer heads; 32 heads over 4 KV heads),
    and ``flash_mla_fwd`` (16 heads scoring 192 wide, values of 128)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import linear_attention
    from ray_tpu.ops import sparse_attention as sparse
    from ray_tpu.ops.attention import attention

    L, top_rows = prompt, max(rows)
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)

    # the CPU's dot takes no bfloat16: a rehearsal there runs float32
    half = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32

    def normal(key, *shape, dtype=half):
        return jax.random.normal(key, (top_rows, *shape), dtype)

    def padded(S):
        at = jnp.arange(S, dtype=jnp.int32)
        return at, at < L

    scale = 128 ** -0.5
    slopes = tuple(map(float, linear_attention.slopes_of(32)))
    wide = [normal(k, 32, 128) for k in ks[:3]]             # q, k, v

    def linear(S, q, k, v):
        o, state = linear_attention.prefill(
            q[None, :S], k[None, :S], v[None, :S], slopes,
            jnp.asarray([L], jnp.int32), scale=scale)
        return o[0, :L], state

    sizes = sparse.BlockSizes(64, 64, 16, 1, 2048, dense_len)
    few = [normal(k, 2, 128) for k in ks[3:5]]              # k, v: 2 KV heads

    def blocks(S, q, k, v):
        at, valid = padded(S)
        sums = sparse.stride_sums(k[:S], valid, sizes.stride)
        q_pos = jnp.where(valid, at, -1)[dense_len:]
        out = sparse.block_attend(q[dense_len:S], k[:S], v[:S], sums, q_pos,
                                  sizes, scale=scale)
        # and the choice alone, of the prompt's last 512 queries
        tile = slice(L - 512, L)
        c = jnp.swapaxes(sparse.compressed_keys(sums, sizes, q.dtype),
                         0, 1)[None]
        scores = sparse.block_scores(
            jnp.swapaxes(q[tile], 0, 1)[None], c, at[None, tile], sizes,
            scale=scale)
        chosen = sparse.block_choice(scores, at[None, tile], sizes)
        return out[:L - dense_len], chosen[..., :-(-L // sizes.size)]

    four = [normal(k, 4, 128) for k in ks[3:5]]             # k, v: 4 KV heads
    index = [normal(ks[5], 16, 128), normal(ks[7], 16, dtype=jnp.float32),
             normal(ks[6], 128)]                            # qi, w, ki

    def indexer(S, q, k, v, qi, w, ki):
        at, valid = padded(S)
        out = sparse.attend(
            q[None, :S], k[None, :S], v[None, :S], qi[None, :S], w[None, :S],
            ki[None, :S], jnp.where(valid, at + 1, 0)[None], top_k=top,
            scale=scale)
        tile = slice(L - 512, L)
        last = at[tile] + 1
        scores = sparse.index_scores(qi[None, tile], w[None, tile],
                                     ki[None, :S], last[None])[0]
        return out[0, :L], sparse.choose(scores, last, top_k=top)[:, :L]

    mla = [normal(ks[0], 16, 192), normal(ks[1], 16, 192),
           normal(ks[2], 16, 128)]

    def latent(S, q, k, v):
        return attention(q[None, :S], k[None, :S], v[None, :S], causal=True,
                         scale=192 ** -0.5,
                         lengths=jnp.asarray([L], jnp.int32))[0, :L],

    report = {}
    for name, run, operands in (
            ("rt_linear_prefill", linear, wide),
            ("flash_block_sparse_fwd", blocks, [wide[0], *few]),
            ("flash_sparse_fwd", indexer, [wide[0], *four, *index]),
            ("flash_mla_fwd", latent, mla)):
        run = jax.jit(run, static_argnums=0)
        got = {S: [np.asarray(x) for x in run(S, *operands)] for S in rows}

        def differ(a, b):
            return int(sum((x != y).sum() for x, y in zip(got[a], got[b])))

        if not all(np.isfinite(x.astype(np.float32)).all()
                   for x in got[rows[0]]):
            raise AssertionError(f"{name}: not finite at {rows[0]} rows")
        report[name] = [differ(a, b) for a, b in zip(rows, rows[1:])]
    return report


def _bucket_first_tokens(seed: int, prompt: int, rung: int, power: int,
                         name: str) -> dict:
    """The whole program, through the engine's own call: two layers of
    the configuration ``name`` (seeded int8 weights; of
    ``minicpm-sala-int8-12l`` a sparse and a linear one) give the prompt
    its first token in the power-of-two bucket and in the rung below it.
    Returns (rows, token) by bucket."""
    import jax

    from benchmarks.harness import families
    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmarks", "configs", name + ".json")) as f:
        config = {**json.load(f), "num_hidden_layers": 2}
    if "mixer_types" in config:
        config["mixer_types"] = ["minicpm4", "lightning-attn"]
    family = families.family_of(config)
    params = family.served_params(jax.random.PRNGKey(seed), config)
    tokens = [1 + t % (config["vocab_size"] - 1)
              for t in seeded_prompt(seed, 0, prompt)]
    first = {}
    for label, rungs in (("power", ()), ("rung", (rung,))):
        engine = LLMEngine(params, family.program_config(config), EngineConfig(
            max_num_seqs=1, page_size=64, num_pages=2 + power // 64,
            max_seq_len=power, decode_burst=8))
        engine._PREFILL_RUNGS = rungs
        rid = engine.add_request(tokens, SamplingParams(temperature=0.0,
                                                        max_tokens=1))
        while engine.has_unfinished():
            engine.step()
        first[label] = (engine.stats()["counters"]["prefill_bucket_tokens"],
                        int(engine.requests[rid].output[0]))
        del engine
    return first


def _bucket_parity(seed: int, cases=BUCKET_CASES, dense_len: int = 8192,
                   top: int = 2048) -> dict:
    """A prompt in a rung's bucket (``runner.PREFILL_RUNGS``) and in the
    power of two beside it: 12,000 tokens in 12,288 and 16,384 rows,
    22,372 in 24,576 and 32,768. The rows behind the prompt are masked,
    so the prompt's own rows come out the same. Held for the five prefill
    kernels (``_bucket_kernels``): a kernel is held to the bit, in every
    case, where the two programs of powers of two that the first case
    runs (16,384 and 32,768 rows) agree to the bit. Then the whole
    program (``_bucket_first_tokens``): the same first token in both
    buckets. Runs in the gang worker. The sizes are arguments so that the
    CPU can rehearse the cases at a few hundred rows (float32 there)."""
    report, unstable = {}, set()
    for prompt, rows, names in cases:
        kernels = _bucket_kernels(seed, prompt, rows, dense_len, top)
        if len(rows) > 2:
            unstable |= {k for k, differs in kernels.items() if differs[1]}
        first = {name: _bucket_first_tokens(seed, prompt, rows[0], rows[1],
                                            name) for name in names}
        report[str(prompt)] = {"rows": list(rows), "differ": kernels,
                               "first_token": first}
        wrong = [k for k, differs in kernels.items()
                 if differs[0] and k not in unstable]
        if wrong or any(
                f["power"][1] != f["rung"][1]
                or [f["rung"][0], f["power"][0]] != list(rows[:2])
                for f in first.values()):
            raise AssertionError(f"a rung's rows are not its neighbour's: "
                                 f"{report}")
    return report


def _run_steps(cfg, mesh, spec: dict, seed: int, on_step=None) -> dict:
    """``spec['steps']`` adamw steps of ``cfg`` on ``mesh`` over one seeded
    batch. Returns losses, timings, each device's bytes in use and the
    number of ``tpu_custom_call``s in the compiled step."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu._private import device_plane
    from ray_tpu.models import init_params, lm_loss, param_logical_axes
    from ray_tpu.train import make_train_step

    t0 = time.time()
    init_fn, step_fn, place_batch = make_train_step(
        lambda p, b: lm_loss(p, b, cfg, mesh=mesh),
        optax.adamw(3e-4, weight_decay=0.1), mesh, param_logical_axes(cfg))
    state = init_fn(init_params(jax.random.PRNGKey(seed), cfg))
    data = place_batch({"tokens": jax.random.randint(
        jax.random.PRNGKey(seed + 1), (spec["batch"], spec["seq"]), 0,
        cfg.vocab, jnp.int32)})
    losses, step_s = [], []
    for i in range(spec["steps"]):
        t = time.time()
        state, metrics = step_fn(state, data)
        losses.append(float(metrics["loss"]))     # waits for the device
        step_s.append(time.time() - t)
        if i == 0:
            to_first_step_s = time.time() - t0
            cache_after_first = device_plane.compilation_cache_stats()
        if on_step is not None:
            on_step(i, losses)
    bytes_in_use = [_hbm(d.memory_stats(), "bytes_in_use")
                    for d in mesh.devices.flat]
    text = step_fn.lower(state, data).compile().as_text()
    del state, data
    return {"losses": losses,
            "to_first_step_s": round(to_first_step_s, 2),
            "first_step_s": round(step_s[0], 2),
            "step_ms": round(1e3 * min(step_s[1:]), 1),
            "compile_cache_after_first_step": cache_after_first,
            "bytes_in_use": bytes_in_use,
            "tpu_custom_calls": text.count("tpu_custom_call")}


def _device_report() -> dict:
    import jax

    from ray_tpu import get_tpu_chip_ids

    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs), "pid": os.getpid(),
            "chip_ids": get_tpu_chip_ids(),
            "compile_cache_dir": jax.config.jax_compilation_cache_dir}


def train_fn(config: dict) -> None:
    """Runs in the gang worker Trainer.fit started: kernel parity first,
    then the train steps, one ``train.report`` per step. The last report
    carries everything the driver checks."""
    import jax

    from ray_tpu import train
    from ray_tpu.models import LLAMA_CONFIGS
    from ray_tpu.parallel import MeshSpec, build_mesh

    report = {"device": _device_report()}
    if config["kernel_parity"]:
        report["kernel_parity"] = _kernel_parity(config["seed"])
        report["expert_kernel_parity"] = _expert_kernel_parity(
            config["seed"])
        report["sparse_kernel_parity"] = _sparse_kernel_parity(
            config["seed"])
        report["bucket_parity"] = _bucket_parity(config["seed"])
    cfg = dataclasses.replace(LLAMA_CONFIGS[config["model"]],
                              remat_policy=ONE_CHIP_REMAT)
    mesh = build_mesh(MeshSpec(), jax.devices()[:1])
    report.update(_run_steps(
        cfg, mesh, config, config["seed"],
        on_step=lambda i, losses: train.report(
            {**report, "step": i + 1, "losses": list(losses)})))
    report["peak_hbm_bytes"] = _hbm(jax.devices()[0].memory_stats(),
                                    "peak_bytes_in_use")
    train.report({**report, "step": config["steps"] + 1, "final": True})


def sharded_train_fn(config: dict) -> None:
    """One gang worker holding the whole host: the same seeded steps on a
    one-device mesh, then on the fsdp x tp mesh over all local chips."""
    import gc

    import jax

    from ray_tpu import train
    from ray_tpu.models import LLAMA_CONFIGS
    from ray_tpu.parallel import MeshSpec, build_mesh

    cfg = LLAMA_CONFIGS[config["model"]]
    devices = jax.devices()
    report = {"device": _device_report()}
    single = _run_steps(
        dataclasses.replace(cfg, remat_policy=ONE_CHIP_REMAT),
        build_mesh(MeshSpec(), devices[:1]), config, config["seed"])
    gc.collect()
    report["single"] = single
    report["bytes_in_use_between"] = [
        _hbm(d.memory_stats(), "bytes_in_use") for d in devices]
    train.report({**report, "step": 1})
    report["sharded"] = _run_steps(
        cfg, build_mesh(MeshSpec(**config["mesh"]), devices), config,
        config["seed"])
    train.report({**report, "step": 2, "final": True})


def _fit(fn, config: dict, tpus: int, name: str) -> dict:
    from ray_tpu.train import RunConfig, ScalingConfig, Trainer

    t0 = time.time()
    result = Trainer(
        fn, train_loop_config=config,
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True,
            resources_per_worker={"CPU": 1, "TPU": tpus}),
        run_config=RunConfig(name=name, storage_path=RUNS_DIR)).fit()
    if result.error is not None:
        raise AssertionError(f"Trainer.fit failed: {result.error}")
    if not result.metrics.get("final"):
        raise AssertionError(f"no final report from the gang worker: "
                             f"{result.metrics}")
    return {**result.metrics, "fit_s": round(time.time() - t0, 1)}


def _check_losses(losses: list) -> None:
    import math

    if not all(math.isfinite(x) for x in losses) or not losses[-1] < losses[0]:
        raise AssertionError(f"losses must be finite and falling: {losses}")


def phase_train(seed: int, spec: dict) -> dict:
    m = _fit(train_fn, {**spec, "seed": seed}, 1, "chip_smoke_train")
    dev = m["device"]
    _check_device(dev)
    if spec["kernel_parity"]:
        emit("kernel_parity", shape="1x512, 8/4 heads of 128, causal, bf16",
             max_error_over_range=m["kernel_parity"])
        emit("expert_kernel_parity",
             shape="64 int8 experts of 768 on 2560, 48 and 73728 rows, "
                   "against lax.ragged_dot, bf16",
             by_product=m["expert_kernel_parity"])
        emit("sparse_kernel_parity",
             shape="512 queries x 8192 keys, 16 indexer heads, top 2048, "
                   "32/4 heads of 128; a decode step of 8 slots; the "
                   "choice a tile of a 22372-token prompt in 24576 rows",
             **m["sparse_kernel_parity"])
        emit("bucket_parity",
             shape="a 12000-token prompt in 12288, 16384 and 32768 rows, "
                   "a 22372-token one in 24576 and 32768: entries of its "
                   "own rows that differ between a bucket and the next, by "
                   "kernel; (rows, first token) of two layers of a "
                   "configuration in the power of two and in the rung",
             **m["bucket_parity"])
    _check_losses(m["losses"])
    if m["tpu_custom_calls"] < 1:
        raise AssertionError("the compiled train step holds no "
                             "tpu_custom_call: flash kernels fell back")
    emit("train", model=spec["model"], batch=spec["batch"], seq=spec["seq"],
         platform=dev["platform"], device_kind=dev["device_kind"],
         worker_pid=dev["pid"], chip_ids=dev["chip_ids"],
         losses=[round(x, 4) for x in m["losses"]],
         tpu_custom_calls=m["tpu_custom_calls"],
         to_first_step_s=m["to_first_step_s"],
         first_step_s=m["first_step_s"], step_ms=m["step_ms"],
         compile_cache_dir=dev["compile_cache_dir"],
         compile_cache=m["compile_cache_after_first_step"],
         peak_hbm_bytes=m["peak_hbm_bytes"], fit_s=m["fit_s"])
    return dev


# --------------------------------------------------------------- four chips
def phase_sharded_train(seed: int, spec: dict) -> dict:
    m = _fit(sharded_train_fn, {**spec, "seed": seed}, 4,
             "chip_smoke_sharded")
    dev = m["device"]
    _check_device(dev, 4)
    single, sharded = m["single"], m["sharded"]
    _check_losses(sharded["losses"])
    for a, b in zip(single["losses"], sharded["losses"]):
        # bf16 matmuls reduced in another order: a few parts in a thousand
        if abs(a - b) > 2e-2 * abs(a):
            raise AssertionError(
                f"sharded losses leave the one-device run: "
                f"{sharded['losses']} vs {single['losses']}")
    per_dev = sharded["bytes_in_use"]
    whole = single["bytes_in_use"][0]
    # parameters and optimizer state spread over the mesh: about a quarter
    # on each device, none of them holding the whole state
    if not all(0.15 * whole < b < 0.4 * whole for b in per_dev):
        raise AssertionError(
            f"state is not spread over the four chips: {per_dev} bytes in "
            f"use against {whole} on one device")
    emit("sharded_train", model=spec["model"], mesh=spec["mesh"],
         platform=dev["platform"], device_kind=dev["device_kind"],
         losses_single=[round(x, 4) for x in single["losses"]],
         losses_sharded=[round(x, 4) for x in sharded["losses"]],
         bytes_in_use_single=whole, bytes_in_use_sharded=per_dev,
         bytes_in_use_between=m["bytes_in_use_between"],
         tpu_custom_calls=sharded["tpu_custom_calls"],
         step_ms_single=single["step_ms"], step_ms_sharded=sharded["step_ms"],
         first_step_s_sharded=sharded["first_step_s"], fit_s=m["fit_s"])
    return dev


def phase_replicas(seed: int, spec: dict) -> None:
    """Four replicas behind the router, each a process holding one chip."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_deployment

    name = "llm4"
    t0 = time.time()
    handle = serve.run(build_llm_deployment(
        spec["model"], name=name, num_replicas=spec["num_replicas"],
        quantize=spec["quantize"], init="random", seed=seed,
        engine_config=spec["engine_config"]))
    payload = {"prompt_ids": seeded_prompt(seed, 0, PROMPT_LENS[0]),
               "temperature": 0.0, "max_tokens": MAX_TOKENS}
    replicas = _replicas_of(name)
    if len(replicas) != spec["num_replicas"]:
        raise AssertionError(f"want {spec['num_replicas']} replicas, "
                             f"got {len(replicas)}")
    # every replica answers the same prompt itself (and so is up)
    answers = [_completion(_replica_call(r, "completions", payload))
               for r in replicas]
    up_s = time.time() - t0
    for tokens in answers:
        _check_tokens(tokens)
    if any(a != answers[0] for a in answers):
        raise AssertionError(f"replicas disagree on one prompt: {answers}")
    infos = [_replica_call(r, "device_info") for r in replicas]
    for info in infos:
        _check_device(info)
    _check_distinct(infos)

    # a burst through the router: who answered?
    completions = handle.options(method_name="completions")
    burst = [{"prompt_ids": seeded_prompt(seed, i % len(PROMPT_LENS),
                                          PROMPT_LENS[i % len(PROMPT_LENS)]),
              "temperature": 0.0, "max_tokens": MAX_TOKENS}
             for i in range(16)]
    t1 = time.time()
    routed = [completions.route(p) for p in burst]
    outs = ray_tpu.get([ref for ref, _ in routed], timeout=900)
    burst_s = time.time() - t1
    for out in outs:
        _check_tokens(_completion(out))
    if _completion(outs[0]) != answers[0]:
        raise AssertionError("the router's answer differs from the "
                             "replicas' own")
    served_by = {replica._actor_id for _, replica in routed}
    if len(served_by) < 2:
        raise AssertionError("one replica answered the whole burst")
    serve.shutdown()
    gone_s = _wait_gone([i["pid"] for i in infos])
    emit("replicas", model=spec["model"], num_replicas=len(infos),
         devices=[{k: i[k] for k in ("pid", "platform", "device_kind",
                                     "device_id", "chip_ids")}
                  for i in infos],
         peak_hbm_bytes=[_hbm(i["memory_stats"], "peak_bytes_in_use")
                         for i in infos],
         replicas_answering_burst=len(served_by), all_up_s=round(up_s, 1),
         burst_s=round(burst_s, 2), replica_exit_s=round(gone_s, 2))


# --------------------------------------------------------------------- main
def _dump_worker_logs(tail_bytes: int = 3000) -> None:
    """On failure, the end of every worker log of this session that
    recorded a traceback, to stderr: the cause is rarely in this process."""
    import glob

    import ray_tpu
    from ray_tpu._private.config import session_log_dir

    node = ray_tpu._worker_api.node()
    if node is None:
        return
    for path in sorted(glob.glob(os.path.join(
            session_log_dir(node.session_name), "worker-*.log"))):
        with open(path, "rb") as f:
            f.seek(max(0, os.path.getsize(path) - tail_bytes))
            tail = f.read().decode(errors="replace")
        if "Traceback" in tail or "Error" in tail:
            print(f"--- {path}\n{tail}", file=sys.stderr, flush=True)


def run(chips: int, seed: int) -> dict:
    """All phases for ``chips``; returns the device for the last line."""
    import ray_tpu

    try:
        phase_runtime(chips)
        if chips == 1:
            info = phase_serve(seed, SERVE)
            dev = phase_train(seed, TRAIN)
            if (dev["platform"], dev["device_kind"]) != (
                    info["platform"], info["device_kind"]):
                raise AssertionError(f"replica and gang worker saw "
                                     f"different devices: {info} vs {dev}")
        else:
            dev = phase_sharded_train(seed, SHARDED)
            phase_replicas(seed, REPLICAS)
    except BaseException:
        _dump_worker_logs()   # the chip's holder failed in another process
        raise
    finally:
        ray_tpu.shutdown()
        shutil.rmtree(RUNS_DIR, ignore_errors=True)
    return {"platform": dev["platform"], "kind": dev["device_kind"],
            "count": dev["device_count"]}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    device = run(args.chips, args.seed)
    if "jax" in sys.modules:
        raise AssertionError("the driver imported jax")
    if device["platform"] != "tpu" or device["count"] != args.chips:
        raise AssertionError(f"not a {args.chips}-chip TPU run: {device}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
