"""The latent cache, held to the reference at the cell's own lengths.

    chiprun -- python3 benchmarks/check_long_context_latent.py [--workload <cell>] [--seed <n>]

A serve cell's ``correct`` comes from ``serve_cell.py``'s two probes of
64 + 16 tokens, which reach neither the second prefill bucket nor a
second page of the cache. This deploys the cell's replica the same way
(``check_long_context.py``'s ``long_replica``: ``serve.run``, the
family's class under the benchmark's watchers, the cell's configuration
and engine settings) and asks it, through the handle, for 64 greedy
tokens after prompts of 64 and 12,000 tokens: once alone, and once
together with three prompts of a few thousand in the batch. Every chosen
token is then teacher-forced through the family's plain
``forward_logits`` on the replica's own weights (``last=``: the logits
of the answer's positions only), and its margins must lie under the
family's two limits (the worst under ``MARGIN_LIMIT``, which is what a
cell's ``correct`` judges; the mean under ``MEAN_MARGIN_LIMIT``). The
controls are read on the same tokens and must each FAIL by one of the
two limits where ``decides``: the layers' int8 weights rounded to 4
bits, the softmax scale without YaRN's m^2, plain rotary frequencies for
YaRN's, the 6 largest of all 160 experts for the group-limited choice,
no shared expert; the router's product in bfloat16 is read and printed.
The last line says ``ok``; exit code 0 only if every answer is under
both limits and every deciding control over one. It edits nothing and
is no cell.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse              # noqa: E402
import json                  # noqa: E402
import os                    # noqa: E402
import random                # noqa: E402
import sys                   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
LENGTHS, SHORT, ANSWER = (64, 12000), (2100, 3000, 4200), 64
CONTROLS = (
    # (name, the reference's keywords, whether it must read over a limit)
    ("layer_weights_in_int4", dict(int4=True), True),
    ("scale_without_m_squared", dict(mscale_squared=False), True),
    ("plain_rotary_for_yarn", dict(yarn=False), True),
    ("ungrouped_top_k", dict(grouped=False), True),
    ("no_shared_expert", dict(shared=False), True),
    ("router_in_bfloat16", dict(router_dtype="bfloat16"), False),
)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", default="deepseekv2-longdoc-steady")
    parser.add_argument("--seed", type=int, default=20260930)
    parser.add_argument("--lengths", default=",".join(map(str, LENGTHS)))
    parser.add_argument("--short", default=",".join(map(str, SHORT)))
    parser.add_argument("--answer", type=int, default=ANSWER)
    args = parser.parse_args()
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    import run as bench_run
    from check_long_context import long_replica

    import ray_tpu
    from benchmarks.harness import families, runtime
    from ray_tpu import serve

    cell = bench_run.load_json("workloads", args.workload + ".json")
    config = bench_run.load_json("configs", cell["config"] + ".json")
    config_path = os.path.join(HERE, "configs", cell["config"] + ".json")
    family = families.family_of(config)
    limit, mean_limit = family.MARGIN_LIMIT, family.MEAN_MARGIN_LIMIT
    lengths = [int(n) for n in args.lengths.split(",")]
    short = [int(n) for n in args.short.split(",")]
    vocab, rng = int(config["vocab_size"]), random.Random(args.seed)
    prompts = {n: [rng.randrange(1, vocab) for _ in range(n)]
               for n in lengths + short}
    quantized = config.get("quantize") == "int8"
    ok = True

    def say(**line):
        print(json.dumps(line), flush=True)

    try:
        runtime.start_runtime(int(cell["chips"]),
                              bool(config.get("rehearsal")))
        handle = serve.run(serve.deployment(
            long_replica(config), name="llm", num_replicas=1).bind(
                config_path, seed=args.seed % 2147483647))
        serve.start()
        completions = handle.options(method_name="completions")
        margins = handle.options(method_name="long_margins")
        device = ray_tpu.get(handle.options(
            method_name="bench_device").remote(), timeout=1500)
        runtime.check_device(device, int(cell["chips"]),
                             bool(config.get("rehearsal")))
        say(ready_s=time.time() - T_PROCESS, device=device["kind"])

        def ask(n):
            return completions.remote({
                "prompt_ids": prompts[n], "temperature": 0.0,
                "max_tokens": args.answer})

        def answer(ref):
            return ray_tpu.get(ref, timeout=1500)["choices"][0]["token_ids"]

        alone = {n: answer(ask(n)) for n in lengths}
        together = {n: ref for n, ref in [
            (n, ask(n)) for n in lengths + short]}
        together = {n: answer(ref) for n, ref in together.items()}

        def read(n, tokens, **control):
            out = ray_tpu.get(margins.remote({
                "prompt": prompts[n], "answer": tokens,
                "control": control}), timeout=3000)
            return (max(out["margins"]),
                    sum(out["margins"]) / len(out["margins"]),
                    sum(m == 0.0 for m in out["margins"]), out["seconds"])

        for route, answers in (("alone", alone), ("together", together)):
            for n, tokens in answers.items():
                if len(tokens) != args.answer:
                    say(prompt_tokens=n, route=route, problem=tokens)
                    ok = False
                    continue
                if route == "together" and tokens == alone.get(n):
                    say(prompt_tokens=n, route=route, same_as="alone")
                    continue
                margin, mean, agreed, seconds = read(n, tokens)
                under = margin <= limit and mean <= mean_limit
                ok &= under
                say(prompt_tokens=n, route=route, margin_worst=margin,
                    margin_mean=mean, first_choices=agreed,
                    distinct=len(set(tokens)), limit=limit,
                    mean_limit=mean_limit, under=under, reference_s=seconds)
        for name, control, decides in CONTROLS:
            decides = decides and (quantized or "int4" not in control)
            for n in lengths:
                margin, mean, agreed, seconds = read(n, alone[n], **control)
                over = margin > limit or mean > mean_limit
                if decides:
                    ok &= over
                say(prompt_tokens=n, control=name, margin_worst=margin,
                    margin_mean=mean, first_choices=agreed, limit=limit,
                    mean_limit=mean_limit, over=over, decides=decides,
                    reference_s=seconds)
        say(memory_peak_bytes=ray_tpu.get(handle.options(
            method_name="bench_device").remote(), timeout=600)[
                "memory_peak_bytes"])
    except BaseException:
        runtime.dump_worker_logs()
        raise
    finally:
        try:
            serve.shutdown()
        finally:
            runtime.stop_runtime()
    say(ok=bool(ok), limit=limit, mean_limit=mean_limit)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
