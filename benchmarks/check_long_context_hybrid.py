"""The state a slot, the chosen blocks and the sums of strides, held to
the reference at the cell's own lengths.

    chiprun -- python3 benchmarks/check_long_context_hybrid.py [--workload <cell>] [--seed <n>]

A serve cell's ``correct`` comes from ``serve_cell.py``'s two probes of
64 + 16 tokens, which never reach ``dense_len`` (8,192), past which a
query of a sparse layer selects. This deploys the cell's replica the
same way (``check_long_context.py``'s ``long_replica``: ``serve.run``,
the family's class under the benchmark's watchers, the cell's
configuration and engine settings) and asks it, through the handle, for
64 greedy tokens after prompts of 8,300, 12,000 and 32,000 tokens: once
alone, and once all together with three shorter prompts in the batch
(other slots decoding beside them). That is the TIMED path: whole-prompt
prefill, then ``decode_burst`` through the pages and the state pool.
Every chosen token is then teacher-forced through the family's plain
``forward_logits`` on the replica's own weights (ONE forward pass;
``last=``: the logits of the answer's positions only), and its margins
must lie under this check's OWN two limits, which the family states
from this check's readings (the worst under ``LONG_MARGIN_LIMIT``, the
mean under ``MEAN_MARGIN_LIMIT``). The controls are read on the same
tokens (``--controls-at``) and must each FAIL by one of the two limits
where ``decides``: every visible key attended past ``dense_len``; the
forced first and local blocks left out; ``lambda_h`` = 1; no rotary in a
lightning layer; the layers' int8 weights rounded to 4 bits. The state
kept in bfloat16 is REQUIRED to fail too (the issue that brought the
family lists it) and does NOT at bf16 activations on seeded weights
(``families/minicpm_sala.py`` has the readings: it reads at most twice
the change's own margins): it is read, printed with ``"required":
true``, and named in the last line's ``required_not_caught`` wherever it
stays under both limits at every length, so that the gap is in every
run's output and not only in a document; it does not decide ``ok``, or
the check could tell a later PR nothing. A deciding control that stays
under both limits at EVERY length it is read at is named there too and
fails the check. The last line says ``ok``; exit code 0 only if every
answer is under both limits and every deciding control over one at some
length. It edits nothing and is no cell.
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
LENGTHS, SHORT, ANSWER = (8300, 12000, 32000), (64, 2100, 4200), 64
CONTROLS_AT = (12000, 32000)
CONTROLS = (
    # (name, the reference's keywords, whether it must read over a limit:
    # None where it is required to and, on seeded weights, does not)
    ("attend_over_every_key", dict(dense=True), True),
    ("forced_blocks_left_out", dict(forced=False), True),
    ("decay_of_one", dict(decay=False), True),
    ("no_rotary_in_lightning", dict(rotary=False), True),
    ("state_in_bfloat16", dict(state_dtype="bfloat16"), None),
    ("layer_weights_in_int4", dict(int4=True), True),
)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", default="minicpmsala-longfile-steady")
    parser.add_argument("--seed", type=int, default=20261046)
    parser.add_argument("--lengths", default=",".join(map(str, LENGTHS)))
    parser.add_argument("--short", default=",".join(map(str, SHORT)))
    parser.add_argument("--answer", type=int, default=ANSWER)
    parser.add_argument("--controls-at",
                        default=",".join(map(str, CONTROLS_AT)),
                        help="the lengths at which the controls are read")
    parser.add_argument("--controls", default="",
                        help="only these controls (names, comma separated)")
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
    limit, mean_limit = family.LONG_MARGIN_LIMIT, family.MEAN_MARGIN_LIMIT
    lengths = [int(n) for n in args.lengths.split(",")]
    short = [int(n) for n in args.short.split(",") if n]
    controls_at = [int(n) for n in args.controls_at.split(",") if n]
    wanted = [c for c in args.controls.split(",") if c]
    vocab, rng = int(config["vocab_size"]), random.Random(args.seed)
    prompts = {n: [rng.randrange(1, vocab) for _ in range(n)]
               for n in lengths + short}
    quantized = config.get("quantize") == "int8"
    ok, not_caught = True, set()

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
        quantized_only = ("layer_weights_in_int4",)
        for name, control, decides in CONTROLS:
            if (wanted and name not in wanted) or (
                    name in quantized_only and not quantized):
                continue
            caught = False
            for n in controls_at:
                margin, mean, agreed, seconds = read(n, alone[n], **control)
                over = margin > limit or mean > mean_limit
                caught |= over
                say(prompt_tokens=n, control=name, margin_worst=margin,
                    margin_mean=mean, first_choices=agreed, limit=limit,
                    mean_limit=mean_limit, over=over, decides=bool(decides),
                    required=True, reference_s=seconds)
            if not caught:
                not_caught.add(name)
                ok &= not decides
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
    say(ok=bool(ok), limit=limit, mean_limit=mean_limit,
        required_not_caught=sorted(not_caught))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
