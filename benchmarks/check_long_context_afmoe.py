"""The window, the share and the router, held to the reference at the
cell's own lengths: ``check_long_context.py`` for the family ``afmoe``.

    chiprun --timeout 3000 -- python3 benchmarks/check_long_context_afmoe.py [--workload <cell>] [--seed <n>]

A serve cell's ``correct`` comes from ``serve_cell.py``'s two probes of
64 + 16 tokens, which never reach a 4,096-token window. This deploys the
cell's replica the same way (``serve.run``, the family's class under the
benchmark's watchers, the cell's configuration and engine settings) and
asks it, through the handle, for 32 greedy tokens after prompts of 64,
4,160, 8,170 and 16,000 tokens: once alone, and once all together with
three short ones in the batch (8 slots). The prompt of 8,170 gives a
page of the window group back at its 22nd token (8,170 - 4,095 = 63 x 64
+ 43), so a page is released under a sequence while it decodes. Every
chosen token is then teacher-forced through the family's plain
``forward_logits`` on the replica's own weights (attention a block of
queries at a time, every held expert on every token), and its margins
(``harness/families.chosen_token_margins``'s unit) must lie under the
family's two limits: the worst of the 32 under ``MARGIN_LIMIT``, which is
what a cell's ``correct`` judges, and their mean under
``MEAN_MARGIN_LIMIT``. The controls, each the reference wrong on purpose
on the same tokens. Six must read OVER one of the two limits on one of
the answers they are read at, or the exit code is 1 and ``not_caught``
names them: every layer full (the window ignored; at the prompts half a
window or more past the window), rotary on the full layers too, the post
norms left out and the layers' int8 weights rounded to 4 bits (the
nearest precision below the one stated), each at the shortest and the
longest prompt; the bias left out of the choice and softmax for sigmoid,
each at every answer but the second prompt's (a wrong router moves one
answer in two to three over a limit: ``families/afmoe.py`` has the
readings that ``SEED_GAINS`` and the two limits were set from). Two are
read at the shortest prompt and printed, and the exit code does not rest
on them (``required_not_caught`` names those that stay under both
limits): the bias added to the weights, which moves a weight by 1.5% at
the seeded bias's size and reads what the sound answers read
(tests/test_llm_trinity.py holds it exactly, in float32), and the
router's product in bfloat16, which adds what the answers themselves
hold (the engine feeds its float32 router a bf16 hidden state). It edits
nothing and is no cell. ``check_long_context.py`` cannot take this
family: it reads SmallThinker's key for the window and has that family's
four controls.
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
LENGTHS, SHORT, ANSWER = (64, 4160, 8170, 16000), (48, 96, 160), 32
# name -> (the reference's keywords, whether it decides the exit code,
# the answers it is read at: "past", the prompts half a window or more
# past the window; "ends", the shortest and the longest prompt; "every",
# each answer but the second prompt's; "first", the shortest prompt)
CONTROLS = {
    "every_layer_full": (dict(all_full=True), True, "past"),
    "rotary_on_the_full_layers": (dict(rotate_all=True), True, "ends"),
    "post_norms_left_out": (dict(post_norms=False), True, "ends"),
    "layer_weights_in_int4": (dict(int4=True), True, "ends"),
    "bias_left_out_of_the_choice": (dict(bias_in_choice=False), True,
                                    "every"),
    "softmax_for_sigmoid": (dict(score="softmax"), True, "every"),
    "bias_added_to_the_weights": (dict(bias_in_weights=True), False,
                                  "first"),
    "router_in_bfloat16": (dict(router_dtype="bfloat16"), False, "first"),
}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", default="trinity-agentctx-steady")
    parser.add_argument("--seed", type=int, default=20261003)
    parser.add_argument("--lengths", default=",".join(map(str, LENGTHS)))
    parser.add_argument("--short", default=",".join(map(str, SHORT)),
                        help="the short prompts batched with the long ones")
    parser.add_argument("--controls", default=",".join(CONTROLS))
    parser.add_argument("--controls-at", default="",
                        help="prompts the controls are read at (default: "
                        "every long one but the second, and the short ones)")
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
    short = [int(n) for n in args.short.split(",") if n]
    window = int(config["sliding_window"])
    at = [int(n) for n in args.controls_at.split(",") if n] or (
        lengths[:1] + lengths[2:] + short)
    long_at = [n for n in at if n in lengths] or lengths[:1]
    read_at = {"past": [n for n in long_at if 2 * n >= 3 * window],
               "ends": sorted({long_at[0], long_at[-1]}),
               "every": at, "first": long_at[:1]}
    vocab, rng = int(config["vocab_size"]), random.Random(args.seed)
    prompts = {n: [rng.randrange(1, vocab) for _ in range(n)]
               for n in lengths + short}
    ok, not_caught, required_not_caught = True, [], []

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
        stats = handle.options(method_name="stats")
        device = ray_tpu.get(handle.options(
            method_name="bench_device").remote(), timeout=1500)
        runtime.check_device(device, int(cell["chips"]),
                             bool(config.get("rehearsal")))
        say(ready_s=time.time() - T_PROCESS, device=device["kind"])

        def ask(n):
            return completions.remote({
                "prompt_ids": prompts[n], "temperature": 0.0,
                "max_tokens": ANSWER})

        def answer(ref):
            return ray_tpu.get(ref, timeout=1500)["choices"][0]["token_ids"]

        def released():
            return ray_tpu.get(stats.remote(), timeout=60)["counters"][
                "groups"]["window"]["released_pages"]

        alone = {}
        for n in lengths:
            before = released()
            alone[n] = answer(ask(n))
            say(prompt_tokens=n, route="alone",
                window_pages_released=released() - before)
        together = {n: ask(n) for n in lengths + short}
        together = {n: answer(ref) for n, ref in together.items()}
        say(groups=ray_tpu.get(stats.remote(), timeout=60)["counters"][
            "groups"], memory_peak_bytes=ray_tpu.get(handle.options(
                method_name="bench_device").remote(), timeout=600)[
                    "memory_peak_bytes"])

        def worst(n, tokens, **control):
            out = ray_tpu.get(margins.remote({
                "prompt": prompts[n], "answer": tokens,
                "control": control}), timeout=3000)
            return (max(out["margins"]),
                    sum(out["margins"]) / len(out["margins"]),
                    sum(m == 0.0 for m in out["margins"]), out["seconds"])

        for route, answers in (("alone", alone), ("together", together)):
            for n, tokens in answers.items():
                if len(tokens) != ANSWER:
                    say(prompt_tokens=n, route=route, problem=tokens)
                    ok = False
                    continue
                if route == "together" and tokens == alone.get(n):
                    say(prompt_tokens=n, route=route, same_as="alone")
                    continue
                margin, mean, agreed, seconds = worst(n, tokens)
                under = margin <= limit and mean <= mean_limit
                ok &= under
                say(prompt_tokens=n, route=route, margin_worst=margin,
                    margin_mean=mean, first_choices=agreed, limit=limit,
                    mean_limit=mean_limit, under=under, reference_s=seconds)
        answered = {**together, **alone}
        for name in filter(None, args.controls.split(",")):
            control, decides, where = CONTROLS[name]
            # (a rehearsal's float32 weights have no bits to drop)
            if name == "layer_weights_in_int4":
                decides = config.get("quantize") == "int8"
            caught = False
            for n in read_at[where]:
                margin, mean, agreed, seconds = worst(n, answered[n],
                                                      **control)
                over = margin > limit or mean > mean_limit
                caught |= over
                say(prompt_tokens=n, control=name, margin_worst=margin,
                    margin_mean=mean, first_choices=agreed, limit=limit,
                    mean_limit=mean_limit, over=over, decides=decides,
                    reference_s=seconds)
            if not caught:
                (not_caught if decides else required_not_caught).append(name)
    except BaseException:
        runtime.dump_worker_logs()
        raise
    finally:
        try:
            serve.shutdown()
        finally:
            runtime.stop_runtime()
    ok = bool(ok and not not_caught)
    say(ok=ok, limit=limit, mean_limit=mean_limit, not_caught=not_caught,
        required_not_caught=required_not_caught)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
