"""The state a slot, the window, the ONE full layer's pages that eight
layers read and the cross-decoder at a prefill's last row, held to the
reference at the cell's own lengths.

    chiprun -- python3 benchmarks/check_long_context_sambay.py [--workload <cell>] [--seed <n>]

A serve cell's ``correct`` comes from ``serve_cell.py``'s two probes of
64 + 16 tokens, which never reach the 512-token window, a page's
release, a second chunk of the scan kernel or a prefill rung. This
deploys the cell's replica the same way (``check_long_context.py``'s
``long_replica``: ``serve.run``, the family's class under the
benchmark's watchers, the cell's configuration and engine settings) and
asks it, through the handle, for 64 greedy tokens after prompts of 2,048
and 12,000 tokens: once alone, and once all together with three shorter
prompts in the batch (other slots decoding beside them). That is the
TIMED path: whole-prompt prefill (the cross-decoder at the last row
only), then ``decode_burst`` through the pages and the state pool. Every
chosen token is then teacher-forced through the family's plain
``forward_logits`` on the replica's own weights (ONE forward pass, every
layer at every position; ``last=``: the logits of the answer's positions
only), and its margins must lie under this check's OWN two limits, which
the family states from this check's readings (the worst under
``LONG_MARGIN_LIMIT``, the mean under ``MEAN_MARGIN_LIMIT``). The
controls are read on the same tokens (``--controls-at``) and must each
FAIL by one of the two limits: every window layer full; ``lam`` = 0;
``m`` taken after the gate; the cross layers given fresh keys and values
of their own; the scan's state reset at every chunk boundary of the
kernel (256 tokens); the weights rounded to int8, the precision below
the stated bfloat16. A control that stays under both limits at EVERY
length it is read at is named in the last line's ``required_not_caught``
and fails the check. Then the cell's OWN comparison at its own shape
(``--probe-seeds``): ``serve_cell.py``'s two probe prompts of 64 tokens
a seed, 16 greedy tokens each by the handle, the worst margin of a
seed's 32 tokens under the family's ``MARGIN_LIMIT`` as the cell judges
it, and the same tokens under the reference on int8 weights, which has
to read OVER that limit on some seed (``weights_in_int8_at_probes``).
The engine's counters are read too:
``cross_prefill_rows`` over ``prefills`` must be 1.0 (no reader can see
a counter: ``harness/llm_server.py`` hands the readers clocks, rounds and
the trace). The last line says ``ok``; exit code 0 only if every answer
is under both limits, every control over one at some length and the
counter 1.0. It edits nothing and is no cell.
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
LENGTHS, SHORT, ANSWER = (2048, 12000), (64, 600, 1300), 64
CONTROLS_AT = (2048, 12000)
CONTROLS = (
    # (name, the reference's keywords, whether it must read over a limit)
    ("every_window_layer_full", dict(window_full=True), True),
    ("lambda_of_zero", dict(lam_zero=True), True),
    ("m_after_the_gate", dict(m_after_gate=True), True),
    ("cross_layers_fresh_kv", dict(cross_fresh=True), True),
    ("state_reset_at_chunks", dict(reset_every=256), True),
    ("weights_in_int8", dict(int8=True), True),
)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", default="phi4flash-reasonlong-steady")
    parser.add_argument("--seed", type=int, default=20261055)
    parser.add_argument("--lengths", default=",".join(map(str, LENGTHS)))
    parser.add_argument("--short", default=",".join(map(str, SHORT)))
    parser.add_argument("--answer", type=int, default=ANSWER)
    parser.add_argument("--controls-at",
                        default=",".join(map(str, CONTROLS_AT)),
                        help="the lengths at which the controls are read")
    parser.add_argument("--controls", default="",
                        help="only these controls (names, comma separated)")
    parser.add_argument("--probe-seeds", type=int, default=4,
                        help="seeds of the cell's own probes read under "
                        "MARGIN_LIMIT, sound and on int8 weights")
    args = parser.parse_args()
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    import run as bench_run
    from check_long_context import long_replica

    import ray_tpu
    from benchmarks.harness import families, runtime, serve_cell
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
        for name, control, decides in CONTROLS:
            if wanted and name not in wanted:
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
        # the cell's own probes, as ``serve_cell._check_probes`` asks
        # and judges them, and the same tokens on int8 weights
        probe_limit = getattr(family, "MARGIN_LIMIT",
                              serve_cell.MARGIN_LIMIT)
        caught = not args.probe_seeds
        for seed in range(args.seed, args.seed + args.probe_seeds):
            worst = {"sound": 0.0, "int8": 0.0}
            for probe in serve_cell._probe_requests(seed, vocab):
                tokens = answer(completions.remote({
                    "prompt_ids": list(probe.prompt_ids),
                    "temperature": 0.0, "max_tokens": probe.max_tokens}))
                for name, control in (("sound", {}),
                                      ("int8", dict(int8=True))):
                    out = ray_tpu.get(margins.remote({
                        "prompt": list(probe.prompt_ids), "answer": tokens,
                        "control": control}), timeout=3000)
                    worst[name] = max(worst[name], *out["margins"])
            ok &= worst["sound"] <= probe_limit
            caught |= worst["int8"] > probe_limit
            say(probe_seed=seed, margin_worst=worst["sound"],
                margin_worst_int8=worst["int8"], limit=probe_limit)
        if not caught and (not wanted or "weights_in_int8" in wanted):
            not_caught.add("weights_in_int8_at_probes")
            ok = False
        counters = ray_tpu.get(handle.options(
            method_name="stats").remote(), timeout=600)["counters"]
        rows = counters["cross_prefill_rows"] / max(counters["prefills"], 1)
        ok &= rows == 1.0
        say(cross_prefill_rows=rows, **{k: counters[k] for k in (
            "prefills", "scan_slots_reset", "scan_state_bytes_step",
            "shared_kv_pages_step")},
            released_pages=counters["groups"]["window"]["released_pages"])
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
