"""The window, held to the reference at the cell's own lengths.

    chiprun -- python3 benchmarks/check_long_context.py [--workload <cell>] [--seed <n>]

A serve cell's ``correct`` comes from ``serve_cell.py``'s two probes of
64 + 16 tokens, which never reach a 4,096-token window. This deploys the
cell's replica the same way (``serve.run``, the family's class under the
benchmark's watchers, the cell's configuration and engine settings) and
asks it, through the handle, for 32 greedy tokens after prompts of 64,
4,160, 6,144, 8,170 and 12,032 tokens: once alone, and once all together
with three short ones in the batch (8 slots). The first four long ones
start a token past a page's edge and give no page back in 32 tokens; the
prompt of 8,170 does, at its 22nd token (8,170 - 4,095 = 63 x 64 + 43),
so a page is released under a sequence while it decodes. Every chosen
token is then teacher-forced through the family's plain
``forward_logits`` on the replica's own weights, and its margins (``harness/families.chosen_token_margins``'s unit) must lie under
the family's two limits: the worst of the 32 under ``MARGIN_LIMIT``, which
is what a cell's ``correct`` judges, and their mean under
``MEAN_MARGIN_LIMIT`` (a swapped expert moves few tokens far, a lower
precision or a wrong mask moves every token a little). Three controls
must FAIL, by one of the two limits, to show the comparison sees what it
is for: the reference with every layer full at the prompts half a window
or more past the window (6,144, 8,170, 12,032), rotary on the NoPE
layers too, and the layers' int8 weights rounded to 4 bits (the nearest
precision below the one stated). A fourth is read and printed and the
exit code does not rest on it: the router's product in bfloat16 reads
what the answers themselves read, because the engine feeds its float32
router a bf16 hidden state and a bf16 product adds only what is already
there (``families/smallthinker.py``, ``MARGIN_LIMIT``). Every line
carries the worst margin, the mean, and the tokens that are the
reference's first choice. The last line says ``ok``; exit code 0 only if
every answer is under both limits and every deciding control over one.
It edits nothing and is no cell.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse              # noqa: E402
import json                  # noqa: E402
import os                    # noqa: E402
import random                # noqa: E402
import sys                   # noqa: E402
import threading             # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
LENGTHS, SHORT, ANSWER = (64, 4160, 6144, 8170, 12032), (48, 96, 160), 32


def long_replica(config: dict) -> type:
    """The cell's replica class with one more pair of eyes: margins of
    chosen tokens against the family's reference with ``last=`` (a
    12,000-token prompt's logits over the whole vocabulary are 7 GB) and
    a control's keywords."""
    from benchmarks.harness.llm_server import replica_class

    class LongContext(replica_class(config)):
        async def long_margins(self, payload: dict):
            import asyncio

            import jax.numpy as jnp
            import numpy as np

            control = dict(payload.get("control") or {})
            if control.get("router_dtype") == "bfloat16":
                control["router_dtype"] = jnp.bfloat16

            def run():
                tokens = jnp.asarray(
                    [payload["prompt"] + payload["answer"]], jnp.int32)
                n = len(payload["answer"])
                with self._engine_lock:
                    logits = self.bench_family.forward_logits(
                        self.engine.params, tokens, self.bench_config,
                        last=n + 1, **control)[0, :-1]
                logits = np.asarray(logits, np.float32)   # [n, vocab]
                chosen = logits[np.arange(n), payload["answer"]]
                return ((logits.max(-1) - chosen)
                        / logits.std(-1)).tolist()

            t0 = time.perf_counter()
            margins = await asyncio.get_event_loop().run_in_executor(
                None, run)
            return {"margins": margins,
                    "seconds": time.perf_counter() - t0}

    return LongContext


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", default="smallthinker-mixedlen-steady")
    parser.add_argument("--seed", type=int, default=20260929)
    parser.add_argument("--lengths", default=",".join(map(str, LENGTHS)))
    parser.add_argument("--short", default=",".join(map(str, SHORT)),
                        help="the short prompts batched with the long ones")
    args = parser.parse_args()
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    import run as bench_run

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
    window = int(config.get("sliding_window_size", 0))
    vocab, rng = int(config["vocab_size"]), random.Random(args.seed)
    prompts = {n: [rng.randrange(1, vocab) for _ in range(n)]
               for n in lengths + short}
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

        # alone, one after another; the window group's pages in use are
        # watched while each decodes
        alone = {}
        for n in lengths:
            most, done = [0], threading.Event()

            def watch():
                while not done.is_set():
                    g = ray_tpu.get(stats.remote(), timeout=60)[
                        "counters"]["groups"].get("window")
                    if g:
                        most[0] = max(most[0],
                                      g["total_pages"] - g["free_pages"])
                    time.sleep(0.02)

            watcher = threading.Thread(target=watch, daemon=True)
            watcher.start()
            alone[n] = answer(ask(n))
            done.set()
            watcher.join()
            say(prompt_tokens=n, route="alone",
                window_pages_held_most=most[0])
        together = {n: ref for n, ref in [
            (n, ask(n)) for n in lengths + short]}
        together = {n: answer(ref) for n, ref in together.items()}
        groups = ray_tpu.get(stats.remote(), timeout=60)["counters"][
            "groups"]
        say(groups=groups)

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
        longest = [n for n in lengths if 2 * n >= 3 * window] \
            or lengths[-1:]
        # (name, the reference's keywords, the lengths, whether it must
        # read over one of the two limits)
        controls = [("every_layer_full", dict(all_full=True), longest, True),
                    ("rotary_on_the_nope_layers", dict(rotate_all=True),
                     lengths[:2], True),
                    # (a rehearsal's float32 weights have no bits to drop)
                    ("layer_weights_in_int4", dict(int4=True), lengths[:2],
                     config.get("quantize") == "int8"),
                    ("router_in_bfloat16", dict(router_dtype="bfloat16"),
                     lengths[:2], False)]
        for name, control, at, decides in controls:
            for n in at:
                margin, mean, agreed, seconds = worst(n, alone[n], **control)
                over = margin > limit or mean > mean_limit
                if decides:
                    ok &= over
                say(prompt_tokens=n, control=name, margin_worst=margin,
                    margin_mean=mean, first_choices=agreed, limit=limit,
                    mean_limit=mean_limit, over=over, decides=decides,
                    reference_s=seconds)
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
