"""A serve cell, driver side: ``serve.run`` -> HTTP proxy -> handle ->
replica -> engine, every response streamed, load from this process.

The driver never imports jax: the chip belongs to the replica.
"""

from __future__ import annotations

import asyncio
import os
import random
import time
from typing import List

from . import client, families, stats, traffic
from .runtime import CellError, check_device, wait_gone

DEPLOYMENT = "llm"
PROBE_PROMPT, PROBE_ANSWER = 64, 16
# Margin of the reference check, in standard deviations of a position's
# reference logits. The reference multiplies the same int8 weights out in
# float32; the engine keeps activations in bf16 (8 bits of mantissa)
# through 32 layers, which moves a logit by a few hundredths of that
# deviation, so the token the engine picks can be the reference's second
# choice where the two lie that close, and is never far below its first.
# A wrong position, a wrong page or a dropped layer picks a token that the
# reference scores like any other: about 4 deviations below its largest
# of 32768. On the chip the worst of 64 probe tokens read 0.017 (PR 23).
# A family whose block this argument does not cover states its own
# ``MARGIN_LIMIT``, with its reason; this one is for those that state none.
MARGIN_LIMIT = 0.15


def _probe_requests(seed: int, vocab: int) -> List[traffic.Request]:
    rng = random.Random(seed ^ 0xC0FFEE)
    return [traffic.Request(
        index=i, due_s=0.0, counted=False,
        prompt_ids=tuple(rng.randrange(1, vocab)
                         for _ in range(PROBE_PROMPT)),
        max_tokens=PROBE_ANSWER, temperature=0.0)
        for i in range(2)]


def _check_probes(handle, url: str, seed: int, vocab: int,
                  limit: float) -> dict:
    """Two seeded prompts, greedy, by the handle and by HTTP; then the
    reference's verdict on every token either route returned."""
    import ray_tpu

    probes = _probe_requests(seed, vocab)
    completions = handle.options(method_name="completions")
    by_handle = [ray_tpu.get(completions.remote({
        "prompt_ids": list(p.prompt_ids), "temperature": 0.0,
        "max_tokens": p.max_tokens}), timeout=1200)["choices"][0]["token_ids"]
        for p in probes]
    by_http = [r["tokens"] for r in asyncio.run(
        client.sequential(url, probes, "probe"))]
    problems = []
    for route, answers in (("handle", by_handle), ("http", by_http)):
        for tokens in answers:
            if len(tokens) != PROBE_ANSWER or not all(
                    isinstance(t, int) and 0 <= t < vocab for t in tokens):
                problems.append(f"{route}: want {PROBE_ANSWER} ids in "
                                f"[0, {vocab}), got {tokens}")
    if problems:
        return {"ok": False, "problems": problems}
    out = ray_tpu.get(handle.options(method_name="bench_reference").remote({
        "prompts": [list(p.prompt_ids) for p in probes] * 2,
        "answers": by_handle + by_http}), timeout=1200)
    worst = max(max(row) for row in out["margins"])
    return {"ok": worst <= limit, "margin_worst": worst,
            "margin_limit": limit, "routes_agree": by_handle == by_http,
            "reference_s": out["seconds"],
            "problems": [] if worst <= limit else [
                f"a chosen token lies {worst:.3f} deviations below the "
                f"reference's first choice (limit {limit})"]}


def _warm_up(url: str, handle, engine: dict, mix: dict, seed: int,
             vocab: int) -> dict:
    """Every shape the mix can meet, one lone request each (see
    ``traffic.warmup_requests``). What it compiled or read from the
    persistent cache is noted; the mix-driven lead-in follows, and
    ``window_compiles`` tells whatever both missed."""
    import ray_tpu

    device = handle.options(method_name="bench_device")
    requests = traffic.warmup_requests(engine, mix, seed, vocab)
    t0 = time.perf_counter()
    before = ray_tpu.get(device.remote(), timeout=600)["compile_cache"]
    records = asyncio.run(client.sequential(url, requests, "warm"))
    bad = [r for r in records if not r["ok"]]
    if bad:
        raise CellError(f"warm-up request failed: {bad[0]['error']}")
    after = ray_tpu.get(device.remote(), timeout=600)["compile_cache"]
    return {"requests": len(requests), "seconds": time.perf_counter() - t0,
            "cache_hits": after["hits"] - before["hits"],
            "cache_misses": after["misses"] - before["misses"]}


def _traced_stretch(schedule: List[traffic.Request], mix: dict, seed: int,
                    seconds: float) -> dict:
    """Where the profiler opens in this seed's window, and what the
    schedule puts there (``notes.traced_stretch``)."""
    for_s = max(0.5, min(4.0, 0.3 * seconds))
    opens_s = traffic.traced_stretch(schedule, seconds, for_s)
    inside = traffic.due_in_middle(schedule, opens_s, for_s)
    entry = traffic.cycle_entry(mix, seed)
    return {"opens_s": opens_s, "for_s": for_s, "cycle_entry": entry,
            # of the first request due in the stretch's middle
            "cycle_position": (entry + inside[0].index)
            % int(mix["cycle_requests"]) if inside else None,
            # [index in the schedule, due_s, prompt tokens]
            "due_inside": [[r.index, r.due_s, r.prompt_tokens]
                           for r in inside]}


class ServeCell:
    """One deployment, held for one or more measured windows (a cell's
    run measures one; the rate sweep measures one per rate)."""

    def __init__(self, cell: dict, config: dict, mix: dict,
                 config_path: str, seed: int, scratch: str,
                 t_process: float):
        import ray_tpu
        from ray_tpu import serve

        from .llm_server import replica_class

        self.cell, self.config, self.mix = cell, config, mix
        self.seed, self.scratch, self.t_process = seed, scratch, t_process
        self.vocab = int(config["vocab_size"])
        # the driver's seeds pass 2**31; a jax key takes this
        self.handle = serve.run(serve.deployment(
            replica_class(config), name=DEPLOYMENT, num_replicas=1).bind(
                config_path, seed=seed % 2147483647))
        port = serve.start()
        self.url = f"http://127.0.0.1:{port}/{DEPLOYMENT}"
        self._device = self.handle.options(method_name="bench_device")
        self._window = self.handle.options(method_name="bench_window")
        self.device = ray_tpu.get(self._device.remote(), timeout=1500)
        check_device(self.device, int(cell["chips"]),
                     bool(config.get("rehearsal")))
        self.ready_s = time.time() - t_process
        self.probes = _check_probes(
            self.handle, self.url, seed, self.vocab,
            getattr(families.family_of(config), "MARGIN_LIMIT",
                    MARGIN_LIMIT))
        self.warm = _warm_up(self.url, self.handle, config["engine"], mix,
                             seed, self.vocab)

    def measure(self, seconds: float, trace: bool,
                rate_rps: float = None) -> dict:
        import ray_tpu

        cell, mix, seed, vocab = self.cell, self.mix, self.seed, self.vocab
        lead_in = float(cell.get("lead_in_s", 5))
        drain = float(cell.get("drain_s", 20))
        schedule = traffic.open_loop_schedule(
            mix, float(rate_rps or cell["rate_rps"]), seconds, lead_in,
            drain, seed, vocab)
        start_payload, stretch = {"op": "start"}, None
        if trace:
            stretch = _traced_stretch(schedule, mix, seed, seconds)
            # the client sends the schedule's first request at once, so
            # the window opens that request's lead after the start call:
            # up to a gap of the cycle under ``lead_in`` (5 s in the
            # sparsest cell), which a stretch placed to the second may
            # not be late by
            start_payload.update(
                trace_dir=os.path.join(self.scratch, "trace"),
                trace_after_s=stretch["opens_s"] - min(0.0,
                                                       schedule[0].due_s),
                trace_for_s=stretch["for_s"])
        t_a = time.perf_counter()
        replica_t = ray_tpu.get(self._window.remote(start_payload),
                                timeout=60)["t"]
        t_b = time.perf_counter()
        driven = asyncio.run(client.open_loop(
            self.url, schedule, seconds, drain, "w"))
        # the window opened at driven["t0"] on the perf_counter clock
        setup_s = (time.time() - (time.perf_counter() - driven["t0"])
                   - self.t_process)
        watched = ray_tpu.get(self._window.remote({"op": "stop"}),
                              timeout=600)
        self.device = ray_tpu.get(self._device.remote(), timeout=60)

        # replica clock -> seconds after the window opened (driver clock);
        # the two perf_counters differ by a constant, read at the start call
        offset = replica_t - 0.5 * (t_a + t_b) + driven["t0"]

        def to_window(t: float) -> float:
            return t - offset

        engine = {
            "finished": [{"rid": rid, "arrival": to_window(a),
                          "first": to_window(f) if f else None,
                          "done": to_window(d), "tokens": n,
                          "prompt_tokens": p}
                         for rid, a, f, d, n, p in watched["finished"]],
            "rounds": [{"t": to_window(t), "width": w, "active": n,
                        "live": live}
                       for t, w, n, live in watched["rounds"]],
            "occupancy": [{"t": to_window(t), "running": r, "compiles": c}
                          for t, r, c in watched["occupancy"]],
            "max_num_seqs": watched["max_num_seqs"],
        }
        traced = dict(watched.get("trace") or {})
        if traced:
            traced["t0"], traced["t1"] = (to_window(traced["t0"]),
                                          to_window(traced["t1"]))
        records = driven["records"]
        counted = [r for r in records if r["counted"]]
        failed = [r for r in counted if not r["ok"]
                  or len(r["tokens"]) != r["asked"]]
        bad_ids = [r for r in counted if r["ok"] and not all(
            isinstance(t, int) and 0 <= t < vocab for t in r["tokens"])]
        return {
            "kind": "serve", "window_s": seconds, "setup_s": setup_s,
            "client": records, "engine": engine, "trace": traced,
            "device": self.device, "attempted": len(counted),
            "failed": len(failed),
            "correct": bool(self.probes["ok"] and not bad_ids),
            "notes": {"probes": self.probes, "warm_up": self.warm,
                      "ready_s": self.ready_s,
                      "run_ended_s": driven["ended"],
                      "first_failures": [r["error"] for r in failed[:3]],
                      **({"traced_stretch": stretch} if stretch else {})},
        }

    def close(self) -> float:
        from ray_tpu import serve

        serve.shutdown()
        return wait_gone([self.device["pid"]])


def run(cell: dict, config: dict, mix: dict, config_path: str, seed: int,
        seconds: float, trace: bool, scratch: str, t_process: float) -> dict:
    serving = ServeCell(cell, config, mix, config_path, seed, scratch,
                        t_process)
    try:
        out = serving.measure(seconds, trace)
    finally:
        out_exit = serving.close()
    out["notes"]["replica_exit_s"] = out_exit
    return out


def end_to_end(run: dict) -> dict:
    """The serve cells' end-to-end metrics, from the client's records."""
    counted = [r for r in run["client"] if r["counted"]]
    window_s, ended = run["window_s"], run["notes"]["run_ended_s"]
    # a request that never showed a token has waited from when it was due
    # to when the run gave up: it counts with that, the worst it can be
    ttft = [(r["first"] if r["first"] is not None else ended) - r["due"]
            for r in counted]
    tpot = [(r["last"] - r["first"]) / (len(r["tokens"]) - 1)
            for r in counted if r["ok"] and len(r["tokens"]) > 1
            and r["last"] > r["first"]]
    # every token that reached the client inside the window, whichever
    # request it belongs to (the lead-in's last, the window's own): all the
    # work of the window over all its time, with no request cut at an edge
    streamed_inside = sum(0 <= t <= window_s for r in run["client"]
                          for t in r["times"])
    return {
        "ttft_p95_ms": (1e3 * stats.percentile(ttft, 0.95), "ms"),
        "tpot_p95_ms": (1e3 * stats.percentile(tpot, 0.95), "ms")
        if tpot else None,
        "serve_tok_s": (streamed_inside / window_s, "tokens/s"),
        "setup_s": (run["setup_s"], "s"),
    }
