"""The serving round and the train step on the profiler's clock: the one
span primitive (util/tracing.span), the fixed ``rt.*`` names the
benchmark's readers match on, and the engine's always-on request clocks
and round counters (ISSUE 24). Traces are read with ``ProfileData``, as
``benchmarks/harness/trace.py`` reads the chip's."""

import asyncio
import collections
import dataclasses
import glob
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.llm import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.llm.engine import PHASES
from ray_tpu.models import LLAMA_CONFIGS, init_params
from ray_tpu.train.telemetry import StepTimeline
from ray_tpu.util import tracing

CFG = LLAMA_CONFIGS["tiny"]
# what a plain round opens; ``decode.draft`` is a speculative round's
ENGINE_SPANS = ["rt.engine." + phase for phase in PHASES
                if phase != "decode.draft"]
PUMP_SPANS = ["rt.pump.fanout", "rt.pump.idle", "rt.pump.lull"]
PROMPTS = [[5, 17, 99, 3], [7, 8, 9, 10, 11, 12], [1, 2, 3]]
IDLE_S = 0.3    # a served replica left without a request: six lull pieces


@pytest.fixture(scope="module")
def tiny_params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _host_events(trace_dir):
    """``[(thread, name, start_ns, end_ns)]`` of the host plane."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events.extend((line.name, e.name, e.start_ns,
                           e.start_ns + e.duration_ns)
                          for e in line.events)
    return events


def _trace(trace_dir, work):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    return _host_events(str(trace_dir))


def _watch_burst_width(engine):
    """Count ``_burst_width`` calls the way the benchmark's replica
    does: by replacing the method on the instance."""
    calls = []
    inner = engine._burst_width

    def watched():
        calls.append(inner())
        return calls[-1]

    engine._burst_width = watched
    return calls


@pytest.fixture(scope="module")
def served(tiny_params):
    """A few requests of unequal length through one engine: the finished
    states, the counters and the widths ``_burst_width`` returned."""
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64,
        decode_burst=4))
    calls = _watch_burst_width(engine)
    ids = [engine.add_request(p, SamplingParams(temperature=0.0,
                                                max_tokens=3 + 2 * i))
           for i, p in enumerate(PROMPTS)]
    while engine.has_unfinished():
        engine.step()
    return ([engine.requests[rid] for rid in ids],
            engine.stats()["counters"], calls)


def test_request_clocks_are_ordered(served):
    states, _counters, _calls = served
    for s in states:
        assert s.finished and s.preemptions == 0
        assert 0.0 < s.arrival_t <= s.admit_t <= s.prefill_start_t \
            <= s.first_token_t
    # two slots, three requests: the third waited for a slot
    assert states[2].admit_t >= states[0].first_token_t


def test_round_counters(served):
    states, c, calls = served
    assert c["rounds"] == len(calls) > 0
    assert c["decode_steps"] == sum(calls)
    assert len(c["width_hist"]) == 4 + 1
    assert sum(c["width_hist"]) == c["rounds"]
    assert sum(i * n for i, n in enumerate(c["width_hist"])) \
        == c["decode_steps"]
    # every token but a request's first comes from a decode step
    assert c["active_slot_steps"] >= sum(len(s.output) - 1 for s in states)
    assert c["prefills"] == len(PROMPTS)
    assert c["prefill_tokens"] == sum(len(p) for p in PROMPTS)
    assert c["preemptions"] == 0
    assert list(c["host_s"]) == list(PHASES)
    assert all(v >= 0.0 for v in c["host_s"].values())
    # a dispatch is two phases: what the host builds, what it hands over
    for half in ("prefill.build", "prefill.dispatch", "decode.build",
                 "decode.dispatch"):
        assert half in PHASES and c["host_s"][half] > 0.0


@pytest.mark.parametrize("chunk, rows", [
    # whole prompts of 4, 6 and 3 tokens, each in the smallest bucket;
    # chunks of 4: one, two and one
    (0, 3 * 16), (4, 4 * 4)], ids=["whole-prompt", "chunked"])
def test_prefill_counts_the_rows_it_padded_to(tiny_params, chunk, rows):
    """``prefill_bucket_tokens`` beside ``prefill_tokens``: their ratio
    is the share of prefill's rows that are tokens."""
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64,
        decode_burst=4, prefill_chunk=chunk))
    for p in PROMPTS:
        engine.add_request(p, SamplingParams(temperature=0.0, max_tokens=2))
    while engine.has_unfinished():
        engine.step()
    c = engine.stats()["counters"]
    assert c["prefill_tokens"] == sum(len(p) for p in PROMPTS)
    assert c["prefill_bucket_tokens"] == rows


@pytest.mark.parametrize("slots, num_pages, prompts, rounds, bucket", [
    # (prompt tokens, max_tokens) a request; a round: the old context of
    # each decoding slot, whose pages of 4 the burst lists
    (2, 13, [(5, 6), (9, 3)], [[5], [9, 9], [10]], 12),
    (8, 64, [(18, 6), (21, 3)], [[18], [22, 21], [22]], 16)],
    ids=["two-slots-short-small-pool", "eight-slots-longer"])
def test_gather_counters_by_hand(tiny_params, slots, num_pages, prompts,
                                 rounds, bucket):
    """``live_pages``, ``gathered_pages`` and ``gather_hist`` of a
    scripted run, counted by hand. Whole-prompt mode prefills one
    request a step and decodes after it: step 1 prefills A and runs a
    burst of 4 (A wants 5 more), step 2 prefills B and runs a burst of
    1 (A wants 1 more), step 3 runs B's last step alone. A round lists
    the pages below each decoding slot's context in one flat list, at
    the smallest bucket the engine has for that many (``_flat_bucket``:
    16, or all the engine can list where that is fewer)."""
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=slots, page_size=4, num_pages=num_pages,
        max_seq_len=32, decode_burst=4))
    calls = _watch_burst_width(engine)
    for i, (n, max_tokens) in enumerate(prompts):
        engine.add_request(list(range(1 + i, 1 + i + n)), SamplingParams(
            temperature=0.0, max_tokens=max_tokens))
    while engine.has_unfinished():
        engine.step()
    c = engine.stats()["counters"]
    assert calls == [4, 1, 1] and c["rounds"] == len(rounds)
    held = [[-(-ctx // 4) for ctx in round_] for round_ in rounds]
    assert c["live_pages"] == sum(map(sum, held))
    assert c["gather_hist"] == {bucket: len(rounds)}
    assert c["gathered_pages"] == bucket * len(rounds) >= c["live_pages"]
    assert engine.decode_buckets()[0] == bucket


def test_a_loaded_engine_compiles_no_decode_program(tiny_params):
    """``load_decode_programs`` (what ``LLMServer`` calls before it is
    ready) runs every bucket a page list can take, with no slot active:
    no page changes, no counter moves but its own two (``loaded_programs``
    and ``load_s``, which only the two loaders move), ``_burst_width`` is
    not called;
    and a mixed run after it (contexts of 3 to 40 tokens, 1 to 3 slots
    decoding, bursts of 1 to 4) adds no entry to ``decode_burst``'s
    compile cache: a width is an operand, a bucket is loaded. Three
    slots of 12 pages: powers of two from 16, and the 36 they hold."""
    import numpy as np

    from ray_tpu.llm.runner import decode_burst

    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=3, page_size=4, num_pages=48, max_seq_len=48,
        decode_burst=4))
    calls = _watch_burst_width(engine)
    before = (np.asarray(engine.cache.k), engine.stats()["counters"])
    buckets = engine.decode_buckets()
    assert buckets == [16, 32, 36]
    assert {engine._flat_bucket(n) for n in range(1, 37)} == set(buckets)
    assert (before[1]["loaded_programs"], before[1]["load_s"]) == (0, 0.0)
    assert engine.load_decode_programs() == len(buckets)
    loaded = engine.stats()["counters"]
    assert loaded["loaded_programs"] == len(buckets)
    assert isinstance(loaded["load_s"], float) and loaded["load_s"] > 0.0
    own = {"loaded_programs": 0, "load_s": 0.0}
    assert not calls and {**loaded, **own} == before[1]
    np.testing.assert_array_equal(np.asarray(engine.cache.k), before[0])
    size = decode_burst._cache_size()
    for i, n in enumerate([40, 38, 36, 3, 17, 9, 5, 24]):
        engine.add_request([1 + (i + j) % 200 for j in range(n)],
                           SamplingParams(temperature=0.0,
                                          max_tokens=8 - i % 7))
    while engine.has_unfinished():
        engine.step()
    c = engine.stats()["counters"]
    assert c["rounds"] == len(calls) > 3 and len(set(calls)) > 1
    assert decode_burst._cache_size() == size
    # serving moved neither; a second load adds to both
    assert (c["loaded_programs"], c["load_s"]) == (
        len(buckets), loaded["load_s"])
    engine.load_decode_programs()
    c = engine.stats()["counters"]
    assert c["loaded_programs"] == 2 * len(buckets)
    assert c["load_s"] > loaded["load_s"]
    assert set(c["gather_hist"]) <= set(buckets)
    assert len(c["gather_hist"]) > 1


@pytest.mark.parametrize("pool, loads", [
    (None, 1), ("decode", 1), ("prefill", 0)])
def test_a_replica_loads_its_decode_programs_with_its_role(pool, loads):
    """``serve``'s ``Replica`` calls ``configure_pool`` in its
    constructor, so before the replica reports ready: a replica that
    decodes (no pools, or the decode pool) loads every decode program
    there, once; a prefill replica, which never runs a decode round,
    loads none."""
    from ray_tpu.llm.serve import LLMServer

    server = LLMServer("tiny", engine_config={
        "max_num_seqs": 2, "page_size": 4, "num_pages": 32,
        "max_seq_len": 32, "decode_burst": 4})
    calls = []
    load = server.engine.load_decode_programs
    server.engine.load_decode_programs = lambda: calls.append(load())
    server.configure_pool(pool, "llm")
    assert len(calls) == loads
    assert calls == [len(server.engine.decode_buckets())] * loads


def test_counters_are_a_copy(served, tiny_params):
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=1, page_size=4, num_pages=32, max_seq_len=32))
    snapshot = engine.stats()["counters"]
    snapshot["width_hist"][1] = 99
    snapshot["host_s"]["append"] = 99.0
    snapshot["gather_hist"][16] = 99
    fresh = engine.stats()["counters"]
    assert fresh["width_hist"][1] == 0 and fresh["host_s"]["append"] == 0.0
    assert fresh["gather_hist"] == {}
    # the keys the harness and the pump read stay where they were
    assert {"running", "waiting", "free_pages", "total_pages",
            "counters"} <= set(engine.stats())


# a configuration, and the tiles of its expert products at a prefill
# bucket of 16 rows and a decode step of 2 slots, by top_k rows each
EXPERT_TILES = {
    "dense": (CFG, {}),
    # widths 128 does not divide: lax.ragged_dot runs the products
    "routed-64x128": (dataclasses.replace(CFG, n_experts=4, top_k=2), {
        "32x64x128:float32": [], "32x128x64:float32": [],
        "4x64x128:float32": [], "4x128x64:float32": []}),
    # lane-aligned widths: whole-width tiles for whole row tiles; a
    # decode step's 4 rows are no multiple of 16
    "routed-128x256": (dataclasses.replace(
        CFG, n_experts=4, top_k=2, dim=128, mlp_dim=256), {
        "32x128x256:float32": [32, 128, 256],
        "32x256x128:float32": [32, 256, 128],
        "4x128x256:float32": [], "4x256x128:float32": []}),
}


@pytest.mark.parametrize("cfg, tiles", EXPERT_TILES.values(),
                         ids=EXPERT_TILES)
def test_expert_tiles_list_the_product_shapes_of_a_routed_configuration(
        cfg, tiles):
    """``ops/moe.py`` records a product's tiles where it chooses them (at
    trace time, whichever path the platform lowers), and the engine lists
    those of its own configuration's widths."""
    engine = LLMEngine(init_params(jax.random.PRNGKey(0), cfg), cfg,
                       EngineConfig(max_num_seqs=2, page_size=4,
                                    num_pages=32, max_seq_len=32,
                                    decode_burst=2))
    assert engine.generate([[5, 17, 99, 3]], SamplingParams(
        temperature=0.0, max_tokens=3))
    listed = engine.stats()["counters"]["expert_tiles"]
    # the record is the process's: an engine of the same widths earlier
    # in this process may have left other row counts beside these
    assert tiles.items() <= listed.items()
    assert bool(listed) == bool(cfg.n_experts)
    widths = {f"{cfg.dim}x{cfg.mlp_dim}", f"{cfg.mlp_dim}x{cfg.dim}"}
    assert all(key.split(":")[0].split("x", 1)[1] in widths
               for key in listed)
    # a copy of the record
    listed["x"] = [1]
    assert "x" not in engine.stats()["counters"]["expert_tiles"]


def test_forced_preemption_is_counted(tiny_params):
    """Six usable pages of four tokens: two prompts fit, their answers
    do not, so the younger is preempted and prefilled again."""
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=7, max_seq_len=24,
        decode_burst=2))
    ids = [engine.add_request(p, SamplingParams(temperature=0.0,
                                                max_tokens=12))
           for p in ([1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12])]
    while engine.has_unfinished():
        engine.step()
    states = [engine.requests[rid] for rid in ids]
    counters = engine.stats()["counters"]
    assert all(s.finished and len(s.output) == 12 for s in states)
    assert counters["preemptions"] == sum(s.preemptions for s in states) > 0
    assert states[1].preemptions > 0        # the younger is the victim
    assert counters["prefills"] == 2 + counters["preemptions"]
    for s in states:    # the clocks keep their first reading
        assert s.arrival_t <= s.admit_t <= s.prefill_start_t \
            <= s.first_token_t


@pytest.fixture(scope="module")
def engine_trace(tiny_params, tmp_path_factory):
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64))
    for p in PROMPTS:       # compile outside the trace
        engine.add_request(p, SamplingParams(temperature=0.0, max_tokens=4))
    while engine.has_unfinished():
        engine.step()

    rounds = _watch_burst_width(engine)
    warm_up = engine.stats()["counters"]["prefills"]

    def work():
        for p in PROMPTS:
            engine.add_request(p, SamplingParams(temperature=0.0,
                                                 max_tokens=4))
        while engine.has_unfinished():
            engine.step()

    events = _trace(tmp_path_factory.mktemp("engine_trace"), work)
    return (events, engine.stats()["counters"]["prefills"] - warm_up,
            len(rounds))


def test_loading_the_decode_programs_is_one_span(tiny_params, tmp_path):
    """``rt.engine.load`` is a span on the profiler's clock, one a call
    of ``load_decode_programs``, and ``load_s`` is its length; serving
    requests opens none."""
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64))

    def work():
        engine.load_decode_programs()
        engine.add_request(PROMPTS[0], SamplingParams(temperature=0.0,
                                                      max_tokens=4))
        while engine.has_unfinished():
            engine.step()

    events = _trace(tmp_path, work)
    loads = [e for e in events if e[1] == "rt.engine.load"]
    assert len(loads) == 1
    assert (loads[0][3] - loads[0][2]) / 1e9 == pytest.approx(
        engine.stats()["counters"]["load_s"], abs=0.05)


@pytest.mark.parametrize("name", ENGINE_SPANS)
def test_engine_phase_is_in_the_profiler_trace(engine_trace, name):
    events, _prefills, _rounds = engine_trace
    assert any(e[1] == name for e in events)


def test_a_dispatch_opens_each_of_its_halves_once(engine_trace):
    """``build`` and ``dispatch`` once a decode round and once a prefill:
    a name that opened twice a round would say nothing about which half
    the device waited for."""
    events, prefills, rounds = engine_trace
    count = collections.Counter(e[1] for e in events)
    assert rounds > 0 and prefills == len(PROMPTS)
    for phase in ("build", "dispatch", "sync"):
        assert count["rt.engine.decode." + phase] == rounds, phase
        assert count["rt.engine.prefill." + phase] == prefills, phase


@pytest.mark.parametrize("options, rounds_of", [
    (dict(prefill_chunk=4), lambda engine, c: c["rounds"]),
    (dict(speculation={"draft_config": "tiny", "num_draft_tokens": 3,
                       "draft_seed": 0}),
     # a verify a speculative round, a burst where no slot could draft
     lambda engine, c: len(engine.spec.verify_times) + c["rounds"])],
    ids=["chunked-prefill", "speculative-decode"])
def test_the_other_dispatch_paths_split_the_same_way(tiny_params, options,
                                                     rounds_of):
    """``_run_prefill_chunk`` and ``_run_spec_decode`` open the same four
    names as the whole-prompt prefill and the burst: ``decode.dispatch``
    once a round (a burst's or a verify's), ``prefill.dispatch`` once a
    chunk and once more where the last chunk's logits are sampled. The
    drafter's calls of the device are ``decode.draft``'s, once a
    speculative round, so that ``build`` is the host's work alone."""
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64,
        **options))
    opened = collections.Counter()
    phase = engine._phase
    engine._phase = lambda name: opened.update([name]) or phase(name)
    assert engine.generate(PROMPTS, SamplingParams(temperature=0.0,
                                                   max_tokens=6))
    c = engine.stats()["counters"]
    assert set(opened) <= set(PHASES)
    assert opened["decode.dispatch"] == opened["decode.sync"] \
        == rounds_of(engine, c) > 0
    chunked = bool(options.get("prefill_chunk"))
    if chunked:     # a round of slots that are all mid-prompt builds nothing
        assert opened["decode.build"] >= opened["decode.dispatch"]
        assert "decode.draft" not in opened
    else:
        assert opened["decode.build"] == opened["decode.dispatch"]
        assert opened["decode.draft"] == len(engine.spec.verify_times) > 0
    assert opened["prefill.dispatch"] == opened["prefill.build"] \
        == c["prefills"] + chunked * len(PROMPTS)
    assert all(c["host_s"][name] > 0.0 for name in opened)


def test_no_span_encloses_a_round(engine_trace):
    """The phases are siblings: every ``rt.`` event is one of ``PHASES``,
    and none of them holds another (a parent over the round would take
    the name of every device idle gap from its children)."""
    ours = sorted((e for e in engine_trace[0] if e[1].startswith("rt.")),
                  key=lambda e: e[2])
    assert {e[1] for e in ours} == set(ENGINE_SPANS)
    for (_, a, _, a_end), (_, b, b_start, _) in zip(ours, ours[1:]):
        assert a_end <= b_start, f"{a} overlaps {b}"


def _tiny_server(**engine_config):
    from ray_tpu.llm.serve import LLMServer

    return LLMServer("tiny", engine_config={
        "max_num_seqs": 2, "page_size": 4, "num_pages": 64,
        "max_seq_len": 64, **engine_config})


@pytest.fixture(scope="module")
def pump_trace(tmp_path_factory):
    server = _tiny_server()
    states = []
    observe = server._observe_finished

    def keep(state, now):
        states.append(state)
        observe(state, now)

    server._observe_finished = keep

    async def serve_some():
        # the replica sits without a request before the burst and after
        await server.check_health()
        await asyncio.sleep(IDLE_S)
        real_step = server.engine.step
        # one empty round, so that the pump idles once under the trace
        server.engine.step = lambda **kw: (
            setattr(server.engine, "step", real_step) or [])
        await asyncio.gather(*[server.completions(
            {"prompt_ids": p, "temperature": 0.0, "max_tokens": 4})
            for p in PROMPTS])
        await asyncio.sleep(IDLE_S)
        return await server.stats()

    asyncio.run(serve_some())       # compile outside the trace
    states.clear()
    stats = {}
    events = _trace(tmp_path_factory.mktemp("pump_trace"),
                    lambda: stats.update(asyncio.run(serve_some())))
    return events, states, stats


@pytest.mark.parametrize("name", PUMP_SPANS)
def test_pump_phase_is_in_the_profiler_trace(pump_trace, name):
    events, _states, _stats = pump_trace
    assert any(e[1] == name for e in events)
    assert {e[1] for e in events if e[1].startswith("rt.")} \
        <= set(ENGINE_SPANS + PUMP_SPANS)


def test_a_lull_is_cut_into_pieces_and_ends_before_the_round(pump_trace):
    """The wait for a request is many short annotations (one that was
    open when a profiler started would be lost to it), and none is open
    while the engine has a request: by the order of events, every piece
    lies wholly before or wholly behind the burst's rounds, and the last
    piece before them ends before their first ``rt.engine.schedule``."""
    from ray_tpu.llm import serve

    events, _states, stats = pump_trace
    pieces = sorted((e[2], e[3]) for e in events if e[1] == "rt.pump.lull")
    rounds = [(e[2], e[3]) for e in events
              if e[1].startswith("rt.engine.") or e[1] == "rt.pump.fanout"]
    # the bound is the code's own; the slack is for a loaded CPU, which
    # can only wake the loop late
    assert serve.LULL_PIECE_S <= 0.05
    assert all(end - start <= (serve.LULL_PIECE_S + 0.5) * 1e9
               for start, end in pieces)
    first, last = min(s for s, _ in rounds), max(e for _, e in rounds)
    before = [p for p in pieces if p[1] <= first]
    behind = [p for p in pieces if p[0] >= last]
    assert len(before) >= 2 and len(behind) >= 2        # cut, both times
    assert len(before) + len(behind) == len(pieces)     # none overlaps
    schedule = min(e[2] for e in events if e[1] == "rt.engine.schedule")
    assert before[-1][1] <= schedule
    # two waits in each of the fixture's two runs, however many pieces
    assert stats["pump"]["lulls"] == 4 < len(pieces) + 2


def test_pump_stamps_the_hand_over_and_passes_counters(pump_trace):
    _events, states, stats = pump_trace
    assert len(states) == len(PROMPTS)
    for s in states:    # the first token leaves after the round it came in
        assert s.first_token_t <= s.emit_t
    assert stats["pool"] == "mono"
    assert stats["counters"]["prefills"] == 2 * len(PROMPTS)


def test_lull_seconds_grow_while_idle_and_stand_still_in_flight():
    """``stats()["pump"]``: ``lull_s`` is fed by the pieces' own seconds,
    so it grows while the replica sits without a request and does not
    move while one is in flight; ``lulls`` counts waits, not pieces."""
    from ray_tpu.llm import serve

    server = _tiny_server(decode_burst=1)   # a round a token: 8 slow steps

    async def go():
        await server.completions(       # compile, and start the pump
            {"prompt_ids": PROMPTS[0], "temperature": 0.0, "max_tokens": 8})
        idle = [await server.stats()]
        await asyncio.sleep(IDLE_S)
        idle.append(await server.stats())
        step = server.engine.step

        def slow_step(**kw):
            time.sleep(0.03)
            return step(**kw)

        server.engine.step = slow_step
        request = asyncio.ensure_future(server.completions(
            {"prompt_ids": PROMPTS[1], "temperature": 0.0,
             "max_tokens": 8}))
        await asyncio.sleep(0.02)
        flight = [await server.stats()]
        await asyncio.sleep(0.04)
        flight.append(await server.stats())
        assert server.engine.has_unfinished()
        await request
        await asyncio.sleep(IDLE_S)
        return idle, flight, await server.stats()

    idle, flight, after = asyncio.run(go())
    (a, b), (c, d) = ([s["pump"] for s in pair] for pair in (idle, flight))
    assert a["lulls"] == b["lulls"] == 1
    assert b["lull_s"] - a["lull_s"] >= 2 * serve.LULL_PIECE_S
    assert c["lull_s"] == d["lull_s"] >= b["lull_s"]
    assert c["lulls"] == d["lulls"] == 1
    assert after["pump"]["lulls"] == 2
    assert after["pump"]["lull_s"] - d["lull_s"] >= 2 * serve.LULL_PIECE_S
    assert set(after["pump"]) == {"lull_s", "lulls"}


@pytest.mark.parametrize("how", ["made-on-the-loop", "configure_pool",
                                 "configure_pool-prefill", "check_health"])
def test_a_replica_that_never_got_a_request_marks_its_lulls(how):
    """From its construction or ``configure_pool``'s end where a loop
    runs there, else from the first call that reaches the loop (the
    controller's health probe): no request is needed to start the pump,
    and a prefill-pool replica marks lulls like any other."""
    server = None if how == "made-on-the-loop" else _tiny_server()

    async def go():
        replica = server or _tiny_server()
        if how.startswith("configure_pool"):
            replica.configure_pool(
                "prefill" if how.endswith("prefill") else None, "llm")
        elif how == "check_health":
            assert replica._pump_task is None   # no loop ran where it was made
            await replica.check_health()
        await asyncio.sleep(IDLE_S)
        assert not replica._pump_task.done()
        return (await replica.stats())["pump"]

    pump = asyncio.run(go())
    assert pump["lulls"] == 1 and pump["lull_s"] > 0.0


def test_a_lull_is_one_record_in_the_sink(tmp_path, monkeypatch):
    """With RAY_TPU_TRACING=1 every span writes a lane record; twenty a
    second from every idle replica would bury the ``pump`` lane, so the
    pieces are annotations only and the lull's end writes one record."""
    monkeypatch.setenv("RAY_TPU_TRACING", "1")
    monkeypatch.setattr(tracing, "_sink", None)
    monkeypatch.setattr(tracing, "_span_dir", lambda: str(tmp_path))
    with tracing.piece_span("rt.pump.lull") as piece:
        pass
    assert piece.seconds >= 0.0 and tracing._sink is None
    server = _tiny_server()

    async def go():
        await server.completions(
            {"prompt_ids": PROMPTS[0], "temperature": 0.0, "max_tokens": 2})
        await asyncio.sleep(IDLE_S)     # the loop's end ends the lull

    asyncio.run(go())
    tracing._sink.close()
    (path,) = glob.glob(str(tmp_path / "spans-*.jsonl"))
    with open(path) as f:
        pump = [r for r in map(json.loads, f) if r["lane"] == "pump"]
    (lull,) = [r for r in pump if r["name"] == "rt.pump.lull"]
    assert lull["end"] - lull["start"] >= IDLE_S / 2
    assert lull["start"] >= max(r["end"] for r in pump
                                if r["name"] == "rt.pump.fanout") - 1e-3


def test_an_arrival_ends_the_lull_at_once():
    """The wait is on a future the arrival resolves, not on a poll: a
    request that arrives in a lull is in a round on the loop's next
    turns, far inside one piece."""
    from ray_tpu.llm import serve

    server = _tiny_server()

    async def go():
        await server.completions(       # compile
            {"prompt_ids": PROMPTS[0], "temperature": 0.0, "max_tokens": 2})
        waits = []
        for _ in range(5):
            await asyncio.sleep(serve.LULL_PIECE_S * 0.4)   # mid-piece
            rid, queue = await server._submit(
                PROMPTS[0], SamplingParams(temperature=0.0, max_tokens=2))
            state = server.engine.requests[rid]
            while not (await queue.get()).finished:
                pass
            waits.append(state.admit_t - state.arrival_t)
        return waits

    waits = asyncio.run(go())
    # a poll would admit after the rest of the piece (30 ms) every time
    assert sorted(waits)[len(waits) // 2] < serve.LULL_PIECE_S * 0.4


def test_finished_requests_feed_metrics_and_the_llm_lane(
        tmp_path, monkeypatch):
    """A replica short of pages, with RAY_TPU_TRACING=1: queue wait and
    preemptions reach the operator's metrics, and every request leaves
    its three intervals on the ``llm`` lane under its own id, beside the
    round's phases on the ``engine`` and ``pump`` lanes."""
    from ray_tpu.llm.serve import LLMServer
    from ray_tpu.util.metrics import snapshot_local

    monkeypatch.setenv("RAY_TPU_TRACING", "1")
    monkeypatch.setattr(tracing, "_sink", None)
    monkeypatch.setattr(tracing, "_span_dir", lambda: str(tmp_path))
    server = LLMServer("tiny", engine_config={
        "max_num_seqs": 2, "page_size": 4, "num_pages": 7,
        "max_seq_len": 24, "decode_burst": 2})
    before = snapshot_local("llm_")

    async def go():
        return await asyncio.gather(*[server.completions(
            {"prompt_ids": p, "temperature": 0.0, "max_tokens": 12})
            for p in ([1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12])])

    outs = asyncio.run(go())
    assert all(len(o["choices"][0]["token_ids"]) == 12 for o in outs)
    after = snapshot_local("llm_")
    wait = "llm_queue_wait_seconds{__stat__=count,model=tiny,pool=mono}"
    assert after.get(wait, 0.0) - before.get(wait, 0.0) == 2, after
    preempted = "llm_preemptions_total{model=tiny,pool=mono}"
    assert after.get(preempted, 0.0) - before.get(preempted, 0.0) >= 1

    tracing._sink.close()
    (path,) = glob.glob(str(tmp_path / "spans-*.jsonl"))
    with open(path) as f:
        records = [json.loads(line) for line in f]
    by_lane = {}
    for r in records:
        by_lane.setdefault(r["lane"], []).append(r)
    assert {"llm", "engine", "pump"} <= set(by_lane)
    ids = {r["args"]["request_id"] for r in by_lane["llm"]}
    assert len(ids) == 2
    for rid in ids:
        mine = {r["name"]: r for r in by_lane["llm"]
                if r["args"]["request_id"] == rid}
        assert set(mine) == {"queue", "prefill", "decode"}
        assert mine["queue"]["end"] == pytest.approx(
            mine["prefill"]["start"])
        assert mine["prefill"]["end"] == pytest.approx(
            mine["decode"]["start"])
    assert {r["name"] for r in by_lane["engine"]} == set(
        ENGINE_SPANS + ["rt.engine.round"])
    assert "rt.pump.fanout" in {r["name"] for r in by_lane["pump"]}


def test_span_off_writes_nothing(tmp_path, monkeypatch):
    monkeypatch.delenv("RAY_TPU_TRACING", raising=False)
    monkeypatch.setattr(tracing, "_sink", None)
    monkeypatch.setattr(tracing, "_span_dir", lambda: str(tmp_path))
    with tracing.span("rt.engine.schedule") as sp:
        pass
    with tracing.step_span("rt.train.step", 3):
        pass
    assert sp.seconds >= 0.0
    assert os.listdir(tmp_path) == []


def test_span_on_writes_a_lane_record(tmp_path, monkeypatch):
    monkeypatch.setenv("RAY_TPU_TRACING", "1")
    monkeypatch.setattr(tracing, "_sink", None)
    monkeypatch.setattr(tracing, "_span_dir", lambda: str(tmp_path))
    with tracing.span("rt.engine.prefill.sync", request_id="abc"):
        pass
    with tracing.step_span("rt.train.step", 7):
        pass
    tracing._sink.close()
    (path,) = glob.glob(str(tmp_path / "spans-*.jsonl"))
    with open(path) as f:
        first, second = [json.loads(line) for line in f]
    assert (first["kind"], first["lane"], first["name"]) == (
        "lane", "engine", "rt.engine.prefill.sync")
    assert first["args"] == {"request_id": "abc"}
    assert first["start"] <= first["end"]
    assert (second["lane"], second["name"], second["args"]) == (
        "train", "rt.train.step", {"step": 7})


def test_tracing_module_does_not_import_jax():
    code = ("import sys; import ray_tpu.util.tracing as t; "
            "import ray_tpu.train.telemetry; "
            "s = t.span('rt.engine.append'); s.__enter__(); "
            "s.__exit__(None, None, None); "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    assert subprocess.run([sys.executable, "-c", code],
                          timeout=120).returncode == 0


@pytest.mark.parametrize("phase", ["data_wait", "collective_sync",
                                   "my_user_phase"])
def test_timeline_phases_are_train_spans(tmp_path, phase):
    """Canonical and user phases alike leave ``rt.train.<name>`` in the
    trace, a nested one inside its parent."""
    timeline = StepTimeline()
    timeline.step = 5

    def work():
        with timeline.phase("checkpoint_save"):
            with timeline.phase(phase):
                pass

    events = [e for e in _trace(tmp_path, work) if e[1].startswith("rt.")]
    assert sorted(e[1] for e in events) == sorted(
        ["rt.train.checkpoint_save", "rt.train." + phase])
    outer = next(e for e in events if e[1] == "rt.train.checkpoint_save")
    inner = next(e for e in events if e[1] == "rt.train." + phase)
    assert outer[2] <= inner[2] and inner[3] <= outer[3]
    _start, _end, phases, _intervals = timeline.close()
    assert set(phases) >= {"checkpoint_save"}


# --- latent attention and the chip's share of the experts: spans in the
# programs, counters in stats() (readers: benchmarks/layer_metrics/
# mla_decode_roofline.py, mla_prefill_roofline.py) ---
def _latent_engine():
    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(
        vocab=128, dim=64, n_layers=3, n_heads=4, n_kv_heads=4, mlp_dim=32,
        max_seq=256, dtype=jnp.float32, remat=False, rope_theta=10000.0,
        n_experts=8, top_k=3, norm_topk_prob=False,
        q_lora_rank=24, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, n_dense_layers=1, dense_mlp_dim=96,
        n_shared_experts=2, n_group=4, topk_group=2, routed_scale=4.0,
        experts_held=(2, 4))
    return LLMEngine(init_params(jax.random.PRNGKey(0), cfg), cfg,
                     EngineConfig(max_num_seqs=2, page_size=4, num_pages=33,
                                  max_seq_len=64, decode_burst=2))


@pytest.mark.parametrize("program, spans", [
    ("prefill", ("rt.attn.mla.prefill", "rt.moe.shared", "rt.moe.route",
                 "rt.moe.experts", "rt.moe.combine")),
    ("decode", ("rt.attn.mla.decode", "rt.moe.shared", "rt.moe.route")),
])
def test_latent_programs_carry_their_spans(program, spans):
    """The scopes are in the programs' own text (what a device trace
    attributes operations to), prefill's expanded form under one name
    and the absorbed form under another."""
    from ray_tpu.llm.runner import decode_burst

    engine = _latent_engine()
    if program == "prefill":
        text = engine.compile_prefill(20)[1].as_text()
    else:
        B = engine.ecfg.max_num_seqs
        zi, zf = jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.float32)
        text = decode_burst.lower(
            engine.params, engine.cache.k, engine.cache.v, zi, zi,
            engine._tables(), jnp.zeros(B, bool), engine.cos, engine.sin,
            0, zf, zi, zf, None, engine._bt(16), jnp.int32(1),
            cfg=engine.cfg, n_steps=2, greedy=True).as_text(
                debug_info=True)
    for span in spans:
        assert span in text, span
    assert ("rt.attn.mla.decode" in text) == (program == "decode")
    # the kernels' names the readers repeat
    from ray_tpu.ops import mla
    from ray_tpu.ops.attention import LATENT_KERNEL

    assert (mla.DECODE_KERNEL, LATENT_KERNEL) == ("rt_mla_decode",
                                                  "flash_mla_fwd")


def test_rows_routed_elsewhere_and_the_latent_bytes_are_counted():
    engine = _latent_engine()
    assert engine.generate([[5, 17, 99, 3, 8, 21, 40]], SamplingParams(
        temperature=0.0, max_tokens=5))
    counters = engine.stats()["counters"]
    # 7 prompt tokens and 4 decode steps through 2 expert layers, 3
    # experts each: every row is given to an expert here or counted as
    # elsewhere
    assert (counters["expert_rows"] + counters["expert_rows_elsewhere"]
            == (7 + 4) * 2 * 3)
    assert counters["expert_rows_elsewhere"] > 0
    # ONE row of 128 float32 values a layer a token (40 of them values)
    assert counters["kv_bytes_per_token"] == 3 * 128 * 4
    # a configuration that holds all its experts counts none elsewhere
    plain = dataclasses.replace(CFG, n_experts=4, top_k=2)
    other = LLMEngine(init_params(jax.random.PRNGKey(0), plain), plain,
                      EngineConfig(max_num_seqs=2, page_size=4, num_pages=32,
                                   max_seq_len=32, decode_burst=2))
    assert "expert_rows_elsewhere" not in other.stats()["counters"]
    assert other.stats()["counters"]["kv_bytes_per_token"] == (
        2 * plain.n_layers * plain.n_kv_heads * plain.head_dim * 4)
