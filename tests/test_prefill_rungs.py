"""The rows a whole prompt is padded to (``runner.prefill_bucket``: the
powers of two and the rungs of ``PREFILL_RUNGS`` between them), the page
lists' buckets beside them (``runner.page_bucket``: powers of two alone),
and the loader of the rungs' programs (``LLMEngine.load_prefill_programs``,
which a replica that prefills calls before it is ready) (ISSUE 49; the
second rung ISSUE 56).

The loader's tests give an engine rungs of 48 and 96 rows so that the
buckets exist at toy sizes (``LLMEngine._PREFILL_RUNGS``): three quarters
of 64 and of 128 as the served rungs are of 16,384 and 32,768, whole
pages of every toy configuration, past the toy ``dense_len`` of the
state-layer family.
"""

import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import families                     # noqa: E402
from ray_tpu.llm.engine import EngineConfig, LLMEngine      # noqa: E402
from ray_tpu.llm.runner import (PREFILL_RUNGS, page_bucket,  # noqa: E402
                                prefill_bucket, prefill_sample)
from ray_tpu.llm.sampling import SamplingParams             # noqa: E402

RUNG = 48
RUNGS = (RUNG, 96)
MAX_SEQ_LENS = [1024, 2048, 12288, 16384, 32768]


def _parent_bucket(seq_len, max_seq, floor=16):
    """``prefill_bucket`` as it was before a rung: what every page list
    and every prompt outside a rung still gets."""
    b = floor
    while b < seq_len:
        b *= 2
    return min(b, max_seq)


# ------------------------------------------------------------------ the rule
def test_the_two_rungs():
    assert PREFILL_RUNGS == (12288, 24576)
    # three quarters of the power of two above, in whole tiles of 512
    # rows (the widest the prefill kernels cut) and whole pages of 64
    assert [r % 512 for r in PREFILL_RUNGS] == [0, 0]
    assert [4 * r // 3 for r in PREFILL_RUNGS] == [16384, 32768]


@pytest.mark.parametrize("max_seq", MAX_SEQ_LENS)
def test_outside_the_rungs_a_prompt_gets_the_parents_bucket(max_seq):
    for n in [*range(1, 8193), *range(12289, 16385), *range(24577, 32769)]:
        assert prefill_bucket(n, max_seq) == _parent_bucket(n, max_seq), n
    if max_seq <= 12288:
        for n in range(8193, 12289):
            assert prefill_bucket(n, max_seq) == _parent_bucket(n, max_seq)
    if max_seq <= 16384:
        # no prompt an engine admits reaches the second rung, and past
        # what it admits the answer is the parent's cap
        for n in range(16385, 24577):
            assert prefill_bucket(n, max_seq) == _parent_bucket(n, max_seq)


@pytest.mark.parametrize("rung, max_seq", [
    *[(12288, m) for m in (12288, 12289, 16384, 32768, 131072)],
    *[(24576, m) for m in (24576, 24577, 32768, 131072)]])
def test_inside_a_rung_a_prompt_gets_its_rows(rung, max_seq):
    below = 2 * rung // 3                      # the power of two under it
    assert {prefill_bucket(n, max_seq)
            for n in range(below + 1, rung + 1)} == {rung}
    assert prefill_bucket(below, max_seq) == below
    assert prefill_bucket(rung + 1, max_seq) == min(2 * below, max_seq)


@pytest.mark.parametrize("max_seq", [1024, 2048, 12288, 13312, 16384])
def test_no_cell_under_16385_positions_meets_the_second_rung(max_seq):
    """The six serve configurations whose ``max_seq_len`` is at most
    16,384 pad as one rung padded them: byte for byte the program of the
    commit before the second rung."""
    for n in range(1, max_seq + 1):
        assert (prefill_bucket(n, max_seq)
                == prefill_bucket(n, max_seq, rungs=PREFILL_RUNGS[:1])), n


@pytest.mark.parametrize("max_seq", MAX_SEQ_LENS + [
    9000, 12000, 12289, 20000, 24576, 24577])
def test_the_rule_is_monotone_and_holds_the_prompt(max_seq):
    rows = [prefill_bucket(n, max_seq) for n in range(1, max_seq + 1)]
    assert all(a <= b for a, b in zip(rows, rows[1:]))
    assert all(n <= r <= max_seq for n, r in enumerate(rows, 1))
    # past what the engine admits the parent's answer stays: the cap
    assert prefill_bucket(max_seq + 1, max_seq) == max_seq


@pytest.mark.parametrize("rungs, want", [
    ((), [16, 32, 64, 64]), ((48,), [16, 32, 48, 64]),
    ((24, 48), [16, 24, 48, 64]), ((96,), [16, 32, 64, 64]),
    ((48, 96), [16, 32, 48, 64])],
    ids=["none", "one", "two", "past-the-cap", "one-of-two-past-the-cap"])
def test_a_rung_is_one_entry(rungs, want):
    """Prompts of 16, 17, 33 and 49 tokens under a cap of 64: a rung
    catches the prompts between it and the power of two below it."""
    assert [prefill_bucket(n, 64, rungs=rungs)
            for n in (16, 17, 33, 49)] == want


@pytest.mark.parametrize("floor, most", [
    (4, 512), (16, 384), (16, 12289), (32, 512), (16, 32768)])
def test_a_page_list_keeps_the_powers_of_two(floor, most):
    """No rung in a page list's bucket, though a list of 8,193 to 12,288
    pages is nothing any configuration has today."""
    for n in [*range(1, 600), *range(8000, 12400), 16384, 20000]:
        assert page_bucket(n, most, floor) == _parent_bucket(n, most, floor)


# --------------------------------------------- the tiny configurations
def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)


# a configuration of every kind of cache: one group of pages; full and
# window groups; latent rows; an indexer's third pool; a state a slot.
# Beside each, ``decode_buckets()`` as the parent commit gives them
FAMILIES = {
    "tiny-rehearsal": [16, 32, 64],
    "tiny-rehearsal-smallthinker": [(16, 16), (32, 16), (32, 20), (64, 16),
                                    (64, 20), (128, 16), (128, 20)],
    "tiny-rehearsal-deepseek-v2": [32],
    "tiny-rehearsal-keye-vl2": [32],
    "tiny-rehearsal-minicpm-sala": [32],
}
_MADE = {}


def _family(name):
    """(program config, params, engine options) of a tiny configuration,
    made once a process."""
    if name not in _MADE:
        config = _config(name)
        family = families.family_of(config)
        _MADE[name] = (family.program_config(config),
                       family.served_params(jax.random.PRNGKey(11), config),
                       dict(config["engine"]))
    return _MADE[name]


def _engine(name, rungs=RUNGS, **more):
    cfg, params, options = _family(name)
    engine = LLMEngine(params, cfg, EngineConfig(**{**options, **more}))
    engine._PREFILL_RUNGS = rungs
    return engine


def _prompt(n, vocab, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(1, vocab, n)]


def _pools(engine):
    cache = engine.cache
    return [None if pool is None else [np.asarray(p) for p in
                                       jax.tree.leaves(pool)]
            for pool in (cache.k, cache.v, cache.i, cache.c, cache.s)]


def _same_pools(a, b):
    for x, y in zip(a, b):
        assert (x is None) == (y is None)
        for p, q in zip(x or [], y or []):
            np.testing.assert_array_equal(p, q)


def _serve(engine, prompts, max_tokens=6):
    rids = [engine.add_request(p, SamplingParams(
        temperature=0.0, max_tokens=max_tokens)) for p in prompts]
    while engine.has_unfinished():
        engine.step()
    return [list(engine.requests[rid].output) for rid in rids]


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_the_page_lists_buckets_are_the_parents(name):
    engine = _engine(name, rungs=PREFILL_RUNGS)
    assert engine.decode_buckets() == FAMILIES[name]
    width = engine.seq_table.block_tables.shape[1]
    for n in range(1, width + 2):
        assert engine._span_bucket(n) == _parent_bucket(
            n, width, engine._SPAN_PAGES)
        assert engine._latent_span(n) == _parent_bucket(
            n, width, engine._LATENT_SPAN_PAGES)
    for g in range(len(engine.windows)):
        most = engine._listable_pages(g)
        for n in range(1, most + 2):
            assert engine._flat_bucket(n, g) == _parent_bucket(
                n, most, engine._FLAT_PAGES)


# ----------------------------------------------------------------- the loader
@pytest.mark.parametrize("rungs", [(RUNG,), RUNGS], ids=["one", "two"])
@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_loading_moves_nothing_but_its_two_counters(name, rungs):
    """A program a rung; pages, state pool (slot 0's too, though a prompt
    of no tokens ends in the zero state), seed and every counter of
    served work are what they were."""
    engine = _engine(name, rungs=rungs)
    if engine.cache.s is not None:
        engine.cache.s = engine.cache.s + 1.5
    before = (_pools(engine), engine.stats(), engine._seed)
    assert engine.load_prefill_programs() == len(rungs)
    after = engine.stats()
    counters = dict(after["counters"])
    assert counters.pop("loaded_programs") == len(rungs)
    assert counters.pop("load_s") > 0.0
    want = dict(before[1]["counters"])
    del want["loaded_programs"], want["load_s"]
    # no counter: the tiles chosen where this PROCESS traced a program
    # with experts, by shape; a rung's rows are a shape
    assert set(counters.pop("expert_tiles")) >= set(want.pop("expert_tiles"))
    assert counters == want
    assert {**after, "counters": None} == {**before[1], "counters": None}
    assert engine._seed == before[2]
    _same_pools(_pools(engine), before[0])
    assert all(t.version == 0 or not t.n_pages.any()
               for t in engine.seq_tables)


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_a_request_in_a_loaded_bucket_traces_nothing(name):
    """Prompts of 40 and 34 tokens run the first rung's 48 rows, one of
    70 the second's 96. After loading, they add no entry to
    ``prefill_sample``'s jit cache (the loader's call is the request's),
    and their tokens are those of an engine that never loaded."""
    vocab = _family(name)[0].vocab
    prompts = [_prompt(40, vocab, 1), _prompt(34, vocab, 2),
               _prompt(70, vocab, 3)]
    loaded = _engine(name)
    loaded.load_prefill_programs()
    size = prefill_sample._cache_size()
    tokens = _serve(loaded, prompts)
    assert prefill_sample._cache_size() == size
    c = loaded.stats()["counters"]
    assert c["prefill_bucket_tokens"] == 2 * RUNGS[0] + RUNGS[1]
    assert c["prefill_tokens"] == 144 and c["loaded_programs"] == 2
    assert tokens == _serve(_engine(name), prompts)
    # and a rung changes no token: the same prompts in 64, 64 and 128 rows
    plain = _engine(name, rungs=())
    assert tokens == _serve(plain, prompts)
    assert plain.stats()["counters"]["prefill_bucket_tokens"] == 256


@pytest.mark.parametrize("max_seq_len, programs", [
    (RUNG, 0), (64, 1), (96, 1), (112, 2), (128, 2)])
def test_an_engine_loads_only_the_rungs_below_its_cap(max_seq_len, programs):
    """A cap at a rung is a bucket lone requests meet, as every cap is;
    a cap between the rungs (the six serve cells under 16,385 positions)
    loads what one rung loaded."""
    engine = _engine("tiny-rehearsal", max_seq_len=max_seq_len)
    assert engine.load_prefill_programs() == programs
    assert engine.stats()["counters"]["loaded_programs"] == programs
    assert sorted({engine._prefill_rows(n) for n in range(
        33, max_seq_len + 1)} - {64, max_seq_len}) == list(RUNGS[:programs])


@pytest.mark.parametrize("name, rungs, more", [
    ("tiny-rehearsal", PREFILL_RUNGS, {}),
    ("tiny-rehearsal-minicpm-sala", PREFILL_RUNGS, {}),
    ("tiny-rehearsal", (), {}),
    ("tiny-rehearsal", (RUNG,), {"max_seq_len": RUNG}),
    ("tiny-rehearsal", (RUNG,), {"prefill_chunk": 16}),
    ("tiny-rehearsal", (RUNG,), {"enable_prefix_caching": True})],
    ids=["toy-sizes", "toy-sizes-state-layers", "no-rung",
         "the-rung-is-the-cap", "chunked", "prefix-cache"])
def test_an_engine_without_the_bucket_loads_nothing(name, rungs, more):
    """No rung below ``max_seq_len`` (a cap at the rung is a bucket lone
    requests meet, as before), or no whole-prompt prefill at all: nothing
    is run, nothing is counted, and ``load_decode_programs`` loads what
    it did."""
    engine = _engine(name, rungs=rungs, **more)
    size = prefill_sample._cache_size()
    assert engine.load_prefill_programs() == 0
    assert prefill_sample._cache_size() == size
    c = engine.stats()["counters"]
    assert (c["loaded_programs"], c["load_s"]) == (0, 0.0)
    buckets = engine.decode_buckets()
    if not more:
        assert buckets == FAMILIES[name]
    assert engine.load_decode_programs() == len(buckets)
    assert engine.stats()["counters"]["loaded_programs"] == len(buckets)


@pytest.mark.parametrize("pool, decode, prefill", [
    (None, 1, 1), ("decode", 1, 0), ("prefill", 0, 1)])
def test_a_replica_loads_its_programs_with_its_role(monkeypatch, pool,
                                                    decode, prefill):
    """``serve``'s ``Replica`` calls ``configure_pool`` in its
    constructor, so before the replica reports ready: a replica that
    prefills (no pools, or the prefill pool) loads the two rungs'
    programs there, once; a decode replica, which is handed its prompts'
    KV, loads none."""
    from ray_tpu.llm.serve import LLMServer

    monkeypatch.setattr(LLMEngine, "_PREFILL_RUNGS", (24, 48))
    server = LLMServer("tiny", engine_config={
        "max_num_seqs": 2, "page_size": 4, "num_pages": 32,
        "max_seq_len": 64, "decode_burst": 4})
    calls = {"decode": [], "prefill": []}
    for kind in calls:
        load = getattr(server.engine, f"load_{kind}_programs")
        setattr(server.engine, f"load_{kind}_programs",
                lambda load=load, kind=kind: calls[kind].append(load()))
    server.configure_pool(pool, "llm")
    assert calls["decode"] == [len(server.engine.decode_buckets())] * decode
    assert calls["prefill"] == [2] * prefill
    assert server.engine.stats()["counters"]["loaded_programs"] == sum(
        calls["decode"] + calls["prefill"])
