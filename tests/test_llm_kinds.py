"""A cache kind is one module (``ray_tpu/llm/kinds``, ISSUE 50):
``kinds.of`` picks it from the configuration alone, it gives every
program or refuses it by name, and its table of refusals is what the
engine raises, whichever door the feature comes through.
"""

import dataclasses
import inspect
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import families                     # noqa: E402
from ray_tpu.llm import kinds, runner                       # noqa: E402
from ray_tpu.llm.cache import init_kv_cache                 # noqa: E402
from ray_tpu.llm.engine import EngineConfig, LLMEngine      # noqa: E402
from ray_tpu.llm.sampling import SamplingParams             # noqa: E402
from ray_tpu.models.llama import LlamaConfig                # noqa: E402
from ray_tpu.ops import rope_frequencies                    # noqa: E402

# every serve configuration of the CPU rehearsal, and its kind
CONFIGS = {
    "tiny-rehearsal": kinds.paged,
    "tiny-rehearsal-moe": kinds.paged,
    "tiny-rehearsal-olmoe": kinds.paged,
    "tiny-rehearsal-smallthinker": kinds.paged,
    "tiny-rehearsal-deepseek-v2": kinds.latent,
    "tiny-rehearsal-keye-vl2": kinds.indexed,
    "tiny-rehearsal-minicpm-sala": kinds.state,
    "tiny-rehearsal-sambay": kinds.scan,
}
# one configuration a row of the table of refusals (paged: its two)
OF_KIND = ["tiny-rehearsal", "tiny-rehearsal-smallthinker",
           "tiny-rehearsal-deepseek-v2", "tiny-rehearsal-keye-vl2",
           "tiny-rehearsal-minicpm-sala", "tiny-rehearsal-sambay"]
KINDS = [kinds.paged, kinds.latent, kinds.indexed, kinds.state, kinds.scan]
FEATURES = ("enable_prefix_caching", "lora_rank", "speculation",
            "kv_transfer")
PROGRAMS = ("prefill", "prefill_chunk", "verify_step", "decode_burst")
_MADE = {}


def _family(name):
    """(program config, the params' shapes, engine options, the params'
    maker), read once a process."""
    if name not in _MADE:
        with open(os.path.join(ROOT, "benchmarks", "configs",
                               name + ".json")) as f:
            config = json.load(f)
        family = families.family_of(config)

        def make():
            return family.served_params(jax.random.PRNGKey(3), config)

        _MADE[name] = (family.program_config(config), jax.eval_shape(make),
                       dict(config["engine"]), make)
    return _MADE[name]


def _engine(name, params=None, **more):
    """An engine of the configuration; without ``params`` one that is
    built and asked, but never stepped."""
    cfg, _, options, _ = _family(name)
    return LLMEngine(params, cfg, EngineConfig(**{**options, **more}))


# ------------------------------------------------- picked from the config
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_the_kind_is_picked_from_the_configuration_alone(name):
    cfg = _family(name)[0]
    assert kinds.of(cfg) is CONFIGS[name]
    # nothing of the engine's options, the dtype or the depth moves it
    assert kinds.of(dataclasses.replace(cfg, dtype=jnp.float32)) \
        is CONFIGS[name]
    assert set(inspect.signature(kinds.of).parameters) == {"cfg"}
    assert _engine(name).kind is CONFIGS[name]


def test_the_default_configuration_is_paged():
    assert kinds.of(LlamaConfig()) is kinds.paged


# ------------------------------------------------- what a kind must give
@pytest.mark.parametrize("kind", KINDS, ids=lambda k: k.__name__[-7:])
def test_a_kind_gives_everything_the_programs_and_the_engine_ask(kind):
    for name in ("init_pools", "heads", *PROGRAMS, "count",
                 "attention_paths", "refuses"):
        assert callable(getattr(kind, name)), name
    # one fact, or one a layer group (there a window group's may be a
    # row a slot)
    own = kind.OWN_PAGES
    assert all(isinstance(o, bool) or o == kinds.ROWS
               for o in (own if isinstance(own, tuple) else (own,)))
    assert kind.LOWEST_BUCKET in (16, 32, 64)
    assert all(isinstance(c, str) for c in kind.COUNTERS)


@pytest.mark.parametrize("name", OF_KIND)
def test_the_hosts_facts_of_a_kind(name):
    cfg = _family(name)[0]
    kind = kinds.of(cfg)
    named, reasons = kind.refuses(cfg)
    assert isinstance(named, str) and set(reasons) <= set(FEATURES)
    assert all(isinstance(why, str) and why for why in reasons.values())
    paths = kind.attention_paths(cfg, "blockwise", False)
    assert set(paths) == set(PROGRAMS)
    # the queries at positions [5, 9) of a burst move the kind's counters
    # and no other
    counters = dict.fromkeys(kind.COUNTERS, 0)
    kind.count(cfg, counters, 4, 5, 9, True)
    assert set(counters) == set(kind.COUNTERS)
    assert all(v >= 0 for v in counters.values())
    engine = _engine(name)
    assert set(kind.COUNTERS) <= set(engine.stats()["counters"])
    assert set(engine.attention_paths()) == set(PROGRAMS)


# ----------------------------------- every program, or a refusal by name
def _lowered(name, program):
    """``program`` of the runner lowered for the tiny configuration on
    abstract arguments; raises what the kind raises."""
    cfg, params, e, _ = _family(name)
    B, page, K = e["max_num_seqs"], e["page_size"], e["decode_burst"]
    from ray_tpu.llm.cache import window_group_pages

    pages = [e["num_pages"] if w is None else window_group_pages(
        B, w, page, K) for w in cfg.kv_groups]
    grouped = len(pages) > 1
    pools = jax.eval_shape(lambda: (lambda c: (c.k, c.v, c.i, c.c, c.s))(
        init_kv_cache(cfg, pages if grouped else pages[0], page, slots=B)))
    ck, cv, rest = pools[0], pools[1], pools[2:]
    cos, sin = rope_frequencies(cfg.rope_dim, cfg.max_seq, cfg.rope_theta,
                                scaling=cfg.rope_scaling)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    def tables(rows, span=16):
        t = tuple(sds((rows, span)) for _ in pages)
        return t if grouped else t[0]

    slots = None if rest[2] is None else sds((1,))
    f32 = sds((B,), jnp.float32)
    if program == "prefill":
        return runner.prefill.lower(
            params, ck, cv, sds((1, 16)), sds((1,)), tables(1), cos, sin,
            None, *rest, slots, cfg=cfg)
    if program == "prefill_chunk":
        return runner.prefill_chunk.lower(
            params, ck, cv, sds((1, 8)), sds(()), sds(()), tables(1), cos,
            sin, *rest, slots, cfg=cfg)
    if program == "verify_step":
        return runner.verify_step.lower(
            params, ck, cv, sds((B, 3)), sds((B, 3)), tables(B), cos, sin,
            0, f32, sds((B,)), f32, *rest, cfg=cfg, greedy=True)
    return runner.decode_burst.lower(
        params, ck, cv, sds((B,)), sds((B,)), tables(B),
        sds((B,), jnp.bool_), cos, sin, 0, f32, sds((B,)), f32, None, None,
        sds(()), *rest, cfg=cfg, n_steps=K, greedy=True)


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("name", OF_KIND)
def test_a_kind_gives_the_program_or_refuses_it_by_name(name, program):
    cfg = _family(name)[0]
    stateful = {kinds.state: "linear", kinds.scan: "scan"}
    if kinds.of(cfg) in stateful and program == "verify_step":
        with pytest.raises(ValueError, match="verify_step is not written "
                           f"for {stateful[kinds.of(cfg)]} layers"):
            _lowered(name, program)
        return
    text = _lowered(name, program).as_text()
    assert "func.func public @main" in text


# ------------------------------------- the table is what the engine raises
def _ask(name, feature):
    """Ask ``feature`` of an engine of the configuration through every
    door it has; returns the engines that were built."""
    spec = {"draft_config": "tiny", "num_draft_tokens": 2}
    if feature == "enable_prefix_caching":
        return [_engine(name, enable_prefix_caching=True)]
    if feature == "lora_rank":
        return [_engine(name, lora_rank=4)]
    if feature == "speculation":
        built = _engine(name, speculation=spec)
        engine = _engine(name)
        engine.enable_speculation(spec)
        return [built, engine]
    cfg = _family(name)[0]
    if feature in kinds.of(cfg).refuses(cfg)[1]:
        return [_engine(name), "req-0"]      # refused before it is looked up
    engine = _engine(name, _family(name)[3]())
    rid = engine.add_request([1, 2, 3, 4, 5], SamplingParams(
        temperature=0.0, max_tokens=32))
    engine.step()
    return [engine, rid]


@pytest.mark.parametrize("feature", FEATURES)
@pytest.mark.parametrize("name", OF_KIND)
def test_a_feature_builds_an_engine_or_raises_the_tables_reason(name,
                                                                feature):
    cfg = _family(name)[0]
    named, reasons = kinds.of(cfg).refuses(cfg)
    if feature not in reasons:
        if feature == "kv_transfer":
            engine, rid = _ask(name, feature)
            payload = engine.snapshot_kv_request(rid)
            assert payload["ctx_len"] >= 5
            assert engine.export_kv_request(rid)["prompt"] == [1, 2, 3, 4, 5]
            assert isinstance(_engine(name).inject_request(payload), str)
        else:
            assert all(isinstance(e, LLMEngine)
                       for e in _ask(name, feature))
        return
    said = f"is not supported with {named}: {reasons[feature]}"
    if feature != "kv_transfer":
        with pytest.raises(ValueError) as refused:
            _ask(name, feature)
        assert str(refused.value) == f"EngineConfig.{feature} {said}"
        if feature == "speculation":
            # through the method too, whoever calls it
            with pytest.raises(ValueError) as refused:
                _engine(name).enable_speculation(
                    {"draft_config": "tiny", "num_draft_tokens": 2})
            assert str(refused.value) == f"EngineConfig.{feature} {said}"
        return
    engine, rid = _ask(name, feature)
    for what, argument in (("export_kv_request", rid),
                           ("snapshot_kv_request", rid),
                           ("inject_request", {"request_id": "x"})):
        with pytest.raises(ValueError) as refused:
            getattr(engine, what)(argument)
        assert str(refused.value) == f"{what} {said}"


def test_one_function_refuses_and_no_kind_is_named_in_the_engine():
    from ray_tpu.llm import engine

    source = inspect.getsource(engine)
    assert "_refuse_with" not in source and "_refuse_kv" not in source
    assert source.count("def _refuse(") == 1
    for flag in ("cfg.latent", "sparse_top_k", "own_weights", "scan_state"):
        assert flag not in source, flag
