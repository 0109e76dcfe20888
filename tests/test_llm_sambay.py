"""A decoder-hybrid-decoder stack on the serving path (``kinds/scan.py``,
``runner._hybrid_layers``; CPU, the tiny preset, float32): the engine
through whole-prompt prefill, chunked prefill and decode past a window of
16 against the family's full forward, logits compared; the cross-decoder
at a prefill's last row equal to running every row; a slot reused after
release starts from zeros; what the kind refuses, by name; the counters.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import families                     # noqa: E402
from ray_tpu.llm import kinds, runner                       # noqa: E402
from ray_tpu.llm.cache import init_kv_cache, window_group_pages  # noqa: E402
from ray_tpu.llm.engine import EngineConfig, LLMEngine      # noqa: E402
from ray_tpu.llm.sampling import SamplingParams             # noqa: E402
from ray_tpu.models.llama import LlamaConfig                # noqa: E402
from ray_tpu.models.sambay import lambda_init, layers_of    # noqa: E402
from ray_tpu.ops import rope_frequencies                    # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "configs",
                       "tiny-rehearsal-sambay.json")) as _f:
    CONFIG = json.load(_f)
FAMILY = families.family_of(CONFIG)
CFG = FAMILY.program_config(CONFIG)
# float32 on the CPU: the program and the reference order their sums
# differently (a layer scan against a Python loop, one softmax over two
# segments against one over all keys), which moves a logit of deviation
# 1 by a few 1e-5
TOL = dict(rtol=2e-4, atol=2e-4)


@pytest.fixture(scope="module")
def params():
    return FAMILY.served_params(jax.random.PRNGKey(3), CONFIG)


def _engine(params, **more):
    return LLMEngine(params, CFG, EngineConfig(
        **{**CONFIG["engine"], **more}))


def _serve(engine, prompts, max_tokens=20):
    ids = [engine.add_request(p, SamplingParams(
        temperature=0.0, max_tokens=max_tokens)) for p in prompts]
    while engine.has_unfinished():
        engine.step()
    return [engine.requests[i].output for i in ids]


def _prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, CFG.vocab, n)] for n in lengths]


def _reference(params, tokens):
    return np.asarray(FAMILY.forward_logits(
        params, jnp.asarray([tokens], jnp.int32), CONFIG))[0]


def test_the_configuration_is_the_stack():
    assert CFG.layer_kinds == (
        "scan", "window_diff", "scan", "window_diff", "scan", "full_diff",
        "gmu", "cross_diff")
    assert CFG.hybrid_periods == (2, 1)
    assert CFG.kv_groups == (None, 16)
    assert (CFG.group_layers(0), CFG.group_layers(1)) == (1, 2)
    assert kinds.of(CFG) is kinds.scan
    assert layers_of(CFG, "scan") == (0, 2, 4)
    assert lambda_init(0) == pytest.approx(0.2)
    assert CFG.state_bytes_per_slot == 3 * 19 * 128 * 4
    cache = init_kv_cache(CFG, [9, 5], 8, slots=3)
    # the full layer's page ONE matrix of (position, row) rows
    assert [p.shape for p in cache.k] == [(1, 9, 16, 16), (2, 5, 8, 2, 16)]
    assert cache.s.shape == (3, 3, 19, 1, 128) and cache.s.dtype == jnp.float32
    with pytest.raises(ValueError, match="two layer groups"):
        init_kv_cache(CFG, 9, 8, slots=3)
    for wrong in (dict(layer_pattern=("scan", "full_diff") * 4),
                  dict(scan_state=0), dict(scan_dt_rank=0),
                  dict(n_heads=6), dict(window=None)):
        with pytest.raises(ValueError):
            LlamaConfig(**{**CFG.__dict__, **wrong})


def _pools(pages=(33, 9), slots=4):
    cache = init_kv_cache(CFG, list(pages), 8, slots=slots)
    cos, sin = rope_frequencies(CFG.rope_dim, 64, CFG.rope_theta)
    return cache, cos, sin


@pytest.mark.parametrize("n", [3, 8, 21, 40])
def test_prefill_at_the_last_row_is_every_row(params, n):
    """``prefill`` runs the full layer's attention and the cross-decoder
    for the LAST row alone; the reference runs every layer at every
    row. Their logits at that row agree, the scan's end state is the
    recurrence's and the pages hold the rows."""
    cache, cos, sin = _pools()
    prompt, = _prompts(n, seed=n)
    tokens = np.zeros((1, 48), np.int32)
    tokens[0, :n] = prompt
    tables = tuple(jnp.asarray([[1 + i for i in range(6)]], jnp.int32)
                   for _ in range(2))
    logits, ck, cv, counts, cs = runner.prefill(
        params, cache.k, cache.v, jnp.asarray(tokens),
        jnp.asarray([n], jnp.int32), tables, cos, sin, None, None, None,
        cache.s, jnp.asarray([2], jnp.int32), cfg=CFG)
    np.testing.assert_allclose(logits[0], _reference(params, prompt)[-1],
                               **TOL)
    assert counts is None
    # the slot's rows of the pool are written, no other slot's
    assert float(jnp.abs(cs[:, 2]).max()) > 0
    assert float(jnp.abs(cs[:, jnp.asarray([0, 1, 3])]).max()) == 0
    # the tail: the last three inputs of the convolution, older first
    assert cs[:, 2, 16:].shape == (3, 3, 1, 128)
    # the full layer's pages hold the prompt's rows and nothing behind
    # (a page ONE matrix of its (position, row) rows, two a position)
    used = np.asarray(jnp.abs(ck[0][0, 1:7].astype(jnp.float32)).sum(
        2)).reshape(-1, 2).min(1)
    # (written a page at a time: the rows behind the prompt's end on its
    # last page are masked by position until a decode step writes them)
    assert (used[:n] > 0).all() and (used[-(-n // 8) * 8:] == 0).all()


@pytest.mark.parametrize("chunk", [0, 8, 16])
def test_the_engine_agrees_with_the_full_forward(params, chunk):
    """Prompts below, at and past the window of 16 (pages given back),
    decoded 20 tokens together: every chosen token is the reference's
    first choice on prompt + answer, and the reference's logits there
    are the program's (teacher-forced through ``prefill``)."""
    more = dict(prefill_chunk=chunk, max_seq_len=120) if chunk else {}
    engine = _engine(params, **more)
    prompts = _prompts(5, 16, 40, 90, seed=chunk)
    outs = _serve(engine, prompts)
    for prompt, out in zip(prompts, outs):
        assert len(out) == 20
        logits = _reference(params, prompt + out)[len(prompt) - 1:-1]
        np.testing.assert_array_equal(logits.argmax(-1), out)
    counters = engine.stats()["counters"]
    assert counters["groups"]["window"]["released_pages"] > 0
    assert counters["scan_slots_reset"] == 4
    # one row of the cross-decoder a prefill dispatch
    assert counters["cross_prefill_rows"] == counters["prefills"]
    assert counters["prefills"] == (4 if not chunk else sum(
        -(-len(p) // chunk) for p in prompts))
    # a step reads and writes a slot's state and tail
    assert counters["scan_state_bytes_step"] == (
        2 * CFG.state_bytes_per_slot * 4 * 19)
    assert counters["shared_kv_pages_step"] > 0
    assert engine.stats()["state_bytes_per_slot"] == 3 * 19 * 128 * 4


def test_a_bursts_lists_are_a_table_span_and_a_row_a_slot(params):
    """The full group is read through the slots' own tables, cut to the
    longest decoding slot's bucket; the window group is gathered a row a
    slot, the pages inside its window (16 / 8 + 1): every shape a round
    meets is one a replica loads before it is ready."""
    engine = _engine(params, max_seq_len=120)
    assert engine.kind.OWN_PAGES == (True, kinds.ROWS)
    shapes = engine.decode_buckets()
    assert shapes == [(15, 3)]          # the toy's whole table, 120 / 8
    assert engine.load_decode_programs() == len(shapes)
    _serve(engine, _prompts(5, 40, 90, seed=7))
    counters = engine.stats()["counters"]
    assert set(counters["gather_hist"]) == {"15/3"}
    groups = counters["groups"]
    # nothing of the full group is copied; every slot's row of the window
    assert groups["full"]["gathered_pages"] == groups["full"]["live_pages"]
    assert groups["window"]["gathered_pages"] == 3 * 4 * counters["rounds"]
    assert groups["window"]["live_pages"] <= groups["window"][
        "gathered_pages"]


@pytest.mark.parametrize("chunk", [0, 16])
def test_decode_logits_are_the_references(params, chunk):
    """The logits themselves, not only their argmax: the tokens the
    engine decoded behind a 40-token prompt, teacher-forced through
    ``prefill`` of prompt + answer[:i], are the reference's rows."""
    more = dict(prefill_chunk=chunk, max_seq_len=120) if chunk else {}
    prompt, = _prompts(40, seed=5)
    out, = _serve(_engine(params, **more), [prompt], max_tokens=12)
    want = _reference(params, prompt + out)
    cache, cos, sin = _pools()
    tables = tuple(jnp.asarray([[1 + i for i in range(8)]], jnp.int32)
                   for _ in range(2))
    for i in (0, 5, 11):
        seq = prompt + out[:i]
        tokens = np.zeros((1, 64), np.int32)
        tokens[0, :len(seq)] = seq
        logits, *_ = runner.prefill(
            params, cache.k, cache.v, jnp.asarray(tokens),
            jnp.asarray([len(seq)], jnp.int32), tables, cos, sin, None,
            None, None, cache.s, jnp.asarray([0], jnp.int32), cfg=CFG)
        np.testing.assert_allclose(logits[0], want[len(seq) - 1], **TOL)
        assert int(logits[0].argmax()) == out[i]
        cache, cos, sin = _pools()


@pytest.mark.parametrize("chunk", [0, 8])
def test_a_slot_reused_after_release_starts_from_zeros(params, chunk):
    """One slot: the second request finds the first's state and tail in
    the pool, and answers as it does alone."""
    more = dict(max_num_seqs=1)
    if chunk:
        # a chunked prompt holds its pages in both groups to its end
        more.update(prefill_chunk=chunk, max_seq_len=32)
    first, second = _prompts(20, 13, seed=9)
    alone, = _serve(_engine(params, **more), [second], max_tokens=8)
    engine = _engine(params, **more)
    _serve(engine, [first], max_tokens=8)
    assert float(jnp.abs(engine.cache.s).max()) > 0
    again, = _serve(engine, [second], max_tokens=8)
    assert again == alone
    assert engine.stats()["counters"]["scan_slots_reset"] == 2


@pytest.mark.parametrize("feature,option", [
    ("enable_prefix_caching", dict(enable_prefix_caching=True)),
    ("lora_rank", dict(lora_rank=4)),
    ("speculation", dict(speculation={"draft_config": "tiny",
                                      "num_draft_tokens": 2}))])
def test_the_kind_refuses_by_name(params, feature, option):
    named, reasons = kinds.scan.refuses(CFG)
    assert named == "scan layers (scan_state=16)"
    with pytest.raises(ValueError) as refused:
        _engine(None, **option)
    assert str(refused.value) == (
        f"EngineConfig.{feature} is not supported with {named}: "
        f"{reasons[feature]}")


def test_the_kind_refuses_a_hand_over_and_a_speculative_window(params):
    engine = _engine(None)
    for what, argument in (("export_kv_request", "r"),
                           ("snapshot_kv_request", "r"),
                           ("inject_request", {"request_id": "x"})):
        with pytest.raises(ValueError, match=f"{what} is not supported "
                           "with scan layers"):
            getattr(engine, what)(argument)
    with pytest.raises(ValueError, match="verify_step is not written for "
                       "scan layers"):
        kinds.scan.verify_step(CFG)


def test_the_programs_trace_three_bodies_not_eight(params):
    """Five kinds of layer in one stack as two scans and a pair: the
    lowered prefill holds two ``while`` loops over layers and the scan
    kernel's jitted body once, however many layers call it."""
    cache, cos, sin = _pools()
    tables = tuple(jnp.zeros((1, 6), jnp.int32) for _ in range(2))
    text = runner.prefill.lower(
        params, cache.k, cache.v, jnp.zeros((1, 48), jnp.int32),
        jnp.asarray([5], jnp.int32), tables, cos, sin, None, None, None,
        cache.s, jnp.asarray([0], jnp.int32), cfg=CFG).as_text()
    assert text.count("stablehlo.while") >= 2
    assert text.count("func.func private @prefill") == 1
