"""The keye_vl2 family: the configuration file is read whole, the counts
are the published model's share by hand arithmetic (``arithmetic_why``),
the three new readers return None on an empty run, on another family's
run and on the CPU and read a run written by hand, no roofline reader
passes 100% at the counts' own inputs, and the program agrees with the
family's plain reference through ``tiny-chat-keye-vl2`` (the serve path,
CPU)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import families, peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "keyevl2-longctx-steady"
READERS = ("sparse_prefill_roofline", "sparse_decode_roofline",
           "select_device_share")


def _json(*path):
    with open(os.path.join(BENCH, *path)) as f:
        return json.load(f)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"),
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert module.NAME == name
    return module


PUBLISHED = _json("configs", "keye-vl-2.0-30b-a3b-ep4-int8-16l.json")


def test_family_of_takes_the_file_and_the_file_states_its_cut():
    family = families.family_of(PUBLISHED)
    assert family.__name__.endswith("keye_vl2")
    with pytest.raises(ValueError, match="does not read.*'extra_width'"):
        families.family_of(dict(PUBLISHED, extra_width=3))
    # every width is the catalog row's; the cuts are listed (the experts
    # held under both of the row's keys for them)
    published = PUBLISHED["published"]
    changed = {k for k, v in published.items() if PUBLISHED[k] != v}
    assert changed == set(PUBLISHED["reduced"]) == {
        "num_hidden_layers", "num_experts", "num_local_experts",
        "vocab_size"}
    assert PUBLISHED["sa_config"] == published["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert (PUBLISHED["num_hidden_layers"], PUBLISHED["num_experts"],
            PUBLISHED["vocab_size"]) == (16, 32, 37984)
    share = family.share_of(PUBLISHED)
    assert share == {"chips": 4, "routed_experts": 128, "first_expert": 0,
                     "vocab_size": 151936}
    assert share["routed_experts"] == published["num_experts"]
    assert share["vocab_size"] == published["vocab_size"]
    # floors of a model_config cut: 4 layers, 8 experts, 1/8 vocabulary
    assert PUBLISHED["num_hidden_layers"] >= 4
    assert family.experts_held(PUBLISHED) >= 8
    assert 8 * PUBLISHED["vocab_size"] >= published["vocab_size"]
    for key, value in (("norm_topk_prob", False), ("num_local_experts", 16),
                       ("use_sliding_window", True)):
        with pytest.raises(ValueError, match=key):
            family.program_config(dict(PUBLISHED, **{key: value}))
    for listed in ("qk_norm", "indexer", "selection", "indexer_precision",
                   "positions", "weights", "router"):
        assert listed in PUBLISHED["assumed"], listed


def test_the_program_configuration_is_the_rows_sizes():
    cfg = families.family_of(PUBLISHED).program_config(PUBLISHED)
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.mlp_dim, cfg.vocab) == (
                2048, 16, 32, 4, 128, 768, 37984)
    assert (cfg.indexer_heads, cfg.indexer_dim, cfg.sparse_top_k) == (
        16, 64, 2048)
    assert (cfg.n_experts, cfg.top_k, cfg.experts_held,
            cfg.norm_topk_prob) == (128, 8, (0, 32), True)
    assert cfg.qk_norm and cfg.qk_norm_by_head and cfg.rope_theta == 1e7


def test_the_family_refuses_a_program_without_an_indexer(tmp_path):
    """On a tree older than this family's seams the import itself stops,
    without jax, so that ``family_of`` ends the run before the runtime
    starts and the parent of PR 43 fails at once in the new cell."""
    package = tmp_path / "ray_tpu"
    (package / "models").mkdir(parents=True)
    (package / "__init__.py").write_text("")
    (package / "models" / "llama.py").write_text(
        "class LlamaConfig: experts_held = None\n")
    code = ("import sys; sys.path.insert(0, %r); sys.path.insert(1, %r)\n"
            "from benchmarks.harness import families\n"
            "import json\n"
            "try:\n"
            "    families.family_of(json.load(open(%r)))\n"
            "except ValueError as e:\n"
            "    assert 'LlamaConfig.sparse_top_k' in str(e), e\n"
            "    assert 'jax' not in sys.modules\n"
            "    print('refused')\n") % (
                str(tmp_path), ROOT, os.path.join(
                    BENCH, "configs",
                    "keye-vl-2.0-30b-a3b-ep4-int8-16l.json"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=str(tmp_path))
    assert out.stdout.strip() == "refused", out.stderr[-2000:]


def test_counts_are_the_published_models_share_by_hand():
    """``arithmetic_why``, number by number."""
    f, c = families.family_of(PUBLISHED), PUBLISHED
    d = 2048
    attention = 2 * d * 32 * 128 + 2 * d * 4 * 128
    assert attention == f._attention_params(c) == 18_874_368   # 18.87 M
    indexer = d * 16 * 64 + d * 64 + d * 16
    assert indexer == f._indexer_params(c) == 2_260_992        # 2.26 M
    assert f._router_params(c) == d * 128 == 262_144
    assert f._expert_params(c) == 3 * d * 768 == 4_718_592     # 4.72 M
    layer = attention + indexer + 262_144 + 32 * 4_718_592
    assert layer == 172_392_448                                # 172.4 M
    held = 16 * layer + 2 * d * 37984
    assert held == f.held_params(c) == 2_913_861_632           # 2.91 GB
    # the uncut model by the same arithmetic is the published 30B
    whole = dict(c["published"], share=None)
    assert 30.5e9 < f.held_params(whole) < 30.8e9
    assert f.held_experts_per_token(c) == 2.0
    # a cached position: K and V of 4 heads of 128 and the indexer's 64,
    # in bf16, 16 layers
    assert f.kv_bytes_per_token(c) == 16 * 2 * (1024 + 64) == 16 * 2176
    # the issue's arithmetic: a prefill token at position t needs 33.5
    # MFLOP a layer over its 2,048 chosen keys, and 2,080 operations a
    # visible key for the scores
    n = 8192.0
    visible, attended = f._pairs(n, 2048)
    assert visible == n * (n + 1) / 2
    assert attended == 2048 * 2049 / 2 + (n - 2048) * 2048
    assert 4 * 32 * 128 * 2048 == 33_554_432
    assert f.sparse_attention_flops(c, n) == 16 * (
        visible * 2 * 16 * 65 + attended * 4 * 32 * 128)
    assert f.prefill_flops(c, n) > f.sparse_attention_flops(c, n)
    # a prompt of at most 2,048 tokens attends over every pair
    assert f._pairs(1000.0, 2048) == (500_500.0, 500_500.0)
    # a decode step at 8 slots x 20k reads 8 x (2.6 MB of indexer rows +
    # 4.2 MB of chosen K and V) a layer
    step = f.sparse_decode_bytes(c, 8, 8 * 20_000) / 16
    assert step == 8 * (20_000 * 128 + 2048 * 2048)
    assert 8 * 20_000 * 128 == pytest.approx(8 * 2.56e6)
    assert 2048 * 2048 == pytest.approx(4.19e6, rel=1e-2)
    # a slot with fewer positions than 2,048 reads its own, not 2,048
    assert f.sparse_decode_bytes(c, 2, 2 * 500) == 16 * 2 * 500 * (
        128 + 2048)


@pytest.mark.parametrize("rows, live", [(1, 4096), (8, 8 * 9000),
                                        (8, 8 * 32000)])
def test_routed_decode_step_bytes_by_hand(rows, live):
    f, c = families.family_of(PUBLISHED), PUBLISHED
    touched = 32 * (1 - (1 - 8 / 128) ** rows)
    assert f.experts_touched(c, rows) == pytest.approx(touched)
    assert f.experts_touched(c, 10_000) == pytest.approx(32)
    d = 2048
    matrices = 16 * (18_874_368 + 2_260_992 + touched * 4_718_592) \
        + d * 37984
    cache = f.sparse_decode_bytes(c, rows, live)
    got = f.routed_decode_step_bytes(c, rows, live, 1)
    assert got > matrices + cache + 4 * 16 * d * 128
    assert got < 1.01 * (matrices + cache + 4 * 16 * d * 128)
    # never more than every held matrix once
    assert got <= f.decode_step_bytes(c, live, 1) * 1.001
    # the chosen rows, not the span: at 32k a slot the span is 16 x more
    assert cache <= live * f.kv_bytes_per_token(c)


def _run(prompts, rounds, programs, ops, config=PUBLISHED, firsts=None):
    """A run written by hand: prompt i arrives at ``firsts[i][0]`` and
    shows its first token at ``firsts[i][1]`` (by default a prefill of
    half a second a second, wholly inside the stretch)."""
    firsts = firsts or [(0.5 + i, 1.0 + i) for i in range(len(prompts))]
    return {
        "config": config,
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "engine": {"rounds": rounds, "finished": [
            {"arrival": a, "first": f, "prompt_tokens": n}
            for (a, f), n in zip(firsts, prompts)]},
        "trace": {"t0": 0.0, "t1": 10.0, "busy_s": 4.0,
                  "programs": programs, "device_ops": ops}}


def test_the_three_readers_read_what_this_family_adds_and_nothing_else():
    f = families.family_of(PUBLISHED)
    chip = peaks.peaks_of("TPU v5 lite")
    prompts = [4000, 9000, 20000]
    rounds = [{"t": 1.0, "width": 8, "active": 8, "live": 8 * 9000},
              {"t": 2.0, "width": 4, "active": 3, "live": 3 * 20000},
              {"t": 2.25, "width": 6, "active": 3, "live": 3 * 20004}]
    # the third prefill is cut by the stretch's end at 10.0
    firsts = [(0.2, 1.0), (0.5, 2.0), (1.0, 11.0)]
    run = _run(prompts, rounds, {
        "jit_prefill_sample": {"seconds": 3.0, "runs": 3},
        "jit_decode_burst": {"seconds": 0.6, "runs": 3}},
        [["flash_sparse_fwd", 1.0], ["rt_sparse_index", 0.3],
         ["rt_sparse_select", 0.2], ["rt_sparse_index_decode", 0.01],
         ["rt_sparse_select_decode", 0.03], ["fusion", 1.2]], firsts=firsts)
    prefill, decode, select = map(_reader, READERS)
    # a round alone lasts 0.25 (2.0 to 2.25, no first token between): the
    # first prefill ran from its arrival, the second from the first
    # round's end (1.25), the third from the last round's (2.5)
    assert prefill._prefills(run["engine"]) == [
        (0.2, 1.0, 4000), (1.25, 2.0, 9000), (2.5, 11.0, 20000)]
    flops = [f.sparse_attention_flops(PUBLISHED, n) for n in prompts]
    assert prefill.compute(run) == pytest.approx(
        100 * (flops[0] + flops[1] + flops[2] * 7.5 / 8.5) / 1.5
        / chip["bf16_flops"])
    # a prefill wholly outside the stretch counts nothing: None
    outside = json.loads(json.dumps(run))
    outside["trace"].update(t0=20.0, t1=24.0)
    assert prefill.compute(outside) is None
    assert select.compute(run) == pytest.approx(100 * 0.23 / 4.0)
    # the indexer's rows of the live positions, and the two named
    # kernels' seconds a step: no other byte, no other second
    steps = 18
    rows = sum(r["width"] * f.indexer_decode_bytes(PUBLISHED, r["live"])
               for r in rounds) / steps
    assert f.indexer_decode_bytes(PUBLISHED, 1000) == 16 * 1000 * 128
    assert decode.compute(run) == pytest.approx(
        100 * rows / chip["hbm_bytes_per_s"] / (0.04 / (3 * 6)))
    assert 0 < decode.compute(run) < 100 and 0 < prefill.compute(run) < 100
    assert [r.MOVES for r in (prefill, decode, select)] == [
        "ttft_p95_ms", "tpot_p95_ms", "ttft_p95_ms"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        contract = json.load(fh)
    for reader in (prefill, decode, select):
        assert (reader.UNIT, reader.SOURCE, reader.LAYER) == (
            "%", "device_trace", "Kernels")
        declared = [m for m in contract["per_layer"]
                    if m["name"] == reader.NAME]
        assert [m["workloads"] for m in declared] == [[CELL]]
        # nothing to read, nothing raised: an empty run, no trace, a
        # program without the kernels (the parent), the CPU
        assert reader.compute({}) is None
        assert reader.compute({"config": PUBLISHED}) is None
        assert reader.compute(dict(run, trace={})) is None
        bare = json.loads(json.dumps(run))
        bare["trace"]["device_ops"] = [["fusion", 1.0]]
        assert reader.compute(bare) is None
    # another family's run, as recorded on the chip
    for name in ("recorded_deepseek_v2_run.json", "recorded_olmoe_run.json"):
        other = _json("tests", name)
        other.setdefault("config", _json(
            "configs", "deepseek-v2-ep4-int8-9l.json" if "deepseek" in name
            else "olmoe-1b-7b-0125-int8.json"))
        for reader in (prefill, decode, select):
            assert reader.compute(other) is None, (name, reader.NAME)
    # a share of a TPU's peak is not read on the CPU
    cpu = json.loads(json.dumps(run))
    cpu["device"]["platform"] = "cpu"
    assert prefill.compute(cpu) is None and decode.compute(cpu) is None


def test_no_roofline_reader_passes_100_at_the_counts_own_inputs():
    """A program that ran exactly at the chip's published peaks, doing
    exactly what the counts say is needed, reads 100%: every visible
    pair computed and masked, padding, and the counting of the choice
    can only take it lower."""
    f, c = families.family_of(PUBLISHED), PUBLISHED
    chip = peaks.peaks_of("TPU v5 lite")
    prompts, rounds = [3072, 8192, 32000], [
        {"t": 1.0, "width": 8, "active": 8, "live": 8 * 9000},
        {"t": 2.0, "width": 3, "active": 2, "live": 2 * 30000}]
    sparse_s = sum(f.sparse_attention_flops(c, n) for n in prompts) \
        / chip["bf16_flops"]
    prefill_s = sum(f.prefill_flops(c, n) for n in prompts) \
        / chip["bf16_flops"]
    step_s = sum(r["width"] * f.routed_decode_step_bytes(
        c, r["active"], r["live"], 1) for r in rounds) \
        / chip["hbm_bytes_per_s"]
    index_s = sum(r["width"] * f.indexer_decode_bytes(c, r["live"])
                  for r in rounds) / chip["hbm_bytes_per_s"]
    run = _run(prompts, rounds, {
        "jit_prefill_sample": {"seconds": prefill_s, "runs": 3},
        "jit_decode_burst": {"seconds": step_s, "runs": 2}},
        [["flash_sparse_fwd", sparse_s * 0.7],
         ["rt_sparse_index", sparse_s * 0.3],
         ["rt_sparse_index_decode", index_s * 0.6],
         ["rt_sparse_select_decode", index_s * 0.4]])
    for name in ("sparse_prefill_roofline", "sparse_decode_roofline",
                 "prefill_roofline", "expert_decode_roofline"):
        assert _reader(name).compute(run) == pytest.approx(100.0), name


def test_the_two_checks_have_limits_of_their_own():
    """A cell's probes (64 + 16 tokens, never selecting) are judged by
    ``MARGIN_LIMIT``, set between THEIR sound readings (at most 0.047)
    and int4 at their length (0.155 or more); the long-context check by
    ``LONG_MARGIN_LIMIT`` and ``MEAN_MARGIN_LIMIT``, set from its own. The
    control the issue required and the limits do not catch is named as
    such, not as one that was never asked for."""
    f = families.family_of(PUBLISHED)
    # room on both sides: over 1.7 times either way
    assert 1.7 * 0.047 < f.MARGIN_LIMIT < 0.155 / 1.7
    assert f.MARGIN_LIMIT < f.LONG_MARGIN_LIMIT
    assert 0.137 < f.LONG_MARGIN_LIMIT < 0.187
    assert 0.0047 < f.MEAN_MARGIN_LIMIT < 0.011
    spec = importlib.util.spec_from_file_location(
        "check_long_context_sparse",
        os.path.join(BENCH, "check_long_context_sparse.py"))
    check = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(check)
    decides = {name: d for name, _control, d in check.CONTROLS}
    assert decides["whole_width_qk_norm"] is None      # required, not caught
    assert decides["indexer_in_bfloat16"] is False     # read only
    assert all(decides[name] is True for name in (
        "attend_over_every_key", "topk_1024", "score_without_relu",
        "router_not_renormalised", "layer_weights_in_int4"))


def test_the_cell_and_the_mix_are_the_issues():
    cell = _json("workloads", CELL + ".json")
    mix = _json("traffic", "longctx-steady.json")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "keye-vl-2.0-30b-a3b-ep4-int8-16l", "longctx-steady", 1)
    assert (cell["lead_in_s"], cell["drain_s"]) == (20, 20)
    # the issue's median of 8,192, or 6,144 by the issue's own rule (0.8
    # of the knee gave fewer than 24 requests a window): PERF.md section 4
    assert mix["prompt_tokens"]["median"] in (8192, 6144)
    assert dict(mix["prompt_tokens"], median=0) == {
        "dist": "lognormal", "median": 0, "sigma": 0.6, "min": 3072,
        "max": 32000}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 96,
                                    "sigma": 0.6, "min": 16, "max": 256}
    assert (mix["arrivals"], mix["mix_seed"], mix["shared_prefix"],
            mix["temperature"]) == ("poisson", 20261001, None, 0.0)
    # every prompt is past the 2,048 keys: every prefill selects
    assert mix["prompt_tokens"]["min"] > PUBLISHED["sa_config"]["topk"]
    # the window holds the cycle once: rate x run_seconds (40)
    assert mix["cycle_requests"] == round(cell["rate_rps"] * 40)
    assert cell["rate_rps"] * 40 == pytest.approx(mix["cycle_requests"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        contract = json.load(fh)
    declared = [m["name"] for m in contract["per_layer"]
                if cell["name"] in m.get("workloads", ())]
    assert set(READERS) | {"prefill_roofline", "expert_decode_roofline",
                           "decode_step_ms", "device_idle.serve"} <= set(
                               declared)
    assert "decode_burst_roofline" not in declared
    for metric in contract["end_to_end"]:
        if metric["name"] in ("ttft_p95_ms", "tpot_p95_ms", "serve_tok_s"):
            assert cell["name"] in metric["workloads"]
    engine = PUBLISHED["engine"]
    assert (engine["max_num_seqs"], engine["page_size"],
            engine["max_seq_len"], engine["decode_burst"]) == (8, 64, 32768,
                                                               8)
    assert "prefill_chunk" not in engine          # whole-prompt prefill
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= engine["max_seq_len"]
    # one sequence of max_seq_len always fits; the pool was lowered from
    # the issue's 4,097 pages by the fit (engine_why has both readings)
    assert 1 + engine["max_seq_len"] // engine["page_size"] \
        <= engine["num_pages"] <= 4097
    assert "4,097" in PUBLISHED["engine_why"]


def test_tiny_chat_keye_vl2_runs_through_serve_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "tiny-chat-keye-vl2", "--seed", str(2**31 + 11), "--seconds",
         "3", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] == 24                  # 8 a second x 3 s
    assert result["device"]["platform"] == "cpu"
    limit = families.family_of(PUBLISHED).MARGIN_LIMIT
    assert result["notes"]["probes"]["margin_limit"] == limit
    # float32 at toy size: the probes agree with the reference outright
    assert result["notes"]["probes"]["margin_worst"] <= 0.01
    # shares of a TPU's peak are not read on the CPU, and the choosing's
    # share needs the kernels' events
    for name in READERS + ("prefill_roofline", "expert_decode_roofline"):
        assert name not in result["metrics"]


def test_the_reference_is_the_program_at_tiny_size():
    """Whole-prompt prefill through the three pools against the family's
    reference, logits, and each control another function."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm.cache import init_kv_cache
    from ray_tpu.llm.runner import prefill
    from ray_tpu.ops import rope_frequencies

    config = _json("configs", "tiny-rehearsal-keye-vl2.json")
    family = families.family_of(config)
    cfg = family.program_config(config)
    params = family.served_params(jax.random.PRNGKey(2), config)
    tokens = np.random.default_rng(0).integers(1, cfg.vocab, (1, 77))
    want = np.asarray(family.forward_logits(
        params, jnp.asarray(tokens, jnp.int32), config))[0]
    last = np.asarray(family.forward_logits(
        params, jnp.asarray(tokens, jnp.int32), config, last=5))[0]
    np.testing.assert_allclose(last, want[-5:], atol=1e-5)
    cache = init_kv_cache(cfg, 33, 4)
    padded = np.zeros((1, 128), np.int32)
    padded[0, :77] = tokens
    cos, sin = rope_frequencies(cfg.rope_dim, cfg.max_seq, cfg.rope_theta)
    logits, *_ = prefill(
        params, cache.k, cache.v, jnp.asarray(padded),
        jnp.asarray([77], jnp.int32),
        jnp.arange(1, 33, dtype=jnp.int32)[None], cos, sin, None, cache.i,
        cfg=cfg)
    np.testing.assert_allclose(np.asarray(logits)[0], want[-1], atol=2e-4)
    for control in (dict(dense=True), dict(topk=8), dict(relu=False),
                    dict(whole_norm=True), dict(renormalise=False),
                    dict(index_dtype="bfloat16")):
        other = np.asarray(family.forward_logits(
            params, jnp.asarray(tokens, jnp.int32), config, **control))[0]
        assert np.abs(other - want).max() > 1e-2, control
