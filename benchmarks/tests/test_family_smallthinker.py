"""The family ``smallthinker``: ``family_of`` takes the published
configuration (every key of the catalog row, depth alone reduced) and
refuses one key more, the import refuses a tree without layer groups,
the counts are the published model's by hand arithmetic (a window
layer's pairs and keys, not causal's), the new reader reads a recorded
gap, and the toy cell runs through ``serve.run`` on the CPU."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import families

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"),
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert module.NAME == name
    return module


PUBLISHED = _config("smallthinker-21b-a3b-int8-24l")


def test_family_of_takes_the_published_file_and_refuses_one_key_more():
    family = families.family_of(PUBLISHED)
    assert family.__name__ == "benchmarks.families.smallthinker"
    with pytest.raises(ValueError, match="does not read.*qk_norm"):
        families.family_of(dict(PUBLISHED, qk_norm=True))
    # every key of the catalog row at top level, unchanged but the depth
    changed = {k for k, v in PUBLISHED["published"].items()
               if PUBLISHED[k] != v}
    assert changed == {"num_hidden_layers"} == set(PUBLISHED["reduced"])
    assert set(PUBLISHED["published"]) == set(family.CONFIG_KEYS)
    assert (PUBLISHED["published"]["num_hidden_layers"],
            PUBLISHED["num_hidden_layers"]) == (52, 24)
    assert {"router", "window", "secondary_experts"} <= set(
        PUBLISHED["assumed"])
    with pytest.raises(ValueError, match="written for"):
        family.program_config(dict(PUBLISHED, norm_topk_prob=False))
    cfg = family.program_config(PUBLISHED)
    assert (cfg.n_experts, cfg.top_k, cfg.norm_topk_prob, cfg.n_layers,
            cfg.dim, cfg.mlp_dim, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.vocab, cfg.window, cfg.rope_theta) == (
                64, 6, True, 24, 2560, 768, 28, 4, 128, 151936, 4096, 1.5e6)
    # six whole periods of (full NoPE, window, window, window)
    assert cfg.layer_pattern == ("full_nope", "window", "window", "window")
    assert (cfg.group_layers(0), cfg.group_layers(1)) == (6, 18)
    assert (cfg.router_input, cfg.expert_act, cfg.qk_norm) == (
        "attention", "relu", False)
    args, kwargs = family.server_arguments(PUBLISHED, 3)
    assert args == (cfg,) and kwargs["quantize"] == "int8"
    engine = kwargs["engine_config"]
    assert (engine["max_num_seqs"], engine["page_size"],
            engine["max_seq_len"], engine["decode_burst"]) == (
                engine["max_num_seqs"], 64, 12288, 8)
    assert engine["num_pages"] == engine["max_num_seqs"] * 192 + 1
    # a depth that cuts a period short is one period of its own length
    odd = family.program_config(dict(PUBLISHED, num_hidden_layers=6))
    assert odd.layer_pattern == ("full_nope", "window", "window", "window",
                                 "full_nope", "window")


def test_the_family_refuses_a_program_without_layer_groups(tmp_path):
    """On a tree older than the layer pattern the import itself stops,
    without jax, so that ``family_of`` ends the run before the runtime
    starts (the parent of PR 33 has ``moe_mlp_routed`` and none of
    these)."""
    package = tmp_path / "ray_tpu"
    for sub in ("ops", "llm", "models"):
        (package / sub).mkdir(parents=True)
    (package / "__init__.py").write_text("")
    (package / "ops" / "moe.py").write_text("def moe_mlp_routed(): pass\n")
    (package / "llm" / "cache.py").write_text("class KVCache: pass\n")
    (package / "models" / "llama.py").write_text("class LlamaConfig: 0\n")
    code = ("import sys; sys.path.insert(0, %r); sys.path.insert(1, %r)\n"
            "from benchmarks.harness import families\n"
            "import json\n"
            "try:\n"
            "    families.family_of(json.load(open(%r)))\n"
            "except ValueError as e:\n"
            "    assert 'layer_pattern' in str(e), e\n"
            "    assert 'window_group_pages' in str(e), e\n"
            "    assert 'jax' not in sys.modules\n"
            "    print('refused')\n") % (
                str(tmp_path), ROOT, os.path.join(
                    BENCH, "configs", "smallthinker-21b-a3b-int8-24l.json"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=str(tmp_path))
    assert out.stdout.strip() == "refused", out.stderr[-2000:]


def test_counts_are_the_published_models():
    family = families.family_of(PUBLISHED)
    c = PUBLISHED
    # by hand: attention 2 x 2560 x 3584 + 2 x 2560 x 512, router
    # 2560 x 64, an expert 3 x 2560 x 768, two norms, table and head
    attention, expert = 2 * 2560 * 3584 + 2 * 2560 * 512, 3 * 2560 * 768
    assert (attention, expert) == (20971520, 5898240)
    layer = attention + 2560 * 64 + 64 * expert + 2 * 2560
    assert round(layer / 1e6, 1) == 398.6
    assert family.held_params(c) == 24 * layer + 2 * 2560 * 151936 + 2560
    assert round(family.held_params(c) / 1e9, 2) == 10.34
    # the published depth: 21.5B
    assert round(family.held_params(dict(c, num_hidden_layers=52)) / 1e9,
                 1) == 21.5
    met = attention + 2560 * 64 + 6 * expert
    assert family.matmul_params(c) == 24 * met + 2560 * 151936
    assert (family.experts_held(c), family.experts_per_token(c),
            family.window_layers(c)) == (64, 6, 18)
    assert family.program_config(c).n_params() == family.held_params(c)
    # a window layer's pairs, not causal's (ISSUE 33's figures)
    assert family.attended_pairs(8192) == 8192 * 8193 / 2
    assert family.attended_pairs(8192, 4096) == (
        4096 * 4097 / 2 + 4096 * 4096)
    assert round(family.attended_pairs(8192, 4096) / 1e6, 1) == 25.2
    assert round(family.attended_pairs(12288, 4096) / 1e6, 1) == 41.9
    assert round(family.attended_pairs(12288) / 1e6, 1) == 75.5
    assert family.attended_pairs(1000, 4096) == family.attended_pairs(1000)
    # prefill of 8,192 tokens: 2 x tokens x the parameters a token meets,
    # 4 x pairs x 3584 in 6 full and 18 window layers, one position's head
    by_hand = (24 * 2 * 8192 * met
               + 6 * 4 * 3584 * family.attended_pairs(8192)
               + 18 * 4 * 3584 * family.attended_pairs(8192, 4096)
               + 2 * 2560 * 151936)
    assert family.prefill_flops(c, 8192) == pytest.approx(by_hand)
    assert family.window_attention_flops(c, 8192) == (
        18 * 4 * 3584 * family.attended_pairs(8192, 4096))
    causal_everywhere = by_hand + 18 * 4 * 3584 * (
        family.attended_pairs(8192) - family.attended_pairs(8192, 4096))
    assert 0.93 < by_hand / causal_everywhere < 0.95
    assert family.kv_bytes_per_token(c) == 48 * 1024


@pytest.mark.parametrize("rows, live", [(1, 500), (8, 8 * 2000),
                                        (8, 8 * 9000), (2, 12000 + 500)])
def test_routed_decode_step_bytes(rows, live):
    family = families.family_of(PUBLISHED)
    c = PUBLISHED
    touched = 64 * (1 - (58 / 64) ** rows)
    assert family.experts_touched(c, rows) == pytest.approx(touched)
    attention, expert = 20971520, 5898240
    scales = 4 * (24 * ((28 + 8) * 128 + 2560 + touched * (2 * 768 + 2560))
                  + 151936)
    # a full layer reads every live key, a window layer a row's newest
    # 4096 (the mean row's: live / rows)
    keys = 2048 * (6 * live + 18 * rows * min(live / rows, 4096))
    by_hand = (24 * (attention + touched * expert) + 2560 * 151936 + scales
               + 4 * 24 * 2560 * 64 + 2 * (24 * 2 * 2560 + 2560) + keys)
    assert family.routed_decode_step_bytes(c, rows, live, 1) == \
        pytest.approx(by_hand)
    assert by_hand <= family.decode_step_bytes(c, live, 1)
    if live / rows > 4096:      # past the window: fewer keys than cached
        assert keys < live * family.kv_bytes_per_token(c)
    if (rows, live) == (2, 12500):
        # mixed lengths: the mean (6,250) is clipped to 4,096 a row, but
        # the short row has only 500 keys: the count is high, never low
        true = 2048 * (6 * live + 18 * (4096 + 500))
        assert keys > true


def test_the_release_reader_on_a_recorded_gap():
    reader = _reader("kv_release_gap_ms")
    run = {"engine": {"rounds": [{"t": t, "width": 8, "active": 4,
                                  "live": 9000} for t in (0.5, 1.5, 2.5,
                                                          9.0)]},
           "trace": {"t0": 0.0, "t1": 3.0, "idle_gaps": [
               ["rt.engine.release", 0.0006], ["rt.engine.schedule", 0.001],
               ["rt.engine.release.other", 0.5]]}}
    # 0.6 ms over the three rounds started in the stretch
    assert reader.compute(run) == pytest.approx(0.2)
    run["trace"]["idle_gaps"] = [["rt.engine.schedule", 0.001]]
    assert reader.compute(run) == 0.0
    assert reader.compute(dict(run, trace={})) is None
    assert reader.compute({"engine": {"rounds": []},
                           "trace": run["trace"]}) is None
    assert (reader.MOVES, reader.LAYER) == ("tpot_p95_ms",
                                            "LLM replica and engine")


def test_tiny_chat_smallthinker_runs_through_serve_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "tiny-chat-smallthinker", "--seed", str(2**31 + 11), "--seconds",
         "3", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] == 24                  # 8 a second x 3 s
    assert result["device"]["platform"] == "cpu"
    limit = families.family_of(PUBLISHED).MARGIN_LIMIT
    assert result["notes"]["probes"]["margin_limit"] == limit == 0.3
    # float32 at toy size: the probes agree with the reference outright
    assert result["notes"]["probes"]["margin_worst"] <= 0.01
    assert "kv_release_gap_ms" in result["metrics"]
    # shares of a TPU's peak are not read on the CPU
    assert "prefill_roofline" not in result["metrics"]
    assert "expert_decode_roofline" not in result["metrics"]
