"""The family ``afmoe``: ``family_of`` takes the published configuration
(every key of the catalog row; depth, dense layers, experts held and
vocabulary reduced), the import refuses a tree without the block, the
counts are the share's by hand arithmetic and by the shapes
``served_params`` makes, the reference is the program at the rehearsal
size, the new reader reads a recorded trace, and the toy cell runs
through ``serve.run`` on the CPU."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import families

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = "trinity-large-preview-ep8-int8-8l"


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"),
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert module.NAME == name
    return module


PUBLISHED = _json("configs", NAME + ".json")
TOY = _json("configs", "tiny-rehearsal-trinity.json")


def test_family_of_takes_the_published_file_and_refuses_one_key_more():
    family = families.family_of(PUBLISHED)
    assert family.__name__ == "benchmarks.families.afmoe"
    with pytest.raises(ValueError, match="does not read.*qk_norm"):
        families.family_of(dict(PUBLISHED, qk_norm=True))
    changed = {k for k, v in PUBLISHED["published"].items()
               if PUBLISHED[k] != v}
    assert changed == set(PUBLISHED["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"}
    assert set(PUBLISHED["published"]) | {"share"} == set(family.CONFIG_KEYS)
    assert PUBLISHED["share"] == {"chips": 8, "routed_experts": 256,
                                  "first_expert": 0, "vocab_size": 200192}
    assert PUBLISHED["vocab_size"] * 8 == 200192
    assert len(PUBLISHED["layer_types"]) == 60
    assert {"weights", "router", "expert_bias", "window", "mup_enabled",
            "load_balance_coeff", "depth_scaled",
            "activations_and_cache"} <= set(PUBLISHED["assumed"])
    for key, other in (("score_func", "softmax"), ("route_norm", False),
                       ("mup_enabled", False), ("n_group", 2)):
        with pytest.raises(ValueError, match="written for"):
            family.program_config(dict(PUBLISHED, **{key: other}))
    cfg = family.program_config(PUBLISHED)
    assert (cfg.n_experts, cfg.experts_held, cfg.top_k, cfg.norm_topk_prob,
            cfg.routed_scale, cfg.n_shared_experts) == (
                256, (0, 32), 4, True, 2.448, 1)
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.dense_mlp_dim, cfg.dim,
            cfg.mlp_dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.vocab, cfg.window, cfg.rope_theta, cfg.norm_eps) == (
                8, 1, 12288, 3072, 3072, 48, 8, 128, 25024, 4096, 1e4, 1e-5)
    assert cfg.layer_pattern == ("window", "window", "window", "full_nope")
    assert (cfg.group_layers(0), cfg.group_layers(1)) == (2, 6)
    assert (cfg.router_score, cfg.router_bias, cfg.post_norms,
            cfg.attn_output_gate, cfg.qk_norm, cfg.qk_norm_by_head) == (
                "sigmoid", True, True, True, True, True)
    assert cfg.embed_scale == pytest.approx(3072 ** 0.5)
    args, kwargs = family.server_arguments(PUBLISHED, 3)
    assert args == (cfg,) and kwargs["quantize"] == "int8"
    assert kwargs["seed_gains"] == family.SEED_GAINS
    engine = kwargs["engine_config"]
    assert (engine["max_num_seqs"], engine["page_size"],
            engine["max_seq_len"], engine["decode_burst"]) == (8, 64, 16384,
                                                               8)
    assert engine["num_pages"] == 8 * 256 + 1


def test_the_family_refuses_a_program_without_the_block(tmp_path):
    """On the parent of PR 52 the import itself stops, without jax, so
    that ``family_of`` ends the run before the runtime starts: the parent
    fails the new cell at once and leaves no process."""
    package = tmp_path / "ray_tpu"
    (package / "models").mkdir(parents=True)
    (package / "__init__.py").write_text("")
    (package / "models" / "llama.py").write_text(
        "class LlamaConfig:\n    experts_held = None\n"
        "    attn_output_gate = False\n")
    code = ("import sys; sys.path.insert(0, %r); sys.path.insert(1, %r)\n"
            "from benchmarks.harness import families\n"
            "import json\n"
            "try:\n"
            "    families.family_of(json.load(open(%r)))\n"
            "except ValueError as e:\n"
            "    assert 'router_score' in str(e), e\n"
            "    assert 'post_norms' in str(e), e\n"
            "    assert 'jax' not in sys.modules\n"
            "    print('refused')\n") % (
                str(tmp_path), ROOT,
                os.path.join(BENCH, "configs", NAME + ".json"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=str(tmp_path))
    assert out.stdout.strip() == "refused", out.stderr[-2000:]


def test_counts_are_the_shares_by_hand_and_by_served_params():
    import jax

    family = families.family_of(PUBLISHED)
    c = PUBLISHED
    attention = 3 * 3072 * 6144 + 2 * 3072 * 1024
    expert, dense = 3 * 3072 * 3072, 3 * 3072 * 12288
    assert (attention, expert, dense) == (62914560, 28311552, 113246208)
    small = 4 * 3072 + 2 * 128
    layer = attention + expert + 3072 * 256 + 256 + 32 * expert + small
    assert round(layer / 1e6, 2) == 998.00
    held = (attention + dense + small + 7 * layer + 2 * 3072 * 25024 + 3072)
    assert family.held_params(c) == held
    assert round(held / 1e9, 2) == 7.32
    # the shapes the replica makes: every leaf but the int8 scales
    shapes = jax.eval_shape(
        lambda: family.served_params(jax.random.PRNGKey(0), c))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    counted = sum(leaf.size for path, leaf in flat
                  if getattr(path[-1], "key", None) != "s")
    assert counted == held
    # the published model: 54 whole expert layers, 6 dense, table and head
    whole = attention + expert + 3072 * 256 + 256 + 256 * expert + small
    published = (6 * (attention + dense + small) + 54 * whole
                 + 2 * 3072 * 200192 + 3072)
    assert round(published / 1e9, 1) == 398.6
    # what a token is multiplied with here: 0.5 routed experts on average
    assert family.held_experts_per_token(c) == 0.5
    met = attention + expert + 3072 * 256 + 0.5 * expert
    assert family.matmul_params(c) == (attention + dense + 7 * met
                                       + 3072 * 25024)
    assert (family.experts_held(c), family.window_layers(c),
            family.dense_layers(c), family.expert_layers(c)) == (32, 6, 1, 7)
    # a sliding layer's pairs, not causal's
    assert family.attended_pairs(8192, 4096) == (
        4096 * 4097 / 2 + 4096 * 4096)
    assert family.attended_pairs(1000, 4096) == family.attended_pairs(1000)
    by_hand = (2 * 8192 * (attention + dense + 7 * met)
               + 2 * 4 * 6144 * family.attended_pairs(8192)
               + 6 * 4 * 6144 * family.attended_pairs(8192, 4096)
               + 2 * 3072 * 25024)
    assert family.prefill_flops(c, 8192) == pytest.approx(by_hand)
    assert family.window_attention_flops(c, 8192) == (
        6 * 4 * 6144 * family.attended_pairs(8192, 4096))
    assert family.kv_bytes_per_token(c) == 8 * 4096
    assert family.train_flops_per_token(c, 4096) > 6 * family.matmul_params(c)


@pytest.mark.parametrize("rows, live", [(1, 500), (8, 8 * 2000),
                                        (8, 8 * 9000), (2, 12000 + 500)])
def test_routed_decode_step_bytes(rows, live):
    family = families.family_of(PUBLISHED)
    c = PUBLISHED
    touched = 32 * (1 - (252 / 256) ** rows)
    assert family.experts_touched(c, rows) == pytest.approx(touched)
    if rows == 8:
        assert 0.9 < touched / 4 < 1.0       # "4 of 32 on average"
    attention, expert, dense = 62914560, 28311552, 113246208
    attention_scales = 4 * ((2 * 48 + 2 * 8) * 128 + 3072)
    scales = (8 * attention_scales + 4 * (2 * 12288 + 3072)
              + 7 * 4 * (touched + 1) * (2 * 3072 + 3072) + 4 * 25024)
    keys = 4096 * (2 * live + 6 * rows * min(live / rows, 4096))
    by_hand = (attention + dense + 7 * (attention + expert
                                        + touched * expert)
               + 3072 * 25024 + scales + 4 * 7 * (3072 * 256 + 256)
               + 2 * (8 * (4 * 3072 + 256) + 3072) + keys)
    assert family.routed_decode_step_bytes(c, rows, live, 1) == \
        pytest.approx(by_hand)
    assert by_hand <= family.decode_step_bytes(c, live, 1)


def test_the_reference_is_the_program_at_the_rehearsal_size():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm.cache import init_kv_cache
    from ray_tpu.llm.runner import prefill
    from ray_tpu.ops import rope_frequencies

    family = families.family_of(TOY)
    cfg = family.program_config(TOY)
    assert (cfg.n_experts, cfg.experts_held, cfg.n_dense_layers) == (
        16, (8, 8), 1)
    params = family.served_params(jax.random.PRNGKey(5), TOY)
    tokens = [int(t) for t in np.random.default_rng(1).integers(1, 256, 45)]
    padded = np.zeros((1, 64), np.int32)
    padded[0, :45] = tokens
    full = np.zeros((1, 32), np.int32)
    full[0, :12] = 1 + np.arange(12)
    first = (45 - 16 + 1) // 4
    window = np.zeros((1, 32), np.int32)
    window[0, first:12] = 1 + np.arange(12 - first)
    cache = init_kv_cache(cfg, (40, 40), 4)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    got, *_ = prefill(params, cache.k, cache.v, jnp.asarray(padded),
                      jnp.asarray([45], jnp.int32),
                      (jnp.asarray(full), jnp.asarray(window)), cos, sin,
                      cfg=cfg)
    want = family.forward_logits(params, jnp.asarray([tokens], jnp.int32),
                                 TOY, last=1)[0, -1]
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want),
                               atol=1e-4)
    loss = family.next_token_loss(params, jnp.asarray([tokens], jnp.int32),
                                  TOY)
    assert np.isfinite(float(loss)) and float(loss) > 0


def test_the_grouped_share_reader_on_a_recorded_trace():
    reader = _reader("expert_gmm_device_share")
    run = {"trace": {"busy_s": 2.0, "device_ops": [
        ["fusion", 0.9], ["rt_moe_gmm", 0.5], ["flash_window_fwd", 0.3]]}}
    assert reader.compute(run) == pytest.approx(25.0)
    # a parent's program, a dense configuration, a trace without the
    # kernel, no trace at all: nothing, never an exception
    for nothing in ({"trace": {"busy_s": 2.0, "device_ops": [
            ["fusion", 0.9]]}}, {"trace": {"busy_s": 0.0, "device_ops": [
                ["rt_moe_gmm", 0.5]]}}, {"trace": {}}, {"trace": None}, {}):
        assert reader.compute(nothing) is None
    assert (reader.MOVES, reader.LAYER, reader.UNIT, reader.SOURCE) == (
        "ttft_p95_ms", "Kernels", "%", "device_trace")
    bench = _json("..", "BENCHMARK.json")
    entry = [m for m in bench["per_layer"] if m["name"] == reader.NAME]
    assert entry and entry[0]["workloads"] == ["trinity-agentctx-steady"]


def test_the_cell_adds_itself_and_changes_no_accepted_cell():
    """PR 41's and PR 51's lesson: a per-layer entry without a
    ``workloads`` list, or with an accepted cell in a new entry's list,
    ends every accepted cell's traced run on the parent."""
    sys.path.insert(0, BENCH)
    import run as bench_run

    bench = _json("..", "BENCHMARK.json")
    new = "trinity-agentctx-steady"
    assert all("workloads" in m for m in bench["per_layer"])
    for group in ("per_layer", "end_to_end"):
        declared = bench_run.declared_metrics(new, group)
        assert declared, group
    assert bench_run.declared_metrics(new, "end_to_end") == {
        "ttft_p95_ms", "tpot_p95_ms", "serve_tok_s", "setup_s"}
    assert {"prefill_roofline", "expert_decode_roofline",
            "window_prefill_roofline", "kv_release_gap_ms",
            "expert_gmm_device_share", "window_compiles"} <= \
        bench_run.declared_metrics(new, "per_layer")
    cell = _json("workloads", new + ".json")
    mix = _json("traffic", cell["traffic"] + ".json")
    assert cell["rate_rps"] * bench["run_seconds"] == mix["cycle_requests"]
    assert (cell["chips"], cell["lead_in_s"], cell["drain_s"]) == (1, 20, 20)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": mix[
        "prompt_tokens"]["median"], "sigma": 0.6, "min": 2048, "max": 16000}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 128,
                                    "sigma": 0.6, "min": 32, "max": 384}
    assert mix["mix_seed"] == 20261003


def test_tiny_chat_trinity_runs_through_serve_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "tiny-chat-trinity", "--seed", str(2**31 + 11), "--seconds", "3",
         "--trace", "1"],
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
    assert "kv_release_gap_ms" in result["metrics"]
    # shares of a TPU's peak are not read on the CPU
    assert "prefill_roofline" not in result["metrics"]
    assert "expert_decode_roofline" not in result["metrics"]
