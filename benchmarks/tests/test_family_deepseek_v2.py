"""The deepseek_v2 family: the configuration file is read whole, the
counts are the published model's share by hand arithmetic, the two new
readers read a recorded trace, no roofline reader passes 100% at the
counts' own inputs, and the program agrees with the family's plain
reference through ``tiny-chat-deepseek-v2`` (the serve path, CPU)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import families, peaks

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


PUBLISHED = _config("deepseek-v2-ep4-int8-9l")
with open(os.path.join(BENCH, "tests", "recorded_deepseek_v2_run.json")) as _f:
    RECORDED = json.load(_f)


def test_family_of_takes_the_file_and_the_file_states_its_cut():
    family = families.family_of(PUBLISHED)
    assert family.__name__.endswith("deepseek_v2")
    with pytest.raises(ValueError, match="does not read.*'extra_width'"):
        families.family_of(dict(PUBLISHED, extra_width=3))
    # every width is the catalog row's; the three cuts are listed
    published = PUBLISHED["published"]
    changed = {k for k, v in published.items() if PUBLISHED[k] != v}
    assert changed == set(PUBLISHED["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert (PUBLISHED["num_hidden_layers"], PUBLISHED["n_routed_experts"],
            PUBLISHED["vocab_size"]) == (9, 40, 25600)
    share = family.share_of(PUBLISHED)
    assert share == {"chips": 4, "routed_experts": 160, "first_expert": 0,
                     "vocab_size": 102400}
    assert share["routed_experts"] == published["n_routed_experts"]
    assert share["vocab_size"] == published["vocab_size"]
    # floors of a model_config cut: 4 expert layers, 8 experts, 1/8 vocab
    assert family.expert_layers(PUBLISHED) >= 4
    assert family.experts_held(PUBLISHED) >= 8
    assert 8 * PUBLISHED["vocab_size"] >= published["vocab_size"]
    with pytest.raises(ValueError, match="norm_topk_prob"):
        family.program_config(dict(PUBLISHED, norm_topk_prob=True))


def test_the_family_refuses_a_program_without_latent_attention(tmp_path):
    """On a tree older than this family's seams the import itself stops,
    without jax, so that ``family_of`` ends the run before the runtime
    starts and the parent of PR 35 fails at once in the new cell."""
    package = tmp_path / "ray_tpu"
    for sub in ("ops", "llm", "models"):
        (package / sub).mkdir(parents=True)
    (package / "__init__.py").write_text("")
    (package / "ops" / "moe.py").write_text("def moe_mlp_routed(): pass\n")
    (package / "ops" / "rotary.py").write_text("def rope_frequencies(): 0\n")
    (package / "models" / "llama.py").write_text("class LlamaConfig: 0\n")
    code = ("import sys; sys.path.insert(0, %r); sys.path.insert(1, %r)\n"
            "from benchmarks.harness import families\n"
            "import json\n"
            "try:\n"
            "    families.family_of(json.load(open(%r)))\n"
            "except ValueError as e:\n"
            "    assert 'LlamaConfig.experts_held' in str(e), e\n"
            "    assert 'jax' not in sys.modules\n"
            "    print('refused')\n") % (
                str(tmp_path), ROOT, os.path.join(
                    BENCH, "configs", "deepseek-v2-ep4-int8-9l.json"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=str(tmp_path))
    assert out.stdout.strip() == "refused", out.stderr[-2000:]


def test_counts_are_the_published_models_share_by_hand():
    f, c = families.family_of(PUBLISHED), PUBLISHED
    d, h = 5120, 128
    attention = (d * 1536 + 1536 * h * 192 + d * 576 + 512 * h * 256
                 + h * 128 * d)
    assert attention == f._attention_params(c) == 149_225_472
    assert f._expert_params(c) == 3 * d * 1536 == 23_592_960
    assert f._shared_params(c) == 2 * 23_592_960
    assert f._dense_params(c) == 3 * d * 12288
    layer = attention + 47_185_920 + d * 160 + 40 * 23_592_960
    assert layer == 1_140_948_992                       # 1,140.9 M
    held = (attention + 3 * d * 12288) + 8 * layer + 2 * d * 25600
    assert held == f.held_params(c) == 9_727_705_088    # 9.73 GB at int8
    # the uncut model by the same arithmetic is the published 236 B
    whole = dict(c["published"], share=None)
    assert 235.5e9 < f.held_params(whole) < 236.0e9
    assert f.held_experts_per_token(c) == 1.5
    assert f.softmax_scale(c) == pytest.approx(0.114721, abs=1e-6)
    assert f.softmax_scale(c, False) == pytest.approx(192 ** -0.5)
    # a prefill token's operations in one layer: 252 of 715 MFLOP are
    # attention at 6,144 tokens, 671 of 1,134 at 16,384 (the issue's)
    per_pair = 2 * h * (192 + 128)
    for n, attn, total in ((6144, 252, 715), (16384, 671, 1134)):
        a = f.latent_attention_flops(c, n) / 9 / n
        assert a == per_pair * (n + 1) / 2
        assert a / 1e6 == pytest.approx(attn, abs=1)
        rest = 2 * (attention + 47_185_920 + d * 160 + 1.5 * 23_592_960)
        assert (a + rest) / 1e6 == pytest.approx(total, abs=3)
    assert f.prefill_flops(c, 7355) / 1e12 == pytest.approx(52.3, abs=0.2)
    # a cached position: 9 layers x 576 values x 2 bytes, against the
    # 128 heads of 192 + 128 it stands for
    assert f.latent_bytes_per_token(c) == 9 * 576 * 2 == 10_368
    assert 9 * 128 * 320 * 2 // f.latent_bytes_per_token(c) == 71
    moved, operations = f.latent_decode_cost(c, 1000.0)
    assert moved == 10_368_000
    assert operations == 9 * 1000 * 128 * 2 * (576 + 512)
    # 242 operations a byte: the v5e's own ratio (197e12 / 819e9 = 241)
    assert operations / moved == pytest.approx(241.8, abs=0.1)


@pytest.mark.parametrize("rows, live", [(1, 2048), (8, 8 * 7000),
                                        (8, 8 * 16384)])
def test_routed_decode_step_bytes_by_hand(rows, live):
    f, c = families.family_of(PUBLISHED), PUBLISHED
    touched = 40 * (1 - (1 - 6 / 160) ** rows)
    assert f.experts_touched(c, rows) == pytest.approx(touched)
    assert f.experts_touched(c, 10_000) == pytest.approx(40)
    d = 5120
    matrices = ((149_225_472 + 3 * d * 12288)
                + 8 * (149_225_472 + 47_185_920 + touched * 23_592_960)
                + d * 25600)
    got = f.routed_decode_step_bytes(c, rows, live, 1)
    assert got > matrices + live * 10_368 + 4 * 8 * d * 160
    assert got < 1.01 * (matrices + live * 10_368 + 4 * 8 * d * 160)
    # never more than every held matrix once
    assert got <= f.decode_step_bytes(c, live, 1) * 1.001


def _recorded():
    return dict(json.loads(json.dumps(RECORDED)), config=PUBLISHED)


def test_the_two_new_readers_on_a_recorded_trace():
    f = families.family_of(PUBLISHED)
    chip = peaks.peaks_of("TPU v5 lite")
    run = _recorded()
    decode, prefill = (_reader("mla_decode_roofline"),
                       _reader("mla_prefill_roofline"))
    ops = dict(run["trace"]["device_ops"])
    # decode, by hand: the larger of bytes over bandwidth and operations
    # over peak, a step, over the kernel's seconds a step
    rounds = run["engine"]["rounds"]
    steps = sum(r["width"] for r in rounds)
    needed = sum(r["width"] * max(
        r["live"] * 10_368 / chip["hbm_bytes_per_s"],
        r["live"] * 9 * 128 * 2 * 1088 / chip["bf16_flops"])
        for r in rounds) / steps
    runs = run["trace"]["programs"]["jit_decode_burst"]["runs"]
    measured = ops["rt_mla_decode"] / (runs * steps / len(rounds))
    assert decode.compute(run) == pytest.approx(100 * needed / measured)
    assert 5 < decode.compute(run) < 100
    # prefill, by hand
    prompts = [r["prompt_tokens"] for r in run["engine"]["finished"]]
    flops = (sum(f.latent_attention_flops(PUBLISHED, n) for n in prompts)
             / len(prompts)
             * run["trace"]["programs"]["jit_prefill_sample"]["runs"])
    assert prefill.compute(run) == pytest.approx(
        100 * flops / ops["flash_mla_fwd"] / chip["bf16_flops"])
    assert 5 < prefill.compute(run) < 100
    for reader in (decode, prefill):
        assert (reader.UNIT, reader.SOURCE, reader.LAYER) == (
            "%", "device_trace", "Kernels")
        # a program without the kernel (the parent), no trace, the CPU:
        # nothing to read, nothing raised
        bare = _recorded()
        bare["trace"]["device_ops"] = [["fusion", 1.0]]
        assert reader.compute(bare) is None
        assert reader.compute(dict(_recorded(), trace={})) is None
        cpu = _recorded()
        cpu["device"]["platform"] = "cpu"
        assert reader.compute(cpu) is None
        other = dict(_recorded(), config=_config("olmoe-1b-7b-0125-int8"))
        assert reader.compute(other) is None
    assert decode.MOVES == "tpot_p95_ms" and prefill.MOVES == "ttft_p95_ms"
    assert decode.KERNEL == "rt_mla_decode"
    assert prefill.KERNEL == "flash_mla_fwd"


def test_no_roofline_reader_passes_100_at_the_counts_own_inputs():
    """A program that ran exactly at the chip's published peaks, doing
    exactly what the counts say is needed, reads 100%: padding, blocks
    computed whole and anything else a real program does can only take
    it lower."""
    f, c = families.family_of(PUBLISHED), PUBLISHED
    chip = peaks.peaks_of("TPU v5 lite")
    prompts, rounds = [2048, 7355, 16000], [
        {"t": 1.0, "width": 8, "active": 8, "live": 8 * 7000},
        {"t": 2.0, "width": 3, "active": 2, "live": 2 * 16000}]
    steps = sum(r["width"] for r in rounds)
    attention_s = sum(f.latent_attention_flops(c, n) for n in prompts) \
        / chip["bf16_flops"]
    prefill_s = sum(f.prefill_flops(c, n) for n in prompts) \
        / chip["bf16_flops"]
    kernel_s = sum(r["width"] * max(
        f.latent_decode_cost(c, r["live"])[0] / chip["hbm_bytes_per_s"],
        f.latent_decode_cost(c, r["live"])[1] / chip["bf16_flops"])
        for r in rounds)
    step_s = sum(r["width"] * f.routed_decode_step_bytes(
        c, r["active"], r["live"], 1) for r in rounds) \
        / chip["hbm_bytes_per_s"]
    run = {
        "config": c, "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "engine": {"rounds": rounds, "finished": [
            {"first": 1.0 + i, "prompt_tokens": n}
            for i, n in enumerate(prompts)]},
        "trace": {"t0": 0.0, "t1": 10.0, "programs": {
            "jit_prefill_sample": {"seconds": prefill_s, "runs": 3},
            "jit_decode_burst": {"seconds": step_s, "runs": 2}},
            "device_ops": [["flash_mla_fwd", attention_s],
                           ["rt_mla_decode", kernel_s]]}}
    for name in ("mla_decode_roofline", "mla_prefill_roofline",
                 "prefill_roofline", "expert_decode_roofline"):
        assert _reader(name).compute(run) == pytest.approx(100.0), name
    assert steps == 11


def test_the_cell_and_the_mix_are_the_issues():
    with open(os.path.join(BENCH, "workloads",
                           "deepseekv2-longdoc-steady.json")) as fh:
        cell = json.load(fh)
    with open(os.path.join(BENCH, "traffic", "longdoc-steady.json")) as fh:
        mix = json.load(fh)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "deepseek-v2-ep4-int8-9l", "longdoc-steady", 1)
    # the issue's lead-in, as issued: one seed in six then read 5% fewer
    # tokens a second (PERF.md section 7 asks the issue's writer)
    assert (cell["lead_in_s"], cell["drain_s"]) == (6, 20)
    # the issue's median of 6,144 fell to 4,096 by the issue's own rule
    # (0.8 of the knee gave 16 requests a window): PERF.md section 4
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 4096,
                                    "sigma": 0.6, "min": 2048, "max": 16000}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 64,
                                    "sigma": 0.6, "min": 16, "max": 192}
    assert (mix["arrivals"], mix["mix_seed"], mix["shared_prefix"]) == (
        "poisson", 20260930, None)
    # the window holds the cycle once: rate x run_seconds (40)
    assert mix["cycle_requests"] == cell["rate_rps"] * 40 == 27
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        contract = json.load(fh)
    declared = [m["name"] for m in contract["per_layer"]
                if cell["name"] in m.get("workloads", ())]
    assert {"mla_decode_roofline", "mla_prefill_roofline",
            "prefill_roofline", "expert_decode_roofline"} <= set(declared)
    engine = PUBLISHED["engine"]
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= engine["max_seq_len"]
    assert engine["num_pages"] == 1 + engine["max_num_seqs"] * (
        engine["max_seq_len"] // engine["page_size"])


def test_tiny_chat_deepseek_v2_runs_through_serve_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "tiny-chat-deepseek-v2", "--seed", str(2**31 + 11), "--seconds",
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
    # shares of a TPU's peak are not read on the CPU
    for name in ("mla_decode_roofline", "mla_prefill_roofline",
                 "prefill_roofline", "expert_decode_roofline"):
        assert name not in result["metrics"]
