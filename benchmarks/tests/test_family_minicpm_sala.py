"""The minicpm_sala family: the configuration file is read whole, the
counts are the published model's by hand arithmetic (``arithmetic_why``),
a file whose pages are not its blocks is refused, the new readers return
None on an empty run, on another family's run and on the CPU and read a
run written by hand, no roofline reader passes 100% at the counts' own
inputs, and the program agrees with the family's plain reference through
``tiny-chat-minicpm-sala`` (the serve path, CPU, every new reader run)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import families, peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "minicpmsala-longfile-steady"
READERS = ("linear_prefill_roofline", "linear_decode_roofline",
           "block_prefill_roofline", "block_select_device_share")


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


PUBLISHED = _json("configs", "minicpm-sala-int8-12l.json")


def test_family_of_takes_the_file_and_the_file_states_its_cut():
    family = families.family_of(PUBLISHED)
    assert family.__name__.endswith("minicpm_sala")
    with pytest.raises(ValueError, match="does not read.*'extra_width'"):
        families.family_of(dict(PUBLISHED, extra_width=3))
    # every top-level key is the harness's or the family's
    for key in PUBLISHED:
        assert (key in families.HARNESS_KEYS or key in family.CONFIG_KEYS
                or key.endswith("_why")), key
    published = PUBLISHED["published"]
    changed = {k for k, v in published.items() if PUBLISHED[k] != v}
    assert changed == set(PUBLISHED["reduced"]) == {
        "num_hidden_layers", "mixer_types"}
    assert PUBLISHED["num_hidden_layers"] == 12
    assert PUBLISHED["mixer_types"] == published["mixer_types"][9:21]
    assert "".join("S" if m == "minicpm4" else "L"
                   for m in PUBLISHED["mixer_types"]) == "SLLLLLLSSLLL"
    assert PUBLISHED["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "topk": 64, "init_blocks": 1, "window_size": 2048,
        "dense_len": 8192}
    for listed in ("decay", "output_norm", "sparse_config", "dense_len",
                   "weights", "state", "compressed_keys"):
        assert listed in PUBLISHED["assumed"], listed
    assert PUBLISHED["engine"] == {
        "max_num_seqs": 16, "page_size": 64, "max_seq_len": 32768,
        "num_pages": 8193, "decode_burst": 8}
    for key, value in (("attn_use_rope", True), ("use_output_norm", False),
                       ("lightning_nkv", 2)):
        with pytest.raises(ValueError):
            family.program_config(dict(PUBLISHED, **{key: value}))


def test_a_page_that_is_not_a_block_is_refused():
    family = families.family_of(PUBLISHED)
    wrong = dict(PUBLISHED, engine=dict(PUBLISHED["engine"], page_size=16))
    with pytest.raises(ValueError, match="page_size=16.*block_size=64"):
        family.program_config(wrong)
    with pytest.raises(ValueError, match="lists every layer"):
        family.program_config(dict(PUBLISHED, num_hidden_layers=11))


def test_the_program_configuration_is_the_rows_sizes():
    cfg = families.family_of(PUBLISHED).program_config(PUBLISHED)
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.mlp_dim, cfg.vocab) == (
                4096, 12, 32, 2, 128, 16384, 73448)
    assert cfg.layer_pattern == (
        "block_nope",) + ("linear",) * 6 + ("block_nope",) * 2 + (
            "linear",) * 3
    assert (cfg.linear_heads, cfg.n_linear_layers, cfg.n_kv_layers) == (
        32, 9, 3)
    assert (cfg.block_size, cfg.block_topk, cfg.block_kernel,
            cfg.block_stride, cfg.block_init, cfg.block_window,
            cfg.block_dense_len) == (64, 64, 32, 16, 1, 2048, 8192)
    assert cfg.embed_scale == 12 and cfg.logit_divisor == 16
    # the PUBLISHED depth, whatever the cut
    assert cfg.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert cfg.qk_norm and cfg.qk_norm_by_head and cfg.attn_output_gate
    assert cfg.rope_theta == 10000 and cfg.norm_eps == 1e-6
    assert cfg.state_bytes_per_slot == 18_874_368           # 18.9 MB


def test_the_family_refuses_a_program_without_state_layers(tmp_path):
    """On a tree older than this family's seams the import itself stops,
    without jax, so that ``family_of`` ends the run before the runtime
    starts and the parent of PR 46 fails at once in the new cell."""
    package = tmp_path / "ray_tpu"
    (package / "models").mkdir(parents=True)
    (package / "__init__.py").write_text("")
    (package / "models" / "llama.py").write_text(
        "class LlamaConfig: sparse_top_k = 0\n")
    code = ("import sys; sys.path.insert(0, %r); sys.path.insert(1, %r)\n"
            "from benchmarks.harness import families\n"
            "import json\n"
            "try:\n"
            "    families.family_of(json.load(open(%r)))\n"
            "except ValueError as e:\n"
            "    assert 'LlamaConfig.linear_heads' in str(e), e\n"
            "    assert 'jax' not in sys.modules\n"
            "    print('refused')\n") % (
                str(tmp_path), ROOT, os.path.join(
                    BENCH, "configs", "minicpm-sala-int8-12l.json"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=str(tmp_path))
    assert out.stdout.strip() == "refused", out.stderr[-2000:]


def test_counts_are_the_published_models_by_hand():
    """``arithmetic_why``, number by number."""
    f, c = families.family_of(PUBLISHED), PUBLISHED
    d = 4096
    square = d * 32 * 128
    assert square == 16_777_216                                  # 16.78 M
    mlp = 3 * d * 16384
    assert mlp == 201_326_592                                    # 201.3 M
    lightning = 5 * square + mlp
    assert lightning == f.lightning_layer_params(c) == 285_212_672
    sparse = 3 * square + 2 * d * 2 * 128 + mlp
    assert sparse == f.sparse_layer_params(c) == 253_755_392
    table = d * 73448
    held = 9 * lightning + 3 * sparse + 2 * table
    assert held == f.held_params(c) == 3_929_866_240             # 3.93 GB
    assert f.matmul_params(c) == held - table
    # the uncut model by the same arithmetic is the published 9B
    whole = dict(c, **{k: c["published"][k]
                       for k in ("num_hidden_layers", "mixer_types")})
    assert f.held_params(whole) == 24 * lightning + 8 * sparse + 2 * table
    assert 9.4e9 < f.held_params(whole) < 9.6e9
    # 6.66 GFLOP a prefill token through the matrices as held
    assert 2 * (held - 2 * table) == pytest.approx(6.66e9, rel=2e-3)
    # a cached position: K and V of 2 heads of 128 in bf16 and a 16th of
    # a float32 row of sums, 3 layers; a slot's state
    assert f.kv_bytes_per_token(c) == 3 * (1024 + 64) == 3264
    assert f.state_bytes_per_slot(c) == 9 * 32 * 128 * 128 * 4 == 18_874_368


@pytest.mark.parametrize("n", [4096, 12000])
def test_the_counts_functions_at_two_lengths_by_hand(n):
    f, c = families.family_of(PUBLISHED), PUBLISHED
    # the recurrence: k^T v in and q S out, a token, head and layer
    assert f.linear_prefill_flops(c, n) == 9 * n * 32 * 4 * 128 * 128
    dense, chosen = f._attended(c, float(n))
    below = min(n, 8192)
    assert dense == below * (below + 1) / 2
    # from 8,192 on: 63 whole blocks and the own block half full on average
    assert chosen == max(n - 8192, 0) * (63 * 64 + 32.5)
    assert f.block_attention_flops(c, n) == 3 * chosen * 4 * 32 * 128
    keys = 0.0 if n <= 8192 else (n * (n + 1) - 8192 * 8193) / 2 / 16
    assert f.block_score_flops(c, n) == 3 * keys * 2 * 32 * 128
    matrices = 2.0 * n * (f.held_params(c) - 2 * 4096 * 73448)
    assert f.prefill_flops(c, n) == pytest.approx(
        matrices + f.linear_prefill_flops(c, n)
        + 3 * dense * 4 * 32 * 128 + f.block_attention_flops(c, n)
        + f.block_score_flops(c, n) + 2 * 4096 * 73448)
    # the matrices are nearly all of it: 6.66 GFLOP a token
    assert matrices / f.prefill_flops(c, n) > 0.9
    # a decode step: 16 slots' states read and written, 37.7 MB a slot
    assert f.linear_decode_bytes(c, 16) == 16 * 2 * 18_874_368
    assert 2 * 18_874_368 == pytest.approx(37.7e6, rel=2e-3)
    # the chosen K and V rows: 64 blocks a slot and KV head, not the span
    live = 16.0 * n
    sums = 3 * 4 * 256 * live / 16
    assert f.block_decode_bytes(c, 16, live) == sums + 3 * 4 * 256 * (
        16 * min(n, 4096))
    assert f.decode_step_bytes(c, live) > f.matmul_params(c)


def _run(prompts, rounds, programs, ops, config=PUBLISHED, firsts=None):
    firsts = firsts or [(0.5 + i, 1.0 + i) for i in range(len(prompts))]
    return {
        "config": config,
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "engine": {"rounds": rounds, "finished": [
            {"arrival": a, "first": f, "prompt_tokens": n}
            for (a, f), n in zip(firsts, prompts)]},
        "trace": {"t0": 0.0, "t1": 10.0, "busy_s": 4.0,
                  "programs": programs, "device_ops": ops}}


def test_the_readers_read_what_this_family_adds_and_nothing_else():
    f = families.family_of(PUBLISHED)
    chip = peaks.peaks_of("TPU v5 lite")
    prompts = [8300, 12000, 30000]
    rounds = [{"t": 1.0, "width": 8, "active": 8, "live": 8 * 9000},
              {"t": 2.0, "width": 4, "active": 3, "live": 3 * 20000},
              {"t": 2.25, "width": 6, "active": 3, "live": 3 * 20004}]
    firsts = [(0.2, 1.0), (0.5, 2.0), (1.0, 11.0)]
    run = _run(prompts, rounds, {
        "jit_prefill_sample": {"seconds": 3.0, "runs": 3},
        "jit_decode_burst": {"seconds": 0.6, "runs": 3}},
        [["rt_linear_prefill", 0.5], ["flash_block_sparse_fwd", 1.0],
         ["rt_block_score", 0.3], ["rt_sparse_select", 0.1],
         ["rt_linear_decode", 0.03], ["fusion", 1.2]], firsts=firsts)
    lin_prefill, lin_decode, blk_prefill, select = map(_reader, READERS)
    # the prefills as ``sparse_prefill_roofline`` cuts them: the third is
    # cut by the stretch's end at 10.0 (7.5 of its 8.5 seconds inside)
    share = [1.0, 1.0, 7.5 / 8.5]
    for reader, name, seconds in (
            (lin_prefill, "linear_prefill_flops", 0.5),
            (blk_prefill, "block_attention_flops", 1.0)):
        needed = sum(getattr(f, name)(PUBLISHED, n) * s
                     for n, s in zip(prompts, share))
        assert reader.compute(run) == pytest.approx(
            100 * needed / seconds / chip["bf16_flops"])
    assert select.compute(run) == pytest.approx(100 * 0.4 / 4.0)
    steps = 18
    state = sum(r["width"] * f.linear_decode_bytes(PUBLISHED, r["active"])
                for r in rounds) / steps
    assert lin_decode.compute(run) == pytest.approx(
        100 * state / chip["hbm_bytes_per_s"] / (0.03 / (3 * 6)))
    assert [r.MOVES for r in (lin_prefill, lin_decode, blk_prefill,
                              select)] == [
        "ttft_p95_ms", "tpot_p95_ms", "ttft_p95_ms", "ttft_p95_ms"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        contract = json.load(fh)
    for reader in (lin_prefill, lin_decode, blk_prefill, select):
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
        for reader in (lin_prefill, lin_decode, blk_prefill, select):
            assert reader.compute(other) is None, (name, reader.NAME)
    cpu = json.loads(json.dumps(run))
    cpu["device"]["platform"] = "cpu"
    assert lin_prefill.compute(cpu) is None
    assert lin_decode.compute(cpu) is None
    assert blk_prefill.compute(cpu) is None


def test_no_roofline_reader_passes_100_at_the_counts_own_inputs():
    """A program that ran exactly at the chip's published peaks, doing
    exactly what the counts say is needed, reads 100%: the chunked
    form's second half, whole key blocks and padding can only take it
    lower."""
    f, c = families.family_of(PUBLISHED), PUBLISHED
    chip = peaks.peaks_of("TPU v5 lite")
    prompts, rounds = [8300, 9216, 32000], [
        {"t": 1.0, "width": 8, "active": 8, "live": 8 * 9000},
        {"t": 2.0, "width": 3, "active": 2, "live": 2 * 30000}]
    linear_s = sum(f.linear_prefill_flops(c, n) for n in prompts) \
        / chip["bf16_flops"]
    block_s = sum(f.block_attention_flops(c, n) for n in prompts) \
        / chip["bf16_flops"]
    prefill_s = sum(f.prefill_flops(c, n) for n in prompts) \
        / chip["bf16_flops"]
    state_s = sum(r["width"] * f.linear_decode_bytes(c, r["active"])
                  for r in rounds) / chip["hbm_bytes_per_s"]
    run = _run(prompts, rounds, {
        "jit_prefill_sample": {"seconds": prefill_s, "runs": 3},
        "jit_decode_burst": {"seconds": 1.0, "runs": 2}},
        [["rt_linear_prefill", linear_s], ["rt_linear_decode", state_s],
         ["flash_block_sparse_fwd", block_s]])
    for name in ("linear_prefill_roofline", "linear_decode_roofline",
                 "block_prefill_roofline", "prefill_roofline"):
        assert _reader(name).compute(run) == pytest.approx(100.0), name


def test_the_cell_and_the_mix_are_the_issues():
    cell = _json("workloads", CELL + ".json")
    mix = _json("traffic", "longfile-steady.json")
    assert (cell["config"], cell["traffic"], cell["chips"],
            cell["kind"]) == ("minicpm-sala-int8-12l", "longfile-steady", 1,
                              "serve")
    assert (cell["lead_in_s"], cell["drain_s"]) == (20, 20)
    assert mix["arrivals"] == "poisson" and mix["temperature"] == 0.0
    assert mix["shared_prefix"] is None
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 160,
                                    "sigma": 0.6, "min": 32, "max": 512}
    prompt = mix["prompt_tokens"]
    assert (prompt["dist"], prompt["sigma"], prompt["max"]) == (
        "lognormal", 0.4, 32000)
    # the issue's shape, or its rule's one step
    assert (prompt["median"], prompt["min"]) in ((9216, 8256), (6144, 4160))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        run_seconds = json.load(fh)["run_seconds"]
    # the window holds the cycle once, and at least 20 requests
    assert cell["rate_rps"] * run_seconds == mix["cycle_requests"] >= 20
    assert mix["mix_seed"] not in {
        _json("traffic", name).get("mix_seed")
        for name in os.listdir(os.path.join(BENCH, "traffic"))
        if name != "longfile-steady.json"}


def test_the_rehearsal_cell_runs_every_new_reader():
    """``tiny-chat-minicpm-sala`` through ``run.py`` on the CPU: the
    serve path end to end at toy size with the whole published list of
    layers; the probes agree with the reference and every reader is
    loaded and called (a share of a TPU's peak is None on the CPU)."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "tiny-chat-minicpm-sala", "--seconds", "3", "--seed", "5",
         "--trace", "1"], capture_output=True, text=True, timeout=900,
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    assert line["notes"]["probes"]["margin_worst"] < 1e-3
    assert "decode_burst_width" in line["metrics"]
    for name in READERS:
        assert _reader(name).KINDS == ("serve",)
