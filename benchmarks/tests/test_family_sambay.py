"""The sambay family: the configuration file is read whole and states
every published key at its published value, the counts are the model's
leaf by leaf (3,852 M), the reference agrees with a token-by-token
decoder written a third way (numpy, float64, explicit caches), the
accepted cells declare what the parent's ``BENCHMARK.json`` declared, the
new readers return None where their kernel is absent and read a run
written by hand, and the program agrees with the reference through
``tiny-chat-sambay`` (the serve path, CPU)."""

import importlib.util
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.harness import families, peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "phi4flash-reasonlong-steady"
READERS = ("scan_prefill_roofline", "scan_decode_roofline")
ACCEPTED = ("mistral7b-chat-steady", "mistral7b-train-fsdp2tp2",
            "olmoe-docqa-steady", "smallthinker-mixedlen-steady",
            "deepseekv2-longdoc-steady", "keyevl2-longctx-steady",
            "minicpmsala-longfile-steady", "trinity-agentctx-steady")
PARENT = "d35d10e0cdddf085f8a617c8cab56b082ed6c58e"


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


PUBLISHED = _json("configs", "phi-4-mini-flash-reasoning-bf16.json")
TINY = _json("configs", "tiny-rehearsal-sambay.json")
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
ROW = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
       "intermediate_size": 10240, "layer_norm_eps": 1e-05,
       "max_position_embeddings": 262144, "mb_per_layer": 2,
       "model_type": "phi4flash", "num_attention_heads": 40,
       "num_hidden_layers": 32, "num_key_value_heads": 20,
       "resid_pdrop": 0, "sliding_window": 512,
       "tie_word_embeddings": True, "mlp_bias": False,
       "lm_head_bias": False, "vocab_size": 200064}


def test_the_file_states_every_published_key_and_cuts_nothing():
    family = families.family_of(PUBLISHED)
    assert family.__name__.endswith("sambay")
    with pytest.raises(ValueError, match="does not read.*'extra_width'"):
        families.family_of(dict(PUBLISHED, extra_width=3))
    for key in PUBLISHED:
        assert (key in families.HARNESS_KEYS or key in family.CONFIG_KEYS
                or key.endswith("_why")), key
    assert PUBLISHED["published"] == ROW
    assert {k: PUBLISHED[k] for k in ROW} == ROW
    assert PUBLISHED["reduced"] == {} and "quantize" not in PUBLISHED
    assert PUBLISHED["mamba"] == {"d_state": 16, "d_conv": 4, "expand": 2,
                                  "dt_rank": math.ceil(2560 / 16)}
    for listed in ("mamba", "layers", "pairing", "lambda", "biases",
                   "memory", "norms", "weights", "head"):
        assert listed in PUBLISHED["assumed"], listed
    e = PUBLISHED["engine"]
    assert (e["page_size"], e["max_seq_len"], e["decode_burst"]) == (
        64, 13312, 8) and "prefill_chunk" not in e
    # the full pool holds every slot at max_seq_len
    assert e["num_pages"] == e["max_num_seqs"] * 208 + 1
    kinds = family.layer_kinds(PUBLISHED)
    assert [i for i, k in enumerate(kinds) if k == "scan"] == [
        0, 2, 4, 6, 8, 10, 12, 14, 16]
    assert [i for i, k in enumerate(kinds) if k == "window_diff"] == [
        1, 3, 5, 7, 9, 11, 13, 15]
    assert kinds[17] == "full_diff" and kinds[18:] == ("gmu",
                                                       "cross_diff") * 7
    for key, value in (("tie_word_embeddings", False), ("mlp_bias", True),
                       ("num_hidden_layers", 30), ("mb_per_layer", 1)):
        with pytest.raises(ValueError):
            family.program_config(dict(PUBLISHED, **{key: value}))
    with pytest.raises(ValueError, match="bfloat16"):
        family.served_params(None, dict(PUBLISHED, quantize="int8"))


def test_the_counts_are_the_models_leaf_by_leaf():
    import jax

    f, c = families.family_of(PUBLISHED), PUBLISHED
    shapes = jax.eval_shape(
        lambda: f.served_params(jax.random.PRNGKey(0), c))
    by_stack = {name: sum(a.size for a in jax.tree.leaves(tree))
                for name, tree in shapes.items()}
    d, each = 2560, f.mlp_params(c) + 4 * 2560
    assert f.mlp_params(c) == 78_643_200
    assert f.scan_layer_params(c) == 41_241_600
    assert f.memory_unit_params(c) == 26_214_400
    assert f.attention_params(c) == 19_668_864
    assert f.cross_params(c) == 13_112_704
    assert by_stack == {
        "embed": 200064 * d, "final_norm": d, "final_norm_bias": d,
        "scan_layers": 9 * (f.scan_layer_params(c) + each),
        # and ``lam0``, a constant a layer that is no parameter
        "layers": 9 * (f.attention_params(c) + each + 1),
        "gmu_layers": 7 * (f.memory_unit_params(c) + each),
        "cross_layers": 7 * (f.cross_params(c) + each + 1)}
    assert sum(by_stack.values()) - 16 == f.held_params(c) == 3_852_562_944
    assert f.program_config(c).n_params() == f.held_params(c)
    assert "lm_head" not in shapes
    bytes_held = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(shapes))
    assert 7.70e9 < bytes_held < 7.72e9
    assert f.kv_bytes_per_token(c) == 9 * 5120
    assert f.state_bytes_per_slot(c) == 9 * 19 * 5120 * 4
    assert f.scan_decode_bytes(c, 1.0) == 2 * 9 * 16 * 5120 * 4
    # a 1,024-token prompt: half the stack's products for every token
    assert 0.5 < f.prefill_flops(c, 1024) / (
        2 * 1024 * (f.held_params(c) - 200064 * d)) < 0.62


def _token_by_token(params, tokens, c):
    """A third way: one token at a time through every layer, the caches
    explicit Python lists, numpy float64."""
    mb = c["mamba"]
    N, taps, R = mb["d_state"], mb["d_conv"], mb["dt_rank"]
    h_, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    hd, eps = c["hidden_size"] // h_, c["layer_norm_eps"]
    rep, window = h_ // kvh, c["sliding_window"]
    p = {s: {k: np.asarray(v, np.float64) for k, v in tree.items()}
         if isinstance(tree, dict) else np.asarray(tree, np.float64)
         for s, tree in params.items()}
    kinds = families.family_of(c).layer_kinds(c)

    def ln(x, w, b):
        x = x - x.mean()
        return x / np.sqrt((x * x).mean() + eps) * w + b

    def silu(x):
        return x / (1 + np.exp(-x))

    def softmax(x):
        x = np.exp(x - x.max())
        return x / x.sum()

    def diff_attend(q, keys, values, lp, i):
        lam0 = 0.8 - 0.6 * math.exp(-0.3 * i)
        lam = (math.exp(lp["lam_q1"] @ lp["lam_k1"])
               - math.exp(lp["lam_q2"] @ lp["lam_k2"]) + lam0)
        out = []
        for pair in range(h_ // 2):
            g = pair // rep
            a1 = softmax(np.array([q[2 * pair] @ k[2 * g] for k in keys])
                         / math.sqrt(hd))
            a2 = softmax(np.array([q[2 * pair + 1] @ k[2 * g + 1]
                                   for k in keys]) / math.sqrt(hd))
            V = np.array([np.concatenate([v[2 * g], v[2 * g + 1]])
                          for v in values])
            o = (a1 - lam * a2) @ V
            out.append(o / np.sqrt((o * o).mean() + eps) * lp["sub_norm"]
                       * (1 - lam0))
        return np.einsum("pk,pkd->d", np.array(out), lp["wo"]) + lp["bo"]

    state = {}
    logits = []
    for t, token in enumerate(tokens):
        x = p["embed"][token]
        place = {"scan_layers": 0, "layers": 0, "gmu_layers": 0,
                 "cross_layers": 0}
        m = shared = None
        for i, kind in enumerate(kinds):
            stack = {"scan": "scan_layers", "gmu": "gmu_layers",
                     "cross_diff": "cross_layers"}.get(kind, "layers")
            lp = {k: v[place[stack]] for k, v in p[stack].items()}
            place[stack] += 1
            h = ln(x, lp["attn_norm"], lp["attn_norm_bias"])
            if kind == "scan":
                uz = h @ lp["w_in"]
                E = uz.size // 2
                past = state.setdefault((i, "conv"),
                                        [np.zeros(E)] * (taps - 1))
                rows = past + [uz[:E]]
                state[(i, "conv")] = rows[1:]
                u = silu(sum(lp["conv_w"][j] * rows[j]
                             for j in range(taps)) + lp["conv_b"])
                rbc = u @ lp["w_x"]
                dt = np.log1p(np.exp(rbc[:R] @ lp["w_dt"] + lp["b_dt"]))
                s = state.get((i, "s"), np.zeros((N, E)))
                s = (np.exp(dt[None] * -np.exp(lp["a_log"])) * s
                     + (dt * u)[None] * rbc[R:R + N, None])
                state[(i, "s")] = s
                m = rbc[R + N:] @ s + lp["d_skip"] * u
                x = x + (m * silu(uz[E:])) @ lp["wo"].reshape(E, -1)
            elif kind == "gmu":
                x = x + (m * silu(h @ lp["wg"])) @ lp["wo"].reshape(
                    m.size, -1)
            else:
                q = np.einsum("d,dhk->hk", h, lp["wq"]) + lp["bq"]
                if kind != "cross_diff":
                    keys, values = state.setdefault((i, "kv"), ([], []))
                    keys.append(np.einsum("d,dhk->hk", h, lp["wk"])
                                + lp["bk"])
                    values.append(np.einsum("d,dhk->hk", h, lp["wv"])
                                  + lp["bv"])
                    if kind == "window_diff":
                        del keys[:-window], values[:-window]
                    shared = (keys, values)
                x = x + diff_attend(q, *shared, lp, i)
            h = ln(x, lp["mlp_norm"], lp["mlp_norm_bias"])
            x = x + (silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) \
                @ lp["w_down"]
        logits.append(ln(x, p["final_norm"], p["final_norm_bias"])
                      @ p["embed"].T)
    return np.array(logits)


def test_the_reference_is_a_token_by_token_decoder():
    import jax
    import jax.numpy as jnp

    f = families.family_of(TINY)
    params = f.served_params(jax.random.PRNGKey(1), TINY)
    tokens = [int(t) for t in np.random.default_rng(2).integers(1, 256, 37)]
    got = np.asarray(f.forward_logits(
        params, jnp.asarray([tokens], jnp.int32), TINY))[0]
    want = _token_by_token(params, tokens, TINY)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # ``last``: the same rows; every control moves the logits
    last = np.asarray(f.forward_logits(
        params, jnp.asarray([tokens], jnp.int32), TINY, last=5))[0]
    np.testing.assert_allclose(last, got[-5:], rtol=1e-5, atol=1e-5)
    for control in (dict(window_full=True), dict(lam_zero=True),
                    dict(m_after_gate=True), dict(cross_fresh=True),
                    dict(reset_every=16)):
        moved = np.asarray(f.forward_logits(
            params, jnp.asarray([tokens], jnp.int32), TINY, last=5,
            **control))[0]
        assert np.abs(moved - last).max() > 1e-2, control


def test_the_accepted_cells_declare_what_the_parents_file_declared():
    sys.path.insert(0, BENCH)
    import run as bench_run

    shown = subprocess.run(
        ["git", "-C", ROOT, "show", f"{PARENT}:BENCHMARK.json"],
        capture_output=True, text=True)
    if shown.returncode:
        pytest.skip("the parent commit is not in this checkout")
    parent = json.loads(shown.stdout)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        contract = json.load(fh)
    for cell in ACCEPTED:
        for group in ("end_to_end", "per_layer"):
            before = {m["name"] for m in parent[group]
                      if cell in m.get("workloads", [cell])}
            assert bench_run.declared_metrics(cell, group) == before, cell
    # nothing that was there changed but for the cell's name appended
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(parent[group], contract[group]):
            if "workloads" in old and CELL in new["workloads"]:
                assert new["workloads"] == old["workloads"] + [CELL]
                new = dict(new, workloads=old["workloads"])
            assert new == old
    added = [m for m in contract["per_layer"][len(parent["per_layer"]):]]
    assert [m["name"] for m in added] == list(READERS)
    assert all(m["workloads"] == [CELL] for m in added)
    assert contract["workloads"][-1]["name"] == CELL
    assert contract["workloads"][-1]["chips"] == 1
    assert len(contract["workloads"][-1]["why"]) <= 200
    assert contract["run_seconds"] == parent["run_seconds"]


def _run(prompts, rounds, programs, device_ops):
    """A traced run written by hand: the prompts' prefills one second
    each from t = 1, the stretch [0, 10]."""
    return {
        "config": PUBLISHED,
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "engine": {"finished": [
            {"rid": str(i), "arrival": float(i), "first": i + 1.0,
             "done": i + 2.0, "tokens": 8, "prompt_tokens": n}
            for i, n in enumerate(prompts)], "rounds": rounds},
        "trace": {"t0": 0.0, "t1": 10.0, "span_s": 10.0, "busy_s": 5.0,
                  "programs": programs, "device_ops": device_ops}}


def test_the_new_readers_read_a_run_and_nothing_where_nothing_is():
    f = families.family_of(PUBLISHED)
    chip = peaks.peaks_of("TPU v5 lite")
    prompts = [1024, 12000]
    rounds = [{"t": 1.0, "width": 8, "active": 20, "live": 20 * 2000},
              {"t": 2.0, "width": 4, "active": 3, "live": 3 * 9000}]
    prefill, decode = (_reader(n) for n in READERS)
    # kernels that ran exactly at the HBM peak read 100%
    scan_s = sum(f.scan_prefill_bytes(PUBLISHED, n) for n in prompts) \
        / chip["hbm_bytes_per_s"]
    state_s = sum(r["width"] * f.scan_decode_bytes(PUBLISHED, r["active"])
                  for r in rounds) / chip["hbm_bytes_per_s"]
    run = _run(prompts, rounds, {
        "jit_prefill_sample": {"seconds": 1.0, "runs": 2},
        "jit_decode_burst": {"seconds": 1.0, "runs": 2}},
        [["rt_scan_prefill", scan_s], ["rt_scan_decode", state_s]])
    assert prefill.compute(run) == pytest.approx(100.0)
    assert decode.compute(run) == pytest.approx(100.0)
    assert (prefill.MOVES, decode.MOVES) == ("ttft_p95_ms", "tpot_p95_ms")
    for reader in (prefill, decode):
        assert (reader.UNIT, reader.SOURCE, reader.LAYER, reader.KINDS) == (
            "%", "device_trace", "Kernels", ("serve",))
        assert reader.compute({}) is None
        assert reader.compute({"config": PUBLISHED}) is None
        assert reader.compute(dict(run, trace={})) is None
        bare = json.loads(json.dumps(run))
        bare["trace"]["device_ops"] = [["fusion", 1.0]]
        assert reader.compute(bare) is None      # the parent's program
        cpu = json.loads(json.dumps(run))
        cpu["device"]["platform"] = "cpu"
        assert reader.compute(cpu) is None
    # another family's run, as recorded on the chip: no such kernel
    for name in ("recorded_deepseek_v2_run.json", "recorded_olmoe_run.json"):
        other = _json("tests", name)
        other.setdefault("config", _json(
            "configs", "deepseek-v2-ep4-int8-9l.json" if "deepseek" in name
            else "olmoe-1b-7b-0125-int8.json"))
        for reader in (prefill, decode):
            assert reader.compute(other) is None, (name, reader.NAME)
    # the accepted readers this cell lists find this family's counts
    listed = _run(prompts, rounds, {
        "jit_prefill_sample": {"seconds": sum(
            f.prefill_flops(PUBLISHED, n) for n in prompts)
            / chip["bf16_flops"], "runs": 2},
        "jit_decode_burst": {"seconds": 1.0, "runs": 2}},
        [["flash_window_fwd", sum(
            f.window_attention_flops(PUBLISHED, n) for n in prompts)
            / chip["bf16_flops"]]])
    assert _reader("prefill_roofline").compute(listed) == pytest.approx(100.0)
    assert _reader("window_prefill_roofline").compute(listed) \
        == pytest.approx(100.0)


def test_the_cell_and_the_mix_are_the_issues():
    cell = _json("workloads", CELL + ".json")
    mix = _json("traffic", "reasonlong-steady.json")
    assert (cell["config"], cell["traffic"], cell["chips"],
            cell["kind"]) == ("phi-4-mini-flash-reasoning-bf16",
                              "reasonlong-steady", 1, "serve")
    # the issue's own, and its slots
    assert (cell["lead_in_s"], cell["drain_s"]) == (20, 25)
    assert PUBLISHED["engine"]["max_num_seqs"] == 32
    assert mix["arrivals"] == "poisson" and mix["temperature"] == 0.0
    assert mix["shared_prefix"] is None
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 1.0, "min": 128, "max": 12288}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 512,
                                    "sigma": 0.45, "min": 192, "max": 1024}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        run_seconds = json.load(fh)["run_seconds"]
    # the window holds the cycle once
    assert round(cell["rate_rps"] * run_seconds) == mix["cycle_requests"]
    assert mix["mix_seed"] not in {
        _json("traffic", name).get("mix_seed")
        for name in os.listdir(os.path.join(BENCH, "traffic"))
        if name != "reasonlong-steady.json"}
    # the longest prompt and answer fit the engine
    e = PUBLISHED["engine"]
    assert 12288 + 1024 <= e["max_seq_len"]


def test_the_rehearsal_cell_runs_the_serve_path():
    """``tiny-chat-sambay`` through ``run.py`` on the CPU: the serve path
    end to end at toy size; the probes agree with the reference and every
    reader is loaded and called (a share of a TPU's peak is None on the
    CPU)."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "tiny-chat-sambay", "--seconds", "3", "--seed", "5", "--trace",
         "1"], capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    assert line["notes"]["probes"]["margin_worst"] < 1e-3
    assert "window_compiles" in line["metrics"]
    assert not set(READERS) & set(line["metrics"])
