"""The family ``olmoe``: its plain reference agrees with the program's
layer at toy size and tells three wrong ones apart (renormalised router
weights, 7 experts for 8, no QK-norm), ``family_of`` takes the published
configuration and refuses one key more, the counts are the published
model's, the two readers read a recorded run, and the toy cell runs
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


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert module.NAME == name
    return module


PUBLISHED = _config("olmoe-1b-7b-0125-int8")


@pytest.fixture(scope="module")
def toy():
    """The toy configuration with 8 experts a token of 16, so that
    '7 experts for 8' is a control here as it is on the chip."""
    import jax
    import numpy as np

    config = dict(_config("tiny-rehearsal-olmoe"), num_experts=16,
                  num_experts_per_tok=8)
    family = families.family_of(config)
    cfg = family.program_config(config)
    params = family.training()[0](jax.random.PRNGKey(7), cfg)
    k1, k2 = jax.random.split(jax.random.PRNGKey(9))
    layers = dict(params["layers"])
    layers["q_norm"] = 1 + 0.25 * jax.random.normal(k1,
                                                    layers["q_norm"].shape)
    layers["k_norm"] = 1 + 0.25 * jax.random.normal(k2,
                                                    layers["k_norm"].shape)
    params = dict(params, layers=layers)
    tokens = np.random.default_rng(8).integers(
        1, config["vocab_size"], (3, 32), dtype=np.int32)
    return config, family, cfg, params, tokens


def _program_logits(params, tokens, cfg):
    """The program's serving block (``runner.prefill``: QK-norm, the
    dropless routed layer) on whole rows: logits at every position, one
    call a prefix length."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm.cache import init_kv_cache
    from ray_tpu.llm.runner import prefill
    from ray_tpu.ops import rope_frequencies

    n, seq = tokens.shape
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    tables = 1 + jnp.arange(n * 2, dtype=jnp.int32).reshape(n, 2)
    out = []
    for length in range(1, seq + 1):
        cache = init_kv_cache(cfg, 1 + n * 2, 16)
        logits, _, _, _ = prefill(
            params, cache.k, cache.v, jnp.asarray(tokens),
            jnp.full((n,), length, jnp.int32), tables, cos, sin, cfg=cfg)
        out.append(np.asarray(logits))
    return np.stack(out, 1)                            # [n, seq, vocab]


def test_reference_agrees_with_the_programs_layer_and_not_three_wrong_ones(
        toy):
    import jax.numpy as jnp
    import numpy as np

    config, family, cfg, params, tokens = toy
    assert (cfg.n_experts, cfg.top_k, cfg.norm_topk_prob, cfg.qk_norm) == (
        16, 8, False, True)
    program = _program_logits(params, tokens, cfg)
    reference = np.asarray(family.forward_logits(
        params, jnp.asarray(tokens), config))
    deviation = reference.std(-1).mean()
    # float32 on both sides: rounding alone
    assert np.abs(program - reference).max() / deviation < 1e-3
    # the controls, in the check's own unit (how far the token the
    # program picks lies below a reference's first choice, in deviations
    # of that position's logits): each is over the family's limit
    picked = program.argmax(-1)

    def worst_margin(**control):
        logits = np.asarray(family.forward_logits(
            params, jnp.asarray(tokens), config, **control))
        chosen = np.take_along_axis(logits, picked[..., None], -1)[..., 0]
        return ((logits.max(-1) - chosen) / logits.std(-1)).max()

    assert worst_margin() == 0.0
    assert worst_margin(renormalise=True) > family.MARGIN_LIMIT
    assert worst_margin(top_k=7) > family.MARGIN_LIMIT
    assert worst_margin(qk_norm=False) > family.MARGIN_LIMIT


def test_loss_has_the_balance_term(toy):
    import jax.numpy as jnp
    import numpy as np

    config, family, _, params, tokens = toy
    logits, balance = family._forward(params, jnp.asarray(tokens), config)
    loss = float(family.next_token_loss(params, jnp.asarray(tokens),
                                        config, z_loss=1e-4))
    logits = np.asarray(logits)[:, :-1].astype(np.float64)
    logz = np.log(np.exp(logits).sum(-1))
    target = np.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    by_hand = (logz - target + 1e-4 * logz ** 2).mean() \
        + family.BALANCE_COEFFICIENT * float(balance)
    assert loss == pytest.approx(by_hand, rel=1e-5)
    # two layers, each near its floor of 1 (uniform routing)
    assert 2.0 <= float(balance) < 2.6


def test_family_of_takes_the_published_file_and_refuses_one_key_more():
    family = families.family_of(PUBLISHED)
    assert family.__name__ == "benchmarks.families.olmoe"
    with pytest.raises(ValueError, match="does not read.*sliding_window"):
        families.family_of(dict(PUBLISHED, sliding_window=None))
    # every key of the catalog row at top level, unchanged, none reduced
    for key, value in PUBLISHED["published"].items():
        assert PUBLISHED[key] == value, key
    assert PUBLISHED["reduced"] == {}
    # another model_type or a bias is not this family's block
    with pytest.raises(ValueError, match="written for"):
        family.program_config(dict(PUBLISHED, attention_bias=True))
    cfg = family.program_config(PUBLISHED)
    assert (cfg.n_experts, cfg.top_k, cfg.norm_topk_prob, cfg.qk_norm,
            cfg.n_layers, cfg.dim, cfg.mlp_dim, cfg.head_dim) == (
                64, 8, False, True, 16, 2048, 1024, 128)
    args, kwargs = family.server_arguments(PUBLISHED, 3)
    assert args == (cfg,) and kwargs["quantize"] == "int8"


def test_the_family_refuses_a_program_without_the_routed_layer(tmp_path):
    """On a tree older than ``moe_mlp_routed`` the import itself stops,
    without jax, so that ``family_of`` ends the run before the runtime
    starts."""
    package = tmp_path / "ray_tpu"
    (package / "ops").mkdir(parents=True)
    (package / "__init__.py").write_text("")
    (package / "ops" / "moe.py").write_text("def moe_mlp_dense(): pass\n")
    code = ("import sys; sys.path.insert(0, %r); sys.path.insert(1, %r)\n"
            "from benchmarks.harness import families\n"
            "import json\n"
            "try:\n"
            "    families.family_of(json.load(open(%r)))\n"
            "except ValueError as e:\n"
            "    assert 'moe_mlp_routed' in str(e), e\n"
            "    assert 'jax' not in sys.modules\n"
            "    print('refused')\n") % (
                str(tmp_path), ROOT,
                os.path.join(BENCH, "configs", "olmoe-1b-7b-0125-int8.json"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=str(tmp_path))
    assert out.stdout.strip() == "refused", out.stderr[-2000:]


def test_counts_are_the_published_models():
    family = families.family_of(PUBLISHED)
    c = PUBLISHED
    # by hand: attention 4 x 2048 x 2048, router 2048 x 64, an expert
    # 3 x 2048 x 1024, norms 2 x 2048 + 2 x 2048, table and head
    # 2048 x 50304 each, the final norm
    attention, expert = 4 * 2048 * 2048, 3 * 2048 * 1024
    layer = attention + 2048 * 64 + 64 * expert + 4 * 2048
    assert family.held_params(c) == 16 * layer + 2 * 2048 * 50304 + 2048
    assert round(family.held_params(c) / 1e9, 2) == 6.92
    assert family.matmul_params(c) == (
        16 * (attention + 2048 * 64 + 8 * expert) + 2048 * 50304)
    assert round(family.matmul_params(c) / 1e9, 2) == 1.18
    assert (family.experts_held(c), family.experts_per_token(c)) == (64, 8)
    # the program's own count agrees (no jax arrays are made for it)
    assert family.program_config(c).n_params() == family.held_params(c)
    assert family.train_flops_per_token(c, 2048) == (
        6 * family.matmul_params(c) + 6 * 16 * 2048 * 2048)
    # a prefill of 1,024 tokens: 2 x tokens x the layers' parameters a
    # token meets, causal attention, one position through the head
    met = attention + 2048 * 64 + 8 * expert
    assert family.prefill_flops(c, 1024) == (
        16 * (2 * 1024 * met + 2 * 1024 * 1024 * 2048) + 2 * 2048 * 50304)
    assert 2.2e12 < family.prefill_flops(c, 1024) < 2.4e12


@pytest.mark.parametrize("rows, touched", [
    (1, 8.0), (5, 64 * (1 - 0.875 ** 5)), (64, 64 * (1 - 0.875 ** 64))])
def test_routed_decode_step_bytes(rows, touched):
    family = families.family_of(PUBLISHED)
    c = PUBLISHED
    assert family.experts_touched(c, rows) == pytest.approx(touched)
    attention, expert = 4 * 2048 * 2048, 3 * 2048 * 1024
    scales = 4 * (16 * (3 * 2048 + 2048 + touched * (2 * 1024 + 2048))
                  + 50304)
    by_hand = (16 * (attention + touched * expert) + 2048 * 50304
               + scales + 4 * 16 * 2048 * 64
               + 2 * (16 * 4 * 2048 + 2048) + 1000 * 131072)
    assert family.routed_decode_step_bytes(c, rows, 1000, 1) == \
        pytest.approx(by_hand)
    # never more than every expert, which is what decode_step_bytes counts
    assert by_hand <= family.decode_step_bytes(c, 1000, 1)
    if rows == 1:        # one row: an eighth of the experts, 1.3 GB
        assert 1.25e9 < by_hand < 1.35e9
    if rows == 64:       # all 64 rows: nearly every expert, 6.9 GB
        assert family.decode_step_bytes(c, 1000, 1) - by_hand < 2e6


with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "recorded_olmoe_run.json")) as _f:
    RECORDED = dict(json.load(_f), config=PUBLISHED)


def test_the_two_readers_on_a_recorded_run():
    """A traced run of the cell on the chip (the file's note says
    which): the readers give what that run printed, and the arithmetic
    is redone here by hand from the same records."""
    family = families.family_of(PUBLISHED)
    prefill, decode = (_reader("prefill_roofline"),
                       _reader("expert_decode_roofline"))
    trace, engine = RECORDED["trace"], RECORDED["engine"]
    assert prefill.compute(RECORDED) == pytest.approx(
        RECORDED["printed"]["prefill_roofline"])
    assert decode.compute(RECORDED) == pytest.approx(
        RECORDED["printed"]["expert_decode_roofline"])
    # prefill: 26 requests' first tokens fell in the stretch (one before
    # and one after it are in the file and left out), 26 runs
    inside = [r for r in engine["finished"]
              if trace["t0"] <= r["first"] <= trace["t1"]]
    assert len(inside) == 26 == len(engine["finished"]) - 2
    program = trace["programs"]["jit_prefill_sample"]
    needed = sum(family.prefill_flops(PUBLISHED, r["prompt_tokens"])
                 for r in inside) / 26 * program["runs"]
    assert prefill.compute(RECORDED) == pytest.approx(
        100 * needed / program["seconds"] / 197e12)
    # decode: the 33 rounds started in the stretch (one after it is left
    # out), their steps weighted by each round's width
    rounds = [r for r in engine["rounds"]
              if trace["t0"] <= r["t"] <= trace["t1"]]
    assert len(rounds) == 33 == len(engine["rounds"]) - 1
    steps = sum(r["width"] for r in rounds)
    program = trace["programs"]["jit_decode_burst"]
    step_s = program["seconds"] / (program["runs"] * steps / 33)
    assert 1e3 * step_s == pytest.approx(
        RECORDED["printed"]["decode_step_ms"])
    needed = sum(r["width"] * family.routed_decode_step_bytes(
        PUBLISHED, r["active"], r["live"], 1) for r in rounds) / steps
    assert decode.compute(RECORDED) == pytest.approx(
        100 * needed / 819e9 / step_s)
    assert 0 < prefill.compute(RECORDED) < 100
    assert 0 < decode.compute(RECORDED) < 100


def test_the_readers_read_nothing_where_there_is_nothing():
    prefill, decode = (_reader("prefill_roofline"),
                       _reader("expert_decode_roofline"))
    off_chip = dict(RECORDED, device={"platform": "cpu", "kind": "cpu"})
    assert prefill.compute(off_chip) is None
    assert decode.compute(off_chip) is None
    # a family without the counts (the dense one): nothing, no error
    dense = dict(RECORDED, config=_config("mistral-7b-v0.3-int8"))
    assert prefill.compute(dense) is None and decode.compute(dense) is None
    untraced = dict(RECORDED, trace={})
    assert prefill.compute(untraced) is None
    assert decode.compute(untraced) is None


def test_tiny_chat_olmoe_runs_through_serve_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "tiny-chat-olmoe",
         "--seed", str(2**31 + 11), "--seconds", "3", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] == 24                  # 8 a second x 3 s
    assert result["device"]["platform"] == "cpu"
    assert result["notes"]["probes"]["margin_worst"] <= 0.15
    assert "decode_burst_width" in result["metrics"]
    # shares of a TPU's peak are not read on the CPU
    assert "prefill_roofline" not in result["metrics"]
    assert "expert_decode_roofline" not in result["metrics"]
