"""The second family, ``llama_moe_rehearsal``: its plain reference agrees
with the program's expert layer and tells a wrong one apart, its counts
are those of the parameters the program makes, and its rehearsal cell
runs through ``serve.run`` on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import families

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@pytest.fixture(scope="module")
def toy():
    import jax
    import numpy as np

    with open(os.path.join(BENCH, "configs", "tiny-rehearsal-moe.json")) as f:
        config = json.load(f)
    family = families.family_of(config)
    cfg = family.program_config(config)
    params = family.training()[0](jax.random.PRNGKey(7), cfg)
    tokens = np.random.default_rng(8).integers(
        0, config["vocab_size"], (4, 48), dtype=np.int32)
    return config, family, cfg, params, tokens


def test_the_family_is_found_and_reads_the_expert_keys(toy):
    config, family, cfg, _, _ = toy
    assert family.__name__ == "benchmarks.families.llama_moe_rehearsal"
    assert (cfg.n_experts, cfg.top_k) == (4, 2)
    with pytest.raises(ValueError, match="renormalises"):
        family.program_config(dict(config, norm_topk_prob=False))


def test_reference_agrees_with_the_programs_layer_and_not_a_wrong_one(toy):
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import forward

    config, family, cfg, params, tokens = toy
    program = np.asarray(forward(params, jnp.asarray(tokens), cfg))
    reference = np.asarray(family.forward_logits(
        params, jnp.asarray(tokens), config))
    deviation = reference.std(-1).mean()
    assert np.abs(program - reference).max() / deviation < 1e-3
    # the control: one expert a token where the program takes two
    one_expert = np.asarray(family.forward_logits(
        params, jnp.asarray(tokens), dict(config, num_experts_per_tok=1)))
    assert np.abs(program - one_expert).max() / deviation > 0.1


def test_loss_agrees_with_the_programs_within_the_tolerance(toy):
    import jax.numpy as jnp

    config, family, cfg, params, tokens = toy
    loss_of = family.training()[1]
    program = float(loss_of(params, {"tokens": jnp.asarray(tokens)}, cfg))
    reference = float(family.next_token_loss(
        params, jnp.asarray(tokens), config, z_loss=1e-4))
    assert abs(program - reference) <= 5e-4 * abs(reference)
    # and the balance term is in it: without it the two differ by more
    bare = float(family.next_token_loss(
        params, jnp.asarray(tokens), dict(config, router_aux_loss_coef=0.0),
        z_loss=1e-4))
    assert abs(program - bare) > 5e-4 * abs(reference)


def test_counts_hold_E_experts_and_use_k(toy):
    import jax

    config, family, cfg, params, _ = toy
    held = sum(a.size for a in jax.tree.leaves(params))
    assert family.held_params(config) == held == cfg.n_params()
    # by hand: attention 2 x 64 x 64 + 2 x 64 x 32, router 64 x 4, an
    # expert 3 x 64 x 32, two norms of 64; head and table 64 x 256 each
    attention, expert = 2 * 4096 + 2 * 2048, 3 * 64 * 32
    assert held == 2 * (attention + 256 + 4 * expert + 128) + 2 * 16384 + 64
    assert family.matmul_params(config) == (
        2 * (attention + 256 + 2 * expert) + 16384)
    assert family.train_flops_per_token(config, 128) == (
        6 * family.matmul_params(config) + 6 * 2 * 64 * 128)
    # a decode step reads all four experts, and 2 x 2 x 2 x 16 x 2 bytes
    # of keys and values a live position
    step = family.decode_step_bytes(config, 0)
    assert step == (2 * (2 * (attention + 4 * expert) + 16384)
                    + 4 * 2 * 64 * 4 + 2 * (2 * 2 * 64 + 64))
    assert family.decode_step_bytes(config, 10) - step == 10 * 256


def test_tiny_chat_moe_runs_through_serve_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "tiny-chat-moe",
         "--seed", str(2**31 + 11), "--seconds", "3", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] == 24                  # 8 a second x 3 s
    assert result["device"]["platform"] == "cpu"
    assert result["notes"]["probes"]["margin_worst"] <= 0.15
    assert "decode_burst_width" in result["metrics"]
