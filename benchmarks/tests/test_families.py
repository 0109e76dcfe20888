"""The seam between the harness and an architecture: a configuration's
family is found by name, reads every key of its file, and hands the
readers the counts the accepted cells were measured with."""

import json
import os

import pytest

from benchmarks.harness import counts, families

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_a_configuration_without_a_family_is_llama_dense():
    for name in ("mistral-7b-v0.3-int8", "mistral-7b-v0.3-train-24l",
                 "tiny-rehearsal", "tiny-rehearsal-train"):
        config = _config(name)
        assert "family" not in config
        assert families.family_of(config).__name__ == (
            "benchmarks.families.llama_dense")


def test_a_missing_family_names_the_directory():
    config = dict(_config("tiny-rehearsal"), family="not_written_yet")
    with pytest.raises(ValueError, match="benchmarks/families/"):
        families.family_of(config)


@pytest.mark.parametrize("key", ["num_experts", "num_experts_per_tok",
                                 "norm_topk_prob", "sliding_window"])
def test_a_key_the_family_does_not_read_is_an_error(key):
    """``llama_dense`` builds a dense block: an expert count it dropped in
    silence would be another model under the published name."""
    config = dict(_config("tiny-rehearsal"), **{key: 8})
    with pytest.raises(ValueError, match=key):
        families.family_of(config)


@pytest.mark.parametrize("name, total, per_trained_token", [
    ("mistral-7b-v0.3-int8", 7_248_023_552, None),
    ("mistral-7b-v0.3-train-24l", 5_503_127_552, 33.4),
])
def test_counts_through_llama_dense_are_the_yardsticks(name, total,
                                                       per_trained_token):
    """What ``decode_burst_roofline`` and ``train_mfu`` get through the
    family of a committed configuration is ``harness/counts.py``'s."""
    config = _config(name)
    family = families.family_of(config)
    assert family.total_params(config) == total == counts.total_params(config)
    for seq in (512, 2048):
        assert family.train_flops_per_token(config, seq) == (
            counts.train_flops_per_token(config, seq))
    if per_trained_token:
        assert round(family.train_flops_per_token(config, 2048) / 1e9,
                     1) == per_trained_token
    for live, weight_bytes in ((0, 1), (3000.5, 1), (3000, 2)):
        assert family.decode_step_bytes(config, live, weight_bytes) == (
            counts.decode_step_bytes(config, live, weight_bytes))
    # 32 or 24 layers x 2 (k, v) x 8 heads x 128 x 2 bytes a position
    a_token = family.decode_step_bytes(config, 1) - family.decode_step_bytes(
        config, 0)
    assert a_token == counts.kv_bytes_per_token(config)
    assert a_token == 131_072 * config["num_hidden_layers"] // 32


def test_the_driver_imports_a_family_without_jax():
    """The driver asks the family for its limits, its counts and the
    class to deploy, and may not import jax (the chip is the replica's)."""
    import subprocess
    import sys

    root = os.path.dirname(BENCH)
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, sys; sys.path.insert(0, sys.argv[1]);"
         "from benchmarks.harness import families, llm_server;"
         "c = json.load(open(sys.argv[2]));"
         "f = families.family_of(c); f.decode_step_bytes(c, 10.0, 1);"
         "print(llm_server.replica_class(c).__mro__[1].__name__,"
         " 'jax' in sys.modules)",
         root, os.path.join(BENCH, "configs", "tiny-rehearsal.json")],
        capture_output=True, text=True, timeout=120)
    assert out.stdout.split() == ["Watchers", "False"], out.stderr[-2000:]
