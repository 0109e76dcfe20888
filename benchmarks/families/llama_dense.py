"""The family of a dense Llama- or Mistral-shaped decoder: the program's
``LlamaConfig`` without experts, served by ``LLMServer`` and trained
through ``ray_tpu.models``. The default family: a configuration file that
names none is one of these.

Its plain reference is ``harness/reference.py`` and its counts are
``harness/counts.py``: both stay where they are, the accepted cells'
yardstick. It states no ``MARGIN_LIMIT`` and no ``LOSS_TOLERANCE``: the
harness's own (``serve_cell.py``, ``train_cell.py``) are argued from this
block's arithmetic.
"""

from __future__ import annotations

# 4. the counts, and what they rest on
from benchmarks.harness.counts import (  # noqa: F401
    decode_step_bytes, kv_bytes_per_token, total_params,
    train_flops_per_token)

# the keys of the published ``config.json`` this family reads
CONFIG_KEYS = frozenset((
    "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "vocab_size",
    "max_position_embeddings", "rope_theta", "rms_norm_eps"))


# 1. the program's configuration
def program_config(config: dict):
    """The program's ``LlamaConfig`` for a published ``config.json``."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    if config["hidden_size"] != (config["num_attention_heads"]
                                 * config["head_dim"]):
        raise ValueError("LlamaConfig derives head_dim as hidden_size / "
                         "num_attention_heads; this configuration's "
                         "head_dim differs")
    return LlamaConfig(
        vocab=config["vocab_size"], dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        mlp_dim=config["intermediate_size"],
        max_seq=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.bfloat16 if not config.get("rehearsal") else jnp.float32,
        remat=not config.get("rehearsal"))


# 2. how the replica is made
def server_class():
    """The class of the program that the benchmark's watchers wrap."""
    from ray_tpu.llm.serve import LLMServer

    return LLMServer


def server_arguments(config: dict, seed: int):
    """``(args, kwargs)`` of that class, called in the replica's process
    before its ``__init__``. ``LLMServer`` takes a model only as a key of
    ``LLAMA_CONFIGS``, so the configuration file's widths are registered
    there under the file's name: the program is not edited."""
    from ray_tpu.models.llama import LLAMA_CONFIGS

    LLAMA_CONFIGS[config["name"]] = program_config(config)
    return (config["name"],), dict(
        init="random", seed=seed, quantize=config.get("quantize"),
        engine_config=dict(config["engine"]))


def served_params(key, config: dict):
    """The weights ``LLMServer`` makes for ``init="random"``, for
    ``aot_fit.py`` to take their shapes from."""
    cfg = program_config(config)
    if config.get("quantize") == "int8":
        from ray_tpu.ops.quant import init_params_quantized

        return init_params_quantized(key, cfg)
    from ray_tpu.models import init_params

    return init_params(key, cfg)


# 3. the plain reference
def forward_logits(params, tokens, config: dict):
    from benchmarks.harness import reference

    return reference.forward_logits(params, tokens, config)


def next_token_loss(params, tokens, config: dict, z_loss: float = 0.0):
    from benchmarks.harness import reference

    return reference.next_token_loss(params, tokens, config, z_loss)


# 5. what ``train_fn`` trains
def training():
    """``(init_params(key, cfg), loss(params, batch, cfg, mesh=...),
    param_logical_axes(cfg))`` of the program, ``cfg`` being
    ``program_config``'s."""
    from ray_tpu.models import init_params, lm_loss, param_logical_axes

    return init_params, lm_loss, param_logical_axes
