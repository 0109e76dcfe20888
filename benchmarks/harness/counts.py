"""Operations and bytes an algorithm needs, computed from a configuration
file's sizes. The yardstick's arithmetic: no PR that claims a gain can
change it. Keys are those of the published ``config.json``."""

from __future__ import annotations


def layer_params(c: dict) -> int:
    """Parameters of one decoder layer: four attention projections, the
    gated feed-forward's three matrices, two norms."""
    d, hd = c["hidden_size"], c["head_dim"]
    q = d * c["num_attention_heads"] * hd
    kv = 2 * d * c["num_key_value_heads"] * hd
    o = c["num_attention_heads"] * hd * d
    mlp = 3 * d * c["intermediate_size"]
    return q + kv + o + mlp + 2 * d


def matmul_params(c: dict) -> int:
    """Parameters a token is multiplied with: every layer and the output
    head, without the embedding table (a gather, not a matmul)."""
    d = c["hidden_size"]
    return (c["num_hidden_layers"] * layer_params(c)
            + d * c["vocab_size"] + d)


def total_params(c: dict) -> int:
    """All parameters, with the (untied) embedding table."""
    return matmul_params(c) + c["vocab_size"] * c["hidden_size"]


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward plus backward operations one trained token requires:
    6 x the parameters it is multiplied with, plus causal attention's
    scores and values (2 matmuls x 2 ops x seq/2 keys on average x the
    attention width, x 3 for forward + backward) in every layer.
    Recomputation is not counted."""
    width = c["num_attention_heads"] * c["head_dim"]
    attention = 6 * c["num_hidden_layers"] * width * seq
    return 6.0 * matmul_params(c) + attention


def kv_bytes_per_token(c: dict, bytes_per_value: int = 2) -> int:
    """Bytes of keys and values one cached position holds, all layers."""
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * c["head_dim"] * bytes_per_value)


def decode_weight_bytes(c: dict, weight_bytes: int = 1) -> int:
    """Weight bytes one decode step must read whatever the batch: every
    layer's matrices and the output head at ``weight_bytes`` a value
    (1 = int8), their float32 per-output-channel scales, and the bf16
    norms. The embedding rows read are a few KB and left out."""
    d, hd = c["hidden_size"], c["head_dim"]
    h, kvh, m = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["intermediate_size"])
    matrices = c["num_hidden_layers"] * (layer_params(c) - 2 * d) \
        + d * c["vocab_size"]
    scales = 0
    if weight_bytes == 1:
        per_layer = h * hd + 2 * kvh * hd + d + 2 * m + d
        scales = 4 * (c["num_hidden_layers"] * per_layer + c["vocab_size"])
    norms = 2 * (2 * c["num_hidden_layers"] * d + d)
    return matrices * weight_bytes + scales + norms


def decode_step_bytes(c: dict, live_context_tokens: float,
                      weight_bytes: int = 1) -> float:
    """Bytes one decode step needs from HBM: the weights once, and the
    keys and values of every live position of every active sequence."""
    return (decode_weight_bytes(c, weight_bytes)
            + live_context_tokens * kv_bytes_per_token(c))
