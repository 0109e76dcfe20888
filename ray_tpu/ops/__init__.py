"""ray_tpu.ops: TPU compute kernels (Pallas + XLA).

Net-new relative to the reference, which has no device kernels of its own
(it delegates tensors to torch/NCCL — SURVEY §5.7 notes ring/sequence
parallel attention is entirely absent there). These ops are the compute
substrate for ray_tpu.models and ray_tpu.serve.
"""

from .norms import layer_norm, rms_norm
from .rotary import apply_rotary, rope_frequencies
from .attention import attention, flash_attention_tpu, naive_attention
from .ring_attention import ring_attention
from .moe import moe_dispatch, moe_mlp, moe_mlp_oracle, moe_mlp_routed
from .quant import (
    dequantize_weight, embed_lookup, init_params_quantized,
    quantize_params, quantize_weight, weight_einsum)

__all__ = [
    "rms_norm", "layer_norm", "apply_rotary", "rope_frequencies",
    "attention", "flash_attention_tpu", "naive_attention",
    "ring_attention", "moe_dispatch", "moe_mlp", "moe_mlp_oracle",
    "moe_mlp_routed",
    "quantize_weight", "dequantize_weight", "weight_einsum",
    "embed_lookup", "quantize_params", "init_params_quantized",
]
