"""Llama-3 family: functional jax transformer, TPU-first.

Design choices for the TPU/XLA compilation model:
  * **scan over layers** — one compiled layer body, stacked params with a
    leading "layers" axis: compile time stays flat as depth grows.
  * **remat per layer** (``jax.checkpoint``) — trades FLOPs for HBM,
    standard recipe for long-sequence training. What a layer's backward
    pass keeps instead of recomputing is ``LlamaConfig.remat_policy``
    (``REMAT_POLICIES``, ordered by what they cost a device); the
    default, ``"attn_up"``, keeps the layer's input, the flash forward's
    output and LSE, the attention output product's result and the up
    product's result, so that kernel, those two products and the
    attention output's ``tp`` all-reduce run once a step, not twice;
    the backward body still runs the norms, q, k and v with their
    rotary embedding and gate again. ``"attn"`` keeps no up product
    (1.3 GiB less a device at the four-chip train cell's shape),
    ``"full"`` the input alone.
  * **logical axis names** on every param; the rules table
    (ray_tpu.parallel.sharding) maps them onto the dp/fsdp/tp/sp mesh, so
    FSDP/TP/SP layouts need no model edits (GSPMD inserts collectives).
  * **bf16 params/activations, f32 accumulation** in norms/softmax/loss.
  * attention dispatches to the Pallas flash kernel on TPU, ring
    attention over the "sp" axis when sequence-parallel is active.

The reference has no native model code (tensors delegated to torch/vLLM
— SURVEY §2.3); this file is the BASELINE "Llama-3 8B" config substrate.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import apply_rotary, attention, ring_attention, rms_norm, rope_frequencies
from ..ops.attention import FLASH_LSE, FLASH_OUT, attention_path
from ..parallel.sharding import (DEFAULT_RULES, logical_sharding,
                                 with_sharding_constraint_logical)


# a layer's kind (``LlamaConfig.layer_pattern``). "linear": a layer of
# linear attention, whose memory is a state a slot and no key or value
# (ops/linear_attention.py); "block_nope": softmax attention without a
# rotary embedding over the tokens of the blocks a query chooses
# (ops/sparse_attention.py, "selection by blocks"). Both have weights of
# their own widths: a stack a kind (``init_params``)
LAYER_KINDS = ("full", "full_nope", "window", "window_nope", "linear",
               "block_nope")
# the kinds of a decoder-hybrid-decoder stack (``LlamaConfig.scan_state``;
# models/sambay.py has their weights, llm/kinds/scan.py their cache):
# "scan", a selective-scan layer whose memory is a state a slot;
# "window_diff" and "full_diff", differential attention with pages of
# its own; "gmu", a gated memory unit on the LAST scan layer's output;
# "cross_diff", differential attention with a query of its own over the
# pages of the one "full_diff" layer. None of them is rotated
HYBRID_KINDS = ("scan", "window_diff", "full_diff", "gmu", "cross_diff")


# the name ``_attn`` gives the result of its output product
# ``bshk,hkd->bsd`` (under a ``tp`` mesh axis: after the all-reduce)
ATTN_OUT = "attn_out"

# the name ``_mlp``'s dense branch gives the result of its up product
# ``bsd,dm->bsm`` with ``w_up`` (the expert branch has no such value)
MLP_UP = "mlp_up"

# what ``jax.checkpoint`` of a layer keeps for the backward pass beside
# the layer's input (``LlamaConfig.remat_policy``), dearest first. What
# an entry costs is its stacks, a value a layer; in GiB a device at the
# four-chip train cell's shape (Mistral-7B's widths, 24 layers, batch 2 x
# 2,048 a device, fsdp=2 x tp=2; the compiler's buffer assignment, of
# 15.75 GiB: PERF.md section 5):
#   "dots"     every product's result; the backward pass recomputes the
#              elementwise work and the flash forward. 15.72 GiB.
#   "attn_up"  the default: what "attn" keeps and the up product's result
#              (batch x seq x mlp_dim values: 1.30 GiB). The SwiGLU's
#              backward needs gate and up: gate is the one product of the
#              feed-forward that is run again. 14.48 GiB. A layer of
#              experts (``n_experts``) has no such value to name and
#              keeps what "attn" keeps.
#   "attn"     the flash forward's output and LSE (the blockwise path has
#              none to keep) and the attention output product's result
#              after its ``tp`` all-reduce, 1.5 x batch x seq x dim
#              values: neither the kernel nor that product nor its
#              all-reduce is run again; the norms, q/k/v, the rotary
#              embedding, gate and up are. 13.17 GiB.
#   "full"     nothing (None): everything is run again, about a third
#              more FLOPs, for whoever stands at the memory limit.
#              12.04 GiB.
# q, k and v are not named: kept after the rotary embedding they were
# worth +0.1% of the cell's tokens a second for 0.33 GiB (the backward
# body then waits for a 58.7 MB weight gather that the recomputation
# hides), and q and k without v lost 0.7% (PERF.md section 6, PR 54).
REMAT_POLICIES = {
    "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    "attn_up": jax.checkpoint_policies.save_only_these_names(
        ATTN_OUT, FLASH_OUT, FLASH_LSE, MLP_UP),
    "attn": jax.checkpoint_policies.save_only_these_names(
        ATTN_OUT, FLASH_OUT, FLASH_LSE),
    "full": None,
}


# deviation of a seeded ``expert_bias`` (``LlamaConfig.router_bias``), in
# units of a sigmoid score: it moves the choice between experts whose
# scores lie that close (the 4th and 5th of 256 lie 0.02 apart on
# average) and leaves every expert about its share of the rows
EXPERT_BIAS_SCALE = 0.02


def windowed(kind: str) -> bool:
    return kind.startswith("window")


def rotated(kind: str) -> bool:
    return not kind.endswith("_nope")


def linear(kind: str) -> bool:
    return kind == "linear"


def pageless(kind: str) -> bool:
    """The layer keeps no page of its own."""
    return kind in ("linear", "scan", "gmu", "cross_diff")


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    mlp_dim: int = 14336
    max_seq: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # Mixture-of-Experts: n_experts > 0 replaces the dense MLP with a
    # top-k routed expert MLP (experts sharded over the "ep" mesh axis)
    n_experts: int = 0
    top_k: int = 2
    capacity_factor: float = 2.0
    aux_loss_coef: float = 0.01
    # the chosen experts' router probabilities are renormalised to sum
    # to 1 (False: used as the router gave them, as OLMoE does)
    norm_topk_prob: bool = True
    # RMSNorm of the projected queries and keys over their whole width,
    # before the split into heads and the rotary embedding (OLMoE)
    qk_norm: bool = False
    # what a layer's backward pass keeps beside the layer's input, where
    # ``remat`` (``REMAT_POLICIES``: what each entry keeps and what it
    # costs a device). One value, chosen by the room a device has; the
    # default keeps the most that the four-chip train cell's step has
    # room for (14.48 of the 15.75 GiB a v5e leaves a program, by the
    # compiler's buffer assignment: size a step by that count and not
    # by ``memory_analysis()``, which reads 18.4 GB there)
    remat_policy: str = "attn_up"
    # a head's width where it is not dim // n_heads (SmallThinker: 28
    # heads of 128 on a hidden size of 2560). None: dim // n_heads
    head_size: Optional[int] = None
    # one period of the layers' kinds, repeated down the stack; a kind is
    # "full" or "window" (a query sees the ``window`` newest keys, its
    # own among them), with "_nope" behind it where the layer has no
    # rotary embedding. None: every layer full and rotated. The serving
    # path (llm/) runs a pattern; the training forward below refuses one
    layer_pattern: Optional[Tuple[str, ...]] = None
    window: Optional[int] = None
    # what the router reads: "mlp", the feed-forward's normalised input,
    # or "attention", the attention's (the logits are known a whole
    # attention before the experts need them)
    router_input: str = "mlp"
    # the gate's activation in an expert: act(gate) * up
    expert_act: str = "silu"
    # ``kv_lora_rank`` 0: queries, keys and values of one head size, K
    # and V pools (GQA). Over 0: latent attention (DeepSeek-V2), the
    # other attention kind (``latent``): queries through a low-rank
    # bottleneck (``q_lora_rank``), keys and values expanded from ONE
    # compressed row a token (``kv_lora_rank`` values and a rotary part of
    # ``qk_rope_dim`` that all heads share): that row is all the cache
    # holds (llm/cache.py), and a head scores ``qk_nope_dim`` +
    # ``qk_rope_dim`` wide and returns ``v_head_dim``. Served only
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # the softmax's scale where it is not head_dim ** -0.5 (YaRN's
    # ``mscale_all_dim`` squared rides on it)
    attn_scale: Optional[float] = None
    # ``ops.rotary.rope_frequencies``' ``scaling``: the published
    # ``rope_scaling`` as sorted (key, value) pairs (hashable). None:
    # plain frequencies of ``rope_theta``
    rope_scaling: Optional[Tuple[Tuple[str, Any], ...]] = None
    # the first layers' feed-forward is dense, of its own width, before
    # the expert layers begin (their weights: ``params["dense_layers"]``)
    n_dense_layers: int = 0
    dense_mlp_dim: int = 0
    # experts every token passes through, beside the routed ones: one
    # SwiGLU of width ``n_shared_experts * mlp_dim``
    n_shared_experts: int = 0
    # group-limited routing: the experts are ``n_group`` groups, a
    # group's score is its best expert's, only the ``topk_group`` best
    # groups' experts can be chosen; the chosen weights x ``routed_scale``
    n_group: int = 1
    topk_group: int = 1
    routed_scale: float = 1.0
    # (first, count): the experts this chip holds of ``n_experts`` (one
    # chip's share of an expert-parallel deployment): the router keeps
    # ``n_experts`` outputs, the expert matrices have ``count`` experts,
    # and a row routed to an expert that is not here adds nothing. None:
    # all of them
    experts_held: Optional[Tuple[int, int]] = None
    # ``qk_norm`` a head: RMSNorm of every query and key head over its
    # own width, one learned ``head_dim`` vector each (Qwen3), not one
    # over the whole projected width (OLMoE)
    qk_norm_by_head: bool = False
    # an indexer beside every attention layer (ops/sparse_attention.py):
    # ``indexer_heads`` query heads of ``indexer_dim`` over ONE key of
    # that width a token, and a query attends over the ``sparse_top_k``
    # keys it scores highest. 0: none, every visible key attended.
    # Served only
    indexer_heads: int = 0
    indexer_dim: int = 0
    sparse_top_k: int = 0
    # "linear" layers (lightning attention): ``linear_heads`` query, key
    # and value heads of ``head_dim`` each, no grouping; a head's memory
    # is a float32 ``head_dim`` x ``head_dim`` state a slot that decays
    # by ``exp(-2 ** (-8 (h + 1) / linear_heads))`` a token. Their
    # weights are ``params["linear_layers"]``, a stack of their own.
    # Served only
    linear_heads: int = 0
    # "block_nope" layers (InfLLM-V2): a query from position
    # ``block_dense_len`` on attends over the tokens of ``block_topk``
    # blocks of ``block_size``: the first ``block_init``, those that
    # touch the ``block_window`` newest tokens, and the best by the
    # scores of compressed keys (the mean of ``block_kernel`` keys every
    # ``block_stride``); one choice a KV head. A page is a block
    # (llm/engine.py refuses another page_size). 0: none. Served only
    block_size: int = 0
    block_topk: int = 0
    block_kernel: int = 0
    block_stride: int = 0
    block_init: int = 0
    block_window: int = 0
    block_dense_len: int = 0
    # attention's output times sigmoid(W_g h) before ``wo`` (``wg``)
    attn_output_gate: bool = False
    # MiniCPM's scalings: the embedding times ``embed_scale``, what a
    # layer's two halves add to the stream times ``residual_scale``, the
    # final norm's output divided by ``logit_divisor``. 1.0: untouched
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_divisor: float = 1.0
    # how the router scores an expert: "softmax" over all the logits, or
    # "sigmoid", every logit's own (the chosen scores then sum to
    # anything: ``norm_topk_prob`` makes them a share)
    router_score: str = "softmax"
    # a bias an expert (``expert_bias`` [E], float32, never quantized)
    # that the router's CHOICE sees, top_k of score + bias, and the
    # chosen experts' weights do not: they are the unbiased scores
    router_bias: bool = False
    # sandwich norms: an RMSNorm with its own weight on what each half
    # of a layer ADDS to the stream (``post_attn_norm``,
    # ``post_mlp_norm``), beside the two on what the halves read
    post_norms: bool = False
    # a decoder-hybrid-decoder stack (SambaY; models/sambay.py): over 0,
    # ``layer_pattern`` lists every layer as P periods of ("scan",
    # "window_diff"), one ("scan", "full_diff"), then Q periods of ("gmu",
    # "cross_diff") (``HYBRID_KINDS``). A scan layer (Mamba-1) has
    # ``scan_expand * dim`` channels, each with ``scan_state`` float32
    # state values a slot and the last ``scan_conv - 1`` inputs of a
    # causal depthwise convolution; ``dt`` comes through a bottleneck of
    # ``scan_dt_rank``. Every norm is a LayerNorm with a bias, no layer is
    # rotated, the attention layers pair their heads (differential
    # attention), and the output head is the embedding table. Served only
    scan_state: int = 0
    scan_conv: int = 4
    scan_expand: int = 2
    scan_dt_rank: int = 0

    def __post_init__(self):
        if self.remat_policy not in REMAT_POLICIES:
            raise ValueError(f"remat_policy {self.remat_policy!r}: one of "
                             f"{sorted(REMAT_POLICIES)}")
        kinds = self.layer_kinds
        unknown = set(kinds) - set(LAYER_KINDS) - set(HYBRID_KINDS)
        if unknown:
            raise ValueError(f"layer_pattern: unknown kinds "
                             f"{sorted(unknown)}; one of "
                             f"{LAYER_KINDS + HYBRID_KINDS}")
        if bool(self.scan_state) != bool(set(kinds) & set(HYBRID_KINDS)):
            raise ValueError(f"the kinds {HYBRID_KINDS} need scan_state, "
                             f"and scan_state needs them")
        if self.scan_state:
            self.hybrid_periods      # refuses any other shape of stack
        if self.n_layers % len(kinds):
            raise ValueError(
                f"n_layers={self.n_layers} is no whole number of periods "
                f"of layer_pattern {kinds}")
        blocks = (self.block_size, self.block_topk, self.block_kernel,
                  self.block_stride, self.block_init, self.block_window,
                  self.block_dense_len)
        if self.own_weights:
            # kinds with weights of their own: the pattern lists every
            # layer, the stacks are a kind's
            if len(kinds) != self.n_layers:
                raise ValueError(
                    f"layer_pattern with 'linear' or 'block_nope' layers "
                    f"lists every layer: {len(kinds)} entries for "
                    f"n_layers={self.n_layers}")
            others = sorted(set(kinds) - {"linear", "block_nope"})
            if others:
                raise ValueError("'linear' and 'block_nope' layers stand "
                                 f"beside each other only, not beside {others}")
            if ("linear" in kinds) != bool(self.linear_heads):
                raise ValueError("'linear' layers need linear_heads, and "
                                 "linear_heads needs 'linear' layers")
            if ("block_nope" in kinds) != all(blocks) or (
                    any(blocks) and not all(blocks)):
                raise ValueError(
                    "'block_nope' layers need block_size, block_topk, "
                    "block_kernel, block_stride, block_init, block_window "
                    "and block_dense_len, all of them, and they need "
                    "'block_nope' layers")
            if "block_nope" not in kinds:
                raise ValueError("a layer_pattern of 'linear' layers alone "
                                 "has no page pool: one 'block_nope' layer "
                                 "at least")
            if all(blocks) and (
                    self.block_size % self.block_stride
                    or self.block_kernel != 2 * self.block_stride
                    or self.block_window % self.block_size
                    or self.block_dense_len % self.block_size):
                raise ValueError(
                    "block selection is written for block_kernel = 2 x "
                    "block_stride, a block a whole number of strides, and "
                    "block_window and block_dense_len whole blocks")
            if (self.n_experts or self.latent or self.sparse_top_k
                    or not (self.qk_norm and self.qk_norm_by_head)):
                raise ValueError(
                    "'linear' and 'block_nope' layers are dense GQA layers "
                    "with a QK-norm a head: no experts, no latent "
                    "attention, no indexer")
        elif any(blocks) or self.linear_heads:
            raise ValueError("linear_heads and the block_* sizes belong to "
                             "'linear' and 'block_nope' layers in "
                             "layer_pattern")
        if any(map(windowed, kinds)) and not self.window:
            raise ValueError("layer_pattern has window layers and "
                             "window is not set")
        if self.router_input not in ("mlp", "attention"):
            raise ValueError(f"router_input {self.router_input!r}: "
                             f"'mlp' or 'attention'")
        if self.expert_act not in ("silu", "relu"):
            raise ValueError(f"expert_act {self.expert_act!r}: "
                             f"'silu' or 'relu'")
        widths = (self.q_lora_rank, self.kv_lora_rank, self.qk_nope_dim,
                  self.qk_rope_dim, self.v_head_dim)
        if any(widths) and not all(widths):
            raise ValueError(
                "latent attention needs q_lora_rank, kv_lora_rank, "
                "qk_nope_dim, qk_rope_dim and v_head_dim, all of them")
        if self.latent and (len(kinds) > 1 or self.qk_norm):
            raise ValueError("latent attention has no layer_pattern (every "
                             "layer full and rotated, ONE stack of weights) "
                             "and no qk_norm")
        if self.n_dense_layers and (not self.n_experts
                                    or not self.dense_mlp_dim
                                    or self.n_dense_layers >= self.n_layers):
            # with a layer_pattern they sit INSIDE it (a window layer of
            # the first period, say), each in its group's pool; a pattern
            # of kinds with weights of their own has no experts
            raise ValueError(
                "n_dense_layers: leading dense layers (of dense_mlp_dim) "
                "come before the expert layers of a configuration with "
                "experts (the expert layers are ONE stack of weights)")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"router_score {self.router_score!r}: "
                             f"'softmax' or 'sigmoid'")
        if self.router_bias and not self.n_experts:
            raise ValueError("router_bias is a bias an expert: it needs "
                             "n_experts")
        if self.n_experts and self.n_experts % self.n_group:
            raise ValueError(f"n_experts={self.n_experts} is no whole "
                             f"number of n_group={self.n_group} groups")
        sizes = (self.indexer_heads, self.indexer_dim, self.sparse_top_k)
        if any(sizes) and not all(sizes):
            raise ValueError("an indexer needs indexer_heads, indexer_dim "
                             "and sparse_top_k, all of them")
        if self.sparse_top_k and (len(kinds) > 1 or self.latent):
            raise ValueError("an indexer sits beside every layer of ONE "
                             "stack of full, rotated GQA layers: no "
                             "layer_pattern (whose kinds may have weights "
                             "of their own), no latent attention")
        if self.qk_norm_by_head and not self.qk_norm:
            raise ValueError("qk_norm_by_head says how qk_norm "
                             "normalises: set qk_norm too")
        if self.experts_held is not None:
            first, count = self.experts_held
            if not (0 <= first and 0 < count
                    and first + count <= self.n_experts):
                raise ValueError(
                    f"experts_held {self.experts_held} is no part of "
                    f"{self.n_experts} experts")

    @property
    def hybrid_periods(self) -> Tuple[int, int]:
        """(P, Q) of a decoder-hybrid-decoder stack: P periods of (scan,
        window attention), the pair (scan, full attention), Q periods of
        (memory unit, cross-attention)."""
        kinds = self.layer_kinds
        P = sum(k == "window_diff" for k in kinds)
        Q = sum(k == "gmu" for k in kinds)
        if (kinds != ("scan", "window_diff") * P + ("scan", "full_diff")
                + ("gmu", "cross_diff") * Q or len(kinds) != self.n_layers
                or not (P and Q and self.scan_dt_rank and self.window)):
            raise ValueError(
                "scan_state: layer_pattern lists every layer as periods "
                "of ('scan', 'window_diff'), one ('scan', 'full_diff'), "
                "then periods of ('gmu', 'cross_diff'), with window and "
                "scan_dt_rank set")
        if (self.n_heads % 2 or self.n_kv_heads % 2
                or (self.n_heads // self.n_kv_heads) % 2
                or self.scan_channels % 128
                or self.n_experts or self.latent or self.sparse_top_k
                or self.qk_norm):
            raise ValueError(
                "scan_state: differential attention pairs the heads (an "
                "even number of query heads a pair of key-value heads), "
                "the scan's channels are whole rows of 128 lanes, and the "
                "layers are dense GQA layers without a QK-norm")
        return P, Q

    @property
    def scan_channels(self) -> int:
        return self.scan_expand * self.dim

    @property
    def latent(self) -> bool:
        """Latent attention, not GQA: see ``kv_lora_rank``."""
        return self.kv_lora_rank > 0

    @property
    def own_weights(self) -> bool:
        """The pattern has kinds with weights of their own ("linear",
        "block_nope"): it lists every layer, and a layer takes its
        weights by its place in its kind (``params["linear_layers"]``,
        ``params["layers"]``)."""
        return any(k in ("linear", "block_nope") for k in self.layer_kinds)

    @property
    def n_linear_layers(self) -> int:
        return sum(map(linear, self.layer_kinds)) if self.own_weights else 0

    @property
    def n_kv_layers(self) -> int:
        """Layers that keep keys and values in pages."""
        return self.n_layers - self.n_linear_layers

    @property
    def linear_decay(self) -> Tuple[float, ...]:
        """``-log`` of a linear head's decay a token: Lightning
        Attention-2's slopes ``2 ** (-8 (h + 1) / heads)``."""
        from ..ops.linear_attention import slopes_of

        return tuple(float(s) for s in slopes_of(self.linear_heads))

    @property
    def block_sizes(self):
        """The block layers' sizes as ``ops/sparse_attention.py`` takes
        them; None without such layers."""
        if not self.block_size:
            return None
        from ..ops.sparse_attention import BlockSizes

        return BlockSizes(self.block_size, self.block_topk,
                          self.block_stride, self.block_init,
                          self.block_window, self.block_dense_len)

    @property
    def state_bytes_per_slot(self) -> int:
        """Bytes of float32 state a slot holds in the linear layers, or
        in the scan layers (the convolution's tail among it)."""
        if self.scan_state:
            return (sum(k == "scan" for k in self.layer_kinds)
                    * (self.scan_state + self.scan_conv - 1)
                    * self.scan_channels * 4)
        return (self.n_linear_layers * self.linear_heads
                * self.head_dim * self.head_dim * 4)

    @property
    def head_dim(self) -> int:
        """A query's (and key's) width in one head."""
        if self.latent:
            return self.qk_nope_dim + self.qk_rope_dim
        return self.head_size or self.dim // self.n_heads

    @property
    def value_dim(self) -> int:
        """What a head returns."""
        return self.v_head_dim if self.latent else self.head_dim

    @property
    def rope_dim(self) -> int:
        """The width the rotary embedding turns: a whole head, or a
        latent layer's rotary part."""
        return self.qk_rope_dim if self.latent else self.head_dim

    @property
    def latent_dim(self) -> int:
        """Width of the ONE row a token keeps in a latent layer's cache:
        the compressed keys and values, then the shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def latent_row(self) -> int:
        """Width of a latent row's slot in the page pool: ``latent_dim``
        rounded up to whole lanes (576 -> 640, the rest zero). A TPU
        array whose last dimension is 576 is given a default layout with
        the PAGES as the fastest dimension (read from the compiled text
        of a program that takes a [9, 2049, 64, 576] pool: {1,3,2,0}), so
        that no page is contiguous, every scatter and gather of a page
        strides the whole pool and a kernel that wants row-major pages
        gets a copy of the pool first; 640 is row-major."""
        return -(-self.latent_dim // 128) * 128

    @property
    def indexer_row(self) -> int:
        """Width of an indexer key's slot in its page pool:
        ``indexer_dim`` rounded up to whole lanes (64 -> 128, the rest
        zero), for ``latent_row``'s reason: a pool whose last dimension
        is 64 gets the pages as its fastest dimension (read from a
        compile for a v5e: bf16[L,P,64,64]{1,3,2,0})."""
        return -(-self.indexer_dim // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return self.attn_scale or self.head_dim ** -0.5

    @property
    def n_experts_held(self) -> int:
        return self.experts_held[1] if self.experts_held else self.n_experts

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """One period of the stack's kinds (``("full",)`` without a
        pattern)."""
        return self.layer_pattern or ("full",)

    @property
    def kv_groups(self) -> Tuple[Optional[int], ...]:
        """The live spans the layers' keys have, one entry a group of
        layers that share a page pool (llm/cache.py): None for the whole
        sequence, else the window. Full layers first."""
        spans = {self._span(k) for k in self.layer_kinds if not pageless(k)}
        return tuple(sorted(spans, key=lambda s: s is not None))

    def _span(self, kind: str) -> Optional[int]:
        return self.window if windowed(kind) else None

    def group_layers(self, group: int) -> int:
        """Layers of the whole stack in ``kv_groups[group]``."""
        if self.own_weights:
            return self.n_kv_layers
        return sum(not pageless(k) and self.layer_group(j)[0] == group
                   for j, k in enumerate(self.layer_kinds)) \
            * (self.n_layers // len(self.layer_kinds))

    def layer_group(self, j: int) -> Tuple[int, int]:
        """(group, place among the period's layers of that group) of the
        period's j-th layer."""
        kinds = self.layer_kinds
        span = self._span(kinds[j])
        return (self.kv_groups.index(span),
                sum(self._span(k) == span for k in kinds[:j]))

    def n_params(self) -> int:
        if self.scan_state:
            from .sambay import n_params

            return n_params(self)
        d, L = self.dim, self.n_layers
        attn = d * self.n_heads * self.head_dim + 2 * d * self.n_kv_heads * self.head_dim \
            + self.n_heads * self.head_dim * d
        mlp = 3 * d * self.mlp_dim
        if self.n_experts:
            mlp = self.n_experts * mlp + d * self.n_experts  # experts+router
        if self.qk_norm:
            attn += 2 * self.head_dim if self.qk_norm_by_head else \
                (self.n_heads + self.n_kv_heads) * self.head_dim
        if self.sparse_top_k:
            attn += d * (self.indexer_heads * (self.indexer_dim + 1)
                         + self.indexer_dim) + 2 * self.indexer_dim
        if self.attn_output_gate:
            attn += d * self.n_heads * self.head_dim
        if self.own_weights:
            # a linear layer: five square matrices, two head norms and
            # the output norm over all heads
            wide = self.linear_heads * self.head_dim
            lin = 5 * d * wide + 2 * self.head_dim + wide
            return (self.vocab * d * 2 + d
                    + self.n_kv_layers * (attn + mlp + 2 * d)
                    + self.n_linear_layers * (lin + mlp + 2 * d))
        return self.vocab * d * 2 + L * (attn + mlp + 2 * d) + d


LLAMA_CONFIGS: Dict[str, LlamaConfig] = {
    # test-size model: fits CPU tests, exercises GQA (4 q heads, 2 kv).
    "tiny": LlamaConfig(vocab=256, dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, mlp_dim=128, max_seq=256,
                        dtype=jnp.float32, remat=False),
    # ~420M: single-chip bench size. head_dim=128 (8 heads on dim 1024) —
    # the MXU-native head width the flash kernels tile on; identical param
    # count to a 16-head/64-dim layout, far faster to train.
    "400m": LlamaConfig(vocab=32768, dim=1024, n_layers=24, n_heads=8,
                        n_kv_heads=4, mlp_dim=2816, max_seq=2048,
                        remat_policy="dots"),
    "1b": LlamaConfig(vocab=128256, dim=2048, n_layers=16, n_heads=16,
                      n_kv_heads=8, mlp_dim=8192, max_seq=8192),
    "8b": LlamaConfig(),  # Llama-3-8B (BASELINE config #1)
    "70b": LlamaConfig(dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
                       mlp_dim=28672),
}


# ---------------------------------------------------------------------------
# Params: nested dict, layer params stacked on a leading "layers" axis.
# ---------------------------------------------------------------------------


def param_logical_axes(cfg: LlamaConfig):
    """Pytree of logical-axis tuples mirroring init_params' structure."""
    if cfg.n_experts:
        mlp_axes = {
            "router": ("layers", "embed", "expert"),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
        }
    else:
        mlp_axes = {
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        }
    if cfg.qk_norm:
        mlp_axes.update(q_norm=("layers", None), k_norm=("layers", None))
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads", "head_dim"),
            "wk": ("layers", "embed", "kv_heads", "head_dim"),
            "wv": ("layers", "embed", "kv_heads", "head_dim"),
            "wo": ("layers", "heads", "head_dim", "embed"),
            "mlp_norm": ("layers", "embed"),
            **mlp_axes,
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def init_params(key, cfg: LlamaConfig, gains=None):
    """Scaled-normal init (1/sqrt(fan_in)); bf16 storage. ``gains``: a
    matrix's name ("embed", "wq", "w_down", ...) -> a factor on its
    seeded scale; None: every matrix at 1/sqrt(fan_in).

    A configuration with leading dense layers has them as their own
    stack ``params["dense_layers"]`` (attention and a dense feed-forward
    of ``dense_mlp_dim``), before ``params["layers"]``, the expert
    layers. A latent configuration's attention leaves are ``wq_a``
    [d, q_lora], ``q_a_norm``, ``wq_b`` [q_lora, h * (nope + rope)]
    (the heads flattened: a last dimension of 192 is no whole number of
    lanes, and a decode burst copied the matrix into another layout),
    ``wkv_a`` [d, kv_lora + rope], ``kv_a_norm``, ``w_uk`` and ``w_uv``
    (the two halves of the published ``kv_b_proj``, each [kv_lora, h,
    nope or v]) and ``wo`` [h, v, d]. Shared experts: ``ws_gate``,
    ``ws_up``, ``ws_down``, one SwiGLU of n_shared_experts * mlp_dim.
    ``router_bias``: ``expert_bias`` [L, E] float32, seeded NON-zero (a
    bias added to the weights, or left out of the choice, is another
    answer). ``post_norms``: ``post_attn_norm`` and ``post_mlp_norm``."""
    if cfg.scan_state:
        from . import sambay

        return sambay.init_params(key, cfg, gains)
    d, hd = cfg.dim, cfg.head_dim
    h, hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 9)
    gains = dict(gains or {})
    used = set()

    def norm(k, shape, fan_in, name):
        used.add(name)
        return (jax.random.normal(k, shape, jnp.float32)
                * (gains.get(name, 1.0) * fan_in ** -0.5)).astype(cfg.dtype)

    def scattered(k, shape):
        # learned gains scattered about 1: a norm over the wrong width
        # or with the wrong weight shows against a reference
        return (1.0 + 0.25 * jax.random.normal(k, shape, jnp.float32)
                ).astype(cfg.dtype)

    def attention_leaves(k4, L):
        if not cfg.latent:
            return {
                "wq": norm(k4[0], (L, d, h, hd), d, "wq"),
                "wk": norm(k4[1], (L, d, hkv, hd), d, "wk"),
                "wv": norm(k4[2], (L, d, hkv, hd), d, "wv"),
                "wo": norm(k4[3], (L, h, hd, d), h * hd, "wo"),
            }
        rq, rkv, v = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.v_head_dim
        ka = jax.random.split(k4[0], 7)
        return {
            "wq_a": norm(ka[0], (L, d, rq), d, "wq_a"),
            "q_a_norm": scattered(ka[1], (L, rq)),
            "wq_b": norm(ka[2], (L, rq, h * hd), rq, "wq_b"),
            "wkv_a": norm(ka[3], (L, d, cfg.latent_dim), d, "wkv_a"),
            "kv_a_norm": scattered(ka[4], (L, rkv)),
            "w_uk": norm(ka[5], (L, rkv, h, cfg.qk_nope_dim), rkv, "w_uk"),
            "w_uv": norm(ka[6], (L, rkv, h, v), rkv, "w_uv"),
            "wo": norm(k4[3], (L, h, v, d), h * v, "wo"),
        }

    def dense_mlp(k3, L, m):
        return {
            "w_gate": norm(k3[0], (L, d, m), d, "w_gate"),
            "w_up": norm(k3[1], (L, d, m), d, "w_up"),
            "w_down": norm(k3[2], (L, m, d), m, "w_down"),
        }

    L, m = cfg.n_moe_layers, cfg.mlp_dim
    if cfg.own_weights:
        L = cfg.n_kv_layers          # ``layers``: the layers with pages
    if cfg.n_experts:
        E, held = cfg.n_experts, cfg.n_experts_held
        kr = jax.random.split(ks[5], 4)
        mlp_params = {
            # router stays genuinely f32 (no bf16 round trip): routing
            # decisions are precision-sensitive
            "router": jax.random.normal(kr[0], (L, d, E), jnp.float32)
            * (d ** -0.5),
            "w_gate": norm(kr[1], (L, held, d, m), d, "w_gate"),
            "w_up": norm(kr[2], (L, held, d, m), d, "w_up"),
            "w_down": norm(kr[3], (L, held, m, d), m, "w_down"),
        }
        if cfg.n_shared_experts:
            ms = cfg.n_shared_experts * m
            kt = jax.random.split(ks[6], 3)
            mlp_params.update(
                ws_gate=norm(kt[0], (L, d, ms), d, "ws_gate"),
                ws_up=norm(kt[1], (L, d, ms), d, "ws_up"),
                ws_down=norm(kt[2], (L, ms, d), ms, "ws_down"))
    else:
        mlp_params = dense_mlp(ks[5:8], L, m)
    def qk_norms(n):
        by_head = cfg.qk_norm_by_head
        used.update(("q_norm", "k_norm"))
        return dict(
            q_norm=jnp.full((n, hd if by_head else h * hd),
                            gains.get("q_norm", 1.0), cfg.dtype),
            k_norm=jnp.full((n, hd if by_head else hkv * hd),
                            gains.get("k_norm", 1.0), cfg.dtype))

    if cfg.qk_norm:
        mlp_params.update(qk_norms(L))
    if cfg.sparse_top_k:
        # the indexer: query heads, the one key, a weight a head, and the
        # key's LayerNorm (gains scattered about 1, biases about 0)
        J, di = cfg.indexer_heads, cfg.indexer_dim
        ki = jax.random.split(jax.random.fold_in(key, 2), 5)
        mlp_params.update(
            wi_q=norm(ki[0], (L, d, J, di), d, "wi_q"),
            wi_k=norm(ki[1], (L, d, di), d, "wi_k"),
            wi_w=norm(ki[2], (L, d, J), d, "wi_w"),
            wi_k_norm=scattered(ki[3], (L, di)),
            wi_k_bias=(0.25 * jax.random.normal(ki[4], (L, di), jnp.float32)
                       ).astype(cfg.dtype))
    params = {
        "embed": norm(ks[0], (cfg.vocab, d), d, "embed"),
        "layers": {
            "attn_norm": jnp.ones((L, d), cfg.dtype),
            **attention_leaves(ks[1:5], L),
            "mlp_norm": jnp.ones((L, d), cfg.dtype),
            **mlp_params,
        },
        "final_norm": jnp.ones((d,), cfg.dtype),
        "lm_head": norm(ks[8], (d, cfg.vocab), d, "lm_head"),
    }
    if cfg.attn_output_gate:
        kg = jax.random.split(jax.random.fold_in(key, 3), 2)
        params["layers"]["wg"] = norm(kg[0], (L, d, h, hd), d, "wg")

    def post_norms(k, n):
        used.update(("post_attn_norm", "post_mlp_norm"))
        return {name: (gains.get(name, 1.0) * scattered(
            jax.random.fold_in(k, i), (n, d))).astype(cfg.dtype)
            for i, name in enumerate(("post_attn_norm", "post_mlp_norm"))}

    if cfg.post_norms:
        params["layers"].update(post_norms(jax.random.fold_in(key, 7), L))
    if cfg.router_bias:
        used.add("expert_bias")
        params["layers"]["expert_bias"] = (
            gains.get("expert_bias", 1.0) * EXPERT_BIAS_SCALE
            * jax.random.normal(jax.random.fold_in(key, 8),
                                (L, cfg.n_experts), jnp.float32))
    if cfg.own_weights:
        # the linear layers' stack: heads of their own number, an output
        # norm over all heads, the gate, and a feed-forward as every layer
        n, lh = cfg.n_linear_layers, cfg.linear_heads
        kl = jax.random.split(jax.random.fold_in(key, 4), 11)
        used.update(("q_norm", "k_norm", "o_norm"))
        params["linear_layers"] = {
            "attn_norm": jnp.ones((n, d), cfg.dtype),
            "wq": norm(kl[0], (n, d, lh, hd), d, "wq"),
            "wk": norm(kl[1], (n, d, lh, hd), d, "wk"),
            "wv": norm(kl[2], (n, d, lh, hd), d, "wv"),
            "wo": norm(kl[3], (n, lh, hd, d), lh * hd, "wo"),
            "wg": norm(kl[4], (n, d, lh, hd), d, "wg"),
            "q_norm": gains.get("q_norm", 1.0) * scattered(kl[5], (n, hd)),
            "k_norm": gains.get("k_norm", 1.0) * scattered(kl[6], (n, hd)),
            "o_norm": gains.get("o_norm", 1.0) * scattered(
                kl[7], (n, lh * hd)),
            "mlp_norm": jnp.ones((n, d), cfg.dtype),
            **dense_mlp(kl[8:], n, m),
        }
        params["layers"].update(
            q_norm=gains.get("q_norm", 1.0) * scattered(
                jax.random.fold_in(key, 5), (L, hd)),
            k_norm=gains.get("k_norm", 1.0) * scattered(
                jax.random.fold_in(key, 6), (L, hd)))
    if cfg.n_dense_layers:
        n = cfg.n_dense_layers
        kd = jax.random.split(jax.random.fold_in(key, 1), 7)
        params["dense_layers"] = {
            "attn_norm": jnp.ones((n, d), cfg.dtype),
            **attention_leaves(kd[:4], n),
            "mlp_norm": jnp.ones((n, d), cfg.dtype),
            **dense_mlp(kd[4:], n, cfg.dense_mlp_dim),
        }
        # what a layer's attention half has beside its projections
        if cfg.qk_norm:
            params["dense_layers"].update(qk_norms(n))
        if cfg.attn_output_gate:
            params["dense_layers"]["wg"] = norm(
                jax.random.fold_in(key, 9), (n, d, h, hd), d, "wg")
        if cfg.post_norms:
            params["dense_layers"].update(
                post_norms(jax.random.fold_in(key, 10), n))
    unknown = set(gains) - used
    if unknown:
        raise ValueError(f"gains for matrices that are not seeded: "
                         f"{sorted(unknown)}")
    return params


# ---------------------------------------------------------------------------
# Forward.
# ---------------------------------------------------------------------------


def sharded_attention(q, k, v, mesh: Mesh, rules=DEFAULT_RULES, *,
                      use_pallas: Optional[bool] = True):
    """Causal attention under a multi-device mesh, one ``attention`` call
    per device on its own batch rows and heads. GSPMD cannot partition a
    Mosaic kernel ("wrap the call in a shard_map"); attention mixes
    neither batch rows nor heads, so no collective is needed. The layout
    is the one ``rules`` give activations (batch over dp x fsdp, heads
    and kv heads over tp by default). Each sharded dimension must divide
    evenly — kv heads too, or a device's query heads would attend to
    another device's kv heads."""
    q_spec = logical_sharding(
        mesh, ("batch", "seq", "heads", "head_dim"), rules).spec
    kv_spec = logical_sharding(
        mesh, ("batch", "seq", "kv_heads", "head_dim"), rules).spec
    for name, x, spec in (("q", q, q_spec), ("k/v", k, kv_spec)):
        for dim, axes in enumerate(spec):
            if axes is None:
                continue
            if dim == 1:
                raise ValueError(
                    f"sharded_attention keeps the sequence whole, but the "
                    f"rules shard it over {axes!r}: sequence parallelism "
                    f"goes through ring attention (an 'sp' mesh axis)")
            ways = math.prod(mesh.shape[a] for a in (
                (axes,) if isinstance(axes, str) else axes))
            if x.shape[dim] % ways:
                raise ValueError(
                    f"attention under mesh {dict(mesh.shape)}: {name} "
                    f"dimension {dim} of {x.shape} (batch, seq, heads, "
                    f"head_dim) does not divide over {axes!r} = {ways} "
                    f"devices; choose a mesh whose axes divide the batch "
                    f"and the kv heads")
    return shard_map(
        partial(attention, causal=True, use_pallas=use_pallas),
        mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec), out_specs=q_spec,
        check_vma=False)(q, k, v)


def qk_norm(q, k, lp, cfg: LlamaConfig):
    """Where ``cfg.qk_norm``: RMSNorm of the projected queries
    (..., h, hd) and keys (..., hkv, hd), each over its WHOLE projected
    width (all heads together, one learned weight ``q_norm`` (h * hd) /
    ``k_norm`` (hkv * hd)), after the projection and before the rotary
    embedding. The one seam every copy of the block calls."""
    if not cfg.qk_norm:
        return q, k
    if cfg.qk_norm_by_head:
        # every head over its own width, one ``head_dim`` vector for all
        return (rms_norm(q, lp["q_norm"], cfg.norm_eps),
                rms_norm(k, lp["k_norm"], cfg.norm_eps))

    def whole(x, weight):
        flat = x.reshape(*x.shape[:-2], x.shape[-2] * x.shape[-1])
        return rms_norm(flat, weight, cfg.norm_eps).reshape(x.shape)

    return whole(q, lp["q_norm"]), whole(k, lp["k_norm"])


def _attn(x, lp, cfg: LlamaConfig, cos, sin, mesh: Optional[Mesh], rules):
    b, s, d = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, lp["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, lp["wv"])
    q, k = qk_norm(q, k, lp, cfg)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        # Sequence parallel: tokens sharded over "sp"; exact ring attention
        # rotates kv shards over single-hop ICI neighbours.
        spec = P(("dp", "fsdp"), "sp", "tp", None)
        out = shard_map(
            partial(ring_attention, axis="sp", causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)
    elif (mesh is not None and mesh.size > 1 and attention_path(
            s, s, cfg.head_dim,
            mesh.devices.flat[0].platform == "tpu") == "pallas"):
        out = sharded_attention(q, k, v, mesh, rules)
    else:
        out = attention(q, k, v, causal=True)
    out = jnp.einsum("bshk,hkd->bsd", out, lp["wo"])
    return checkpoint_name(out, ATTN_OUT)


def _mlp(x, lp, cfg: LlamaConfig, csl):
    if cfg.n_experts:
        from ..ops.moe import moe_mlp

        out, aux = moe_mlp(
            x, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
            top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
            norm_topk_prob=cfg.norm_topk_prob, csl=csl)
        return out, aux
    # SwiGLU; gate/up fuse into one pass over x in XLA.
    g = jnp.einsum("bsd,dm->bsm", x, lp["w_gate"])
    u = checkpoint_name(jnp.einsum("bsd,dm->bsm", x, lp["w_up"]), MLP_UP)
    out = jnp.einsum("bsm,md->bsd", jax.nn.silu(g) * u, lp["w_down"])
    return out, jnp.zeros((), jnp.float32)


def forward(params, tokens, cfg: LlamaConfig, *,
            mesh: Optional[Mesh] = None, rules=DEFAULT_RULES,
            return_aux: bool = False):
    """tokens (B, S) int32 → logits (B, S, vocab) in f32.

    ``return_aux``: also return the summed MoE load-balancing loss."""
    if (cfg.layer_pattern or cfg.router_input != "mlp"
            or cfg.expert_act != "silu" or cfg.latent or cfg.n_dense_layers
            or cfg.n_shared_experts or cfg.n_group > 1
            or cfg.experts_held is not None or cfg.sparse_top_k
            or cfg.attn_output_gate or cfg.embed_scale != 1.0
            or cfg.residual_scale != 1.0 or cfg.logit_divisor != 1.0
            or cfg.router_score != "softmax" or cfg.router_bias
            or cfg.post_norms):
        raise ValueError(
            "the training forward runs ONE stack of layers of one kind "
            "(full, rotated, GQA, the router on the feed-forward's input, "
            "ungrouped, silu experts, all held, none shared, no leading "
            "dense layer, no output gate, no scalings); a layer_pattern "
            "(of kinds that share a stack, or of 'linear' and 'block_nope' "
            "layers with weights of their own), router_input='attention', "
            "expert_act='relu', latent attention, n_dense_layers, "
            "n_shared_experts, n_group, experts_held, an indexer "
            "(sparse_top_k), attn_output_gate, embed_scale, residual_scale, "
            "logit_divisor, router_score='sigmoid', router_bias or "
            "post_norms is served by llm/runner.py only")
    csl = partial(with_sharding_constraint_logical, rules=rules, mesh=mesh)
    cos, sin = rope_frequencies(cfg.head_dim, tokens.shape[1],
                                cfg.rope_theta, dtype=jnp.float32)

    # Embedding lookup, transpose-stable: the stored table is
    # (vocab→tp, embed→fsdp)-sharded while activations are batch-sharded
    # over (dp, fsdp); gathering straight from the stored layout makes
    # SPMD move data between the fsdp and dp mesh dims — a device-order
    # transposition it can only do by full rematerialization (replicate
    # + repartition), in the forward AND its jvp transpose. Dropping the
    # table's embed-dim sharding first keeps the gather's vocab dim on
    # tp (masked gather + psum, the efficient partitioned path) and the
    # output reshard to batch is then a local slice.
    tbl = csl(params["embed"], ("vocab", None))
    x = jnp.take(tbl, tokens, axis=0)
    x = csl(x, ("batch", "seq", "embed"))

    def layer(x, lp):
        h = x + _attn(rms_norm(x, lp["attn_norm"], cfg.norm_eps),
                      lp, cfg, cos, sin, mesh, rules)
        h = csl(h, ("batch", "seq", "embed"))
        mlp_out, aux = _mlp(rms_norm(h, lp["mlp_norm"], cfg.norm_eps),
                            lp, cfg, csl)
        out = h + mlp_out
        return csl(out, ("batch", "seq", "embed")), aux

    body = layer
    if cfg.remat:
        body = jax.checkpoint(layer, policy=REMAT_POLICIES[cfg.remat_policy])
    x, aux_losses = jax.lax.scan(body, x, params["layers"])

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    # bf16 operands on the MXU with f32 accumulation — an f32 lm_head
    # matmul runs at half peak and is ~10% of model FLOPs at 32k vocab
    logits = jnp.einsum("bsd,dv->bsv", x.astype(cfg.dtype),
                        params["lm_head"].astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
    logits = csl(logits, ("batch", "seq", "vocab"))
    if return_aux:
        return logits, jnp.sum(aux_losses)
    return logits


def lm_loss(params, batch, cfg: LlamaConfig, *,
            mesh: Optional[Mesh] = None, rules=DEFAULT_RULES,
            z_loss: float = 1e-4):
    """Next-token cross-entropy (f32) with optional z-loss regularizer.

    batch: {"tokens": (B, S) int32, "mask": optional (B, S) 0/1 valid}.
    Targets are tokens shifted left; the final position is dropped.
    """
    tokens = batch["tokens"]
    logits, aux = forward(params, tokens, cfg, mesh=mesh, rules=rules,
                          return_aux=True)
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt_logit = jnp.take_along_axis(logits, targets[..., None],
                                    axis=-1)[..., 0]
    nll = logz - tgt_logit
    if z_loss:
        nll = nll + z_loss * jnp.square(logz)
    mask = batch.get("mask")
    mask = jnp.ones_like(nll) if mask is None else mask[:, 1:].astype(nll.dtype)
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    if cfg.n_experts:
        loss = loss + cfg.aux_loss_coef * aux
    return loss
