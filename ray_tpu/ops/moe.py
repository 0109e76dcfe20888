"""Mixture-of-Experts with expert parallelism, TPU-first.

Training (``moe_mlp``): GShard/Switch-style DENSE dispatch: routing is
expressed as one-hot einsums with a static per-expert capacity, so the
whole layer is three batched matmuls + masks — fully static shapes,
MXU-friendly, and GSPMD inserts the token all-to-alls automatically when
the expert axis is sharded over the "ep" mesh axis (logical axis
"expert"). Tokens over an expert's capacity are dropped.

Serving (``moe_mlp_routed``): one dropless routed layer: the (token,
expert) rows sorted by expert into ragged groups, the expert products as
grouped matrix multiplications over them (a Pallas kernel on a TPU).

The reference has no MoE of its own (SURVEY §2.3: EP listed as "not
implemented", placement groups only as the placement substrate) — this is
net-new TPU substrate, required natively by BASELINE.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .quant import weight_einsum


def _top_k_mask(probs: jnp.ndarray, k: int) -> jnp.ndarray:
    """(T, E) probs → (T, E) 0/1 mask of each token's top-k experts."""
    mask = jnp.zeros_like(probs)
    remaining = probs
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)
        one = jax.nn.one_hot(idx, probs.shape[-1], dtype=probs.dtype)
        mask = mask + one
        remaining = remaining * (1.0 - one) - one  # never re-pick
    return mask


def moe_dispatch(gates: jnp.ndarray, top_k: int, capacity: int,
                 norm_topk_prob: bool = True
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Build dispatch/combine tensors from router probabilities.

    gates: (T, E) softmax router output.
    Returns (dispatch (T, E, C) 0/1, combine (T, E, C) weights,
    aux_loss scalar). Tokens beyond an expert's capacity are dropped
    (standard Switch behavior — the residual stream carries them).
    """
    T, E = gates.shape
    mask = _top_k_mask(gates, top_k)                       # (T, E)
    # position of each token in each expert's buffer: order by token index
    position = jnp.cumsum(mask, axis=0) - 1.0              # (T, E)
    in_capacity = (position < capacity) & (mask > 0)
    pos_hot = jax.nn.one_hot(position.astype(jnp.int32), capacity,
                             dtype=gates.dtype)            # (T, E, C)
    dispatch = pos_hot * in_capacity[..., None].astype(gates.dtype)
    # combine weights: the top-k gate probs, renormalized to sum to 1
    # unless the router's own are wanted (norm_topk_prob False)
    selected = gates * mask
    if norm_topk_prob:
        selected = selected / jnp.maximum(
            selected.sum(-1, keepdims=True), 1e-9)
    combine = dispatch * selected[..., None]
    # Switch aux loss: E * sum_e f_e * p_e  (f: token fraction routed to e,
    # p: mean router prob) — pushes toward uniform load. f is divided by
    # top_k so the uniform-load floor is 1.0 regardless of k (the
    # coefficient stays top_k-invariant).
    f = mask.mean(axis=0) / top_k
    p = gates.mean(axis=0)
    aux = E * jnp.sum(f * p)
    return dispatch, combine, aux


def moe_mlp(x: jnp.ndarray, router_w: jnp.ndarray, w_gate: jnp.ndarray,
            w_up: jnp.ndarray, w_down: jnp.ndarray, *,
            top_k: int = 2, capacity_factor: float = 1.25,
            norm_topk_prob: bool = True,
            csl=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SwiGLU expert MLP over a routed token subset.

    x (B, S, D); router_w (D, E); w_gate/w_up (E, D, M); w_down (E, M, D).
    ``csl``: optional sharding-constraint fn (arr, logical_axes) -> arr —
    pins the expert-major intermediates to the ep axis so GSPMD routes the
    dispatch/combine einsums as all-to-alls over ICI.
    Returns (out (B, S, D), aux_loss).
    """
    B, S, D = x.shape
    E = router_w.shape[-1]
    T = B * S
    xt = x.reshape(T, D)
    # router in f32: tiny matmul, stability matters more than speed
    gates = jax.nn.softmax(
        jnp.einsum("td,de->te", xt.astype(jnp.float32),
                   router_w.astype(jnp.float32)), axis=-1)
    capacity = max(int(top_k * T / E * capacity_factor), 1)
    capacity = -(-capacity // 8) * 8  # sublane-aligned buffers
    dispatch, combine, aux = moe_dispatch(gates, top_k, capacity,
                                          norm_topk_prob)
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(x.dtype)

    expert_in = jnp.einsum("tec,td->ecd", dispatch, xt)    # all-to-all in
    if csl is not None:
        expert_in = csl(expert_in, ("expert", None, "embed"))
    g = jnp.einsum("ecd,edm->ecm", expert_in, w_gate)
    u = jnp.einsum("ecd,edm->ecm", expert_in, w_up)
    h = jax.nn.silu(g) * u
    expert_out = jnp.einsum("ecm,emd->ecd", h, w_down)
    if csl is not None:
        expert_out = csl(expert_out, ("expert", None, "embed"))
    out = jnp.einsum("tec,ecd->td", combine, expert_out)   # all-to-all out
    return out.reshape(B, S, D), aux


def moe_mlp_oracle(x, router_w, w_gate, w_up, w_down, *, top_k=2,
                   norm_topk_prob=True):
    """Per-token reference (no capacity drops): for each token, sum over
    its top-k experts of prob * SwiGLU_e(x), the chosen probabilities
    renormalised to sum to 1 where ``norm_topk_prob``. The test oracle
    of ``moe_mlp`` and ``moe_mlp_routed``: it computes every expert on
    every token, in float32."""
    B, S, D = x.shape
    xt = x.reshape(-1, D).astype(jnp.float32)
    gates = jax.nn.softmax(xt @ router_w.astype(jnp.float32), axis=-1)
    mask = _top_k_mask(gates, top_k)
    weights = gates * mask
    if norm_topk_prob:
        weights = weights / jnp.maximum(
            weights.sum(-1, keepdims=True), 1e-9)
    # compute EVERY expert on every token, weight, and sum
    g = jnp.einsum("td,edm->etm", xt, w_gate.astype(jnp.float32))
    u = jnp.einsum("td,edm->etm", xt, w_up.astype(jnp.float32))
    h = jax.nn.silu(g) * u
    outs = jnp.einsum("etm,emd->etd", h, w_down.astype(jnp.float32))
    out = jnp.einsum("te,etd->td", weights, outs)
    return out.reshape(B, S, D).astype(x.dtype)


# ---------------------------------------------------------------------------
# Serving: one dropless routed expert layer.
# ---------------------------------------------------------------------------
# The (token, expert) rows are sorted by expert into ragged groups and the
# three expert products run as grouped matrix multiplications over those
# groups. No capacity exists, so nothing is dropped and a sequence's
# output does not depend on what else is in the batch; rows that are not
# tokens (a prefill bucket's padding, inactive decode slots) sort behind
# every group and are multiplied with nothing; int8 expert weights are
# read as int8 and widened a tile at a time, their per-output-channel
# scales applied to the product by each row's expert.


# The grouped kernel's tiles, chosen from the shapes it is handed. Rows:
# a row tile no larger than a group (a 1,024-token prompt gives one of
# OLMoE's experts 128 rows) is not computed again for every group that
# shares it (PR 28), and 256 rows were slower or no faster than 128 at
# every bucket from 1,024 to 12,288 tokens at both served families'
# widths (PR 34, PERF.md section 6). Widths: the weight block may hold
# as many elements as the block this kernel runs on a v5e at OLMoE's
# widths, 2,048 x 1,024 (2 MB of int8, fetched twice over and widened in
# VMEM); within that the whole contracted width comes first (one pass
# over the accumulator, and the row tiles of one group ask for the same
# weight block one after another, so it is fetched once a group), then
# the widest output tile (the sorted rows are read once).
_TILE_M, _TILE_ELEMENTS = 128, 2048 * 1024

# the kernel's name in a compiled program and a device trace
GROUPED_KERNEL = "rt_moe_gmm"

# the tiles chosen so far, by product shape: (m, k, n, weight dtype) ->
# [tm, tk, tn], or [] where ``lax.ragged_dot`` runs the product
_chosen_tiles: Dict[Tuple[int, int, int, str], List[int]] = {}


def _pick_tiles(m: int, k: int, n: int) -> Optional[Tuple[int, int, int]]:
    """(tm, tk, tn) for an [m, k] x [k, n] grouped product: tk and tn
    divisors of k and n that are multiples of 128, tk the largest that
    leaves room for a tn inside ``_TILE_ELEMENTS``, tn the largest beside
    it. None where the kernel cannot run the shape (a width 128 does not
    divide, rows that are no whole row tiles or no multiple of bf16's
    sublane packing)."""
    tm = min(m, _TILE_M)
    if k % 128 or n % 128 or m % 16 or m % tm:
        return None

    def lane_divisors(size):
        return [t for t in range(128, size + 1, 128) if size % t == 0]

    tk, tn = max((tk, tn) for tk in lane_divisors(k)
                 for tn in lane_divisors(n) if tk * tn <= _TILE_ELEMENTS)
    return tm, tk, tn


def chosen_tiles(dim: int, width: int) -> Dict[str, List[int]]:
    """The tiles ``grouped_matmul`` chose in this process for the
    products of an expert layer of these widths ([dim, width] and
    [width, dim]), by shape: "<rows>x<k>x<n>:<weight dtype>" ->
    [tm, tk, tn], or [] for a product left to ``lax.ragged_dot``. Chosen
    where a program is traced, so reading them costs a step nothing."""
    return {f"{m}x{k}x{n}:{dtype}": list(tiles)
            for (m, k, n, dtype), tiles in _chosen_tiles.items()
            if (k, n) in ((dim, width), (width, dim))}


def _gmm_xla(lhs, w, scale, layer, row_expert, group_sizes, out_dtype):
    """Grouped product in plain jax (the CPU's path, and a TPU's where
    the widths are not lane-aligned): ``lax.ragged_dot`` over the
    groups, rows behind the last group left at zero."""
    w = w[layer]
    out = jax.lax.ragged_dot(lhs, w.astype(lhs.dtype), group_sizes,
                             preferred_element_type=jnp.float32)
    if scale is not None:
        scale = scale[layer]
        out = out * scale[jnp.minimum(row_expert, scale.shape[0] - 1)]
    return out.astype(out_dtype)


def _gmm_tpu(lhs, w, scale, layer, group_sizes, out_dtype,
             interpret=False):
    """Grouped product as a Pallas kernel, after megablox's ``gmm``
    (whose group bookkeeping it reuses): the weight tile arrives in VMEM
    as it is stored (int8 or bf16) and is widened there, so no wide copy
    of the experts is ever written to HBM; the float32 accumulator is
    scaled by the group's per-output-channel scales as it is stored.
    ``w`` is the whole stack [L, E, K, N] and ``layer`` picks its layer
    in the tile's address: slicing a layer out first would copy that
    layer's experts (134 MB a matrix at OLMoE's widths) every step.
    Only row tiles that hold a group's rows are visited (the grid's
    extent is computed from ``group_sizes``): rows behind the last group
    cost nothing and are left unwritten."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)

    m, k = lhs.shape
    n_layers, groups, _, n = w.shape
    tm, tk, tn = _pick_tiles(m, k, n)
    tiles_k = k // tk
    (offsets, group_ids, m_tile_ids), active_tiles = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=groups, visit_empty_groups=False)
    if scale is None:
        scale = jnp.ones((n_layers, groups, n), jnp.float32)

    def kernel(layer, offsets, group_ids, m_tile_ids, lhs_ref, w_ref,
               s_ref, out_ref, acc):
        tile, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += jnp.dot(
            lhs_ref[...],
            w_ref[...].astype(jnp.float32).astype(lhs_ref.dtype),
            preferred_element_type=jnp.float32)

        @pl.when(k_i == tiles_k - 1)
        def _():
            # a row tile on a group boundary is visited once per group:
            # store this group's rows, keep what the others stored
            group = group_ids[tile]
            rows = m_tile_ids[tile] * tm + jax.lax.broadcasted_iota(
                jnp.int32, (tm, tn), 0)
            mine = (rows >= offsets[group]) & (rows < offsets[group + 1])
            out_ref[...] = jnp.where(
                mine, acc[...] * s_ref[...],
                out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, t, k_i, lay, off, gid,
                             mid: (mid[t], k_i)),
                pl.BlockSpec((None, None, tk, tn),
                             lambda n_i, t, k_i, lay, off, gid, mid:
                             (lay[0], gid[t], k_i, n_i)),
                pl.BlockSpec((None, None, 1, tn),
                             lambda n_i, t, k_i, lay, off, gid, mid:
                             (lay[0], gid[t], 0, n_i)),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n_i, t, k_i, lay, off, gid, mid:
                (mid[t], n_i)),
            grid=(n // tn, active_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name=GROUPED_KERNEL,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), offsets, group_ids,
      m_tile_ids, lhs, w, scale[:, :, None, :])


def grouped_matmul(lhs, w, row_expert, group_sizes, layer=None):
    """``lhs[rows of group e] @ w[e]`` for every group: ``lhs`` [M, K]
    sorted by group, ``w`` [E, K, N] raw or quantized (``{"q", "s"}``
    with ``s`` [E, N]), ``row_expert`` [M] each row's group (E for a row
    behind the last group), ``group_sizes`` [E]. With ``layer`` (a
    traced index), ``w`` is a stack [L, E, K, N] (``s`` [L, E, N]) and
    that layer's experts are used, without a copy of them being made.
    Rows behind the last group hold nothing a caller may read. The
    kernel runs where the program is lowered for a TPU and the widths
    are lane-aligned."""
    q, scale = (w["q"], w["s"]) if isinstance(w, dict) else (w, None)
    if layer is None:
        layer = jnp.int32(0)
        q, scale = q[None], (None if scale is None else scale[None])
    m, k = lhs.shape
    n = q.shape[-1]
    operands = (lhs, q, scale, layer, row_expert, group_sizes)

    def xla(lhs, q, scale, layer, row_expert, group_sizes):
        return _gmm_xla(lhs, q, scale, layer, row_expert, group_sizes,
                        lhs.dtype)

    tiles = _pick_tiles(m, k, n)
    _chosen_tiles[m, k, n, str(q.dtype)] = list(tiles or ())
    if tiles is None:
        return xla(*operands)

    def tpu(lhs, q, scale, layer, row_expert, group_sizes):
        return _gmm_tpu(lhs, q, scale, layer, group_sizes, lhs.dtype)

    return jax.lax.platform_dependent(*operands, tpu=tpu, default=xla)


_EXPERT_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}
_ROUTER_SCORES = {"softmax": lambda logits: jax.nn.softmax(logits, axis=-1),
                  "sigmoid": jax.nn.sigmoid}


def router_logits(x, router_w):
    """The router's product, float32 at full precision: a tie between
    the k-th and the next expert decides everything after it. x [..., D],
    router_w [D, E] -> [..., E]."""
    with jax.named_scope("rt.moe.route"):
        return jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                          router_w.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)


def route(probs, top_k: int, *, norm_topk_prob: bool = True,
          n_group: int = 1, topk_group: int = 1, scale: float = 1.0,
          bias=None):
    """The router's choice: probs [T, E] float32 (softmax probabilities
    or sigmoid scores) -> (weight [T, k], chosen [T, k]). ``bias`` [E]
    float32: the CHOICE (of groups and of experts) is made by ``probs +
    bias``, and the chosen experts' weights are their ``probs``, which
    never see it. Group-limited (``n_group`` > 1, DeepSeek-V2's
    ``group_limited_greedy``): the experts are ``n_group`` groups of
    neighbours, a group's score is its best expert's probability, every
    expert outside the ``topk_group`` best groups is given probability
    0, and the ``top_k`` largest of what is left are chosen. The chosen
    probabilities are used as the router gave them, or renormalised to
    sum to 1 where ``norm_topk_prob``; then x ``scale``."""
    T, E = probs.shape
    by = probs if bias is None else probs + bias
    if n_group > 1:
        best = by.reshape(T, n_group, E // n_group).max(-1)
        _, groups = jax.lax.top_k(best, topk_group)             # [T, g]
        kept = jnp.zeros((T, n_group), bool).at[
            jnp.arange(T)[:, None], groups].set(True)
        # a biased score can lie below 0: what is left out lies below all
        by = jnp.where(jnp.repeat(kept, E // n_group, axis=1), by,
                       0.0 if bias is None else -jnp.inf)
    weight, chosen = jax.lax.top_k(by, top_k)                   # [T, k]
    if bias is not None:
        weight = jnp.take_along_axis(probs, chosen, axis=-1)
    if norm_topk_prob:
        weight = weight / jnp.maximum(weight.sum(-1, keepdims=True), 1e-9)
    return weight * scale if scale != 1.0 else weight, chosen


def moe_mlp_routed(x, router_w, w_gate, w_up, w_down, *, top_k: int,
                   norm_topk_prob: bool = True, valid=None, layer=None,
                   logits=None, activation: str = "silu",
                   n_group: int = 1, topk_group: int = 1,
                   scale: float = 1.0, held=None, shared=None,
                   score: str = "softmax", bias=None):
    """Dropless top-k gated expert layer, the serving path's one.

    x (B, S, D); router_w (D, E) float32; w_gate/w_up (E, D, M) and
    w_down (E, M, D), raw or int8 (``ops/quant.py``); ``valid`` (B, S)
    bool, False for rows that are not tokens (absent: all are). With
    ``layer`` the three expert weights are whole stacks with a leading
    layers axis (see ``grouped_matmul``). The
    chosen experts' probabilities are used as the router gave them, or
    renormalised to sum to 1 where ``norm_topk_prob``. ``logits``
    (B, S, E) float32: the router's logits where they were computed
    earlier and from another tensor than ``x`` (``router_logits`` of the
    attention's input, for a model whose router sits there); the rows
    are routed by them and ``router_w`` is not read. ``activation``: an
    expert is ``act(gate) * up``, "silu" or "relu". ``n_group``,
    ``topk_group``, ``scale``, ``bias``: see ``route``. ``score``: what
    ``route`` is handed, the logits' "softmax" over all experts or their
    "sigmoid", an expert's own.

    ``held`` (first, count): the experts that are HERE, one chip's share
    of an expert-parallel layer. The router keeps its E outputs and
    chooses among all of them; the expert matrices hold ``count``
    experts, E's ``first`` .. ``first + count - 1``; a (token, expert)
    row whose expert is elsewhere sorts behind every group, exactly as
    a row that is not a token, and adds nothing: the output is this
    chip's part of the layer's result. Nothing stands in for the other
    chips or the exchange with them. None: every expert is here.

    ``shared`` (w_gate (D, Ms), w_up, w_down (Ms, D)): a dense SwiGLU
    every token passes through, added to the routed part.

    Returns (out (B, S, D), the routed part zero at rows that are not
    tokens; counts int32 [3]: the (token, expert) rows the expert
    products were given, the experts with at least one row, and the
    tokens' rows routed to experts that are not here).
    """
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D)
    if logits is None:
        logits = router_logits(xt, router_w)
    E = logits.shape[-1]
    first, count = held or (0, E)
    with jax.named_scope("rt.moe.route"):
        probs = _ROUTER_SCORES[score](logits.reshape(T, E))
        weight, chosen = route(probs, top_k, norm_topk_prob=norm_topk_prob,
                               n_group=n_group, topk_group=topk_group,
                               scale=scale, bias=bias)
        expert = chosen.reshape(T * top_k) - first
        given = None if valid is None else jnp.repeat(valid.reshape(T),
                                                      top_k)
        elsewhere = jnp.int32(0)
        if held is not None:
            here = (expert >= 0) & (expert < count)
            elsewhere = jnp.sum(~here if given is None else given & ~here,
                                dtype=jnp.int32)
            given = here if given is None else given & here
        if given is not None:
            # rows that are not tokens, and rows whose expert is
            # elsewhere, sort behind every group
            expert = jnp.where(given, expert, count)
        order = jnp.argsort(expert, stable=True)
        row_expert = expert[order]
        group_sizes = jnp.sum(
            expert[:, None] == jnp.arange(count, dtype=expert.dtype)[None, :],
            axis=0, dtype=jnp.int32)
        rows = xt[order // top_k]                               # [T*k, D]
    with jax.named_scope("rt.moe.experts"):
        gate = grouped_matmul(rows, w_gate, row_expert, group_sizes, layer)
        up = grouped_matmul(rows, w_up, row_expert, group_sizes, layer)
        hidden = (_EXPERT_ACTS[activation](gate.astype(jnp.float32))
                  * up.astype(jnp.float32)).astype(x.dtype)
        down = grouped_matmul(hidden, w_down, row_expert, group_sizes,
                              layer)
    with jax.named_scope("rt.moe.combine"):
        # rows behind the last group were never written (they may hold
        # anything, NaN too): mask them, which also zeroes the output of
        # rows that are not tokens
        down = jnp.where((row_expert < count)[:, None], down, 0)
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * top_k, dtype=order.dtype), unique_indices=True)
        out = jnp.einsum("tk,tkd->td", weight,
                         down[back].reshape(T, top_k, D).astype(jnp.float32))
    if shared is not None:
        with jax.named_scope("rt.moe.shared"):
            g = weight_einsum("td,dm->tm", xt, shared[0])
            u = weight_einsum("td,dm->tm", xt, shared[1])
            out = out + weight_einsum(
                "tm,md->td", jax.nn.silu(g) * u, shared[2]
            ).astype(jnp.float32)
    counts = jnp.stack([group_sizes.sum(),
                        jnp.sum(group_sizes > 0, dtype=jnp.int32),
                        elsewhere])
    return out.reshape(B, S, D).astype(x.dtype), counts
