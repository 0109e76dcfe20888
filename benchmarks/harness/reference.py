"""The plain reference: a Mistral/Llama-shaped decoder's forward pass and
next-token loss in straightforward ``jax.numpy``.

float32 throughout, ``default_matmul_precision("highest")``, a Python
loop over layers, attention as one masked softmax over the whole
sequence: no kernels, no cache, no paging, no sharding rules. It follows
the published architecture (pre-norm residual blocks, RMSNorm, rotary
embeddings in the half-split layout of the Hugging Face checkpoints,
grouped-query attention, SwiGLU, untied output head). One departure, so
that it reads the system's own weights: an int8 weight arrives as
``{"q": int8, "s": float32 per output channel}`` and is multiplied out to
float32, one layer at a time, before use.

It takes the parameter tree in the layout ``ray_tpu.models.llama`` keeps
(layers stacked on a leading axis) and the configuration as the keys of
the published ``config.json``; it imports nothing from ``ray_tpu``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

# axes a matmul contracts, per weight of ONE layer (no leading layers axis)
_CONTRACT = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
             "w_gate": (0,), "w_up": (0,), "w_down": (0,)}


def _f32(w, contract=()):
    """A weight as float32; an int8 weight times its per-channel scale."""
    if isinstance(w, dict):
        scale = jnp.expand_dims(w["s"].astype(jnp.float32), contract)
        return w["q"].astype(jnp.float32) * scale
    return w.astype(jnp.float32)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def _rotate(x, theta):
    """x: [batch, seq, heads, head_dim]; pairs are (i, i + head_dim/2)."""
    seq, hd = x.shape[1], x.shape[3]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                / hd))
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "theta", "eps"))
def _layer(x, lp, *, n_heads, n_kv_heads, theta, eps):
    w = {name: _f32(lp[name], _CONTRACT[name]) for name in _CONTRACT}
    h = _rms_norm(x, _f32(lp["attn_norm"]), eps)
    q = _rotate(jnp.einsum("bsd,dhk->bshk", h, w["wq"]), theta)
    k = _rotate(jnp.einsum("bsd,dhk->bshk", h, w["wk"]), theta)
    v = jnp.einsum("bsd,dhk->bshk", h, w["wv"])
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) * q.shape[-1] ** -0.5
    seq = x.shape[1]
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attended = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(scores, -1), v)
    x = x + jnp.einsum("bshk,hkd->bsd", attended, w["wo"])
    h = _rms_norm(x, _f32(lp["mlp_norm"]), eps)
    gate = jnp.einsum("bsd,dm->bsm", h, w["w_gate"])
    up = jnp.einsum("bsd,dm->bsm", h, w["w_up"])
    return x + jnp.einsum("bsm,md->bsd", jax.nn.silu(gate) * up,
                          w["w_down"])


@jax.jit
def _embed(table, tokens):
    if isinstance(table, dict):       # per-row scales
        rows = jnp.take(table["q"], tokens, axis=0).astype(jnp.float32)
        return rows * jnp.take(table["s"], tokens, axis=0)[..., None]
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, *, eps):
    return jnp.einsum("bsd,dv->bsv", _rms_norm(x, _f32(final_norm), eps),
                      _f32(lm_head, (0,)))


def forward_logits(params, tokens, config: dict):
    """tokens [batch, seq] int32 -> float32 logits [batch, seq, vocab]."""
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], tokens)
        for i in range(int(config["num_hidden_layers"])):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x = _layer(x, lp, n_heads=int(config["num_attention_heads"]),
                       n_kv_heads=int(config["num_key_value_heads"]),
                       theta=float(config["rope_theta"]),
                       eps=float(config["rms_norm_eps"]))
        return _head(x, params["final_norm"], params["lm_head"],
                     eps=float(config["rms_norm_eps"]))


def next_token_loss(params, tokens, config: dict, z_loss: float = 0.0):
    """Mean next-token cross-entropy of ``tokens`` [batch, seq] (targets
    are the tokens shifted left; the last position has none), plus
    ``z_loss`` x the squared log-partition, the regulariser the system's
    ``lm_loss`` adds by default (1e-4)."""
    logits = forward_logits(params, tokens, config)[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    target = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(logz - target + z_loss * logz * logz)


def chosen_token_margins(params, prompts, answers, config: dict):
    """Teacher-forcing the reference on prompt + answer: for every token
    the system chose, how far its reference logit lies below the
    reference's largest logit at that position, in units of that
    position's logit standard deviation. 0 where the reference agrees.
    ``prompts`` [batch, p] and ``answers`` [batch, a] are whole arrays
    (equal lengths within a call). Returns float32 [batch, a]."""
    tokens = jnp.concatenate([prompts, answers], axis=1)
    logits = forward_logits(params, tokens, config)
    p = prompts.shape[1]
    at = logits[:, p - 1:-1]                      # predicts answers[:, i]
    chosen = jnp.take_along_axis(at, answers[..., None], -1)[..., 0]
    return (at.max(-1) - chosen) / at.std(-1)
