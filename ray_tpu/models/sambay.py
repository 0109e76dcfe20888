"""The weights of a decoder-hybrid-decoder stack (SambaY, arXiv:2507.06607:
``LlamaConfig.scan_state``), a stack a layer kind, each layer with its two
LayerNorms (weight and bias) and its SwiGLU:

  ``scan_layers``   every "scan" layer, in the stack's order: ``w_in``
                    [d, 2E] (u, then z), the convolution ``conv_w``
                    [taps, E] (the newest input last) and ``conv_b``,
                    ``w_x`` [E, R + 2N] (r, B, C), ``w_dt`` [R, E] and
                    ``b_dt``, ``a_log`` [N, E], ``d_skip`` [E], ``wo``
                    [E / 128, 128, d]
  ``layers``        the "window_diff" layers, then the "full_diff" one:
                    ``wq``, ``wk``, ``wv`` with biases ``bq``, ``bk``,
                    ``bv``, the four ``lam_*`` vectors of a head's width,
                    ``lam0`` (``lambda_init`` of the layer's index: a
                    constant a layer, made here and no parameter),
                    ``sub_norm`` [2 hd], ``wo`` [pairs, 2 hd, d] and ``bo``
  ``gmu_layers``    ``wg`` [d, E] and ``wo`` [E / 128, 128, d]
  ``cross_layers``  ``wq``, ``bq``, the ``lam_*``, ``lam0``, ``sub_norm``,
                    ``wo``, ``bo``: no key and no value of their own

E = ``scan_expand * dim`` channels, N = ``scan_state``, R =
``scan_dt_rank``. The channel axis is last everywhere (whole rows of
lanes); the state of a channel is a column of ``a_log``. The table is
the head (``params`` has no ``lm_head``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# a step's size ``dt`` is seeded log-uniform between these (Mamba's
# ``dt_min``, ``dt_max``): with ``A = -(1 .. N)`` a channel forgets in 1 to
# 1,000 tokens
DT_MIN, DT_MAX = 1e-3, 1e-1
LAMBDA_INIT_SCALE = 0.1


def lambda_init(layer: int) -> float:
    """Differential attention's ``lambda_0`` of the stack's ``layer``."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def layers_of(cfg, kind: str):
    """The stack indices of the layers of ``kind``."""
    return tuple(i for i, k in enumerate(cfg.layer_kinds) if k == kind)


def n_params(cfg) -> int:
    d, m, E = cfg.dim, cfg.mlp_dim, cfg.scan_channels
    N, R, hd = cfg.scan_state, cfg.scan_dt_rank, cfg.head_dim
    h, hkv = cfg.n_heads, cfg.n_kv_heads
    each = 3 * d * m + 4 * d
    scan = (d * 2 * E + cfg.scan_conv * E + E + E * (R + 2 * N) + R * E + E
            + N * E + E + E * d)
    shared = 4 * hd + 2 * hd + h * hd * d + d    # lam, norm, wo, bo
    attn = d * (h + 2 * hkv) * hd + (h + 2 * hkv) * hd + shared
    cross = d * h * hd + h * hd + shared
    gmu = 2 * d * E
    P, Q = cfg.hybrid_periods
    return (cfg.vocab * d + 2 * d + (P + 1) * (scan + attn) + Q * (gmu + cross)
            + cfg.n_layers * each)


def init_params(key, cfg, gains=None):
    """Seeded weights: matrices normal at 1/sqrt(fan_in) (``gains``: a
    matrix's name -> a factor), norm weights scattered about 1, every
    bias about 0 and NOT 0 (a bias left out is another answer), ``dt``'s
    bias and ``a_log`` as Mamba seeds them."""
    d, m, E = cfg.dim, cfg.mlp_dim, cfg.scan_channels
    N, R, hd, taps = (cfg.scan_state, cfg.scan_dt_rank, cfg.head_dim,
                      cfg.scan_conv)
    h, hkv = cfg.n_heads, cfg.n_kv_heads
    gains = dict(gains or {})
    used = set()
    count = iter(range(10_000))

    def k():
        return jax.random.fold_in(key, next(count))

    def norm(shape, fan_in, name):
        used.add(name)
        return (jax.random.normal(k(), shape, jnp.float32)
                * (gains.get(name, 1.0) * fan_in ** -0.5)).astype(cfg.dtype)

    def about(shape, centre, spread, dtype=None):
        return (centre + spread * jax.random.normal(k(), shape, jnp.float32)
                ).astype(dtype or cfg.dtype)

    def block(L):
        """What every layer has: the two norms and the feed-forward."""
        return {
            "attn_norm": about((L, d), 1.0, 0.25),
            "attn_norm_bias": about((L, d), 0.0, 0.1),
            "mlp_norm": about((L, d), 1.0, 0.25),
            "mlp_norm_bias": about((L, d), 0.0, 0.1),
            "w_gate": norm((L, d, m), d, "w_gate"),
            "w_up": norm((L, d, m), d, "w_up"),
            "w_down": norm((L, m, d), m, "w_down"),
        }

    def differential(*kinds):
        """A query's side of differential attention, and what follows
        the difference, of the layers of ``kinds`` in that order."""
        layers = sum((layers_of(cfg, kind) for kind in kinds), ())
        L = len(layers)
        return {
            "lam0": jnp.asarray([lambda_init(i) for i in layers],
                                jnp.float32),
            "wq": norm((L, d, h, hd), d, "wq"),
            "bq": about((L, h, hd), 0.0, 0.1),
            **{name: about((L, hd), 0.0, LAMBDA_INIT_SCALE, jnp.float32)
               for name in ("lam_q1", "lam_k1", "lam_q2", "lam_k2")},
            "sub_norm": about((L, 2 * hd), 1.0, 0.25),
            "wo": norm((L, h // 2, 2 * hd, d), h * hd, "wo"),
            "bo": about((L, d), 0.0, 0.1),
        }

    P, Q = cfg.hybrid_periods
    n_scan = P + 1
    dt = jnp.exp(jax.random.uniform(k(), (n_scan, E), jnp.float32)
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    params = {
        "embed": norm((cfg.vocab, d), d, "embed"),
        "scan_layers": {
            **block(n_scan),
            "w_in": norm((n_scan, d, 2 * E), d, "w_in"),
            "conv_w": norm((n_scan, taps, E), taps, "conv_w"),
            "conv_b": about((n_scan, E), 0.0, 0.1),
            "w_x": norm((n_scan, E, R + 2 * N), E, "w_x"),
            "w_dt": norm((n_scan, R, E), R, "w_dt"),
            # softplus(b_dt) = dt
            "b_dt": (dt + jnp.log(-jnp.expm1(-dt))),
            "a_log": jnp.broadcast_to(jnp.log(jnp.arange(
                1, N + 1, dtype=jnp.float32))[None, :, None], (n_scan, N, E)),
            "d_skip": about((n_scan, E), 1.0, 0.25, jnp.float32),
            "wo": norm((n_scan, E // 128, 128, d), E, "wo_scan"),
        },
        "layers": {
            **block(n_scan), **differential("window_diff", "full_diff"),
            "wk": norm((n_scan, d, hkv, hd), d, "wk"),
            "wv": norm((n_scan, d, hkv, hd), d, "wv"),
            "bk": about((n_scan, hkv, hd), 0.0, 0.1),
            "bv": about((n_scan, hkv, hd), 0.0, 0.1),
        },
        "gmu_layers": {
            **block(Q),
            "wg": norm((Q, d, E), d, "wg"),
            "wo": norm((Q, E // 128, 128, d), E, "wo_gmu"),
        },
        "cross_layers": {**block(Q), **differential("cross_diff")},
        "final_norm": about((d,), 1.0, 0.25),
        "final_norm_bias": about((d,), 0.0, 0.1),
    }
    unknown = set(gains) - used
    if unknown:
        raise ValueError(f"gains for matrices that are not seeded: "
                         f"{sorted(unknown)}")
    return params
