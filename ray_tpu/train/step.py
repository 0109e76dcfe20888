"""pjit train-step factory: sharded, donated, compiled once.

This is the device-plane heart of training: given a loss function, a
mesh, and logical-axis rules, produce a jitted ``step(state, batch)``
whose inputs/outputs carry NamedShardings (params FSDP/TP-sharded, batch
dp-sharded) and whose buffers are donated, so XLA keeps params in HBM and
overlaps the grad all-reduce with the backward pass. The reference's
equivalent is torch DDP inside Train workers (ref:
train/torch/train_loop_utils.py prepare_model) — rebuilt here as GSPMD.
"""

from __future__ import annotations

import os
import time
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.sharding import DEFAULT_RULES, logical_sharding, shard_pytree
from ..util import tracing
from .telemetry import StepInstrumenter, estimate_flops_per_token  # noqa: F401
from . import session as _sess


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt_state: Any


def _batch_sharding(mesh: Mesh, rules) -> NamedSharding:
    return logical_sharding(mesh, ("batch", "seq"), rules)


# ---- goodput-plane helpers (worker-side step instrumentation) ----------

def _batch_signature(batch) -> str:
    """Stable shape/dtype fingerprint of a batch pytree: the unit of
    XLA compilation the recompile detector keys on."""
    leaves = jax.tree.leaves(batch)
    return ",".join(f"{getattr(x, 'shape', ())}/{getattr(x, 'dtype', '?')}"
                    for x in leaves)


def _batch_tokens(batch) -> int:
    """Token count for throughput math: the ``tokens`` leaf when the
    batch names one (the lm convention), else the largest leaf."""
    if isinstance(batch, dict) and "tokens" in batch:
        return int(getattr(batch["tokens"], "size", 0))
    sizes = [int(getattr(x, "size", 0)) for x in jax.tree.leaves(batch)]
    return max(sizes, default=0)


def _compile_cache_entries() -> int:
    """Entry count of the persistent XLA compile cache dir (cold-compile
    ground truth for classify_compile)."""
    try:
        d = jax.config.jax_compilation_cache_dir
        if not d or not os.path.isdir(d):
            return 0
        return len(os.listdir(d))
    except Exception:  # graftlint: ignore[swallow] — cache probe is
        return 0  # advisory; classify_compile falls back to duration


def _note_recompile(old_sig: str, new_sig: str) -> None:
    """A NEW batch signature after the first compile: the silent
    step-time killer. Raise a WARNING cluster event naming the shape
    change (fire-and-forget — telemetry must not stall the step)."""
    try:
        from .. import _worker_api

        core = _worker_api._core
        if core is None:
            return
        core.io.spawn(core.gcs.call("report_event", {
            "source": "train", "severity": "WARNING",
            "message": ("train step recompiled: batch signature changed "
                        f"{old_sig or '<none>'} -> {new_sig}"),
            "fields": {"kind": "train_recompile",
                       "old_signature": old_sig,
                       "new_signature": new_sig}}))
    except Exception:  # graftlint: ignore[swallow] — fire-and-forget
        pass  # event; losing it must not stall the step


def opt_state_shardings(optimizer, params, param_shardings, mesh: Mesh):
    """Shardings for ``optimizer.init(params)`` output, explicitly.

    Optax first/second-moment states embed whole copies of the param
    pytree (mu/nu); any subtree whose structure matches ``params`` gets
    the param shardings leaf-for-leaf, everything else (step counters,
    scalars) replicates. ``jax.jit`` gives no mirroring guarantee on its
    own — at 8B scale replicated Adam moments would blow HBM.
    """
    pdef = jax.tree.structure(params)
    replicated = NamedSharding(mesh, P())

    def matches_params(sub) -> bool:
        try:
            return jax.tree.structure(sub) == pdef
        except Exception:
            return False

    abstract = jax.eval_shape(optimizer.init, params)
    return jax.tree.map(
        lambda sub: param_shardings if matches_params(sub) else replicated,
        abstract,
        is_leaf=lambda x: matches_params(x)
        or isinstance(x, jax.ShapeDtypeStruct))


def make_train_step(
    loss_fn: Callable[..., jax.Array],
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    param_axes,
    rules=DEFAULT_RULES,
    model_flops_per_token: Optional[float] = None,
):
    """Build (init_fn, step_fn) for ``loss_fn(params, batch) -> scalar``.

    init_fn(params) -> TrainState with sharded params/opt state placed on
    the mesh. step_fn(state, batch) -> (state, metrics); compiled with
    donated state so params update in place in HBM.

    ``model_flops_per_token`` (e.g. ``estimate_flops_per_token(
    cfg.n_params())``) lets the goodput ledger compute per-step MFU and
    tok/s/chip. Inside a Trainer session the returned step_fn and
    place_batch are instrumented — compile vs cache-hit vs compute phase
    attribution, recompile detection, token/flops accounting — at the
    cost of a device sync per call; outside a session they are the bare
    jitted functions.
    """
    param_shardings = lambda params: shard_pytree(
        params, param_axes, mesh, rules)

    def init_fn(params):
        ps = param_shardings(params)
        params = jax.device_put(params, ps)
        opt_sh = opt_state_shardings(optimizer, params, ps, mesh)
        opt_state = jax.jit(optimizer.init, out_shardings=opt_sh)(params)
        step0 = jnp.zeros((), jnp.int32)
        return TrainState(step=step0, params=params, opt_state=opt_state)

    @partial(jax.jit, donate_argnums=(0,))
    def step_fn(state: TrainState, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params)
        params = optax.apply_updates(state.params, updates)
        gnorm = optax.global_norm(grads)
        return TrainState(state.step + 1, params, opt_state), {
            "loss": loss, "grad_norm": gnorm, "step": state.step + 1,
        }

    from .._private.config import global_config

    instrumenter = StepInstrumenter(
        cache_entries=_compile_cache_entries,
        hit_threshold_s=global_config().train_compile_cache_hit_threshold_s,
        on_recompile=_note_recompile)

    def instrumented_step(state: TrainState, batch):
        session = _sess._session
        if session is None or not session.telemetry_on:
            return step_fn(state, batch)
        sig = _batch_signature(batch)
        step = session.timeline.step
        # the timeline learns the phase after the fact (compile or
        # compute), so the span is opened here, around the work
        with tracing.step_span("rt.train.step", step), \
                tracing.span("rt.train.compute", step=step):
            out = instrumenter.run(lambda: step_fn(state, batch), sig,
                                   block=jax.block_until_ready)
        last = instrumenter.last
        session.timeline.record_interval(last["phase"], last["t0"],
                                         last["t1"])
        tokens = _batch_tokens(batch)
        session.note_step(
            tokens=tokens,
            flops=(model_flops_per_token or 0.0) * tokens,
            chips=jax.local_device_count(),
            compile_kind=last["compile_kind"],
            recompile=last["recompile"],
            batch_shape=sig)
        return out

    # ahead-of-time access to the jitted step for callers that read the
    # compiled program (chip_smoke.py looks for the flash kernels in it)
    instrumented_step.lower = step_fn.lower

    def place_batch(batch):
        session = _sess._session
        if session is None or not session.telemetry_on:
            return jax.device_put(batch, _batch_sharding(mesh, rules))
        t0 = time.time()
        with tracing.span("rt.train.host_to_device",
                          step=session.timeline.step):
            placed = jax.block_until_ready(
                jax.device_put(batch, _batch_sharding(mesh, rules)))
        session.timeline.record_interval("host_to_device", t0, time.time())
        return placed

    return init_fn, instrumented_step, place_batch


def make_eval_step(loss_fn: Callable[..., jax.Array]):
    @jax.jit
    def eval_fn(params, batch):
        return loss_fn(params, batch)

    return eval_fn
