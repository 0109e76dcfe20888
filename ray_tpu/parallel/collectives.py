"""Collective ops over mesh axes — the XLA/ICI replacement for the
reference's NCCL/GLOO groups (ref: python/ray/util/collective/collective.py:
init_collective_group:123, allreduce:268, reducescatter:482, send:541,
recv:604; backends at util/collective/types.py:29-34).

Two usage modes:

1. **Inside shard_map / pjit** — call ``allreduce(x, axis="tp")`` etc.
   directly; they are thin wrappers over ``jax.lax`` collectives, so XLA
   schedules them on ICI and fuses around them.

2. **Eager, host-level** — ``pgroup(mesh, axis)`` returns a
   ``ProcessGroup`` whose methods compile one-off shard_map programs over
   global arrays. This mirrors the reference's imperative
   ``col.allreduce(tensor, group_name)`` API for code that isn't already
   inside a compiled program.
"""

from __future__ import annotations

import functools
import socket as _socket
import time as _time
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from ..exceptions import CollectiveTimeoutError

AxisName = Union[str, tuple]


def _try_core():
    """The connected runtime, or None when running outside a cluster
    (pure-jax usage must keep working with zero control-plane traffic)."""
    try:
        from .. import _worker_api

        return _worker_api.core()
    except Exception:
        return None

# ---------------------------------------------------------------------------
# Mode 1: symbolic — use inside shard_map/pjit-traced functions.
# ---------------------------------------------------------------------------


def allreduce(x, axis: AxisName, op: str = "sum"):
    """Allreduce along a mesh axis (ref: collective.py:268 allreduce)."""
    if op == "sum":
        return jax.lax.psum(x, axis)
    if op == "max":
        return jax.lax.pmax(x, axis)
    if op == "min":
        return jax.lax.pmin(x, axis)
    if op == "mean":
        return jax.lax.pmean(x, axis)
    if op == "prod":
        # exp(psum(log|x|)) with the sign recovered from the parity of
        # negative factors; a zero anywhere zeroes the product.
        mag = jnp.exp(jax.lax.psum(jnp.log(jnp.maximum(jnp.abs(x), 1e-300)),
                                   axis))
        n_neg = jax.lax.psum((x < 0).astype(jnp.int32), axis)
        has_zero = jax.lax.pmax((x == 0).astype(jnp.int32), axis)
        sign = jnp.where(n_neg % 2 == 0, 1.0, -1.0).astype(mag.dtype)
        return jnp.where(has_zero == 1, jnp.zeros_like(mag), sign * mag)
    raise ValueError(f"unsupported reduce op: {op}")


def allgather(x, axis: AxisName, *, concat_axis: int = 0, tiled: bool = True):
    """Allgather along a mesh axis (ref: collective.py allgather:~430)."""
    return jax.lax.all_gather(x, axis, axis=concat_axis, tiled=tiled)


def reducescatter(x, axis: AxisName, *, scatter_axis: int = 0, op: str = "sum"):
    """Reduce-scatter along a mesh axis (ref: collective.py:482)."""
    if op not in ("sum", "mean"):
        raise ValueError("reducescatter supports sum/mean")
    out = jax.lax.psum_scatter(x, axis, scatter_dimension=scatter_axis,
                               tiled=True)
    if op == "mean":
        out = out / jax.lax.psum(jnp.ones((), x.dtype), axis)
    return out


def broadcast(x, axis: AxisName, root: int = 0):
    """Broadcast the root shard's value to all shards along ``axis``."""
    idx = jax.lax.axis_index(axis)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return jax.lax.psum(masked, axis)


def alltoall(x, axis: AxisName, *, split_axis: int, concat_axis: int):
    """All-to-all: scatter ``split_axis``, gather ``concat_axis``.

    The primitive behind Ulysses-style sequence<->head swaps and MoE token
    dispatch (absent in the reference — SURVEY §5.7).
    """
    return jax.lax.all_to_all(x, axis, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def send(x, axis: AxisName, *, shift: int = 1):
    """Neighbour p2p along a ring: every rank sends to rank+shift.

    XLA has no one-sided send; ``ppermute`` is the ICI-native p2p — each
    device simultaneously sends and receives, riding neighbouring ICI
    links (ref: NCCL send at collective.py:541).
    """
    n = axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis, perm)


def recv(x, axis: AxisName, *, shift: int = 1):
    """Inverse permutation of ``send``: pull from rank+shift (ref: :604).

    ``recv(send(x, shift=k), shift=k) == x``.
    """
    return send(x, axis, shift=-shift)


# ---------------------------------------------------------------------------
# Mode 2: eager host-level process groups.
# ---------------------------------------------------------------------------


class ProcessGroup:
    """Imperative collective API over one mesh axis.

    Compiles (and caches) a shard_map program per (op, shape, dtype).
    Mirrors the reference's group objects
    (ref: util/collective/collective_group/nccl_collective_group.py).

    Input convention: the **leading axis is the rank axis** — inputs carry
    one slice per rank along dim 0 (shape ``(size, ...)`` or a multiple),
    for every op including ``reducescatter`` (matching the reference's
    per-rank input contribution semantics, collective.py:482).
    """

    def __init__(self, mesh: Mesh, axis: str, *,
                 group_name: Optional[str] = None, rank: int = 0,
                 world_size: Optional[int] = None):
        """``group_name`` opts the group into the stall sentinel: every
        op registers a per-participant arrival timestamp (clock-corrected
        in the GCS via the node table) under (group_name, step) so the
        collective watchdog can flag a step with some-but-not-all
        arrivals and per-step skew rolls into per-host straggler scores.
        ``rank``/``world_size`` identify this PROCESS among the
        participating processes (multi-host SPMD); they default to a
        single-process group the size of the mesh axis."""
        if axis not in mesh.axis_names:
            raise ValueError(f"axis {axis!r} not in mesh {mesh.axis_names}")
        self.mesh = mesh
        self.axis = axis
        self._cache = {}
        self.group_name = group_name
        self.rank = rank
        self.world_size = world_size if world_size is not None else 1
        self._step = 0

    @property
    def size(self) -> int:
        return self.mesh.shape[self.axis]

    # ------------------------------------------------ stall-sentinel hooks
    def _next_step(self) -> int:
        self._step += 1
        return self._step

    def _note_arrival(self, op: str, step: int,
                      deadline_s: Optional[float] = None):
        """Fire the arrival record for (group, step) at the GCS. Returns
        the GCS reply, or None when unregistered/offline — ops never
        fail because telemetry could not be delivered."""
        if self.group_name is None:
            return None
        core = _try_core()
        if core is None:
            return None
        try:
            return core.io.run(core.gcs.call("collective_arrival", {
                "group": self.group_name, "step": step,
                "rank": self.rank, "size": self.world_size, "op": op,
                "t": _time.time(),
                "node_id": core.node_id.hex() if core.node_id else "",
                "host": _socket.gethostname(),
                "deadline_s": deadline_s,
            }), timeout=5)
        except Exception:
            return None

    def _await_peers(self, op: str, step: int, timeout_s: float) -> None:
        """Block until every participating process reached (group, step)
        or raise CollectiveTimeoutError naming the missing ranks."""
        core = _try_core()
        if core is None:
            return
        try:
            reply = core.io.run(core.gcs.call("collective_wait", {
                "group": self.group_name, "step": step,
                "timeout_s": timeout_s, "size": self.world_size,
            }), timeout=timeout_s + 10)
        except CollectiveTimeoutError:
            raise
        except Exception:
            return  # GCS unreachable: the op itself still runs
        if not reply.get("complete", True):
            raise CollectiveTimeoutError(
                op, reply.get("missing", []), timeout_s,
                detail=f"group {self.group_name} step {step}: "
                       f"{reply.get('arrived', 0)}/{self.world_size} "
                       f"ranks arrived")

    def _sync(self, op: str, timeout_s: Optional[float]) -> None:
        """Per-op arrival registration (+ peer wait when a timeout is
        requested). No-ops entirely for plain single-process groups."""
        if self.group_name is None:
            return
        step = self._next_step()
        self._note_arrival(op, step, deadline_s=timeout_s)
        if timeout_s is not None and self.world_size > 1:
            self._await_peers(op, step, timeout_s)

    def _run(self, name, fn, x, in_spec, out_spec):
        key = (name, x.shape, str(x.dtype), in_spec, out_spec)
        if key not in self._cache:
            sm = shard_map(fn, mesh=self.mesh, in_specs=in_spec,
                           out_specs=out_spec, check_vma=False)
            self._cache[key] = jax.jit(sm)
        return self._cache[key](x)

    def allreduce(self, x, op: str = "sum",
                  timeout_s: Optional[float] = None):
        # x: replicated per-rank value laid out with leading axis = rank.
        self._sync(f"allreduce_{op}", timeout_s)
        spec = P(self.axis)
        return self._run(f"ar_{op}", lambda s: allreduce(s, self.axis, op),
                         x, spec, spec)

    def allgather(self, x, timeout_s: Optional[float] = None):
        self._sync("allgather", timeout_s)
        spec = P(self.axis)
        return self._run("ag", lambda s: allgather(s, self.axis),
                         x, spec, P())

    def reducescatter(self, x, op: str = "sum",
                      timeout_s: Optional[float] = None):
        # x: (size * chunk, ...) — rank i contributes x[i*chunk:(i+1)*chunk]
        # and receives sum_j x_j's i-th chunk (leading-axis-is-rank).
        self._sync(f"reducescatter_{op}", timeout_s)
        return self._run(f"rs_{op}",
                         lambda s: reducescatter(s, self.axis, op=op),
                         x, P(self.axis), P(self.axis))

    def broadcast(self, x, root: int = 0,
                  timeout_s: Optional[float] = None):
        self._sync(f"broadcast_{root}", timeout_s)
        spec = P(self.axis)
        return self._run(f"bc_{root}",
                         lambda s: broadcast(s, self.axis, root=root),
                         x, spec, spec)

    def shift(self, x, shift: int = 1,
              timeout_s: Optional[float] = None):
        self._sync(f"shift_{shift}", timeout_s)
        spec = P(self.axis)
        return self._run(f"sh_{shift}",
                         lambda s: send(s, self.axis, shift=shift),
                         x, spec, spec)

    def barrier(self, timeout_s: Optional[float] = None):
        """Synchronize the axis (and, for a named group, every
        participating process). With ``timeout_s`` the wait is bounded:
        a barrier some participants never reach raises
        CollectiveTimeoutError naming the missing ranks instead of
        blocking forever."""
        self._sync("barrier", timeout_s)
        # A zero-byte psum forces a synchronization point across the axis.
        one = jnp.zeros((self.size,), jnp.float32)
        if timeout_s is not None and self.group_name is None:
            # purely local sync with a deadline: run the device sync on a
            # helper thread so a wedged backend cannot block forever
            import concurrent.futures as _cf

            # no context manager: its exit does shutdown(wait=True),
            # which would block on the very sync the timeout bounds
            ex = _cf.ThreadPoolExecutor(1)
            fut = ex.submit(
                lambda: self.allreduce(one).block_until_ready())
            try:
                fut.result(timeout_s)
                return
            except _cf.TimeoutError:
                raise CollectiveTimeoutError(
                    "barrier", [], timeout_s,
                    detail="local mesh sync did not complete") from None
            finally:
                ex.shutdown(wait=False)
        self.allreduce(one).block_until_ready()


def pgroup(mesh: Mesh, axis: str, *, group_name: Optional[str] = None,
           rank: int = 0,
           world_size: Optional[int] = None) -> ProcessGroup:
    """Create (or fetch) the eager process group for a mesh axis
    (ref: init_collective_group collective.py:123)."""
    return ProcessGroup(mesh, axis, group_name=group_name, rank=rank,
                        world_size=world_size)


def barrier(mesh: Mesh, axis: Optional[str] = None,
            timeout_s: Optional[float] = None, *,
            group_name: Optional[str] = None, rank: int = 0,
            world_size: Optional[int] = None):
    """Cluster-wide barrier (ref: collective.py barrier). ``timeout_s``
    bounds the wait and raises CollectiveTimeoutError naming the
    missing ranks (stall sentinel, via ``group_name``/``rank``/
    ``world_size`` when multiple processes participate)."""
    axes = [axis] if axis else [a for a in mesh.axis_names
                                if mesh.shape[a] > 1]
    if not axes and group_name is not None:
        # single-device mesh but a multi-process group: the rendezvous
        # is the whole point — still register + wait
        ProcessGroup(mesh, mesh.axis_names[0], group_name=group_name,
                     rank=rank, world_size=world_size) \
            ._sync("barrier", timeout_s)
        return
    for a in axes:
        ProcessGroup(mesh, a, group_name=group_name, rank=rank,
                     world_size=world_size).barrier(timeout_s=timeout_s)
