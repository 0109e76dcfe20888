"""Serve controller: deployment reconciliation + replica lifecycle
(ref: python/ray/serve/_private/controller.py:84 ServeController,
deployment_state.py DeploymentState — replica STARTING/RUNNING/STOPPING
reconciliation loops, rolling updates, health checks).

A detached async actor: deployments survive the deploying driver. The
reconcile loop converges actual replicas toward each deployment's target
(scale up/down, replace unhealthy), and bumps a version consumers use to
refresh their cached replica sets."""

from __future__ import annotations

import asyncio
import time
from typing import Any, Dict, List, Optional

from .._private.gcs import CONSTRUCTING as _CONSTRUCTING
from .._private.gcs import CONSTRUCTOR_TIMEOUT_S as REPLICA_STARTUP_TIMEOUT_S
from .._private.rpc import RpcError
from ..exceptions import RayTpuError

CONTROLLER_NAME = "SERVE::controller"
HEALTH_PERIOD_S = 2.0
HEALTH_TIMEOUT_S = 15.0
# REPLICA_STARTUP_TIMEOUT_S: the longest a replica's constructor may run
# before it is replaced; _CONSTRUCTING: the GCS actor states in which the
# constructor has not returned yet (both the GCS's own, shared with the
# wait of a call behind a constructor)

# What best-effort calls against a possibly-dead replica/proxy can
# raise (transport loss, timeouts, the actor already being gone).
# Anything outside this set is a controller bug and must surface.
_REMOTE_ERRORS = (asyncio.TimeoutError, ConnectionError, OSError,
                  RuntimeError, ValueError, RpcError, RayTpuError)


def _still_starting(actor_state: Optional[str], unanswered_s: float) -> bool:
    """STARTING, not unhealthy (ref: deployment_state.py replica states):
    a health check that times out while the GCS says the replica's
    constructor is still running only queued behind that constructor.
    Any other state timing out — ALIVE above all — is a hung replica, and
    so is a constructor that has run ``REPLICA_STARTUP_TIMEOUT_S``."""
    return (actor_state in _CONSTRUCTING
            and unanswered_s < REPLICA_STARTUP_TIMEOUT_S)


async def _actor_state(actor_id) -> Optional[str]:
    """The actor's lifecycle state as the GCS has it (None: not known)."""
    from .._worker_api import core

    c = core()
    try:
        info = await asyncio.wait_for(asyncio.wrap_future(
            asyncio.run_coroutine_threadsafe(
                c.gcs.call("get_actor", {"actor_id": actor_id}),
                c.io.loop)), 5)
    except _REMOTE_ERRORS:
        return None
    return None if info is None else info.state


async def _await_ref(ref):
    """Adapter: ObjectRef's __await__ into a coroutine asyncio.wait_for
    accepts."""
    return await ref


class ServeController:
    def __init__(self):
        self._deployments: Dict[str, dict] = {}
        self._version = 0
        self._reconcile_task: Optional[asyncio.Task] = None
        self._proxy = None
        self._proxy_port: Optional[int] = None
        self._proxy_lock: Optional[asyncio.Lock] = None
        self._grpc_proxy = None
        self._grpc_proxy_port: Optional[int] = None
        self._grpc_proxy_lock: Optional[asyncio.Lock] = None
        # serializes deploy/delete/reconcile: the reconcile gather suspends
        # for seconds, and a concurrent mutation of dep["replicas"] would
        # pair stale health verdicts with fresh replicas (killing them) or
        # resurrect replicas of a just-deleted deployment
        self._reconcile_lock: Optional[asyncio.Lock] = None

    def _lock(self) -> asyncio.Lock:
        if self._reconcile_lock is None:
            self._reconcile_lock = asyncio.Lock()
        return self._reconcile_lock

    # ------------------------------------------------------------- deploy
    async def deploy(self, name: str, cls_blob: bytes, init_args_blob: bytes,
                     config: dict) -> int:
        """Create or update a deployment; returns the new version. A change
        to code/init-args/config bumps the deployment's code_version, and
        reconciliation ROLLS the running replicas onto it (ref:
        deployment_state.py rolling updates) — stale replicas must not keep
        serving old code."""
        async with self._lock():
            # mutation happens under the SAME lock as reconciliation: a
            # reconcile suspended in health checks must not observe a
            # half-updated deployment (new code, old code_version)
            dep = self._deployments.get(name)
            if dep is None:
                dep = self._deployments[name] = {
                    "name": name,
                    "replicas": [],  # [(handle, code_version, pool)]
                    "next_replica": 0, "code_version": 0,
                }
            if (dep.get("cls_blob") != cls_blob
                    or dep.get("init_args_blob") != init_args_blob
                    or dep.get("config") != config):
                dep["code_version"] += 1
            dep["cls_blob"] = cls_blob
            dep["init_args_blob"] = init_args_blob
            dep["config"] = config
            self._version += 1
            await self._reconcile_deployment(dep)
            self._publish_version()
        self._ensure_reconcile_loop()
        return self._version

    async def delete_deployment(self, name: str) -> bool:
        async with self._lock():
            dep = self._deployments.pop(name, None)
            if dep is None:
                return False
            for entry in dep["replicas"]:
                await self._stop_replica(entry[0])
            self._version += 1
            self._publish_version()
            return True

    async def _make_replica(self, dep: dict, pool: Optional[str] = None):
        from .. import remote
        from .replica import Replica

        index = dep["next_replica"]
        dep["next_replica"] += 1
        config = dep["config"]
        actor_opts = dict(config.get("ray_actor_options") or {})
        actor_opts.setdefault("num_cpus", 1)
        tag = f"{pool}-" if pool else ""
        handle = remote(Replica).options(
            name=f"SERVE::{dep['name']}#{tag}{index}",
            lifetime="detached",
            max_restarts=3,
            **actor_opts,
        ).remote(dep["cls_blob"], dep["init_args_blob"],
                 config.get("max_ongoing_requests", 100), dep["name"],
                 pool, config.get("speculation"))
        return handle

    async def _stop_replica(self, handle) -> None:
        from .. import kill

        try:
            kill(handle)
        except _REMOTE_ERRORS:
            pass  # already dead: the goal state

    async def _autoscale_target(self, dep: dict, auto: dict) -> int:
        """Queue-length-driven replica target (ref: serve/_private/
        autoscaling_state.py + serve/autoscaling_policy.py): desired =
        ceil(total ongoing / target_ongoing_requests), clamped to
        [min, max]. Upscale applies immediately; downscale waits for
        ``downscale_ticks`` consecutive low observations so a burst lull
        doesn't thrash replicas."""
        import math

        min_r = int(auto.get("min_replicas", 1))
        max_r = int(auto.get("max_replicas", max(min_r, 1)))
        per = float(auto.get("target_ongoing_requests", 2))
        ticks_needed = int(auto.get("downscale_ticks", 3))

        lens = await self._queue_lens(dep["replicas"])
        dep["_last_qlens"] = lens  # reused by this round's downscale
        total = sum(max(q, 0) for q in lens)
        desired = max(min_r, min(max_r,
                                 math.ceil(total / per) if total else min_r))
        current = len(dep["replicas"])
        if desired >= current:
            dep["_low_ticks"] = 0
            return desired
        dep["_low_ticks"] = dep.get("_low_ticks", 0) + 1
        if dep["_low_ticks"] >= ticks_needed:
            dep["_low_ticks"] = 0
            return desired
        return current

    async def _queue_lens(self, replicas) -> list:
        """Concurrent queue-depth sample; unreachable replicas read -1
        (sorts first for downscale victim selection, counts as 0 load)."""
        async def _one(entry):
            try:
                return await asyncio.wait_for(
                    _await_ref(entry[0].queue_len.remote()), 5)
            except _REMOTE_ERRORS:
                return -1

        return list(await asyncio.gather(*[_one(e) for e in replicas]))

    async def _reconcile_deployment(self, dep: dict) -> None:
        # disaggregated serving: a "pools" config splits the deployment
        # into named replica pools (prefill/decode for LLMs) with static
        # per-pool targets; pool-less deployments reconcile as the
        # single anonymous pool None (autoscaling applies only there)
        pools = dep["config"].get("pools")
        auto = None if pools else dep["config"].get("autoscaling_config")
        if auto:
            target = await self._autoscale_target(dep, auto)
            dep["_auto_target"] = target
            targets: Dict[Optional[str], int] = {None: target}
        elif pools:
            targets = {str(p): int(n) for p, n in pools.items()}
        else:
            targets = {None: dep["config"].get("num_replicas", 1)}
        code_version = dep["code_version"]

        # when each replica last answered a check (or was created)
        answered = dep.setdefault("_answered", {})
        now = time.monotonic()

        # concurrent health checks: one hung replica must not stall the
        # control loop for 15s per replica (NB: awaiting ObjectRefs — a
        # blocking get() would stall this actor's loop)

        async def _check(entry):
            # stale code OR a pool dropped from config = replace
            current = entry[1] == code_version and entry[2] in targets
            aid = entry[0]._actor_id
            try:
                await asyncio.wait_for(
                    _await_ref(entry[0].health_check.remote()),
                    HEALTH_TIMEOUT_S)
                answered[aid] = time.monotonic()
                return current
            except asyncio.TimeoutError:
                return current and _still_starting(
                    await _actor_state(aid), now - answered.get(aid, now))
            except _REMOTE_ERRORS:
                return False

        results = await asyncio.gather(
            *[_check(entry) for entry in dep["replicas"]])
        alive = []
        for entry, healthy in zip(dep["replicas"], results):
            if healthy:
                alive.append(entry)
            else:
                await self._stop_replica(entry[0])
        changed = len(alive) != len(dep["replicas"])
        replicas = []
        for pool, target in targets.items():
            entries = [e for e in alive if e[2] == pool]
            while len(entries) < target:
                entries.append((await self._make_replica(dep, pool),
                                code_version, pool))
                answered[entries[-1][0]._actor_id] = time.monotonic()
                changed = True
            if len(entries) > target:
                # downscale the IDLEST replicas first: killing a replica
                # fails its in-flight requests, so rank by queue depth
                # (sampled this round by _autoscale_target when
                # autoscaling; unreachable replicas read -1, drop first)
                depths = dep.pop("_last_qlens", None)
                if depths is None or len(depths) != len(entries):
                    depths = await self._queue_lens(entries)
                ranked = sorted(zip(depths, range(len(entries))),
                                key=lambda p: p[0])
                drop = {i for _, i in ranked[:len(entries) - target]}
                keep = []
                for i, entry in enumerate(entries):
                    if i in drop:
                        await self._stop_replica(entry[0])
                    else:
                        keep.append(entry)
                entries = keep
                changed = True
            replicas.extend(entries)
        dep["replicas"] = replicas
        for aid in answered.keys() - {e[0]._actor_id for e in replicas}:
            del answered[aid]
        if changed:
            self._version += 1
            self._publish_version()
        await self._gossip_summaries(dep)

    async def _gossip_summaries(self, dep: dict) -> None:
        """Fleet KV plane: poll replica prefix-cache summaries on the
        reconcile tick (routing freshness rides the existing heartbeat
        path — no extra control loop). Handles pull the aggregated
        table through get_prefix_summaries and score replicas by
        longest cached-prefix match (serve/kv_router.py)."""
        from .._private.config import global_config

        cfg = global_config()
        if not cfg.serve_prefix_routing_enabled or not dep["replicas"]:
            return
        # a code version that exposed no summaries is never re-polled:
        # non-LLM deployments pay one probe per deploy, not per tick
        if (dep.get("_summary_probe_version") == dep["code_version"]
                and not dep.get("_prefix_summaries")):
            return
        now = time.monotonic()
        if now - dep.get("_summary_poll_t", 0.0) \
                < cfg.serve_prefix_summary_interval_s:
            return
        dep["_summary_poll_t"] = now

        async def _one(entry):
            try:
                return await asyncio.wait_for(
                    _await_ref(entry[0].prefix_summary.remote()), 5), True
            except _REMOTE_ERRORS:
                return None, False

        results = await asyncio.gather(
            *[_one(e) for e in dep["replicas"]])
        summaries = dep.setdefault("_prefix_summaries", {})
        for entry, (summary, _ok) in zip(dep["replicas"], results):
            if summary:
                summaries[entry[0]._actor_id] = {
                    "summary": summary, "t": now}
        live = {e[0]._actor_id for e in dep["replicas"]}
        for aid in [a for a in summaries if a not in live]:
            del summaries[aid]
        if all(ok for _, ok in results):
            # only a clean all-replicas probe may conclude "no summary
            # hook here" — a replica still initializing must be retried
            dep["_summary_probe_version"] = dep["code_version"]

    def _publish_version(self) -> None:
        """Push the new config version to every router/handle over GCS
        pubsub (the long-poll push, ref: serve/_private/long_poll.py:66
        LongPollHost) — subscribed handles skip their poll entirely and
        re-pull the replica set only when this lands."""
        try:
            from .._worker_api import core

            core().publish_channel("serve", {"version": self._version})
        except _REMOTE_ERRORS + (ImportError, KeyError):
            pass  # pushes are an optimization; handles still fall back

    def _ensure_reconcile_loop(self) -> None:
        if self._reconcile_task is None or self._reconcile_task.done():
            self._reconcile_task = asyncio.ensure_future(self._loop())

    async def _loop(self):
        while self._deployments:
            await asyncio.sleep(HEALTH_PERIOD_S)
            for name in list(self._deployments):
                async with self._lock():
                    dep = self._deployments.get(name)
                    if dep is None:
                        continue  # deleted while we waited on the lock
                    try:
                        await self._reconcile_deployment(dep)
                    except Exception:
                        # the loop must survive a bad round, but the
                        # failure has to be visible somewhere
                        import sys
                        import traceback

                        print(f"[serve] reconcile({name}) failed:\n"
                              f"{traceback.format_exc()}",
                              file=sys.stderr)

    # ------------------------------------------------------------ queries
    async def get_replicas(self, name: str, pool: Optional[str] = None):
        """(version, [replica handles]) — consumers cache until the version
        moves (the long-poll config-push role, ref: _private/long_poll.py).

        ``pool`` narrows a pooled deployment to one replica pool. For a
        pooled deployment with pool=None, plain traffic lands on the
        ENTRY pool (prefill — requests start with their prompt pass)."""
        dep = self._deployments.get(name)
        if dep is None:
            return self._version, None
        entries = dep["replicas"]
        pools = dep["config"].get("pools")
        if pool is None and pools:
            pool = "prefill" if "prefill" in pools else next(iter(pools))
        if pool is not None:
            entries = [e for e in entries if e[2] == pool]
        return self._version, [e[0] for e in entries]

    async def get_prefix_summaries(self, name: str) -> dict:
        """Aggregated prefix-cache summary table for a deployment:
        {replica actor_id: {"page_size", "digests", "age_s"}}. Ages are
        controller-side monotonic deltas so consumers judge staleness
        without cross-process clock agreement."""
        dep = self._deployments.get(name)
        if dep is None:
            return {}
        now = time.monotonic()
        out = {}
        for aid, rec in dep.get("_prefix_summaries", {}).items():
            summary = rec["summary"]
            out[aid] = {"page_size": summary.get("page_size"),
                        "digests": summary.get("digests"),
                        "age_s": now - rec["t"]}
        return out

    async def get_version(self) -> int:
        return self._version

    async def list_deployments(self) -> List[dict]:
        out = []
        for d in self._deployments.values():
            pools = d["config"].get("pools")
            info = {
                "name": d["name"],
                "num_replicas": len(d["replicas"]),
                # autoscaled deployments report their last computed
                # target, not the static num_replicas default
                "target_replicas": (
                    d.get("_auto_target", len(d["replicas"]))
                    if d["config"].get("autoscaling_config")
                    else (sum(int(n) for n in pools.values()) if pools
                          else d["config"].get("num_replicas", 1)))}
            if pools:
                counts: Dict[str, int] = {str(p): 0 for p in pools}
                for e in d["replicas"]:
                    if e[2] in counts:
                        counts[e[2]] += 1
                info["pools"] = counts
            if d.get("_prefix_summaries"):
                # count ROUTABLE summaries only: a digest-less entry
                # (engine cache still empty) can't steer any request,
                # and waiters key "routing is live" off this number
                info["prefix_summaries"] = sum(
                    1 for rec in d["_prefix_summaries"].values()
                    if rec["summary"].get("digests"))
            out.append(info)
        return out

    # -------------------------------------------------------------- proxy
    async def _ensure_ingress(self, slot: str, actor_cls, name: str,
                              port: int) -> int:
        """Single-instance ingress actor with ping recovery, shared by
        the HTTP and gRPC listeners. ``slot`` names the state attributes
        (self.<slot>, <slot>_port, <slot>_lock). No max_restarts: a bare
        actor restart would re-run __init__ but not start(), leaving no
        listener — recreation through this path (ping fails -> new actor
        + start) is the recovery."""
        from .. import remote

        if getattr(self, slot + "_lock") is None:
            setattr(self, slot + "_lock", asyncio.Lock())
        async with getattr(self, slot + "_lock"):
            # concurrent starts interleave on the actor loop; without
            # the lock both would create the named actor
            if getattr(self, slot + "_port") is not None:
                try:  # the cached proxy may have died since
                    await asyncio.wait_for(
                        _await_ref(getattr(self, slot).ping.remote()), 10)
                    return getattr(self, slot + "_port")  # one instance
                except Exception:
                    from .. import kill

                    try:
                        kill(getattr(self, slot))
                    except _REMOTE_ERRORS:
                        pass  # it's being replaced either way
                    setattr(self, slot, None)
                    setattr(self, slot + "_port", None)
            actor = remote(actor_cls).options(
                name=name, lifetime="detached", num_cpus=0.5,
            ).remote()
            setattr(self, slot, actor)
            bound = await asyncio.wait_for(
                _await_ref(actor.start.remote(port)), 60)
            setattr(self, slot + "_port", bound)
            return bound

    async def ensure_proxy(self, port: int) -> int:
        from .proxy import ProxyActor

        return await self._ensure_ingress(
            "_proxy", ProxyActor, "SERVE::proxy", port)

    async def ensure_grpc_proxy(self, port: int) -> int:
        from .grpc_proxy import GrpcProxyActor

        return await self._ensure_ingress(
            "_grpc_proxy", GrpcProxyActor, "SERVE::grpc_proxy", port)

    async def shutdown(self) -> bool:
        from .. import kill

        for name in list(self._deployments):
            await self.delete_deployment(name)
        if self._proxy is not None:
            try:
                kill(self._proxy)
            except _REMOTE_ERRORS:
                pass
        if self._grpc_proxy is not None:
            try:
                kill(self._grpc_proxy)
            except _REMOTE_ERRORS:
                pass
        return True
