"""Serve public API (ref: python/ray/serve/api.py — serve.run:591,
@serve.deployment, serve.start/shutdown, get_deployment_handle)."""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import cloudpickle

from .controller import CONTROLLER_NAME, ServeController
from .handle import DeploymentHandle


class Application:
    """A deployment bound to its init args (ref: Application from
    Deployment.bind)."""

    def __init__(self, deployment: "Deployment", args: tuple, kwargs: dict):
        self.deployment = deployment
        self.init_args = args
        self.init_kwargs = kwargs


class Deployment:
    def __init__(self, cls: type, name: str, config: Dict[str, Any]):
        self._cls = cls
        self.name = name
        self.config = config

    def bind(self, *args, **kwargs) -> Application:
        return Application(self, args, kwargs)

    def options(self, *, name: Optional[str] = None,
                num_replicas: Optional[int] = None,
                max_ongoing_requests: Optional[int] = None,
                ray_actor_options: Optional[dict] = None,
                autoscaling_config: Optional[dict] = None,
                pools: Optional[dict] = None,
                speculation: Optional[dict] = None) -> "Deployment":
        config = dict(self.config)
        if num_replicas is not None:
            config["num_replicas"] = num_replicas
        if max_ongoing_requests is not None:
            config["max_ongoing_requests"] = max_ongoing_requests
        if ray_actor_options is not None:
            config["ray_actor_options"] = ray_actor_options
        if autoscaling_config is not None:
            config["autoscaling_config"] = autoscaling_config
        if pools is not None:
            config["pools"] = pools
        if speculation is not None:
            if not isinstance(speculation, dict):
                raise ValueError(
                    "speculation must be a dict ({'draft_config': ..., "
                    "'num_draft_tokens': k})")
            config["speculation"] = speculation
        _validate_pools(config)
        return Deployment(self._cls, name or self.name, config)


def _validate_pools(config: Dict[str, Any]) -> None:
    pools = config.get("pools")
    if not pools:
        return
    if config.get("autoscaling_config"):
        raise ValueError(
            "pools and autoscaling_config are mutually exclusive: pool "
            "targets are static per-pool counts")
    for pool, n in pools.items():
        if not isinstance(pool, str) or not pool:
            raise ValueError(f"pool names must be non-empty strings, "
                             f"got {pool!r}")
        if int(n) < 1:
            raise ValueError(f"pool {pool!r} needs at least 1 replica")


def deployment(cls: Optional[type] = None, *,
               name: Optional[str] = None,
               num_replicas: int = 1,
               max_ongoing_requests: int = 100,
               ray_actor_options: Optional[dict] = None,
               autoscaling_config: Optional[dict] = None,
               pools: Optional[dict] = None,
               speculation: Optional[dict] = None):
    """@serve.deployment — turn a class into a deployable unit.

    ``autoscaling_config`` (ref: serve AutoscalingConfig):
    {"min_replicas", "max_replicas", "target_ongoing_requests",
    "downscale_ticks"} — replica count then tracks live queue lengths
    instead of num_replicas.

    ``pools`` (fleet KV plane, disaggregated serving): {"prefill": n,
    "decode": m} splits the deployment into named replica pools with
    static per-pool counts; ``num_replicas`` is ignored. Each replica
    learns its pool through the user class's ``configure_pool(pool,
    deployment_name)`` hook; plain traffic routes to the entry pool
    (prefill) and the deployment class hops requests across pools
    (e.g. LLMServer ships prefilled KV pages to the decode pool).

    ``speculation`` (speculative decoding, llm/spec_decode.py):
    {"draft_config": ..., "num_draft_tokens": k} reaches each replica
    through the user class's ``configure_speculation(spec)`` hook — a
    deployment-config knob, so YAML deploys toggle draft/verify
    decoding without touching the pickled init args."""
    if speculation is not None and not isinstance(speculation, dict):
        raise ValueError("speculation must be a dict "
                         "({'draft_config': ..., 'num_draft_tokens': k})")
    def _wrap(target: type) -> Deployment:
        config = {
            "num_replicas": num_replicas,
            "max_ongoing_requests": max_ongoing_requests,
            "ray_actor_options": ray_actor_options,
            **({"autoscaling_config": autoscaling_config}
               if autoscaling_config else {}),
            **({"pools": pools} if pools else {}),
            **({"speculation": speculation} if speculation else {}),
        }
        _validate_pools(config)
        return Deployment(target, name or target.__name__, config)

    if cls is not None:
        return _wrap(cls)
    return _wrap


def _get_or_create_controller():
    import ray_tpu

    try:
        return ray_tpu.get_actor(CONTROLLER_NAME)
    except ValueError:
        return ray_tpu.remote(ServeController).options(
            name=CONTROLLER_NAME, lifetime="detached", num_cpus=0.5,
        ).remote()


def run(app: Application, *, name: Optional[str] = None,
        local_testing_mode: bool = False) -> DeploymentHandle:
    """Deploy (or update) an application; returns its handle
    (ref: serve.run → controller.deploy_applications).

    ``local_testing_mode=True`` runs the whole application in-process —
    no cluster, no actors (ref: serve/_private/local_testing_mode.py);
    see ray_tpu/serve/local_testing.py."""
    if local_testing_mode:
        from .local_testing import run_local

        return run_local(app)  # type: ignore[return-value]
    import ray_tpu

    dep = app.deployment
    dep_name = name or dep.name
    controller = _get_or_create_controller()
    config = dep.config
    # a class may say what its replicas ask of the cluster it is deployed
    # to (LLMServer: a chip each, where nodes have chips). Decided here,
    # where the cluster is known, and not when the deployment was built;
    # ray_actor_options given to the deployment stand.
    default_options = getattr(dep._cls, "replica_actor_options", None)
    if default_options is not None and config.get("ray_actor_options") is None:
        config = {**config, "ray_actor_options": default_options()}
    ray_tpu.get(controller.deploy.remote(
        dep_name,
        cloudpickle.dumps(dep._cls),
        cloudpickle.dumps((app.init_args, app.init_kwargs)),
        config,
    ), timeout=120)
    return DeploymentHandle(dep_name)


def get_deployment_handle(name: str,
                          pool: Optional[str] = None) -> DeploymentHandle:
    return DeploymentHandle(name, pool=pool)


def start(http_port: int = 0) -> int:
    """Ensure the HTTP proxy is up; returns the bound port."""
    import ray_tpu

    controller = _get_or_create_controller()
    return ray_tpu.get(controller.ensure_proxy.remote(http_port), timeout=120)


def start_grpc(grpc_port: int = 0) -> int:
    """Ensure the gRPC ingress is up; returns the bound port
    (ref: the reference proxy's gRPC listener; see serve/grpc_proxy.py
    for the generic-ingress design)."""
    import ray_tpu

    controller = _get_or_create_controller()
    return ray_tpu.get(controller.ensure_grpc_proxy.remote(grpc_port),
                       timeout=120)


def status() -> list:
    import ray_tpu

    controller = _get_or_create_controller()
    return ray_tpu.get(controller.list_deployments.remote(), timeout=60)


def delete(name: str) -> None:
    import ray_tpu

    controller = _get_or_create_controller()
    ray_tpu.get(controller.delete_deployment.remote(name), timeout=60)


def shutdown() -> None:
    """Tear down all deployments, replicas, proxy, and the controller."""
    import ray_tpu

    try:
        controller = ray_tpu.get_actor(CONTROLLER_NAME)
    except ValueError:
        return
    try:
        ray_tpu.get(controller.shutdown.remote(), timeout=60)
    except Exception:
        pass
    ray_tpu.kill(controller)
