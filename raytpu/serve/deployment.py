"""Deployment decorator and application graph building.

Reference analogue: ``python/ray/serve/deployment.py`` (``Deployment``,
``Application``) and ``python/ray/serve/_private/build_app.py``: a
``Deployment`` is the declarative unit; ``.bind(*args)`` produces an
application node; bound nodes appearing in another node's args become
``DeploymentHandle``s at build time (model composition).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import cloudpickle

from raytpu.serve.config import AutoscalingConfig, DeploymentConfig, ReplicaConfig


class Deployment:
    def __init__(self, target: Callable, name: str, config: DeploymentConfig):
        self._target = target
        self.name = name
        self.config = config

    def options(self, **kwargs) -> "Deployment":
        cfg_fields = {
            "num_replicas", "max_ongoing_requests", "user_config",
            "graceful_shutdown_timeout_s", "graceful_shutdown_wait_loop_s",
            "health_check_period_s", "health_check_timeout_s",
            "autoscaling_config", "ray_actor_options", "max_queued_requests",
            "role",
        }
        name = kwargs.pop("name", self.name)
        updates = {k: v for k, v in kwargs.items() if k in cfg_fields}
        unknown = set(kwargs) - cfg_fields
        if unknown:
            raise ValueError(f"unknown deployment options: {sorted(unknown)}")
        merged = {**self.config.__dict__, **updates}
        if merged.get("num_replicas") == "auto":
            merged["num_replicas"] = 1
            if merged.get("autoscaling_config") is None:
                merged["autoscaling_config"] = AutoscalingConfig()
        return Deployment(self._target, name, DeploymentConfig(**merged))

    def bind(self, *args, **kwargs) -> "Application":
        """An application node of this deployment built from ``args``.

        A target may say what its constructor's arguments imply for its
        own deployment: ``deployment_options(*args, **kwargs)`` -> a dict
        of :meth:`options` fields (a replica whose engine seats 128
        sequences has to take 128 requests). An implied value stands
        where the field is still at the serve layer's default; what the
        decorator or ``.options(...)`` set is kept."""
        dep = self
        implied = getattr(self._target, "deployment_options", None)
        if implied is not None:
            defaults = DeploymentConfig()
            updates = {k: v for k, v in implied(*args, **kwargs).items()
                       if getattr(self.config, k) == getattr(defaults, k)}
            if updates:
                dep = self.options(**updates)
        return Application(DeploymentNode(dep, args, kwargs))

    def __call__(self, *a, **kw):
        raise TypeError(
            f"deployment {self.name} cannot be called directly; deploy it "
            f"with serve.run(...) and call the handle"
        )


class DeploymentNode:
    def __init__(self, deployment: Deployment, args: tuple, kwargs: dict):
        self.deployment = deployment
        self.args = args
        self.kwargs = kwargs


class Application:
    """A bound ingress node plus (transitively) everything it depends on."""

    def __init__(self, ingress: DeploymentNode):
        self._ingress = ingress

    def _collect(self) -> List[DeploymentNode]:
        seen: Dict[int, DeploymentNode] = {}
        order: List[DeploymentNode] = []

        def visit(node: DeploymentNode):
            if id(node) in seen:
                return
            seen[id(node)] = node
            for a in list(node.args) + list(node.kwargs.values()):
                if isinstance(a, Application):
                    visit(a._ingress)
                elif isinstance(a, DeploymentNode):
                    visit(a)
            order.append(node)

        visit(self._ingress)
        return order


def build_app(
    app: Application, app_name: str
) -> Tuple[str, bytes, Dict[str, DeploymentConfig]]:
    """Resolve the graph into ReplicaConfigs; nested bound nodes become
    DeploymentHandles in the parent's init args."""
    from raytpu.serve.handle import DeploymentHandle

    nodes = app._collect()
    names: Dict[int, str] = {}
    used: Dict[str, int] = {}
    for node in nodes:
        base = node.deployment.name
        n = used.get(base, 0)
        used[base] = n + 1
        names[id(node)] = base if n == 0 else f"{base}_{n}"

    def resolve(v):
        if isinstance(v, Application):
            v = v._ingress
        if isinstance(v, DeploymentNode):
            return DeploymentHandle(
                names[id(v)], app_name,
                max_ongoing=v.deployment.config.max_ongoing_requests,
            )
        return v

    configs: List[ReplicaConfig] = []
    dep_configs: Dict[str, DeploymentConfig] = {}
    for node in nodes:
        dep = node.deployment
        configs.append(
            ReplicaConfig(
                deployment_name=names[id(node)],
                app_name=app_name,
                serialized_callable=cloudpickle.dumps(dep._target),
                init_args=tuple(resolve(a) for a in node.args),
                init_kwargs={k: resolve(v) for k, v in node.kwargs.items()},
                deployment_config=dep.config,
            )
        )
        dep_configs[names[id(node)]] = dep.config
    ingress_name = names[id(app._ingress)]
    return ingress_name, cloudpickle.dumps(configs), dep_configs


def deployment(
    _target: Optional[Callable] = None,
    *,
    name: Optional[str] = None,
    num_replicas: Any = 1,
    max_ongoing_requests: int = 100,
    user_config: Optional[Any] = None,
    autoscaling_config: Optional[Any] = None,
    ray_actor_options: Optional[Dict[str, Any]] = None,
    health_check_period_s: float = 2.0,
    health_check_timeout_s: float = 30.0,
    graceful_shutdown_timeout_s: float = 20.0,
    max_queued_requests: int = -1,
    role: Optional[str] = None,
) -> Any:
    """``@serve.deployment`` (reference: ``python/ray/serve/api.py``)."""

    def wrap(target: Callable) -> Deployment:
        nonlocal num_replicas, autoscaling_config
        if num_replicas == "auto":
            num_replicas = 1
            if autoscaling_config is None:
                autoscaling_config = AutoscalingConfig()
        cfg = DeploymentConfig(
            num_replicas=num_replicas,
            max_ongoing_requests=max_ongoing_requests,
            user_config=user_config,
            autoscaling_config=autoscaling_config,
            ray_actor_options=dict(ray_actor_options or {}),
            health_check_period_s=health_check_period_s,
            health_check_timeout_s=health_check_timeout_s,
            graceful_shutdown_timeout_s=graceful_shutdown_timeout_s,
            max_queued_requests=max_queued_requests,
            role=role,
        )
        return Deployment(target, name or target.__name__, cfg)

    if _target is not None:
        return wrap(_target)
    return wrap
