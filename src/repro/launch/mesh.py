"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes.

    ``jax.make_mesh`` builds ``Explicit`` axes by default, which
    ``with_sharding_constraint`` (``repro.parallel.act_sharding``) and the
    policy-driven shardings here do not accept; every mesh of this repo
    leaves the partitioning to the compiler.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 (one v5e pod, 256 chips) or 2×16×16 (two pods, 512 chips).

    Axes: ``data`` carries DP/FSDP, ``model`` carries TP/EP.  The ``pod``
    axis (multi-pod) extends DP across the inter-pod DCN link — parameter
    all-gathers stay inside a pod's ICI torus; only gradient reductions
    cross pods.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as np

    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        return make_mesh(shape, axes)
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for {shape}; have {len(devices)}. The dry-run "
            "sets XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import.")
    # dry-run env exposes 512 host devices; single-pod uses the first 256
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_host_mesh(model: int = 1):
    """Whatever fits the *current* device set (tests / local runs)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


def usable_cluster_devices(num_clusters: int) -> int:
    """Largest device count that divides ``num_clusters``.

    The single source of truth for the cluster-mesh selection rule —
    ``make_cluster_mesh`` shards across exactly this many devices, and
    ``ClusterConfig.validate`` warns when it is 1 despite multiple
    devices being available.
    """
    devices = jax.device_count()
    return max(k for k in range(1, min(num_clusters, devices) + 1)
               if num_clusters % k == 0)


def make_cluster_mesh(num_clusters: int):
    """1-D ``clusters`` mesh for federated burst allocation, or ``None``.

    Uses the largest available device count that divides ``num_clusters``
    so every device owns the same (smallest possible) number of cluster
    shards.  Returns ``None`` on a single device or when no device split
    > 1 divides the clusters — the federated arithmetic then runs
    unsharded on one device (the documented fallback).
    """
    import numpy as np

    devices = jax.devices()
    d = usable_cluster_devices(num_clusters)
    if d <= 1:
        return None
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices[:d]), ("clusters",))
