"""Production mesh construction (defined as functions so importing this module
never touches jax device state)."""
from __future__ import annotations

from typing import Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod; multi-pod = 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def data_axes_of(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """Axes used for batch/data parallelism (pod axis is pure DP)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def all_axes_of(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def make_test_mesh(n_devices: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over however many local devices exist (CPU tests)."""
    n = min(n_devices, jax.device_count())
    return jax.make_mesh((1, n), ("data", "model"))


def store_node_of_host(host: int, n_hosts: int, n_store_nodes: int) -> int:
    """Which store node a trainer host's DPP workers treat as *local*.

    The disaggregated immutable tier (``storage.sharded_store``) is deployed
    alongside the trainer mesh; hosts map onto store nodes round-robin so
    each node serves ``ceil(n_hosts / n_store_nodes)`` hosts and a host's
    affinity-planned work items (already node-local via the placement map)
    can be routed to the co-located node's feed partition."""
    if not 0 <= host < n_hosts:
        raise ValueError(f"host {host} out of range [0, {n_hosts})")
    return host % n_store_nodes


def replica_nodes_of_host(host: int, n_hosts: int, n_store_nodes: int,
                          replication_factor: int = 1) -> Tuple[int, ...]:
    """Ordered store-node preference chain for a trainer host.

    Head = the co-located node (``store_node_of_host``); tail = that node's
    round-robin replica successors — the SAME anti-affinity chain
    ``PlacementMap.replicas_of`` uses, so when the host's local node is down
    its DPP reads fail over to nodes that actually replicate the local
    node's primary data, instead of scattering across the tier."""
    primary = store_node_of_host(host, n_hosts, n_store_nodes)
    r = max(1, min(replication_factor, n_store_nodes))
    return tuple((primary + k) % n_store_nodes for k in range(r))
