"""Multi-cluster federated allocation layout (beyond-paper scale-out).

The paper evaluates one KubeAdaptor against one cluster; production scale
means a *federation*: K clusters, each a contiguous range of the global
node table, pooled behind one allocator (KubeAdaptor is explicitly a
docking framework for heterogeneous clusters, arXiv:2207.01222).  This
module owns the data layout that makes that federation a pure array
transform of the existing burst pipeline:

* ``FederatedLayout`` — the static shape contract: per-cluster node
  counts, every cluster padded to the same number of ``LANE``-wide
  residual blocks (``nb_per``), so the residual/capacity tiles are
  ``[K · nb_per, LANE]`` with the cluster axis flattened into the block
  axis.  A cluster is then a contiguous block range, per-shard reductions
  are reshapes, and the cross-shard reduce is an argmax over K per-shard
  maxima.
* ``pad_tiles_federated`` — flat ``[m]`` node arrays → federated tiles
  (single-cluster layouts delegate to the legacy ``pad_tiles``, so the
  ``num_clusters=1`` path is bit-for-bit the existing allocator);
  ``host_tiles`` lays out the same tiles in NumPy, for the one upload
  that builds the device-resident state.
* ``shard_totals`` — per-shard residual totals ``[K]``; the sequential
  core debits only the accepting shard's entry (O(1), like the legacy
  scalar totals) and re-derives the federation-wide total by a static
  left-fold, which at K=1 is the identity.
* ``global_nodes`` / ``flat_positions`` — kernel flat node indices ↔
  global node ids (the engine binds pods against the global node table;
  the device-resident state scatters dirty nodes back into the tiles).
* ``tile_mask`` / ``tile_block_sums`` / ``totals_from_block_sums`` — the
  hierarchical totals shared by the full re-pad path and the
  incremental dirty-tile path (``repro.cluster.device_state``): masked
  per-block sums ``[nb]``, then a fixed-order reduce to the legacy
  scalar or per-shard ``[K]`` totals.  Equal tile contents give
  bitwise-equal totals, which is what holds the two paths bit-for-bit.
* ``resolve_mesh`` / ``shard_tiles`` — ``jax.sharding`` placement of the
  tile arrays along a 1-D ``clusters`` device mesh
  (``launch.mesh.make_cluster_mesh``); on a single device the mesh is
  ``None`` and everything stays resident exactly as today (documented
  single-device fallback).

Everything here is shape/static metadata — hashable, so layouts ride
through ``jax.jit`` as static arguments without retraces per burst.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Lane width of the residual tiles ([num_blocks, LANE]).  Canonical here —
# the layout module must import nothing from repro (it sits below both the
# allocator and the kernels in the import graph); the sequential cores
# (``repro.kernels.alloc_scan``) re-export it.
LANE = 128


def pad_tiles(arr: jax.Array, pad_value: float) -> jax.Array:
    """Reshape a flat per-node array to [num_blocks, LANE] tiles."""
    m = arr.shape[0]
    nb = -(-m // LANE)
    return jnp.pad(arr, (0, nb * LANE - m),
                   constant_values=pad_value).reshape(nb, LANE)


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


@dataclasses.dataclass(frozen=True)
class FederatedLayout:
    """Static layout of a K-cluster federation over the global node table.

    ``node_counts[k]`` is cluster *k*'s node count; clusters partition the
    global node table contiguously and in order, so global node ids are
    preserved (the property the cross-shard parity suite leans on: a
    federation that never overflows a shard makes exactly the
    single-cluster decisions).
    """

    node_counts: Tuple[int, ...]

    def __post_init__(self):
        if not self.node_counts or any(m <= 0 for m in self.node_counts):
            raise ValueError(
                f"every cluster needs at least one node: {self.node_counts}"
            )

    @property
    def num_clusters(self) -> int:
        return len(self.node_counts)

    @property
    def num_nodes(self) -> int:
        return sum(self.node_counts)

    @property
    def nb_per(self) -> int:
        """Residual blocks per cluster — every shard padded to the max."""
        return max(_ceil_div(m, LANE) for m in self.node_counts)

    @property
    def num_blocks(self) -> int:
        return self.num_clusters * self.nb_per

    @property
    def offsets(self) -> Tuple[int, ...]:
        """Global node id of each cluster's first node."""
        out, acc = [], 0
        for m in self.node_counts:
            out.append(acc)
            acc += m
        return tuple(out)

    @functools.cached_property
    def node_perm(self) -> np.ndarray:
        """``[K · nb_per · LANE]`` map: padded flat position → global node
        id, ``-1`` for padding lanes."""
        span = self.nb_per * LANE
        perm = np.full((self.num_clusters * span,), -1, np.int32)
        for k, (m, off) in enumerate(zip(self.node_counts, self.offsets)):
            perm[k * span: k * span + m] = np.arange(off, off + m)
        return perm

    @staticmethod
    def single(num_nodes: int) -> "FederatedLayout":
        return FederatedLayout((num_nodes,))

    @staticmethod
    def split(num_nodes: int, num_clusters: int) -> "FederatedLayout":
        """Partition ``num_nodes`` into ``num_clusters`` contiguous,
        as-even-as-possible clusters (first clusters take the remainder)."""
        if not 1 <= num_clusters <= num_nodes:
            raise ValueError(
                f"need 1 <= num_clusters <= num_nodes, got "
                f"{num_clusters} clusters for {num_nodes} nodes"
            )
        base, extra = divmod(num_nodes, num_clusters)
        return FederatedLayout(
            tuple(base + (1 if k < extra else 0)
                  for k in range(num_clusters))
        )


def layout_of(cluster) -> FederatedLayout:
    """The layout of a ``ClusterSim`` (single- or multi-cluster mode)."""
    return FederatedLayout(tuple(cluster.cluster_node_counts))


# ------------------------------------------------------------ tile layout

def pad_tiles_federated(
    arr: jax.Array, layout: Optional[FederatedLayout], pad_value: float
) -> jax.Array:
    """Flat ``[m]`` per-node array → ``[K · nb_per, LANE]`` residual tiles.

    ``layout=None`` (and K=1 layouts, whose permutation is the identity)
    take the legacy ``pad_tiles`` path — bit-for-bit today's tiles.
    """
    if layout is None or layout.num_clusters == 1:
        return pad_tiles(arr, pad_value)
    perm = jnp.asarray(layout.node_perm)
    gathered = jnp.where(perm >= 0, arr[jnp.clip(perm, 0)],
                         jnp.asarray(pad_value, arr.dtype))
    return gathered.reshape(layout.num_blocks, LANE)


def host_tiles(arr, layout: Optional[FederatedLayout],
               pad_value: float) -> np.ndarray:
    """:func:`pad_tiles_federated` in NumPy, on the host: the same
    float32 tiles, laid out before their one copy to the device."""
    arr = np.asarray(arr, np.float32)
    layout = layout or FederatedLayout.single(arr.shape[0])
    out = np.full((layout.num_clusters, layout.nb_per * LANE), pad_value,
                  np.float32)
    for k, (m, off) in enumerate(zip(layout.node_counts, layout.offsets)):
        out[k, :m] = arr[off: off + m]
    return out.reshape(layout.num_blocks, LANE)


def shard_totals(arr: jax.Array, layout: Optional[FederatedLayout]):
    """Residual totals: legacy scalar (``layout=None``) or per-shard [K].

    Per-shard entries are plain slice sums over the contiguous cluster
    ranges; the K=1 vector holds exactly the legacy scalar.
    """
    if layout is None:
        return jnp.sum(arr)
    return jnp.stack([
        jnp.sum(arr[off: off + m])
        for off, m in zip(layout.offsets, layout.node_counts)
    ])


@functools.lru_cache(maxsize=None)
def tile_mask(num_nodes: int, layout: Optional[FederatedLayout]) -> np.ndarray:
    """Bool ``[nb, LANE]``: which tile lanes hold real nodes.

    The incremental-state path and the full re-pad path both derive their
    block sums from this one mask, so padding lanes contribute exactly
    ``0.0`` to every reduction in both.  Cached per (size, layout) — the
    mask is static shape metadata, like the layout itself.
    """
    if layout is None or layout.num_clusters == 1:
        nb = _ceil_div(num_nodes, LANE)
        mask = np.zeros((nb * LANE,), bool)
        mask[:num_nodes] = True
        return mask.reshape(nb, LANE)
    return (layout.node_perm >= 0).reshape(layout.num_blocks, LANE)


def tile_block_sums(tiles: jax.Array, mask2) -> jax.Array:
    """Per-block masked sums ``[nb]`` of residual tiles.

    The single reduction shape both totals paths share: the re-pad path
    computes it from freshly padded tiles, the incremental path re-sums
    only dirty blocks — equal tile contents therefore give bitwise-equal
    block sums, and (via :func:`totals_from_block_sums`) bitwise-equal
    carried totals.
    """
    return jnp.sum(jnp.where(mask2, tiles, jnp.float32(0.0)), axis=1)


def totals_from_block_sums(
    bsum: jax.Array, layout: Optional[FederatedLayout]
) -> jax.Array:
    """Residual totals from block sums: legacy scalar or per-shard [K].

    Replaces the flat ``[m]`` reduction of :func:`shard_totals` on the
    burst path so the totals can be re-derived from device-resident
    block sums without ever re-staging the flat node arrays.
    """
    if layout is None:
        return jnp.sum(bsum)
    return jnp.sum(bsum.reshape(layout.num_clusters, layout.nb_per), axis=1)


def flat_positions(
    nodes: np.ndarray, layout: Optional[FederatedLayout]
) -> np.ndarray:
    """Global node ids → padded flat tile positions (host-side).

    The inverse of :func:`global_nodes`, used to target dirty-node
    scatter updates at the device-resident tiles.
    """
    nodes = np.asarray(nodes, np.int64)
    if layout is None or layout.num_clusters == 1:
        return nodes
    offs = np.asarray(layout.offsets, np.int64)
    k = np.searchsorted(offs, nodes, side="right") - 1
    return k * (layout.nb_per * LANE) + (nodes - offs[k])


def global_nodes(
    nodes: np.ndarray, layout: Optional[FederatedLayout]
) -> np.ndarray:
    """Kernel flat node indices → global node ids (``-1`` passes through).

    Host-side, applied once per burst after the single device sync.
    """
    if layout is None or layout.num_clusters == 1:
        return nodes
    nodes = np.asarray(nodes)
    span = layout.nb_per * LANE
    k = np.clip(nodes // span, 0, layout.num_clusters - 1)
    local = nodes - k * span
    offs = np.asarray(layout.offsets, nodes.dtype)
    return np.where(nodes < 0, nodes, offs[k] + local).astype(nodes.dtype)


# --------------------------------------------------------- device sharding

@functools.lru_cache(maxsize=None)
def _cached_mesh(num_clusters: int):
    from repro.launch.mesh import make_cluster_mesh

    return make_cluster_mesh(num_clusters)


SHARDING_POLICIES = ("auto", "force", "off")


def validate_sharding_policy(policy: str) -> str:
    """Fail loudly on a typo'd policy — the single source of truth for
    the allowed ``cluster_sharding`` values (engine construction and
    mesh resolution both call this)."""
    if policy not in SHARDING_POLICIES:
        raise ValueError(
            f"unknown cluster_sharding policy {policy!r} "
            f"(want one of {SHARDING_POLICIES})"
        )
    return policy


def resolve_mesh(layout: Optional[FederatedLayout], policy: str):
    """The ``clusters`` device mesh for a layout, or ``None``.

    ``policy``: ``"auto"``/``"force"`` shard across devices whenever some
    device count > 1 divides the cluster count; ``"off"`` never shards.
    On a single device this always returns ``None`` — the federated
    arithmetic is unchanged, just unsharded (the documented fallback).
    """
    # Validate before any early return: a typo'd policy must fail even
    # in single-cluster setups, not silently run the legacy path.
    validate_sharding_policy(policy)
    if policy == "off" or layout is None or layout.num_clusters == 1:
        return None
    return _cached_mesh(layout.num_clusters)


def shard_tiles(tiles: jax.Array, mesh) -> jax.Array:
    """Lay residual/capacity tiles out along the ``clusters`` mesh axis.

    The block axis is cluster-major and every shard owns ``nb_per``
    blocks, so partitioning the leading axis puts whole clusters on
    devices.
    """
    if mesh is None:
        return tiles
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.device_put(
        tiles, NamedSharding(mesh, PartitionSpec("clusters", None))
    )
