"""The scheduler's chip path compiles for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached, so these tests refuse what
the chip's compiler would refuse (Mosaic layouts, scalar access, VMEM)
at the cluster sizes the served path runs.  Interpret mode, which every
other kernel test uses, accepts all of that.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off around the
compiles, since a program compiled for an absent chip cannot be read
back from it.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.cluster.federation import LANE, FederatedLayout
from repro.core.allocator import _packed_core_dispatch, _state_step
from repro.core.types import DEFAULT_ALPHA, DEFAULT_BETA
from repro.kernels.alloc_scan import ops
from repro.kernels.alloc_scan.kernel import alloc_scan_pallas

pytestmark = pytest.mark.tier1

NODES = 5_000  # Kubernetes' documented single-cluster limit
# How a device trace names the kernel: a trace reader finds the kernel's
# events by these names in each op's instruction text.
KERNEL_NAME = re.compile(r"_scan_kernel|alloc_scan_pallas")
# Instructions that launch no device op of their own.
NO_DEVICE_OP = ("get-tuple-element", "bitcast", "tuple", "parameter",
                "constant")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # no compiler logs outside
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _kernel_args(sharding, rows: int, mode: str, clusters: int = 1,
                 flag=jnp.int32, nodes: int = NODES):
    """Shapes of one sequential-core call at ``nodes`` nodes (the kernel
    takes int32 row flags, the engine's dispatch bools)."""
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    nb = FederatedLayout.split(nodes, clusters).num_blocks
    tiles = spec((nb, LANE))
    total = spec(()) if clusters == 1 else spec((clusters,))
    row, flag = spec((rows,)), spec((rows,), flag)
    delta = spec((rows, rows if mode == "aras" else 1))
    return (tiles, tiles, tiles, tiles, total, total,
            row, row, row, row, row, row, delta, delta, flag, flag, flag)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _kernel_named_ops(compiled) -> int:
    """Device ops of the entry computation whose instruction text (name
    and operands, without metadata) names the kernel."""
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    count = 0
    for line in entry.splitlines()[1:]:
        inst = line.split(", metadata=")[0]
        opcode = re.search(r"= .*? ([\w-]+)\(", inst)
        if opcode and opcode.group(1) not in NO_DEVICE_OP \
                and KERNEL_NAME.search(inst):
            count += 1
    return count


@pytest.mark.parametrize("mode,policy,rows", [
    ("aras", "worst_fit", 256),
    ("aras", "worst_fit", 1024),
    ("aras", "balanced", 256),
    ("aras", "balanced", 1024),
    ("aras", "worst_fit", 1),
    ("aras", "worst_fit", 8192),  # slab rows per step cut to fit VMEM
    ("fcfs", "worst_fit", 8),
    ("fcfs", "worst_fit", 1),
])
def test_alloc_scan_compiles_for_v5e(one_chip, mode, policy, rows):
    compiled = alloc_scan_pallas.lower(
        *_kernel_args(one_chip, rows, mode),
        alpha=DEFAULT_ALPHA, beta=DEFAULT_BETA, policy=policy, mode=mode,
        interpret=False,
    ).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("clusters,nodes,rows", [
    (4, NODES, 256),
    # The federation cell: 512,000 padded nodes, whose tiles need more
    # than the default scoped VMEM limit, and 100 unrolled shard loops.
    (100, 100 * NODES, 1024),
])
def test_federated_totals_kernel_compiles_for_v5e(one_chip, clusters, nodes,
                                                  rows):
    """K per-shard totals in SMEM, tiles cluster-major on one chip."""
    compiled = alloc_scan_pallas.lower(
        *_kernel_args(one_chip, rows, "aras", clusters=clusters,
                      nodes=nodes),
        alpha=DEFAULT_ALPHA, beta=DEFAULT_BETA, policy="worst_fit",
        mode="aras", interpret=False,
    ).compile()
    assert _has_kernel(compiled)


def test_federation_scan_core_compiles_across_four_chips(topo):
    """The scan core with K=4 cluster tiles sharded over a 2x2 mesh — the
    program ``cluster_sharding="auto"`` runs on four chips."""
    mesh = Mesh(np.asarray(topo.devices), ("clusters",))
    args = list(_kernel_args(NamedSharding(mesh, PartitionSpec()), 256,
                             "aras", clusters=4, flag=jnp.bool_))
    clusters = NamedSharding(mesh, PartitionSpec("clusters", None))
    for i in range(4):
        args[i] = jax.ShapeDtypeStruct(args[i].shape, args[i].dtype,
                                       sharding=clusters)
    compiled = _packed_core_dispatch.lower(
        *args, alpha=DEFAULT_ALPHA, beta=DEFAULT_BETA, policy="worst_fit",
        mode="aras", backend="scan",
    ).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("n_rows", [8, 1024])
def test_fused_state_step_holds_the_kernel(one_chip, monkeypatch, n_rows):
    """The served path's one jitted dispatch, maintain-and-decide, with
    the compiled kernel inside (``backend="pallas"`` on a TPU), and the
    kernel the one device op named after it: packing the decisions adds
    no op that a trace would count as the kernel."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    nb = FederatedLayout.single(NODES).num_blocks
    tiles, bsum = spec((nb, LANE)), spec((nb,))
    n_idx = n_blk = 8
    n_rec = 1024
    buf = spec((3 * n_idx + n_blk + 8 * n_rows + 4 * n_rec + 1,))
    try:
        compiled = _state_step.lower(
            tiles, tiles, tiles, tiles, bsum, bsum, spec((nb, LANE), bool),
            buf, n_idx=n_idx, n_blk=n_blk, n_rows=n_rows, n_rec=n_rec,
            alpha=DEFAULT_ALPHA, beta=DEFAULT_BETA, policy="worst_fit",
            mode="aras", backend="pallas", layout=None,
        ).compile()
    finally:
        # The trace above holds the compiled kernel; no later CPU call of
        # _state_step at these shapes may reuse it.
        jax.clear_caches()
    assert _has_kernel(compiled)
    assert _kernel_named_ops(compiled) == 1
