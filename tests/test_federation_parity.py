"""Cross-shard parity: the federated allocator ≡ the single-cluster one.

Two regression gates for ``repro.cluster.federation``:

(a) the ``num_clusters=1`` federated path (K=1 layout, vector totals,
    per-shard argmax staging) is **bit-for-bit** the legacy allocator —
    array-level over random bursts for both allocators × all four
    placement policies × both sequential-core backends, and engine-level
    (``cluster_sharding="force"``) for batched *and* per-task replay
    modes;
(b) K clusters that partition the node table in order (so global node
    ids are preserved) reproduce the single-cluster accept/reject
    sequence, nodes and quotas exactly.  ARAS cases use integer-valued
    resources so the per-shard total fold re-associates exactly; FCFS
    never reads the totals, so it matches for arbitrary values.

Plus: scan ≡ pallas at K > 1, the multi-cluster ``ClusterSim`` mode
(layout metadata, sharded views, a deterministic bind/finish/delete fuzz
walk with invariants), layout/mesh plumbing, and the single-device
sharding fallback.
"""
import numpy as np
import pytest

from repro.cluster import federation
from repro.cluster.federation import FederatedLayout
from repro.cluster.simulator import ClusterSim
from repro.core.allocator import AdaptiveAllocator, FCFSAllocator
from repro.core.placement import PLACEMENT_POLICIES
from repro.core.types import Allocation, PodPhase, TaskBatch, TaskSpec, TaskWindow
from repro.engine import EngineConfig, TimingConfig, run_experiment

pytestmark = pytest.mark.tier1

FAST = EngineConfig(timing=TimingConfig(
    pod_startup_delay=1.0, cleanup_delay=1.0, duration_multiplier=1.0))

ALLOCATORS = (AdaptiveAllocator, FCFSAllocator)
FIELDS = ("cpu", "mem", "node", "feasible", "attempted", "scenario")


def _window_empty():
    z = np.zeros((0,), np.float32)
    return TaskWindow(t_start=z, cpu=z, mem=z, done=np.zeros((0,), bool))


def _window(rng, num_rec):
    return TaskWindow(
        t_start=rng.integers(0, 50, num_rec).astype(np.float32),
        cpu=rng.integers(0, 4000, num_rec).astype(np.float32),
        mem=rng.integers(0, 8000, num_rec).astype(np.float32),
        done=rng.random(num_rec) < 0.3,
    )


def _case(seed, m=11, num_rec=8, num_rows=6, *, integral):
    """Random burst against m nodes; ``integral`` draws integer-valued
    resources (exact under any float32 re-association)."""
    rng = np.random.default_rng(seed)
    draw = ((lambda lo, hi, n: rng.integers(lo, hi, n).astype(np.float32))
            if integral else
            (lambda lo, hi, n: rng.uniform(lo, hi, n).astype(np.float32)))
    res_cpu = draw(100, 8000, m)
    res_mem = draw(100, 16000, m)
    cap_cpu = np.full((m,), 8000.0, np.float32)
    cap_mem = np.full((m,), 16000.0, np.float32)
    tasks = [
        TaskSpec(task_id=f"t{i}", image="i",
                 cpu=float(draw(100, 6000, 1)[0]),
                 mem=float(draw(100, 12000, 1)[0]),
                 duration=10.0,
                 min_cpu=float(draw(1, 100, 1)[0]),
                 min_mem=float(draw(1, 200, 1)[0]))
        for i in range(num_rows)
    ]
    slots = rng.permutation(num_rec)[:num_rows].astype(np.int32)
    slots[rng.random(num_rows) < 0.25] = -1
    batch = TaskBatch.from_tasks(
        tasks, 5.0, self_slots=slots,
        pending=rng.random(num_rows) < 0.4,
    )
    return batch, res_cpu, res_mem, cap_cpu, cap_mem, _window(rng, num_rec)


def _decide(alloc_cls, layout, case, policy, backend="scan"):
    batch, res_cpu, res_mem, cap_cpu, cap_mem, window = case
    alloc = alloc_cls(placement=policy, backend=backend, layout=layout)
    return alloc.allocate_batch(batch, res_cpu, res_mem, window, 5.0,
                                cap_cpu=cap_cpu, cap_mem=cap_mem)


def _assert_batch_equal(a, b, ctx):
    for name in FIELDS:
        x, y = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        assert (x == y).all(), (ctx, name, x, y)


# ------------------------------------------------- (a) K=1 ≡ legacy, exact

@pytest.mark.parametrize("policy", PLACEMENT_POLICIES)
@pytest.mark.parametrize("alloc_cls", ALLOCATORS)
def test_single_cluster_layout_is_bitwise_legacy(alloc_cls, policy):
    """The K=1 federated layout is byte-identical to layout=None."""
    for seed in range(3):
        case = _case(seed, integral=False)  # arbitrary float32 values
        legacy = _decide(alloc_cls, None, case, policy)
        fed = _decide(alloc_cls, FederatedLayout.single(11), case, policy)
        _assert_batch_equal(legacy, fed, (alloc_cls.__name__, policy, seed))


@pytest.mark.parametrize("alloc_cls", ALLOCATORS)
def test_single_cluster_layout_bitwise_legacy_pallas(alloc_cls):
    """Same gate through the Pallas sequential core (interpret off-TPU)."""
    case = _case(0, integral=False)
    legacy = _decide(alloc_cls, None, case, "worst_fit", backend="pallas")
    fed = _decide(alloc_cls, FederatedLayout.single(11), case, "worst_fit",
                  backend="pallas")
    _assert_batch_equal(legacy, fed, alloc_cls.__name__)


def _engine_metrics_equal(a, b):
    assert a.makespan == b.makespan
    assert a.workflow_durations == b.workflow_durations
    assert a.alloc_trace == b.alloc_trace
    assert a.oom_events == b.oom_events
    assert a.realloc_events == b.realloc_events
    assert a.num_allocations == b.num_allocations
    assert a.usage_series == b.usage_series


@pytest.mark.parametrize("policy", PLACEMENT_POLICIES)
@pytest.mark.parametrize("allocator", ["aras", "fcfs"])
def test_engine_forced_federation_is_bitwise_legacy(allocator, policy):
    """cluster_sharding="force" routes num_clusters=1 through the K=1
    federated path; whole-simulation metrics must not move a bit."""
    def run(sharding):
        cfg = FAST.evolve(placement=policy, cluster_sharding=sharding)
        return run_experiment("montage", [(0.0, 3)], allocator, seed=0,
                              config=cfg)

    _engine_metrics_equal(run("auto"), run("force"))


@pytest.mark.parametrize("allocator", ["aras", "fcfs"])
def test_engine_forced_federation_replay_mode(allocator):
    """The per-task replay (batch_allocation=False) takes the same K=1
    federated path and still matches the legacy engine exactly."""
    def run(sharding):
        cfg = FAST.evolve(batch_allocation=False, cluster_sharding=sharding)
        return run_experiment("montage", [(0.0, 3)], allocator, seed=0,
                              config=cfg)

    _engine_metrics_equal(run("auto"), run("force"))


# ---------------------------------- (b) K shards ≡ single cluster, in order

@pytest.mark.parametrize("policy", PLACEMENT_POLICIES)
@pytest.mark.parametrize("alloc_cls", ALLOCATORS)
@pytest.mark.parametrize("counts", [(6, 5), (4, 4, 3), (5, 3, 2, 1)])
def test_federated_reproduces_single_cluster_sequence(alloc_cls, policy,
                                                      counts):
    """Order-preserving K-cluster partitions make the single-cluster
    decisions: same accept/reject sequence, same global nodes, same
    quotas.  Integer-valued resources keep the ARAS total fold exact."""
    for seed in range(3):
        case = _case(seed, m=sum(counts), integral=True)
        single = _decide(alloc_cls, None, case, policy)
        fed = _decide(alloc_cls, FederatedLayout(counts), case, policy)
        _assert_batch_equal(single, fed,
                            (alloc_cls.__name__, policy, counts, seed))


def test_federated_fcfs_any_values():
    """FCFS never reads the residual totals, so the federated sequence
    matches for arbitrary (non-integral) float32 resources too."""
    for seed in range(3):
        case = _case(seed, m=11, integral=False)
        single = _decide(FCFSAllocator, None, case, "worst_fit")
        fed = _decide(FCFSAllocator, FederatedLayout((4, 4, 3)), case,
                      "worst_fit")
        _assert_batch_equal(single, fed, seed)


@pytest.mark.parametrize("counts", [(4, 3, 2), (1,) * 100],
                         ids=["K3", "K100"])
@pytest.mark.parametrize("mode_cls", ALLOCATORS)
@pytest.mark.parametrize("policy", PLACEMENT_POLICIES)
def test_federated_scan_matches_pallas(mode_cls, policy, counts):
    """Both sequential-core backends agree bit-for-bit at K > 1, up to
    the hundred clusters of the federation cell (one node each: the
    shard loops, not the tiles, are what K = 100 exercises)."""
    case = _case(1, m=sum(counts), integral=False)
    lay = FederatedLayout(counts)
    ref = _decide(mode_cls, lay, case, policy, backend="scan")
    ker = _decide(mode_cls, lay, case, policy, backend="pallas")
    _assert_batch_equal(ref, ker, (mode_cls.__name__, policy))


@pytest.mark.parametrize("allocator", ["aras", "fcfs"])
def test_engine_multi_cluster_runs(allocator):
    """A 2-cluster engine drives workflows to completion under invariant
    checks; FCFS federations additionally reproduce the single-cluster
    metrics exactly (decisions are placement-only)."""
    cfg = FAST.evolve(num_clusters=2)
    fed = run_experiment("montage", [(0.0, 3)], allocator, seed=0,
                         config=cfg)
    assert len(fed.workflow_durations) == 3
    if allocator == "fcfs":
        single = run_experiment("montage", [(0.0, 3)], allocator, seed=0,
                                config=FAST)
        _engine_metrics_equal(single, fed)


# ------------------------------------------------------- layout & plumbing

def test_layout_split_and_perm():
    lay = FederatedLayout.split(10, 3)
    assert lay.node_counts == (4, 3, 3)
    assert lay.offsets == (0, 4, 7)
    assert lay.num_nodes == 10 and lay.num_clusters == 3
    perm = lay.node_perm
    assert perm.shape == (lay.num_blocks * 128,)
    # every global node appears exactly once, in cluster-major order
    real = perm[perm >= 0]
    assert sorted(real.tolist()) == list(range(10))
    # flat → global round-trips through global_nodes
    flat = np.flatnonzero(perm >= 0).astype(np.int32)
    assert (federation.global_nodes(flat, lay) == perm[flat]).all()
    assert federation.global_nodes(np.array([-1], np.int32), lay)[0] == -1


def test_layout_validation():
    with pytest.raises(ValueError, match="num_clusters"):
        FederatedLayout.split(3, 4)
    with pytest.raises(ValueError, match="at least one node"):
        FederatedLayout((2, 0))


def test_resolve_mesh_single_device_fallback():
    lay = FederatedLayout((3, 3))
    # On one device gcd(K, 1) == 1: no mesh, federated math unsharded.
    import jax
    mesh = federation.resolve_mesh(lay, "auto")
    if len(jax.devices()) == 1:
        assert mesh is None
    assert federation.resolve_mesh(lay, "off") is None
    assert federation.resolve_mesh(None, "auto") is None
    assert federation.resolve_mesh(FederatedLayout.single(4), "auto") is None
    with pytest.raises(ValueError, match="cluster_sharding"):
        federation.resolve_mesh(lay, "wat")


def test_cluster_sim_multi_cluster_metadata():
    sim = ClusterSim(7, 8000.0, 16000.0, num_clusters=3)
    assert sim.cluster_node_counts == (3, 2, 2)
    assert [s.stop - s.start for s in sim.cluster_slices] == [3, 2, 2]
    assert [sim.cluster_of(n) for n in range(7)] == [0, 0, 0, 1, 1, 2, 2]
    shards = sim.residual_view_sharded()
    caps = sim.capacity_view_sharded()
    assert len(shards) == 3 and len(caps) == 3
    # the sharded views alias the live flat arrays
    flat_cpu, _ = sim.residual_view()
    assert shards[0][0].base is flat_cpu
    assert federation.layout_of(sim) == FederatedLayout((3, 2, 2))
    with pytest.raises(ValueError, match="num_clusters"):
        ClusterSim(3, 8000.0, 16000.0, num_clusters=4)


def test_device_sharded_federation_matches_unsharded():
    """With 2 forced host devices, cluster_sharding="auto" builds the
    2-way ``clusters`` mesh and the device-sharded engine reproduces the
    unsharded federated metrics exactly (subprocess keeps this process
    at one device, like the dry-run tests)."""
    import os
    import subprocess
    import sys

    prog = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
from repro.engine import EngineConfig, TimingConfig, run_experiment
from repro.launch.mesh import make_cluster_mesh

assert len(jax.devices()) == 2
mesh = make_cluster_mesh(2)
assert mesh is not None and mesh.axis_names == ("clusters",), mesh
FAST = EngineConfig(timing=TimingConfig(
    pod_startup_delay=1.0, cleanup_delay=1.0, duration_multiplier=1.0))

def run(sharding):
    cfg = FAST.evolve(num_clusters=2, cluster_sharding=sharding)
    return run_experiment("montage", [(0.0, 2)], "fcfs", seed=0, config=cfg)

off, auto = run("off"), run("auto")
assert off.alloc_trace == auto.alloc_trace
assert off.makespan == auto.makespan
assert off.workflow_durations == auto.workflow_durations
print("SHARDED-PARITY-OK")
"""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=600, cwd=repo_root,
        env={**os.environ,
             "PYTHONPATH": os.path.join(repo_root, "src")},
    )
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "SHARDED-PARITY-OK" in out.stdout


def test_cluster_sim_fuzz_walk_invariants():
    """Deterministic bind/finish/delete random walk (single- and multi-
    cluster): invariants + O(1) utilization totals hold at every step.
    The hypothesis stateful twin lives in tests/property/."""
    for num_clusters in (1, 3):
        rng = np.random.default_rng(7)
        sim = ClusterSim(6, 8000.0, 16000.0, num_clusters=num_clusters)
        running, terminal, now = [], [], 0.0
        task = TaskSpec(task_id="t", image="i", cpu=1.0, mem=1.0,
                        duration=1.0, min_cpu=1.0, min_mem=1.0)
        for step in range(200):
            op = rng.random()
            if op < 0.5:
                node = int(rng.integers(0, sim.num_nodes))
                free_c = sim._alloc_cpu[node] - sim._used_cpu[node]
                free_m = sim._alloc_mem[node] - sim._used_mem[node]
                # Quotas floored to quarter-millicore/MiB granularity:
                # dyadic values at these magnitudes make the float64
                # books exact, like real (integral) K8s quantities.
                alloc = Allocation(
                    cpu=np.floor(free_c * rng.uniform(0, 1) * 4) / 4,
                    mem=np.floor(free_m * rng.uniform(0, 1) * 4) / 4,
                    node=node, feasible=True)
                running.append(sim.bind(task, alloc, now).uid)
            elif op < 0.8 and running:
                uid = running.pop(int(rng.integers(0, len(running))))
                phase = (PodPhase.SUCCEEDED if rng.random() < 0.7
                         else PodPhase.OOM_KILLED)
                sim.finish(uid, now, phase)
                terminal.append(uid)
            elif terminal:
                sim.delete(terminal.pop(int(rng.integers(0, len(terminal)))))
            now += 1.0
            sim.check_invariants()
            # O(1) utilization totals ≡ a from-scratch recompute
            u = sim.utilization()
            assert np.isclose(u.cpu, sim._used_cpu.sum() / sim._alloc_cpu.sum(),
                              rtol=1e-9, atol=1e-9)
            assert np.isclose(u.mem, sim._used_mem.sum() / sim._alloc_mem.sum(),
                              rtol=1e-9, atol=1e-9)
