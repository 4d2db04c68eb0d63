"""Device-resident incremental state: parity is bit-for-bit, not close.

The tentpole contract of the incremental dispatch path: an allocator
state maintained by dirty-tile scatter updates (``repro.cluster.
device_state``) decides **bitwise identically** to the full re-pad path
it replaces, across both allocators, both sequential-core backends, and
federated layouts — at the allocator level (``allocate_batch_async`` vs
``allocate_batch``, including the fused maintain-and-decide step that
folds the dirty set into the decision dispatch), at the engine level
(``AllocatorConfig.incremental_state`` on vs off), and at the serving
level (``StreamEngine.serve()`` vs the offline ``run()``).

Donation note: ``allocate_batch_async`` with ``updates`` *consumes* the
input state (its tile buffers are donated to the fused dispatch), so
every chain below threads ``state = pending.state`` and never touches a
state it already passed in.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import AllocatorConfig, TimingConfig
from repro.cluster import device_state, federation
from repro.cluster.device_state import DeviceResidualState
from repro.cluster.federation import LANE, FederatedLayout
from repro.core.allocator import RES_PAD, make_allocator
from repro.core.types import TaskBatch, TaskWindow
from repro.engine import EngineConfig, KubeAdaptor
from repro.serving import StreamEngine, serve_stream
from repro.workflows.spec import TaskSpec, WorkflowSpec

pytestmark = pytest.mark.tier1

N_NODES = 24


def _layout(k: int):
    return FederatedLayout.split(N_NODES, k) if k > 1 else None


def _cluster_arrays(rng):
    cap_cpu = rng.uniform(1000.0, 4000.0, N_NODES).astype(np.float32)
    cap_mem = rng.uniform(2000.0, 8000.0, N_NODES).astype(np.float32)
    res_cpu = (cap_cpu * rng.uniform(0.2, 1.0, N_NODES)).astype(np.float32)
    res_mem = (cap_mem * rng.uniform(0.2, 1.0, N_NODES)).astype(np.float32)
    return res_cpu, res_mem, cap_cpu, cap_mem


def _batch(rng, b: int) -> TaskBatch:
    cpu = rng.uniform(100.0, 900.0, b).astype(np.float32)
    mem = rng.uniform(200.0, 1800.0, b).astype(np.float32)
    return TaskBatch(
        cpu=cpu,
        mem=mem,
        min_cpu=(cpu * 0.25).astype(np.float32),
        min_mem=(mem * 0.25).astype(np.float32),
        window_end=rng.uniform(5.0, 50.0, b).astype(np.float32),
        self_slot=np.full((b,), -1, np.int32),
        pending=np.zeros((b,), bool),
    )


def _window(rng, t: int, now: float) -> TaskWindow:
    return TaskWindow(
        t_start=rng.uniform(0.0, now + 10.0, t).astype(np.float32),
        cpu=rng.uniform(100.0, 800.0, t).astype(np.float32),
        mem=rng.uniform(200.0, 1500.0, t).astype(np.float32),
        done=rng.uniform(size=t) < 0.3,
    )


def _assert_alloc_equal(a, b):
    for field in ("cpu", "mem", "node", "feasible", "attempted", "scenario"):
        got, want = getattr(a, field), getattr(b, field)
        assert np.array_equal(got, want), field


# ------------------------------------------------- DeviceResidualState

@pytest.mark.parametrize("k", [1, 2, 4])
def test_apply_updates_matches_recreate(k):
    """Scatter-updated tiles equal the tiles a fresh ``create`` would
    rebuild from the same host caches — element for element, block sums
    included."""
    rng = np.random.default_rng(7 + k)
    res_cpu, res_mem, cap_cpu, cap_mem = _cluster_arrays(rng)
    state = DeviceResidualState.create(
        res_cpu, res_mem, cap_cpu, cap_mem, _layout(k), RES_PAD)
    for trial in range(3):
        nodes = rng.choice(N_NODES, size=rng.integers(1, 6), replace=False)
        res_cpu[nodes] = (cap_cpu[nodes]
                          * rng.uniform(0.1, 1.0, nodes.size)).astype(
                              np.float32)
        res_mem[nodes] = (cap_mem[nodes]
                          * rng.uniform(0.1, 1.0, nodes.size)).astype(
                              np.float32)
        state = state.apply_updates(nodes, res_cpu[nodes], res_mem[nodes])
        fresh = DeviceResidualState.create(
            res_cpu, res_mem, cap_cpu, cap_mem, _layout(k), RES_PAD)
        for field in ("rc2", "rm2", "cc2", "cm2", "mask2",
                      "bsum_c", "bsum_m"):
            assert np.array_equal(np.asarray(getattr(state, field)),
                                  np.asarray(getattr(fresh, field))), \
                (field, trial)


@pytest.mark.parametrize("k", [1, 4, N_NODES])
def test_create_lays_out_the_repad_tiles(k):
    """``create`` lays the tiles out on the host and copies each once:
    they equal, element for element, what the re-pad path pads on the
    device, and ``staged_bytes`` is four float32 tiles and the mask."""
    rng = np.random.default_rng(3 + k)
    res_cpu, res_mem, cap_cpu, cap_mem = _cluster_arrays(rng)
    lay = _layout(k)
    state = DeviceResidualState.create(
        res_cpu, res_mem, cap_cpu, cap_mem, lay, RES_PAD)
    for field, arr, pad in (("rc2", res_cpu, RES_PAD),
                            ("rm2", res_mem, RES_PAD),
                            ("cc2", cap_cpu, 0.0), ("cm2", cap_mem, 0.0)):
        want = federation.pad_tiles_federated(jnp.asarray(arr), lay, pad)
        assert np.array_equal(np.asarray(getattr(state, field)),
                              np.asarray(want)), field
    nb = lay.num_blocks if lay else -(-N_NODES // LANE)
    assert state.rc2.shape == (nb, LANE)
    assert state.staged_bytes == nb * LANE * (4 * 4 + 1)


def test_apply_updates_empty_is_noop():
    rng = np.random.default_rng(11)
    state = DeviceResidualState.create(
        *_cluster_arrays(rng), None, RES_PAD)
    assert state.apply_updates(np.zeros((0,), np.int64),
                               np.zeros((0,), np.float32),
                               np.zeros((0,), np.float32)) is state


def test_update_segment_buckets_have_a_floor():
    """Dirty-set buckets are floored so the fused decision jit (which
    inlines the scatter) does not recompile across the tiny per-burst
    dirty counts a streaming engine produces."""
    assert device_state._pow2(1) == device_state._MIN_BUCKET
    assert device_state._pow2(0) == device_state._MIN_BUCKET
    nodes = np.array([3, 4, 5])
    seg, n_idx, n_blk = device_state.pack_update_segment(
        nodes, np.ones(3, np.float32), np.ones(3, np.float32), None, 1)
    assert n_idx == device_state._MIN_BUCKET
    assert n_blk == device_state._MIN_BUCKET
    assert seg.shape == (3 * n_idx + n_blk,)
    # Int positions travel as raw float32 bits: bitcast-exact roundtrip.
    assert np.array_equal(seg[:3].view(np.int32), nodes.astype(np.int32))


# ------------------------------------------- allocator-level parity

_COMBOS = [(name, backend, k)
           for name in ("aras", "fcfs")
           for backend in ("scan", "pallas")
           for k in (1, 2, 4)]


@pytest.mark.parametrize("name,backend,k", _COMBOS)
def test_async_state_dispatch_matches_allocate_batch(name, backend, k):
    """The device-state dispatch (no pending updates) is bit-for-bit the
    re-pad dispatch."""
    rng = np.random.default_rng(hash((name, backend, k)) % 2**31)
    alloc = make_allocator(name, backend=backend, layout=_layout(k),
                           cluster_sharding="off")
    res_cpu, res_mem, cap_cpu, cap_mem = _cluster_arrays(rng)
    state = alloc.create_state(res_cpu, res_mem, cap_cpu, cap_mem)
    batch, window = _batch(rng, 5), _window(rng, 9, 4.0)
    want = alloc.allocate_batch(batch, res_cpu, res_mem, window, 4.0,
                                cap_cpu=cap_cpu, cap_mem=cap_mem)
    pending = alloc.allocate_batch_async(batch, window, 4.0, state=state)
    assert pending.state is state  # passthrough: nothing was folded
    _assert_alloc_equal(pending.wait(), want)


@pytest.mark.parametrize("name,backend,k", _COMBOS)
def test_fused_update_chain_matches_allocate_batch(name, backend, k):
    """The fused maintain-and-decide step — dirty deltas folded into the
    decision dispatch, state threaded through ``pending.state`` — stays
    bit-for-bit with re-padding the mutated host caches every burst."""
    rng = np.random.default_rng(hash((k, backend, name)) % 2**31)
    alloc = make_allocator(name, backend=backend, layout=_layout(k),
                           cluster_sharding="off")
    res_cpu, res_mem, cap_cpu, cap_mem = _cluster_arrays(rng)
    state = alloc.create_state(res_cpu, res_mem, cap_cpu, cap_mem)
    for trial in range(3):
        now = 2.0 * trial
        nodes = rng.choice(N_NODES, size=rng.integers(1, 6), replace=False)
        res_cpu[nodes] = (cap_cpu[nodes]
                          * rng.uniform(0.1, 1.0, nodes.size)).astype(
                              np.float32)
        res_mem[nodes] = (cap_mem[nodes]
                          * rng.uniform(0.1, 1.0, nodes.size)).astype(
                              np.float32)
        batch, window = _batch(rng, 4), _window(rng, 7, now)
        want = alloc.allocate_batch(batch, res_cpu, res_mem, window, now,
                                    cap_cpu=cap_cpu, cap_mem=cap_mem)
        pending = alloc.allocate_batch_async(
            batch, window, now, state=state,
            updates=(nodes, res_cpu[nodes].copy(), res_mem[nodes].copy()))
        state = pending.state  # the input state was donated — never reuse
        _assert_alloc_equal(pending.wait(), want)


def test_empty_burst_still_applies_updates():
    """A drain with no allocatable rows must not drop the dirty set."""
    rng = np.random.default_rng(23)
    alloc = make_allocator("aras")
    res_cpu, res_mem, cap_cpu, cap_mem = _cluster_arrays(rng)
    state = alloc.create_state(res_cpu, res_mem, cap_cpu, cap_mem)
    nodes = np.array([1, 5])
    res_cpu[nodes] = 42.0
    res_mem[nodes] = 84.0
    pending = alloc.allocate_batch_async(
        _batch(rng, 0), _window(rng, 3, 1.0), 1.0, state=state,
        updates=(nodes, res_cpu[nodes].copy(), res_mem[nodes].copy()))
    assert pending.wait().size == 0
    fresh = DeviceResidualState.create(
        res_cpu, res_mem, cap_cpu, cap_mem, None, RES_PAD)
    assert np.array_equal(np.asarray(pending.state.rc2),
                          np.asarray(fresh.rc2))
    assert np.array_equal(np.asarray(pending.state.bsum_m),
                          np.asarray(fresh.bsum_m))


# --------------------------------------------- engine-level parity

def _chain_wf(i: int, n_tasks: int = 2, duration: float = 6.0,
              cpu: float = 600.0) -> WorkflowSpec:
    tasks = {
        f"t{j}": TaskSpec(task_id=f"t{j}", image="img", cpu=cpu,
                          mem=2.0 * cpu, duration=duration + j,
                          min_cpu=cpu / 6.0, min_mem=cpu / 3.0)
        for j in range(n_tasks)
    }
    edges = [(f"t{j}", f"t{j + 1}") for j in range(n_tasks - 1)]
    return WorkflowSpec(workflow_id=f"w{i}", tasks=tasks, edges=edges)


_ARRIVALS = [(0.0, _chain_wf(0)), (0.5, _chain_wf(1, n_tasks=1)),
             (4.0, _chain_wf(2, duration=2.0)), (4.2, _chain_wf(3)),
             (11.0, _chain_wf(4, n_tasks=3, cpu=900.0))]


def _engine(name: str, k: int, window: float,
            incremental: bool) -> KubeAdaptor:
    return KubeAdaptor(EngineConfig(
        timing=TimingConfig(pod_startup_delay=1.0, cleanup_delay=1.0,
                            duration_multiplier=1.0, batch_window=window),
    ).evolve(allocator=name, num_clusters=k,
             incremental_state=incremental))


def _offline_metrics(name, k, window, incremental):
    eng = _engine(name, k, window, incremental)
    for t, wf in _ARRIVALS:
        eng.submit(wf, t)
    return eng.run()


def _assert_metrics_equal(a, b):
    assert a.alloc_trace == b.alloc_trace
    assert a.num_dispatches == b.num_dispatches
    assert a.num_allocations == b.num_allocations
    assert a.num_waits == b.num_waits
    assert a.makespan == b.makespan
    assert a.usage_series == b.usage_series
    assert a.workflow_durations == b.workflow_durations
    assert a.node_events == b.node_events
    assert a.displaced_tasks == b.displaced_tasks
    assert a.recovery_times == b.recovery_times
    assert a.failed_tasks == b.failed_tasks
    assert a.failed_workflows == b.failed_workflows


@pytest.mark.parametrize("name", ["aras", "fcfs"])
@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("window", [0.0, 3.0])
def test_engine_incremental_matches_repad(name, k, window):
    """``incremental_state`` flips the dispatch machinery, never the
    simulation: every metric of a full run is identical."""
    _assert_metrics_equal(_offline_metrics(name, k, window, True),
                          _offline_metrics(name, k, window, False))


def test_replay_mode_gates_device_state_off():
    """Per-task replay is *defined* as rebuilding the carry from host
    caches row by row — the device-state path must stand down."""
    eng = KubeAdaptor(EngineConfig(
        alloc=AllocatorConfig(batch_allocation=False)))
    assert not eng._use_device_state
    eng.submit(_chain_wf(0), 0.0)
    eng.run()
    assert eng._state is None


def test_incremental_state_config_gate():
    assert KubeAdaptor(EngineConfig())._use_device_state
    assert not KubeAdaptor(
        EngineConfig().evolve(incremental_state=False))._use_device_state


# --------------------------------------------- chaos-path parity

def _chaos_metrics(k: int, incremental: bool, schedule: str,
                   params: dict, oom_fraction: float = 0.0):
    eng = KubeAdaptor(EngineConfig(
        timing=TimingConfig(pod_startup_delay=1.0, cleanup_delay=1.0,
                            duration_multiplier=1.0, batch_window=3.0,
                            oom_fraction=oom_fraction),
    ).evolve(allocator="aras", num_clusters=k, incremental_state=incremental,
             fault_schedule=schedule, fault_params=params))
    for t, wf in _ARRIVALS:
        eng.submit(wf, t)
    return eng.run()


@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("schedule,params", [
    ("node_crash", {"at": 5.0, "nodes": 2}),
    ("node_flap", {"at": 3.0, "down_for": 6.0, "nodes": 2}),
])
def test_chaos_incremental_matches_repad(k, schedule, params):
    """Node down/up capacity deltas ride the same dirty-node journal as
    pod binds — the device-resident state stays bit-for-bit with the
    host re-pad path through cordons, drains, and restorations."""
    _assert_metrics_equal(_chaos_metrics(k, True, schedule, params),
                          _chaos_metrics(k, False, schedule, params))


def _vertical_metrics(k: int, incremental: bool):
    from repro.vertical import attach_usage
    eng = KubeAdaptor(EngineConfig(
        timing=TimingConfig(pod_startup_delay=1.0, cleanup_delay=1.0,
                            duration_multiplier=1.0, batch_window=3.0),
    ).evolve(allocator="aras", num_clusters=k, incremental_state=incremental,
             vertical=True, resize_interval=2.0))
    for t, wf in _ARRIVALS:
        eng.submit(attach_usage(wf, "ramp", {"start": 0.9, "end": 0.3}), t)
    return eng.run()


@pytest.mark.parametrize("k", [1, 2])
def test_vertical_resize_incremental_matches_repad(k):
    """RESIZE quota deltas ride the same dirty-node journal as binds and
    finishes — the device-resident state stays bit-for-bit with the host
    re-pad path through every in-place shrink and grow."""
    a = _vertical_metrics(k, True)
    b = _vertical_metrics(k, False)
    assert a.resize_events == b.resize_events and a.resize_events
    assert a.num_shrinks == b.num_shrinks
    assert a.reclaimed_cpu_seconds == b.reclaimed_cpu_seconds
    _assert_metrics_equal(a, b)


@pytest.mark.parametrize("k", [1, 2])
def test_oom_selfheal_incremental_matches_repad(k):
    """The OOM kill → reallocate-with-learned-floor loop under federation:
    identical healing with the dirty-tile dispatch on or off."""
    a = _chaos_metrics(k, True, "oom_storm", {"at": 4.0, "victims": 2},
                       oom_fraction=1.0)
    b = _chaos_metrics(k, False, "oom_storm", {"at": 4.0, "victims": 2},
                       oom_fraction=1.0)
    assert a.oom_events == b.oom_events and a.oom_events
    _assert_metrics_equal(a, b)


# --------------------------------------------- serving-level parity

@pytest.mark.parametrize("incremental", [True, False])
def test_stream_serve_matches_offline_run(incremental):
    """The pump feeds arrivals just in time; the windowed drain defines
    which arrivals a decision may fold — so serving a live stream equals
    submitting the schedule up front, bit for bit, with or without the
    device-state overlap."""
    offline = _offline_metrics("aras", 1, 3.0, incremental)
    eng = _engine("aras", 1, 3.0, incremental)
    stats = StreamEngine(eng, _ARRIVALS, prefetch_chunk=2).serve()
    _assert_metrics_equal(stats.metrics, offline)
    assert stats.decisions == offline.dispatched_rows
    assert stats.dispatches == offline.num_dispatches


def test_stream_serve_overlaps_ingestion_under_dispatch():
    """With the device-state path on, at least part of the arrival
    schedule is queued while a fused dispatch is in flight."""
    eng = _engine("aras", 1, 3.0, True)
    stats = StreamEngine(eng, _ARRIVALS, prefetch_chunk=2).serve()
    assert stats.overlapped_ingests > 0


def test_stream_rejects_unsorted_arrivals():
    eng = _engine("aras", 1, 0.0, True)
    with pytest.raises(ValueError, match="sorted"):
        StreamEngine(eng, [(1.0, _chain_wf(0)), (0.5, _chain_wf(1))])


def test_stream_stats_schema():
    """``to_dict`` is the schema CI's stream smoke step checks."""
    stats = serve_stream(_engine("fcfs", 1, 0.0, True), _ARRIVALS)
    d = stats.to_dict()
    assert set(d) == {"decisions", "dispatches", "wall_seconds",
                      "decisions_per_sec", "overlapped_ingests",
                      "shed_workflows", "deferred_workflows"}
    assert d["shed_workflows"] == 0 and d["deferred_workflows"] == 0
    assert d["decisions"] > 0 and d["dispatches"] > 0
    assert d["decisions_per_sec"] > 0.0
    assert all(isinstance(v, (int, float)) for v in d.values())
