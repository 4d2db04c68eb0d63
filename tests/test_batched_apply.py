"""A fused dispatch's decisions applied as arrays ≡ row-at-a-time binds.

On the batched path the engine decodes a dispatch's packed result once
and, for dispatches of at least ``DEFERRED_BOOKS_MIN_ROWS`` rows, writes
the node books of its binds in one array update
(``ClusterSim.defer_books`` / ``flush_books``).  The per-row replay keeps
immediate books.  The same bursts run through both engines in lockstep,
and after every step the books must agree bit for bit: the float64 and
float32 node arrays, the pod-slot arrays, the cluster totals, the
dirty-node journal, ``alloc_trace``, ``usage_series``, the utilization
integral and the queued events.

Also: a forged overcommitting result raises from the array update,
``EngineMetrics.batched_binds`` counts the binds that went through it,
and an instance-level wrapper on ``cluster.bind`` (as a harness records
binds) sees every bind once, in admission order, with the decision's
node, quota and scenario.
"""
from typing import NamedTuple

import numpy as np
import pytest

from repro.api import ClusterConfig, EngineConfig, TimingConfig
from repro.core.evaluation import SCENARIO_NAMES
from repro.core.types import BatchAllocation
from repro.engine import KubeAdaptor
from repro.engine.kubeadaptor import DEFERRED_BOOKS_MIN_ROWS
from repro.workflows.spec import TaskSpec, WorkflowSpec

pytestmark = pytest.mark.tier1


class Case(NamedTuple):
    nodes: int
    clusters: int
    placement: str
    bursts: int
    width: int  # tasks per burst, one workflow each
    cpu: tuple  # uniform range of a task's request, millicores
    mem: tuple  # MiB
    min_cpu: float
    min_mem: float


CASES = {
    # Every row fits: 1,024-row-style bursts at test size.
    "all_feasible": Case(64, 1, "worst_fit", 3, 48, (100.0, 900.0),
                         (200.0, 1800.0), 50.0, 100.0),
    # Contended: ARAS cuts quotas and refuses rows, which wait in the
    # pending queue and come back with head-of-line retries.
    "pending_hol": Case(8, 1, "worst_fit", 3, 24, (1500.0, 4000.0),
                        (3000.0, 8000.0), 1500.0, 2000.0),
    # First fit packs many rows of one dispatch onto one node, so the
    # order of the array update's adds shows in the float books.
    "one_node": Case(16, 1, "first_fit", 3, 32, (10.0, 700.0),
                     (100.0, 1300.0), 5.0, 5.0),
    # A federation of 100 one-node clusters: global node ids.
    "federation": Case(100, 100, "worst_fit", 3, 160, (300.0, 2500.0),
                       (500.0, 5000.0), 100.0, 200.0),
}


def _workflows(case: Case, seed: int = 7):
    rng = np.random.default_rng(seed)
    out = []
    for k in range(case.bursts):
        tasks = {}
        for i in range(case.width):
            tid = f"t{i}"
            tasks[tid] = TaskSpec(
                task_id=tid, image="img",
                cpu=float(rng.uniform(*case.cpu)),
                mem=float(rng.uniform(*case.mem)),
                duration=float(rng.uniform(2.0, 9.0)),
                min_cpu=case.min_cpu, min_mem=case.min_mem)
        out.append((float(k), WorkflowSpec(workflow_id=f"b{k}", tasks=tasks,
                                           edges=[])))
    return out


def _engine(case: Case, batched: bool, incremental: bool = True
            ) -> KubeAdaptor:
    cfg = EngineConfig(
        cluster=ClusterConfig(num_nodes=case.nodes, node_cpu=8000.0,
                              node_mem=16000.0),
        timing=TimingConfig(pod_startup_delay=1.0, cleanup_delay=1.0,
                            duration_multiplier=1.0),
        invariant_checks=False,
    ).evolve(batch_allocation=batched, incremental_state=incremental,
             placement=case.placement, num_clusters=case.clusters)
    eng = KubeAdaptor(cfg)
    for t, spec in _workflows(case):
        eng.submit(spec, t)
    return eng


BOOKS = ("_used_cpu", "_used_mem", "_res_cpu32", "_res_mem32",
         "_pod_node", "_pod_cpu", "_pod_mem", "_pod_active")


def _assert_same_books(a: KubeAdaptor, b: KubeAdaptor) -> None:
    ca, cb = a.cluster, b.cluster
    for name in BOOKS:
        x, y = getattr(ca, name), getattr(cb, name)
        assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), name
    assert (ca._used_cpu_total, ca._used_mem_total) \
        == (cb._used_cpu_total, cb._used_mem_total)
    assert [(p.uid, p.node, p.slot, p.quota) for p in ca.pods.values()] \
        == [(p.uid, p.node, p.slot, p.quota) for p in cb.pods.values()]
    assert a.metrics.alloc_trace == b.metrics.alloc_trace
    assert a.metrics.usage_series == b.metrics.usage_series
    assert a._util_integral.tobytes() == b._util_integral.tobytes()
    assert a.queue._heap == b.queue._heap


def _assert_same_drain(a: KubeAdaptor, b: KubeAdaptor) -> None:
    da, db = a.cluster.drain_dirty(), b.cluster.drain_dirty()
    for x, y in zip(da, db):
        assert x.dtype == y.dtype and x.tobytes() == y.tobytes()


@pytest.mark.parametrize("incremental", [False, True],
                         ids=["repad", "device_state"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_batched_apply_matches_replay(name, incremental):
    """Lockstep: the same events, the same books after every step.

    The replay journals dirty nodes from the start; so does the batched
    engine on the re-pad path, where no dispatch drains the journal, and
    the two drains must match at the end.  On the device-state path each
    dispatch drains the journal, so the last drain must read the replay's
    residuals at the nodes it names.
    """
    case = CASES[name]
    a, b = _engine(case, True, incremental), _engine(case, False)
    if not incremental:
        a.cluster.track_dirty()
    b.cluster.track_dirty()
    per_node = {}  # (time, node) -> binds, on the batched engine
    bind = a.cluster.bind

    def counted(task, alloc, now, workflow_id=""):
        per_node[now, alloc.node] = per_node.get((now, alloc.node), 0) + 1
        return bind(task, alloc, now, workflow_id=workflow_id)

    a.cluster.bind = counted
    while a.queue:
        assert b.queue
        ea, eb = a.step(), b.step()
        assert ea == eb
        _assert_same_books(a, b)
    assert not b.queue
    if incremental:
        nodes, res_cpu, res_mem = a.cluster.drain_dirty()
        assert nodes.size
        assert res_cpu.tobytes() == b.cluster._res_cpu32[nodes].tobytes()
        assert res_mem.tobytes() == b.cluster._res_mem32[nodes].tobytes()
    else:
        _assert_same_drain(a, b)
    ma, mb = a.finalize(), b.finalize()
    assert (ma.makespan, ma.avg_cpu_usage, ma.avg_mem_usage) \
        == (mb.makespan, mb.avg_cpu_usage, mb.avg_mem_usage)
    assert ma.num_allocations == case.bursts * case.width
    assert ma.batched_binds > 0 and mb.batched_binds == 0
    if name == "pending_hol":
        assert ma.num_waits > 0 and ma.num_dispatches > case.bursts
    if name == "one_node":
        assert max(per_node.values()) >= 8
    if name == "federation":
        assert len({node for _, node in per_node}) == case.nodes


def _forged(n: int, cpu: float) -> BatchAllocation:
    return BatchAllocation(
        cpu=np.full((n,), cpu, np.float32),
        mem=np.full((n,), 100.0, np.float32),
        node=np.zeros((n,), np.int32),
        feasible=np.ones((n,), bool),
        attempted=np.ones((n,), bool),
        scenario=np.zeros((n,), np.int32),
    )


@pytest.mark.parametrize("rows,match", [
    (2 * DEFERRED_BOOKS_MIN_ROWS, "a dispatch's binds took it"),
    (DEFERRED_BOOKS_MIN_ROWS - 1, "overcommit on node 0: used="),
], ids=["array_update", "at_each_bind"])
def test_overcommitting_result_raises(rows, match):
    """A result that puts more on node 0 than it holds raises: from the
    one array update in a wide dispatch, from the bind in a narrow one.
    Either way the books that were written agree with the pods."""
    case = CASES["all_feasible"]._replace(bursts=1, width=rows)
    eng = _engine(case, True)
    eng._decide = lambda entries, dispatch: _forged(len(entries),
                                                    16000.0 / rows * 2)
    with pytest.raises(RuntimeError, match=match):
        while eng.queue:
            eng.step()
    assert eng.cluster._deferred is None


@pytest.mark.parametrize("batched,width,expected", [
    (True, 48, "all"),
    (True, DEFERRED_BOOKS_MIN_ROWS - 1, "none"),
    (False, 48, "none"),
], ids=["batched", "batched_narrow", "replay"])
def test_batched_binds_counts_the_array_update(batched, width, expected):
    """Every bind of a wide batched dispatch goes through the array
    update; narrow dispatches and the replay write books at each bind."""
    case = CASES["all_feasible"]._replace(width=width)
    eng = _engine(case, batched)
    metrics = eng.run()
    assert metrics.num_allocations == case.bursts * width
    assert metrics.batched_binds == (
        metrics.num_allocations if expected == "all" else 0)


@pytest.mark.parametrize("name", ["pending_hol", "federation"])
def test_bind_wrapper_sees_each_decision_once(name):
    """A wrapper set on the ``cluster.bind`` instance attribute, as a
    harness records binds, sees every bound row of every dispatch once,
    in admission order, carrying the decision's node, quota and
    scenario."""
    eng = _engine(CASES[name], True)
    decided = []
    decide = eng._decide

    def recording_decide(entries, dispatch):
        result = decide(entries, dispatch)
        for i, (wf_id, task, _) in enumerate(entries):
            if result.feasible[i]:
                decided.append((
                    f"{wf_id}/{task.task_id}", int(result.node[i]),
                    float(result.cpu[i]), float(result.mem[i]),
                    SCENARIO_NAMES[int(result.scenario[i])]))
        return result

    eng._decide = recording_decide
    seen = []
    bind = eng.cluster.bind

    def recorded(task, alloc, now, workflow_id=""):
        assert type(alloc.node) is int and type(alloc.cpu) is float
        seen.append((f"{workflow_id}/{task.task_id}", alloc.node, alloc.cpu,
                     alloc.mem, alloc.scenario))
        return bind(task, alloc, now, workflow_id=workflow_id)

    eng.cluster.bind = recorded
    metrics = eng.run()
    assert seen == decided
    assert len(seen) == metrics.num_allocations
    assert len({key for key, *_ in seen}) == len(seen)
    assert [key for key, *_ in seen] == [k for _, k, *_ in
                                         metrics.alloc_trace]
