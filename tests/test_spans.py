"""Trace spans of the served path: names, nesting, ids and bytes staged.

The engine and the allocator open ``jax.profiler.TraceAnnotation`` spans
at their layer boundaries (README, "Tracing the scheduler").  A small
stream is served under ``jax.profiler.trace`` and the ``.xplane.pb`` is
read back with ``ProfileData``: every span is there, the per-dispatch
spans carry the engine's dispatch index once each and in order,
injection nests only where it may, ``EngineMetrics.staged_bytes`` equals
a count made from the allocator's inputs alone, ``EngineMetrics.
fetched_bytes`` equals the bytes of the ``alloc.wait`` spans (one packed
``int32[6, pow2(B)]`` per dispatch), ``alloc.state`` opens once per
device-state build with ``EngineMetrics.state_builds`` and
``state_bytes`` counting the builds and their bytes, and tracing changes
no decision.
"""
import glob
import os
from typing import NamedTuple

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.api import ClusterConfig, EngineConfig, TimingConfig
from repro.engine import KubeAdaptor
from repro.serving import serve_stream
from repro.workflows.spec import TaskSpec, WorkflowSpec

pytestmark = pytest.mark.tier1

N_NODES = 64
SPANS = ("engine.inject", "engine.fold", "engine.stage", "alloc.pack",
         "alloc.launch", "alloc.wait", "engine.apply")
# The device-resident state's build: once per engine, on that path only.
STATE = "alloc.state"
# The spans of one dispatch, in the order they open.
PER_DISPATCH = ("engine.stage", "alloc.pack", "alloc.launch", "alloc.wait",
                "engine.apply")


def _arrivals(n_workflows: int = 60, seed: int = 3):
    """About 300 pods: chains and fan-outs, arriving 0-1.5 s apart, so
    some arrivals fold inline into a drain and others head their own
    step."""
    rng = np.random.default_rng(seed)
    out, t = [], 0.0
    for i in range(n_workflows):
        n = int(rng.integers(2, 9))
        tasks = {f"t{j}": TaskSpec(
            task_id=f"t{j}", image="img", cpu=float(rng.uniform(200, 1500)),
            mem=float(rng.uniform(400, 3000)),
            duration=float(rng.uniform(2.0, 12.0)), min_cpu=100.0,
            min_mem=200.0) for j in range(n)}
        if i % 2:
            edges = [(f"t{j}", f"t{j + 1}") for j in range(n - 1)]
        else:
            edges = [("t0", f"t{j}") for j in range(1, n)]
        out.append((t, WorkflowSpec(workflow_id=f"w{i}", tasks=tasks,
                                    edges=edges)))
        t += float(rng.uniform(0.0, 1.5))
    return out


def _engine(incremental: bool) -> KubeAdaptor:
    return KubeAdaptor(EngineConfig(
        cluster=ClusterConfig(num_nodes=N_NODES, node_cpu=8000.0,
                              node_mem=16000.0),
        timing=TimingConfig(pod_startup_delay=1.0, cleanup_delay=1.0,
                            duration_multiplier=1.0, batch_window=0.5),
        invariant_checks=False,
    ).evolve(incremental_state=incremental))


def _pow2(n: int, floor: int = 1) -> int:
    return 1 << (max(n, floor) - 1).bit_length()


def _count_staging(eng: KubeAdaptor, expected: list, fetched: list
                   ) -> None:
    """Append, per dispatch, the bytes its inputs imply to ``expected``,
    and the bytes of its packed decisions (``24 * pow2(B)``) to
    ``fetched``.

    Device-resident path: one flat float32 buffer of the dirty segment
    (``3 * n_idx + n_blk``, buckets floored at 8), ``[8, pow2(B)]`` rows,
    ``[4, pow2(T)]`` records and ``now``; the engine's first dispatch
    creates the state and stages rows, records and ``now`` alone.
    Re-pad path: four float32 node arrays, six 4-byte and two 1-byte row
    columns, three 4-byte and one 1-byte record columns, and ``now``.
    """
    alloc = eng.allocator
    issue_async, issue = alloc.allocate_batch_async, alloc.issue_batch

    def counted_async(batch, window, now, *, state, updates=None,
                      dispatch=0):
        rows, recs = 8 * _pow2(batch.size), 4 * _pow2(window.t_start.size)
        seg = 0
        if updates is not None:
            nodes = np.asarray(updates[0])
            seg = (3 * _pow2(nodes.size, 8)
                   + _pow2(np.unique(nodes // 128).size, 8))
        expected.append(4 * (seg + rows + recs + 1))
        fetched.append(24 * _pow2(batch.size))
        return issue_async(batch, window, now, state=state, updates=updates,
                           dispatch=dispatch)

    def counted(batch, res_cpu, res_mem, window, now, cap_cpu=None,
                cap_mem=None, *, dispatch=0):
        expected.append(4 * 4 * res_cpu.size
                        + _pow2(batch.size) * (6 * 4 + 2)
                        + _pow2(window.t_start.size) * (3 * 4 + 1) + 4)
        fetched.append(24 * _pow2(batch.size))
        return issue(batch, res_cpu, res_mem, window, now, cap_cpu, cap_mem,
                     dispatch=dispatch)

    alloc.allocate_batch_async, alloc.issue_batch = counted_async, counted


class Served(NamedTuple):
    incremental: bool
    metrics: object  # EngineMetrics
    expected: list  # bytes per dispatch, from the allocator's inputs
    fetched: list  # bytes per dispatch, from the burst's width
    spans: list  # (name, start_ns, end_ns, metadata)


def _traced(trace_dir, serve):
    """Run ``serve()`` under the profiler; its result and the program's
    spans, as ``(name, start_ns, end_ns, metadata)``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        out = serve()
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in SPANS or ev.name == STATE:
                    start = int(ev.start_ns)
                    spans.append((ev.name, start, start + int(ev.duration_ns),
                                  dict(ev.stats)))
    return out, spans


def _serve(incremental: bool, trace_dir=None) -> Served:
    eng = _engine(incremental)
    expected: list = []
    fetched: list = []
    _count_staging(eng, expected, fetched)
    if trace_dir is None:
        metrics = serve_stream(eng, _arrivals()).metrics
        return Served(incremental, metrics, expected, fetched, [])
    metrics, spans = _traced(
        trace_dir, lambda: serve_stream(eng, _arrivals()).metrics)
    return Served(incremental, metrics, expected, fetched, spans)


@pytest.fixture(scope="module", params=[True, False],
                ids=["device_state", "repad"])
def traced(request, tmp_path_factory):
    return _serve(request.param, tmp_path_factory.mktemp("trace"))


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def test_every_span_is_recorded(traced):
    spans = traced.spans
    state = {STATE} if traced.incremental else set()
    assert {s[0] for s in spans} == set(SPANS) | state


@pytest.mark.parametrize("name", PER_DISPATCH)
def test_one_span_per_dispatch(traced, name):
    metrics, spans = traced.metrics, traced.spans
    ids = sorted(s[3]["dispatch"] for s in _named(spans, name))
    assert metrics.num_dispatches > 10
    assert ids == list(range(metrics.num_dispatches))


def test_dispatch_spans_open_in_order(traced):
    """Stage, pack, launch, wait and apply of one dispatch follow one
    another without overlap."""
    metrics, spans = traced.metrics, traced.spans
    by_id = {}
    for name, start, end, stats in spans:
        if name in PER_DISPATCH:
            by_id.setdefault(stats["dispatch"], {})[name] = (start, end)
    for d in range(metrics.num_dispatches):
        seq = [by_id[d][name] for name in PER_DISPATCH]
        for (_, end), (start, _) in zip(seq, seq[1:]):
            assert end <= start, (d, seq)
    rows = {s[3]["dispatch"]: s[3]["rows"] for s in
            _named(spans, "engine.stage")}
    assert rows == {s[3]["dispatch"]: s[3]["rows"] for s in
                    _named(spans, "engine.apply")}
    assert sum(rows.values()) == metrics.dispatched_rows


def test_inject_nests_only_in_fold(traced):
    """An arrival is injected from ``step()`` (top level) or inline from
    the drain's fold loop; no other program span encloses it."""
    spans = traced.spans
    where = []
    for name, start, end, _ in _named(spans, "engine.inject"):
        around = [(e - s, n) for n, s, e, _ in spans
                  if n != "engine.inject" and s <= start and end <= e]
        where.append(min(around)[1] if around else None)
    assert set(where) == {None, "engine.fold"}


def test_staged_bytes_match_an_independent_count(traced):
    metrics, expected, spans = traced.metrics, traced.expected, traced.spans
    assert len(expected) == metrics.num_dispatches
    assert metrics.staged_bytes == sum(expected)
    assert sum(s[3]["bytes"] for s in _named(spans, "alloc.pack")) \
        == metrics.staged_bytes


def test_fetched_bytes_match_the_wait_spans(traced):
    """One packed buffer per dispatch comes back: ``alloc.wait`` carries
    its bytes, and they sum to ``EngineMetrics.fetched_bytes``."""
    metrics, fetched, spans = traced.metrics, traced.fetched, traced.spans
    assert len(fetched) == metrics.num_dispatches
    waits = _named(spans, "alloc.wait")
    assert all("bytes" in s[3] for s in waits)
    assert sorted(s[3]["bytes"] for s in waits) == sorted(fetched)
    assert sum(s[3]["bytes"] for s in waits) == metrics.fetched_bytes


def test_tracing_changes_no_decision(traced):
    on, off = traced.metrics, _serve(traced.incremental).metrics
    assert on.alloc_trace == off.alloc_trace
    assert on.num_dispatches == off.num_dispatches
    assert on.staged_bytes == off.staged_bytes
    assert on.fetched_bytes == off.fetched_bytes
    assert on.makespan == off.makespan


def test_state_build_opens_in_the_first_stage(traced):
    """The device-resident state is built once, inside the first
    dispatch's ``engine.stage``, and counted: ``state_builds`` spans,
    ``state_bytes`` their bytes, four float32 tiles and the mask.  The
    re-pad path builds none."""
    metrics, builds = traced.metrics, _named(traced.spans, STATE)
    if not traced.incremental:
        assert builds == []
        assert (metrics.state_builds, metrics.state_bytes) == (0, 0)
        return
    (_, start, end, stats), = builds
    first, = [s for s in _named(traced.spans, "engine.stage")
              if s[3]["dispatch"] == 0]
    assert first[1] <= start and end <= first[2]
    assert stats["nodes"] == N_NODES
    nb = -(-N_NODES // 128)
    assert metrics.state_builds == 1
    assert metrics.state_bytes == stats["bytes"] == nb * 128 * (4 * 4 + 1)


@pytest.mark.parametrize("clusters", [1, 4])
def test_one_state_span_per_build(tmp_path, clusters):
    """Three fresh engines build three states, each under its own
    ``alloc.state`` span, and the counters sum what each uploaded: a
    federation's tiles hold every cluster, each padded to whole
    128-lane blocks."""
    nodes = 200  # 4 clusters of 50 nodes: one block each, 4 in all
    cfg = _engine(True).cfg.evolve(num_nodes=nodes, num_clusters=clusters)

    def serve():
        out = []
        for _ in range(3):
            out.append(serve_stream(KubeAdaptor(cfg), _arrivals(6)).metrics)
        return out

    metrics, spans = _traced(tmp_path, serve)
    builds = _named(spans, STATE)
    blocks = 2 if clusters == 1 else clusters
    per_build = blocks * 128 * (4 * 4 + 1)
    assert [s[3]["bytes"] for s in builds] == [per_build] * 3
    assert [s[3]["nodes"] for s in builds] == [nodes] * 3
    assert [m.state_builds for m in metrics] == [1, 1, 1]
    assert [m.state_bytes for m in metrics] == [per_build] * 3
