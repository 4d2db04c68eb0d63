"""KubeAdaptor — the workflow engine (paper Fig. 2), discrete-event form.

Components map 1:1 to the paper:

* Workflow Injection Module  → ``inject`` events from an arrival pattern
* Interface Unit             → ready-task decomposition + state tracking
* Resource Manager           → pluggable allocator (ARAS / FCFS baseline)
  driven through the MAPE-K cycle
* Containerized Executor     → ``ClusterSim.bind`` (pod creation)
* Task Container Cleaner     → delayed pod deletion, OOMKilled watch
* Redis                      → ``StateStore``

Fault-tolerance semantics follow §6.2.2: a pod whose memory quota is below
its *runtime* requirement + β turns OOMKilled mid-run; the engine deletes
it, re-allocates with the learned floor, and relaunches (self-healing).

Vertical adaptivity (``EngineConfig.vertical`` / ``repro.vertical``,
ARC-V) layers an in-place resize controller on top: while usage-curve
pods run, a periodic ``RESIZE`` sweep shrinks over-provisioned quotas
back into the cluster books (the freed capacity is offered to the
pending queue by a same-time retry) and grows under-provisioned ones
headroom-permitting, and the §6.2.2 kill becomes a *resize-first*
policy — an OOM-bound pod on a node with memory headroom is grown to
its runtime floor in place and runs to its original completion.

Injected chaos (``EngineConfig.faults``, schedules from ``repro.chaos``)
extends that story beyond OOM: ``NODE_DOWN`` cordons a node (its running
pods terminate ``FAILED`` and re-enter admission through the same HEAL
path), ``NODE_UP`` restores it (scheduling a retry against the recovered
capacity), and ``OOM_STORM`` force-OOMs the longest-running pods.  Pod
events can therefore go *stale* — a queued COMPLETE/OOM whose pod was
already killed by chaos or a workflow failure — so the handlers guard on
the pod still being Running.  Degradation is bounded: an optional retry
budget (``max_retries``) turns the next admission failure into a FAILED
workflow outcome, exponential backoff (``backoff_base``/``factor``)
gates the retry queue between failed rounds, and ``workflow_timeout``
deadlines terminate stuck workflows — all surfaced on
:class:`EngineMetrics` (displaced/recovered/failed counters and
time-to-recovery).

The allocation unit is the **arrival burst**: retry/ready/heal events
within ``TimingConfig.batch_window`` seconds of the head event drain into
a single ``allocate_batch`` dispatch (one fused MAPE-K cycle for the
whole burst) instead of one cycle per task — the event machinery lives
in ``repro.engine.events`` (typed :class:`EventKind` taxonomy +
:class:`EventQueue` with the windowed-drain primitive).  The default
``batch_window=0.0`` folds only same-timestamp events, bit-for-bit the
seed's lockstep drain; a positive window additionally folds jittered
near-simultaneous arrivals from stochastic injectors ("decide at t+ε"),
with the decision made at the last folded event's timestamp.  The
batched retry preserves the seed's FIFO admission order *and* its
head-of-line discipline (§6.1.6: the engine "waits ... for the CURRENT
task request"): pending rows go first, and once one fails the rest of the
queue is skipped, exactly as the sequential loop would.

Multi-cluster mode (``num_clusters > 1``) federates the node table into
contiguous cluster shards (``repro.cluster.federation``): bursts dispatch
through the sharded residual carry (per-shard totals, cluster-major
tiles, optional ``clusters`` device mesh) while the event loop, retry
queue and self-healing stay unchanged — node ids in every result are
global, so binding is cluster-agnostic.

Per-task mode (``batch_allocation=False``) drains the same burst but
*replays* it one dispatch per row — each decision syncs back to the host,
binds, and the next row's residual carry is rebuilt from the engine's
incremental float32 caches (``repro.core.allocator.BurstReplay``).  Both
modes execute the same step arithmetic against the same caches, so
decisions are bit-for-bit identical — see ``tests/test_batch_parity.py``
— while the replay independently verifies that the fused core's in-scan
debits and record stamps track the engine's host-side state transitions.

The phases of a step are ``jax.profiler.TraceAnnotation`` spans —
``engine.inject``, ``engine.fold``, ``engine.stage`` and
``engine.apply``, around the allocator's ``alloc.pack``,
``alloc.launch`` and ``alloc.wait``, and ``alloc.state`` inside
``engine.stage`` where an engine's first dispatch builds the
device-resident state — recorded only while a profiler session runs
(README, "Tracing the scheduler").
"""
from __future__ import annotations

import contextlib
import dataclasses
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from repro.api.config import (
    AllocatorConfig,
    ClusterConfig,
    EngineConfig,
    FaultConfig,
    TimingConfig,
    VerticalConfig,
)
from repro.api.registry import ALLOCATORS
from repro.cluster import federation
from repro.cluster.simulator import ClusterSim
from repro.core.allocator import decoded_rows
from repro.core.types import (
    Allocation,
    BatchAllocation,
    PodPhase,
    TaskBatch,
    TaskSpec,
    TaskWindow,
)
from repro.engine.events import ALLOCATABLE, Event, EventKind, EventQueue
from repro.engine.state_store import StateStore, TaskRecord
from repro.workflows.spec import WorkflowSpec

# The engine configuration is the composed, typed form from the
# Scenario API (repro.api.config): frozen ClusterConfig /
# AllocatorConfig / TimingConfig composed into EngineConfig (flat
# constructor kwargs completed their deprecation cycle and are gone).
# Re-exported here so `from repro.engine import EngineConfig` keeps
# working across the redesign.
__all__ = [
    "AllocatorConfig", "ClusterConfig", "EngineConfig", "EngineMetrics",
    "FaultConfig", "KubeAdaptor", "TimingConfig", "VerticalConfig",
    "WorkflowRun", "run_experiment",
]

# A batched dispatch of at least this many rows writes its binds' node
# books in one array update (``ClusterSim.flush_books``); below it the
# update's fixed cost (about 16 us on a CPU core, against about 4 us a
# row written at once) outweighs what it saves.
DEFERRED_BOOKS_MIN_ROWS = 8


@dataclasses.dataclass
class WorkflowRun:
    spec: WorkflowSpec
    injected_at: float
    indegree: Dict[str, int] = dataclasses.field(default_factory=dict)
    done: set = dataclasses.field(default_factory=set)
    first_start: Optional[float] = None
    finished_at: Optional[float] = None

    @property
    def complete(self) -> bool:
        return len(self.done) == self.spec.num_tasks


@dataclasses.dataclass
class EngineMetrics:
    """Evaluation metrics of §6.1.5 + trace series for Figs. 5-9."""

    makespan: float = 0.0  # Total Duration of All Workflows
    workflow_durations: Dict[str, float] = dataclasses.field(default_factory=dict)
    # time-weighted average utilization (quota / allocatable)
    avg_cpu_usage: float = 0.0
    avg_mem_usage: float = 0.0
    usage_series: List[Tuple[float, float, float]] = dataclasses.field(
        default_factory=list
    )
    oom_events: List[Tuple[float, str]] = dataclasses.field(default_factory=list)
    realloc_events: List[Tuple[float, str]] = dataclasses.field(default_factory=list)
    alloc_trace: List[Tuple[float, str, float, float, str]] = dataclasses.field(
        default_factory=list
    )
    num_allocations: int = 0
    num_waits: int = 0
    # Dispatch efficiency of the windowed drain: how many device
    # dispatches the allocation path issued (batched mode: one per
    # drained burst; per-task replay: one per row) and how many task
    # rows they carried in total.
    num_dispatches: int = 0
    dispatched_rows: int = 0
    # Bytes the batched paths copied host→device to issue those
    # dispatches (``PendingBurst.staged_bytes``; the per-row replay is
    # not counted).
    staged_bytes: int = 0
    # Device-resident states built (one per engine, at its first
    # batched dispatch) and the bytes each copied host to device: the
    # whole cluster's tiles (``DeviceResidualState.staged_bytes``).
    state_builds: int = 0
    state_bytes: int = 0
    # Bytes those dispatches copied device→host: one packed decision
    # buffer each (``PendingBurst.fetched_bytes``).
    fetched_bytes: int = 0
    # Binds whose node books the per-dispatch array update wrote
    # (``ClusterSim.flush_books``): every bind of a batched dispatch,
    # none of the per-row replay's.
    batched_binds: int = 0
    # SLA accounting (paper Eqs. 2-4): per-workflow deadline violations
    sla_violations: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list  # (workflow, finished_at, deadline)
    )
    # Fault-injection + graceful-degradation accounting (repro.chaos):
    node_events: List[Tuple[float, int, str]] = dataclasses.field(
        default_factory=list  # (t, node, "down"|"up")
    )
    displaced_tasks: List[Tuple[float, str]] = dataclasses.field(
        default_factory=list  # (t, wf/task) running pods lost to NODE_DOWN
    )
    recovery_times: List[Tuple[str, float]] = dataclasses.field(
        default_factory=list  # (wf/task, displaced -> re-bound seconds)
    )
    failed_tasks: List[Tuple[float, str]] = dataclasses.field(
        default_factory=list  # (t, wf/task) retry budget exhausted
    )
    failed_workflows: List[Tuple[float, str, str]] = dataclasses.field(
        default_factory=list  # (t, workflow, "retry_budget"|"deadline")
    )
    # Forecast telemetry (EngineConfig.forecast / repro.forecast):
    # arrivals observed, drains whose fold window came from a live
    # prediction (+ the summed window for the mean), and burst decisions
    # that priced a ghost forecast-demand record (adaptive_scaling).
    forecast_observations: int = 0
    forecast_predictions: int = 0
    forecast_window_sum: float = 0.0
    forecast_ghost_rows: int = 0
    # Vertical adaptivity telemetry (EngineConfig.vertical /
    # repro.vertical): in-place resizes of running pods, the capacity a
    # shrink returned to the books integrated over the pod's remaining
    # lifetime (millicore·s / MiB·s), and OOM kills the resize-first
    # policy converted into in-place grows.
    num_resizes: int = 0
    num_shrinks: int = 0
    num_grows: int = 0
    resizes_avoided_oom: int = 0
    reclaimed_cpu_seconds: float = 0.0
    reclaimed_mem_seconds: float = 0.0
    resize_events: List[Tuple[float, str, float, float]] = dataclasses.field(
        default_factory=list  # (t, wf/task, Δcpu, Δmem) signed quota deltas
    )

    @property
    def mean_forecast_window(self) -> float:
        """Mean adaptive fold window across predicted drains, seconds."""
        return (self.forecast_window_sum / self.forecast_predictions
                if self.forecast_predictions else 0.0)

    @property
    def sla_violation_rate(self) -> float:
        n = len(self.workflow_durations)
        return len(self.sla_violations) / n if n else 0.0

    @property
    def num_displaced(self) -> int:
        return len(self.displaced_tasks)

    @property
    def num_recovered(self) -> int:
        """Displaced tasks that re-entered admission and re-bound."""
        return len(self.recovery_times)

    @property
    def mean_time_to_recovery(self) -> float:
        """Mean seconds from displacement to the recovering bind."""
        return (float(np.mean([dt for _, dt in self.recovery_times]))
                if self.recovery_times else 0.0)

    @property
    def avg_workflow_duration(self) -> float:
        vals = list(self.workflow_durations.values())
        return float(np.mean(vals)) if vals else 0.0

    @property
    def mean_burst_width(self) -> float:
        """Mean task rows per allocation dispatch (1.0 in replay mode)."""
        return (self.dispatched_rows / self.num_dispatches
                if self.num_dispatches else 0.0)


class KubeAdaptor:
    """Discrete-event engine executing workflows under an allocator."""

    def __init__(self, config: EngineConfig):
        # Fail at construction, not first dispatch, on a typo'd name or
        # an impossible federation split (actionable messages).
        config.validate()
        self.cfg = config
        cluster_cfg, alloc_cfg = config.cluster, config.alloc
        self.cluster = ClusterSim(cluster_cfg.num_nodes,
                                  cluster_cfg.node_cpu,
                                  cluster_cfg.node_mem,
                                  num_clusters=cluster_cfg.num_clusters)
        # Burst dispatches go through the federated layout whenever there
        # is more than one cluster; "force" also routes the single-cluster
        # setup through the K=1 federated path (bit-for-bit the legacy
        # allocator — the cross-shard parity suite holds it to that).
        layout = (federation.layout_of(self.cluster)
                  if cluster_cfg.num_clusters > 1
                  or cluster_cfg.sharding == "force" else None)
        entry = ALLOCATORS.get(alloc_cfg.algorithm)
        kwargs = {"placement": alloc_cfg.placement,
                  "backend": alloc_cfg.backend,
                  "layout": layout,
                  "cluster_sharding": cluster_cfg.sharding}
        if entry.supports("adaptive_scaling"):
            kwargs.update(alpha=alloc_cfg.alpha, beta=alloc_cfg.beta)
        self.allocator = entry.factory(**kwargs)
        # Device-resident incremental dispatch: fused bursts decide
        # against tiles that persist on device, maintained by dirty-node
        # scatter updates instead of per-burst O(nodes) re-pads.  Gated
        # on the allocator capability, batched mode (the replay path is
        # *defined* as rebuilding the carry from host caches), the
        # config knob, and the absence of a device mesh (the sharded
        # layout re-places tiles per dispatch).
        self._use_device_state = (
            alloc_cfg.batch_allocation
            and alloc_cfg.incremental_state
            and entry.supports("device_state")
            and self.allocator._mesh() is None
        )
        self._state = None  # DeviceResidualState, created on first burst
        # Online arrival forecasting (EngineConfig.forecast).  The
        # forecaster observes every injection; the drain sizes its fold
        # window from the predicted gap, and forecast-capable allocators
        # (``adaptive_scaling``) additionally price a ghost record
        # carrying the forecast-horizon demand.  Disabled (default) the
        # attribute stays None and every consumer takes the static path
        # — bit-for-bit today's engine.
        self._predictive = entry.supports("forecast")
        if config.forecast.enabled:
            from repro.forecast import ArrivalForecaster

            self._forecaster: Optional[ArrivalForecaster] = \
                ArrivalForecaster(config.forecast)
        else:
            self._forecaster = None
        # Streaming overlap hook: called between issuing a fused dispatch
        # and syncing its results, while the device is busy
        # (repro.serving.stream sets it to pump arrival ingestion).
        self.ingest_hook: Optional[Callable[[], None]] = None
        self.store = StateStore()
        self.runs: Dict[str, WorkflowRun] = {}
        self.metrics = EngineMetrics()
        self.queue = EventQueue()
        self._pending: Deque[Tuple[str, TaskSpec]] = deque()
        self._now = 0.0
        self._t_first: Optional[float] = None
        self._last_sample = (0.0, 0.0, 0.0)  # (t, cpu_util, mem_util)
        self._util_integral = np.zeros(2)
        # Fault injection + graceful degradation (repro.chaos).  The
        # bookkeeping dicts stay empty without a FaultConfig, so the hot
        # path pays one falsy check per bind at most.
        faults = config.faults
        self._fault_cfg = faults
        self._attempts: Dict[str, int] = {}  # wf/task -> failed admissions
        self._displaced_at: Dict[str, float] = {}  # wf/task -> t displaced
        self._failed_workflows: set = set()
        self._retry_gate = 0.0  # retries before this time stay gated
        self._backoff_round = 0
        # Stale-event dropping (see _event_stale) only matters once
        # something can kill pods or fail workflows; keep it off the
        # no-fault hot path.
        self._chaos_on = (faults.schedule != "none"
                          or faults.max_retries is not None
                          or faults.workflow_timeout is not None
                          or faults.backoff_base > 0)
        # Vertical adaptivity (EngineConfig.vertical / repro.vertical):
        # the resize controller arms a periodic RESIZE event while a
        # usage-curve pod is running.  Disabled (default) no RESIZE event
        # is ever queued — bit-for-bit today's engine.
        self._vertical = config.vertical.enabled
        self._resize_armed = False
        if faults.schedule != "none":
            from repro.api.registry import FAULTS

            entry = FAULTS.get(faults.schedule)
            schedule = entry.factory(
                num_nodes=cluster_cfg.num_nodes,
                **{"seed": faults.seed, **dict(faults.params)})
            for fault in schedule:
                self._push(fault.t, fault.kind, fault.payload)

    # ----------------------------------------------------------- plumbing
    def _push(self, t: float, kind: EventKind, payload: tuple) -> None:
        self.queue.push(t, kind, payload)

    def submit(self, spec: WorkflowSpec, at: float) -> None:
        self._push(at, EventKind.INJECT, (spec,))

    def _sample_usage(self) -> None:
        """Advance the time-weighted utilization integral to ``now``."""
        t0, cu, mu = self._last_sample
        dt = self._now - t0
        if dt > 0:
            self._util_integral += dt * np.array([cu, mu])
        u = self.cluster.utilization()
        self._last_sample = (self._now, u.cpu, u.mem)
        self.metrics.usage_series.append((self._now, u.cpu, u.mem))

    # -------------------------------------------------------------- phases
    def _inject(self, spec: WorkflowSpec) -> None:
        """Workflow Injection Module + Interface Unit decomposition."""
        with TraceAnnotation("engine.inject", tasks=spec.num_tasks):
            if self._forecaster is not None:
                # One observation per arrival: timestamp + total declared
                # demand (the horizon-demand intensity estimate).
                self._forecaster.observe(
                    self._now,
                    cpu=sum(t.cpu for t in spec.tasks.values()),
                    mem=sum(t.mem for t in spec.tasks.values()),
                )
                self.metrics.forecast_observations += 1
            run = WorkflowRun(spec=spec, injected_at=self._now,
                              indegree=spec.indegrees())
            self.runs[spec.workflow_id] = run
            # Plan-phase knowledge: projected earliest starts for every task.
            est = spec.earliest_starts(self._now)
            for tid, task in spec.tasks.items():
                self.store.put(TaskRecord(
                    key=f"{spec.workflow_id}/{tid}", t_start=est[tid],
                    duration=task.duration, cpu=task.cpu, mem=task.mem,
                ))
            for tid in spec.roots():
                self._push(self._now, EventKind.READY, (spec.workflow_id, tid))
            if self._fault_cfg.workflow_timeout is not None:
                self._push(self._now + self._fault_cfg.workflow_timeout,
                           EventKind.WF_DEADLINE, (spec.workflow_id,))

    # --------------------------------------------------- burst allocation
    def _batch_of(self, entries: List[Tuple[str, TaskSpec, str]]
                  ) -> TaskBatch:
        return TaskBatch.from_tasks(
            [task for _, task, _ in entries],
            self._now,
            self_slots=[
                self.store.index_of(f"{wf_id}/{task.task_id}")
                for wf_id, task, _ in entries
            ],
            pending=[origin == "pending" for _, _, origin in entries],
        )

    def fold_window(self) -> float:
        """Seconds of fold entitlement for the next drained burst.

        The static ``TimingConfig.batch_window`` unless forecasting is
        enabled *and* the forecaster has enough history, in which case
        the window is sized from the predicted next inter-arrival gap
        (``repro.forecast.ArrivalForecaster.fold_window``).  Public
        because the serving pump (``repro.serving.stream``) must grant
        the engine exactly this entitlement when deciding which
        arrivals the next step may see.
        """
        if self._forecaster is None:
            return self.cfg.timing.batch_window
        return self._forecaster.fold_window(self.cfg.timing.batch_window)

    def _alloc_window(self) -> TaskWindow:
        """The knowledge-base window a burst decision prices against.

        For forecast-capable allocators (``adaptive_scaling``) with a
        live prediction, one *ghost record* is appended: stamped at
        ``now``, never done, carrying the expected resource demand of
        the forecast horizon.  Alg. 1's request accumulation then sees
        load that has not arrived yet and Alg. 3's proportional cuts
        tighten quotas ahead of the predicted burst — predictive
        pre-provisioning with zero kernel changes.  The ghost lives
        only in this per-decision view; the store itself is untouched.
        """
        window = self.store.window()
        if not self._predictive or self._forecaster is None:
            return window
        # Present demand outranks predicted demand: while tasks already
        # sit in the retry queue the cluster is refusing real admissions,
        # and a ghost on top would tighten quotas further — each
        # no-progress round arms the exponential backoff gate, so the
        # compounding idle time dwarfs any pre-provisioning benefit.
        if self._pending:
            return window
        ghost_cpu, ghost_mem = self._forecaster.horizon_demand()
        # Bound the ghost to a fraction of what the cluster could even
        # give: pre-provisioning shares capacity with predicted load,
        # it must never starve present admissions below their floors.
        res_cpu, res_mem = self.cluster.residual_view()
        cap = self.cfg.forecast.ghost_cap
        ghost_cpu = min(ghost_cpu, cap * float(np.sum(res_cpu)))
        ghost_mem = min(ghost_mem, cap * float(np.sum(res_mem)))
        if ghost_cpu <= 0.0 and ghost_mem <= 0.0:
            return window
        self.metrics.forecast_ghost_rows += 1
        # Appending keeps every existing slot index valid (self-exclusion
        # masks point at unchanged positions); the store's free tail
        # slots are done=True and numerically inert either way.
        return TaskWindow(
            t_start=np.append(window.t_start, np.float32(self._now)),
            cpu=np.append(window.cpu, np.float32(ghost_cpu)),
            mem=np.append(window.mem, np.float32(ghost_mem)),
            done=np.append(window.done, False),
        )

    def _flush_state(self):
        """The device state plus the dirty set pending against it.

        First call stages the whole cluster once and turns on the
        simulator's dirty-node journal (no updates pending); afterwards
        it drains the nodes touched since the previous burst, with
        values read from the same authoritative float32 caches
        ``residual_view`` exposes.  The allocator folds the returned
        dirty set into the decision dispatch itself (one fused
        maintain-and-decide call), so the tiles always equal what the
        re-pad path would rebuild.
        """
        if self._state is None:
            res_cpu, res_mem = self.cluster.residual_view()
            cap_cpu, cap_mem = self.cluster.capacity_view()
            self._state = self.allocator.create_state(
                res_cpu, res_mem, cap_cpu, cap_mem)
            self.metrics.state_builds += 1
            self.metrics.state_bytes += self._state.staged_bytes
            self.cluster.track_dirty()
            return self._state, None
        return self._state, self.cluster.drain_dirty()

    def _decide(self, entries: List[Tuple[str, TaskSpec, str]],
                dispatch: int) -> BatchAllocation:
        """One fused MAPE-K cycle for a burst of task requests.

        Monitor reads the incremental caches (no snapshot rebuild);
        Analyse/Plan run inside the allocator's single dispatch; Execute
        happens in ``_allocate_group`` from the one synced result.

        On the device-state path the dispatch is issued asynchronously
        against the incrementally-maintained tiles; while the device
        computes, the streaming ingest hook (if any) runs — the
        double-buffered overlap — and only then does the engine block on
        the results.
        """
        with TraceAnnotation("engine.stage", dispatch=dispatch,
                             rows=len(entries)):
            if self._use_device_state:
                state, updates = self._flush_state()
            else:
                res_cpu, res_mem = self.cluster.residual_view()
                cap_cpu, cap_mem = self.cluster.capacity_view()
            batch = self._batch_of(entries)
            window = self._alloc_window()
        if self._use_device_state:
            pending = self.allocator.allocate_batch_async(
                batch, window, self._now, state=state, updates=updates,
                dispatch=dispatch,
            )
            self._state = pending.state
            if self.ingest_hook is not None:
                self.ingest_hook()
        else:
            pending = self.allocator.issue_batch(
                batch, res_cpu, res_mem, window, self._now,
                cap_cpu=cap_cpu, cap_mem=cap_mem, dispatch=dispatch,
            )
        self.metrics.staged_bytes += pending.staged_bytes
        self.metrics.fetched_bytes += pending.fetched_bytes
        return pending.wait()

    def _replay_rows(self, entries: List[Tuple[str, TaskSpec, str]]):
        """Yield (feasible, attempted, Allocation) per entry, in order.

        Per-task mode replays the burst one dispatch per row, reading the
        engine's live residual caches *after* each preceding bind (the
        generator suspends at ``yield`` while the consumer applies the
        decision) — the sequential MAPE-K reference.
        """
        res_cpu, res_mem = self.cluster.residual_view()
        cap_cpu, cap_mem = self.cluster.capacity_view()
        replay = self.allocator.begin_replay(
            self._batch_of(entries), res_cpu, res_mem,
            self._alloc_window(), self._now,
            cap_cpu=cap_cpu, cap_mem=cap_mem,
        )
        for i in range(len(entries)):
            res_cpu, res_mem = self.cluster.residual_view()
            alloc, attempted = replay.step(i, res_cpu, res_mem)
            yield alloc.feasible, attempted, alloc

    def _binder(self) -> Callable[[str, TaskSpec, Allocation], None]:
        """Execute phase for one dispatch's rows: the Containerized
        Executor creates each pod and schedules its end.

        Returns ``bind(wf_id, task, alloc)``.  What no bind of the
        dispatch can change is read once here: the clock, the timing and
        beta, whether any displaced task waits for its recovery, and
        whether retry budgets are kept (without ``max_retries`` the
        attempt books stay as they are through the dispatch).  Each bind
        still goes through ``self.cluster.bind``, once per row, in
        admission order.
        """
        cluster, store, metrics, runs = (self.cluster, self.store,
                                         self.metrics, self.runs)
        now = self._now
        timing = self.cfg.timing
        startup, oom_fraction = timing.pod_startup_delay, timing.oom_fraction
        multiplier = timing.duration_multiplier
        beta = self.cfg.alloc.beta
        displaced = self._displaced_at if self._displaced_at else None
        attempts = (self._attempts if self._attempts
                    or self._fault_cfg.max_retries is not None else None)
        vertical = self._vertical
        push = self.queue.push
        trace = metrics.alloc_trace.append

        def bind(wf_id: str, task: TaskSpec, alloc: Allocation) -> None:
            key = f"{wf_id}/{task.task_id}"
            pod = cluster.bind(task, alloc, now, workflow_id=wf_id)
            store.mark_started(key, now)
            if displaced is not None:
                t0 = displaced.pop(key, None)
                if t0 is not None:  # a displaced task recovered (re-bound)
                    metrics.recovery_times.append((key, now - t0))
            if attempts is not None:
                # A successful bind resets the task's retry budget.
                attempts.pop(key, None)
            run = runs[wf_id]
            if run.first_start is None:
                run.first_start = now
            metrics.num_allocations += 1
            trace((now, key, alloc.cpu, alloc.mem, alloc.scenario))
            # Will this quota OOM? (§6.2.2: runtime memory floor + β)
            runtime_floor = task.runtime_min_mem() + beta
            wall = multiplier * task.duration
            if alloc.mem < runtime_floor - 1e-9 and task.mem > 0:
                push(now + startup + oom_fraction * wall, EventKind.OOM,
                     (pod.uid, wf_id))
            else:
                push(now + startup + wall, EventKind.COMPLETE,
                     (pod.uid, wf_id))
            if vertical and not self._resize_armed \
                    and task.usage_curve is not None:
                # First usage-curve pod on an idle controller: arm the
                # periodic sweep.  The controller re-arms itself while
                # resizable pods remain and disarms (in ``step``) when
                # none do, so a drained cluster queues no trailing
                # RESIZE events.
                self._resize_armed = True
                push(now + self.cfg.vertical.check_interval,
                     EventKind.RESIZE, ())
            self._sample_usage()

        return bind

    @contextlib.contextmanager
    def _books(self, deferred: bool):
        """Node books for one dispatch's binds.  ``deferred``: written in
        one array update once the rows are applied (or a bind fails),
        counted in ``batched_binds``; else at each bind, as the replay
        needs, since it reads ``residual_view`` after every bind."""
        if not deferred:
            yield
            return
        self.cluster.defer_books()
        try:
            yield
        finally:
            self.metrics.batched_binds += self.cluster.flush_books()

    def _budget_exhausted(self, wf_id: str, task: TaskSpec) -> bool:
        """Count one attempted admission failure against the retry budget.

        Returns True once the task has failed more than ``max_retries``
        times since its last successful bind — the caller then terminates
        the whole workflow as a FAILED outcome.  With the default
        unbounded budget this is a no-op returning False.
        """
        budget = self._fault_cfg.max_retries
        if budget is None:
            return False
        key = f"{wf_id}/{task.task_id}"
        n = self._attempts.get(key, 0) + 1
        self._attempts[key] = n
        if n <= budget:
            return False
        self.metrics.failed_tasks.append((self._now, key))
        return True

    def _allocate_group(self, entries: List[Tuple[str, TaskSpec, str]],
                        include_pending: bool) -> None:
        """Decide a drained burst and apply the results in admission order.

        Graceful degradation rides the result application: every
        *attempted* failure counts against the task's retry budget (a
        blown budget marks the workflow dying — terminated after the
        pending queue is rebuilt, so the rebuild sees a consistent
        deque), and a round that made no progress arms the exponential
        backoff gate.  Which rows bind is untouched — decided rows of a
        dying workflow still bind (batched and replay modes already
        applied their in-scan debits identically) and are then killed by
        ``_fail_workflow``, keeping the two modes bit-for-bit.
        """
        if include_pending:
            entries = [(wf_id, task, "pending")
                       for wf_id, task in self._pending] + entries
        if not entries:
            return
        dispatch = self.metrics.num_dispatches
        self.metrics.dispatched_rows += len(entries)
        kept: Deque[Tuple[str, TaskSpec]] = deque()
        failed: List[Tuple[str, TaskSpec]] = []
        dying: Dict[str, None] = {}  # insertion-ordered workflow set
        bound_any = False
        waited_any = False
        batched = self.cfg.alloc.batch_allocation
        if batched:
            # One fused dispatch decides every row before any applies.
            self.metrics.num_dispatches += 1
            rows = decoded_rows(self._decide(entries, dispatch))
            applying = TraceAnnotation("engine.apply", dispatch=dispatch,
                                       rows=len(entries))
        else:
            self.metrics.num_dispatches += len(entries)
            rows = self._replay_rows(entries)
            applying = contextlib.nullcontext()
        bind = self._binder()
        deferred = batched and len(entries) >= DEFERRED_BOOKS_MIN_ROWS
        with applying, self._books(deferred):
            for (wf_id, task, origin), (feasible, attempted, alloc) in zip(
                    entries, rows):
                if feasible:
                    bind(wf_id, task, alloc)
                    bound_any = True
                elif origin == "pending":
                    # Skipped rows (head-of-line) were never attempted and
                    # do not count as waits, matching the sequential retry
                    # loop.
                    if attempted:
                        self.metrics.num_waits += 1
                        waited_any = True
                        if self._budget_exhausted(wf_id, task):
                            dying[wf_id] = None
                            continue
                    kept.append((wf_id, task))
                else:
                    self.metrics.num_waits += 1
                    waited_any = True
                    if self._budget_exhausted(wf_id, task):
                        dying[wf_id] = None
                        continue
                    failed.append((wf_id, task))
        if include_pending:
            kept.extend(failed)
            self._pending = kept
        else:
            self._pending.extend(failed)
        for wf_id in dying:
            if wf_id not in self._failed_workflows:
                self._fail_workflow(wf_id, "retry_budget")
        if bound_any:
            self._backoff_round = 0
            self._retry_gate = 0.0
        elif waited_any and self._pending \
                and self._fault_cfg.backoff_base > 0:
            # No progress this round: park the pending queue and schedule
            # the RETRY that reopens the gate — base * factor^round.
            delay = self._fault_cfg.backoff_base * \
                self._fault_cfg.backoff_factor ** self._backoff_round
            self._backoff_round += 1
            self._retry_gate = self._now + delay
            self._push(self._retry_gate, EventKind.RETRY, ("backoff",))

    def _drain_group(self, first: Event) -> None:
        """Fold the head's allocatable-event window into one burst.

        Events are consumed in heap order (time, kind, sequence), so the
        batch rows land in exactly the order the sequential loop would
        have decided them; virtual tasks complete inline, which may
        surface more in-window READY events — the loop keeps draining
        while the next queued event folds: an allocatable request due
        within ``batch_window`` seconds of the head ("decide at t+ε"),
        or a strictly-later INJECT within that deadline, which is
        injected inline so the jittered arrival's READY events join the
        burst.  The clock advances with each folded event, so the fused
        decision is made at the *last* arrival's timestamp, never before
        a request exists; a capacity-changing event inside the window
        (completion, deletion, OOM) stops the fold once the burst holds
        an undecided request, because it must apply first.  While the
        burst is still *empty* (no entries and no retried pending queue)
        strictly-later ``COMPLETE``/``DELETE`` events fold through — the
        freed capacity cannot change a decision that does not exist yet,
        so short-task streams stop fragmenting every window on their own
        completions (``OOM`` always anchors its own drain: it mutates a
        pod's outcome and schedules self-healing).  With
        ``batch_window=0.0`` the deadline is the head's own timestamp
        and only same-timestamp allocatable events fold — the seed's
        lockstep drain, bit for bit.  With forecasting enabled the
        window comes from :meth:`fold_window` instead — sized per burst
        from the predicted next inter-arrival gap.  Both engine modes
        share this drain; they differ only in how the group is decided
        (one fused dispatch vs the row-at-a-time replay — see
        ``_allocate_group``).
        """
        window = self.fold_window()
        if self._forecaster is not None and self._forecaster.ready:
            self.metrics.forecast_predictions += 1
            self.metrics.forecast_window_sum += window
        deadline = first.t + window
        include_pending = False
        entries: List[Tuple[str, TaskSpec, str]] = []
        event: Optional[Event] = first
        with TraceAnnotation("engine.fold"):
            while event is not None:
                self._now = event.t
                if event.kind is EventKind.INJECT:
                    self._inject(*event.payload)
                elif event.kind is EventKind.COMPLETE:
                    # Folded only while the burst is idle (see below).
                    self._complete(*event.payload)
                elif event.kind is EventKind.DELETE:
                    self.cluster.delete(*event.payload)
                elif event.kind is EventKind.RETRY:
                    # Backoff gate: retries scheduled before the gate reopens
                    # leave the pending queue parked (the gate-time RETRY
                    # pushed by the failed round reopens it).
                    include_pending = self._now >= self._retry_gate
                elif event.kind is EventKind.READY:
                    wf_id, tid = event.payload
                    if wf_id in self._failed_workflows:
                        pass  # workflow already terminated FAILED
                    else:
                        task = self.runs[wf_id].spec.tasks[tid]
                        if task.cpu == 0 and task.mem == 0:
                            # Virtual entrance/exit: completes at once,
                            # no pod.
                            self._task_done(wf_id, tid)
                        else:
                            entries.append((wf_id, task, "ready"))
                else:  # HEAL
                    wf_id, task = event.payload
                    if wf_id not in self._failed_workflows:
                        self.metrics.realloc_events.append(
                            (self._now, f"{wf_id}/{task.task_id}")
                        )
                        entries.append((wf_id, task, "heal"))
                idle = not entries and not (include_pending and self._pending)
                event = self.queue.pop_mergeable(first.t, deadline,
                                                 fold_capacity_free=idle)
        self._allocate_group(entries, include_pending)

    # --------------------------------------------------------- completion
    def _task_done(self, wf_id: str, tid: str) -> None:
        run = self.runs[wf_id]
        key = f"{wf_id}/{tid}"
        self.store.mark_done(key, self._now)
        run.done.add(tid)
        for child in run.spec.children(tid):
            run.indegree[child] -= 1
            if run.indegree[child] == 0:
                self._push(self._now, EventKind.READY, (wf_id, child))
        if run.complete:
            run.finished_at = self._now
            dur_start = run.first_start if run.first_start is not None \
                else run.injected_at
            self.metrics.workflow_durations[wf_id] = self._now - dur_start
            # SLA check (Eq. 4: workflow deadline = last task's deadline)
            if run.spec.deadline is not None \
                    and self._now > run.injected_at + run.spec.deadline:
                self.metrics.sla_violations.append(
                    (wf_id, self._now, run.injected_at + run.spec.deadline))

    def _stale(self, uid: int) -> bool:
        """A queued pod event whose pod was already terminated (killed by
        injected chaos or a workflow failure) — drop it."""
        pod = self.cluster.pods.get(uid)
        return pod is None or pod.phase is not PodPhase.RUNNING

    def _complete(self, uid: int, wf_id: str) -> None:
        if self._stale(uid):
            return
        pod = self.cluster.finish(uid, self._now, PodPhase.SUCCEEDED)
        self._sample_usage()
        self._push(self._now + self.cfg.timing.cleanup_delay,
                   EventKind.DELETE, (uid,))
        self._task_done(wf_id, pod.task.task_id)
        self._push(self._now, EventKind.RETRY, ())

    def _oom(self, uid: int, wf_id: str, forced: bool = False) -> None:
        """OOMKilled watch → delete → reallocate (self-healing, Fig. 9).

        With vertical adaptivity the kill is the *fallback*: an OOM-bound
        pod whose node has memory headroom is grown to its runtime floor
        in place instead (``_resize_rescue``) — no restart delay, no lost
        progress.  ``forced`` OOMs (injected storms — pressure beyond the
        quota's control) always kill.
        """
        if self._stale(uid):
            return
        vertical = self.cfg.vertical
        if vertical.enabled and vertical.resize_on_oom and not forced \
                and self._resize_rescue(uid, wf_id):
            return
        pod = self.cluster.finish(uid, self._now, PodPhase.OOM_KILLED)
        self._sample_usage()
        key = f"{wf_id}/{pod.task.task_id}"
        self.metrics.oom_events.append((self._now, key))
        self._push(self._now + self.cfg.timing.cleanup_delay,
                   EventKind.DELETE, (uid,))
        # Learn the runtime floor so the reallocation cannot repeat the OOM.
        learned = dataclasses.replace(
            pod.task, min_mem=max(pod.task.min_mem, pod.task.runtime_min_mem())
        )
        self._push(self._now + self.cfg.timing.restart_delay, EventKind.HEAL,
                   (wf_id, learned))

    # -------------------------------------------------- vertical adaptivity
    def _resize_rescue(self, uid: int, wf_id: str) -> bool:
        """Resize-first OOM policy (ARC-V): grow the quota in place.

        The §6.2.2 watch fired because the admitted memory quota sits
        below the runtime floor + β.  If the node's float64 books have
        headroom for the missing delta, the pod grows to the floor in
        place and runs to its *original* completion time — the kill, the
        cleanup/restart delays and the re-admission queue round-trip are
        all avoided.  Returns ``False`` when the node is full; the caller
        then falls back to the seed kill-and-reallocate path.
        """
        pod = self.cluster.pods[uid]
        task = pod.task
        need = task.runtime_min_mem() + self.cfg.alloc.beta
        if pod.quota.mem < need - 1e-9:
            head = self.cluster.node_headroom(pod.node)
            if need - pod.quota.mem > head.mem + 1e-9:
                return False  # node full: kill-and-reallocate
            old_mem = pod.quota.mem
            self.cluster.resize(uid, pod.quota.cpu, need)
            grown = pod.quota.mem - old_mem  # post-snap, matches the books
            self.metrics.num_resizes += 1
            self.metrics.num_grows += 1
            self.metrics.resize_events.append(
                (self._now, f"{wf_id}/{task.task_id}", 0.0, grown))
            self._sample_usage()
        # Quota now covers the floor (grown here, or already grown by an
        # earlier controller sweep): the kill is averted.
        self.metrics.resizes_avoided_oom += 1
        timing = self.cfg.timing
        t_done = pod.t_created + timing.pod_startup_delay + \
            timing.duration_multiplier * task.duration
        self._push(t_done, EventKind.COMPLETE, (uid, wf_id))
        return True

    def _any_resizable(self) -> bool:
        """A Running usage-curve pod exists — the controller has work."""
        return any(pod.phase is PodPhase.RUNNING
                   and pod.task.usage_curve is not None
                   for pod in self.cluster.pods.values())

    def _resize_tick(self) -> None:
        """One controller sweep: compare usage against quota, resize.

        For every Running usage-curve pod (uid order — deterministic) the
        target quota is the curve's *remaining-lifetime peak* usage plus
        the ``grow_margin`` headroom, floored at the acceptance minimum
        and (for memory) the §6.2.2 runtime floor + β so a shrink can
        never re-create the OOM condition admission cleared, and capped
        at the declared request.  Over-provisioned quotas shrink once
        they exceed the target by the ``shrink_margin`` hysteresis band;
        under-provisioned ones grow as far as the node's float64 headroom
        allows.  Shrinks credit ``reclaimed_*_seconds`` with the freed
        quota integrated over the pod's remaining lifetime and schedule a
        same-time RETRY (RESIZE sorts before RETRY) so the pending queue
        decides against the reclaimed capacity immediately.
        """
        cfg = self.cfg.vertical
        timing = self.cfg.timing
        beta = self.cfg.alloc.beta
        from repro import vertical as curves

        changed = False
        shrank = False
        for uid in sorted(self.cluster.pods):
            pod = self.cluster.pods[uid]
            task = pod.task
            if pod.phase is not PodPhase.RUNNING or task.usage_curve is None:
                continue
            wall = timing.duration_multiplier * task.duration
            if wall <= 0:
                continue
            p = (self._now - pod.t_started - timing.pod_startup_delay) / wall
            if p >= 1.0:
                continue  # completing at this instant
            p = max(p, 0.0)
            peak_cpu, peak_mem = curves.peak_usage(task, p)
            floor_cpu = task.min_cpu
            floor_mem = max(task.min_mem, task.runtime_min_mem() + beta) \
                if task.mem > 0 else 0.0
            want_cpu = min(max(peak_cpu * (1.0 + cfg.grow_margin),
                               floor_cpu), max(task.cpu, floor_cpu))
            want_mem = min(max(peak_mem * (1.0 + cfg.grow_margin),
                               floor_mem), max(task.mem, floor_mem))
            q_cpu, q_mem = pod.quota.cpu, pod.quota.mem
            new_cpu, new_mem = q_cpu, q_mem
            if q_cpu > want_cpu * (1.0 + cfg.shrink_margin) \
                    or want_cpu > q_cpu:
                new_cpu = want_cpu
            if q_mem > want_mem * (1.0 + cfg.shrink_margin) \
                    or want_mem > q_mem:
                new_mem = want_mem
            # Grows are bounded by the node's remaining headroom (the
            # resize itself re-checks against the authoritative books).
            if new_cpu > q_cpu or new_mem > q_mem:
                head = self.cluster.node_headroom(pod.node)
                new_cpu = min(new_cpu, q_cpu + max(head.cpu, 0.0)) \
                    if new_cpu > q_cpu else new_cpu
                new_mem = min(new_mem, q_mem + max(head.mem, 0.0)) \
                    if new_mem > q_mem else new_mem
            # ClusterSim.resize snaps quotas onto the float32 lattice
            # (the pod slot arrays are float32); snap here too so the
            # telemetry deltas below equal the books' deltas exactly.
            new_cpu = float(np.float32(new_cpu))
            new_mem = float(np.float32(new_mem))
            if abs(new_cpu - q_cpu) < 1e-9 and abs(new_mem - q_mem) < 1e-9:
                continue
            self.cluster.resize(uid, new_cpu, new_mem)
            changed = True
            self.metrics.num_resizes += 1
            if new_cpu < q_cpu or new_mem < q_mem:
                self.metrics.num_shrinks += 1
            if new_cpu > q_cpu or new_mem > q_mem:
                self.metrics.num_grows += 1
            remaining = (1.0 - p) * wall
            if new_cpu < q_cpu:
                self.metrics.reclaimed_cpu_seconds += \
                    (q_cpu - new_cpu) * remaining
                shrank = True
            if new_mem < q_mem:
                self.metrics.reclaimed_mem_seconds += \
                    (q_mem - new_mem) * remaining
                shrank = True
            self.metrics.resize_events.append(
                (self._now, f"{pod.workflow_id}/{task.task_id}",
                 new_cpu - q_cpu, new_mem - q_mem))
        if changed:
            self._sample_usage()
        if shrank:
            self._push(self._now, EventKind.RETRY, ())
        # Re-arm unconditionally; a sweep that finds nothing resizable is
        # dropped (and disarmed) by the guard in ``step`` without
        # advancing the clock, so trailing RESIZE events cannot stretch
        # the makespan.
        self._push(self._now + cfg.check_interval, EventKind.RESIZE, ())

    # ------------------------------------------------------- fault handling
    def _node_down(self, node: int) -> None:
        """Injected NODE_DOWN: cordon the node, displace its pods.

        Each displaced Running pod terminates ``FAILED`` (inside
        ``ClusterSim.set_node_down``), is cleaned up like any terminal
        pod, and its *original* task re-enters admission through the HEAL
        path after ``restart_delay`` — the same self-healing road an
        OOMKilled pod takes, minus the learned floor (the task itself was
        fine; its node was not).
        """
        displaced = self.cluster.set_node_down(node, self._now)
        if displaced is None:  # already offline
            return
        self.metrics.node_events.append((self._now, node, "down"))
        self._sample_usage()
        timing = self.cfg.timing
        for pod in displaced:
            key = f"{pod.workflow_id}/{pod.task.task_id}"
            self.metrics.displaced_tasks.append((self._now, key))
            self._push(self._now + timing.cleanup_delay,
                       EventKind.DELETE, (pod.uid,))
            if pod.workflow_id in self._failed_workflows:
                continue
            self._displaced_at.setdefault(key, self._now)
            heal_task = pod.task
            if pod.resized:
                # A resized pod re-enters admission at its *current*
                # quota, not the stale declared request — the vertical
                # controller's sizing survives displacement.
                heal_task = dataclasses.replace(
                    heal_task,
                    cpu=max(pod.quota.cpu, heal_task.min_cpu),
                    mem=max(pod.quota.mem, heal_task.min_mem))
            self._push(self._now + timing.restart_delay, EventKind.HEAL,
                       (pod.workflow_id, heal_task))

    def _node_up(self, node: int) -> None:
        """Injected NODE_UP: restore the node, retry against it.

        The same-time RETRY sorts after NODE_UP (kind order), so pending
        tasks decide against the recovered capacity immediately.
        """
        if not self.cluster.set_node_up(node):  # was not offline
            return
        self.metrics.node_events.append((self._now, node, "up"))
        self._sample_usage()
        self._push(self._now, EventKind.RETRY, ())

    def _oom_storm(self, victims: int) -> None:
        """Injected OOM_STORM: force-OOM the longest-running pods.

        Victims are the lowest-uid Running pods — creation order, so the
        choice is deterministic for a seeded run.  Each goes through the
        ordinary ``_oom`` self-healing path; its still-queued COMPLETE
        event goes stale and is dropped by the guard.
        """
        running = sorted(uid for uid, pod in self.cluster.pods.items()
                         if pod.phase is PodPhase.RUNNING)
        for uid in running[:victims]:
            # forced: storm pressure is beyond the quota's control, so
            # the resize-first rescue never applies — the victim dies.
            self._oom(uid, self.cluster.pods[uid].workflow_id, forced=True)

    def _wf_deadline(self, wf_id: str) -> None:
        """Per-workflow deadline check: incomplete -> FAILED outcome."""
        run = self.runs.get(wf_id)
        if run is None or run.complete \
                or wf_id in self._failed_workflows:
            return
        self._fail_workflow(wf_id, "deadline")

    def _fail_workflow(self, wf_id: str, reason: str) -> None:
        """Terminate a workflow as a FAILED outcome (graceful degradation).

        Its queued tasks leave the pending queue, its Running pods are
        killed (``FAILED`` + cleanup), and its unfinished task records go
        numerically inert via ``mark_done`` so the allocator's demand
        window no longer prices them in.  The workflow is *not* added to
        ``workflow_durations`` — completed-workflow statistics stay
        completed-only; it is counted on ``metrics.failed_workflows``.
        """
        self._failed_workflows.add(wf_id)
        self.metrics.failed_workflows.append((self._now, wf_id, reason))
        if self._pending:
            self._pending = deque(
                (w, t) for w, t in self._pending if w != wf_id)
        victims = [pod for pod in self.cluster.pods.values()
                   if pod.workflow_id == wf_id
                   and pod.phase is PodPhase.RUNNING]
        for pod in victims:
            self.cluster.finish(pod.uid, self._now, PodPhase.FAILED)
            self._push(self._now + self.cfg.timing.cleanup_delay,
                       EventKind.DELETE, (pod.uid,))
        run = self.runs[wf_id]
        run.finished_at = self._now
        for tid in run.spec.tasks:
            if tid not in run.done:
                self.store.mark_done(f"{wf_id}/{tid}", self._now)
        if victims:
            self._sample_usage()
            # Freed capacity: let the pending queue retry against it.
            self._push(self._now, EventKind.RETRY, ())

    # ------------------------------------------------------------ run loop
    def _event_stale(self, event: Event) -> bool:
        """Queued events whose subject already terminated are no-ops.

        They are dropped *before* the clock advances, so a trailing
        deadline check for a long-completed workflow, a COMPLETE for a
        chaos-killed pod, or a backoff retry with nothing left pending
        cannot inflate the makespan (only consulted when faults are
        configured — without them no event ever goes stale).
        """
        kind = event.kind
        if kind is EventKind.COMPLETE or kind is EventKind.OOM:
            return self._stale(event.payload[0])
        if kind is EventKind.WF_DEADLINE:
            wf_id = event.payload[0]
            run = self.runs.get(wf_id)
            return run is None or run.complete \
                or wf_id in self._failed_workflows
        if kind is EventKind.RETRY and event.payload == ("backoff",):
            return not self._pending
        return False

    def step(self) -> Event:
        """Pop and process the next event; returns the processed head.

        An allocatable head (retry/ready/heal) drains its whole
        ``batch_window`` of follow-on requests in the same step — see
        ``_drain_group``.  Exposed so harnesses (benchmarks, tests) can
        drive the engine event by event instead of to completion.
        """
        if not self.queue:
            raise RuntimeError("step() on an empty event queue — guard "
                               "the loop with `while engine.queue: ...`")
        event = self.queue.pop()
        if self._chaos_on and self._event_stale(event):
            return event
        if event.kind is EventKind.RESIZE and not self._any_resizable():
            # Quiescent controller: drop the sweep *before* the clock
            # advances (a trailing RESIZE must not stretch the makespan)
            # and disarm — the next usage-curve bind re-arms it.
            self._resize_armed = False
            return event
        if event.t > self.cfg.timing.max_time:
            raise RuntimeError("simulation exceeded max_time — deadlock?")
        self._now = event.t
        if self._t_first is None:
            self._t_first = event.t
        if event.kind is EventKind.INJECT:
            self._inject(*event.payload)
        elif event.kind is EventKind.COMPLETE:
            self._complete(*event.payload)
        elif event.kind is EventKind.OOM:
            self._oom(*event.payload)
        elif event.kind is EventKind.OOM_STORM:
            self._oom_storm(*event.payload)
        elif event.kind is EventKind.DELETE:
            self.cluster.delete(*event.payload)
        elif event.kind is EventKind.NODE_DOWN:
            self._node_down(*event.payload)
        elif event.kind is EventKind.NODE_UP:
            self._node_up(*event.payload)
        elif event.kind is EventKind.WF_DEADLINE:
            self._wf_deadline(*event.payload)
        elif event.kind is EventKind.RESIZE:
            self._resize_tick()
        else:  # RETRY / READY / HEAL
            self._drain_group(event)
        return event

    def finalize(self) -> EngineMetrics:
        """Deadlock check + final metrics — the epilogue of ``run()``.

        Public so harnesses that drive ``step()`` themselves (the
        streaming engine, benchmarks) finish a drained run identically
        to ``run()``.
        """
        incomplete = [w for w, r in self.runs.items()
                      if not r.complete and w not in self._failed_workflows]
        if incomplete or self._pending:
            raise RuntimeError(
                f"deadlocked workflows: {incomplete}, pending={len(self._pending)}"
            )
        self._sample_usage()
        total = self._now - (self._t_first or 0.0)
        self.metrics.makespan = total
        if total > 0:
            self.metrics.avg_cpu_usage = float(self._util_integral[0] / total)
            self.metrics.avg_mem_usage = float(self._util_integral[1] / total)
        return self.metrics

    def run(self) -> EngineMetrics:
        while self.queue:
            self.step()
            if self.cfg.invariant_checks:
                self.cluster.check_invariants()
        return self.finalize()


def run_experiment(
    workflow_kind: str,
    pattern: List[Tuple[float, int]],
    allocator: str,
    seed: int = 0,
    config: Optional[EngineConfig] = None,
    task_kwargs: Optional[dict] = None,
) -> EngineMetrics:
    """Inject `pattern` bursts of `workflow_kind` and run to completion."""
    from repro.workflows.dags import WORKFLOW_BUILDERS

    cfg = (config or EngineConfig()).evolve(allocator=allocator)
    engine = KubeAdaptor(cfg)
    rng = np.random.default_rng(seed)
    builder = WORKFLOW_BUILDERS[workflow_kind]
    idx = 0
    for t, count in pattern:
        for _ in range(count):
            spec = builder(f"{workflow_kind}-{idx}", rng, task_kwargs)
            engine.submit(spec, t)
            idx += 1
    return engine.run()
