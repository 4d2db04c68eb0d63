"""Declarative experiment scenarios and the unified runner.

A :class:`Scenario` is the paper's experimental unit made declarative
(AHPA, arXiv:2303.03640, makes the same move for autoscaling
comparisons): a workflow set, an arrival pattern (registry name +
parameters) and an engine configuration, JSON-round-trippable so a sweep
is data, not wiring.  :func:`run_scenario` executes one scenario through
the KubeAdaptor engine and returns a structured :class:`RunResult`
carrying the paper's Table-2 / Fig-9 metrics (avg total duration, avg
per-workflow duration, CPU/mem usage rates, per-decision latency).

The paper grid — 2 allocators × 3 arrival patterns — is then one
declarative sweep::

    base = Scenario(workflows=("ligo",))
    results = [run_scenario(s) for s in grid(base,
                                             allocators=("aras", "fcfs"),
                                             arrivals=("constant", "linear",
                                                       "pyramid"))]

``run_scenario`` with a single workflow kind is injection-for-injection
identical to the legacy ``repro.engine.run_experiment`` (same rng
stream, same workflow ids), which ``tests/test_scenario_api.py`` gates.
"""
from __future__ import annotations

import dataclasses
import inspect
import json
import time
from typing import Any, Dict, List, Mapping, Optional, Tuple

from repro.api.config import EngineConfig
from repro.api.registry import ARRIVALS


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One declarative experiment: workflows × arrival × engine config."""

    name: str = "scenario"
    # Workflow kinds (repro.workflows.dags builders); injections cycle
    # through the set, so a single entry reproduces the paper's
    # one-topology experiments and the full set mixes topologies.
    workflows: Tuple[str, ...] = ("ligo",)
    arrival: str = "constant"  # ARRIVALS registry name
    # Keyword arguments for the arrival builder (e.g. y/bursts/interval).
    arrival_params: Mapping[str, Any] = dataclasses.field(
        default_factory=dict)
    engine: EngineConfig = dataclasses.field(default_factory=EngineConfig)
    seed: int = 0
    # Optional task-shape overrides handed to every non-virtual task
    # builder (repro.workflows.spec.make_task kwargs).
    task_kwargs: Optional[Mapping[str, Any]] = None
    # Serving mode: run the arrival schedule through the streaming loop
    # (repro.serving.StreamEngine — just-in-time pump, optional
    # admission control) instead of submitting everything up front.
    # stream_params are StreamEngine keyword arguments (prefetch_chunk,
    # max_pending, overload_policy); the serving telemetry lands on the
    # RunResult (decisions/sec, shed/deferred counts).
    stream: bool = False
    stream_params: Mapping[str, Any] = dataclasses.field(
        default_factory=dict)
    # Usage-curve declarations per workflow kind (ARC-V, repro.vertical):
    # {"montage": "ramp"} or {"montage": {"curve": "ramp", "params":
    # {"start": 0.9, "end": 0.2}}}.  Every injected workflow of that kind
    # gets the curve stamped onto its non-virtual tasks
    # (repro.vertical.attach_usage) with seeds derived from the scenario
    # seed, so actual consumption diverges from the admitted quota — the
    # signal EngineConfig.vertical's resize controller acts on.
    usage_curves: Optional[Mapping[str, Any]] = None

    # --------------------------------------------------------------- seeds
    def _arrival_args(self) -> Dict[str, Any]:
        """``arrival_params`` with the scenario seed wired into stochastic
        patterns (``stochastic`` capability flag): the one scenario
        ``seed`` then drives workflow shapes *and* arrival times, so a
        ``grid(seeds=...)`` sweep replicates the whole experiment.  An
        explicit ``arrival_params["seed"]`` pins the arrivals instead."""
        params = dict(self.arrival_params)
        if ARRIVALS.get(self.arrival).supports("stochastic"):
            params.setdefault("seed", self.seed)
        return params

    # ---------------------------------------------------------- validation
    def validate(self) -> "Scenario":
        from repro.workflows.dags import WORKFLOW_BUILDERS

        if not self.workflows:
            raise ValueError("Scenario.workflows must name at least one "
                             "workflow kind")
        unknown = [w for w in self.workflows if w not in WORKFLOW_BUILDERS]
        if unknown:
            raise ValueError(
                f"unknown workflow kind(s) {unknown} "
                f"(registered: {', '.join(sorted(WORKFLOW_BUILDERS))})"
            )
        entry = ARRIVALS.get(self.arrival)  # raises with registered names
        try:
            # Signature-bind only: validation must not execute the
            # builder (it may be expensive or stateful) — run_scenario
            # builds the pattern exactly once, via pattern().
            inspect.signature(entry.factory).bind(**self._arrival_args())
        except TypeError as exc:
            raise ValueError(
                f"arrival_params {dict(self.arrival_params)} do not fit "
                f"arrival pattern {self.arrival!r}: {exc}"
            ) from exc
        unknown_stream = sorted(
            set(self.stream_params)
            - {"prefetch_chunk", "max_pending", "overload_policy"})
        if unknown_stream:
            raise ValueError(
                f"unknown stream_params {unknown_stream} (StreamEngine "
                f"accepts prefetch_chunk/max_pending/overload_policy)")
        if self.stream_params and not self.stream:
            raise ValueError("stream_params given but stream=False — set "
                             "stream=True to run the serving loop")
        if self.usage_curves:
            from repro.api.registry import CURVES

            bad_kinds = sorted(set(self.usage_curves) - set(self.workflows))
            if bad_kinds:
                raise ValueError(
                    f"usage_curves name workflow kind(s) {bad_kinds} not in "
                    f"Scenario.workflows {list(self.workflows)}")
            for kind in self.usage_curves:
                curve, params = self._curve_spec(kind)
                entry = CURVES.get(curve)  # raises with registered names
                try:
                    inspect.signature(entry.factory).bind(**params)
                except TypeError as exc:
                    raise ValueError(
                        f"usage_curves[{kind!r}] params {params} do not "
                        f"fit curve {curve!r}: {exc}") from None
        self.engine.validate()
        return self

    def _curve_spec(self, kind: str) -> Tuple[str, Dict[str, Any]]:
        """Normalize one ``usage_curves`` entry to (curve, params)."""
        decl = self.usage_curves[kind]
        if isinstance(decl, str):
            return decl, {}
        decl = dict(decl)
        unknown = sorted(set(decl) - {"curve", "params"})
        if unknown or "curve" not in decl:
            raise ValueError(
                f"usage_curves[{kind!r}] must be a curve name or a "
                f"{{'curve': ..., 'params': {{...}}}} mapping, got {decl}")
        return decl["curve"], dict(decl.get("params") or {})

    # ------------------------------------------------------------ behavior
    def pattern(self) -> List[Tuple[float, int]]:
        """The concrete (time, count) burst list of this scenario."""
        return ARRIVALS.get(self.arrival).factory(**self._arrival_args())

    def num_workflows(self) -> int:
        return sum(count for _, count in self.pattern())

    # --------------------------------------------------------- (de)serial
    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "workflows": list(self.workflows),
            "arrival": self.arrival,
            "arrival_params": dict(self.arrival_params),
            "engine": self.engine.to_dict(),
            "seed": self.seed,
            "task_kwargs": dict(self.task_kwargs)
            if self.task_kwargs is not None else None,
            "stream": self.stream,
            "stream_params": dict(self.stream_params),
            "usage_curves": ({k: (v if isinstance(v, str) else dict(v))
                              for k, v in self.usage_curves.items()}
                             if self.usage_curves is not None else None),
        }

    @classmethod
    def from_dict(cls, data: Dict[str, Any]) -> "Scenario":
        kwargs = dict(data)
        if "workflows" in kwargs:
            workflows = kwargs["workflows"]
            kwargs["workflows"] = ((workflows,)
                                   if isinstance(workflows, str)
                                   else tuple(workflows))
        if "engine" in kwargs:
            kwargs["engine"] = EngineConfig.from_dict(kwargs["engine"])
        return cls(**kwargs)

    def to_json(self, **dumps_kwargs: Any) -> str:
        return json.dumps(self.to_dict(), **dumps_kwargs)

    @classmethod
    def from_json(cls, text: str) -> "Scenario":
        return cls.from_dict(json.loads(text))


def grid(base: Scenario, *,
         allocators: Tuple[str, ...] = ("aras", "fcfs"),
         arrivals: Tuple[str, ...] = ("constant", "linear", "pyramid"),
         seeds: Optional[Tuple[int, ...]] = None,
         fault_params: Optional[Tuple[Mapping[str, Any], ...]] = None,
         ) -> List[Scenario]:
    """The paper's evaluation grid as a flat list of scenarios.

    Every (allocator, arrival) pair of the sweep becomes one scenario
    derived from ``base`` (name suffixed ``-<allocator>-<arrival>``);
    ``base.arrival_params`` apply to every arrival pattern, so pass only
    parameters the swept patterns share (or none for the paper defaults).

    ``seeds`` adds a replication axis (suffix ``-s<seed>``): each seed
    re-draws the workflow task shapes, and — for arrival patterns
    carrying the ``stochastic`` capability flag (``poisson``,
    ``jittered``) — the arrival timestamps too, since the scenario seed
    feeds the arrival builder unless ``arrival_params`` pins one.

    Forecast-capable allocators (the ``forecast`` capability flag, e.g.
    ``adaptive_scaling``) get ``EngineConfig.forecast`` enabled
    automatically when the base engine leaves it off, so
    ``allocators=("aras", "adaptive_scaling")`` sweeps static-vs-
    predictive without a hand-built engine per cell; an explicit
    ``base.engine.forecast`` is kept as-is for every cell.

    ``fault_params`` adds a chaos axis (suffix ``-f<i>``): each entry is
    a parameter-override mapping merged over the base engine's
    ``FaultConfig.params`` — so recovery-time sweeps are one call::

        grid(base, fault_params=tuple({"recovery_time": r}
                                      for r in (60.0, 120.0, 300.0)))

    with ``base.engine`` carrying ``fault_schedule="node_flap"``.  The
    merged params must fit the schedule's signature; an override that
    does not (e.g. ``recovery_time`` against the default ``none``
    schedule) fails the scenario's ``validate()`` with the signature in
    the message.
    """
    from repro.api.registry import ALLOCATORS

    def _engine_for(algorithm: str,
                    overrides: Optional[Mapping[str, Any]]) -> EngineConfig:
        engine = base.engine.evolve(allocator=algorithm)
        if ALLOCATORS.get(algorithm).supports("forecast") \
                and not engine.forecast.enabled:
            engine = engine.evolve(forecast=True)
        if overrides is not None:
            engine = engine.evolve(fault_params={
                **dict(engine.faults.params), **dict(overrides)})
        return engine

    seed_axis: Tuple[Optional[int], ...] = \
        (None,) if seeds is None else tuple(seeds)
    fault_axis: Tuple[Optional[Mapping[str, Any]], ...] = \
        (None,) if fault_params is None else tuple(fault_params)
    return [
        dataclasses.replace(
            base,
            name=(f"{base.name}-{algorithm}-{arrival}"
                  + ("" if seed is None else f"-s{seed}")
                  + ("" if overrides is None else f"-f{fi}")),
            arrival=arrival,
            engine=_engine_for(algorithm, overrides),
            seed=base.seed if seed is None else seed,
        )
        for algorithm in allocators
        for arrival in arrivals
        for seed in seed_axis
        for fi, overrides in enumerate(fault_axis)
    ]


@dataclasses.dataclass
class RunResult:
    """Structured outcome of one scenario — §6.1.5 metrics + trace.

    The scalar fields are the paper's comparison metrics (Table 2 /
    Fig. 9) and JSON-serialize via :meth:`to_dict`; ``metrics`` keeps the
    full :class:`repro.engine.EngineMetrics` trace (usage series,
    allocation trace, OOM events) for plotting and is deliberately left
    out of the serialized form.
    """

    scenario: Scenario
    avg_total_duration: float  # makespan: Total Duration of All Workflows
    avg_workflow_duration: float
    cpu_usage_rate: float  # time-weighted quota / allocatable
    mem_usage_rate: float
    per_decision_latency_us: float
    num_workflows: int
    num_allocations: int
    num_waits: int
    num_oom_events: int
    num_reallocations: int
    # Dispatch efficiency of the windowed drain (TimingConfig.batch_window):
    # how many device dispatches the allocation path issued and the mean
    # task rows per dispatch — a wider mean burst at fewer dispatches is
    # the win of folding jittered arrivals into one fused MAPE-K cycle.
    num_dispatches: int
    mean_burst_width: float
    sla_violation_rate: float
    wall_time_s: float
    # Fault injection + graceful degradation (EngineConfig.faults):
    # displaced = running pods lost to NODE_DOWN, recovered = displaced
    # tasks that re-bound via HEAL, failed = retry-budget/deadline
    # terminations (FAILED outcomes; failed workflows do not count in
    # num_workflows, which stays completed-only).
    num_displaced: int = 0
    num_recovered: int = 0
    num_failed_tasks: int = 0
    num_failed_workflows: int = 0
    mean_time_to_recovery: float = 0.0
    # Forecast telemetry (EngineConfig.forecast / repro.forecast):
    # arrivals observed, drains sized by a live prediction, the mean
    # adaptive fold window they used, and burst decisions that priced a
    # ghost forecast-demand record (adaptive_scaling allocator).
    forecast_observations: int = 0
    forecast_predictions: int = 0
    mean_forecast_window: float = 0.0
    forecast_ghost_rows: int = 0
    # Vertical adaptivity telemetry (EngineConfig.vertical /
    # repro.vertical): in-place resizes, shrink-reclaimed capacity
    # integrated over the pods' remaining lifetimes (millicore·s /
    # MiB·s), and OOM kills the resize-first policy avoided.
    num_resizes: int = 0
    num_shrinks: int = 0
    num_grows: int = 0
    resizes_avoided_oom: int = 0
    reclaimed_cpu_seconds: float = 0.0
    reclaimed_mem_seconds: float = 0.0
    # Serving telemetry (Scenario.stream=True): StreamStats wired in so
    # grid() sweeps can gate on serving throughput, not just makespan.
    decisions_per_sec: float = 0.0
    shed_workflows: int = 0
    deferred_workflows: int = 0
    metrics: Any = dataclasses.field(repr=False, compare=False, default=None)

    def to_dict(self) -> Dict[str, Any]:
        out = {
            field.name: getattr(self, field.name)
            for field in dataclasses.fields(self)
            if field.name not in ("scenario", "metrics")
        }
        out["scenario"] = self.scenario.to_dict()
        return out

    def to_json(self, **dumps_kwargs: Any) -> str:
        return json.dumps(self.to_dict(), **dumps_kwargs)


def run_scenario(scenario: Scenario) -> RunResult:
    """Validate, execute and summarize one scenario.

    Workflows are injected with the same rng stream and id scheme as the
    legacy ``run_experiment`` (``<kind>-<index>`` against one
    ``default_rng(seed)``), so a single-kind scenario reproduces it bit
    for bit; multi-kind scenarios cycle the workflow set per injection.
    """
    import numpy as np

    from repro.engine.kubeadaptor import KubeAdaptor
    from repro.workflows.dags import WORKFLOW_BUILDERS

    scenario.validate()
    engine = KubeAdaptor(scenario.engine)
    rng = np.random.default_rng(scenario.seed)
    task_kwargs = dict(scenario.task_kwargs) if scenario.task_kwargs else None
    arrivals = []
    idx = 0
    for t, count in scenario.pattern():
        for _ in range(count):
            kind = scenario.workflows[idx % len(scenario.workflows)]
            spec = WORKFLOW_BUILDERS[kind](f"{kind}-{idx}", rng, task_kwargs)
            if scenario.usage_curves and kind in scenario.usage_curves:
                from repro.vertical import attach_usage

                curve, params = scenario._curve_spec(kind)
                # Per-injection seed: seeded curves (bursty) differ
                # across workflows but replay bit for bit per scenario.
                spec = attach_usage(spec, curve, params,
                                    seed=scenario.seed * 1_000_003 + idx)
            arrivals.append((t, spec))
            idx += 1
    stats = None
    if scenario.stream:
        from repro.serving.stream import StreamEngine

        server = StreamEngine(engine, arrivals,
                              **dict(scenario.stream_params))
        t0 = time.perf_counter()
        stats = server.serve()
        wall = time.perf_counter() - t0
        metrics = stats.metrics
    else:
        for t, spec in arrivals:
            engine.submit(spec, t)
        t0 = time.perf_counter()
        metrics = engine.run()
        wall = time.perf_counter() - t0
    decisions = max(metrics.num_allocations, 1)
    return RunResult(
        scenario=scenario,
        avg_total_duration=metrics.makespan,
        avg_workflow_duration=metrics.avg_workflow_duration,
        cpu_usage_rate=metrics.avg_cpu_usage,
        mem_usage_rate=metrics.avg_mem_usage,
        per_decision_latency_us=1e6 * wall / decisions,
        num_workflows=len(metrics.workflow_durations),
        num_allocations=metrics.num_allocations,
        num_waits=metrics.num_waits,
        num_oom_events=len(metrics.oom_events),
        num_reallocations=len(metrics.realloc_events),
        num_dispatches=metrics.num_dispatches,
        mean_burst_width=metrics.mean_burst_width,
        sla_violation_rate=metrics.sla_violation_rate,
        wall_time_s=wall,
        num_displaced=metrics.num_displaced,
        num_recovered=metrics.num_recovered,
        num_failed_tasks=len(metrics.failed_tasks),
        num_failed_workflows=len(metrics.failed_workflows),
        mean_time_to_recovery=metrics.mean_time_to_recovery,
        forecast_observations=metrics.forecast_observations,
        forecast_predictions=metrics.forecast_predictions,
        mean_forecast_window=metrics.mean_forecast_window,
        forecast_ghost_rows=metrics.forecast_ghost_rows,
        num_resizes=metrics.num_resizes,
        num_shrinks=metrics.num_shrinks,
        num_grows=metrics.num_grows,
        resizes_avoided_oom=metrics.resizes_avoided_oom,
        reclaimed_cpu_seconds=metrics.reclaimed_cpu_seconds,
        reclaimed_mem_seconds=metrics.reclaimed_mem_seconds,
        decisions_per_sec=stats.decisions_per_sec if stats else 0.0,
        shed_workflows=stats.shed_workflows if stats else 0,
        deferred_workflows=stats.deferred_workflows if stats else 0,
        metrics=metrics,
    )


def run_grid(scenarios: List[Scenario]) -> List[RunResult]:
    """Run a list of scenarios (e.g. from :func:`grid`), in order."""
    return [run_scenario(s) for s in scenarios]
