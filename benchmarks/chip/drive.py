"""Drive the program's served path: warm-up, then the measured window.

The window drives the public per-event API of the engine
(``KubeAdaptor.submit``, ``step``, ``queue.peek``, ``fold_window``), as
``repro.serving.StreamEngine`` does, but on the wall clock:

* arrivals are submitted once the engine may see them (at or before the
  head event's time plus the fold window), so decisions are exactly
  those of an offline run of the same stream;
* paced drives place simulated time on the wall clock,
  ``wall(t) = w0 + (t - t0) / time_scale``, and wait for each arrival's
  and each step's due time; the bind lag of a pod is the wall time its
  step returned minus ``wall(t_bind)``;
* closed-loop episodes serve the whole stream as fast as they can, on a
  fresh engine each, until every pod is bound;
* set-up serves traffic unpaced (``Driver.run_unpaced``), on a deep copy
  of the window's engine (``Driver.fork``) where the window goes on
  from where set-up left it.

Each bind is recorded from a wrapper around the engine's cluster
``bind`` (pod key, simulated time, node, quota, scenario), which is all
the check needs.  With tracing on, every ``step()`` runs inside a
``TraceAnnotation`` named ``step:<event kind>``, waits for the clock in
``pace_wait`` and engine rebuilds in ``episode_reset``, so the trace
reduction can put host time beside device time.  The window's change in
every integer counter of the engine's ``EngineMetrics`` is summed over
its episodes (``Window.counters``), read between steps and never inside
one.
"""
from __future__ import annotations

import contextlib
import copy
import dataclasses
import heapq
import time
from typing import Dict, List, Optional, Tuple


def to_program(stream):
    """Plain workflows to the program's ``WorkflowSpec`` objects."""
    from repro.core.types import TaskSpec
    from repro.workflows.spec import WorkflowSpec

    out = []
    for t, wf in stream:
        tasks = {name: TaskSpec(task_id=name, image="bench", cpu=task["cpu"],
                                mem=task["mem"], duration=task["duration"],
                                min_cpu=task["min_cpu"],
                                min_mem=task["min_mem"])
                 for name, task in wf["tasks"].items()}
        out.append((t, WorkflowSpec(workflow_id=wf["id"], tasks=tasks,
                                    edges=list(wf["edges"]))))
    return out


@dataclasses.dataclass
class Episode:
    """One engine's life: its binds and what the window saw of it."""

    # key -> (t, node, cpu, mem, scenario), as ``reference.Decision``
    binds: Dict[str, tuple] = dataclasses.field(default_factory=dict)
    order: List[str] = dataclasses.field(default_factory=list)
    horizon: float = 0.0  # last simulated time processed
    complete: bool = False  # every event due by its horizon processed


@dataclasses.dataclass
class Window:
    """What a measured window produced."""

    seconds: float
    lags: List[float] = dataclasses.field(default_factory=list)
    binds_in_window: int = 0
    episodes: List[Episode] = dataclasses.field(default_factory=list)
    dispatches: int = 0
    dispatch_rows: List[int] = dataclasses.field(default_factory=list)
    steps: int = 0
    offered: int = 0  # arrivals due in the window
    submitted: int = 0
    late_s: float = 0.0  # how far behind its due time the loop ran, max
    overrun_s: float = 0.0  # wall time past the close to finish due work
    capped: bool = False  # due work was left undone at the cap
    # The five longest paced steps: (seconds, event kind, simulated
    # time, rows dispatched).
    slowest: List[tuple] = dataclasses.field(default_factory=list)
    # Change in each integer field of ``EngineMetrics`` over the window,
    # summed over its episodes.
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)
    trace: Optional[object] = None  # tracing.Reduction, when traced

    def add_counters(self, eng, before: Dict[str, int]) -> None:
        """Add ``eng``'s counters less ``before`` to the window's."""
        for name, value in counters(eng.metrics).items():
            self.counters[name] = (self.counters.get(name, 0) + value
                                   - before.get(name, 0))


class Driver:
    """Builds engines from one configuration and records their binds."""

    def __init__(self, cfg, tracing: bool = False):
        self.cfg = cfg
        self.tracing = tracing

    def span(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def engine(self, episode: Episode):
        from repro.engine import KubeAdaptor

        eng = KubeAdaptor(self.cfg)
        _record(eng.cluster, episode)
        return eng

    def fork(self, eng, episode: Episode):
        """A deep copy of ``eng`` whose binds go to ``episode``.  The
        original is left as it was: device arrays are copied too, which
        matters because the fused step donates its state."""
        twin = copy.deepcopy(eng)
        # The copy's cluster still holds the original's recording
        # wrapper (functions are not copied); record into ``episode``.
        del twin.cluster.bind
        _record(twin.cluster, episode)
        return twin

    def step(self, eng) -> None:
        if self.tracing:
            with self.span(f"step:{eng.queue.peek().kind.name}"):
                eng.step()
        else:
            eng.step()
        if self.cfg.invariant_checks:
            eng.cluster.check_invariants()

    # ----------------------------------------------------------- unpaced
    def run_unpaced(self, eng, episode: Episode, stream, start: int = 0,
                    horizon: float = float("inf")) -> int:
        """Serve arrivals from ``start`` as fast as the engine goes, up
        to the simulated ``horizon``; returns the index of the first
        arrival not submitted."""
        i = start
        fold = eng.fold_window()
        while True:
            head = eng.queue.peek()
            while i < len(stream) and (head is None
                                       or stream[i][0] <= head.t + fold):
                if stream[i][0] > horizon:
                    break
                eng.submit(stream[i][1], stream[i][0])
                i += 1
                head = eng.queue.peek()
            if head is None or head.t > horizon:
                episode.complete = True
                break
            episode.horizon = head.t
            self.step(eng)
        return i


def _record(cluster, episode: Episode) -> None:
    """Wrap ``cluster.bind`` so that every bind lands in ``episode``."""
    bind = cluster.bind

    def recorded(task, alloc, now, workflow_id=""):
        key = f"{workflow_id}/{task.task_id}"
        # The fields of a ``Decision`` as a plain tuple: the collector
        # stops tracking those, where it would walk every recorded bind
        # of the window in each full collection of the program's heap.
        episode.binds[key] = (now, int(alloc.node), float(alloc.cpu),
                              float(alloc.mem), alloc.scenario)
        episode.order.append(key)
        return bind(task, alloc, now, workflow_id=workflow_id)

    cluster.bind = recorded


def _count(eng) -> Tuple[int, int]:
    return eng.metrics.num_dispatches, eng.metrics.dispatched_rows


def counters(metrics) -> Dict[str, int]:
    """Every integer field of an ``EngineMetrics``."""
    values = {f.name: getattr(metrics, f.name)
              for f in dataclasses.fields(metrics)}
    return {name: v for name, v in values.items() if type(v) is int}


def paced(driver: Driver, eng, episode: Episode, stream, start: int,
          t0: float, scale: float, seconds: float, window: Window,
          cap_s: float = 60.0) -> None:
    """Open loop on the wall clock from simulated time ``t0``.

    Processes every arrival and event due up to ``t0 + seconds * scale``,
    each no earlier than its due wall time; work due in the window that
    is still queued at the close is finished (its lag counts), for at
    most ``cap_s`` seconds past the close.
    """
    horizon = t0 + seconds * scale
    fold = eng.fold_window()
    w0 = time.perf_counter()
    w_end = w0 + seconds
    i = start
    n_binds = len(episode.order)
    before = counters(eng.metrics)
    while True:
        head = eng.queue.peek()
        t_arr = stream[i][0] if i < len(stream) else float("inf")
        t_evt = head.t + fold if head is not None else float("inf")
        submit = t_arr <= t_evt
        t_next = t_arr if submit else t_evt
        if t_next > horizon:
            break
        due = w0 + (t_next - t0) / scale
        now = time.perf_counter()
        if now < due:
            with driver.span("pace_wait"):
                while time.perf_counter() < due:
                    left = due - time.perf_counter()
                    if left > 2e-3:
                        time.sleep(left - 1e-3)
        else:
            window.late_s = max(window.late_s, now - due)
            if now > w_end + cap_s:
                break
        if submit:
            eng.submit(stream[i][1], stream[i][0])
            window.submitted += 1
            i += 1
            continue
        d0, r0 = _count(eng)
        episode.horizon = head.t
        began = time.perf_counter()
        driver.step(eng)
        returned = time.perf_counter()
        d1, r1 = _count(eng)
        window.steps += 1
        if d1 > d0:
            window.dispatches += d1 - d0
            window.dispatch_rows.append(r1 - r0)
        heapq.heappush(window.slowest, (returned - began, head.kind.name,
                                        head.t, r1 - r0))
        if len(window.slowest) > 5:
            heapq.heappop(window.slowest)
        for key in episode.order[n_binds:]:
            t_bind = episode.binds[key][0]
            window.lags.append(returned - (w0 + (t_bind - t0) / scale))
        n_binds = len(episode.order)
    window.overrun_s = max(window.overrun_s, time.perf_counter() - w_end)
    window.add_counters(eng, before)
    window.offered += sum(1 for t, _ in stream[start:] if t <= horizon)
    head = eng.queue.peek()
    if head is not None and head.t <= horizon:
        window.capped = True
    else:
        episode.horizon = horizon
        episode.complete = head is None and i >= len(stream)
    window.binds_in_window = len(window.lags)


def closed(driver: Driver, horizon: float, seconds: float, window: Window,
           stream, cap_s: float = 60.0) -> None:
    """Closed-loop episodes back to back until ``seconds`` have passed.

    An episode serves the stream on a fresh engine, as fast as it goes,
    until every event due by ``horizon`` (the last arrival's time) is
    processed.  Pods bound in steps that returned inside the window
    count, and the engine rebuild between episodes is inside the window.
    If no episode has finished when the window closes, the one running
    is finished (at most ``cap_s`` past the close) so that the check
    has a whole episode; its late binds do not count.
    """
    w0 = time.perf_counter()
    w_end = w0 + seconds
    while time.perf_counter() < w_end:
        episode = Episode()
        window.episodes.append(episode)
        with driver.span("episode_reset"):
            eng = driver.engine(episode)
        before = counters(eng.metrics)
        i = 0
        fold = eng.fold_window()
        n_binds = 0
        while True:
            head = eng.queue.peek()
            while i < len(stream) and (head is None
                                       or stream[i][0] <= head.t + fold):
                eng.submit(stream[i][1], stream[i][0])
                i += 1
                head = eng.queue.peek()
            if head is None or head.t > horizon:
                episode.complete = True
                episode.horizon = horizon
                break
            now = time.perf_counter()
            if now >= w_end and head.t > episode.horizon and (
                    any(e.complete for e in window.episodes)
                    or now >= w_end + cap_s):
                break
            episode.horizon = head.t
            d0, r0 = _count(eng)
            driver.step(eng)
            returned = time.perf_counter()
            d1, r1 = _count(eng)
            window.steps += 1
            if d1 > d0:
                window.dispatches += d1 - d0
                window.dispatch_rows.append(r1 - r0)
            if returned <= w_end:
                window.binds_in_window += len(episode.order) - n_binds
            n_binds = len(episode.order)
        window.add_counters(eng, before)
