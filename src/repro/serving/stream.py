"""Continuous serving loop over the KubeAdaptor engine.

``KubeAdaptor.run()`` is an *offline* driver: every workflow is
submitted up front, then the event loop drains to completion.  A
production docking engine (the ROADMAP's streaming north-star) never
sees the full arrival schedule — submissions keep landing while decided
bursts execute.  :class:`StreamEngine` is that serving mode, built on
the pieces this engine already has:

* **Bounded look-ahead ingestion.**  The pump submits, before each
  ``step()``, exactly the arrivals the engine is entitled to know about:
  everything due at or before the current head event's fold deadline
  (``head.t + batch_window``).  The deadline is re-anchored after every
  submission, because an arrival earlier than the current head becomes
  the head itself.  Results are therefore *identical* to submitting the
  whole schedule up front (``tests/test_incremental_state.py`` holds it
  bit-for-bit): the windowed drain already defines which arrivals a
  decision may fold, and the pump never withholds one inside the window
  nor reveals one beyond it.
* **Double-buffered ingest overlap.**  While a fused dispatch is in
  flight on device, the engine calls back into
  :meth:`StreamEngine._overlap_ingest` (the ``ingest_hook``), which
  pushes a chunk of *future* arrivals into the event queue — host work
  hidden under device compute.  Folding rules are unaffected: those
  arrivals are all beyond the current fold deadline, so they cannot
  join the in-flight decision; they are simply queued earlier.
* **Serving telemetry.**  ``serve()`` returns :class:`StreamStats`
  with sustained decisions/sec over the run's wall time next to the
  usual engine metrics.  Where the time of a step goes is read from a
  profiler trace of the engine's spans (README, "Tracing the
  scheduler").
* **Admission control (backpressure).**  With ``max_pending`` set, the
  pump watches the engine's pending admission queue; while its backlog
  exceeds the bound, new arrivals are *shed* (dropped and counted — the
  AHPA-style graceful degradation) or *deferred* (withheld and
  submitted once the backlog drains, re-timed to the engine clock so
  time never runs backwards), per ``overload_policy``.  Overload then
  produces a measured, bounded queue instead of unbounded growth;
  ``StreamStats`` reports the shed/deferred counts.  Unset (default),
  the pump admits everything — bit-for-bit the offline run.

The stream driver works with any engine configuration; it is fastest
with the device-resident incremental state (``AllocatorConfig.
incremental_state``), where the overlap hook has a real in-flight
dispatch to hide under.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

from repro.engine.kubeadaptor import EngineMetrics, KubeAdaptor
from repro.workflows.spec import WorkflowSpec


@dataclasses.dataclass
class StreamStats:
    """Serving-loop report: throughput + engine metrics."""

    decisions: int  # allocation rows decided (= metrics.dispatched_rows)
    dispatches: int  # fused dispatches issued
    wall_seconds: float  # total serve() wall time
    decisions_per_sec: float  # sustained throughput over the whole run
    overlapped_ingests: int  # arrivals submitted under in-flight dispatches
    shed_workflows: int  # arrivals dropped by admission control
    deferred_workflows: int  # arrivals withheld (at least once) by backlog
    metrics: EngineMetrics  # the usual offline-run metrics

    def to_dict(self) -> Dict[str, float]:
        """Schema-stable summary for benchmark JSON / CI checks."""
        return {
            "decisions": self.decisions,
            "dispatches": self.dispatches,
            "wall_seconds": self.wall_seconds,
            "decisions_per_sec": self.decisions_per_sec,
            "overlapped_ingests": self.overlapped_ingests,
            "shed_workflows": self.shed_workflows,
            "deferred_workflows": self.deferred_workflows,
        }


class StreamEngine:
    """Drive a :class:`KubeAdaptor` against a live arrival stream.

    ``arrivals`` is a time-sorted sequence of ``(t, WorkflowSpec)``; the
    pump feeds them to the engine just in time (see the module
    docstring), so the engine behaves exactly as if it were long-lived
    and submissions arrived from outside.
    """

    def __init__(self, engine: KubeAdaptor,
                 arrivals: Sequence[Tuple[float, WorkflowSpec]],
                 prefetch_chunk: int = 64,
                 max_pending: Optional[int] = None,
                 overload_policy: str = "shed"):
        times = [t for t, _ in arrivals]
        if any(b < a for a, b in zip(times, times[1:])):
            raise ValueError("arrivals must be sorted by time")
        if overload_policy not in ("shed", "defer"):
            raise ValueError(
                f"unknown overload_policy {overload_policy!r} "
                f"(want 'shed' or 'defer')")
        if max_pending is not None and max_pending < 0:
            raise ValueError(f"max_pending must be None (unbounded) or "
                             f">= 0, got {max_pending}")
        self.engine = engine
        self._arrivals: List[Tuple[float, WorkflowSpec]] = list(arrivals)
        self._next = 0  # first arrival not yet submitted
        self._prefetch_chunk = prefetch_chunk
        self._max_pending = max_pending
        self._overload_policy = overload_policy
        self.overlapped_ingests = 0
        self.shed_workflows = 0
        self.deferred_workflows = 0
        self._deferred_seen = 0  # arrivals counted deferred at least once
        engine.ingest_hook = self._overlap_ingest

    # ------------------------------------------------------------ ingestion
    def _backlogged(self) -> bool:
        """Admission control: is the engine's pending queue over bound?"""
        return (self._max_pending is not None
                and len(self.engine._pending) > self._max_pending)

    def _pump(self) -> None:
        """Submit every arrival the next step is entitled to see.

        The fold deadline is re-anchored after each submission: an
        arrival earlier than the current head becomes the head, and its
        own window may entitle the step to further arrivals.

        Under admission control (``max_pending``) an over-bound backlog
        sheds the arrival (dropped + counted) or defers the whole pump
        until the backlog drains — except on an empty event queue, where
        withholding would stall the loop (an empty queue also implies an
        empty pending queue: pending tasks always have a completion or
        retry scheduled, so the backlog check passes there anyway).
        Deferred arrivals whose timestamp the engine has already passed
        are submitted at the engine clock — time never runs backwards.
        """
        engine = self.engine
        while self._next < len(self._arrivals):
            head = engine.queue.peek()
            t, spec = self._arrivals[self._next]
            # The entitlement window is re-read per arrival: with
            # forecasting enabled (EngineConfig.forecast) the engine
            # sizes its fold deadline from the predicted inter-arrival
            # gap, and the pump must grant exactly that look-ahead.
            # Forecast off, this is the static batch_window as before.
            if head is not None and t > head.t + engine.fold_window():
                break
            if self._backlogged():
                if self._overload_policy == "shed":
                    self.shed_workflows += 1
                    self._next += 1
                    continue
                if self._next >= self._deferred_seen:
                    self.deferred_workflows += 1
                    self._deferred_seen = self._next + 1
                break
            # An empty queue (quiescent gap between workload phases)
            # anchors the next period on this arrival itself.
            if self._max_pending is not None:
                t = max(t, engine._now)
            engine.submit(spec, t)
            self._next += 1

    def _overlap_ingest(self) -> None:
        """Queue a chunk of future arrivals under the in-flight dispatch.

        Called by the engine between issuing a fused dispatch and
        blocking on its results.  Every remaining arrival is strictly
        beyond the current fold deadline (``_pump`` already submitted
        everything inside it), so queueing them cannot change the
        decision in flight — this is pure host-side work hidden under
        device compute.  Disabled under admission control: prefetched
        arrivals would bypass the backlog check.
        """
        if self._max_pending is not None:
            return
        end = min(self._next + self._prefetch_chunk, len(self._arrivals))
        for i in range(self._next, end):
            t, spec = self._arrivals[i]
            self.engine.submit(spec, t)
            self.overlapped_ingests += 1
        self._next = end

    # -------------------------------------------------------------- serving
    def serve(self) -> StreamStats:
        """Run the stream to completion; returns the serving report."""
        engine = self.engine
        t_serve0 = time.perf_counter()
        while True:
            self._pump()
            if not engine.queue:
                break  # arrivals exhausted and the event loop drained
            engine.step()
            if engine.cfg.invariant_checks:
                engine.cluster.check_invariants()
        wall = time.perf_counter() - t_serve0
        metrics = engine.finalize()
        return StreamStats(
            decisions=metrics.dispatched_rows,
            dispatches=metrics.num_dispatches,
            wall_seconds=wall,
            decisions_per_sec=(metrics.dispatched_rows / wall
                               if wall > 0 else 0.0),
            overlapped_ingests=self.overlapped_ingests,
            shed_workflows=self.shed_workflows,
            deferred_workflows=self.deferred_workflows,
            metrics=metrics,
        )


def serve_stream(engine: KubeAdaptor,
                 arrivals: Sequence[Tuple[float, WorkflowSpec]],
                 prefetch_chunk: int = 64,
                 max_pending: Optional[int] = None,
                 overload_policy: str = "shed") -> StreamStats:
    """One-call convenience: build a :class:`StreamEngine` and serve."""
    return StreamEngine(engine, arrivals, prefetch_chunk=prefetch_chunk,
                        max_pending=max_pending,
                        overload_policy=overload_policy).serve()
