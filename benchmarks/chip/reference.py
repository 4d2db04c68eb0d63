"""Plain reference of the scheduler's semantics, and the check built on it.

This module imports nothing of the program under test.  It re-states,
in plain Python and NumPy, what the served path is specified to do for
the deployments this benchmark runs:

* the discrete-event engine of KubeAdaptor (paper Fig. 2): workflow
  injection with planned start times (critical-path earliest starts),
  DAG readiness, pod start-up, run and clean-up delays, completion that
  frees the quota and retries the pending queue;
* the drain at a zero fold window: every retry/ready request due at the
  head event's time is decided as one burst, pending retries first in
  FIFO order with head-of-line blocking, then new requests in event
  order;
* ARAS (arXiv 2301.08409, Alg. 1-3): the in-window competing demand of
  each request (records not done, other than its own, whose start lies
  in ``[now, now + duration)``), the cluster's total residual, the node
  with the largest CPU residual, the evaluator's four scenarios with
  Eq. 9's proportional cut and the alpha guard, and the acceptance
  floor ``quota >= min`` (memory: ``min + beta``);
* worst-fit placement: the fitting node with the largest CPU residual,
  lowest index on ties;
* the cluster's books: a float64 ledger of quotas per node, the float32
  residual each decision reads (debited by each grant, re-derived from
  the ledger on each release), and no node above its allocatable;
* a federation of ``num_clusters`` K clusters pooled behind one
  scheduler: K contiguous clusters partition the node table in order,
  as even as possible, the first clusters taking the remainder, and
  node ids are global.  Placement is worst fit over every node of the
  federation (lowest global id on ties), and ARAS's total residual is
  the federation's.  The program sums it per cluster and folds; the
  reference sums in its own order, which the quota gap allows for (the
  same float32 reassociation as within one cluster).  ``sharding`` only
  lays the clusters out over devices and is not read.  At K = 1 this is
  the single cluster above.

``Reference.follow`` checks a program's decisions one by one.  At every
request the reference decides from its own state and compares the
program's answer for that pod: whether it was bound at this time, on
which node, with which quota and scenario.  It then carries on from the
program's answer, so a fault is counted where it happens and does not
hide or multiply later ones.  Quotas are compared by their relative gap
(the program sums the demand and the residual in float32 and in another
order); everything else exactly, except a decision whose inputs sit
within ``AMBIGUOUS`` of one of the evaluator's thresholds, which is
counted apart.

``Reference.decide_all`` runs the same semantics standalone; with
``dtype="bfloat16"`` every stored quantity and every sum is rounded to
bfloat16, which is the control: the reference computed one precision
below the float32 that the configurations state.
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
from collections import deque
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

# Event kinds in heap order at equal times (the engine's documented
# order): releases before retries before arrivals before ready requests.
COMPLETE, DELETE, RETRY, INJECT, READY = 0, 3, 8, 9, 10

# Relative distance to an evaluator threshold inside which float32
# rounding in another summation order may take either branch.
AMBIGUOUS = 1e-5
# Fit slack of the placement rule (a quota fits where residual >= quota
# minus this), as the deployment's scheduler states it.
FIT_EPS = 1e-6
# A node may exceed its allocatable by at most this much (float32
# residuals read a few ulps above the float64 ledger).
OVERCOMMIT_EPS = 0.5

SCENARIOS = ("sufficient", "cpu_insufficient", "mem_insufficient",
             "both_insufficient")


def _dtype(name: str):
    if name == "float32":
        return np.float32
    if name == "bfloat16":
        import ml_dtypes

        return ml_dtypes.bfloat16
    raise ValueError(f"unknown reference dtype {name!r}")


class Decision(NamedTuple):
    """One bind: the answer a scheduler gives for one pod.  A tuple of
    plain numbers, which the garbage collector stops tracking, so that a
    window's record of binds adds nothing to the program's collections."""

    t: float
    node: int
    cpu: float
    mem: float
    scenario: str


@dataclasses.dataclass
class Readings:
    """What a check compares, plus what it saw."""

    decisions: int = 0  # pods bound by the program and checked
    mismatches: int = 0  # decisions that differ from the reference
    missing: int = 0  # pods the reference binds that the program never did
    quota_gap: float = 0.0  # widest relative quota gap of a bound pod
    overcommits: int = 0  # binds that took a node above its allocatable
    ambiguous: int = 0  # differing decisions at a threshold (not counted)
    first_mismatch: str = ""

    def miss(self, what: str) -> None:
        self.mismatches += 1
        if not self.first_mismatch:
            self.first_mismatch = what


class Reference:
    """The deployment's semantics over plain workload data.

    ``config`` is the ``engine`` block of a configuration file;
    ``arrivals`` is a time-sorted list of ``(t, workflow)`` with
    workflows as the traffic generator makes them.
    """

    def __init__(self, config: dict, arrivals, dtype: str = "float32"):
        cluster, alloc, timing = (config["cluster"], config["alloc"],
                                  config["timing"])
        if alloc.get("algorithm", "aras") != "aras":
            raise ValueError("the reference states ARAS only")
        if alloc.get("placement", "worst_fit") != "worst_fit":
            raise ValueError("the reference states worst-fit placement only")
        if float(timing.get("batch_window", 0.0)) != 0.0:
            raise ValueError("the reference states a zero fold window only")
        if config.get("faults", {}).get("schedule", "none") != "none" \
                or config.get("forecast", {}).get("enabled") \
                or config.get("vertical", {}).get("enabled"):
            raise ValueError("the reference states no faults, forecast or "
                             "vertical resizing")
        self.dt = _dtype(dtype)
        self.lowp = dtype != "float32"
        m = int(cluster["num_nodes"])
        k = int(cluster.get("num_clusters", 1))
        if not 1 <= k <= m:
            raise ValueError(f"{k} clusters cannot partition {m} nodes")
        self.cap_cpu = np.full(m, float(cluster["node_cpu"]))
        self.cap_mem = np.full(m, float(cluster["node_mem"]))
        self.used_cpu = np.zeros(m)
        self.used_mem = np.zeros(m)
        self.res_cpu = self.cap_cpu.astype(self.dt)
        self.res_mem = self.cap_mem.astype(self.dt)
        self.alpha = float(alloc["alpha"])
        self.beta = float(alloc["beta"])
        self.startup = float(timing["pod_startup_delay"])
        self.cleanup = float(timing["cleanup_delay"])
        self.mult = float(timing["duration_multiplier"])
        # Knowledge base: one record per task, float32 like its spec.
        cap = 1024
        self.rec_t = np.zeros(cap, np.float32)
        self.rec_cpu = np.zeros(cap, np.float32)
        self.rec_mem = np.zeros(cap, np.float32)
        self.rec_done = np.ones(cap, bool)
        self.slots: Dict[str, int] = {}
        self.heap: List[tuple] = []
        self.seq = itertools.count()
        self.pending: deque = deque()
        self.runs: Dict[str, dict] = {}
        self.pods: Dict[int, tuple] = {}
        self.uid = itertools.count()
        self.now = 0.0
        self.bound: Dict[str, Decision] = {}
        for t, wf in arrivals:
            self._push(t, INJECT, (wf,))

    # ---------------------------------------------------------- plumbing
    def _push(self, t: float, kind: int, payload: tuple) -> None:
        heapq.heappush(self.heap, (t, kind, next(self.seq), payload))

    def _round(self, x: float) -> float:
        return float(self.dt(x))

    def _record(self, key: str, t_start: float, cpu: float,
                mem: float) -> None:
        n = len(self.slots)
        if n == self.rec_t.shape[0]:
            grow = n
            self.rec_t = np.concatenate([self.rec_t, np.zeros(grow,
                                                              np.float32)])
            self.rec_cpu = np.concatenate([self.rec_cpu,
                                           np.zeros(grow, np.float32)])
            self.rec_mem = np.concatenate([self.rec_mem,
                                           np.zeros(grow, np.float32)])
            self.rec_done = np.concatenate([self.rec_done,
                                            np.ones(grow, bool)])
        self.slots[key] = n
        self.rec_t[n] = t_start
        self.rec_cpu[n] = cpu
        self.rec_mem[n] = mem
        self.rec_done[n] = False

    # ------------------------------------------------------------ events
    def _inject(self, wf: dict) -> None:
        tasks, edges = wf["tasks"], wf["edges"]
        parents: Dict[str, List[str]] = {name: [] for name in tasks}
        children: Dict[str, List[str]] = {name: [] for name in tasks}
        indeg = {name: 0 for name in tasks}
        for a, b in edges:
            parents[b].append(a)
            children[a].append(b)
            indeg[b] += 1
        # Planned starts: earliest start along the critical path.
        order, ready = [], sorted(n for n, d in indeg.items() if d == 0)
        deg = dict(indeg)
        while ready:
            n = ready.pop(0)
            order.append(n)
            for c in children[n]:
                deg[c] -= 1
                if deg[c] == 0:
                    ready.append(c)
            ready.sort()
        est: Dict[str, float] = {}
        for n in order:
            est[n] = (self.now if not parents[n] else
                      max(est[p] + tasks[p]["duration"] for p in parents[n]))
        for name, task in tasks.items():
            self._record(f"{wf['id']}/{name}", est[name], task["cpu"],
                         task["mem"])
        self.runs[wf["id"]] = {"wf": wf, "indeg": indeg,
                               "children": children, "done": set()}
        for name in tasks:
            if indeg[name] == 0:
                self._push(self.now, READY, (wf["id"], name))

    def _task_done(self, wf_id: str, name: str) -> None:
        run = self.runs[wf_id]
        self.rec_done[self.slots[f"{wf_id}/{name}"]] = True
        run["done"].add(name)
        for c in run["children"][name]:
            run["indeg"][c] -= 1
            if run["indeg"][c] == 0:
                self._push(self.now, READY, (wf_id, c))

    def _complete(self, uid: int) -> None:
        wf_id, name, node, cpu, mem = self.pods.pop(uid)
        self.used_cpu[node] -= cpu
        self.used_mem[node] -= mem
        # A release re-derives the node's residual from the ledger.
        self.res_cpu[node] = self.dt(self.cap_cpu[node] - self.used_cpu[node])
        self.res_mem[node] = self.dt(self.cap_mem[node] - self.used_mem[node])
        self._push(self.now + self.cleanup, DELETE, (uid,))
        self._task_done(wf_id, name)
        self._push(self.now, RETRY, ())

    def _bind(self, wf_id: str, name: str, task: dict, d: Decision,
              readings: Optional[Readings]) -> None:
        node = d.node
        if (self.used_cpu[node] + d.cpu > self.cap_cpu[node] + OVERCOMMIT_EPS
                or self.used_mem[node] + d.mem
                > self.cap_mem[node] + OVERCOMMIT_EPS):
            if readings is not None:
                readings.overcommits += 1
            elif not self.lowp:
                raise RuntimeError(f"reference overcommitted node {node}")
        self.used_cpu[node] += d.cpu
        self.used_mem[node] += d.mem
        self.res_cpu[node] = self.dt(float(self.res_cpu[node])
                                     - float(self.dt(d.cpu)))
        self.res_mem[node] = self.dt(float(self.res_mem[node])
                                     - float(self.dt(d.mem)))
        key = f"{wf_id}/{name}"
        self.rec_t[self.slots[key]] = np.float32(self.now)
        self.bound[key] = d
        uid = next(self.uid)
        self.pods[uid] = (wf_id, name, node, d.cpu, d.mem)
        if d.mem < task["min_mem"] + self.beta - 1e-9:
            raise RuntimeError("a grant below the runtime floor would OOM; "
                               "the reference does not state OOM handling")
        wall = self.mult * task["duration"]
        self._push(self.now + self.startup + wall, COMPLETE, (uid,))

    # ---------------------------------------------------------- decisions
    def _demand(self, key: str, task: dict) -> Tuple[float, float]:
        """Alg. 1 lines 4-13: own request plus in-window competitors."""
        now32 = np.float32(self.now)
        end32 = np.float32(self.now + task["duration"])
        n = len(self.slots)
        t = self.rec_t[:n]
        w = (t >= now32) & (t < end32) & ~self.rec_done[:n]
        w[self.slots[key]] = False
        if self.lowp:
            c = self.rec_cpu[:n][w].astype(self.dt)
            mm = self.rec_mem[:n][w].astype(self.dt)
            return (float(self.dt(task["cpu"]) + np.sum(c, dtype=self.dt)),
                    float(self.dt(task["mem"]) + np.sum(mm, dtype=self.dt)))
        return (task["cpu"] + float(np.sum(self.rec_cpu[:n][w],
                                           dtype=np.float64)),
                task["mem"] + float(np.sum(self.rec_mem[:n][w],
                                           dtype=np.float64)))

    def _evaluate(self, key: str, task: dict):
        """Alg. 3 with Eq. 9; returns (cpu, mem, scenario, margin)."""
        req_c, req_m = self._demand(key, task)
        req_c, req_m = max(req_c, 1e-9), max(req_m, 1e-9)
        if self.lowp:
            tot_c = float(np.sum(self.res_cpu, dtype=self.dt))
            tot_m = float(np.sum(self.res_mem, dtype=self.dt))
        else:
            tot_c = float(np.sum(self.res_cpu, dtype=np.float64))
            tot_m = float(np.sum(self.res_mem, dtype=np.float64))
        imax = int(np.argmax(self.res_cpu))
        rmax_c = float(self.res_cpu[imax])
        rmax_m = float(self.res_mem[imax])
        t_c = self._round(task["cpu"])
        t_m = self._round(task["mem"])
        cut_c = self._round(t_c * tot_c / req_c)
        cut_m = self._round(t_m * tot_m / req_m)
        a1, a2 = req_c < tot_c, req_m < tot_m
        b1, b2 = t_c < rmax_c, t_m < rmax_m
        c1, c2 = cut_c < rmax_c, cut_m < rmax_m
        guard_c = self._round(rmax_c * self.alpha)
        guard_m = self._round(rmax_m * self.alpha)
        if a1:
            cpu = t_c if b1 else guard_c
        else:
            cpu = (cut_c if c1 else guard_c) if a2 else cut_c
        if a2:
            mem = t_m if b2 else guard_m
        else:
            mem = (cut_m if c2 else guard_m) if a1 else cut_m
        scenario = SCENARIOS[(0 if a1 else 1) + (0 if a2 else 2)]

        def rel(x, y):
            return abs(x - y) / max(abs(y), 1.0)

        margin = min(rel(req_c, tot_c), rel(req_m, tot_m), rel(t_c, rmax_c),
                     rel(t_m, rmax_m), rel(cut_c, rmax_c),
                     rel(cut_m, rmax_m), rel(cpu, task["min_cpu"]),
                     rel(mem, task["min_mem"] + self.beta))
        return cpu, mem, scenario, margin

    def _place(self, cpu: float, mem: float) -> int:
        """Worst fit: the fitting node with the most CPU left, or -1."""
        c = float(self.dt(cpu)) - FIT_EPS
        m = float(self.dt(mem)) - FIT_EPS
        fits = (self.res_cpu >= self.dt(c)) & (self.res_mem >= self.dt(m))
        if not fits.any():
            return -1
        key = np.where(fits, self.res_cpu.astype(np.float64), -np.inf)
        return int(np.argmax(key))

    def _allocate(self, entries: list, include_pending: bool,
                  prog: Optional[Dict[str, Decision]],
                  readings: Optional[Readings]) -> None:
        if include_pending:
            entries = [(w, n, "pending") for w, n in self.pending] + entries
        kept: deque = deque()
        failed = []
        blocked = False
        for wf_id, name, origin in entries:
            task = self.runs[wf_id]["wf"]["tasks"][name]
            key = f"{wf_id}/{name}"
            is_pending = origin == "pending"
            attempt = not (is_pending and blocked)
            if attempt:
                cpu, mem, scenario, margin = self._evaluate(key, task)
                ok = (cpu >= task["min_cpu"]
                      and mem >= task["min_mem"] + self.beta)
                node = self._place(cpu, mem) if ok else -1
            else:  # behind a blocked head of line: not even evaluated
                cpu = mem = margin = 0.0
                scenario, ok, node = "", False, -1
            accept = attempt and ok and node >= 0
            mine = Decision(self.now, node, cpu, mem, scenario)
            if prog is None:
                outcome = mine if accept else None
            else:
                outcome = self._compare(key, task, attempt, ok, accept, mine,
                                        margin, prog, readings)
            if outcome is not None:
                self._bind(wf_id, name, task, outcome, readings)
            else:
                if is_pending and attempt:
                    blocked = True
                (kept if is_pending else failed).append((wf_id, name))
        if include_pending:
            kept.extend(failed)
            self.pending = kept
        else:
            self.pending.extend(failed)

    def _compare(self, key, task, attempt, ok, accept, mine: Decision,
                 margin, prog, readings: Readings) -> Optional[Decision]:
        """Judge the program's answer for one request; return what the
        program did (None: not bound at this time)."""
        theirs = prog.get(key)
        if theirs is not None and theirs.t != self.now:
            theirs_now = None
        else:
            theirs_now = theirs
        if theirs_now is None:
            if accept:
                if margin < AMBIGUOUS:
                    readings.ambiguous += 1
                elif theirs is None:
                    readings.missing += 1
                    if not readings.first_mismatch:
                        readings.first_mismatch = (
                            f"{key}: due bound at {self.now!r}, the program "
                            f"never bound it")
                else:
                    readings.miss(f"{key}: due bound at {self.now!r} on node "
                                  f"{mine.node}, program bound it at "
                                  f"{theirs.t!r}")
            return None
        readings.decisions += 1
        if not attempt:
            readings.miss(f"{key}: bound at {self.now!r} behind a blocked "
                          f"head of line")
            return theirs_now
        if not accept and margin >= AMBIGUOUS:
            readings.miss(f"{key}: bound at {self.now!r}, reference refuses "
                          f"it (quota {mine.cpu!r}/{mine.mem!r})")
            return theirs_now
        gap = max(abs(theirs_now.cpu - mine.cpu) / max(abs(mine.cpu), 1.0),
                  abs(theirs_now.mem - mine.mem) / max(abs(mine.mem), 1.0))
        if theirs_now.scenario != mine.scenario or not accept:
            if margin < AMBIGUOUS:
                readings.ambiguous += 1
            else:
                readings.miss(f"{key}: scenario {theirs_now.scenario} vs "
                              f"{mine.scenario}")
            return theirs_now
        readings.quota_gap = max(readings.quota_gap, gap)
        # Placement is judged for the quota the program granted, on the
        # reference's own residuals.
        expect = self._place(theirs_now.cpu, theirs_now.mem)
        if theirs_now.node != expect:
            readings.miss(f"{key}: node {theirs_now.node} vs {expect} at "
                          f"{self.now!r}")
        return theirs_now

    # -------------------------------------------------------------- runs
    def _drain(self, first: tuple, prog, readings) -> None:
        t0 = first[0]
        entries: list = []
        include_pending = False
        event = first
        while event is not None:
            _, kind, _, payload = event
            if kind == RETRY:
                include_pending = True
            else:  # READY
                wf_id, name = payload
                task = self.runs[wf_id]["wf"]["tasks"][name]
                if task["cpu"] == 0 and task["mem"] == 0:
                    self._task_done(wf_id, name)  # virtual entrance/exit
                else:
                    entries.append((wf_id, name, "ready"))
            event = None
            if self.heap and self.heap[0][0] <= t0 \
                    and self.heap[0][1] in (RETRY, READY):
                event = heapq.heappop(self.heap)
        if entries or include_pending:
            self._allocate(entries, include_pending, prog, readings)

    def _step(self, prog, readings) -> None:
        event = heapq.heappop(self.heap)
        t, kind, _, payload = event
        self.now = t
        if kind == INJECT:
            self._inject(*payload)
        elif kind == COMPLETE:
            self._complete(*payload)
        elif kind == DELETE:
            pass
        else:
            self._drain(event, prog, readings)

    def run(self, horizon: float = float("inf"), pods: Optional[int] = None,
            prog: Optional[Dict[str, Decision]] = None,
            readings: Optional[Readings] = None) -> None:
        """Process events up to ``horizon`` (simulated seconds), or until
        ``pods`` pods are bound and the current timestamp is finished."""
        while self.heap and self.heap[0][0] <= horizon:
            if pods is not None and len(self.bound) >= pods \
                    and self.heap[0][0] > self.now:
                break
            self._step(prog, readings)

    # ------------------------------------------------------------ entry
    def decide_all(self, horizon: float = float("inf"),
                   pods: Optional[int] = None) -> Dict[str, Decision]:
        """Run standalone; returns every bind as the program would."""
        self.run(horizon, pods)
        return dict(self.bound)

    def follow(self, prog: Dict[str, Decision],
               horizon: float = float("inf"),
               pods: Optional[int] = None) -> Readings:
        """Check a program's binds (``key -> Decision``, or the same
        five fields as a plain tuple) up to the horizon; binds the
        reference never reaches are mismatches."""
        prog = {key: Decision._make(d) for key, d in prog.items()}
        readings = Readings()
        self.run(horizon, pods, prog=prog, readings=readings)
        for key, d in prog.items():
            if key not in self.bound and d.t <= self.now:
                readings.miss(f"{key}: program bound it at {d.t!r}, the "
                              f"reference never did")
        return readings
