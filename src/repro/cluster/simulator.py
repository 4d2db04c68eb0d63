"""Discrete-event K8s-cluster model.

Stands in for the paper's 6-node testbed (one Master + six 8-core/16 GB
workers, §6.1.1).  The simulator tracks nodes, pods and phases with the
same semantics the ARAS algorithms assume:

* a pod's *quota* (allocated cpu/mem) counts against its node while the pod
  is Pending or Running (Alg. 2 line 8);
* Succeeded / Failed / OOMKilled pods stop consuming but linger until the
  Task Container Cleaner deletes them (paper §4.2), matching the deletion
  latency visible in Fig. 9;
* ``snapshot()`` is the Informer analogue — a cached, consistent view that
  the Resource Discovery reads instead of hitting the API server.

State is struct-of-arrays and **incremental**: node accounting and the
float32 residual cache are mutated in place on ``bind``/``finish``, and
pods live in slot arrays with a free list, so ``snapshot()`` is a flat
array copy instead of a per-call Python rebuild and ``residual_view()``
costs nothing.  ``residual_view`` hands
the allocator the exact float32 arrays the fused burst kernel carries in
its scan, which is what makes batched and per-task decisions bit-for-bit
identical: both see residuals produced by the same sequence of float32
debits.  Pod capacity grows in powers of two so Informer consumers keep
stable JIT shapes (free slots are ``active=False`` and numerically inert).

A fused dispatch's binds may defer their node books (``defer_books`` /
``flush_books``): each ``bind`` then does only the per-pod work and the
dispatch's node, quota and slot writes land in one array update, in bind
order, so the books come out bit for bit as row-at-a-time binds leave
them.

Invariant (checked): at every instant, Σ quotas of consuming pods on a
node ≤ the node's allocatable capacity.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.cluster.federation import FederatedLayout
from repro.core.types import Allocation, ClusterSnapshot, PodPhase, Resources, TaskSpec


@dataclasses.dataclass
class Pod:
    uid: int
    task: TaskSpec
    quota: Resources
    node: int
    phase: PodPhase = PodPhase.PENDING
    t_created: float = 0.0
    t_started: float = 0.0
    t_finished: float = 0.0
    workflow_id: str = ""
    slot: int = -1  # row in the pod arrays
    resized: bool = False  # quota changed in place after admission (ARC-V)


class ClusterSim:
    """Mutable cluster state + capacity accounting.

    ``num_clusters > 1`` runs the simulator in multi-cluster (federated)
    mode: the node table is partitioned into contiguous, as-even-as-
    possible cluster ranges (global node ids are unchanged — cluster *k*
    owns ``cluster_slices[k]``).  All accounting stays global and
    incremental; the sharded views hand the allocator per-cluster slices
    of the same live arrays, so single- and multi-cluster mode see
    residuals produced by the identical sequence of float32 debits.
    """

    def __init__(self, num_nodes: int, node_cpu: float, node_mem: float,
                 num_clusters: int = 1):
        # The partition rule (and its validation) is owned by
        # FederatedLayout.split — one source of truth for the simulator,
        # the allocator tiles and the global_nodes index mapping.
        self._layout = FederatedLayout.split(num_nodes, num_clusters)
        self.num_nodes = num_nodes
        self.num_clusters = num_clusters
        self.cluster_node_counts = self._layout.node_counts
        # Node accounting: float64 is authoritative (overcommit guard,
        # utilization); the float32 mirror feeds the JAX allocator.
        self._alloc_cpu = np.full((num_nodes,), node_cpu, np.float64)
        self._alloc_mem = np.full((num_nodes,), node_mem, np.float64)
        self._used_cpu = np.zeros((num_nodes,), np.float64)
        self._used_mem = np.zeros((num_nodes,), np.float64)
        # O(1) cluster-wide accounting for utilization sampling (the
        # engine samples on every bind/finish — summing [m] arrays there
        # dominated large-cluster benchmarks).
        self._alloc_cpu_total = float(self._alloc_cpu.sum())
        self._alloc_mem_total = float(self._alloc_mem.sum())
        self._used_cpu_total = 0.0
        self._used_mem_total = 0.0
        self._res_cpu32 = np.full((num_nodes,), node_cpu, np.float32)
        self._res_mem32 = np.full((num_nodes,), node_mem, np.float32)
        self._alloc_cpu32 = self._alloc_cpu.astype(np.float32)
        self._alloc_mem32 = self._alloc_mem.astype(np.float32)
        # Pod registry: dict for object access + slot arrays for the
        # Informer view, mutated on bind/finish/delete.
        self.pods: Dict[int, Pod] = {}
        self._uid = itertools.count()
        self._free_slots: List[int] = []
        self._capacity = 0
        self._pod_node = np.zeros((0,), np.int32)
        self._pod_cpu = np.zeros((0,), np.float32)
        self._pod_mem = np.zeros((0,), np.float32)
        self._pod_active = np.zeros((0,), bool)
        # Dirty-node journal for the device-resident allocator state:
        # when tracking is on, every bind/finish records the touched node
        # so the engine can scatter just those rows into the device tiles
        # instead of re-staging all [m] residuals per dispatch.
        self._track_dirty = False
        self._dirty: List[int] = []
        # Cordon/offline mask (fault injection): an offline node's float32
        # residual mirrors are zeroed — the allocator sees no capacity —
        # and its capacity leaves the O(1) utilization totals, while the
        # float64 books stay untouched so recovery is an exact resync.
        # The counter keeps the hot-path guard in bind() a no-op when no
        # chaos is configured.
        self._offline = np.zeros((num_nodes,), bool)
        self._num_offline = 0
        # Binds whose node books wait for ``flush_books``, as
        # ``(node, cpu, mem, slot)``; None while books are written at once.
        self._deferred: Optional[List[Tuple[int, float, float, int]]] = None

    # ------------------------------------------------------------- plumbing
    def _grow(self) -> None:
        new_cap = max(1, self._capacity * 2)
        self._free_slots.extend(range(self._capacity, new_cap))
        for name in ("_pod_node", "_pod_cpu", "_pod_mem", "_pod_active"):
            old = getattr(self, name)
            grown = np.zeros((new_cap,), old.dtype)
            grown[: self._capacity] = old
            setattr(self, name, grown)
        self._capacity = new_cap

    # ------------------------------------------------------------- pod ops
    # The allocator decides against the float32 mirror, whose rounding can
    # sit a few ULPs above the float64 books; quotas within this slack are
    # admitted (the books may then exceed capacity by up to the epsilon)
    # instead of crashing the run, while genuine overcommits (a real
    # allocator bug) still raise.  0.5 millicores/MiB is far above float32
    # noise and far below any real request.
    _OVERCOMMIT_EPS = 0.5

    def bind(self, task: TaskSpec, alloc: Allocation, now: float,
             workflow_id: str = "") -> Pod:
        """Create a pod with the allocated quota on the chosen node.

        Between ``defer_books`` and ``flush_books`` the node's books and
        the pod's slot arrays are queued instead of written; the cluster
        totals, the slot and the ``Pod`` are made here either way.
        """
        i, cpu, mem = alloc.node, alloc.cpu, alloc.mem
        if self._num_offline and self._offline[i]:
            raise RuntimeError(
                f"bind on offline node {i}: the allocator placed "
                f"quota=({cpu}, {mem}) on a cordoned node "
                f"whose residuals should read zero"
            )
        deferred = self._deferred
        if deferred is None:
            if (self._used_cpu[i] + cpu
                    > self._alloc_cpu[i] + self._OVERCOMMIT_EPS
                    or self._used_mem[i] + mem
                    > self._alloc_mem[i] + self._OVERCOMMIT_EPS):
                raise RuntimeError(
                    f"overcommit on node {i}: "
                    f"used=({self._used_cpu[i]}, {self._used_mem[i]}) "
                    f"quota=({cpu}, {mem}) "
                    f"cap=({self._alloc_cpu[i]}, {self._alloc_mem[i]})"
                )
            self._used_cpu[i] += cpu
            self._used_mem[i] += mem
            self._res_cpu32[i] -= np.float32(cpu)
            self._res_mem32[i] -= np.float32(mem)
            if self._track_dirty:
                self._dirty.append(i)
        self._used_cpu_total += cpu
        self._used_mem_total += mem
        if not self._free_slots:
            self._grow()
        slot = self._free_slots.pop()
        if deferred is None:
            self._pod_node[slot] = i
            self._pod_cpu[slot] = cpu
            self._pod_mem[slot] = mem
            self._pod_active[slot] = True
        else:
            deferred.append((i, cpu, mem, slot))
        pod = Pod(
            uid=next(self._uid), task=task, quota=Resources(cpu, mem),
            node=i, phase=PodPhase.RUNNING, t_created=now, t_started=now,
            workflow_id=workflow_id, slot=slot,
        )
        self.pods[pod.uid] = pod
        return pod

    def defer_books(self) -> None:
        """Queue the node books of the binds that follow until
        :meth:`flush_books`.  Nothing may read the per-node arrays in
        between: ``residual_view`` and ``drain_dirty`` refuse to."""
        assert self._deferred is None, "node books already deferred"
        self._deferred = []

    def flush_books(self) -> int:
        """Write the deferred binds' books in one update; returns how many.

        ``ufunc.at`` adds in bind order (a fancy-index ``+=`` would drop
        repeated nodes), so the float64 books and the float32 residuals
        read bit for bit what row-at-a-time binds leave.  Quotas are
        never negative, so a node's final books exceed its capacity iff
        some bind on it did, and one test over the touched nodes raises
        where row-at-a-time binds would have.
        """
        deferred, self._deferred = self._deferred, None
        if not deferred:
            return 0
        nodes, cpu, mem, slots = zip(*deferred)
        idx = np.array(nodes, np.int64)
        cpu64 = np.array(cpu, np.float64)
        mem64 = np.array(mem, np.float64)
        np.add.at(self._used_cpu, idx, cpu64)
        np.add.at(self._used_mem, idx, mem64)
        np.subtract.at(self._res_cpu32, idx, cpu64.astype(np.float32))
        np.subtract.at(self._res_mem32, idx, mem64.astype(np.float32))
        if self._track_dirty:
            self._dirty.extend(nodes)
        at = np.array(slots, np.int64)
        self._pod_node[at] = idx
        self._pod_cpu[at] = cpu64
        self._pod_mem[at] = mem64
        self._pod_active[at] = True
        touched = np.unique(idx)
        eps = self._OVERCOMMIT_EPS
        over = touched[
            (self._used_cpu[touched] > self._alloc_cpu[touched] + eps)
            | (self._used_mem[touched] > self._alloc_mem[touched] + eps)]
        if over.size:
            i = int(over[0])
            raise RuntimeError(
                f"overcommit on node {i}: a dispatch's binds took it to "
                f"used=({self._used_cpu[i]}, {self._used_mem[i]}) "
                f"cap=({self._alloc_cpu[i]}, {self._alloc_mem[i]})"
            )
        return len(deferred)

    def finish(self, uid: int, now: float, phase: PodPhase) -> Pod:
        """Transition a Running pod to a terminal phase, releasing quota."""
        pod = self.pods[uid]
        assert pod.phase == PodPhase.RUNNING, pod
        i = pod.node
        self._used_cpu[i] -= pod.quota.cpu
        self._used_mem[i] -= pod.quota.mem
        self._used_cpu_total -= pod.quota.cpu
        self._used_mem_total -= pod.quota.mem
        # In-place resizes update the books by quota *deltas*, which
        # cannot cancel bit-exactly against the final quota subtraction;
        # snap the ±ulp residue left when a node empties (never triggered
        # by the exact bind/finish pairs of a resize-free run).
        if -1e-6 < self._used_cpu[i] < 0.0:
            self._used_cpu_total -= self._used_cpu[i]
            self._used_cpu[i] = 0.0
        if -1e-6 < self._used_mem[i] < 0.0:
            self._used_mem_total -= self._used_mem[i]
            self._used_mem[i] = 0.0
        assert self._used_cpu[i] >= 0 and self._used_mem[i] >= 0, (i, pod)
        # Resync the float32 mirror from the float64 books on every
        # release: per-op rounding then cannot accumulate across pod
        # lifetimes, keeping the allocator's view within ULPs of truth.
        # Deterministic, and identical for batched and per-task modes
        # (releases only ever happen between bursts).
        self._res_cpu32[i] = np.float32(self._alloc_cpu[i] - self._used_cpu[i])
        self._res_mem32[i] = np.float32(self._alloc_mem[i] - self._used_mem[i])
        if self._track_dirty:
            self._dirty.append(i)
        self._pod_active[pod.slot] = False
        pod.phase = phase
        pod.t_finished = now
        return pod

    def resize(self, uid: int, new_cpu: float, new_mem: float) -> Resources:
        """In-place vertical resize of a Running pod's quota (ARC-V).

        Adjusts the float64 books and O(1) totals by the quota delta,
        resyncs the node's float32 residual mirror from the books (the
        same release-time rule as :meth:`finish`, so per-op rounding
        cannot accumulate across repeated resizes), journals the node
        dirty — a resize rides the identical scatter path into
        device-resident allocator state as any bind/finish — and updates
        the pod slot arrays so Informer consumers see the new quota.

        Grows are bounded by the node's allocatable capacity (same
        ``_OVERCOMMIT_EPS`` slack as :meth:`bind`); shrinks may go to
        zero but not negative.  Returns the previous quota.
        """
        pod = self.pods[uid]
        assert pod.phase == PodPhase.RUNNING, pod
        if new_cpu < 0 or new_mem < 0:
            raise RuntimeError(
                f"resize of pod {uid} to negative quota "
                f"({new_cpu}, {new_mem})")
        # Quotas live on the float32 lattice, like every allocator grant:
        # the pod slot arrays are float32, and the invariant cross-check
        # sums them against the float64 books.
        new_cpu = float(np.float32(new_cpu))
        new_mem = float(np.float32(new_mem))
        i = pod.node
        d_cpu = new_cpu - pod.quota.cpu
        d_mem = new_mem - pod.quota.mem
        if (self._used_cpu[i] + d_cpu
                > self._alloc_cpu[i] + self._OVERCOMMIT_EPS
                or self._used_mem[i] + d_mem
                > self._alloc_mem[i] + self._OVERCOMMIT_EPS):
            raise RuntimeError(
                f"resize overcommit on node {i}: "
                f"used=({self._used_cpu[i]}, {self._used_mem[i]}) "
                f"new quota=({new_cpu}, {new_mem}) "
                f"cap=({self._alloc_cpu[i]}, {self._alloc_mem[i]})"
            )
        self._used_cpu[i] += d_cpu
        self._used_mem[i] += d_mem
        self._used_cpu_total += d_cpu
        self._used_mem_total += d_mem
        self._res_cpu32[i] = np.float32(
            self._alloc_cpu[i] - self._used_cpu[i])
        self._res_mem32[i] = np.float32(
            self._alloc_mem[i] - self._used_mem[i])
        if self._track_dirty:
            self._dirty.append(i)
        self._pod_cpu[pod.slot] = new_cpu
        self._pod_mem[pod.slot] = new_mem
        old = pod.quota
        pod.quota = Resources(new_cpu, new_mem)
        pod.resized = True
        return old

    def node_headroom(self, node: int) -> Resources:
        """Unused allocatable capacity on a node, from the float64 books.

        The vertical controller's grow budget; an offline node reports
        zero (nothing may grow into cordoned capacity).
        """
        if self._offline[node]:
            return Resources(0.0, 0.0)
        return Resources(
            float(self._alloc_cpu[node] - self._used_cpu[node]),
            float(self._alloc_mem[node] - self._used_mem[node]),
        )

    def delete(self, uid: int) -> None:
        """Task Container Cleaner: remove terminal pods from the registry."""
        pod = self.pods.pop(uid)
        assert not pod.phase.consumes_resources, pod
        self._pod_cpu[pod.slot] = 0.0
        self._pod_mem[pod.slot] = 0.0
        self._free_slots.append(pod.slot)

    # ------------------------------------------------------------ fault ops
    def set_node_down(self, node: int, now: float):
        """Take a node offline (injected fault / cordon).

        Every Running pod on the node terminates ``FAILED`` (registry
        insertion order — deterministic), then the node's float32
        residual mirrors are zeroed and journaled dirty so the capacity
        loss rides the same scatter path into device-resident allocator
        state as any bind.  The float64 books are untouched — recovery
        (:meth:`set_node_up`) is an exact resync, not a replay.

        Returns the displaced pods (post-``finish``), or ``None`` if the
        node was already offline (idempotent no-op).
        """
        if self._offline[node]:
            return None
        displaced = [pod for pod in self.pods.values()
                     if pod.node == node and pod.phase is PodPhase.RUNNING]
        # Finish first: each finish resyncs the residual mirror from the
        # books, so the zeroing below must come after.
        for pod in displaced:
            self.finish(pod.uid, now, PodPhase.FAILED)
        self._offline[node] = True
        self._num_offline += 1
        self._res_cpu32[node] = np.float32(0.0)
        self._res_mem32[node] = np.float32(0.0)
        if self._track_dirty:
            self._dirty.append(node)
        self._alloc_cpu_total -= float(self._alloc_cpu[node])
        self._alloc_mem_total -= float(self._alloc_mem[node])
        return displaced

    def set_node_up(self, node: int) -> bool:
        """Bring an offline node back (recovery half of a flap).

        Resyncs the float32 residual mirrors from the float64 books
        (nothing ran while offline, so that is the full allocatable
        capacity), journals the node dirty, and restores its capacity to
        the utilization totals.  Returns ``False`` if the node was not
        offline (idempotent no-op).
        """
        if not self._offline[node]:
            return False
        self._offline[node] = False
        self._num_offline -= 1
        self._res_cpu32[node] = np.float32(
            self._alloc_cpu[node] - self._used_cpu[node])
        self._res_mem32[node] = np.float32(
            self._alloc_mem[node] - self._used_mem[node])
        if self._track_dirty:
            self._dirty.append(node)
        self._alloc_cpu_total += float(self._alloc_cpu[node])
        self._alloc_mem_total += float(self._alloc_mem[node])
        return True

    @property
    def offline_nodes(self):
        """Sorted global ids of currently-offline nodes."""
        return [int(n) for n in np.flatnonzero(self._offline)]

    # --------------------------------------------------------- dirty nodes
    def track_dirty(self, on: bool = True) -> None:
        """Start (or stop) journaling nodes whose residuals change.

        The engine turns this on when it maintains device-resident
        allocator state; ``delete`` never touches residuals, so only
        ``bind``/``finish`` record entries.
        """
        self._track_dirty = on
        self._dirty.clear()

    def drain_dirty(self):
        """Unique dirty node ids + their current float32 residuals.

        Returns ``(nodes, res_cpu, res_mem)`` — copies, safe to hold
        across further mutation — and clears the journal.  The residual
        values are read from the authoritative mirror at drain time, so
        scattering them into device tiles reproduces ``residual_view``
        exactly for those rows.
        """
        assert self._deferred is None, "node books deferred"
        if not self._dirty:
            return (np.zeros((0,), np.int64), np.zeros((0,), np.float32),
                    np.zeros((0,), np.float32))
        nodes = np.unique(np.asarray(self._dirty, np.int64))
        self._dirty.clear()
        return (nodes, self._res_cpu32[nodes].copy(),
                self._res_mem32[nodes].copy())

    # ----------------------------------------------------------- informer
    @property
    def cluster_slices(self):
        """Per-cluster ``slice`` into the global node arrays."""
        return tuple(
            slice(off, off + m)
            for off, m in zip(self._layout.offsets,
                              self._layout.node_counts)
        )

    def cluster_of(self, node: int) -> int:
        """The cluster owning a global node id."""
        for k, (off, m) in enumerate(zip(self._layout.offsets,
                                         self._layout.node_counts)):
            if off <= node < off + m:
                return k
        raise IndexError(node)

    def residual_view_sharded(self):
        """Per-cluster float32 residual views — the federated layout.

        One ``(cpu, mem)`` pair of live array views per cluster (treat as
        read-only), slicing the same incrementally-maintained arrays
        ``residual_view`` returns; zero-copy.
        """
        return tuple(
            (self._res_cpu32[s], self._res_mem32[s])
            for s in self.cluster_slices
        )

    def capacity_view_sharded(self):
        """Per-cluster float32 allocatable-capacity views (read-only)."""
        return tuple(
            (self._alloc_cpu32[s], self._alloc_mem32[s])
            for s in self.cluster_slices
        )

    def residual_view(self):
        """Float32 per-node residuals — the allocator's Monitor input.

        These are the live incrementally-maintained arrays (treat as
        read-only); identical to what Alg. 2 would recompute, without the
        O(pods) pass.
        """
        assert self._deferred is None, "node books deferred"
        return self._res_cpu32, self._res_mem32

    def capacity_view(self):
        """Float32 per-node allocatable capacity (read-only).

        Feeds capacity-normalized placement scoring (the ``balanced``
        policy) without a snapshot copy.
        """
        return self._alloc_cpu32, self._alloc_mem32

    def snapshot(self) -> ClusterSnapshot:
        """Informer-style struct-of-arrays view for the JAX algorithms.

        A consistent point-in-time copy (later ``bind``/``finish`` calls
        do not mutate it), as callers of an Informer cache expect.  Pod
        arrays are capacity-sized (stable JIT shapes); free slots are
        ``active=False`` with zero quota, so Alg. 2 sees the same totals.
        The engine's hot path uses ``residual_view`` instead and never
        pays this copy.
        """
        return ClusterSnapshot(
            allocatable_cpu=self._alloc_cpu32,
            allocatable_mem=self._alloc_mem32,
            pod_node=self._pod_node.copy(),
            pod_cpu=self._pod_cpu.copy(),
            pod_mem=self._pod_mem.copy(),
            pod_active=self._pod_active.copy(),
        )

    # ------------------------------------------------------------- metrics
    def utilization(self) -> Resources:
        """Fraction of allocatable capacity currently held by quotas.

        O(1): reads the incrementally-maintained cluster totals instead of
        re-summing the node arrays (this runs on every bind/finish).
        Offline nodes' capacity is excluded; a fully-offline cluster
        reports zero utilization rather than dividing by zero.
        """
        if self._alloc_cpu_total <= 0.0 or self._alloc_mem_total <= 0.0:
            return Resources(0.0, 0.0)
        return Resources(
            self._used_cpu_total / self._alloc_cpu_total,
            self._used_mem_total / self._alloc_mem_total,
        )

    def check_invariants(self) -> None:
        assert (self._used_cpu >= 0).all() and (self._used_mem >= 0).all(), \
            (self._used_cpu, self._used_mem)
        eps = self._OVERCOMMIT_EPS
        assert (self._used_cpu <= self._alloc_cpu + eps).all(), self._used_cpu
        assert (self._used_mem <= self._alloc_mem + eps).all(), self._used_mem
        # cross-check node accounting against the pod slot arrays
        active = self._pod_active
        cpu = np.zeros((self.num_nodes,), np.float64)
        mem = np.zeros((self.num_nodes,), np.float64)
        np.add.at(cpu, self._pod_node[active], self._pod_cpu[active])
        np.add.at(mem, self._pod_node[active], self._pod_mem[active])
        assert np.abs(cpu - self._used_cpu).max(initial=0.0) < 1e-3, \
            (cpu, self._used_cpu)
        assert np.abs(mem - self._used_mem).max(initial=0.0) < 1e-3, \
            (mem, self._used_mem)
        # the O(1) cluster totals must track the per-node books; capacity
        # totals count online nodes only
        online = ~self._offline
        assert abs(self._used_cpu_total - self._used_cpu.sum()) < 1e-3
        assert abs(self._used_mem_total - self._used_mem.sum()) < 1e-3
        assert abs(self._alloc_cpu_total - self._alloc_cpu[online].sum()) \
            < 1e-3
        assert abs(self._alloc_mem_total - self._alloc_mem[online].sum()) \
            < 1e-3
        # offline nodes hold no consuming pods and read zero residuals
        if self._num_offline:
            assert (self._used_cpu[self._offline] == 0.0).all()
            assert (self._res_cpu32[self._offline] == 0.0).all()
            assert (self._res_mem32[self._offline] == 0.0).all()
        # the float32 residual caches must track the float64 books
        # (offline nodes are pinned to zero by construction, so the drift
        # check covers online nodes only)
        for res32, alloc, used in (
            (self._res_cpu32, self._alloc_cpu, self._used_cpu),
            (self._res_mem32, self._alloc_mem, self._used_mem),
        ):
            drift = np.abs(res32.astype(np.float64) - (alloc - used))[online]
            assert drift.max(initial=0.0) < 1.0, drift
