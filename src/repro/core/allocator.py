"""Allocator front-ends: ARAS (Algorithm 1) and the FCFS baseline.

``AdaptiveAllocator`` composes the three modules of the Resource Manager
(paper Fig. 2): Resource Discovery (Alg. 2), the lifecycle window +
summaries (Alg. 1), and the Resource Evaluator (Alg. 3).  The baseline
(``FCFSAllocator``) reproduces the paper's §6.1.6 comparison strategy: it
allocates the *full* declared request if some node can host it, otherwise
reports infeasible so the engine queues the task until resources free up.

The allocation unit is the **burst**, not the task: ``allocate_batch``
decides a whole batch of ready requests in one fused JAX dispatch.  The
paper's loop is sequential by construction — each accepted allocation
must be visible to the next request — but only through three true carry
dependencies: the per-node residuals, the cluster totals and the set of
records stamped ``t_start = now`` mid-burst.  Everything else is hoisted
into a parallel precompute:

* **window demand** (Alg. 1 lines 4-13) — one ``[B, T]`` masked reduction
  over the record table at its pre-burst start times
  (``lifecycle.masked_demand_batch``), plus a ``[B, B]`` *correction
  table* whose row *i* holds what each mid-burst-stamped record adds to
  request *i*'s window versus its pre-burst contribution.  The sequential
  core folds the correction in with a triangular stamped mask — O(B) per
  step instead of O(T).
* **cluster totals** (Alg. 1 lines 15-18) — summed once per burst, then
  debited O(1) per accepted row inside the carry.

The remaining decide→debit→place recurrence runs on a pluggable backend
(``repro.kernels.alloc_scan``): a ``lax.scan`` reference, or a Pallas TPU
kernel that keeps the residual tiles resident in VMEM across the whole
burst.  Decisions are bit-for-bit identical across backends *and* against
the engine's per-task replay mode (one dispatch per decision, carry
reconstructed from the engine's incremental caches), gated by
``tests/test_batch_parity.py`` / ``tests/test_alloc_scan.py``.

Batch and record-table lengths are padded to power-of-two buckets so JIT
caches stay warm as the knowledge base grows (padding rows carry
``attempt=False`` / ``done=True`` and are numerically inert).

Federated multi-cluster mode (``repro.cluster.federation``): a
``FederatedLayout`` lays the residual/capacity tiles out cluster-major
with per-shard totals in the carry; the same precompute → sequential core
→ sync pipeline then decides one burst against K cluster shards (accepts
debit only the owning shard, the evaluator pools federation-wide
capacity), optionally with the tiles sharded across a ``clusters``
device mesh.  ``layout=None`` is the legacy single-cluster path, bit for
bit — ``tests/test_federation_parity.py`` holds the K=1 layout to it.

Device-resident incremental dispatch (``repro.cluster.device_state``):
``allocate_batch`` stages the full O(nodes) residual arrays per burst;
``allocate_batch_async`` instead decides against a
``DeviceResidualState`` whose tiles/block sums persist on device and are
maintained by dirty-tile scatter updates, so only the O(burst) rows move
per dispatch.  It returns a ``PendingBurst`` (sync deferred to
``wait()``), letting the engine overlap host event folding with the
in-flight fused dispatch.  Both paths share the hierarchical totals
reduction, so decisions stay bit-for-bit identical
(``tests/test_incremental_state.py``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

from repro.api.registry import ALLOCATORS
from repro.cluster import device_state, federation
from repro.cluster.device_state import DeviceResidualState
from repro.cluster.federation import FederatedLayout
from repro.core import discovery, lifecycle
from repro.core.evaluation import SCENARIO_NAMES
from repro.core.types import (
    DEFAULT_ALPHA,
    DEFAULT_BETA,
    Allocation,
    BatchAllocation,
    ClusterSnapshot,
    TaskBatch,
    TaskSpec,
    TaskWindow,
)
from repro.kernels.alloc_scan import alloc_scan, resolve_backend
from repro.kernels.alloc_scan.ref import RES_PAD, alloc_step


def _pow2(n: int) -> int:
    """Smallest power of two ≥ max(n, 1) — the JIT shape bucket."""
    return 1 << max(n - 1, 0).bit_length()


@functools.partial(jax.jit, static_argnames=("mode", "layout"))
def _burst_precompute(
    residual_cpu: jax.Array,  # [m] f32 per-node residuals (Alg. 2 output)
    residual_mem: jax.Array,  # [m] f32
    cap_cpu: jax.Array,  # [m] f32 allocatable capacity (balanced scoring)
    cap_mem: jax.Array,  # [m] f32
    rec_t_start: jax.Array,  # [T] f32 knowledge-base record table
    rec_cpu: jax.Array,  # [T] f32
    rec_mem: jax.Array,  # [T] f32
    rec_done: jax.Array,  # [T] bool
    b_cpu: jax.Array,  # [B] f32 batch rows, admission order
    b_mem: jax.Array,  # [B] f32
    b_wend: jax.Array,  # [B] f32 lifecycle window ends
    b_self: jax.Array,  # [B] int32 record slot to exclude, -1 = none
    now: jax.Array,  # scalar f32
    *,
    mode: str,
    layout: FederatedLayout | None = None,
):
    """Everything the sequential core does NOT need to recompute per step.

    Returns residual/capacity tiles, the O(1)-carried totals, the hoisted
    base window demand and the ``[B, B]`` stamp-correction tables.

    ``layout`` selects the federated multi-cluster tile layout (blocks
    cluster-major, per-shard totals); ``None`` is the legacy
    single-cluster path, bit for bit.
    """
    rc2 = federation.pad_tiles_federated(residual_cpu, layout, RES_PAD)
    rm2 = federation.pad_tiles_federated(residual_mem, layout, RES_PAD)
    cc2 = federation.pad_tiles_federated(cap_cpu, layout, 0.0)
    cm2 = federation.pad_tiles_federated(cap_mem, layout, 0.0)
    # Alg. 1 lines 15-18, hoisted: one reduction per burst (per shard in
    # federated mode); the core debits O(1) on every accept.  Derived
    # hierarchically — masked per-block tile sums, then a fixed-order
    # block reduce — which is the exact reduction the device-resident
    # incremental state maintains, so the re-pad and incremental paths
    # carry bitwise-equal totals into the sequential core.
    mask2 = jnp.asarray(federation.tile_mask(residual_cpu.shape[0], layout))
    tot_cpu = federation.totals_from_block_sums(
        federation.tile_block_sums(rc2, mask2), layout)
    tot_mem = federation.totals_from_block_sums(
        federation.tile_block_sums(rm2, mask2), layout)
    base_cpu, base_mem, delta_cpu, delta_mem = _demand_tables(
        rec_t_start, rec_cpu, rec_mem, rec_done,
        b_cpu, b_mem, b_wend, b_self, now, mode=mode,
    )
    return (rc2, rm2, cc2, cm2, tot_cpu, tot_mem,
            base_cpu, base_mem, delta_cpu, delta_mem)


def _demand_tables(rec_t_start, rec_cpu, rec_mem, rec_done,
                   b_cpu, b_mem, b_wend, b_self, now, *, mode):
    """Hoisted window-demand terms, shared by both precompute entries.

    Traced inside ``_burst_precompute`` (re-pad path) and
    ``_state_dispatch`` (device-resident path) alike, so the two paths
    cannot drift.
    """
    num_slots = rec_t_start.shape[0]
    num_rows = b_cpu.shape[0]
    if mode != "aras":
        # FCFS never reads the demand terms; stream width-1 placeholders
        # instead of dense [B, B] zero tables.
        zeros_b = jnp.zeros((num_rows,), jnp.float32)
        zeros_bb = jnp.zeros((num_rows, 1), jnp.float32)
        return zeros_b, zeros_b, zeros_bb, zeros_bb
    # Alg. 1 lines 4-13, hoisted: in-window demand of every row against
    # the record table at its *pre-burst* start times.
    slot_ids = jnp.arange(num_slots, dtype=jnp.int32)
    base_cpu, base_mem = lifecycle.masked_demand_batch(
        rec_t_start, rec_cpu, rec_mem, rec_done, slot_ids,
        now, b_wend, b_cpu, b_mem, b_self,
    )
    # Correction tables: delta[i, j] = row j's record demand seen by row
    # i's window once j is stamped to t_start=now, minus its pre-burst
    # contribution already inside base[i].  Row j's own column and
    # slot-less rows are masked; self-exclusion (Alg. 1 line 9) carries
    # over because slots are unique within a burst.
    cs = jnp.clip(b_self, 0, num_slots - 1)
    g_cpu = rec_cpu[cs]
    g_mem = rec_mem[cs]
    g_pre = rec_t_start[cs]
    g_valid = (b_self >= 0) & ~rec_done[cs]
    not_self = b_self[None, :] != b_self[:, None]
    w_mask = g_valid[None, :] & not_self
    w_now = (now < b_wend[:, None]) & w_mask
    w_pre = ((g_pre[None, :] >= now) & (g_pre[None, :] < b_wend[:, None])
             & w_mask)
    dw = w_now.astype(jnp.float32) - w_pre.astype(jnp.float32)
    delta_cpu = g_cpu[None, :] * dw
    delta_mem = g_mem[None, :] * dw
    return base_cpu, base_mem, delta_cpu, delta_mem


# Slot order of the packed staging arrays used by the device-resident
# fast path.  On small bursts the staging cost is dominated by the fixed
# per-transfer dispatch overhead, not bytes, so the eight row arrays
# travel as one [8, B] float32 transfer (ints and bools ride along as
# exact float32: slot ids stay below 2**24, flags are 0/1) and the four
# record columns as one [4, T] — two host→device copies per dispatch
# instead of twelve.
_ROW_CPU, _ROW_MEM, _ROW_MIN_CPU, _ROW_MIN_MEM, _ROW_WEND, _ROW_SELF, \
    _ROW_ATTEMPT, _ROW_PENDING = range(8)
_REC_T_START, _REC_CPU, _REC_MEM, _REC_DONE = range(4)


def _fill_packed(rows: np.ndarray, recs: np.ndarray,
                 batch: TaskBatch, window: TaskWindow) -> None:
    """Fill preallocated ``[8, B]`` / ``[4, T]`` staging views in place."""
    n = batch.size
    rows[_ROW_CPU, :n] = batch.cpu
    rows[_ROW_MEM, :n] = batch.mem
    rows[_ROW_MIN_CPU, :n] = batch.min_cpu
    rows[_ROW_MIN_MEM, :n] = batch.min_mem
    rows[_ROW_WEND, :n] = batch.window_end
    rows[_ROW_SELF] = -1.0  # pad rows exclude no record slot
    rows[_ROW_SELF, :n] = batch.self_slot
    rows[_ROW_ATTEMPT, :n] = 1.0
    rows[_ROW_PENDING, :n] = batch.pending
    nrec = window.t_start.shape[0]
    recs[_REC_T_START, :nrec] = window.t_start
    recs[_REC_CPU, :nrec] = window.cpu
    recs[_REC_MEM, :nrec] = window.mem
    recs[_REC_DONE] = 1.0  # padding records are done: numerically inert
    recs[_REC_DONE, :nrec] = window.done


def _nbytes(*arrays) -> int:
    """Bytes of the distinct device arrays a dispatch staged from the host."""
    return sum(a.nbytes for a in {id(a): a for a in arrays}.values())


def _packed_row_inputs(batch: TaskBatch, window: TaskWindow, now: float):
    """``_row_inputs`` packed into two transfers, for the hot stream path."""
    rows = np.zeros((8, _pow2(batch.size)), np.float32)
    recs = np.zeros((4, _pow2(window.t_start.shape[0])), np.float32)
    _fill_packed(rows, recs, batch, window)
    return jnp.asarray(rows), jnp.asarray(recs), jnp.float32(now)


def _pack_outs(cpu, mem, node, feasible, attempted, scenario):
    """The six decision rows as one ``int32[6, B]`` buffer (traceable).

    The device→host fetch costs per transfer, not per byte, so every
    jitted entry that decides returns this one buffer and
    ``PendingBurst.wait`` fetches it once.  The quotas ride as their
    float32 bit patterns (``bitcast_convert_type``: exact, -0.0 and NaN
    kept), the flags as 0/1.  The quotas are stacked before the bitcast
    so that XLA fuses the whole pack into one copy: no op of its own
    reads the kernel's outputs, which keeps the kernel the one device
    op that trace readers find by its name
    (``tests/test_chip_compile.py``).
    """
    quotas = lax.bitcast_convert_type(jnp.stack([cpu, mem]), jnp.int32)
    return jnp.concatenate([quotas, jnp.stack([
        node.astype(jnp.int32),
        feasible.astype(jnp.int32),
        attempted.astype(jnp.int32),
        scenario.astype(jnp.int32),
    ])])


def _unpack_outs(packed: np.ndarray, n: int):
    """Host inverse of :func:`_pack_outs` over the first ``n`` rows."""
    cpu, mem, node, feasible, attempted, scenario = packed[:, :n]
    return (cpu.view(np.float32), mem.view(np.float32), node,
            feasible != 0, attempted != 0, scenario)


def alloc_scan_packed(*args, **kwargs):
    """``alloc_scan`` with its six outputs packed by :func:`_pack_outs`.

    Jitted, its name makes the compiled module ``jit_alloc_scan_packed``,
    which trace readers match as the sequential core by its prefix.
    """
    return _pack_outs(*alloc_scan(*args, **kwargs))


def _decide_packed(rc2, rm2, cc2, cm2, bsum_c, bsum_m, rows, recs, now,
                   *, alpha, beta, policy, mode, backend, layout):
    """Traceable device-resident decision over packed staging arrays.

    ``_burst_precompute`` minus the tiles, fused with the sequential
    core: the residual/capacity tiles already live on device
    (``repro.cluster.device_state``), the carried totals come from the
    incrementally-maintained block sums via the same fixed-order reduce
    the re-pad path uses, and the hoisted demand tables feed straight
    into ``alloc_scan`` without re-crossing a dispatch boundary.  The
    decisions come back packed (:func:`_pack_outs`).
    """
    b_cpu, b_mem = rows[_ROW_CPU], rows[_ROW_MEM]
    b_min_cpu, b_min_mem = rows[_ROW_MIN_CPU], rows[_ROW_MIN_MEM]
    b_wend = rows[_ROW_WEND]
    b_self = rows[_ROW_SELF].astype(jnp.int32)
    b_attempt = rows[_ROW_ATTEMPT] != 0
    b_pending = rows[_ROW_PENDING] != 0
    rec_done = recs[_REC_DONE] != 0
    tot_cpu = federation.totals_from_block_sums(bsum_c, layout)
    tot_mem = federation.totals_from_block_sums(bsum_m, layout)
    base_cpu, base_mem, delta_cpu, delta_mem = _demand_tables(
        recs[_REC_T_START], recs[_REC_CPU], recs[_REC_MEM], rec_done,
        b_cpu, b_mem, b_wend, b_self, now, mode=mode,
    )
    return alloc_scan_packed(
        rc2, rm2, cc2, cm2, tot_cpu, tot_mem,
        b_cpu, b_mem, b_min_cpu, b_min_mem, base_cpu, base_mem,
        delta_cpu, delta_mem, b_self, b_attempt, b_pending,
        alpha=alpha, beta=beta, policy=policy, mode=mode, backend=backend,
    )


@functools.partial(
    jax.jit,
    static_argnames=("alpha", "beta", "policy", "mode", "backend", "layout"),
)
def _state_dispatch(
    rc2, rm2, cc2, cm2,  # device-resident tiles (DeviceResidualState)
    bsum_c, bsum_m,  # [nb] f32 incrementally-maintained block sums
    rows,  # [8, B] f32 packed burst rows (_ROW_* slots)
    recs,  # [4, T] f32 packed record table (_REC_* slots)
    now,  # scalar f32
    *,
    alpha, beta, policy, mode, backend,
    layout: FederatedLayout | None = None,
):
    """The device-resident decision as **one** jitted dispatch.

    Nothing O(nodes) moves, and the host pays a single call's fixed
    overhead per burst (see :func:`_decide_packed`).
    """
    return _decide_packed(
        rc2, rm2, cc2, cm2, bsum_c, bsum_m, rows, recs, now,
        alpha=alpha, beta=beta, policy=policy, mode=mode, backend=backend,
        layout=layout,
    )


def _pack_state_step(batch: TaskBatch, window: TaskWindow, now: float,
                     seg: np.ndarray):
    """Stage one maintain-and-decide step as a single flat f32 buffer.

    Layout: the dirty-set update segment (``pack_update_segment``), the
    ``[8, B]`` packed rows, the ``[4, T]`` packed record table, then the
    scalar ``now`` — one host→device copy for the whole step.
    """
    n_rows = _pow2(batch.size)
    n_rec = _pow2(window.t_start.shape[0])
    u = seg.shape[0]
    buf = np.zeros((u + 8 * n_rows + 4 * n_rec + 1,), np.float32)
    buf[:u] = seg
    rows = buf[u: u + 8 * n_rows].reshape(8, n_rows)
    recs = buf[u + 8 * n_rows: u + 8 * n_rows + 4 * n_rec].reshape(4, n_rec)
    _fill_packed(rows, recs, batch, window)
    buf[-1] = now
    return jnp.asarray(buf), n_rows, n_rec


@functools.partial(
    jax.jit,
    static_argnames=("n_idx", "n_blk", "n_rows", "n_rec",
                     "alpha", "beta", "policy", "mode", "backend", "layout"),
    # The caller hands over the pre-update tiles/block sums for good
    # (PendingBurst.state replaces them), so XLA scatters in place
    # instead of copying the whole residual tile table per step.
    donate_argnums=(0, 1, 4, 5),
)
def _state_step(
    rc2, rm2, cc2, cm2, bsum_c, bsum_m, mask2,  # DeviceResidualState
    buf,  # flat f32 staging buffer (_pack_state_step)
    *,
    n_idx, n_blk, n_rows, n_rec,
    alpha, beta, policy, mode, backend,
    layout: FederatedLayout | None = None,
):
    """Maintain **and** decide in one fused jitted dispatch.

    The streaming hot path: scatter the dirty-node deltas into the
    device-resident tiles (``repro.cluster.device_state.apply_packed``),
    re-derive the dirty block sums, then run the fused decision against
    the updated state — one host→device copy, one dispatch, per burst.
    Returns the updated ``(rc2, rm2, bsum_c, bsum_m)`` carry (device
    arrays the next step chains on without syncing) plus the packed
    decision outputs (:func:`_pack_outs`).  The residual tiles and block
    sums are **donated**: the input state is consumed (its buffers
    updated in place) and only the returned state is valid afterwards.
    Ops are identical to ``apply_updates`` followed by
    ``_state_dispatch``, so decisions stay bit-for-bit with the re-pad
    path (``tests/test_incremental_state.py``).
    """
    u = 3 * n_idx + n_blk
    rc2, rm2, bsum_c, bsum_m = device_state.apply_packed(
        rc2, rm2, bsum_c, bsum_m, mask2, buf[:u], n_idx, n_blk)
    rows = buf[u: u + 8 * n_rows].reshape(8, n_rows)
    recs = buf[u + 8 * n_rows: u + 8 * n_rows + 4 * n_rec].reshape(4, n_rec)
    outs = _decide_packed(
        rc2, rm2, cc2, cm2, bsum_c, bsum_m, rows, recs, buf[-1],
        alpha=alpha, beta=beta, policy=policy, mode=mode, backend=backend,
        layout=layout,
    )
    return (rc2, rm2, bsum_c, bsum_m), outs


# The sequential core as one dispatch, its six outputs apart: the entry
# the kernel parity tests compare backends through.
_core_dispatch = jax.jit(
    alloc_scan,
    static_argnames=("alpha", "beta", "policy", "mode", "backend"),
)
# The re-pad and mesh path's sequential core, outputs packed
# (``_issue_burst``).
_packed_core_dispatch = jax.jit(
    alloc_scan_packed,
    static_argnames=("alpha", "beta", "policy", "mode", "backend"),
)


@functools.partial(
    jax.jit, static_argnames=("alpha", "beta", "policy", "mode", "layout")
)
def _replay_step(
    residual_cpu, residual_mem, cap_cpu2, cap_mem2,
    tot_cpu, tot_mem, stamped, blocked,
    b_cpu, b_mem, b_min_cpu, b_min_mem, base_cpu, base_mem,
    delta_cpu, delta_mem, b_self, b_attempt, b_pending,
    i,
    *,
    alpha, beta, policy, mode, layout=None,
):
    """One decision of the per-task replay: the shared step at row ``i``.

    The residual carry is rebuilt from the engine's live float32 caches
    (tiling and block maxima are exact), so the replay independently
    verifies that the fused core's in-scan debits and stamps track the
    host-side state transitions bit-for-bit.
    """
    rc2 = federation.pad_tiles_federated(residual_cpu, layout, RES_PAD)
    rm2 = federation.pad_tiles_federated(residual_mem, layout, RES_PAD)
    carry = (rc2, rm2, jnp.max(rc2, axis=1), tot_cpu, tot_mem,
             stamped, blocked)
    row = (b_cpu[i], b_mem[i], b_min_cpu[i], b_min_mem[i],
           base_cpu[i], base_mem[i], delta_cpu[i], delta_mem[i],
           b_self[i], b_attempt[i], b_pending[i], i)
    carry, out = alloc_step(carry, row, cap_cpu2, cap_mem2,
                            alpha=alpha, beta=beta, policy=policy, mode=mode)
    _, _, _, tot_cpu, tot_mem, stamped, blocked = carry
    return out, tot_cpu, tot_mem, stamped, blocked


def _pad_1d(arr: np.ndarray, size: int, fill) -> np.ndarray:
    if arr.shape[0] == size:
        return arr
    out = np.full((size,), fill, dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def _row_inputs(batch: TaskBatch, window: TaskWindow, now: float):
    """Pad the burst rows + record table to shape buckets and stage them.

    The O(burst)-sized half of ``_device_inputs`` — all the
    device-resident dispatch path ever stages per burst (the O(nodes)
    residual/capacity arrays stay on device across dispatches).
    """
    n = batch.size
    nb = _pow2(n)
    nt = _pow2(window.t_start.shape[0])
    rows = dict(
        b_cpu=jnp.asarray(_pad_1d(batch.cpu, nb, 0.0)),
        b_mem=jnp.asarray(_pad_1d(batch.mem, nb, 0.0)),
        b_min_cpu=jnp.asarray(_pad_1d(batch.min_cpu, nb, 0.0)),
        b_min_mem=jnp.asarray(_pad_1d(batch.min_mem, nb, 0.0)),
        b_wend=jnp.asarray(_pad_1d(batch.window_end, nb, 0.0)),
        b_self=jnp.asarray(_pad_1d(batch.self_slot, nb, -1)),
        b_attempt=jnp.asarray(_pad_1d(np.ones((n,), bool), nb, False)),
        b_pending=jnp.asarray(_pad_1d(batch.pending, nb, False)),
    )
    recs = dict(
        rec_t_start=jnp.asarray(
            _pad_1d(np.asarray(window.t_start, np.float32), nt, 0.0)),
        rec_cpu=jnp.asarray(
            _pad_1d(np.asarray(window.cpu, np.float32), nt, 0.0)),
        rec_mem=jnp.asarray(
            _pad_1d(np.asarray(window.mem, np.float32), nt, 0.0)),
        # Padding records are complete zero-demand rows: numerically inert.
        rec_done=jnp.asarray(_pad_1d(np.asarray(window.done, bool), nt, True)),
    )
    return rows, recs, jnp.float32(now)


def _device_inputs(
    batch: TaskBatch,
    residual_cpu,
    residual_mem,
    window: TaskWindow,
    now: float,
    cap_cpu,
    cap_mem,
):
    """Pad to shape buckets and stage the burst on device."""
    res_c = jnp.asarray(residual_cpu, jnp.float32)
    res_m = jnp.asarray(residual_mem, jnp.float32)
    # Capacity defaults to the current residuals when the caller has no
    # capacity view (legacy snapshot-less paths); only ``balanced``
    # scoring reads it.
    cap_c = res_c if cap_cpu is None else jnp.asarray(cap_cpu, jnp.float32)
    cap_m = res_m if cap_mem is None else jnp.asarray(cap_mem, jnp.float32)
    rows, recs, now32 = _row_inputs(batch, window, now)
    return res_c, res_m, cap_c, cap_m, rows, recs, now32


@dataclasses.dataclass
class PendingBurst:
    """A fused dispatch issued but not yet synced back to the host.

    JAX dispatch is asynchronous: once the jitted call returns, the
    device is computing while the host is free — so the engine can fold
    queued events (and flush dirty-tile updates into the *next* state)
    before paying the one blocking ``wait()`` sync of the burst.  The
    split is what makes the double-buffered overlap of the streaming
    engine possible; ``wait()`` is exactly the sync the one-shot path
    always did, so decisions are unaffected.  The sync is one
    device→host transfer of one packed ``int32[6, pow2(B)]`` buffer
    (:func:`_pack_outs`), unpacked on the host.
    """

    outs: jax.Array | None  # packed decisions; None = empty burst
    n: int
    layout: FederatedLayout | None
    # Post-update device state when the dispatch also folded dirty-node
    # deltas (the fused maintain-and-decide step): valid immediately —
    # device arrays chain asynchronously — and never synced by wait().
    state: "DeviceResidualState | None" = None
    dispatch: int = 0  # the engine's dispatch index (trace metadata)
    staged_bytes: int = 0  # bytes copied host→device to issue it

    @property
    def fetched_bytes(self) -> int:
        """Bytes ``wait()`` copies device→host (``24 * pow2(B)``)."""
        return 0 if self.outs is None else self.outs.nbytes

    def wait(self) -> BatchAllocation:
        """Block on the device results and map nodes back to global ids."""
        if self.outs is None:
            return BatchAllocation.empty()
        # The one host↔device sync of the whole burst: one transfer of
        # the one packed buffer.
        with TraceAnnotation("alloc.wait", dispatch=self.dispatch,
                             bytes=self.fetched_bytes):
            packed = jax.device_get(self.outs)
        cpu, mem, node, feasible, attempted, scenario = _unpack_outs(
            packed, self.n)
        return BatchAllocation(
            cpu=cpu,
            mem=mem,
            node=federation.global_nodes(node, self.layout),
            feasible=feasible,
            attempted=attempted,
            scenario=scenario,
        )


def _issue_burst(
    batch: TaskBatch,
    residual_cpu,
    residual_mem,
    window: TaskWindow,
    now: float,
    *,
    alpha: float,
    beta: float,
    policy: str,
    mode: str,
    backend: str,
    cap_cpu=None,
    cap_mem=None,
    layout: FederatedLayout | None = None,
    mesh=None,
    dispatch: int = 0,
) -> PendingBurst:
    """Stage → precompute → sequential core; returns without syncing.

    ``layout`` runs the burst on the federated multi-cluster tile layout
    (``repro.cluster.federation``); ``mesh`` additionally lays the tiles
    out across a ``clusters`` device mesh via ``jax.sharding``.  Node
    indices are mapped back to global node ids at ``wait()``, so callers
    never see the padded federated index space.  ``dispatch`` only tags
    the ``alloc.pack`` / ``alloc.launch`` / ``alloc.wait`` trace spans.
    """
    n = batch.size
    if n == 0:
        return PendingBurst(None, 0, layout)
    with TraceAnnotation("alloc.pack", dispatch=dispatch) as span:
        res_c, res_m, cap_c, cap_m, rows, recs, now32 = _device_inputs(
            batch, residual_cpu, residual_mem, window, now, cap_cpu, cap_mem
        )
        staged = _nbytes(res_c, res_m, cap_c, cap_m, *rows.values(),
                         *recs.values(), now32)
        span.set_metadata(bytes=staged)
    with TraceAnnotation("alloc.launch", dispatch=dispatch):
        (rc2, rm2, cc2, cm2, tot_c, tot_m, base_c, base_m, dlt_c, dlt_m) = \
            _burst_precompute(
                res_c, res_m, cap_c, cap_m,
                recs["rec_t_start"], recs["rec_cpu"], recs["rec_mem"],
                recs["rec_done"],
                rows["b_cpu"], rows["b_mem"], rows["b_wend"], rows["b_self"],
                now32, mode=mode, layout=layout,
            )
        concrete_backend = resolve_backend(backend)
        if mesh is not None and concrete_backend != "pallas":
            # pallas_call has no cross-device partitioning rule (outside
            # shard_map), so the device mesh only applies to the scan
            # backend; the Pallas kernel instead keeps the whole
            # federation VMEM-resident on one device.
            rc2, rm2, cc2, cm2 = (
                federation.shard_tiles(t, mesh) for t in (rc2, rm2, cc2, cm2))
        outs = _packed_core_dispatch(
            rc2, rm2, cc2, cm2, tot_c, tot_m,
            rows["b_cpu"], rows["b_mem"], rows["b_min_cpu"],
            rows["b_min_mem"], base_c, base_m, dlt_c, dlt_m,
            rows["b_self"], rows["b_attempt"], rows["b_pending"],
            alpha=alpha, beta=beta, policy=policy, mode=mode,
            backend=concrete_backend,
        )
    return PendingBurst(outs, n, layout, dispatch=dispatch,
                        staged_bytes=staged)


def _issue_state_burst(
    batch: TaskBatch,
    state,
    window: TaskWindow,
    now: float,
    *,
    alpha: float,
    beta: float,
    policy: str,
    mode: str,
    backend: str,
    updates=None,
    dispatch: int = 0,
) -> PendingBurst:
    """Issue one fused dispatch against device-resident allocator state.

    The O(nodes) staging of ``_issue_burst`` disappears: tiles and block
    sums come straight from the :class:`DeviceResidualState` the engine
    maintains by dirty-tile scatter updates; only the O(burst) rows and
    the record table cross to the device, and precompute + sequential
    core run as one fused jit call.  With ``updates`` (a
    ``(nodes, res_cpu, res_mem)`` dirty set, as drained from
    ``ClusterSim.drain_dirty``) the scatter maintenance fuses into the
    same dispatch — one flat staging buffer, one call — and the
    returned burst carries the post-update state (``PendingBurst.
    state``); the input state is **consumed** (its residual buffers are
    donated to the in-place scatter) and must not be used again.  Tile
    contents equal to what the re-pad path would build give
    bitwise-identical decisions (``tests/test_incremental_state.py``).
    """
    n = batch.size
    if n == 0:
        if updates is not None:
            state = state.apply_updates(*updates)
        return PendingBurst(None, 0, state.layout, state=state)
    if updates is None:
        with TraceAnnotation("alloc.pack", dispatch=dispatch) as span:
            rows, recs, now32 = _packed_row_inputs(batch, window, now)
            staged = _nbytes(rows, recs, now32)
            span.set_metadata(bytes=staged)
        with TraceAnnotation("alloc.launch", dispatch=dispatch):
            outs = _state_dispatch(
                state.rc2, state.rm2, state.cc2, state.cm2,
                state.bsum_c, state.bsum_m, rows, recs, now32,
                alpha=alpha, beta=beta, policy=policy, mode=mode,
                backend=resolve_backend(backend), layout=state.layout,
            )
        return PendingBurst(outs, n, state.layout, state=state,
                            dispatch=dispatch, staged_bytes=staged)
    with TraceAnnotation("alloc.pack", dispatch=dispatch) as span:
        seg, n_idx, n_blk = device_state.pack_update_segment(
            updates[0], updates[1], updates[2],
            state.layout, int(state.rc2.shape[0]),
        )
        buf, n_rows, n_rec = _pack_state_step(batch, window, now, seg)
        span.set_metadata(bytes=buf.nbytes)
    with TraceAnnotation("alloc.launch", dispatch=dispatch):
        (rc2, rm2, bsum_c, bsum_m), outs = _state_step(
            state.rc2, state.rm2, state.cc2, state.cm2,
            state.bsum_c, state.bsum_m, state.mask2, buf,
            n_idx=n_idx, n_blk=n_blk, n_rows=n_rows, n_rec=n_rec,
            alpha=alpha, beta=beta, policy=policy, mode=mode,
            backend=resolve_backend(backend), layout=state.layout,
        )
    new_state = dataclasses.replace(
        state, rc2=rc2, rm2=rm2, bsum_c=bsum_c, bsum_m=bsum_m)
    return PendingBurst(outs, n, state.layout, state=new_state,
                        dispatch=dispatch, staged_bytes=buf.nbytes)


class BurstReplay:
    """Per-task replay of one drained burst — the parity reference.

    The engine (``batch_allocation=False``) decides the same burst one
    dispatch per row, rebuilding the residual carry from its own
    incremental caches between decisions, while the demand/stamp carry
    (totals, stamped mask, head-of-line flag) advances through the same
    shared step function the fused core scans.  Decisions are therefore
    bit-for-bit identical to one fused dispatch — that is precisely what
    ``tests/test_batch_parity.py`` gates.
    """

    def __init__(self, batch, residual_cpu, residual_mem, window, now,
                 cap_cpu, cap_mem, *, alpha, beta, policy, mode,
                 layout=None):
        self._params = dict(alpha=alpha, beta=beta, policy=policy, mode=mode,
                            layout=layout)
        self._layout = layout
        res_c, res_m, cap_c, cap_m, rows, recs, now32 = _device_inputs(
            batch, residual_cpu, residual_mem, window, now, cap_cpu, cap_mem
        )
        pre = _burst_precompute(
            res_c, res_m, cap_c, cap_m,
            recs["rec_t_start"], recs["rec_cpu"], recs["rec_mem"],
            recs["rec_done"],
            rows["b_cpu"], rows["b_mem"], rows["b_wend"], rows["b_self"],
            now32, mode=mode, layout=layout,
        )
        (_, _, self._cc2, self._cm2, self._tot_c, self._tot_m,
         self._base_c, self._base_m, self._dlt_c, self._dlt_m) = pre
        self._rows = rows
        num_rows = rows["b_cpu"].shape[0]
        self._stamped = jnp.zeros((num_rows,), jnp.float32)
        self._blocked = jnp.bool_(False)

    def step(self, i: int, residual_cpu, residual_mem
             ) -> Tuple[Allocation, bool]:
        """Decide row ``i`` against the engine's current residuals."""
        rows = self._rows
        out, self._tot_c, self._tot_m, self._stamped, self._blocked = \
            _replay_step(
                jnp.asarray(residual_cpu, jnp.float32),
                jnp.asarray(residual_mem, jnp.float32),
                self._cc2, self._cm2, self._tot_c, self._tot_m,
                self._stamped, self._blocked,
                rows["b_cpu"], rows["b_mem"], rows["b_min_cpu"],
                rows["b_min_mem"], self._base_c, self._base_m,
                self._dlt_c, self._dlt_m,
                rows["b_self"], rows["b_attempt"], rows["b_pending"],
                jnp.int32(i),
                **self._params,
            )
        alloc_c, alloc_m, node, accept, attempted, scenario = \
            jax.device_get(out)
        node = federation.global_nodes(np.asarray(node), self._layout)
        return (
            Allocation(
                cpu=float(alloc_c),
                mem=float(alloc_m),
                node=int(node),
                feasible=bool(accept),
                scenario=SCENARIO_NAMES[int(scenario)],
            ),
            bool(attempted),
        )


def allocation_at(result: BatchAllocation, i: int) -> Allocation:
    """Row ``i`` of a batch result as a scalar ``Allocation``."""
    return Allocation(
        cpu=float(result.cpu[i]),
        mem=float(result.mem[i]),
        node=int(result.node[i]),
        feasible=bool(result.feasible[i]),
        scenario=SCENARIO_NAMES[int(result.scenario[i])],
    )


def decoded_rows(result: BatchAllocation):
    """Yield ``(feasible, attempted, alloc)`` per row of a batch result.

    The arrays are turned into Python values once for the whole result
    (``tolist`` gives the floats ``float(np.float32)`` gives); ``alloc``
    is the row's ``Allocation`` where it is feasible, else None.
    """
    names = [SCENARIO_NAMES[s] for s in result.scenario.tolist()]
    for feasible, attempted, cpu, mem, node, scenario in zip(
            result.feasible.tolist(), result.attempted.tolist(),
            result.cpu.tolist(), result.mem.tolist(), result.node.tolist(),
            names):
        # Positional: a third cheaper than keywords, once per bound row.
        alloc = (Allocation(cpu, mem, node, True, scenario) if feasible
                 else None)
        yield feasible, attempted, alloc


@dataclasses.dataclass
class AdaptiveAllocator:
    """ARAS — Algorithm 1, burst-at-a-time.

    ``allocate_batch`` runs the paper's ``for each task pod's resource
    request`` loop as one fused dispatch; rows rejected by the line-27
    acceptance gate come back ``feasible=False`` and the engine re-queues
    them until a cluster-state change — identical to the paper's blocking
    behaviour.  ``allocate`` is the same pipeline at batch size 1.
    ``backend`` selects the sequential core: ``auto`` | ``scan`` |
    ``pallas`` (see ``repro.kernels.alloc_scan``).  ``layout`` federates
    the burst across cluster shards (``repro.cluster.federation``) and
    ``cluster_sharding`` governs whether those shards are additionally
    laid out across devices (``auto``/``force`` when a device count
    divides the clusters, ``off`` never); ``layout=None`` is the legacy
    single-cluster path.
    """

    alpha: float = DEFAULT_ALPHA
    beta: float = DEFAULT_BETA
    placement: str = "worst_fit"
    backend: str = "auto"
    layout: FederatedLayout | None = None
    cluster_sharding: str = "auto"

    name: str = "aras"
    mode = "aras"

    def _mesh(self):
        return federation.resolve_mesh(self.layout, self.cluster_sharding)

    def allocate_batch(
        self,
        batch: TaskBatch,
        residual_cpu,
        residual_mem,
        window: TaskWindow,
        now: float,
        cap_cpu=None,
        cap_mem=None,
    ) -> BatchAllocation:
        """Precompute → sequential core → sync back **once**."""
        return self.issue_batch(batch, residual_cpu, residual_mem, window,
                                now, cap_cpu, cap_mem).wait()

    def issue_batch(self, batch, residual_cpu, residual_mem, window, now,
                    cap_cpu=None, cap_mem=None, *, dispatch: int = 0
                    ) -> PendingBurst:
        """``allocate_batch`` without the sync: returns the issued burst."""
        return _issue_burst(
            batch, residual_cpu, residual_mem, window, now,
            alpha=self.alpha, beta=self.beta, policy=self.placement,
            mode=self.mode, backend=self.backend,
            cap_cpu=cap_cpu, cap_mem=cap_mem,
            layout=self.layout, mesh=self._mesh(), dispatch=dispatch,
        )

    def create_state(self, residual_cpu, residual_mem, cap_cpu, cap_mem
                     ) -> DeviceResidualState:
        """Stage the cluster state on device once, for the incremental
        dispatch path (``allocate_batch_async``)."""
        return DeviceResidualState.create(
            residual_cpu, residual_mem, cap_cpu, cap_mem,
            self.layout, RES_PAD,
        )

    def allocate_batch_async(
        self,
        batch: TaskBatch,
        window: TaskWindow,
        now: float,
        *,
        state: DeviceResidualState,
        updates=None,
        dispatch: int = 0,
    ) -> PendingBurst:
        """Issue one fused dispatch against device-resident state.

        Returns a :class:`PendingBurst`; the caller overlaps host work
        with the in-flight dispatch and syncs via ``wait()``.  Requires
        the ``device_state`` capability path: ``state`` plus the pending
        ``updates`` dirty set (``(nodes, res_cpu, res_mem)``, folded
        into the same dispatch; the post-update state comes back on
        ``PendingBurst.state``) must mirror the residuals
        ``allocate_batch`` would have been handed.  ``dispatch`` only
        tags the burst's trace spans.
        """
        return _issue_state_burst(
            batch, state, window, now,
            alpha=self.alpha, beta=self.beta, policy=self.placement,
            mode=self.mode, backend=self.backend, updates=updates,
            dispatch=dispatch,
        )

    def begin_replay(
        self,
        batch: TaskBatch,
        residual_cpu,
        residual_mem,
        window: TaskWindow,
        now: float,
        cap_cpu=None,
        cap_mem=None,
    ) -> BurstReplay:
        return BurstReplay(
            batch, residual_cpu, residual_mem, window, now, cap_cpu, cap_mem,
            alpha=self.alpha, beta=self.beta, policy=self.placement,
            mode=self.mode, layout=self.layout,
        )

    def allocate(
        self,
        task: TaskSpec,
        snapshot: ClusterSnapshot,
        window: TaskWindow,
        now: float,
    ) -> Allocation:
        # Monitor (Alg. 2) for callers holding a raw snapshot; the engine's
        # hot path hands residuals straight from its incremental cache.
        residual_cpu, residual_mem = discovery.discover(snapshot)
        result = self.allocate_batch(
            TaskBatch.from_tasks([task], now), residual_cpu, residual_mem,
            window, now,
            cap_cpu=snapshot.allocatable_cpu, cap_mem=snapshot.allocatable_mem,
        )
        return allocation_at(result, 0)


@dataclasses.dataclass
class FCFSAllocator:
    """Baseline (§6.1.6): first-come-first-serve full-request allocation.

    No lifecycle look-ahead, no scaling: the task gets exactly its declared
    request when some node has room, else it waits for other pods to
    release resources.
    """

    placement: str = "worst_fit"
    backend: str = "auto"
    layout: FederatedLayout | None = None
    cluster_sharding: str = "auto"

    name: str = "fcfs"
    mode = "fcfs"

    def _mesh(self):
        return federation.resolve_mesh(self.layout, self.cluster_sharding)

    def allocate_batch(
        self,
        batch: TaskBatch,
        residual_cpu,
        residual_mem,
        window: TaskWindow,
        now: float,
        cap_cpu=None,
        cap_mem=None,
    ) -> BatchAllocation:
        """See ``AdaptiveAllocator.allocate_batch``."""
        return self.issue_batch(batch, residual_cpu, residual_mem, window,
                                now, cap_cpu, cap_mem).wait()

    def issue_batch(self, batch, residual_cpu, residual_mem, window, now,
                    cap_cpu=None, cap_mem=None, *, dispatch: int = 0
                    ) -> PendingBurst:
        """See ``AdaptiveAllocator.issue_batch``."""
        return _issue_burst(
            batch, residual_cpu, residual_mem, window, now,
            alpha=0.0, beta=0.0, policy=self.placement, mode=self.mode,
            backend=self.backend, cap_cpu=cap_cpu, cap_mem=cap_mem,
            layout=self.layout, mesh=self._mesh(), dispatch=dispatch,
        )

    def create_state(self, residual_cpu, residual_mem, cap_cpu, cap_mem
                     ) -> DeviceResidualState:
        """See ``AdaptiveAllocator.create_state``."""
        return DeviceResidualState.create(
            residual_cpu, residual_mem, cap_cpu, cap_mem,
            self.layout, RES_PAD,
        )

    def allocate_batch_async(
        self,
        batch: TaskBatch,
        window: TaskWindow,
        now: float,
        *,
        state: DeviceResidualState,
        updates=None,
        dispatch: int = 0,
    ) -> PendingBurst:
        """See ``AdaptiveAllocator.allocate_batch_async``."""
        return _issue_state_burst(
            batch, state, window, now,
            alpha=0.0, beta=0.0, policy=self.placement, mode=self.mode,
            backend=self.backend, updates=updates, dispatch=dispatch,
        )

    def begin_replay(
        self,
        batch: TaskBatch,
        residual_cpu,
        residual_mem,
        window: TaskWindow,
        now: float,
        cap_cpu=None,
        cap_mem=None,
    ) -> BurstReplay:
        return BurstReplay(
            batch, residual_cpu, residual_mem, window, now, cap_cpu, cap_mem,
            alpha=0.0, beta=0.0, policy=self.placement, mode=self.mode,
            layout=self.layout,
        )

    def allocate(
        self,
        task: TaskSpec,
        snapshot: ClusterSnapshot,
        window: TaskWindow,
        now: float,
    ) -> Allocation:
        residual_cpu, residual_mem = discovery.discover(snapshot)
        result = self.allocate_batch(
            TaskBatch.from_tasks([task], now), residual_cpu, residual_mem,
            window, now,
            cap_cpu=snapshot.allocatable_cpu, cap_mem=snapshot.allocatable_mem,
        )
        return allocation_at(result, 0)


# Registry entries (repro.api.registry.ALLOCATORS): the engine selects
# allocators by name and consults capability flags instead of
# string-matching — ``adaptive_scaling`` tells it to hand over the ARAS
# alpha/beta knobs; third-party allocators register the same way.

@ALLOCATORS.register(
    "aras",
    capabilities=("adaptive_scaling", "federation_aware",
                  "lifecycle_window", "device_state"),
    doc="ARAS (Alg. 1): lifecycle-window demand + Alg. 3 adaptive "
        "scaling")
def _build_aras(**kwargs) -> AdaptiveAllocator:
    return AdaptiveAllocator(**kwargs)


@ALLOCATORS.register(
    "adaptive_scaling",
    capabilities=("adaptive_scaling", "federation_aware",
                  "lifecycle_window", "device_state", "forecast"),
    doc="predictive ARAS: Alg. 3 priced against forecast-horizon demand "
        "(repro.forecast ghost record)")
def _build_adaptive_scaling(**kwargs) -> AdaptiveAllocator:
    """ARAS arithmetic, predictive demand window.

    The allocator itself is the unmodified :class:`AdaptiveAllocator`
    (mode ``"aras"`` — same fused kernel, bit-identical sequential
    core).  The ``forecast`` capability is what changes behaviour: the
    engine appends a *ghost record* to the knowledge-base window of
    every burst decision, carrying the expected resource demand of the
    forecast horizon (``repro.forecast.ArrivalForecaster.
    horizon_demand``).  Alg. 1's request accumulation then prices load
    that has not arrived yet, so Alg. 3's proportional cuts tighten
    quotas *ahead* of a predicted burst — pre-provisioning — instead of
    waiting for the burst to saturate the cluster.  Requires
    ``ForecastConfig.enabled`` (EngineConfig.validate enforces it).
    """
    return AdaptiveAllocator(name="adaptive_scaling", **kwargs)


@ALLOCATORS.register(
    "fcfs",
    aliases=("baseline",),
    capabilities=("federation_aware", "device_state"),
    doc="§6.1.6 baseline: first-come-first-serve full-request allocation")
def _build_fcfs(**kwargs) -> FCFSAllocator:
    # FCFS has no scaling knobs: accept-and-drop alpha/beta so callers
    # can hand every allocator the same kwargs.
    return FCFSAllocator(
        **{k: v for k, v in kwargs.items()
           if k in ("placement", "backend", "layout", "cluster_sharding")}
    )


def make_allocator(name: str, **kwargs) -> AdaptiveAllocator | FCFSAllocator:
    """Build a registered allocator by name (see ``ALLOCATORS``)."""
    return ALLOCATORS.get(name).factory(**kwargs)
