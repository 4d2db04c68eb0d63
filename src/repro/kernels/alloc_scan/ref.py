"""Sequential allocation core — ``lax.scan`` reference implementation.

One step = one task request of an arrival burst, decided against the
*carry*: residual tiles, O(1) cluster totals, the stamped-row mask (whose
records started mid-burst) and the head-of-line flag.  Everything O(T)
(knowledge-base window demand) and O(m)-reduction-per-step (cluster
totals) is hoisted out by the caller (``repro.core.allocator``):

* ``base_cpu/base_mem [B]`` — per-row in-window demand over the record
  table at its pre-burst ``t_start`` (one ``[B, T]`` masked reduction);
* ``delta_cpu/delta_mem [B, B]`` — the correction table:
  ``delta[i, j]`` is what row *j*'s record adds to row *i*'s window
  demand **iff** row *j* was accepted (stamped to ``t_start = now``)
  earlier in the burst, minus its pre-burst contribution already in
  ``base[i]``.  The scan consumes it with a triangular mask carried as
  ``stamped``: at step *i* only rows *j < i* can be stamped.
* ``tot_cpu/tot_mem`` — cluster residual totals, summed once and then
  debited O(1) per accepted row (Alg. 1 lines 15-18 maintained
  incrementally instead of re-reduced over ``[m]`` every step).

Residuals are shaped ``[num_blocks, LANE]`` (padding lanes carry
``RES_PAD`` so they never fit and never win an argmax).  Per-step
reductions are two-stage — a block-max along the lane axis, then tiny
argmaxes over block maxima — which keeps exact first-index tie semantics
(max/compare are exact in IEEE) while avoiding the fork-join cost of a
flat ``[m]`` argmax on CPU.  The Pallas kernel computes the same values
with flat max + min-index reductions; results are bit-identical.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.evaluation import FCFS_SCENARIO, EvalInputs, evaluate
from repro.core.placement import placement_key

# Lane width of the residual tiles ([num_blocks, LANE]); matches the TPU
# lane dimension so the Pallas kernel shares the layout.  Canonically
# defined by the federation layout module (which owns the tile layout and
# must stay import-cycle-free); re-exported here for the kernel callers.
from repro.cluster.federation import LANE, pad_tiles  # noqa: E402

# Padding residual: loses every argmax and never fits any request.
RES_PAD = -1e30


def _fold_sum(vec: jax.Array) -> jax.Array:
    """Static left-fold sum of a [K] vector (K up to 100) — exact order.

    The federated core and the Pallas kernel must agree bit-for-bit on
    the federation-wide total, so both reduce the per-shard totals in
    the same (unrolled, left-to-right) order.  At K=1 this is the
    identity — the legacy scalar total, untouched.
    """
    acc = vec[0]
    for k in range(1, vec.shape[0]):
        acc = acc + vec[k]
    return acc


def _tile_argmax(tiles: jax.Array, bmax: jax.Array, num_shards: int = 1
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Two-stage exact argmax over [nb, LANE] given its block maxima.

    Returns ``(block, offset, tiles[block])``.  First-max-index tie
    semantics in both stages — identical to a flat ``argmax`` and to the
    Pallas kernel's flat min-index reduction, since max/compare are
    exact.

    ``num_shards > 1`` runs the block stage per cluster shard and picks
    the winner with a cheap [K] cross-shard argmax reduce.  The block
    axis is cluster-major, so "first shard attaining the max, first
    block within it" is exactly the flat first-max block — federation
    changes where the reduction runs, not its result.
    """
    if num_shards == 1:
        blk = jnp.argmax(bmax)
    else:
        nb_per = bmax.shape[0] // num_shards
        smax = bmax.reshape(num_shards, nb_per)
        shard = jnp.argmax(jnp.max(smax, axis=1))  # cross-shard reduce
        within = jnp.argmax(
            jax.lax.dynamic_index_in_dim(smax, shard, 0, keepdims=False))
        blk = shard * nb_per + within
    row = jax.lax.dynamic_index_in_dim(tiles, blk, 0, keepdims=False)
    return blk, jnp.argmax(row), row


def alloc_step(carry, row, cap_cpu2, cap_mem2, *, alpha, beta, policy, mode):
    """Decide one request and debit the carry — the shared step semantics.

    Also used standalone (jitted at batch 1) by the engine's per-task
    replay mode, which reconstructs the carry from its own incremental
    caches between dispatches; the scan, the Pallas kernel and the replay
    therefore execute the same float32 arithmetic and agree bit-for-bit.

    Federated mode is selected by the carry's totals shape: scalar totals
    are the legacy single-cluster path, a ``[K]`` vector means K cluster
    shards laid out cluster-major along the block axis (uniform
    ``nb_per = nb // K`` blocks per shard — ``repro.cluster.federation``).
    The evaluator then sees the federation-wide total (exact static fold),
    argmaxes reduce per-shard then cross-shard, and an accept debits only
    the owning shard's total.
    """
    rc2, rm2, bmax, tot_c, tot_m, stamped, blocked = carry
    (cpu, mem, min_cpu, min_mem, base_c, base_m, d_c, d_m,
     self_slot, attempt_in, pending, rid) = row
    num_shards = tot_c.shape[0] if tot_c.ndim == 1 else 1
    federated = tot_c.ndim == 1
    # Head-of-line: once a pending row fails, later pending rows are
    # skipped (the seed's retry loop breaks at the first failure).
    attempt = attempt_in & ~(pending & blocked)
    if mode == "aras":
        # Alg. 1 lines 4-13: hoisted base + triangular mid-burst correction.
        req_c = base_c + jnp.sum(d_c * stamped)
        req_m = base_m + jnp.sum(d_m * stamped)
        # Alg. 1 lines 19-22: the max-residual-CPU node, via block maxima.
        blk, off, rc_blk = _tile_argmax(rc2, bmax, num_shards)
        re_max_cpu = rc_blk[off]
        re_max_mem = jax.lax.dynamic_index_in_dim(
            rm2, blk, 0, keepdims=False)[off]
        result = evaluate(
            EvalInputs(
                task_cpu=cpu,
                task_mem=mem,
                request_cpu=req_c,
                request_mem=req_m,
                total_residual_cpu=_fold_sum(tot_c) if federated else tot_c,
                total_residual_mem=_fold_sum(tot_m) if federated else tot_m,
                re_max_cpu=re_max_cpu,
                re_max_mem=re_max_mem,
            ),
            alpha,
        )
        alloc_c, alloc_m = result.cpu, result.mem
        scenario = result.scenario
        # Alg. 1 line 27 acceptance gate.
        ok = (alloc_c >= min_cpu) & (alloc_m >= min_mem + beta)
    else:  # fcfs: full declared request, placement-only feasibility
        alloc_c, alloc_m = cpu, mem
        scenario = jnp.int32(FCFS_SCENARIO)
        ok = jnp.bool_(True)

    key = placement_key(policy, rc2, rm2, alloc_c, alloc_m,
                        cap_cpu2, cap_mem2)
    pblk, poff, key_row = _tile_argmax(key, jnp.max(key, axis=1), num_shards)
    fits_any = key_row[poff] > -jnp.inf
    node = (pblk * LANE + poff).astype(jnp.int32)

    accept = attempt & ok & fits_any
    debit = accept.astype(rc2.dtype)
    rc2 = rc2.at[pblk, poff].add(-alloc_c * debit)
    rm2 = rm2.at[pblk, poff].add(-alloc_m * debit)
    if federated:
        # Only the shard owning the chosen block pays for the accept;
        # ``debit · onehot`` keeps the arithmetic identical to the scalar
        # path on the owner (·1.0) and a no-op elsewhere (·0.0).
        owner = pblk // (rc2.shape[0] // num_shards)
        onehot = (jnp.arange(num_shards) == owner).astype(rc2.dtype)
        tot_c = tot_c - alloc_c * debit * onehot
        tot_m = tot_m - alloc_m * debit * onehot
    else:
        tot_c = tot_c - alloc_c * debit
        tot_m = tot_m - alloc_m * debit
    if mode == "aras":
        # Only the debited block's maximum can have changed.
        bmax = bmax.at[pblk].set(jnp.max(
            jax.lax.dynamic_index_in_dim(rc2, pblk, 0, keepdims=False)))
    # mark_started: the accepted record now competes at t_start = now,
    # visible to every later row through its delta column.
    stamped = jnp.where(
        (jnp.arange(stamped.shape[0]) == rid) & (self_slot >= 0),
        debit, stamped,
    )
    blocked = blocked | (pending & attempt & ~(ok & fits_any))
    out = (
        alloc_c,
        alloc_m,
        jnp.where(fits_any, node, jnp.int32(-1)),
        accept,
        attempt,
        scenario,
    )
    return (rc2, rm2, bmax, tot_c, tot_m, stamped, blocked), out


def alloc_scan_ref(
    rc2: jax.Array,  # [nb, LANE] f32 residual CPU tiles (RES_PAD padded)
    rm2: jax.Array,  # [nb, LANE] f32
    cap_cpu2: jax.Array,  # [nb, LANE] f32 allocatable capacity tiles
    cap_mem2: jax.Array,  # [nb, LANE] f32
    tot_cpu: jax.Array,  # scalar f32 Σ residual cpu (real nodes only),
    #                      or [K] per-shard totals in federated mode
    tot_mem: jax.Array,  # scalar f32 (or [K])
    b_cpu: jax.Array,  # [B] f32 batch rows, admission order
    b_mem: jax.Array,  # [B] f32
    b_min_cpu: jax.Array,  # [B] f32
    b_min_mem: jax.Array,  # [B] f32
    base_cpu: jax.Array,  # [B] f32 hoisted pre-burst window demand
    base_mem: jax.Array,  # [B] f32
    delta_cpu: jax.Array,  # [B, B] f32 mid-burst stamp corrections
    delta_mem: jax.Array,  # [B, B] f32
    b_self: jax.Array,  # [B] int32 record slot, -1 = none
    b_attempt: jax.Array,  # [B] bool (False = padding row)
    b_pending: jax.Array,  # [B] bool (retry-queue row: head-of-line rules)
    *,
    alpha: float,
    beta: float,
    policy: str,
    mode: str,
):
    """Run the sequential core over a whole burst with ``lax.scan``."""
    num_rows = b_cpu.shape[0]
    init = (
        rc2,
        rm2,
        jnp.max(rc2, axis=1),
        tot_cpu,
        tot_mem,
        jnp.zeros((num_rows,), rc2.dtype),
        jnp.bool_(False),
    )
    rows = (b_cpu, b_mem, b_min_cpu, b_min_mem, base_cpu, base_mem,
            delta_cpu, delta_mem, b_self, b_attempt, b_pending,
            jnp.arange(num_rows, dtype=jnp.int32))

    def step(carry, row):
        return alloc_step(carry, row, cap_cpu2, cap_mem2,
                          alpha=alpha, beta=beta, policy=policy, mode=mode)

    _, outs = jax.lax.scan(step, init, rows)
    return outs
