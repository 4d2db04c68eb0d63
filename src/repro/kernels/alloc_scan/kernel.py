"""Burst allocation scan (decide → debit → place) — Pallas TPU.

The sequential core of ``repro.core.allocator``: B task requests walk the
carry (residual tiles, scalar totals, stamped mask, head-of-line flag) in
admission order.  TPU-native blocking follows ``mamba_scan``: the grid's
single (minor, sequential) dimension walks row chunks; the carry lives in
VMEM/SMEM scratch for the whole burst (never returns to HBM), the row
scalars sit whole in SMEM, and each chunk streams only its ``[chunk, B]``
slab of the mid-burst correction tables.  Within a chunk the recurrence
is a short ``fori_loop``; every step is branchless — the Alg. 3
evaluator lattice, the placement key and both argmaxes (flat max +
min-index, exact first-index tie semantics) are VPU element-wise ops
over the resident ``[num_blocks, LANE]`` residual tiles.

Decisions are bit-for-bit identical to ``ref.alloc_scan_ref``: max /
compare / select are exact, and all rounding arithmetic (demand
correction, evaluator, debits) uses the same float32 expressions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.evaluation import FCFS_SCENARIO, EvalInputs, evaluate
from repro.core.placement import placement_key

from repro.kernels.alloc_scan.ref import LANE

_BIG_I32 = 2**31 - 1  # python int: traced literals may not be captured
_SLAB_ELEMS = 1 << 19  # f32 elements in one buffered correction-table slab
_VMEM_DEFAULT = 16 << 20  # Mosaic's default scoped VMEM limit, bytes


def _vmem_limit(nb: int, chunk: int, width: int) -> int:
    """Scoped VMEM for one call, sized from its tiles.

    Compiled for v5e, a call holds eight whole ``[nb, 128]`` 32-bit
    tiles (the four inputs, the two residual carries, the flat index
    and one temporary of the step) beside the double-buffered pair of
    ``[chunk, width]`` slabs.  The default limit holds that up to about
    450,000 padded nodes at 1,024 rows; above, the limit grows with the
    tiles, a quarter over what they take.
    """
    need = 8 * nb * LANE * 4 + 2 * 2 * chunk * width * 4
    return max(_VMEM_DEFAULT, need + need // 4)


def _flat_argmax(x: jax.Array, flat_idx: jax.Array):
    """(max value, first flat index attaining it) — both exact."""
    m = jnp.max(x)
    idx = jnp.min(jnp.where(x == m, flat_idx,
                            jnp.full_like(flat_idx, _BIG_I32)))
    return m, idx


def _pick(x: jax.Array, flat_idx: jax.Array, idx: jax.Array) -> jax.Array:
    """Gather x[idx] from tiles via a one-hot masked sum (exact)."""
    return jnp.sum(jnp.where(flat_idx == idx, x, jnp.zeros_like(x)))


def _scan_kernel(
    # inputs
    rc2_ref, rm2_ref, cc2_ref, cm2_ref, tot_c_ref, tot_m_ref,
    cpu_ref, mem_ref, min_cpu_ref, min_mem_ref, base_c_ref, base_m_ref,
    dc_ref, dm_ref, self_ref, attempt_ref, pending_ref,
    # outputs
    alloc_c_ref, alloc_m_ref, node_ref, accept_ref, attempted_ref,
    scenario_ref,
    # scratch
    rc_s, rm_s, stamped_s, tot_s, blocked_s,
    *,
    chunk: int,
    alpha: float,
    beta: float,
    policy: str,
    mode: str,
):
    si = pl.program_id(0)
    nb, lane = rc_s.shape
    num_rows = stamped_s.shape[1]
    # Cluster shards (repro.cluster.federation): K per-shard totals in
    # SMEM, blocks cluster-major with a uniform nb // K blocks per shard.
    # The legacy single-cluster burst is simply K=1.  The shard loops
    # below unroll statically; at K = 100 (100 x 5,000 nodes) they are
    # about 500 scalar SMEM operations a row beside the vector work over
    # 4,000 blocks, and the kernel compiles for v5e in about 2 s.
    num_shards = tot_s.shape[1]
    shard_span = (nb // num_shards) * lane
    blk_ids = jax.lax.broadcasted_iota(jnp.int32, (nb, lane), 0)
    off_ids = jax.lax.broadcasted_iota(jnp.int32, (nb, lane), 1)
    flat_idx = blk_ids * lane + off_ids
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (1, num_rows), 1)[0]

    @pl.when(si == 0)
    def _init():
        rc_s[...] = rc2_ref[...]
        rm_s[...] = rm2_ref[...]
        stamped_s[...] = jnp.zeros_like(stamped_s)
        for k in range(num_shards):  # static unroll, once per call
            tot_s[0, k] = tot_c_ref[0, k]
            tot_s[1, k] = tot_m_ref[0, k]
        blocked_s[0] = jnp.int32(0)

    def step(t, _):
        rid = si * chunk + t
        rc2, rm2 = rc_s[...], rm_s[...]
        stamped = stamped_s[0]
        cpu, mem = cpu_ref[rid], mem_ref[rid]
        self_slot = self_ref[rid]
        pending = pending_ref[rid] != 0
        blocked = blocked_s[0] != 0
        attempt = (attempt_ref[rid] != 0) & ~(pending & blocked)
        if mode == "aras":
            req_c = base_c_ref[rid] + jnp.sum(dc_ref[t] * stamped)
            req_m = base_m_ref[rid] + jnp.sum(dm_ref[t] * stamped)
            re_max_cpu, imax = _flat_argmax(rc2, flat_idx)
            re_max_mem = _pick(rm2, flat_idx, imax)
            # Federation-wide totals: same static left-fold as the ref's
            # _fold_sum, so both backends re-associate identically
            # (K - 1 scalar adds a row).
            glob_c, glob_m = tot_s[0, 0], tot_s[1, 0]
            for k in range(1, num_shards):
                glob_c = glob_c + tot_s[0, k]
                glob_m = glob_m + tot_s[1, k]
            result = evaluate(
                EvalInputs(
                    task_cpu=cpu,
                    task_mem=mem,
                    request_cpu=req_c,
                    request_mem=req_m,
                    total_residual_cpu=glob_c,
                    total_residual_mem=glob_m,
                    re_max_cpu=re_max_cpu,
                    re_max_mem=re_max_mem,
                ),
                alpha,
            )
            alloc_c, alloc_m = result.cpu, result.mem
            scenario = result.scenario
            ok = ((alloc_c >= min_cpu_ref[rid])
                  & (alloc_m >= min_mem_ref[rid] + beta))
        else:  # fcfs
            alloc_c, alloc_m = cpu, mem
            scenario = jnp.int32(FCFS_SCENARIO)
            ok = jnp.bool_(True)

        key = placement_key(policy, rc2, rm2, alloc_c, alloc_m,
                            cc2_ref[...], cm2_ref[...])
        kmax, node = _flat_argmax(key, flat_idx)
        fits_any = kmax > -jnp.inf

        accept = attempt & ok & fits_any
        debit = accept.astype(rc2.dtype)
        hit = flat_idx == node
        rc_s[...] = rc2 - jnp.where(hit, alloc_c * debit, 0.0)
        rm_s[...] = rm2 - jnp.where(hit, alloc_m * debit, 0.0)
        # Debit the owning shard only (static unroll, branchless: the
        # indicator is 1.0 on the owner, 0.0 elsewhere — exact either way;
        # a compare and two read-modify-writes per shard a row).
        owner = node // shard_span
        for k in range(num_shards):
            ind = (owner == k).astype(rc2.dtype)
            tot_s[0, k] = tot_s[0, k] - alloc_c * debit * ind
            tot_s[1, k] = tot_s[1, k] - alloc_m * debit * ind
        stamped_s[0] = jnp.where((row_ids == rid) & (self_slot >= 0),
                                 debit, stamped)
        blocked_s[0] = (blocked | (pending & attempt & ~(ok & fits_any))
                        ).astype(jnp.int32)

        alloc_c_ref[rid] = alloc_c
        alloc_m_ref[rid] = alloc_m
        node_ref[rid] = jnp.where(fits_any, node, jnp.int32(-1))
        accept_ref[rid] = accept.astype(jnp.int32)
        attempted_ref[rid] = attempt.astype(jnp.int32)
        scenario_ref[rid] = scenario
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)


@functools.partial(
    jax.jit,
    static_argnames=("chunk", "alpha", "beta", "policy", "mode", "interpret"),
)
def alloc_scan_pallas(
    rc2: jax.Array,  # [nb, LANE] f32 residual tiles (RES_PAD padded)
    rm2: jax.Array,
    cap_cpu2: jax.Array,
    cap_mem2: jax.Array,
    tot_cpu: jax.Array,  # scalar f32, or [K] per-shard federated totals
    tot_mem: jax.Array,
    b_cpu: jax.Array,  # [B] f32
    b_mem: jax.Array,
    b_min_cpu: jax.Array,
    b_min_mem: jax.Array,
    base_cpu: jax.Array,  # [B] f32 hoisted window demand
    base_mem: jax.Array,
    delta_cpu: jax.Array,  # [B, B] f32
    delta_mem: jax.Array,
    b_self: jax.Array,  # [B] int32
    b_attempt: jax.Array,  # [B] int32 (bools as ints for ref-friendliness)
    b_pending: jax.Array,  # [B] int32
    *,
    chunk: int = 128,
    alpha: float,
    beta: float,
    policy: str,
    mode: str,
    interpret: bool = False,
):
    """Returns (alloc_cpu, alloc_mem, node, accept, attempted, scenario)."""
    num_rows = b_cpu.shape[0]
    nb, lane = rc2.shape
    assert lane == LANE, (lane, LANE)
    # Wide bursts stream fewer correction-table rows per grid step, so the
    # double-buffered pair of [chunk, B] slabs stays within 8 MiB of VMEM
    # (the sublane tiling floors a partial slab at 8 rows).
    chunk = min(chunk, num_rows, max(8, _SLAB_ELEMS // delta_cpu.shape[1]))
    assert num_rows % chunk == 0, (num_rows, chunk)
    grid = (num_rows // chunk,)
    # Scalar legacy totals become a K=1 federation; [K] vectors carry one
    # total per cluster shard (blocks cluster-major, nb % K == 0).
    tot_c2 = jnp.atleast_1d(tot_cpu).reshape(1, -1)
    tot_m2 = jnp.atleast_1d(tot_mem).reshape(1, -1)
    num_shards = tot_c2.shape[1]
    assert nb % num_shards == 0, (nb, num_shards)

    whole = pl.BlockSpec((nb, lane), lambda si: (0, 0))
    scalar = pl.BlockSpec((1, num_shards), lambda si: (0, 0),
                          memory_space=pltpu.SMEM)
    # Row scalars are read and written one at a time at a dynamic index,
    # which Mosaic allows only in SMEM.  Each row array is one whole
    # block: a [chunk] block of a 1-D array need not match XLA's tiling
    # of that array, a whole one always does.
    row = pl.BlockSpec(memory_space=pltpu.SMEM)
    # Correction-table slab: [chunk, B] for ARAS, width-1 placeholder
    # (never read) in FCFS mode.
    slab = pl.BlockSpec((chunk, delta_cpu.shape[1]), lambda si: (si, 0))

    outs = pl.pallas_call(
        functools.partial(
            _scan_kernel, chunk=chunk, alpha=alpha, beta=beta,
            policy=policy, mode=mode,
        ),
        grid=grid,
        in_specs=[
            whole, whole, whole, whole, scalar, scalar,
            row, row, row, row, row, row,
            slab, slab, row, row, row,
        ],
        out_specs=[row, row, row, row, row, row],
        out_shape=[
            jax.ShapeDtypeStruct((num_rows,), jnp.float32),
            jax.ShapeDtypeStruct((num_rows,), jnp.float32),
            jax.ShapeDtypeStruct((num_rows,), jnp.int32),
            jax.ShapeDtypeStruct((num_rows,), jnp.int32),
            jax.ShapeDtypeStruct((num_rows,), jnp.int32),
            jax.ShapeDtypeStruct((num_rows,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((nb, lane), jnp.float32),
            pltpu.VMEM((nb, lane), jnp.float32),
            pltpu.VMEM((1, num_rows), jnp.float32),
            pltpu.SMEM((2, num_shards), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit(
            nb, chunk, delta_cpu.shape[1])),
        interpret=interpret,
    )(
        rc2, rm2, cap_cpu2, cap_mem2,
        tot_c2, tot_m2,
        b_cpu, b_mem, b_min_cpu, b_min_mem, base_cpu, base_mem,
        delta_cpu, delta_mem,
        b_self, b_attempt, b_pending,
    )
    alloc_c, alloc_m, node, accept, attempted, scenario = outs
    return (alloc_c, alloc_m, node, accept.astype(bool),
            attempted.astype(bool), scenario)
