"""Pallas alloc-scan kernel ≡ the ``lax.scan`` reference, bit for bit.

Array-level parity over random bursts (all four placement policies, both
allocator modes, head-of-line pending rows, padding rows), plus an
engine-level end-to-end check that a full simulation driven through the
Pallas backend (interpret mode off-TPU) reproduces the scan backend's
metrics exactly.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.allocator import _burst_precompute, _core_dispatch
from repro.core.placement import PLACEMENT_POLICIES
from repro.engine import EngineConfig, TimingConfig, run_experiment
from repro.kernels.alloc_scan.kernel import alloc_scan_pallas

pytestmark = pytest.mark.tier1

FAST = EngineConfig(timing=TimingConfig(
    pod_startup_delay=1.0, cleanup_delay=1.0, duration_multiplier=1.0))


def _random_burst(seed, m=37, num_rec=16, num_rows=8):
    rng = np.random.default_rng(seed)
    res_cpu = rng.uniform(0, 8000, m).astype(np.float32)
    res_mem = rng.uniform(0, 16000, m).astype(np.float32)
    cap_cpu = np.full((m,), 8000.0, np.float32)
    cap_mem = np.full((m,), 16000.0, np.float32)
    rec_t = rng.uniform(0, 50, num_rec).astype(np.float32)
    rec_cpu = rng.uniform(0, 4000, num_rec).astype(np.float32)
    rec_mem = rng.uniform(0, 8000, num_rec).astype(np.float32)
    rec_done = rng.random(num_rec) < 0.3
    b_cpu = rng.uniform(100, 6000, num_rows).astype(np.float32)
    b_mem = rng.uniform(100, 12000, num_rows).astype(np.float32)
    b_min_cpu = (b_cpu * rng.uniform(0.1, 0.9, num_rows)).astype(np.float32)
    b_min_mem = (b_mem * rng.uniform(0.1, 0.9, num_rows)).astype(np.float32)
    b_wend = rng.uniform(0, 40, num_rows).astype(np.float32)
    slots = rng.permutation(num_rec)[:num_rows].astype(np.int32)
    slots[rng.random(num_rows) < 0.25] = -1
    b_attempt = rng.random(num_rows) < 0.9
    b_pending = rng.random(num_rows) < 0.4
    now = np.float32(10.0)
    return (res_cpu, res_mem, cap_cpu, cap_mem, rec_t, rec_cpu, rec_mem,
            rec_done, b_cpu, b_mem, b_min_cpu, b_min_mem, b_wend, slots,
            b_attempt, b_pending, now)


def _core_args(case, mode):
    """The sequential core's arguments for a random burst."""
    (res_cpu, res_mem, cap_cpu, cap_mem, rec_t, rec_cpu, rec_mem, rec_done,
     b_cpu, b_mem, b_min_cpu, b_min_mem, b_wend, slots, b_attempt,
     b_pending, now) = [jnp.asarray(x) for x in case]
    pre = _burst_precompute(
        res_cpu, res_mem, cap_cpu, cap_mem, rec_t, rec_cpu, rec_mem,
        rec_done, b_cpu, b_mem, b_wend, slots, now, mode=mode,
    )
    rc2, rm2, cc2, cm2, tot_c, tot_m, base_c, base_m, dlt_c, dlt_m = pre
    return (rc2, rm2, cc2, cm2, tot_c, tot_m,
            b_cpu, b_mem, b_min_cpu, b_min_mem, base_c, base_m, dlt_c, dlt_m,
            slots, b_attempt, b_pending)


def _run_backend(case, policy, mode, backend):
    return _core_dispatch(
        *_core_args(case, mode),
        alpha=0.8, beta=20.0, policy=policy, mode=mode, backend=backend,
    )


@pytest.mark.parametrize("policy", PLACEMENT_POLICIES)
@pytest.mark.parametrize("mode", ["aras", "fcfs"])
def test_kernel_matches_scan_ref(policy, mode):
    for seed in range(3):
        case = _random_burst(seed)
        ref = _run_backend(case, policy, mode, "scan")
        ker = _run_backend(case, policy, mode, "pallas")
        for name, a, b in zip(
                ("cpu", "mem", "node", "accept", "attempted", "scenario"),
                ref, ker):
            a, b = np.asarray(a), np.asarray(b)
            assert a.dtype.kind == b.dtype.kind, name
            assert (a == b).all(), (policy, mode, seed, name, a, b)


@pytest.mark.parametrize("mode", ["aras", "fcfs"])
def test_kernel_row_chunks_match_scan_ref(mode):
    """A burst walked in several grid steps (chunk < B): row scalars are
    read and written at their burst-wide index, the correction-table
    slab streams chunk by chunk."""
    case = _random_burst(3, num_rec=48, num_rows=32)
    args = _core_args(case, mode)
    ref = _run_backend(case, "worst_fit", mode, "scan")
    ker = alloc_scan_pallas(
        *args[:14], *(x.astype(jnp.int32) for x in args[14:]),
        chunk=8, alpha=0.8, beta=20.0, policy="worst_fit", mode=mode,
        interpret=True,
    )
    for name, a, b in zip(
            ("cpu", "mem", "node", "accept", "attempted", "scenario"),
            ref, ker):
        assert (np.asarray(a) == np.asarray(b)).all(), (mode, name, a, b)


@pytest.mark.parametrize("allocator", ["aras", "fcfs"])
def test_engine_end_to_end_kernel_parity(allocator):
    """Full simulation through the Pallas backend ≡ the scan backend."""
    for policy in PLACEMENT_POLICIES:
        runs = {}
        for backend in ("scan", "pallas"):
            cfg = FAST.evolve(placement=policy, alloc_backend=backend)
            runs[backend] = run_experiment("montage", [(0.0, 2)], allocator,
                                           seed=0, config=cfg)
        scan, pallas = runs["scan"], runs["pallas"]
        assert scan.alloc_trace == pallas.alloc_trace, (allocator, policy)
        assert scan.makespan == pallas.makespan
        assert scan.workflow_durations == pallas.workflow_durations
        assert scan.oom_events == pallas.oom_events


def test_unknown_backend_raises():
    from repro.kernels.alloc_scan import resolve_backend
    with pytest.raises(ValueError, match="unknown alloc backend"):
        resolve_backend("cuda")
    assert resolve_backend("scan") == "scan"
    assert resolve_backend("pallas") == "pallas"
    assert resolve_backend("auto") in ("scan", "pallas")
