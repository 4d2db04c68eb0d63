"""The six decision outputs cross to the host as one packed buffer.

Every jitted entry that decides (``_state_step``, ``_state_dispatch``,
``_packed_core_dispatch``) returns ``_pack_outs``' ``int32[6, pow2(B)]``,
and ``PendingBurst.wait`` fetches it in one transfer and unpacks it on
the host.  The pack and unpack round-trip bit for bit (subnormals, -0.0
and NaN payloads included), each path hands ``wait()`` one device array,
what ``wait()`` returns equals the six arrays of ``alloc_scan`` on the
same inputs, and ``fetched_bytes`` counts the one buffer.
"""
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.cluster.federation import FederatedLayout, global_nodes
from repro.core.allocator import (
    _burst_precompute,
    _core_dispatch,
    _device_inputs,
    _pack_outs,
    _unpack_outs,
    make_allocator,
)
from repro.core.evaluation import SCENARIO_NAMES
from repro.core.types import TaskBatch, TaskWindow
from repro.kernels.alloc_scan import resolve_backend

pytestmark = pytest.mark.tier1

N_NODES = 24
SPECIAL_F32 = np.array(
    [0.0, -0.0, 1e-45, -1e-45, 1.17e-38 / 3, np.finfo(np.float32).tiny,
     np.finfo(np.float32).max, np.inf, -np.inf, np.nan, 2500.0, -1.5],
    np.float32)


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _f32_rows(rng, width: int) -> np.ndarray:
    """Random bit patterns (NaN payloads among them) plus the specials."""
    out = rng.integers(-2**31, 2**31, width, dtype=np.int64).astype(
        np.int32).view(np.float32)
    k = min(width, SPECIAL_F32.size)
    out[:k] = SPECIAL_F32[:k]
    return out


@pytest.mark.parametrize("n,width", [(1, 1), (5, 8), (1000, 1024)])
def test_pack_unpack_round_trip_bit_for_bit(n, width):
    rng = np.random.default_rng(width)
    cpu, mem = _f32_rows(rng, width), _f32_rows(rng, width)[::-1].copy()
    node = rng.integers(-1, N_NODES, width).astype(np.int32)
    node[0] = -1
    codes = np.array(sorted(SCENARIO_NAMES), np.int32)
    scenario = codes[np.arange(width) % codes.size]
    feasible = rng.random(width) < 0.5
    attempted = rng.random(width) < 0.5
    packed = jax.jit(_pack_outs)(cpu, mem, node, feasible, attempted,
                                 scenario)
    assert packed.shape == (6, width) and packed.dtype == jnp.int32
    got = _unpack_outs(np.asarray(packed), n)
    want = (cpu, mem, node, feasible, attempted, scenario)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == (n,)
        assert np.array_equal(g.view(np.uint8), w[:n].view(np.uint8))


def _cluster(rng):
    cap_cpu = rng.uniform(1000.0, 4000.0, N_NODES).astype(np.float32)
    cap_mem = rng.uniform(2000.0, 8000.0, N_NODES).astype(np.float32)
    res_cpu = (cap_cpu * rng.uniform(0.2, 1.0, N_NODES)).astype(np.float32)
    res_mem = (cap_mem * rng.uniform(0.2, 1.0, N_NODES)).astype(np.float32)
    return res_cpu, res_mem, cap_cpu, cap_mem


def _burst(rng, b: int, t: int, now: float):
    cpu = rng.uniform(100.0, 2500.0, b).astype(np.float32)
    mem = rng.uniform(200.0, 5000.0, b).astype(np.float32)
    batch = TaskBatch(
        cpu=cpu, mem=mem,
        min_cpu=(cpu * 0.25).astype(np.float32),
        min_mem=(mem * 0.25).astype(np.float32),
        window_end=rng.uniform(5.0, 50.0, b).astype(np.float32),
        self_slot=rng.permutation(t)[:b].astype(np.int32),
        pending=rng.random(b) < 0.4,
    )
    window = TaskWindow(
        t_start=rng.uniform(0.0, now + 10.0, t).astype(np.float32),
        cpu=rng.uniform(100.0, 800.0, t).astype(np.float32),
        mem=rng.uniform(200.0, 1500.0, t).astype(np.float32),
        done=rng.uniform(size=t) < 0.3,
    )
    return batch, window


def _six_arrays(alloc, batch, res_cpu, res_mem, window, now, cap_cpu,
                cap_mem):
    """The same burst through ``alloc_scan``'s six outputs, unpacked."""
    res_c, res_m, cap_c, cap_m, rows, recs, now32 = _device_inputs(
        batch, res_cpu, res_mem, window, now, cap_cpu, cap_mem)
    (rc2, rm2, cc2, cm2, tot_c, tot_m, base_c, base_m, dlt_c, dlt_m) = \
        _burst_precompute(
            res_c, res_m, cap_c, cap_m, recs["rec_t_start"],
            recs["rec_cpu"], recs["rec_mem"], recs["rec_done"],
            rows["b_cpu"], rows["b_mem"], rows["b_wend"], rows["b_self"],
            now32, mode=alloc.mode, layout=alloc.layout)
    outs = jax.device_get(_core_dispatch(
        rc2, rm2, cc2, cm2, tot_c, tot_m, rows["b_cpu"], rows["b_mem"],
        rows["b_min_cpu"], rows["b_min_mem"], base_c, base_m, dlt_c, dlt_m,
        rows["b_self"], rows["b_attempt"], rows["b_pending"],
        alpha=getattr(alloc, "alpha", 0.0), beta=getattr(alloc, "beta", 0.0),
        policy=alloc.placement,
        mode=alloc.mode, backend=resolve_backend(alloc.backend)))
    n = batch.size
    cpu, mem, node, feasible, attempted, scenario = (o[:n] for o in outs)
    return dict(cpu=cpu, mem=mem, node=global_nodes(node, alloc.layout),
                feasible=feasible, attempted=attempted, scenario=scenario)


@pytest.mark.parametrize("clusters", [1, 2])
@pytest.mark.parametrize("name", ["aras", "fcfs"])
@pytest.mark.parametrize("path", ["state_step", "state_dispatch",
                                  "core_dispatch"])
def test_one_packed_buffer_per_dispatch(path, name, clusters):
    seed = zlib.crc32(f"{path}/{name}/{clusters}".encode())
    rng = np.random.default_rng(seed)
    layout = (FederatedLayout.split(N_NODES, clusters) if clusters > 1
              else None)
    alloc = make_allocator(name, layout=layout, cluster_sharding="off")
    res_cpu, res_mem, cap_cpu, cap_mem = _cluster(rng)
    now, b = 4.0, 5
    if path == "core_dispatch":
        batch, window = _burst(rng, b, 9, now)
        pending = alloc.issue_batch(batch, res_cpu, res_mem, window, now,
                                    cap_cpu=cap_cpu, cap_mem=cap_mem)
    else:
        state = alloc.create_state(res_cpu, res_mem, cap_cpu, cap_mem)
        updates = None
        if path == "state_step":
            nodes = np.array([0, 3, N_NODES - 1])
            res_cpu[nodes] *= np.float32(0.5)
            res_mem[nodes] *= np.float32(0.25)
            updates = (nodes, res_cpu[nodes].copy(), res_mem[nodes].copy())
        batch, window = _burst(rng, b, 9, now)
        pending = alloc.allocate_batch_async(batch, window, now, state=state,
                                             updates=updates)
        assert (pending.state is state) == (updates is None)
    assert isinstance(pending.outs, jax.Array)
    assert pending.outs.shape == (6, _pow2(b))
    assert pending.outs.dtype == jnp.int32
    assert pending.fetched_bytes == 24 * _pow2(b)
    got = pending.wait()
    want = _six_arrays(alloc, batch, res_cpu, res_mem, window, now,
                       cap_cpu, cap_mem)
    for field, w in want.items():
        g = getattr(got, field)
        assert g.dtype == w.dtype, field
        assert np.array_equal(g.view(np.uint8), w.view(np.uint8)), field


def test_empty_burst_fetches_nothing():
    rng = np.random.default_rng(7)
    alloc = make_allocator("aras")
    res_cpu, res_mem, cap_cpu, cap_mem = _cluster(rng)
    batch, window = _burst(rng, 0, 3, 1.0)
    pending = alloc.issue_batch(batch, res_cpu, res_mem, window, 1.0)
    assert pending.outs is None and pending.fetched_bytes == 0
    assert pending.wait().size == 0
