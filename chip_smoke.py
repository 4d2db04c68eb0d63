"""Serve a seeded stream through the scheduler on one TPU and check it.

The path is the one a scheduler user runs: arrivals go through
``repro.serving.StreamEngine``, then ``KubeAdaptor``'s windowed drain,
then the fused maintain-and-decide dispatch
(``repro.core.allocator._state_step``).  On a TPU, ``backend="auto"``
makes its sequential core the compiled Pallas kernel
(``repro.kernels.alloc_scan``).

Cluster: 5,000 nodes of 8,000 m / 16,000 Mi, the largest single cluster
Kubernetes documents ("Considerations for large clusters"), with
``AllocatorConfig()`` defaults.  Traffic, drawn from ``--seed``: a
lockstep burst of 1,024 ready tasks at t=0, then 2,000 Poisson arrivals
of single-task workflows, so 3,024 pods to place.  ARAS and FCFS are
each served once with ``backend="auto"`` and compared, exactly, with two
references on the same chip: ``backend="scan"`` and the per-row replay
(``batch_allocation=False``).  Compared are every decision
(``alloc_trace``: time, task, quota, scenario), the node of every bind
and the makespan.

``--four-chips`` runs only the federation phase: K=4 clusters of 1,250
nodes with ``sharding="auto"`` across four chips, against the same
federation with ``sharding="off"``, for backends ``auto`` and ``scan``.

Usage::

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips

The last line is ``{"ok": true, "device": {...}}`` only on a TPU and only
when every comparison held.  Without a TPU the same phases run at a tiny
size as a rehearsal (the kernel in Pallas interpret mode), and the script
exits non-zero without that line.  The times printed are set-up
(compilation) and smoke wall times, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# The chip size (Kubernetes' single-cluster limit) and the rehearsal size.
FULL = {"nodes": 5_000, "burst": 1_024, "arrivals": 2_000}
TINY = {"nodes": 512, "burst": 64, "arrivals": 96}
CLUSTERS = 4  # federation phase: K clusters, one per chip


def make_stream(burst: int, arrivals: int, seed: int):
    """A lockstep burst at t=0, then Poisson single-task arrivals: the
    traffic of ``benchmarks/allocator_scale.py``."""
    from benchmarks.allocator_scale import _burst_spec, _stream_arrivals

    first = _burst_spec(burst, np.random.default_rng(seed))
    return [(0.0, first)] + _stream_arrivals(arrivals, seed=seed)


def engine_config(nodes: int, algorithm: str, clusters: int = 1):
    from repro.api import (AllocatorConfig, ClusterConfig, EngineConfig,
                           TimingConfig)

    return EngineConfig(
        cluster=ClusterConfig(num_nodes=nodes, node_cpu=8000.0,
                              node_mem=16000.0, num_clusters=clusters),
        alloc=AllocatorConfig(algorithm=algorithm),
        timing=TimingConfig(pod_startup_delay=1.0, cleanup_delay=1.0,
                            duration_multiplier=1.0),
        invariant_checks=False,
    )


def serve(cfg, size, seed):
    """Serve the stream once; returns what the comparisons read."""
    from repro.engine import KubeAdaptor
    from repro.serving import StreamEngine

    engine = KubeAdaptor(cfg)
    binds = []
    bind = engine.cluster.bind

    def spy(task, alloc, now, workflow_id=""):
        binds.append((workflow_id, task.task_id, alloc.node))
        return bind(task, alloc, now, workflow_id=workflow_id)

    engine.cluster.bind = spy
    stream = make_stream(size["burst"], size["arrivals"], seed)
    t0 = time.perf_counter()
    stats = StreamEngine(engine, stream).serve()
    return {
        "engine": engine,
        "trace": stats.metrics.alloc_trace,
        "binds": binds,
        "makespan": stats.metrics.makespan,
        "decisions": stats.decisions,
        "dispatches": stats.dispatches,
        "wall": time.perf_counter() - t0,
    }


def same(label: str, got: dict, ref: dict, pods: int) -> bool:
    """Exact comparison of decisions, bind nodes and makespan."""
    problems = []
    if len(got["binds"]) != pods:
        problems.append(f"{len(got['binds'])} of {pods} pods bound")
    for key in ("trace", "binds"):
        a, b = got[key], ref[key]
        if a != b:
            i = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                     min(len(a), len(b)))
            problems.append(f"{key} differs at entry {i} of {len(a)}/"
                            f"{len(b)}: {a[i:i + 1]} vs {b[i:i + 1]}")
    if got["makespan"] != ref["makespan"]:
        problems.append(f"makespan {got['makespan']!r} vs "
                        f"{ref['makespan']!r}")
    verdict = "equal" if not problems else "DIFFERENT: " + "; ".join(problems)
    print(f"  {label}: {len(got['trace'])} decisions, {len(got['binds'])} "
          f"binds, makespan {got['makespan']!r} -> {verdict}")
    return not problems


def fused_step_hlo(engine, burst: int):
    """Compile the engine's fused maintain-and-decide step for a burst of
    ``burst`` rows against its device-resident state; returns the
    compiled HLO text and the compile seconds (set-up time)."""
    import jax
    import jax.numpy as jnp

    from repro.core.allocator import _pow2, _state_step
    from repro.kernels.alloc_scan import resolve_backend

    alloc, state = engine.allocator, engine._state
    n_idx = n_blk = 8
    n_rows = n_rec = _pow2(burst)
    buf = jax.ShapeDtypeStruct(
        (3 * n_idx + n_blk + 8 * n_rows + 4 * n_rec + 1,), jnp.float32)
    t0 = time.perf_counter()
    compiled = _state_step.lower(
        state.rc2, state.rm2, state.cc2, state.cm2, state.bsum_c,
        state.bsum_m, state.mask2, buf,
        n_idx=n_idx, n_blk=n_blk, n_rows=n_rows, n_rec=n_rec,
        alpha=getattr(alloc, "alpha", 0.0), beta=getattr(alloc, "beta", 0.0),
        policy=alloc.placement, mode=alloc.mode,
        backend=resolve_backend(alloc.backend), layout=state.layout,
    ).compile()
    return compiled.as_text(), time.perf_counter() - t0


def one_chip_phase(size: dict, seed: int, on_tpu: bool) -> bool:
    from repro.kernels.alloc_scan import resolve_backend

    pods = size["burst"] + size["arrivals"]
    auto = resolve_backend("auto")
    print(f"resolve_backend('auto') -> {auto}")
    ok = auto == "pallas" or not on_tpu
    for algorithm in ("aras", "fcfs"):
        cfg = engine_config(size["nodes"], algorithm)
        if not on_tpu:  # rehearse the kernel itself, in interpret mode
            cfg = cfg.evolve(alloc_backend="pallas")
        print(f"[{algorithm}] {size['nodes']} nodes, {pods} pods, backend "
              f"{cfg.alloc.backend}")
        cold = serve(cfg, size, seed)
        run = serve(cfg, size, seed)
        print(f"  set-up: first serve, compilations included: "
              f"{cold['wall']:.3f} s")
        print(f"  smoke: serve wall {run['wall']:.3f} s, "
              f"{run['decisions']} decisions in {run['dispatches']} "
              f"dispatches")
        hlo, compile_s = fused_step_hlo(run["engine"], size["burst"])
        kernel = "tpu_custom_call" in hlo
        print(f"  fused _state_step at {size['burst']} rows: "
              f"tpu_custom_call={kernel} (set-up: compile {compile_s:.3f} s)")
        ok &= kernel or not on_tpu
        ok &= same("served vs its first serve", run, cold, pods)
        scan = serve(cfg.evolve(alloc_backend="scan"), size, seed)
        ok &= same("served vs backend=scan", run, scan, pods)
        replay = serve(cfg.evolve(batch_allocation=False), size, seed)
        ok &= same("served vs per-row replay", run, replay, pods)
    return ok


def devices_of(x) -> list:
    return sorted(d.id for d in x.devices())


def four_chip_phase(size: dict, seed: int, on_tpu: bool) -> bool:
    import jax

    import repro.core.allocator as allocator

    if jax.device_count() != CLUSTERS:
        print(f"the federation phase needs {CLUSTERS} devices, JAX has "
              f"{jax.device_count()}")
        return False
    pods = size["burst"] + size["arrivals"]
    ok = True
    core = allocator._packed_core_dispatch
    for algorithm in ("aras", "fcfs"):
        # Off the TPU "auto" is "scan": rehearse the kernel by name.
        for backend in ("auto" if on_tpu else "pallas", "scan"):
            cfg = engine_config(size["nodes"], algorithm, CLUSTERS).evolve(
                alloc_backend=backend)
            print(f"[{algorithm}, backend={backend}] K={CLUSTERS} x "
                  f"{size['nodes'] // CLUSTERS} nodes, {pods} pods")
            placed = {}

            def spy(rc2, *args, **kwargs):
                outs = core(rc2, *args, **kwargs)
                if not placed:
                    placed["tiles"] = devices_of(rc2)
                    placed["outputs"] = devices_of(outs)
                return outs

            allocator._packed_core_dispatch = spy
            try:
                sharded = serve(cfg.evolve(cluster_sharding="auto"), size,
                                seed)
            finally:
                allocator._packed_core_dispatch = core
            mesh = sharded["engine"].allocator._mesh()
            mesh_ids = (None if mesh is None
                        else sorted(d.id for d in mesh.devices.flat))
            print(f"  sharding=auto: mesh devices {mesh_ids}, "
                  f"device-resident state "
                  f"{sharded['engine']._use_device_state}, tiles on devices "
                  f"{placed.get('tiles')}, outputs on devices "
                  f"{placed.get('outputs')}; smoke: serve wall "
                  f"{sharded['wall']:.3f} s")
            off = serve(cfg.evolve(cluster_sharding="off"), size, seed)
            print(f"  sharding=off: tiles on devices "
                  f"{devices_of(off['engine']._state.rc2)}; smoke: serve "
                  f"wall {off['wall']:.3f} s")
            ok &= same("sharding=auto vs sharding=off", sharded, off, pods)
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the K=4 federation phase on four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the arrival stream")
    args = ap.parse_args()

    from repro.launch.cache import use_compile_cache

    cache = Path(use_compile_cache(ROOT))
    warm = len(list(cache.iterdir())) if cache.is_dir() else 0
    print(f"compile cache: {cache}")
    print(f"  entries at start: {warm}")
    import jax

    # Load repro through its API first: importing the kernel package
    # before the rest of repro is circular.
    import repro.api  # noqa: F401

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}")
    on_tpu = device["platform"] == "tpu"
    size = FULL if on_tpu else TINY
    if not on_tpu:
        print("no TPU: rehearsing every phase at a tiny size; no result")
    if args.four_chips:
        ok = four_chip_phase(size, args.seed, on_tpu)
    else:
        ok = one_chip_phase(size, args.seed, on_tpu)
    if not ok:
        print("FAILED: a comparison or a device check did not hold")
        return 1
    if not on_tpu:
        print("rehearsal passed; exiting non-zero without a TPU")
        return 2
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
