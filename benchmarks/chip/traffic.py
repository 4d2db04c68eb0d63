"""The one traffic generator: a mix file's parameters to plain workflows.

A traffic mix is a JSON file under ``traffic/``.  Its keys:

``drive``
    ``"paced"``: one engine, arrivals placed on the wall clock at
    ``time_scale`` simulated seconds per wall second, after an unpaced
    warm-up prefix of ``warmup.arrivals`` arrivals.
    ``"episodes"``: closed loop; each episode serves the whole stream
    unpaced on a fresh engine, up to the last arrival's time.
``arrivals``
    ``{"kind": "poisson", "rate_per_s": r}``: single-pod workflows with
    exponential gaps (a count covering the run is drawn).
    ``{"kind": "lockstep", "bursts": n, "pods_per_burst": b,
    "interval_s": s}``: burst ``i`` is one workflow of ``b`` independent
    pods at ``i * s``.
    ``{"kind": "pattern", "counts": [...], "interval_s": s,
    "workflows": ["montage", ...]}``: ``counts[i]`` workflows at
    ``i * s``, shapes drawn round-robin from the list.
``pod``
    Per-pod sizes: each of ``cpu``, ``mem``, ``duration`` is a number or
    a ``[low, high]`` uniform range; ``min_cpu`` and ``min_mem`` numbers.
``rehearsal``
    Keys merged over the above for the CPU rehearsal at a tiny size.

Workflows come out as plain data, ``{"id", "tasks": {name: {"cpu",
"mem", "duration", "min_cpu", "min_mem"}}, "edges": [(parent, child)]}``
with virtual entrance/exit tasks of zero size, so the reference and the
program are fed the same numbers.  The draws follow the repository's own
generators (``benchmarks/allocator_scale.py`` ``_burst_spec`` and
``_stream_arrivals``, ``repro.workflows.dags``, ``make_task``), copied
here so that no later change to the program moves the yardstick.
"""
from __future__ import annotations

import copy
import math
from typing import List, Tuple

import numpy as np


def merge(base: dict, over: dict) -> dict:
    """``over`` merged into a copy of ``base``, nested dicts key by key."""
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def _draw(spec, rng: np.random.Generator) -> float:
    if isinstance(spec, (list, tuple)):
        return float(rng.uniform(spec[0], spec[1]))
    return float(spec)


def _pod(pod: dict, rng: np.random.Generator) -> dict:
    # Draw order: cpu, mem, duration (as the copied generators draw).
    cpu = _draw(pod["cpu"], rng)
    mem = _draw(pod["mem"], rng)
    duration = _draw(pod["duration"], rng)
    return {"cpu": cpu, "mem": mem, "duration": duration,
            "min_cpu": float(pod["min_cpu"]), "min_mem": float(pod["min_mem"])}


_VIRTUAL = {"cpu": 0.0, "mem": 0.0, "duration": 0.0, "min_cpu": 0.0,
            "min_mem": 0.0}


# ------------------------------------------------- Pegasus DAG shapes
# Small-scale variants of the Pegasus gallery workflows with virtual
# entrance and exit (paper §6.1.2, Fig. 4): Montage 21, Epigenomics 20,
# CyberShake 22, LIGO 23 tasks.

def _montage() -> Tuple[List[str], List[Tuple[str, str]]]:
    proj = [f"mProject_{i}" for i in range(4)]
    diff = [f"mDiffFit_{i}" for i in range(5)]
    bg = [f"mBackground_{i}" for i in range(4)]
    names = (["entrance"] + proj + diff + ["mConcatFit", "mBgModel"] + bg
             + ["mImgtbl", "mAdd", "mShrink", "mJPEG", "exit"])
    edges = [("entrance", p) for p in proj]
    for i, d in enumerate(diff):
        edges += [(proj[i % 4], d), (proj[(i + 1) % 4], d)]
    edges += [(d, "mConcatFit") for d in diff]
    edges.append(("mConcatFit", "mBgModel"))
    edges += [("mBgModel", b) for b in bg]
    edges += [(b, "mImgtbl") for b in bg]
    edges += [("mImgtbl", "mAdd"), ("mAdd", "mShrink"), ("mShrink", "mJPEG"),
              ("mJPEG", "exit")]
    return names, edges


def _epigenomics():
    stages = ["filterContams", "sol2sanger", "fastq2bfq", "map"]
    names = ["entrance", "fastqSplit"]
    edges = [("entrance", "fastqSplit")]
    for lane in range(4):
        prev = "fastqSplit"
        for s in stages:
            names.append(f"{s}_{lane}")
            edges.append((prev, f"{s}_{lane}"))
            prev = f"{s}_{lane}"
        edges.append((prev, "mapMerge"))
    names += ["mapMerge", "exit"]
    edges.append(("mapMerge", "exit"))
    return names, edges


def _cybershake():
    extract = [f"ExtractSGT_{i}" for i in range(2)]
    synth = [f"SeisSynth_{i}" for i in range(15)]
    peak = [f"PeakValCalc_{i}" for i in range(2)]
    names = ["entrance"] + extract + synth + peak + ["ZipSeis", "exit"]
    edges = [("entrance", e) for e in extract]
    for i, s in enumerate(synth):
        edges += [(extract[i % 2], s), (s, peak[i % 2]), (s, "ZipSeis")]
    edges += [(p, "exit") for p in peak]
    edges.append(("ZipSeis", "exit"))
    return names, edges


def _ligo():
    tmplt = [f"TmpltBank_{i}" for i in range(8)]
    insp = [f"Inspiral_{i}" for i in range(8)]
    trig = [f"TrigBank_{i}" for i in range(2)]
    thinca = [f"Thinca_{i}" for i in range(2)]
    names = ["entrance"] + tmplt + insp + trig + thinca + ["Coire", "exit"]
    edges = [("entrance", t) for t in tmplt]
    edges += [(tmplt[i], insp[i]) for i in range(8)]
    for i, t in enumerate(trig):
        edges += [(insp[4 * i + j], t) for j in range(4)]
    edges += [(trig[i], thinca[i]) for i in range(2)]
    edges += [(t, "Coire") for t in thinca]
    edges.append(("Coire", "exit"))
    return names, edges


DAGS = {"montage": _montage, "epigenomics": _epigenomics,
        "cybershake": _cybershake, "ligo": _ligo}


def _dag(kind: str, wf_id: str, pod: dict, rng) -> dict:
    names, edges = DAGS[kind]()
    tasks = {}
    for name in names:
        tasks[name] = (dict(_VIRTUAL) if name in ("entrance", "exit")
                       else _pod(pod, rng))
    return {"id": wf_id, "tasks": tasks, "edges": edges}


# --------------------------------------------------------------- entry
def arrivals(mix: dict, seed: int, seconds: float) -> List[Tuple[float, dict]]:
    """Time-sorted ``(t, workflow)`` list for one run of ``seconds``.

    Poisson streams cover the warm-up prefix plus half again the arrivals
    the measured window can take, so the stream never runs dry.
    """
    rng = np.random.default_rng(seed)
    spec, pod = mix["arrivals"], mix["pod"]
    kind = spec["kind"]
    out: List[Tuple[float, dict]] = []
    if kind == "poisson":
        rate = float(spec["rate_per_s"])
        scale = float(mix.get("time_scale", 1.0))
        warm = int(mix.get("warmup", {}).get("arrivals", 0))
        count = warm + int(math.ceil(1.5 * rate * seconds * scale)) + 64
        t = 0.0
        for i in range(count):
            t += float(rng.exponential(1.0 / rate))
            out.append((t, {"id": f"s{i}", "tasks": {f"t{i}": _pod(pod, rng)},
                            "edges": []}))
    elif kind == "lockstep":
        b, step = int(spec["pods_per_burst"]), float(spec["interval_s"])
        for k in range(int(spec["bursts"])):
            tasks = {f"t{k * b + i}": _pod(pod, rng) for i in range(b)}
            out.append((k * step, {"id": f"b{k}", "tasks": tasks,
                                   "edges": []}))
    elif kind == "pattern":
        shapes, n = spec["workflows"], 0
        for k, count in enumerate(spec["counts"]):
            for _ in range(int(count)):
                out.append((k * float(spec["interval_s"]),
                            _dag(shapes[n % len(shapes)], f"w{n}", pod, rng)))
                n += 1
    else:
        raise ValueError(f"unknown arrival kind {kind!r}")
    return out


def pod_count(stream) -> int:
    """Pods (non-virtual tasks) in a stream."""
    return sum(1 for _, wf in stream for task in wf["tasks"].values()
               if task["cpu"] or task["mem"])

