#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root
of the checkout.  Everything that belongs to it is found by name: its
configuration in ``configs/<config>.json``, its traffic mix in
``traffic/<traffic>.json``, and each per-layer metric's reader in
``metrics/<metric>.py``.

A run makes its inputs from ``--seed``, warms up every shape its traffic
uses (set-up), measures for ``--seconds``, then checks every decision of
the timed path it samples against the plain reference
(``reference.py``).  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
and, with ``--trace 1``, ``breakdown``, then ``checks`` (each number
compared, with its limit), which the last lines of standard error
repeat.  With ``--trace 0`` the metrics are the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, from a profiler trace
of the window.

Without a TPU the cell is rehearsed at a tiny size on the CPU (the
Pallas kernel in interpret mode) and the run exits 2 without a result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional, Tuple  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE / "metrics"))
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

import drive  # noqa: E402
import tracing  # noqa: E402
import traffic  # noqa: E402
from reference import Reference  # noqa: E402

# Fixed inside the checkout: the path is part of each cached program's
# key, so only the first run of a cell in a checkout compiles.
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(name: str) -> dict:
    """The entry ``name`` of ``workloads`` in ``BENCHMARK.json``, with its
    configuration file, traffic mix and metrics attached."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    return attach(dict(cells[name]), bench)


def attach(cell: dict, bench: dict) -> dict:
    """``cell`` (``name``, ``config``, ``traffic``, ``chips``) with its
    configuration file, traffic mix and the metrics that list it."""
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cell["config_file"] = json.loads((ROOT / config["file"]).read_text())
    cell["mix"] = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())

    def listed(metric):
        return cell["name"] in metric.get("workloads", [cell["name"]])

    cell["end_to_end"] = [m for m in bench["end_to_end"] if listed(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if listed(m)]
    return cell


def mix_cell(config: str, traffic_name: str) -> dict:
    """A cell of a configuration in ``BENCHMARK.json`` under any traffic
    mix file, on one chip, whether or not a workload names the pair (for
    sweeps and tests)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return attach({"name": f"{config}.{traffic_name}", "config": config,
                   "traffic": traffic_name, "chips": 1}, bench)


def reader(metric: str) -> Callable:
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


class CompileCounter(logging.Handler):
    """Counts programs built or loaded from the cache, by phase, and
    keeps what JAX logs about each compile inside the window."""

    NAMES = ("/jax/core/compile/backend_compile_duration",
             "/jax/compilation_cache/cache_retrieval_time_sec")
    # Tracing to a jaxpr and lowering it: a program looked up again.
    TRACES = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self):
        import jax

        super().__init__(logging.WARNING)
        self.phase = "setup"
        self.count: Dict[str, int] = {}
        self.seconds: Dict[str, float] = {}
        self.what: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._seen)
        logging.getLogger("jax").addHandler(self)

    def enter(self, phase: str) -> None:
        import jax

        self.phase = phase
        jax.config.update("jax_log_compiles", phase == "window")

    def _seen(self, name, duration, **kwargs):
        if name in self.TRACES:
            key = self.phase + ":traced"
            self.count[key] = self.count.get(key, 0) + 1
            self.seconds[key] = self.seconds.get(key, 0.0) + duration
        elif name in self.NAMES:
            self.count[self.phase] = self.count.get(self.phase, 0) + 1
            self.seconds[self.phase] = (self.seconds.get(self.phase, 0.0)
                                        + duration)

    def emit(self, record) -> None:
        if self.phase == "window" and "ompil" in record.getMessage()[:40]:
            self.what.append(record.getMessage()[:300])


class GcPauses:
    """Wall time of the interpreter's garbage collections while on."""

    def __init__(self):
        self.pauses: List[Tuple[int, float]] = []  # (generation, seconds)
        self._t = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)

    def summary(self) -> str:
        full = sum(1 for g, _ in self.pauses if g == 2)
        longest = max((d for _, d in self.pauses), default=0.0)
        total = sum(d for _, d in self.pauses)
        return (f"{len(self.pauses)} collections, {full} of the oldest "
                f"generation; longest {longest * 1e3:.3f} ms, "
                f"{total * 1e3:.3f} ms in all")


def failure(where: str, exc: Exception) -> str:
    """Log a failure of the program under test; the run reads incorrect."""
    import traceback

    log(f"the program raised {where}:\n{traceback.format_exc()}")
    return f"{type(exc).__name__}: {exc}"


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile (0-100), linear between closest ranks."""
    xs = sorted(values)
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def execute(cell: dict, seed: int, seconds: float, trace: bool,
            tiny: bool, phases: Optional[Dict[str, float]] = None):
    """One run; returns the result object (not printed) and the window.
    ``phases`` holds the set-up's marks so far (seconds since start)."""
    import jax

    from repro.api import EngineConfig

    cfile = cell["config_file"]
    engine = cfile["engine"]
    mix = cell["mix"]
    if tiny:
        engine = traffic.merge(engine, cfile.get("rehearsal", {})
                               .get("engine", {}))
        mix = traffic.merge(mix, mix.get("rehearsal", {}))
    cfg = EngineConfig.from_dict(engine)
    if tiny:  # off the chip, rehearse the kernel itself in interpret mode
        cfg = cfg.evolve(alloc_backend="pallas")
    compiles = CompileCounter()
    stream = traffic.arrivals(mix, seed, seconds)
    program = drive.to_program(stream)
    pods = traffic.pod_count(stream)
    kind = mix["drive"]
    driver = drive.Driver(cfg)
    window = drive.Window(seconds)
    events: list = []
    scale = float(mix.get("time_scale", 1.0))
    phases = dict(phases or {})
    phases["inputs"] = time.perf_counter() - T_START

    # ------------------------------------------------ set-up: warm up
    # Decisions, and so every shape the program compiles, depend only on
    # simulated time: serving the window's own traffic unpaced once
    # compiles (or loads from the cache) every program the window runs.
    error = None
    episode = drive.Episode()
    try:
        if kind == "paced":
            # The window's engine serves the warm-up prefix, so the window
            # starts with its traffic under way; a copy of it then serves
            # the window's arrivals unpaced and is thrown away.
            t_w = stream[int(mix["warmup"]["arrivals"]) - 1][0]
            horizon = t_w + seconds * scale
            eng = driver.engine(episode)
            nxt = driver.run_unpaced(eng, episode, program, 0, horizon=t_w)
            phases["prefix"] = time.perf_counter() - T_START
            twin_episode = drive.Episode()
            twin = driver.fork(eng, twin_episode)
            driver.run_unpaced(twin, twin_episode, program, nxt,
                               horizon=horizon)
            twin = None
        else:
            # Closed loop: one whole episode, up to the last arrival.
            horizon = stream[-1][0]
            driver.run_unpaced(driver.engine(drive.Episode()),
                               drive.Episode(), program, horizon=horizon)
        gc.collect()
    except Exception as exc:  # the program failed while warming up
        error = failure("warming up", exc)
    setup_s = time.perf_counter() - T_START
    phases["warm"] = setup_s
    log(f"set-up: {setup_s:.3f} s ({kind} drive, {pods} pods in the "
        f"stream); at " + ", ".join(f"{k} {v:.3f} s" for k, v in
                                    phases.items())
        + f"; {compiles.count.get('setup', 0)} programs compiled or loaded "
        f"in {compiles.seconds.get('setup', 0.0):.3f} s")

    # --------------------------------------------------- the window
    driver.tracing = trace
    compiles.enter("window")
    ctx_mgr = tracing.capture(events) if trace else contextlib.nullcontext()
    cpu0, wall0 = time.process_time(), time.perf_counter()
    with GcPauses() as pauses, ctx_mgr, driver.span("window"):
        try:
            if error is not None:
                window.episodes.append(episode)
            elif kind == "paced":
                window.episodes.append(episode)
                drive.paced(driver, eng, episode, program, nxt, t_w, scale,
                            seconds, window)
                if any(episode.binds.get(k, d) != d
                       for k, d in twin_episode.binds.items()):
                    log("the warm-up copy decided otherwise than the "
                        "window's engine")
            else:
                drive.closed(driver, horizon, seconds, window, program)
        except Exception as exc:  # the program failed inside the window
            error = failure("inside the window", exc)
    cpu_s, wall_s = time.process_time() - cpu0, time.perf_counter() - wall0
    compiles.enter("after")
    window_compiles = compiles.count.get("window", 0)

    devices = jax.devices()[: cell["chips"]]
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    eng = None
    gc.collect()

    # ------------------------------------- the check, after the window
    t_check = time.perf_counter()
    rng_pick = np.random.default_rng(seed)
    if kind == "episodes":
        # One finished episode drawn from the seed is checked in full;
        # every other finished one has to repeat its binds exactly.
        full = [e for e in window.episodes if e.complete] \
            or window.episodes[:1]
        checked = full[int(rng_pick.integers(len(full)))]
        readings = Reference(engine, stream).follow(
            checked.binds, horizon=checked.horizon)
        for other in full:
            if other is not checked:
                readings.mismatches += sum(
                    1 for k, d in checked.binds.items()
                    if other.binds.get(k) != d)
    else:
        checked = window.episodes[0]
        readings = Reference(engine, stream).follow(checked.binds,
                                                    horizon=checked.horizon)
    check_s = time.perf_counter() - t_check
    log(f"check: {readings.decisions} decisions of episode "
        f"{window.episodes.index(checked)} compared in {check_s:.3f} s; "
        f"{readings.mismatches} mismatches, {readings.missing} never "
        f"bound, quota gap {readings.quota_gap!r}, {readings.ambiguous} "
        f"at a threshold")
    if readings.first_mismatch:
        log(f"first mismatch: {readings.first_mismatch}")

    # ------------------------------------------------------ metrics
    log(f"compiles inside the window: {window_compiles}; traced or "
        f"lowered: {compiles.count.get('window:traced', 0)} in "
        f"{compiles.seconds.get('window:traced', 0.0):.3f} s")
    log(f"garbage collection in the window: {pauses.summary()}")
    log(f"process CPU time in the window: {cpu_s:.3f} s over {wall_s:.3f} s "
        f"of wall time, all threads")
    for what in compiles.what[:8]:
        log(f"  compiled in the window: {what}")
    log(f"episodes in the window: {len(window.episodes)} "
        f"({sum(e.complete for e in window.episodes)} completed)")
    if kind != "episodes":
        offered = window.offered / seconds
        achieved = window.submitted / seconds
        log(f"arrivals: offered {offered:.3f}/s, submitted {achieved:.3f}/s; "
            f"loop at most {window.late_s * 1e3:.3f} ms late; "
            f"{window.overrun_s:.3f} s past the close")
        log("slowest steps: " + "; ".join(
            f"{d * 1e3:.3f} ms {kind} at t={t:.3f} ({rows} rows)"
            for d, kind, t, rows in sorted(window.slowest, reverse=True)))
    log(f"dispatches in the window: {window.dispatches}, rows "
        f"{sum(window.dispatch_rows)}, steps {window.steps}")
    log("counters in the window: " + ", ".join(
        f"{k} {v}" for k, v in sorted(window.counters.items())))
    metrics: Dict[str, dict] = {}
    breakdown = None
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if not trace:
        values = {"setup_s": setup_s}
        if window.lags:
            lags_ms = [x * 1e3 for x in window.lags]
            values["bind_p50_ms"] = percentile(lags_ms, 50)
            values["bind_p95_ms"] = percentile(lags_ms, 95)
            log(f"bind lag over {len(lags_ms)} pods: p50 "
                f"{values['bind_p50_ms']!r} ms, p95 "
                f"{values['bind_p95_ms']!r} ms, p99 "
                f"{percentile(lags_ms, 99)!r} ms, mean "
                f"{statistics.fmean(lags_ms)!r} ms")
        if window.binds_in_window:
            values["pods_per_s"] = window.binds_in_window / seconds
        for m in cell["end_to_end"]:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        from _common import Context
        import kernel_cost
        import peaks as peak_table

        red = window.trace = tracing.Reduction(events)
        try:
            chip = peak_table.peaks(devices[0].device_kind)
        except KeyError:
            if not tiny:
                raise
            chip = None
        ctx = Context(trace=red, dispatch_rows=window.dispatch_rows,
                      counters=window.counters,
                      nodes=kernel_cost.kernel_nodes(
                          int(engine["cluster"]["num_nodes"]),
                          int(engine["cluster"]["num_clusters"])),
                      engine=engine, peaks=chip)
        for m in cell["per_layer"]:
            value = reader(m["name"])(ctx)
            if value is None:
                log(f"per-layer metric {m['name']}: nothing to read")
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        busy = [red.busy_s(d) or 0.0 for d in range(len(devices))]
        for d, b in enumerate(busy):
            log(f"device {d}: busy {b!r} s of {red.window_s!r} s")
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = red.window_s
        breakdown = {"device_ops": red.top_ops(0),
                     "idle_gaps": red.idle_gaps(0)}
        log(f"kernel events {len(red.kernel_events(0))}, fused steps "
            f"{len(red.fused_steps(0))}, step spans {len(red.steps)}")
        log("program spans: " + ", ".join(
            f"{name} {red.count(name)}"
            for name in sorted({sp[0] for sp in red.program})))

    limits = cell["config_file"]["check_limits"]
    checks = {
        "mismatches": {"value": readings.mismatches,
                       "limit": limits["mismatches"]},
        "never_bound": {"value": readings.missing,
                        "limit": limits["never_bound"]},
        "quota_gap": {"value": readings.quota_gap,
                      "limit": limits["quota_gap"]},
        "overcommits": {"value": readings.overcommits,
                        "limit": limits["overcommits"]},
    }
    correct = (readings.decisions > 0 and not window.capped
               and error is None
               and all(c["value"] <= c["limit"] for c in checks.values()))
    attempted = window.binds_in_window + readings.missing
    result = {"correct": correct, "attempted": attempted,
              "failed": readings.missing, "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result, window


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    import repro.api  # noqa: F401  (loads the kernel package in order)

    phases = {"imports": time.perf_counter() - T_START}
    devices = jax.devices()
    phases["devices"] = time.perf_counter() - T_START
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        log("no TPU: rehearsing the cell at a tiny size on the CPU; "
            "no result")
        execute(cell, args.seed, min(args.seconds, 2.0), bool(args.trace),
                tiny=True, phases=phases)
        return 2
    if len(devices) < cell["chips"]:
        log(f"the cell needs {cell['chips']} chips, JAX has "
            f"{len(devices)}; no result")
        return 3
    result, _ = execute(cell, args.seed, args.seconds, bool(args.trace),
                        tiny=False, phases=phases)
    print(json.dumps(result), flush=True)
    # The numbers compared, beside their limits, end standard error.
    for name, c in result["checks"].items():
        log(f"{name} {c['value']!r} limit {c['limit']!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
