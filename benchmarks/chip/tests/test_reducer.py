"""The trace reduction on small traces with known answers, the per-layer
readers on them, and one rehearsal of ``spans.py`` on the CPU."""
import gc
import json
from pathlib import Path

import pytest

import spans
import tracing
from _common import Context, dispatch_device_ms, host_ms, idle_pct
from run import reader

DATA = Path(__file__).resolve().parent / "data"

DEV = "/device:TPU:0"
HOST = "/host:CPU"
US = 1000


def synthetic():
    """A 100 us window: two steps, each with device work inside."""
    us = US
    return [
        (HOST, "python", "window", 0, 100 * us),
        (HOST, "python", "step:READY", 10 * us, 30 * us),
        (HOST, "python", "pace_wait", 40 * us, 20 * us),
        (HOST, "python", "step:COMPLETE", 60 * us, 10 * us),
        (HOST, "python", "step:READY", 70 * us, 20 * us),
        (DEV, "XLA Modules", "jit__state_step(1)", 20 * us, 10 * us),
        (DEV, "XLA Ops", "fusion.1", 20 * us, 4 * us),
        (DEV, "XLA Ops", "alloc_scan_pallas", 22 * us, 8 * us),
        (DEV, "XLA Modules", "jit__state_step(1)", 75 * us, 10 * us),
        (DEV, "XLA Ops", "alloc_scan_pallas", 75 * us, 10 * us),
        # outside the window: ignored
        (DEV, "XLA Ops", "fusion.1", 150 * us, 10 * us),
    ]


def context(red, dispatches=2, **counters):
    counters.setdefault("num_dispatches", dispatches)
    return Context(trace=red, dispatch_rows=[1] * dispatches,
                   counters=counters, nodes=5000, engine={}, peaks=None)


def test_synthetic_trace():
    red = tracing.Reduction(synthetic())
    assert red.window_s == pytest.approx(100e-6)
    # Busy: [20, 30) and [75, 85) -> 20 us.
    assert red.busy_s(0) == pytest.approx(20e-6)
    assert red.idle_share(0) == pytest.approx(0.8)
    assert len(red.kernel_events(0)) == 2
    assert len(red.fused_steps(0)) == 2
    # Steps: 30 - 10, 10 - 0, 20 - 10 us of host -> mean 40/3 us.
    ctx = context(red)
    assert host_ms(ctx) == pytest.approx(40 / 3 * 1e-3)
    assert dispatch_device_ms(ctx) == pytest.approx(10e-3)
    assert idle_pct(ctx) == pytest.approx(80.0)
    gaps = dict(red.idle_gaps(0))
    # Idle 80 us, in gaps [0, 20), [30, 75), [85, 100): step:READY
    # 10 + 10 + 5 + 5 = 30, pace_wait 20, step:COMPLETE 10, and 20 with
    # no span open (the harness before and after the steps).
    assert gaps["step:READY"] == pytest.approx(30e-6)
    assert gaps["pace_wait"] == pytest.approx(20e-6)
    assert gaps["step:COMPLETE"] == pytest.approx(10e-6)
    assert gaps["harness"] == pytest.approx(20e-6)
    assert sum(gaps.values()) == pytest.approx(80e-6)


def test_kernel_matched_by_the_ops_own_name():
    """The trace names an op by its whole instruction text; an op that
    reads the kernel's output names the kernel among its operands and is
    not a kernel event."""
    kernel = ("%alloc_scan_pallas.1 = (f32[1024]{0}, s32[1024]{0}) "
              "custom-call(f32[40,128]{1,0} %p0), "
              "custom_call_target=\"tpu_custom_call\"")
    copy = ("%copy.3 = f32[1024]{0} copy(f32[1024]{0} "
            "%get-tuple-element.alloc_scan_pallas.1)")
    fused = ("%bitcast_fusion = s32[2,1024]{1,0} fusion(%alloc_scan_pallas.1,"
             " %alloc_scan_pallas.2), kind=kLoop")
    events = [(HOST, "python", "window", 0, 100 * US),
              (DEV, "XLA Ops", kernel, 10 * US, 8 * US),
              (DEV, "XLA Ops", copy, 18 * US, 1 * US),
              (DEV, "XLA Ops", fused, 19 * US, 1 * US)]
    red = tracing.Reduction(events)
    assert [e[0] for e in red.kernel_events(0)] == [kernel]


def test_missing_names_read_nothing():
    events = [e for e in synthetic() if e[1] != "XLA Ops"]
    red = tracing.Reduction(events)
    assert red.busy_s(0) is None
    ctx = context(red)
    assert idle_pct(ctx) is None
    assert dispatch_device_ms(ctx) is None


# What the recorded trace's device idle time read by the harness's own
# spans before the reduction kept the program's (the trace has none).
RECORDED_IDLE_GAPS = {"step:READY": 0.38475335000000005,
                      "step:INJECT": 0.11283939000000001,
                      "episode_reset": 0.010162007,
                      "harness": 0.0037097090000000003}


def test_recorded_trace():
    """A short window of the burst cell, recorded on a TPU v5 lite.

    The file keeps the events the reduction reads (op names cut to the
    HLO instruction's name) and, beside them, the numbers the harness
    printed for that run.
    """
    rec = json.loads((DATA / "burst_trace.json").read_text())
    red = tracing.Reduction([tuple(e) for e in rec["events"]])
    kernels = red.kernel_events(0)
    # One kernel call and one fused step per dispatch of the window.
    assert len(kernels) == len(rec["dispatch_rows"])
    assert len(red.fused_steps(0)) == len(rec["dispatch_rows"])
    assert 0 < red.busy_s(0) < red.window_s
    gaps = red.idle_gaps(0, n=100)
    assert sum(s for _, s in gaps) == pytest.approx(
        red.window_s - red.busy_s(0), rel=1e-9)
    assert rec["device"]["busy_s"] == pytest.approx(red.busy_s(0))
    assert rec["device"]["window_s"] == pytest.approx(red.window_s)
    ctx = Context(trace=red, dispatch_rows=rec["dispatch_rows"],
                  counters={"num_dispatches": len(rec["dispatch_rows"])},
                  nodes=5000,
                  engine=json.loads((DATA.parent.parent / "configs"
                                     / "k8s-5k.json").read_text())["engine"],
                  peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    for name, metric in rec["metrics"].items():
        assert reader(name)(ctx) == pytest.approx(metric["value"]), name


def test_no_program_spans_gives_the_harness_breakdown():
    """On a trace without program spans, the idle time falls where the
    harness's own spans put it, as it did before the reduction kept the
    program's spans."""
    rec = json.loads((DATA / "burst_trace.json").read_text())
    red = tracing.Reduction([tuple(e) for e in rec["events"]])
    assert red.program == []
    assert dict(red.idle_gaps(0, n=100)) == pytest.approx(
        RECORDED_IDLE_GAPS, rel=1e-12)


# --------------------------------------------------------- program spans
def nested():
    """A 100 us window: a READY step that folds (an inline injection and
    a collection inside the fold) and dispatches once, then an INJECT
    step with a collection inside the injection.  The device runs
    40-48 us.  Program spans carry their metadata as a sixth element."""
    harness = [
        (HOST, "python", "window", 0, 100 * US),
        (HOST, "python", "step:READY", 10 * US, 50 * US),
        (HOST, "python", "step:INJECT", 70 * US, 20 * US),
        (DEV, "XLA Modules", "jit__state_step(1)", 40 * US, 8 * US),
        (DEV, "XLA Ops", "fusion.1", 40 * US, 8 * US),
    ]
    program = [
        ("engine.fold", 12, 30, {}),
        ("engine.inject", 14, 20, {"tasks": 3}),
        ("gc", 22, 26, {}),
        ("engine.stage", 30, 34, {"dispatch": 0, "rows": 3}),
        ("alloc.pack", 34, 36, {"dispatch": 0, "bytes": 4096}),
        ("alloc.launch", 36, 38, {"dispatch": 0}),
        ("alloc.wait", 38, 50, {"dispatch": 0}),
        ("engine.apply", 50, 58, {"dispatch": 0, "rows": 3}),
        ("engine.inject", 72, 88, {"tasks": 5}),
        ("gc", 80, 84, {}),
    ]
    return harness + [(HOST, "python", n, s * US, (e - s) * US, m)
                      for n, s, e, m in program]


def test_self_times():
    red = tracing.Reduction(nested())
    # The fold less its inline injection; the collection inside it is
    # not subtracted.
    assert red.self_ms(["engine.fold"]) == pytest.approx(12e-3)
    assert red.self_ms(["engine.inject"]) == pytest.approx(22e-3)
    assert red.self_ms(["alloc.launch", "alloc.wait"]) \
        == pytest.approx(14e-3)
    assert red.count("engine.inject") == 2
    assert red.meta("alloc.pack", "bytes") == [4096]
    assert red.gc_in == {"engine.fold": 4 * US, "engine.inject": 4 * US}
    # Launch returns at 38 us, the step runs 40-48 us, the wait returns
    # at 50 us.
    assert red.round_trip(0) == pytest.approx(
        {"queue_ms": 2e-3, "device_ms": 8e-3, "return_ms": 2e-3})
    # The harness's own spans stay apart from the program's.
    assert [h[0] for h in red.host] == ["step:READY", "step:INJECT"]


def test_round_trip_reads_nothing_on_a_count_mismatch():
    extra = (HOST, "python", "alloc.launch", 52 * US, 1 * US,
             {"dispatch": 1})
    assert tracing.Reduction(nested() + [extra]).round_trip(0) is None


def test_idle_by_innermost_span():
    red = tracing.Reduction(nested())
    gaps = {k: v * 1e9 / US for k, v in red.idle_gaps(0, n=100)}
    assert gaps == pytest.approx({
        "harness": 30, "step:READY": 4, "step:INJECT": 4,
        "engine.fold": 8, "engine.inject": 18, "gc": 8,
        "engine.stage": 4, "alloc.pack": 2, "alloc.launch": 2,
        "alloc.wait": 4, "engine.apply": 8})
    # Idle inside steps: [10, 40), [48, 60), [70, 90); of it, 8 us with
    # no program span or collection open.
    assert red.idle_in_steps(0) == (62 * US, 8 * US)
    steps = red.step_split()
    assert steps["step:READY"]["rest_ms"] == pytest.approx(4e-3)
    assert steps["step:INJECT"]["program_ms"] == pytest.approx(16e-3)


def test_summary_per_dispatch():
    line = spans.summary(tracing.Reduction(nested()), dispatches=1)
    assert line["per_dispatch_ms"] == pytest.approx(
        {"fold": 12e-3, "stage": 6e-3, "sync": 14e-3, "apply": 8e-3})
    assert line["inject_ms"] == pytest.approx(11e-3)
    assert line["staged_kb"] == pytest.approx(4.0)
    assert line["idle_in_steps_bare_share"] == pytest.approx(8 / 62)


# The new readers on the nested trace: one dispatch of 4,096 staged bytes.
READINGS = {"apply_host_ms.tput": 8e-3, "inject_host_ms.tput": 11e-3,
            "sync_wait_ms.lat": 14e-3, "stage_host_ms.lat": 6e-3,
            "staged_kb.tput": 4.0}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_span_readers(name):
    red = tracing.Reduction(nested())
    ctx = context(red, dispatches=1, staged_bytes=4096)
    assert reader(name)(ctx) == pytest.approx(READINGS[name])


@pytest.mark.parametrize("name", sorted(READINGS))
def test_span_readers_read_nothing_on_a_count_mismatch(name):
    """Two dispatches counted against one span of each kind (none of
    ``engine.inject``, whose reader divides by its own count)."""
    events = nested()
    if name == "inject_host_ms.tput":
        events = [e for e in events if e[2] != "engine.inject"]
    ctx = context(tracing.Reduction(events), dispatches=2,
                  staged_bytes=4096)
    assert reader(name)(ctx) is None


def test_capture_keeps_program_spans():
    """A traced block on the CPU: the program's spans come back with
    their metadata, a collection as a ``gc`` span, and a host event that
    follows neither convention is dropped."""
    from jax.profiler import TraceAnnotation

    events = []
    with tracing.capture(events):
        with TraceAnnotation("window"):
            with TraceAnnotation("engine.apply", dispatch=7, rows=3):
                gc.collect()
            with TraceAnnotation("Engine.Apply"):
                pass
    red = tracing.Reduction(events)
    names = [sp[0] for sp in red.program]
    assert "engine.apply" in names and "gc" in names
    assert all(n in ("engine.apply", "gc") for n in names), names
    assert red.meta("engine.apply", "rows") == [3]
    assert red.gc_in["engine.apply"] > 0


def test_rehearsal(capsys):
    """The whole tool at the burst cell's rehearsal size on the CPU: one
    span of each per-dispatch kind per dispatch of the window."""
    assert spans.main(["--workload", "k8s-5k.burst", "--seed", "7",
                       "--seconds", "1"]) == 2
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["dispatches"] > 0
    for name in spans.PARTS["stage"] + spans.PARTS["sync"] + (
            "engine.apply",):
        assert line["counts"][name] == line["dispatches"], name
    assert all(v > 0 for v in line["per_dispatch_ms"].values())
    assert line["staged_kb"] > 0 and line["inject_ms"] > 0
    assert line["traced"]["pods_per_s"] > 0
