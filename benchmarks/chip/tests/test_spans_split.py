"""The split of host time by the program's spans, on traces with known
answers, and one rehearsal of ``spans.py`` on the CPU."""
import json
from pathlib import Path

import pytest

import spans
import tracing

DATA = Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"
HOST = "/host:CPU"
US = 1000


def synthetic():
    """A 100 us window: a READY step that folds (an inline injection and
    a collection inside the fold) and dispatches once, then an INJECT
    step with a collection inside the injection.  The device runs
    40-48 us."""
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
    program = [(n, s * US, e * US, m) for n, s, e, m in program]
    return tracing.Reduction(harness), program


def test_self_times():
    split = spans.Split(*synthetic())
    # The fold less its inline injection; the collection inside it is
    # not subtracted.
    assert split.self_ms(["engine.fold"]) == pytest.approx(12e-3)
    assert split.self_ms(["engine.inject"]) == pytest.approx(22e-3)
    assert split.self_ms(["alloc.launch", "alloc.wait"]) \
        == pytest.approx(14e-3)
    assert split.count("engine.inject") == 2
    assert split.meta("alloc.pack", "bytes") == [4096]
    assert split.gc_in == {"engine.fold": 4 * US, "engine.inject": 4 * US}
    # Launch returns at 38 us, the step runs 40-48 us, the wait returns
    # at 50 us.
    assert split.round_trip(0) == pytest.approx(
        {"queue_ms": 2e-3, "device_ms": 8e-3, "return_ms": 2e-3})


def test_round_trip_reads_nothing_on_a_count_mismatch():
    red, program = synthetic()
    extra = ("alloc.launch", 52 * US, 53 * US, {"dispatch": 1})
    assert spans.Split(red, program + [extra]).round_trip(0) is None


def test_idle_by_innermost_span():
    split = spans.Split(*synthetic())
    gaps = {k: v / US for k, v in split.idle_gaps(0).items()}
    assert gaps == pytest.approx({
        "harness": 30, "step:READY": 4, "step:INJECT": 4,
        "engine.fold": 8, "engine.inject": 18, "gc": 8,
        "engine.stage": 4, "alloc.pack": 2, "alloc.launch": 2,
        "alloc.wait": 4, "engine.apply": 8})
    # Idle inside steps: [10, 40), [48, 60), [70, 90); of it, 8 us with
    # no program span or collection open.
    assert split.idle_in_steps(0) == (62 * US, 8 * US)
    steps = split.steps()
    assert steps["step:READY"]["rest_ms"] == pytest.approx(4e-3)
    assert steps["step:INJECT"]["program_ms"] == pytest.approx(16e-3)


def test_summary_per_dispatch():
    line = spans.summary(spans.Split(*synthetic()), dispatches=1)
    assert line["per_dispatch_ms"] == pytest.approx(
        {"fold": 12e-3, "stage": 6e-3, "sync": 14e-3, "apply": 8e-3})
    assert line["inject_ms"] == pytest.approx(11e-3)
    assert line["staged_kb"] == pytest.approx(4.0)
    assert line["idle_in_steps_bare_share"] == pytest.approx(8 / 62)


def test_no_program_spans_gives_the_harness_breakdown():
    """On a trace without program spans, the idle time falls where the
    harness's own reduction puts it."""
    rec = json.loads((DATA / "burst_trace.json").read_text())
    red = tracing.Reduction([tuple(e) for e in rec["events"]])
    gaps = spans.Split(red, []).idle_gaps(0)
    want = dict(red.idle_gaps(0, n=100))
    assert {k: v * 1e-9 for k, v in gaps.items() if v} \
        == pytest.approx(want, rel=1e-12)


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
