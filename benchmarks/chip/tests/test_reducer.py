"""The trace reduction on a small trace with known answers."""
import json
from pathlib import Path

import pytest

import tracing
from _common import Context, dispatch_device_ms, host_ms, idle_pct

DATA = Path(__file__).resolve().parent / "data"

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def synthetic():
    """A 100 us window: two steps, each with device work inside."""
    us = 1000
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


def test_synthetic_trace():
    red = tracing.Reduction(synthetic())
    assert red.window_s == pytest.approx(100e-6)
    # Busy: [20, 30) and [75, 85) -> 20 us.
    assert red.busy_s(0) == pytest.approx(20e-6)
    assert red.idle_share(0) == pytest.approx(0.8)
    assert len(red.kernel_events(0)) == 2
    assert len(red.fused_steps(0)) == 2
    # Steps: 30 - 10, 10 - 0, 20 - 10 us of host -> mean 40/3 us.
    ctx = Context(trace=red, dispatch_rows=[1, 1], nodes=5000, engine={},
                  peaks=None)
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


def test_missing_names_read_nothing():
    events = [e for e in synthetic() if e[1] != "XLA Ops"]
    red = tracing.Reduction(events)
    assert red.busy_s(0) is None
    ctx = Context(trace=red, dispatch_rows=[1, 1], nodes=5000, engine={},
                  peaks=None)
    assert idle_pct(ctx) is None
    assert dispatch_device_ms(ctx) is None


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
    ctx = Context(trace=red, dispatch_rows=rec["dispatch_rows"], nodes=5000,
                  engine=json.loads((DATA.parent.parent / "configs"
                                     / "k8s-5k.json").read_text())["engine"],
                  peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    for name, metric in rec["metrics"].items():
        from run import reader

        assert reader(name)(ctx) == pytest.approx(metric["value"]), name
