"""The check's control: the reference in bfloat16 must read as wrong.

At sizes a test run can hold: the paper's testbed cell at its own size,
and the 5,000-node configuration cut to 256 nodes under each of its
mixes.  The float32 reference's own decisions pass the same check.
"""
import json
from pathlib import Path

import pytest

import control
import traffic

HERE = Path(__file__).resolve().parent.parent


def load(config, mix, nodes=None, rehearse=False):
    engine = json.loads((HERE / "configs" / f"{config}.json").read_text())
    limits = engine["check_limits"]
    engine = engine["engine"]
    if nodes:
        engine = traffic.merge(engine, {"cluster": {"num_nodes": nodes}})
    mix = json.loads((HERE / "traffic" / f"{mix}.json").read_text())
    if rehearse:
        mix = traffic.merge(mix, mix["rehearsal"])
    return engine, mix, limits


CASES = [("aras-testbed", "constant", None, False),
         ("k8s-5k", "burst", 256, True),
         ("k8s-5k", "poisson", 256, True)]


def fails(reading, limits):
    return any(reading[k] > limits[k] for k in
               ("mismatches", "never_bound", "quota_gap", "overcommits"))


@pytest.mark.parametrize("config,mix,nodes,rehearse", CASES)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bfloat16_control_fails(config, mix, nodes, rehearse, seed):
    engine, m, limits = load(config, mix, nodes, rehearse)
    reading = control.readings(engine, m, seed, 10.0)
    assert reading["decisions"] > 0
    assert fails(reading, limits), reading


@pytest.mark.parametrize("config,mix,nodes,rehearse", CASES)
def test_float32_reference_passes(config, mix, nodes, rehearse):
    engine, m, limits = load(config, mix, nodes, rehearse)
    reading = control.readings(engine, m, 5, 10.0, dtype="float32")
    assert reading["decisions"] > 0
    assert not fails(reading, limits), reading
    assert reading["quota_gap"] == 0.0
