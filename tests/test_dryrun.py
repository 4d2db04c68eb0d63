"""Dry-run machinery integration test on a small (8-device) mesh.

Exercises the real lowering paths (train/prefill/decode with policy
shardings, activation constraints, collective-byte extraction) in a
subprocess so the main test process keeps a single device.
"""
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json, jax
from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.launch.dryrun import (analyse, collective_bytes, cost_dict,
                                 lower_decode, lower_prefill, lower_train)
from repro.models.api import ShapeSpec, build_model
from repro.parallel.act_sharding import activation_sharding
from repro.parallel.policy import ShardingPolicy

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
policy = ShardingPolicy(mesh)

for arch in ("llama3-8b", "olmoe-1b-7b", "falcon-mamba-7b",
             "jamba-1.5-large-398b", "whisper-base"):
    cfg = dataclasses.replace(get_smoke_config(arch), remat=True,
                              scan_layers=True)
    model = build_model(cfg)
    shape_train = ShapeSpec("t", "train", 16, 8)
    shape_pre = ShapeSpec("p", "prefill", 16, 8)
    shape_dec = ShapeSpec("d", "decode", 32, 8)
    with mesh, activation_sharding(policy):
        ct = lower_train(model, policy, shape_train).compile()
        cp = lower_prefill(model, policy, shape_pre).compile()
    with mesh, activation_sharding(policy, serve=True):
        cd = lower_decode(model, policy, shape_dec).compile()
    for name, c in (("train", ct), ("prefill", cp), ("decode", cd)):
        cost = cost_dict(c)
        assert cost.get("flops", 0) > 0, (arch, name)
        mem = c.memory_analysis()
        assert mem.temp_size_in_bytes >= 0
    print(f"{arch}: OK")
print("DRYRUN_OK")
"""


def test_dryrun_small_mesh_all_families():
    r = subprocess.run(
        [sys.executable, "-c", _PROG],
        capture_output=True, text=True, timeout=560,
        env={**os.environ, "PYTHONPATH": "src"},
    )
    assert "DRYRUN_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes

    hlo = """
  %ag = bf16[16,1024]{1,0} all-gather(%x), dimensions={0}
  %ar.1 = f32[256,128]{1,0} all-reduce-start(%y), to_apply=%sum
  %tup = (f32[4,4]{1,0}, f32[8]{0}) all-to-all(%a, %b)
  %cp = u32[32]{0} collective-permute(%z), source_target_pairs={{0,1}}
  %notacoll = f32[2,2]{1,0} add(%p, %q)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 16 * 1024 * 2
    assert out["all-reduce"] == 256 * 128 * 4
    assert out["all-to-all"] == 4 * 4 * 4 + 8 * 4
    assert out["collective-permute"] == 32 * 4
