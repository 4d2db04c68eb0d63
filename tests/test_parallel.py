"""Parallelism primitives: pipeline, compression, sharding policy.

These run on a small host-device mesh (subprocess sets the device count
where >1 devices are needed, keeping the main test process at 1 device).
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.parallel.compression import (
    dequantize_int8,
    make_pod_compressor,
    quantize_int8,
    simulate_roundtrip,
)

pytestmark = pytest.mark.slow


# --------------------------------------------------------- compression

def test_int8_roundtrip_error_bound():
    x = jax.random.normal(jax.random.key(0), (256, 256)) * 3.0
    q, s = quantize_int8(x)
    y = dequantize_int8(q, s)
    assert q.dtype == jnp.int8
    # max quantization error is half a step = scale/2
    assert float(jnp.abs(y - x).max()) <= float(s) * 0.5 + 1e-6


def test_stochastic_rounding_unbiased():
    x = jnp.full((10_000,), 0.3)
    q, s = quantize_int8(x * 127.0 / 0.9, jax.random.key(1))
    y = dequantize_int8(q, s)
    assert abs(float(jnp.mean(y)) - float(x[0]) * 127.0 / 0.9) < 0.05


def test_compressor_error_feedback_reduces_bias():
    grads = {"w": jax.random.normal(jax.random.key(2), (64, 64))}
    plain = simulate_roundtrip(grads)
    comp = make_pod_compressor(None, error_feedback=True)
    # accumulate the same gradient 20 times with/without feedback
    acc_plain = jnp.zeros_like(grads["w"])
    acc_ef = jnp.zeros_like(grads["w"])
    for _ in range(20):
        acc_plain += simulate_roundtrip(grads)["w"]
        acc_ef += comp(grads)["w"]
    target = grads["w"] * 20
    assert float(jnp.abs(acc_ef - target).mean()) <= \
        float(jnp.abs(acc_plain - target).mean()) + 1e-6


def test_train_step_with_compression_converges():
    """Quantized gradients must still train the smoke model."""
    from repro.configs import get_smoke_config
    from repro.data.synthetic import SyntheticDataset
    from repro.models.api import build_model
    from repro.optim import make_optimizer
    from repro.training import init_train_state, make_train_step

    cfg = get_smoke_config("llama3-8b")
    model = build_model(cfg)
    opt = make_optimizer("adamw", learning_rate=3e-3)
    ds = SyntheticDataset(cfg, batch=4, seq=16, seed=0)
    step = jax.jit(make_train_step(model, opt,
                                   compress_grads=simulate_roundtrip))
    state = init_train_state(model, opt, jax.random.key(0))
    losses = []
    for i in range(20):
        state, m = step(state, ds.batch_at(i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.2, losses


# ------------------------------------------------------------ pipeline
# needs >1 device: run in a subprocess with forced host devices

_PIPELINE_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.parallel.pipeline import pipeline_apply

mesh = make_mesh((4,), ("pp",))
S, M, mb, d = 4, 8, 2, 16
key = jax.random.key(0)
stage_params = jax.random.normal(key, (S, d, d)) / jnp.sqrt(d)
x = jax.random.normal(jax.random.key(1), (M, mb, d))

def body(w, h):
    return jnp.tanh(h @ w)

out = pipeline_apply(body, mesh, "pp", stage_params, x)

# oracle: sequential application of the 4 stages
ref = x
for s in range(S):
    ref = body(stage_params[s], ref.reshape(M * mb, d)).reshape(M, mb, d)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           atol=1e-5, rtol=1e-5)
print("PIPELINE_OK")
"""


def test_pipeline_matches_sequential_oracle():
    r = subprocess.run(
        [sys.executable, "-c", _PIPELINE_PROG],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
    )
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr


# ------------------------------------------------------- sharding policy

_POLICY_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.parallel.policy import ShardingPolicy

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
pol = ShardingPolicy(mesh)

# FSDP+TP on a weight: [D, F] -> (('pod','data'), 'model')
spec = pol.param_spec("layers/attn/wq", (64, 128))
assert spec == P(("pod", "data"), "model"), spec
# divisibility fallback: odd dim cannot shard
spec = pol.param_spec("layers/attn/wq", (63, 128))
assert spec == P(None, "model"), spec
assert any("63" in f for f in pol.fallbacks)
# experts shard over model (EP)
spec = pol.param_spec("layers/moe/experts_wg", (8, 64, 96))
assert spec == P("model", ("pod", "data"), None), spec
# adafactor factored stats mirror the parent param minus an axis
spec = pol.param_spec("stats/layers/attn/wq/vr", (64,))
assert spec == P(("pod", "data")), spec
spec = pol.param_spec("stats/layers/attn/wq/vc", (128,))
assert spec == P("model"), spec
# adam moments resolve to the parameter rule
spec = pol.param_spec("mu/layers/mlp/wd", (128, 64))
assert spec == P("model", ("pod", "data")), spec
print("POLICY_OK")
"""


def test_policy_specs():
    r = subprocess.run(
        [sys.executable, "-c", _POLICY_PROG],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
    )
    assert "POLICY_OK" in r.stdout, r.stdout + r.stderr
