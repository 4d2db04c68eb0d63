"""Activation-sharding constraint rules (train vs serve modes)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.parallel import act_sharding as act


def test_noop_without_context():
    """Outside a context every helper is the identity (CPU tests rely
    on this)."""
    x = jnp.ones((2, 4, 8))
    assert act.constrain_tokens(x) is x
    assert act.constrain_ff(x) is x
    assert act.constrain_logits(x) is x
    q = jnp.ones((2, 4, 4, 8))
    k = v = jnp.ones((2, 4, 2, 8))
    q2, k2, v2 = act.constrain_qkv(q, k, v)
    assert q2 is q and k2 is k and v2 is v

import pytest

pytestmark = pytest.mark.slow

_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.parallel.policy import ShardingPolicy
from repro.parallel import act_sharding as act

mesh = make_mesh((4, 2), ("data", "model"))
pol = ShardingPolicy(mesh)


def spec_of(fn, shape, serve=False, **kw):
    with act.activation_sharding(pol, serve=serve):
        out = jax.jit(fn).lower(jax.ShapeDtypeStruct(shape, jnp.float32))
    return out  # we only need it to lower without error


# train mode: constraints must not break lowering and must shard batch
with act.activation_sharding(pol):
    x = jnp.ones((8, 4, 16))
    y = act.constrain_tokens(x)
    assert "data" in str(y.sharding.spec), y.sharding.spec
    h = jnp.ones((8, 4, 32))
    hh = act.constrain_ff(h)
    assert "model" in str(hh.sharding.spec)
    # divisibility fallback: 7 doesn't divide anything -> replicated dim
    odd = jnp.ones((7, 4, 16))
    oo = act.constrain_tokens(odd)
    assert oo.sharding.spec[0] is None
    assert any("7" in f for f in pol.fallbacks)

# serve mode: batch replicated, features over data
with act.activation_sharding(pol, serve=True):
    x = jnp.ones((8, 1, 16))
    y = act.constrain_tokens(x)
    assert y.sharding.spec[0] is None  # batch replicated
    assert y.sharding.spec[2] == "data"  # features over data

# qkv head fallback: 14 heads don't divide model=2? 14%2==0 -> heads shard
with act.activation_sharding(pol):
    q = jnp.ones((8, 16, 14, 8))
    k = v = jnp.ones((8, 16, 2, 8))
    q2, k2, v2 = act.constrain_qkv(q, k, v)
    assert q2.sharding.spec[2] == "model"
    # 3 heads don't divide model=2 -> seq sharding fallback
    q = jnp.ones((8, 16, 3, 8))
    k = v = jnp.ones((8, 16, 3, 8))
    q2, k2, v2 = act.constrain_qkv(q, k, v)
    assert q2.sharding.spec[1] == "model"  # query-seq over model
    assert q2.sharding.spec[2] is None

print("ACT_OK")
"""


def test_constraint_rules_on_mesh():
    r = subprocess.run(
        [sys.executable, "-c", _PROG],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": "src"},
    )
    assert "ACT_OK" in r.stdout, r.stdout + r.stderr[-3000:]


def test_moe_grouping_modes():
    """Training context groups by |dp|; serve context keeps groups=1;
    both match the no-context reference exactly."""
    import dataclasses
    from repro.models.config import MoEConfig

    cfg = ModelConfig(
        name="t", num_layers=1, d_model=32, num_heads=4, num_kv_heads=4,
        d_ff=0, vocab_size=128,
        moe=MoEConfig(num_experts=8, top_k=2, expert_d_ff=48,
                      capacity_factor=8.0))
    p = L.init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, 32))
    y_ref, _ = L.moe(p, cfg, x)
    # (grouped paths under a real mesh are exercised in test_dryrun;
    # here we check the no-context path is deterministic and matches the
    # einsum reference)
    cfg_e = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, dispatch_mode="einsum"))
    y_e, _ = L.moe(p, cfg_e, x)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_e),
                               atol=1e-4)
