"""Checkpoint store: atomicity, async, retention, elastic resharding."""
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (
    CheckpointManager,
    latest_step,
    restore_pytree,
    save_pytree,
)

pytestmark = pytest.mark.slow


@pytest.fixture
def tmpdir(tmp_path):
    return str(tmp_path / "ckpt")


def tree():
    return {
        "params": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
                   "b": jnp.ones((4,), jnp.bfloat16)},
        "step": jnp.asarray(7, jnp.int32),
    }


def test_roundtrip(tmpdir):
    t = tree()
    save_pytree(t, tmpdir, 7)
    out = restore_pytree(t, tmpdir, 7)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype


def test_tree_mismatch_rejected(tmpdir):
    t = tree()
    save_pytree(t, tmpdir, 1)
    bad = {"params": {"w": t["params"]["w"]}, "step": t["step"]}
    with pytest.raises(ValueError, match="mismatch"):
        restore_pytree(bad, tmpdir, 1)


def test_latest_and_gc(tmpdir):
    mgr = CheckpointManager(tmpdir, keep=2)
    t = tree()
    for s in (10, 20, 30):
        mgr.save(t, s, blocking=True)
    assert latest_step(tmpdir) == 30
    kept = sorted(os.listdir(tmpdir))
    assert kept == ["step_00000020", "step_00000030"]


def test_async_save_then_restore(tmpdir):
    mgr = CheckpointManager(tmpdir, keep=3)
    t = tree()
    mgr.save(t, 5, blocking=False)
    got = mgr.restore_latest(t)
    assert got is not None and got[0] == 5


def test_tmp_dirs_never_restored(tmpdir):
    os.makedirs(os.path.join(tmpdir, "step_00000099.tmp"))
    assert latest_step(tmpdir) is None


_ELASTIC_PROG = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import save_pytree, restore_pytree
from repro.launch.mesh import make_mesh

d = sys.argv[1]
# "save" on a 4-device (2x2) mesh
mesh4 = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
w = jnp.arange(64 * 64, dtype=jnp.float32).reshape(64, 64)
sharded = jax.device_put(w, NamedSharding(mesh4, P("data", "model")))
save_pytree({"w": sharded}, d, 1)

# restore onto an 8-device (4x2) mesh — elastic scale-up
mesh8 = make_mesh((4, 2), ("data", "model"))
sh = lambda path: NamedSharding(mesh8, P("data", "model"))
out = restore_pytree({"w": jax.ShapeDtypeStruct((64, 64), jnp.float32)},
                     d, 1, sharding_fn=sh)
assert out["w"].sharding.mesh.shape["data"] == 4
np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(w))
print("ELASTIC_OK")
"""


def test_elastic_reshard(tmpdir):
    """Checkpoint written under a 4-chip mesh restores onto 8 chips."""
    r = subprocess.run(
        [sys.executable, "-c", _ELASTIC_PROG, tmpdir],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": "src"},
    )
    assert "ELASTIC_OK" in r.stdout, r.stdout + r.stderr
