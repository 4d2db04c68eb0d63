"""Pipeline parallelism over a mesh axis (GPipe-style, shard_map).

Maps a layer stack onto ``num_stages`` mesh shards along ``axis`` (on the
production mesh: the ``pod`` axis — each pod is one stage, so only
boundary activations cross the inter-pod DCN link, the natural cut for a
2-pod 512-chip job).  Microbatches stream through stages with
``ppermute`` handoffs; the bubble is the standard (S−1)/(M+S−1) GPipe
fraction.

The default production config keeps the pod axis on DP (DESIGN §3); PP is
a config-flag alternative for deeper-than-HBM models, exercised by
``tests/test_pipeline.py`` against a single-stage oracle.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    fn: Callable[[Any, jax.Array], jax.Array],
    mesh: Mesh,
    axis: str,
    stage_params: Any,  # leaves [num_stages, ...] — one slice per stage
    x: jax.Array,  # [num_micro, micro_batch, ...] microbatched input
) -> jax.Array:
    """Stream microbatches through pipeline stages living on ``axis``.

    ``fn(stage_param_slice, microbatch) -> microbatch`` is the stage
    body; stages compose left-to-right in axis order.  Returns
    [num_micro, micro_batch, ...] — the last stage's outputs.
    """
    S = mesh.shape[axis]
    M = x.shape[0]

    def per_stage(params, xs):
        stage = jax.lax.axis_index(axis)
        params = jax.tree.map(lambda a: a[0], params)
        steps = M + S - 1
        fwd = [(i, i + 1) for i in range(S - 1)]  # stage i -> i+1

        def tick(carry, t):
            recv, outbuf = carry
            inp = jnp.where(stage == 0, xs[jnp.clip(t, 0, M - 1)], recv)
            out = fn(params, inp)
            nxt = jax.lax.ppermute(out, axis, fwd)
            done = t - (S - 1)
            write = (stage == S - 1) & (done >= 0)
            upd = outbuf.at[jnp.clip(done, 0, M - 1)].set(out)
            outbuf = jnp.where(write, upd, outbuf)
            return (nxt, outbuf), None

        init = (jnp.zeros_like(xs[0]), jnp.zeros_like(xs))
        (_, outbuf), _ = jax.lax.scan(tick, init, jnp.arange(steps))
        # broadcast the last stage's buffer to every stage
        mask = (stage == S - 1).astype(outbuf.dtype)
        return jax.lax.psum(outbuf * mask, axis)

    return jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stage_params),
                  P(*([None] * x.ndim))),
        out_specs=P(*([None] * x.ndim)),
        check_vma=False,
    )(stage_params, x)
