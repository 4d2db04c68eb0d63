"""Device-resident incremental residual state for the burst allocator.

Every dispatch used to rebuild the full ``[nb, LANE]`` residual and
capacity tile tables from the engine's host float32 caches
(``pad_tiles_federated``): four O(nodes) host→device transfers plus an
O(nodes) pad/gather/reduce per burst, even when the burst touched a
handful of rows.  :class:`DeviceResidualState` keeps the tiles, the
per-block sums and (implicitly, via ``totals_from_block_sums``) the
``[K]`` shard totals resident on device across dispatches and applies
bind/complete deltas as **dirty-tile scatter updates**: a single jitted
``apply`` that touches only the affected 128-wide tiles.

Parity is by construction, not by approximation:

* Updates are *scatter-set*, never device-side arithmetic: the values
  written are read from the engine's authoritative host float32 caches
  at flush time, so after every ``apply_updates`` the device tiles are
  element-for-element the tiles ``pad_tiles_federated`` would rebuild
  from those caches.
* Block sums are re-derived only for dirty blocks, with the same masked
  128-lane row reduction ``tile_block_sums`` uses on the re-pad path;
  equal tile contents therefore give bitwise-equal block sums, and the
  totals both paths feed the sequential core are bitwise-equal too
  (``tests/test_incremental_state.py`` holds the whole pipeline to it).
* The state is functional: ``apply_updates`` returns a new value while
  the old tiles stay alive — a dispatch already issued against the old
  tiles keeps computing against them (JAX arrays are immutable), which
  is what lets the engine double-buffer: fold host events and flush
  deltas while the previous fused dispatch is still in flight.

Scatter index buckets are padded to powers of two (pad indices point one
past the end and are dropped by the scatter), so JIT caches stay warm as
dirty-set sizes vary.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.cluster import federation
from repro.cluster.federation import LANE, FederatedLayout


# Scatter buckets are floored at 8 so the expensive fused decision jit
# (repro.core.allocator._state_step, which inlines apply_packed) does not
# recompile across the tiny dirty-set sizes a streaming engine produces.
_MIN_BUCKET = 8


def _pow2(n: int) -> int:
    n = max(n, _MIN_BUCKET)
    return 1 << (n - 1).bit_length()


def pack_update_segment(nodes: np.ndarray, res_cpu: np.ndarray,
                        res_mem: np.ndarray,
                        layout: Optional[FederatedLayout],
                        nb: int):
    """Stage one dirty-set update as a single flat float32 segment.

    Layout: ``n_idx`` padded flat tile positions and ``n_blk`` padded
    dirty block ids, both int32 travelling as raw float32 bits
    (bitcast-exact), followed by ``[2, n_idx]`` cpu/mem residual values —
    one host→device copy instead of four.  Pad positions point one past
    the end (dropped by the scatter); returns ``(seg, n_idx, n_blk)``.
    ``nodes`` may be empty: the segment is then pure padding and the
    apply is a no-op.
    """
    nodes = np.asarray(nodes)
    flat = federation.flat_positions(nodes, layout)
    blocks = np.unique(flat // LANE)
    n_idx = _pow2(flat.shape[0])
    n_blk = _pow2(blocks.shape[0])
    ints = np.empty((n_idx + n_blk,), np.int32)
    ints[: flat.shape[0]] = flat
    ints[flat.shape[0]: n_idx] = nb * LANE
    ints[n_idx: n_idx + blocks.shape[0]] = blocks
    ints[n_idx + blocks.shape[0]:] = nb
    seg = np.zeros((n_idx + n_blk + 2 * n_idx,), np.float32)
    seg[: n_idx + n_blk] = ints.view(np.float32)
    seg[n_idx + n_blk: n_idx + n_blk + nodes.shape[0]] = res_cpu
    seg[2 * n_idx + n_blk: 2 * n_idx + n_blk + nodes.shape[0]] = res_mem
    return seg, n_idx, n_blk


def apply_packed(rc2, rm2, bsum_c, bsum_m, mask2, seg, n_idx: int,
                 n_blk: int):
    """Scatter dirty node values into the tiles, re-sum dirty blocks.

    Traceable (jit-inlinable) form over a :func:`pack_update_segment`
    buffer — the fused streaming dispatch inlines it ahead of the
    decision so one jit call both maintains and consumes the state.
    Duplicate indices carry identical values (deduped host-side, read
    from the same cache), so scatter order cannot matter.
    """
    ints = jax.lax.bitcast_convert_type(seg[: n_idx + n_blk], jnp.int32)
    idx, blk = ints[:n_idx], ints[n_idx:]
    vals = seg[n_idx + n_blk:].reshape(2, n_idx)
    val_c, val_m = vals[0], vals[1]
    nb, lane = rc2.shape
    rc2 = rc2.reshape(-1).at[idx].set(val_c, mode="drop").reshape(nb, lane)
    rm2 = rm2.reshape(-1).at[idx].set(val_m, mode="drop").reshape(nb, lane)
    safe = jnp.clip(blk, 0, nb - 1)  # gather rows; pad rows land nowhere
    rows_mask = mask2[safe]
    rows_c = jnp.where(rows_mask, rc2[safe], jnp.float32(0.0))
    rows_m = jnp.where(rows_mask, rm2[safe], jnp.float32(0.0))
    bsum_c = bsum_c.at[blk].set(jnp.sum(rows_c, axis=1), mode="drop")
    bsum_m = bsum_m.at[blk].set(jnp.sum(rows_m, axis=1), mode="drop")
    return rc2, rm2, bsum_c, bsum_m


_apply = jax.jit(apply_packed, static_argnames=("n_idx", "n_blk"))


@dataclasses.dataclass(frozen=True)
class DeviceResidualState:
    """Allocator state held on device across dispatches.

    ``rc2/rm2`` are the residual tiles (``res_pad`` in padding lanes),
    ``cc2/cm2`` the static capacity tiles, ``bsum_c/bsum_m`` the masked
    per-block residual sums the carried totals are derived from.
    """

    layout: Optional[FederatedLayout]
    num_nodes: int
    res_pad: float
    rc2: jax.Array  # [nb, LANE] f32 residual cpu tiles
    rm2: jax.Array  # [nb, LANE] f32 residual mem tiles
    cc2: jax.Array  # [nb, LANE] f32 allocatable capacity tiles (static)
    cm2: jax.Array  # [nb, LANE] f32
    mask2: jax.Array  # [nb, LANE] bool, True on real-node lanes
    bsum_c: jax.Array  # [nb] f32 masked per-block residual sums
    bsum_m: jax.Array  # [nb] f32

    @staticmethod
    def create(residual_cpu, residual_mem, cap_cpu, cap_mem,
               layout: Optional[FederatedLayout],
               res_pad: float) -> "DeviceResidualState":
        """Stage the host caches once; afterwards only deltas move.

        The tiles are laid out on the host and each copied to the device
        once (:attr:`staged_bytes`), inside an ``alloc.state`` span.
        """
        num_nodes = int(np.shape(residual_cpu)[0])
        with TraceAnnotation("alloc.state", nodes=num_nodes) as span:
            host = [federation.host_tiles(residual_cpu, layout, res_pad),
                    federation.host_tiles(residual_mem, layout, res_pad),
                    federation.host_tiles(cap_cpu, layout, 0.0),
                    federation.host_tiles(cap_mem, layout, 0.0),
                    federation.tile_mask(num_nodes, layout)]
            span.set_metadata(bytes=sum(a.nbytes for a in host))
            rc2, rm2, cc2, cm2, mask2 = (jnp.asarray(a) for a in host)
            return DeviceResidualState(
                layout=layout, num_nodes=num_nodes, res_pad=res_pad,
                rc2=rc2, rm2=rm2, cc2=cc2, cm2=cm2, mask2=mask2,
                bsum_c=federation.tile_block_sums(rc2, mask2),
                bsum_m=federation.tile_block_sums(rm2, mask2),
            )

    @property
    def staged_bytes(self) -> int:
        """Bytes :meth:`create` copied host to device: the four tiles
        and the mask."""
        return sum(int(a.nbytes) for a in
                   (self.rc2, self.rm2, self.cc2, self.cm2, self.mask2))

    def apply_updates(self, nodes: np.ndarray, res_cpu: np.ndarray,
                      res_mem: np.ndarray) -> "DeviceResidualState":
        """Scatter the given nodes' current host residuals into the tiles.

        ``nodes`` are unique global node ids; ``res_cpu/res_mem`` their
        authoritative host float32 residuals.  Returns a new state; the
        old one stays valid for any dispatch still in flight.
        """
        nodes = np.asarray(nodes)
        if nodes.size == 0:
            return self
        seg, n_idx, n_blk = pack_update_segment(
            nodes, res_cpu, res_mem, self.layout, int(self.rc2.shape[0]))
        rc2, rm2, bsum_c, bsum_m = _apply(
            self.rc2, self.rm2, self.bsum_c, self.bsum_m, self.mask2,
            jnp.asarray(seg), n_idx=n_idx, n_blk=n_blk,
        )
        return dataclasses.replace(
            self, rc2=rc2, rm2=rm2, bsum_c=bsum_c, bsum_m=bsum_m)
