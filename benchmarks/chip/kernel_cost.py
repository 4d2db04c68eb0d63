"""Operations and HBM bytes of one ``alloc_scan`` Pallas call, by shape.

The kernel (``repro.kernels.alloc_scan.kernel``) walks ``B`` rows in
sequence against residual tiles of ``N = nb * 128`` padded nodes that
stay in VMEM for the whole call.  Per row, in ARAS mode with worst-fit
placement, every tile element takes 20 element-wise operations:

* arg-max of the CPU residual (max, compare, select, min-index): 4;
* the memory residual at that node (compare, select, sum): 3;
* the placement key (two fit compares, and, select): 4;
* arg-max of the key: 4;
* the debit (node compare, then select and subtract on both tiles): 5;

plus ``7 * B`` on the row axis: the correction dot products against the
stamped mask (a multiply and an add per element, for CPU and memory: 4)
and the stamped-mask update (two compares and a select: 3).  Scalar
work per row is left out; it is a few dozen operations against
``20 * N``.

Bytes moved to and from HBM: the four ``[nb, 128]`` float32 tiles read
once (their block index never changes, so they are fetched once), the
two ``[B, B]`` float32 correction tables streamed slab by slab, twelve
``[B]`` row arrays read (seven inputs in float32 or int32) or written
(six outputs), and the two totals.

The least time is the larger of bytes over HBM bandwidth and operations
over peak.  At every shape this benchmark runs, the bytes bound is the
larger (``bound`` says which): the operations are vector-unit work, far
below the matrix-unit peak the table states, so the share reads how far
the call is from streaming its inputs at full bandwidth.
"""
from __future__ import annotations

LANE = 128
OPS_PER_ELEMENT = 20
OPS_PER_ROW_ELEMENT = 7


def padded_nodes(num_nodes: int) -> int:
    """Nodes padded to whole 128-lane blocks."""
    return -(-num_nodes // LANE) * LANE


def ops(rows: int, nodes: int) -> int:
    """Element-wise operations of one call at ``rows`` (padded) rows."""
    n = padded_nodes(nodes)
    return rows * (OPS_PER_ELEMENT * n + OPS_PER_ROW_ELEMENT * rows)


def hbm_bytes(rows: int, nodes: int) -> int:
    n = padded_nodes(nodes)
    tiles = 4 * n * 4
    tables = 2 * rows * rows * 4
    row_arrays = (7 + 6) * rows * 4
    totals = 2 * 4
    return tiles + tables + row_arrays + totals


def least_time(rows: int, nodes: int, peaks: dict):
    """(seconds, bound) of one call at the chip's peaks."""
    t_bytes = hbm_bytes(rows, nodes) / peaks["hbm_bytes_per_s"]
    t_ops = ops(rows, nodes) / peaks["flops_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_ops else (t_ops, "compute")
