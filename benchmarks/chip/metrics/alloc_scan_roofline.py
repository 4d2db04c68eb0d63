"""The alloc_scan Pallas kernel's share of its roofline.

Least time of every kernel call in the window (``kernel_cost``, from the
call's padded rows and nodes at the chip's peaks), summed, over the
summed device time of the kernel's events in the trace.  The calls are
matched to the window's dispatches one for one; a count that differs
means the trace and the window disagree, and nothing is read.
"""
import kernel_cost


def read(ctx):
    alloc = ctx.engine["alloc"]
    if alloc["algorithm"] != "aras" or alloc["placement"] != "worst_fit":
        raise ValueError("kernel_cost counts ARAS with worst-fit only")
    events = ctx.trace.kernel_events(0)
    if not events or len(events) != len(ctx.dispatch_rows) \
            or ctx.peaks is None:
        return None
    spent = sum(e - s for _, s, e in events) * 1e-9
    least = 0.0
    for rows in ctx.dispatch_rows:
        padded = 1 << max(rows - 1, 0).bit_length()
        least += kernel_cost.least_time(padded, ctx.nodes, ctx.peaks)[0]
    return least / spent * 100.0
