"""Pallas kernel (``kernels/bitmap_support.py``): share of the HBM roofline
of ``bitmap_support_kernel``, the node-partitioned peel wave's per-chunk
support count (``peel._partitioned_bitmap_peel``).

Each call's bytes are counted from that call's own operand and result
shapes in the trace (``tracing.call_bytes``): the two gathered
``[chunk, W/S]`` row blocks read and the partial supports written.  The
call moves O(1) bytes per operation, so bytes bound it: the least time is
bytes over the chip's HBM bandwidth, and the share is that over the
kernel's summed device time, over every chip of the cell."""
KERNEL = "bitmap_support_kernel"


def read(ctx):
    if ctx.trace is None:
        return None
    calls = ctx.trace.kernel_calls(KERNEL)
    secs = sum(s for s, _ in calls)
    if not calls or secs <= 0:
        return None
    least = sum(b for _, b in calls) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / secs
