"""The whole all-reduce's share of its roofline: the least time that the
placement's work needs at the chip's ICI and HBM peaks
(``bench/gradsync_work.py``: messages from the tree and the blue
switches, sums from the gradient's bytes, never the executor's buffers)
over the first chip's device time per sync."""
from bench import gradsync_work, sync_ops


def read(ctx):
    drv = ctx.driver
    ms = sync_ops.per_sync_ms(ctx, sync_ops.device_busy_s(ctx))
    if ms is None:
        return None
    work = gradsync_work.sync_work(drv.parent, drv.load, drv.device_leaf,
                                   drv.blue, drv.grad_bytes)
    return 100.0 * gradsync_work.least_seconds(work, ctx.peaks) / (ms / 1e3)
