"""Device time per all-reduce on the first chip (the root's home, which
sums the most): the union of its operations in the traced window, over
the syncs started in it."""
from bench import sync_ops


def read(ctx):
    return sync_ops.per_sync_ms(ctx, sync_ops.device_busy_s(ctx))
