"""Device time per all-reduce of the first chip's collectives: the
ppermute rounds and the root's broadcast (``bench/sync_ops.py``)."""
from bench import sync_ops, trace


def read(ctx):
    return sync_ops.per_sync_ms(
        ctx, trace.op_seconds(ctx.trace, sync_ops.is_collective))
