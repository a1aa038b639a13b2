"""Device time per all-reduce of the first chip's other operations: the
receive-accumulate scatter-add, the compress and root folds, the buffer
set-up (``bench/sync_ops.py``)."""
from bench import sync_ops, trace


def read(ctx):
    return sync_ops.per_sync_ms(ctx, trace.op_seconds(
        ctx.trace, lambda n: not sync_ops.is_collective(n)))
