"""How far the mesh's devices' busy times lie apart over the traced
window: (largest - smallest) / largest. A shard that routes slower than
the others sets the pace of every call, which no one-chip cell shows.
Fewer than two devices in the trace: nothing to read."""


def read(ctx):
    busy = ctx.trace.device_busy_s
    if len(busy) < 2 or max(busy) <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / max(busy)
