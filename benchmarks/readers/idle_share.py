"""The device's idle share of the traced window: 1 - (union of the intervals
in which an operation ran on the device, averaged over chips) / window."""

from harness import trace_reduce


def read(ctx):
    window = ctx.record.trace_window_s
    busy = trace_reduce.busy_seconds(ctx.trace)
    if not window or not busy:
        return None
    return 100.0 * (1.0 - busy / window)
