"""The share of the traced window in which no program of the trainer was on
the device: 1 - (summed time of the executed programs, the ``XLA Modules``
line, averaged over chips) / window. Between two epoch programs the host
fetches losses, runs callbacks and hands over the next epoch's rows."""

from harness import trace_reduce


def read(ctx):
    window = ctx.record.trace_window_s
    on_device = trace_reduce.program_seconds(ctx.trace)
    if not window or not on_device:
        return None
    return 100.0 * (1.0 - on_device / window)
