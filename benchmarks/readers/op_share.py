"""The share of the device's busy time that went to operations of some kind:
the summed device time of the operations whose name (the HLO name without its
operands: ``all-reduce.3``, ``all-reduce-start.1``) holds one of ``contains``,
averaged over chips, over the busy time (the union of all operations'
intervals, averaged over chips), in percent. Container operations are left
out, as in ``trace_reduce.top_ops``. No such operation in the trace: no
metric."""

from harness import trace_reduce


def read(ctx, contains: list):
    busy = trace_reduce.busy_seconds(ctx.trace)
    found = trace_reduce.kernel_seconds(ctx.trace, contains)
    seconds = sum(sec for sec, _ in found.values())
    if not busy or not seconds:
        return None
    return 100.0 * seconds / busy
