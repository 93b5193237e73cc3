"""A kernel's share of its roofline: the least time the chip could take for
the calls the traced part of the window made (the larger of operations over
peak FLOP/s and bytes over peak bytes/s, both from ``harness/flops.py``) over
the summed device time of the kernel's operations in the trace, in percent.
The binding bound is printed on stderr. No event of the kernel: no metric."""

import sys

from harness import flops, trace_reduce


def read(ctx, kernels: list, cost: str):
    found = trace_reduce.kernel_seconds(ctx.trace, kernels)
    seconds = sum(sec for sec, _ in found.values())
    if not seconds or set(found) != set(kernels):
        return None
    ops, nbytes = flops.KERNEL_COSTS[cost](ctx.sizes, ctx.record.trace_counters)
    least, bound = flops.least_seconds(ops, nbytes, ctx.peaks)
    print(f"roofline {'+'.join(kernels)}: bound by {bound}; least {least:.6f}s of "
          f"{seconds:.6f}s in {sum(n for _, n in found.values())} calls; "
          f"{ops:.4g} ops, {nbytes:.4g} bytes", file=sys.stderr)
    return 100.0 * least / seconds
