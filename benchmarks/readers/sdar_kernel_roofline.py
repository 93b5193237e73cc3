"""A kernel's share of its roofline in a block-diffusion cell: as
``kernel_roofline``, with the operations and bytes of the SDAR family's own
cost functions (``harness/sdar_family.KERNEL_COSTS``) at the sizes of the cell's
configuration. ``counters`` names, for each argument of the cost function,
the traced counters that add up to it. The binding bound is printed on
stderr. No event of the kernel, or a counter missing: no metric."""

import sys

from harness import flops, sdar_family, trace_reduce


def read(ctx, kernels: list, cost: str, counters: dict):
    found = trace_reduce.kernel_seconds(ctx.trace, kernels)
    seconds = sum(sec for sec, _ in found.values())
    c = ctx.record.trace_counters
    if not seconds or set(found) != set(kernels) \
            or any(n not in c for names in counters.values() for n in names):
        return None
    args = {k: sum(c[n] for n in names) for k, names in counters.items()}
    ops, nbytes = sdar_family.KERNEL_COSTS[cost](
        sdar_family.cell_sizes(ctx.cell), args)
    least, bound = flops.least_seconds(ops, nbytes, ctx.peaks)
    print(f"roofline {'+'.join(kernels)}: bound by {bound}; least {least:.6f}s of "
          f"{seconds:.6f}s in {sum(n for _, n in found.values())} calls; "
          f"{ops:.4g} ops, {nbytes:.4g} bytes", file=sys.stderr)
    return 100.0 * least / seconds
