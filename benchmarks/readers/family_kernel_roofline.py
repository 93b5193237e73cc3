"""A kernel's share of its roofline in a cell of a model family that the
configuration names: as ``kernel_roofline``, with the operations and bytes
of the family's own cost functions (``harness/<family>.KERNEL_COSTS``) at
the sizes of the cell's configuration. ``counters`` names, for each
argument of the cost function, the traced counters that add up to it. The
binding bound is printed on stderr. No event of the kernel, a counter
missing, or no family: no metric."""

import sys

from harness import flops, trace_reduce
from readers.family_mfu import family_of


def read(ctx, kernels: list, cost: str, counters: dict):
    found = trace_reduce.kernel_seconds(ctx.trace, kernels)
    seconds = sum(sec for sec, _ in found.values())
    c = ctx.record.trace_counters
    family = family_of(ctx.cell)
    if not seconds or set(found) != set(kernels) or family is None \
            or any(n not in c for names in counters.values() for n in names):
        return None
    args = {k: sum(c[n] for n in names) for k, names in counters.items()}
    ops, nbytes = family.KERNEL_COSTS[cost](family.cell_sizes(ctx.cell), args)
    least, bound = flops.least_seconds(ops, nbytes, ctx.peaks)
    print(f"roofline {'+'.join(kernels)}: bound by {bound}; least {least:.6f}s of "
          f"{seconds:.6f}s in {sum(n for _, n in found.values())} calls; "
          f"{ops:.4g} ops, {nbytes:.4g} bytes", file=sys.stderr)
    return 100.0 * least / seconds
