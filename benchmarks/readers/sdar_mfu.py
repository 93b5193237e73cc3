"""The whole block-diffusion step's share of the chip's peak: the model
operations of the traced part of the window (``harness/sdar_family.step_flops``:
routed experts only, attention over the keys attended, the vocabulary head
where logits are needed) over its wall time over chips x peak bf16 FLOP/s, in
percent. The sizes are the SDAR family's own, read from the cell's
configuration, never ``ctx.sizes``."""

from harness import sdar_family


def read(ctx):
    c, window = ctx.record.trace_counters, ctx.record.trace_window_s
    if not window or not c.get("pass_rows"):
        return None
    ops = sdar_family.step_flops(sdar_family.cell_sizes(ctx.cell), c)
    return 100.0 * ops / window / (ctx.chips * ctx.peaks["flops_bf16"])
