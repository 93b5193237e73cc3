"""The whole serving step's share of the chip's peak for a model family
that the cell's configuration names (``"family"``: a module of
``harness/`` with ``cell_sizes`` and ``step_flops``): the model operations
of the traced part of the window, by the family's own count from the loop's
counters, over its wall time over chips x peak bf16 FLOP/s, in percent.
Never ``ctx.sizes``. A record without the family's counters (another
driver's), or a configuration that names no family: no metric."""

import importlib
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def family_of(cell: dict):
    """The family module of a cell's configuration, or None."""
    with open(os.path.join(HERE, "configs", cell["config"] + ".json")) as f:
        name = json.load(f).get("family")
    return None if name is None else importlib.import_module("harness." + name)


def read(ctx, needs: list):
    c, window = ctx.record.trace_counters, ctx.record.trace_window_s
    family = family_of(ctx.cell)
    if not window or family is None or any(n not in c for n in needs):
        return None
    ops = family.step_flops(family.cell_sizes(ctx.cell), c)
    return 100.0 * ops / window / (ctx.chips * ctx.peaks["flops_bf16"])
