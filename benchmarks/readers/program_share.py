"""The share of the device's program time that went to some of the programs,
by name: the summed time of the events of the trace's ``XLA Modules`` line
whose name holds one of ``contains`` (a jitted function's name:
``jit_serving_prefill(...)``), over the summed time of all of them, in
percent. No program of such a name in the trace: no metric."""


def read(ctx, contains: list):
    named = total = 0
    for events in ctx.trace.device_programs.values():
        for a, b, name in events:
            total += b - a
            if any(c in name for c in contains):
                named += b - a
    if not named:
        return None
    return 100.0 * named / total
