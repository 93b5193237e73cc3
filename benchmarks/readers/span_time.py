"""Host time inside one of the program's spans (``distkeras_tpu.obs.span``,
read from the host plane of the profiler's trace): the summed duration of
the events named ``span``, less the time of the events named in ``minus``
that lie inside them (spans in which the host only waits for the device).
With ``per`` it is divided by the number of events of that name and given
in milliseconds (``serving.step``: host work of one engine step); with
``share_of_window`` it is given in percent of the traced window. No event
named ``span`` in the trace: no metric."""

from harness import trace_reduce


def _named(trace, name):
    return [s for s in trace.host_spans if s[2] == name]


def read(ctx, span: str, minus=(), per=None, share_of_window=False):
    events = _named(ctx.trace, span)
    if not events:
        return None
    inside = trace_reduce.union(events)
    seconds = sum(b - a for a, b in inside) / 1e9
    for name in minus:
        waits = _named(ctx.trace, name)
        for a, b in inside:
            seconds -= sum(y - x for x, y, _ in
                           trace_reduce.clip(waits, a, b)) / 1e9
    if per is not None:
        n = len(_named(ctx.trace, per))
        return 1e3 * seconds / n if n else None
    if share_of_window:
        window = ctx.record.trace_window_s
        return 100.0 * seconds / window if window else None
    return seconds
