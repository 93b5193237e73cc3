"""Seconds inside one of the program's spans over the whole run, from the
program's own aggregates (``distkeras_tpu.obs.span_records()``: total seconds
and count by span path, process-global, so they outlive the driver's engine
or trainer and are read after the run): the summed totals of every path that
ends in one of ``names``. Set-up spans (``serving.init``, ``train.setup``)
close before the traced window opens, so the profiler's trace cannot hold
them. No path that ends in one of the names (an older ``distkeras_tpu``, or
telemetry off): no metric. A total of zero is a value."""


def read(ctx, names: list):
    from distkeras_tpu import obs
    totals = [total for path, total, _ in obs.span_records()
              if path and path[-1] in names]
    return sum(totals) if totals else None
