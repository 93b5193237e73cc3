"""A statistic that the driver took over the whole window by its own clock,
by name (``record.stats``): the serving loop's latency percentiles."""


def read(ctx, name: str):
    return ctx.record.stats.get(name)
