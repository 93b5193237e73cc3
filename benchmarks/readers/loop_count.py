"""A ratio of the driver's own counts over the whole window, in percent:
``numerator`` over the product of ``denominators`` (names of counters)."""


def read(ctx, numerator: str, denominators: list):
    c = ctx.record.counters
    den = 1.0
    for name in denominators:
        den *= c.get(name, 0)
    if not den or numerator not in c:
        return None
    return 100.0 * c[numerator] / den
