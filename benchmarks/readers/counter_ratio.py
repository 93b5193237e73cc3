"""A plain ratio of the run's counters over the whole window: the sum of
``numerators`` over the sum of ``denominators`` (names in
``record.counters``; the block-diffusion driver copies the engine's own
counters there). A name that is missing, or a zero denominator: no metric."""


def read(ctx, numerators: list, denominators: list):
    c = ctx.record.counters
    if any(name not in c for name in numerators + denominators):
        return None
    den = sum(c[name] for name in denominators)
    if not den:
        return None
    return sum(c[name] for name in numerators) / den
