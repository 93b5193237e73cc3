"""The whole step's share of the chips' peak: model operations (from
``harness/flops.py``, never ``cost_analysis()``) of the traced part of the
window, over its wall time, over chips x peak bf16 FLOP/s, in percent."""

from harness import flops


def read(ctx, kind: str):
    c, window = ctx.record.trace_counters, ctx.record.trace_window_s
    if not window or not c:
        return None
    if kind == "train":
        if not c.get("tokens"):
            return None
        ops = flops.train_flops_per_token(ctx.sizes, c["seq_len"]) * c["tokens"]
    elif kind == "serve":
        if not c.get("decode_tokens") and not c.get("prefill_tokens"):
            return None
        ops = flops.serve_flops(ctx.sizes, c)
    else:
        raise ValueError(f"mfu reader: unknown kind {kind!r}")
    return 100.0 * ops / window / (ctx.chips * ctx.peaks["flops_bf16"])
