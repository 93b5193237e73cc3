"""What the program's own set-up compiled or loaded, from its compile log
(``distkeras_tpu.obs.compile_log()``: one entry for each backend compile,
with the program's name, its tracing, lowering and backend seconds, what the
persistent cache said and the ``obs.span`` path that was open). The log is
process-global and outlives the driver's engine or trainer, so it is read
after the run. "Set-up" is the log's first
``record.notes["compiles_in_setup"]`` entries: every driver writes that count
from the same listener at the window's opening, so the reference's compiles
after the window are left out without a clock of the reader's own. Of those,
the program's are the ones that fell inside one of its spans
(``serving.init``, a ``serving.step`` of the warm-up, ``train.setup``, the
first ``train.dispatch``): an entry outside any span is the benchmark making
weights, keys and inputs one small program a time, which is the harness's
cost and not a layer's. The whole process's count and backend seconds are the
notes ``compiles_in_setup`` and ``compile_s_in_setup``.

``what``: ``"programs"`` their number; ``"hit_share"`` the share of them, in
percent, that the persistent cache answered; ``"backend_s"`` their backend
seconds (an XLA compile, or the load of a cached executable);
``"trace_lower_s"`` their tracing and lowering seconds, which no cache saves.
A program without a compile log (an older ``distkeras_tpu``), telemetry off
(no entry has a span then), a run without the note, or a log that dropped
entries at its bound: no metric. No persistent cache asked: no hit share. A
count or a sum of zero is a value."""

_SUMS = {"backend_s": ("backend_s",), "trace_lower_s": ("trace_s", "lower_s")}


def setup_entries(ctx):
    from distkeras_tpu import obs
    n = ctx.record.notes.get("compiles_in_setup")
    if n is None or not hasattr(obs, "compile_log") or not obs.enabled() \
            or obs.compile_totals().get("overflow"):
        return None
    return [e for e in obs.compile_log()[:n] if e["span"]]


def read(ctx, what: str):
    entries = setup_entries(ctx)
    if entries is None:
        return None
    if what == "programs":
        return len(entries)
    if what == "hit_share":
        asked = [e for e in entries if e["cache"] is not None]
        if not asked:
            return None
        return 100.0 * sum(e["cache"] == "hit" for e in asked) / len(asked)
    return sum(e[key] for e in entries for key in _SUMS[what])
