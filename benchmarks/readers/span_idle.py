"""The device's idle time of the traced window by the program's own host
spans (``distkeras_tpu.obs.span``: ``serving.*``, ``train.*``), in percent
of the window. ``harness/trace_reduce.idle_gaps`` gives every idle gap of
the first chip to the shortest span with one of ``prefixes`` that covers
most of it; this reader sums the gaps given to ``under`` or to a span below
it (``under + "."``). With ``under`` null it is the whole idle share as
``readers/idle_share.py`` reckons it, less the gaps under each name in
``minus``: what no named span explains, the window's two edges and the
caller's own code between the program's spans. No span with such a prefix
in the trace: no metric.

A gap given to a span in which the host itself waits for the device
(``serving.decode.fetch``, ``serving.prefill.first_token``, ``train.fetch``)
is the device's own gap inside or between its programs, not the host's
doing. The table by span, printed on stderr once a run, tells the two
apart, with the idle between programs against the idle inside them."""

import sys

from harness import trace_reduce


def _below(rows, name):
    return sum(sec for what, sec in rows
               if what == name or what.startswith(name + "."))


def read(ctx, prefixes: list, under=None, minus=()):
    window = ctx.record.trace_window_s
    prefix = tuple(prefixes)
    busy = trace_reduce.busy_seconds(ctx.trace)
    if not window or not busy \
            or not any(s[2].startswith(prefix) for s in ctx.trace.host_spans):
        return None
    rows = trace_reduce.idle_gaps(ctx.trace, n=10 ** 6, prefix=prefix)
    idle = window - busy
    if not getattr(ctx, "span_idle_printed", False):
        ctx.span_idle_printed = True
        between = window - trace_reduce.program_seconds(ctx.trace)
        print(f"idle by span {'|'.join(prefix)}: {idle:.6f}s of {window:.6f}s; "
              f"between programs {between:.6f}s, inside them "
              f"{idle - between:.6f}s", file=sys.stderr)
        for what, sec in rows:
            print(f"  {what:<36s} {sec:.6f}s {100.0 * sec / window:6.3f}%",
                  file=sys.stderr)
        print(f"  {'(edges of the window)':<36s} "
              f"{idle - sum(sec for _, sec in rows):.6f}s", file=sys.stderr)
    if under is not None:
        return 100.0 * _below(rows, under) / window
    return 100.0 * (idle - sum(_below(rows, name) for name in minus)) / window
