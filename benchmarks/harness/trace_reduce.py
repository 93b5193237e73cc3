"""From a profiler trace (``.xplane.pb``) to numbers: the device's busy
time, an operation's or a kernel's time, the operations that took most time
and the longest idle gaps by what the host was doing in them.

Read with ``jax.profiler.ProfileData`` and nothing else. On a TPU the trace
has one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` holds one
event per executed HLO operation (its name is the HLO text, ``%name = ...``;
a Pallas kernel's ``name=`` is part of that name) and whose line
``XLA Modules`` holds one event per executed program. Host threads are lines
of the plane ``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans land on the
line of the thread that opened them. All times are nanoseconds on one clock.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
#: operations that only contain others: their time is their children's
CONTAINERS = ("while", "conditional", "call")


@dataclass
class Trace:
    """Events of one trace, as ``(start_ns, end_ns, name)`` tuples."""
    device_ops: dict = field(default_factory=dict)   # plane name -> [events]
    device_programs: dict = field(default_factory=dict)  # "XLA Modules" events
    host_spans: list = field(default_factory=list)   # every host-thread event

    @property
    def chips(self) -> int:
        return len(self.device_ops)


def short_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def op_kind(hlo: str) -> str:
    """``fusion.12`` -> ``fusion``: the name without its running number."""
    return re.sub(r"[._]*\d*$", "", short_name(hlo)) or short_name(hlo)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file (or the newest one under a directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                into = {OPS_LINE: trace.device_ops,
                        PROGRAMS_LINE: trace.device_programs}.get(line.name)
                if into is not None:
                    into[plane.name] = [
                        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                trace.host_spans.extend(
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in line.events)
    return trace


def clip(events, t0=None, t1=None):
    """The events cut to ``[t0, t1]`` (either may be None)."""
    out = []
    for a, b, name in events:
        a = a if t0 is None else max(a, t0)
        b = b if t1 is None else min(b, t1)
        if b > a:
            out.append((a, b, name))
    return out


def union(events):
    """Merged ``[start, end]`` intervals covered by any of ``events``."""
    merged = []
    for a, b, _ in sorted(events):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_seconds(trace: Trace, t0=None, t1=None) -> float:
    """Seconds in which an operation ran on the device, averaged over the
    chips in the trace."""
    if not trace.device_ops:
        return 0.0
    total = sum(b - a for ops in trace.device_ops.values()
                for a, b in union(clip(ops, t0, t1)))
    return total / trace.chips / 1e9


def program_seconds(trace: Trace, t0=None, t1=None) -> float:
    """Seconds in which a compiled program was executing, averaged over chips."""
    if not trace.device_programs:
        return 0.0
    total = sum(b - a for evs in trace.device_programs.values()
                for a, b in union(clip(evs, t0, t1)))
    return total / len(trace.device_programs) / 1e9


def _leaf_ops(ops):
    return [e for e in ops if op_kind(e[2]) not in CONTAINERS]


def kernel_seconds(trace: Trace, names, t0=None, t1=None) -> dict:
    """Per kernel ``name=``: summed device seconds of the operations whose
    name holds it, averaged over chips, and how many ran. A name that matches
    nothing is left out."""
    out = {}
    for ops in trace.device_ops.values():
        for a, b, hlo in clip(_leaf_ops(ops), t0, t1):
            sn = short_name(hlo)
            for k in names:
                if k in sn:
                    sec, n = out.get(k, (0.0, 0))
                    out[k] = (sec + (b - a) / 1e9 / trace.chips, n + 1)
    return out


def top_ops(trace: Trace, n: int = 10, t0=None, t1=None) -> list:
    """``[[kind, seconds], ...]``: device time by kind of operation (the HLO
    name without its number), containers left out, averaged over chips."""
    acc: dict = {}
    for ops in trace.device_ops.values():
        for a, b, hlo in clip(_leaf_ops(ops), t0, t1):
            k = op_kind(hlo)
            acc[k] = acc.get(k, 0.0) + (b - a) / 1e9 / trace.chips
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10, t0=None, t1=None,
              prefix: str = "bench.") -> list:
    """``[[what, seconds], ...]``: the first chip's idle time, by the
    shortest of the benchmark's own host spans (names starting with
    ``prefix``) that covers most of each gap; where none does, by the runtime's
    own host event that does (``host:<name>``), else ``"(no span)"``."""
    if not trace.device_ops:
        return []
    ops = clip(next(iter(sorted(trace.device_ops.items())))[1], t0, t1)
    busy = union(ops)
    if not busy:
        return []
    lo = busy[0][0] if t0 is None else t0
    hi = busy[-1][1] if t1 is None else t1
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    by_length = lambda s: s[1] - s[0]              # most specific first
    spans = sorted((s for s in trace.host_spans if s[2].startswith(prefix)),
                   key=by_length)
    others = sorted((s for s in trace.host_spans if not s[2].startswith(prefix)),
                    key=by_length)

    def covering(a, b, candidates):
        for sa, sb, name in candidates:
            if min(b, sb) - max(a, sa) >= 0.5 * (b - a):
                return name
        return None

    acc: dict = {}
    for a, b in gaps:
        what = covering(a, b, spans)
        if what is None:        # none of the benchmark's spans: the runtime's own
            what = covering(a, b, others)
            what = "(no span)" if what is None else "host:" + what
        acc[what] = acc.get(what, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]
