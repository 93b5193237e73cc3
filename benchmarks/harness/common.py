"""What both drivers share: the run's record, host spans, the device's
memory peak, the profiler window, and the model built from a configuration."""

from __future__ import annotations

import contextlib
import os
import shutil
import time
from dataclasses import dataclass, field

import jax


@dataclass
class RunRecord:
    """What a driver hands back to ``run.py``."""
    end_to_end: dict = field(default_factory=dict)    # metric name -> value
    counters: dict = field(default_factory=dict)      # whole window
    stats: dict = field(default_factory=dict)         # whole window, by name
    trace_counters: dict = field(default_factory=dict)  # the traced part
    trace_window_s: float = 0.0
    trace_dir: str = ""
    attempted: int = 0
    failed: int = 0
    numbers: dict = field(default_factory=dict)       # compared by the check
    memory_peak_bytes: int = 0
    notes: dict = field(default_factory=dict)


def config_for(cfg: dict, rehearse: bool) -> dict:
    """The configuration as it is run: with ``rehearse`` its builder's sizes
    are replaced by the file's tiny ``rehearse`` sizes (CPU only)."""
    if not rehearse:
        return cfg
    out = dict(cfg)
    out["builder"] = {**cfg["builder"],
                      "kwargs": {**cfg["builder"]["kwargs"], **cfg["rehearse"]}}
    return out


def build_module(cfg: dict):
    """The program's model object for a configuration's ``builder``."""
    from distkeras_tpu.models import zoo
    name = cfg["builder"]["function"].rsplit(".", 1)[1]
    kw = dict(cfg["builder"]["kwargs"])
    return getattr(zoo, name)(kw.pop("vocab_size"), **kw)


def build_model(cfg: dict, seed: int, seq_len: int, served_dtype=None):
    """The program's ``Model`` around the benchmark's weights. The shapes the
    program would have made itself are compared first: a weight of another
    shape, or one the program has and the benchmark lacks, is an error."""
    from distkeras_tpu.models import Model
    from harness import weights
    module = build_module(cfg)
    box = {}

    def init(key):
        params, state, box["out"] = module.init(key, (seq_len,))
        return params, state

    want, state = jax.eval_shape(init, jax.random.PRNGKey(0))
    params = weights.make_program_params(cfg, seed, served_dtype)
    got = jax.tree_util.tree_map(lambda a: a.shape, params)
    if jax.tree_util.tree_map(lambda a: a.shape, want) != got:
        raise RuntimeError("the benchmark's weights do not match the shapes "
                           f"{cfg['builder']['function']} makes")
    return Model(module, params, state, (seq_len,), box["out"])


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where the backend reports none)."""
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class Profiler:
    """The traced part of a window: started and stopped by the driver, host
    spans named ``bench.*`` only while it runs, the Python tracer off (it
    slows the host loop that the trace is there to show)."""

    def __init__(self, enabled: bool, trace_dir: str):
        self.enabled, self.dir = enabled, trace_dir
        self.running = False
        self.t0 = self.t1 = self.stop_s = None

    def start(self):
        if not self.enabled:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.running, self.t0 = True, time.perf_counter()

    def stop(self):
        if self.running:
            self.t1 = time.perf_counter()
            jax.profiler.stop_trace()       # writes the trace out: seconds
            self.stop_s = time.perf_counter() - self.t1
            self.running = False

    def span(self, name: str):
        if self.running:
            return jax.profiler.TraceAnnotation("bench." + name)
        return contextlib.nullcontext()

    @property
    def window_s(self) -> float:
        return 0.0 if self.t0 is None or self.t1 is None else self.t1 - self.t0
