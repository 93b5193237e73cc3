"""The closed-loop driver of one-token decode for a model FAMILY that the
configuration file names (``"family": "<module>"``, a module of this
directory): the loop, the warm-up idea and the clock of
``serve_closed_driver`` (``clients`` callers, each submits its next request
when it sees its last one finished; timed from outside the engine), with
everything the GPT family's driver has built in asked of the family's
module instead:

``sizes(cfg)``, ``build_model(cfg, seed, max_len)``  the model and weights
``PROGRAM_PATHS``, ``health_check(health)``   what a chip run has to show
``prefill_counts(s, traffic, p, shared)``     the loop's own counts, by the
``decode_counts(s, ctx, page_len)``           family's kinds of layer
``engine_counters(engine)``                   the engine's, cumulative
``serve_numbers(cfg, seed, sample, traffic, control)``  the comparison

``--control ref-<x>`` hands ``<x>`` to the family's ``serve_numbers`` (a
planted fault in the program's place: the check has to fail). A further
family is a module with these names and a configuration file; no driver.
"""

from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from harness import common
from harness.serve_blockdiff_driver import _Requests
from harness.serve_closed_driver import _Loop, _latency_stats


class _FamilyLoop(_Loop):
    """The closed loop, with the family's counts beside the driver's."""

    def __init__(self, engine, requests, clients, profiler, family, sizes,
                 traffic):
        super().__init__(engine, requests, clients, profiler)
        self.family, self.sizes, self.traffic = family, sizes, traffic
        zero = {**family.prefill_counts(sizes, traffic, 1, 0),
                **family.decode_counts(sizes, 0, self.page_len)}
        self.counters.update(dict.fromkeys(zero, 0))

    def step(self):
        c = self.counters
        for r in self.engine.scheduler.running.values():
            ctx = len(r.prompt) + len(r.generated)
            for k, v in self.family.decode_counts(
                    self.sizes, ctx - 1, self.page_len).items():
                c[k] += v
        super().step()

    def _see(self, info, r, now):
        first = info["first_t"] is None and bool(r.generated)
        super()._see(info, r, now)
        if first:
            shared = int(getattr(r, "_shared_len", 0) or 0)
            for k, v in self.family.prefill_counts(
                    self.sizes, self.traffic, len(r.prompt), shared).items():
                self.counters[k] += v


def _warm(loop, requests):
    """One request of every prefill shape the mix can produce: each prompt
    length without a template; then every templated length three times
    over with prompts of their own: the first round registers the template,
    the second finds prompts parting at its end, misses, and leaves what a
    hit there needs (docs/serving.md §Page groups), the third hits."""
    eng = loop.engine
    shapes = requests.shapes_possible()
    rounds = [[sh for sh in shapes if not sh[1]]] \
        + [[sh for sh in shapes if sh[1]]] * 3
    for round_ in rounds:
        for length, templated in round_:
            eng.submit(*requests.make(length, templated, 4, template=0))
        while eng.scheduler.pending:
            eng.step()


def run(cell, cfg, traffic, args, t_start, trace_dir) -> common.RunRecord:
    from distkeras_tpu import obs
    from distkeras_tpu.serving import ServingEngine

    if traffic["sampling"] != "greedy":
        raise NotImplementedError("only greedy traffic is driven yet")
    family = importlib.import_module("harness." + cfg["family"])
    s = family.sizes(cfg)
    engine_kw = dict(traffic["engine"])
    ref_control = None
    if args.control and args.control.startswith("ref-"):
        ref_control = args.control[4:]    # a planted fault of the reference
    elif args.control:
        raise NotImplementedError("only the reference's controls are driven")
    model = family.build_model(cfg, args.seed, engine_kw["max_len"])
    engine = ServingEngine(model, **engine_kw)
    requests = _Requests(traffic, s["vocab"], args.seed)
    profiler = common.Profiler(bool(args.trace), trace_dir)
    loop = _FamilyLoop(engine, requests, traffic["clients"], profiler, family,
                       s, traffic)
    rec = common.RunRecord()

    _warm(loop, requests)
    while len(loop.done) + loop.failed < traffic["warm_completions"]:
        loop.fill()
        loop.step()
    health = engine.health()
    programs = health["programs"]
    rec.notes["programs"] = programs
    if not args.rehearse:
        for name, paths in family.PROGRAM_PATHS.items():
            if not all(p in programs.get(name, "") for p in paths):
                raise RuntimeError(f"a reference path served the cell: {programs}")
    family.health_check(health)

    # --- the window: the same loop, from now for --seconds ------------------
    def counters_now():
        return {**loop.counters, **family.engine_counters(engine)}

    totals = obs.compile_totals()
    compiles = totals["count"]
    rec.notes.update(compiles_in_setup=compiles, compile_s_in_setup=totals["seconds"])
    n_before, failed_before = len(loop.done), loop.failed
    base = counters_now()
    profiler.start()
    t_open = t_stats = time.perf_counter()
    traced = None
    while True:
        loop.fill()
        loop.step()
        now = time.perf_counter()
        if profiler.running and now - t_open >= traffic["trace_seconds"]:
            profiler.stop()       # writes the trace out: seconds in which no step runs
            traced = {k: v - base[k] for k, v in counters_now().items()}
            t_stats = time.perf_counter()
        if now - t_open >= args.seconds:
            break
    t_close = time.perf_counter()
    if profiler.running:
        profiler.stop()
        traced = {k: v - base[k] for k, v in counters_now().items()}
    compiles_in_window = obs.compile_totals()["count"] - compiles
    window = loop.done[n_before:]
    failed = loop.failed - failed_before
    counters = {k: v - base[k] for k, v in counters_now().items()}

    # every request still under way gets its answer, or counts as failed
    deadline = time.perf_counter() + traffic["drain_seconds"]
    while loop.inflight and time.perf_counter() < deadline:
        loop.step()
        loop.free.clear()
    failed += len(loop.inflight)
    rec.memory_peak_bytes = common.memory_peak_bytes()

    wall = t_close - t_open
    rec.end_to_end = {"setup_s": t_open - t_start}
    if counters["seen_tokens"]:
        rec.end_to_end["serve_tokens_per_s"] = counters["seen_tokens"] / wall
    # a traced run's latencies: of the requests sent once the trace was written
    rec.stats = _latency_stats([r for r in window if r["submit_t"] >= t_stats])
    slots = {"num_slots": engine_kw["num_slots"]}
    rec.counters = {**counters, "window_s": wall, "requests": len(window), **slots}
    rec.trace_counters = {**(traced or {}), **slots}
    rec.trace_window_s, rec.trace_dir = profiler.window_s, trace_dir
    rec.notes["trace_stop_s"] = profiler.stop_s
    rec.attempted, rec.failed = len(window) + failed, failed
    health = engine.health()
    rec.notes.update(compiles_in_window=compiles_in_window,
                     completed_tokens_per_s=counters["completed_tokens"] / wall,
                     kv_groups=health.get("kv_groups"), pages=health["pages"],
                     prefix_cache=engine.metrics.summary().get("prefix_cache"))
    if compiles_in_window:
        raise RuntimeError(f"{compiles_in_window} compilations inside the window")

    # a sample of what the window finished, the longest in it, drawn from the seed
    rng = np.random.default_rng(args.seed)
    order = sorted(range(len(window)),
                   key=lambda i: -(len(window[i]["prompt"]) + len(window[i]["served"])))
    picks = order[:1] + [int(i) for i in rng.permutation(order[1:])
                         [:max(traffic["checked_requests"] - 1, 0)]]
    sample = [(window[i]["prompt"], window[i]["served"]) for i in picks]

    # the program's state goes before the reference comes
    del engine, model, loop, window, health
    gc.collect()
    t_check = time.perf_counter()
    rec.numbers = family.serve_numbers(cfg, args.seed, sample, traffic,
                                       control=ref_control) if sample else {}
    rec.notes["check_s"] = time.perf_counter() - t_check
    return rec
