"""The closed-loop driver of a block-diffusion cell: the loop, the warm-up
and the clock of ``serve_closed_driver`` (``clients`` callers, each submits its
next request when it sees its last one finished; timed from outside the
engine), around a ``ServingEngine`` that decodes a block of tokens at a time.

What differs: a step yields no token for most streams and a whole block for
some, so ``serve_tokens_per_s`` counts whole committed blocks as a streaming
client saw them appear; the loop's own counts are of passes (rows of live
slots, keys they attend, pages read, rows that need the vocabulary head), not
of one token a slot; the engine's block-diffusion counters (passes, blocks and
tokens committed, rows routed and experts touched by the passes and by the
prefills, as the programs report them) are copied into the record for the
readers; and the check is ``sdar_check``: the reference rebuilds the
denoising states of a sample of served blocks from the pass that fixed each
token, which the engine keeps on ``Request.fixed_pass``.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from harness import common, sdar_check, sdar_family, traffic as traffic_mod
from harness.serve_closed_driver import _Loop, _latency_stats, _warm

#: engine counters copied into the record (``metrics.summary()
#: ["block_diffusion"]``), under these names
ENGINE_COUNTERS = ("passes_denoise", "passes_commit", "slot_passes_denoise",
                   "slot_passes_commit", "blocks_committed",
                   "tokens_committed", "rows_routed", "experts_touched",
                   "prefill_rows_routed", "prefill_experts_touched")


class _Requests(traffic_mod.ClosedLoopRequests):
    """The stream of the chat cell; a templated prompt no longer than its
    template IS the template (there is no room for an id of its own), so its
    pages are the template's and every prefill shape stays one that set-up
    warmed."""

    def make(self, length, templated, output_len, template=None):
        if not templated or length > self.tlen:
            return super().make(length, templated, output_len, template)
        t = int(self.rng.integers(len(self.templates))) if template is None \
            else template
        self.made += 1
        return self.templates[t][:length].copy(), int(output_len)


class _BlockLoop(_Loop):
    """The closed loop, counting what a block-diffusion pass does."""

    def __init__(self, engine, requests, clients, profiler, sizes):
        super().__init__(engine, requests, clients, profiler)
        self.sizes = sizes
        self.counters.update(dict.fromkeys(
            ("pass_rows", "pass_context", "pass_page_tokens", "denoise_rows"),
            0))

    def step(self):
        from distkeras_tpu.serving.scheduler import RequestState
        c, eng, b = self.counters, self.engine, self.sizes["block_len"]
        for start, masked in eng.block_positions().values():
            keys = start + b                  # the cached blocks and its own
            c["pass_rows"] += b
            c["pass_context"] += b * keys
            c["pass_page_tokens"] += -(-keys // self.page_len) * self.page_len
            c["denoise_rows"] += b if masked else 0
        with self.profiler.span("step"):
            finished = eng.step()
        now = time.perf_counter()
        with self.profiler.span("observe"):
            c["steps"] += 1
            c["occupied_slots"] += len(eng.scheduler.running)
            for r in finished:
                info = self.inflight.pop(r.rid, None)
                if info is None:
                    continue
                self._see(info, r, now)
                self.free.append(info["client"])
                if r.state is not RequestState.FINISHED \
                        or len(r.generated) != info["out_len"]:
                    self.failed += 1
                    continue
                info.update(done_t=now, served=list(r.generated),
                            fixed_pass=list(r.fixed_pass))
                c["completed_tokens"] += len(r.generated)
                self.done.append(info)
            for rid, info in self.inflight.items():
                self._see(info, eng[rid], now)

    def _see(self, info, r, now):
        """A streaming client's view after this step (whole blocks), and,
        once per request, what its prefill processed: the whole blocks of
        the prompt past the cache hit, each row over the keys up to the end
        of its own block."""
        c, s = self.counters, self.sizes
        c["seen_tokens"] += len(r.generated) - info["seen"]
        info["seen"] = len(r.generated)
        if info["first_t"] is not None or not r.generated:
            return
        info["first_t"] = now
        b = s["block_len"]
        whole = len(r.prompt) // b * b
        shared = int(getattr(r, "_shared_len", 0) or 0)
        blocks = lambda n: (n // b) * (n // b + 1) // 2 * b * b
        c["prefills"] += 1
        c["prefill_tokens"] += whole - shared
        c["prefill_context"] += blocks(whole) - blocks(shared)


def _engine_counters(engine) -> dict:
    bd = engine.metrics.summary().get("block_diffusion") or {}
    flat = {f"{k}_{kind}": v for k in ("passes", "slot_passes")
            for kind, v in bd.get(k, {}).items()}
    flat.update({k: v for k, v in bd.items() if not isinstance(v, dict)})
    return {k: flat.get(k, 0) for k in ENGINE_COUNTERS}


def run(cell, cfg, traffic, args, t_start, trace_dir) -> common.RunRecord:
    from distkeras_tpu import obs
    from distkeras_tpu.serving import ServingEngine

    s = sdar_family.sizes(cfg)
    engine_kw = dict(traffic["engine"])
    ref_control = None
    if args.control and args.control.startswith("ref-"):
        ref_control = args.control[4:]        # the reference at that precision
    elif args.control:
        raise NotImplementedError("only the reference's controls are driven")
    model = sdar_family.build_model(cfg, args.seed, engine_kw["max_len"])
    engine = ServingEngine(model, **engine_kw)
    requests = _Requests(traffic, s["vocab"], args.seed)
    profiler = common.Profiler(bool(args.trace), trace_dir)
    loop = _BlockLoop(engine, requests, traffic["clients"], profiler, s)
    rec = common.RunRecord()

    _warm(loop, requests, traffic)
    # one stream alone over two blocks: its commit pass has no denoising
    # slot beside it, which is the only way the head-less program runs
    engine.submit(*requests.make(requests.shapes_possible()[0][0], False,
                                 2 * s["block_len"]))
    while engine.scheduler.pending:
        engine.step()
    while len(loop.done) + loop.failed < traffic["warm_completions"]:
        loop.fill()
        loop.step()
    programs = engine.health()["programs"]
    rec.notes["programs"] = programs
    if not args.rehearse:
        for name, paths in (("prefill", ("flash_attention=kernel",
                                         "moe=grouped_kernel")),
                            ("denoise", ("paged_attention=kernel",
                                         "moe=grouped_kernel")),
                            ("commit", ("paged_attention=kernel",))):
            if not all(p in programs.get(name, "") for p in paths):
                raise RuntimeError(f"a reference path served the cell: {programs}")

    # --- the window: the same loop, from now for --seconds ------------------
    def counters_now():
        return {**loop.counters, **_engine_counters(engine)}

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
    rec.notes.update(compiles_in_window=compiles_in_window,
                     completed_tokens_per_s=counters["completed_tokens"] / wall,
                     block_diffusion=engine.health().get("block_diffusion"),
                     prefix_cache=engine.metrics.summary().get("prefix_cache"))
    if compiles_in_window:
        raise RuntimeError(f"{compiles_in_window} compilations inside the window")

    # a sample of what the window finished, the longest in it, drawn from the seed
    rng = np.random.default_rng(args.seed)
    order = sorted(range(len(window)),
                   key=lambda i: -(len(window[i]["prompt"]) + len(window[i]["served"])))
    picks = order[:1] + [int(i) for i in rng.permutation(order[1:])
                         [:max(traffic["checked_requests"] - 1, 0)]]
    sample = [(window[i]["prompt"], window[i]["served"], window[i]["fixed_pass"])
              for i in picks]
    pad_to = max(traffic["prompt_lengths"]) + traffic["output"]["max"]

    # the program's state goes before the reference comes
    del engine, model, loop, window
    gc.collect()
    t_check = time.perf_counter()
    rec.numbers = sdar_check.serve_numbers(
        cfg, args.seed, sample, engine_kw, traffic["checked_blocks"], pad_to,
        control=ref_control) if sample else {}
    rec.notes["check_s"] = time.perf_counter() - t_check
    return rec
