"""The closed-loop serving driver: ``clients`` callers that each submit
their next request when they see their last one finished, timed on the wall
clock from outside the engine (``submit()`` to the ``step()`` after which the
token is seen), so that a change to the engine cannot change the yardstick.

Set-up warms every prefill shape the mix can produce and the decode program,
then runs the loop untimed until ``warm_completions`` requests have finished,
so that the window opens on a loop in its steady state (slots staggered,
prefix cache filled). The window is a stretch of that same loop.
"""

from __future__ import annotations

import gc
import time

import jax.numpy as jnp
import numpy as np

from harness import check, common, traffic as traffic_mod, weights


class _Loop:
    """The loop's state: who waits for what, and what was seen when."""

    def __init__(self, engine, requests, clients, profiler):
        self.engine, self.requests, self.profiler = engine, requests, profiler
        self.free = list(range(clients))
        self.inflight = {}            # rid -> dict
        self.done = []                # finished request records, in order
        self.failed = 0
        self.counters = dict.fromkeys(
            ("steps", "prefills", "prefill_tokens", "prefill_context",
             "decode_tokens", "decode_context", "decode_page_tokens",
             "occupied_slots", "seen_tokens", "completed_tokens"), 0)
        self.page_len = engine.page_len or 1

    def submit(self, prompt, out_len, client):
        t = time.perf_counter()
        rid = self.engine.submit(prompt, out_len)
        self.inflight[rid] = {"client": client, "submit_t": t, "first_t": None,
                              "prompt": prompt, "out_len": out_len, "seen": 0}

    def fill(self):
        with self.profiler.span("submit"):
            while self.free:
                self.submit(*next(self.requests), self.free.pop())

    def step(self):
        from distkeras_tpu.serving.scheduler import RequestState
        c, eng = self.counters, self.engine
        for r in eng.scheduler.running.values():      # the batch this step decodes
            ctx = len(r.prompt) + len(r.generated)
            c["decode_tokens"] += 1
            c["decode_context"] += ctx
            c["decode_page_tokens"] += -(-ctx // self.page_len) * self.page_len
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
                info.update(done_t=now, served=list(r.generated))
                c["completed_tokens"] += len(r.generated)
                self.done.append(info)
            for rid, info in self.inflight.items():
                self._see(info, eng[rid], now)

    def _see(self, info, r, now):
        """What a streaming client sees after this step: the request's new
        tokens, and the first one's time."""
        self.counters["seen_tokens"] += len(r.generated) - info["seen"]
        info["seen"] = len(r.generated)
        if info["first_t"] is not None or not r.generated:
            return
        info["first_t"] = now
        p, shared = len(r.prompt), int(getattr(r, "_shared_len", 0) or 0)
        c = self.counters
        c["prefills"] += 1
        c["prefill_tokens"] += p - shared
        c["prefill_context"] += (p * (p + 1) - shared * (shared + 1)) // 2


def _warm(loop, requests, traffic):
    """One request of every prefill shape the mix can produce: each prompt
    length without a template, then with one (the template's first use shares
    nothing, so it is sent once before), a few tokens each."""
    eng = loop.engine
    plain = [sh for sh in requests.shapes_possible() if not sh[1]]
    templ = [sh for sh in requests.shapes_possible() if sh[1]]
    batches = [[requests.make(length, False, 4) for length, _ in plain]]
    if templ:
        batches.append([requests.make(templ[0][0], True, 4, template=0)])
        batches.append([requests.make(length, True, 4, template=0)
                        for length, _ in templ])
    for batch in batches:
        for prompt, out in batch:
            eng.submit(prompt, out)
        while eng.scheduler.pending:
            eng.step()


def percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def _latencies(finished):
    """Per request, in ms: first token seen - submit; and (last token - first
    token) / (tokens - 1)."""
    ttft = [1e3 * (r["first_t"] - r["submit_t"]) for r in finished]
    tpot = [1e3 * (r["done_t"] - r["first_t"]) / (len(r["served"]) - 1)
            for r in finished if len(r["served"]) > 1]
    return ttft, tpot


def _latency_stats(finished):
    """Medians and 95th percentiles over ALL requests finished in the window."""
    ttft, tpot = _latencies(finished)
    if not tpot:
        return {}
    return {f"{name}_p{q}_ms": percentile(values, q)
            for name, values in (("ttft", ttft), ("tpot", tpot)) for q in (50, 95)}


def run(cell, cfg, traffic, args, t_start, trace_dir) -> common.RunRecord:
    from distkeras_tpu import obs
    from distkeras_tpu.serving import ServingEngine

    if traffic["sampling"] != "greedy":
        raise NotImplementedError("only greedy traffic is driven yet")
    s = weights.sizes(cfg)
    engine_kw = dict(traffic["engine"])
    ref_control = None
    if args.control and args.control.startswith("ref-"):
        ref_control = args.control[4:]        # the reference at that precision
    elif args.control:
        engine_kw["weight_quant"] = args.control     # the program's own lower precision
    model = common.build_model(cfg, args.seed, engine_kw["max_len"],
                               served_dtype=jnp.bfloat16)
    engine = ServingEngine(model, **engine_kw)
    requests = traffic_mod.ClosedLoopRequests(traffic, s["vocab"], args.seed)
    profiler = common.Profiler(bool(args.trace), trace_dir)
    loop = _Loop(engine, requests, traffic["clients"], profiler)
    rec = common.RunRecord()

    _warm(loop, requests, traffic)
    while len(loop.done) + loop.failed < traffic["warm_completions"]:
        loop.fill()
        loop.step()
    programs = engine.health()["programs"]
    rec.notes["programs"] = programs
    if not args.rehearse:
        if "paged_attention=kernel" not in programs.get("decode_greedy", "") \
                or "flash_attention=kernel" not in programs.get("prefill", ""):
            raise RuntimeError(f"a reference path served the cell: {programs}")

    # --- the window: the same loop, from now for --seconds ------------------
    totals = obs.compile_totals()
    compiles = totals["count"]
    rec.notes.update(compiles_in_setup=compiles, compile_s_in_setup=totals["seconds"])
    n_before, failed_before = len(loop.done), loop.failed
    base = dict(loop.counters)
    profiler.start()
    t_open = t_stats = time.perf_counter()
    traced = None
    while True:
        loop.fill()
        loop.step()
        now = time.perf_counter()
        if profiler.running and now - t_open >= traffic["trace_seconds"]:
            profiler.stop()       # writes the trace out: seconds in which no step runs
            traced = {k: v - base[k] for k, v in loop.counters.items()}
            t_stats = time.perf_counter()
        if now - t_open >= args.seconds:
            break
    t_close = time.perf_counter()
    if profiler.running:
        profiler.stop()
        traced = {k: v - base[k] for k, v in loop.counters.items()}
    compiles_in_window = obs.compile_totals()["count"] - compiles
    window = loop.done[n_before:]
    failed = loop.failed - failed_before
    counters = {k: v - base[k] for k, v in loop.counters.items()}

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
    slowest = sorted(window, key=lambda r: r["submit_t"] - r["first_t"])[:8]
    slots = {"num_slots": engine_kw["num_slots"]}
    rec.counters = {**counters, "window_s": wall, "requests": len(window), **slots}
    rec.trace_counters = {**(traced or {}), **slots}
    rec.trace_window_s, rec.trace_dir = profiler.window_s, trace_dir
    rec.notes["trace_stop_s"] = profiler.stop_s
    rec.attempted, rec.failed = len(window) + failed, failed
    rec.notes.update(compiles_in_window=compiles_in_window,
                     completed_tokens_per_s=counters["completed_tokens"] / wall,
                     # [ttft ms, prompt length, seconds into the window]
                     slowest_ttft=[[round(1e3 * (r["first_t"] - r["submit_t"]), 1),
                                    len(r["prompt"]), round(r["submit_t"] - t_open, 2)]
                                   for r in slowest],
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
    pad_to, max_out = engine_kw["max_len"], traffic["output"]["max"]

    # the program's state goes before the reference comes
    del engine, model, loop, window
    gc.collect()
    t_check = time.perf_counter()
    rec.numbers = check.serve_numbers(cfg, args.seed, sample, pad_to, max_out,
                                      control=ref_control) if sample else {}
    rec.notes["check_s"] = time.perf_counter() - t_check
    return rec
