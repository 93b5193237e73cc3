"""Serving metrics: the numbers that describe a serving workload, none
of which a single ``generate()`` call can even express.

Per request: TTFT (submit -> first token — prefill queueing + prompt
ingestion), TPOT (mean seconds per generated token after the first —
the streaming-cadence number the ``tpot_p99`` SLO reads) and
end-to-end latency. A step does not always yield one token a stream:
speculation yields 1..k+1, and a block-diffusion engine yields nothing
for ``denoising_steps - 1`` passes and then a whole block, so TTFT is
submit -> the first BLOCK a client may see and TPOT is per token of
the committed blocks (``summary()["block_diffusion"]`` has the passes,
blocks and tokens behind it). Per engine iteration: queue depth,
slot occupancy, decoding-slot count and decode wall time (the
steady-state tokens/s series ``bench.py --model serving`` reduces).
Phase wall-clock (prefill vs decode) rides on
``utils.profiling.StepTimer``.

Since the telemetry PR this class is a thin shape over the
``obs.MetricsRegistry``: TTFT/latency/queue-depth/occupancy live in
registry **reservoir histograms**, so memory is BOUNDED —
O(reservoir + in-flight requests + distinct batch sizes) no matter how
long the engine runs (previously the ttft/latency/occupancy lists grew
one float per request/iteration forever). Exact count/sum/min/max are
streaming; percentiles come from the reservoir (exact until it fills,
a uniform sample after). Per-request state is still streaming: submit
timestamps live only while a request is in flight and are evicted at
finish. A fresh ``ServingMetrics`` per reporting interval
(``engine.metrics = ServingMetrics()``, the ``bench.py`` per-pass
pattern) remains the way to get windowed percentiles.

``summary()`` keys are unchanged from the pre-registry class — the
backward-compat contract existing callers (bench, tests, dashboards)
rely on; ``docs/observability.md`` is the glossary.
"""

from __future__ import annotations

from collections import deque
from typing import Dict, List, Optional

import numpy as np

from distkeras_tpu.obs import MetricsRegistry
from distkeras_tpu.utils.profiling import StepTimer, now

#: per-histogram reservoir: the percentile window of a metrics instance
DEFAULT_RESERVOIR = 2048


class ServingMetrics:
    """Host-side counters; negligible overhead (a few registry updates
    and two clock reads per phase). ``clock`` is injectable so tests
    can drive deterministic time. ``registry`` defaults to a PRIVATE
    registry per instance — a metrics object is a measurement window,
    and windows must not share reservoirs; the engine attaches the
    window to the unified ``obs.telemetry_snapshot()`` by reference."""

    def __init__(self, clock=now, registry: Optional[MetricsRegistry] = None,
                 reservoir: int = DEFAULT_RESERVOIR):
        self.clock = clock
        self.registry = registry if registry is not None \
            else MetricsRegistry(reservoir_size=reservoir)
        self.timer = StepTimer()                 # "prefill" / "decode"
        self.submit_ts: Dict[int, float] = {}    # in-flight only
        self.first_ts: Dict[int, float] = {}     # in-flight only
        self._ttft = self.registry.histogram("serving.ttft_s")
        self._tpot = self.registry.histogram("serving.tpot_s")
        self._latency = self.registry.histogram("serving.latency_s")
        self._qdepth = self.registry.histogram("serving.queue_depth")
        self._occ = self.registry.histogram("serving.slot_occupancy")
        self._finished = self.registry.counter("serving.requests_finished")
        self._tokens = self.registry.counter("serving.tokens_generated")
        self._chunks = self.registry.counter("serving.prefill_chunks")
        # degradation counters (resilience PR): shed at admission,
        # expired deadlines, poisoned-request isolations
        self._rejected = self.registry.counter("serving.requests_rejected")
        self._timed_out = self.registry.counter(
            "serving.requests_timed_out")
        self._cancelled = self.registry.counter(
            "serving.requests_cancelled")
        self._decode_toks = self.registry.counter("serving.decode_tokens")
        self._decode_secs = self.registry.counter("serving.decode_seconds")
        # paged-KV accounting (paged-cache PR): page-budget gauges set
        # once per iteration, prefix-cache hit counters, preemptions.
        # Gauges stay unset (None) until the engine's first iteration
        # is recorded — summary keys are additive
        self._pages_free = self.registry.gauge("serving.pages_free")
        self._pages_shared = self.registry.gauge("serving.pages_shared")
        self._page_frag = self.registry.gauge(
            "serving.page_fragmentation")
        self._prefix_hits = self.registry.counter("serving.prefix_hits")
        self._prefix_lookups = self.registry.counter(
            "serving.prefix_lookups")
        self._prefix_hit_toks = self.registry.counter(
            "serving.prefix_hit_tokens")
        self._prefix_lookup_toks = self.registry.counter(
            "serving.prefix_lookup_tokens")
        # the prefix cache's eviction index (PrefixCache's class doc):
        # pages it gave back, heap tops and nodes it looked at to
        # choose them and keep its counts, evictable_pages() calls
        self._prefix_evictions = self.registry.counter(
            "serving.prefix_evictions")
        self._prefix_evict_examined = self.registry.counter(
            "serving.prefix_evict_examined")
        self._prefix_evictable_queries = self.registry.counter(
            "serving.prefix_evictable_queries")
        self._preempted = self.registry.counter(
            "serving.requests_preempted")
        # host KV offload tier (offload PR): pages swapped D2H on
        # preemption / prefix spill, pages restored H2D, bytes moved;
        # resume-latency histograms split by path (page swap-in vs
        # context re-prefill — the bench's crossover measurement) and
        # the re-prefill token tallies (recomputed vs avoided)
        self._pages_offloaded = self.registry.counter(
            "serving.pages_offloaded")
        self._pages_restored = self.registry.counter(
            "serving.pages_restored")
        self._offload_bytes = self.registry.counter(
            "serving.offload_bytes")
        self._resume_swap = self.registry.histogram(
            "serving.resume_swap_s")
        self._resume_reprefill = self.registry.histogram(
            "serving.resume_reprefill_s")
        self._reprefill_toks = self.registry.counter(
            "serving.reprefill_tokens")
        self._reprefill_toks_avoided = self.registry.counter(
            "serving.reprefill_tokens_avoided")
        # serving router (router PR): requests detached from this
        # engine for re-admission on another replica (prefill->decode
        # handoff, drain rebalancing) — NOT terminal, NOT preemptions
        self._transferred = self.registry.counter(
            "serving.requests_transferred")
        # speculative decoding (spec-decode PR): drafts offered to the
        # verify step vs drafts the target accepted, plus a per-slot
        # per-iteration acceptance-rate histogram (the bench's
        # percentile source) and streams the acceptance EMA kicked
        # back to plain decode
        self._spec_proposed = self.registry.counter("serving.spec_proposed")
        self._spec_accepted = self.registry.counter("serving.spec_accepted")
        self._spec_rate = self.registry.histogram(
            "serving.spec_accept_rate")
        self._spec_disabled = self.registry.counter(
            "serving.spec_disabled")
        # adaptive re-enable (ServingEngine(spec_reprobe=...)): demoted
        # streams the cooldown re-probe won back to speculation
        self._spec_reenabled = self.registry.counter(
            "serving.spec_reenabled")
        # tree speculation (tree-speculation PR): the per-verify tree
        # width a stream ran at and the accepted root-path length —
        # the adaptive controller's observable trajectory
        self._spec_tree_width = self.registry.histogram(
            "serving.spec_tree_width")
        self._spec_path_len = self.registry.histogram(
            "serving.spec_path_len")
        # MoE serving (MoE-serving PR): per-expert routing load (one
        # gauge series per expert id — BOUNDED by the model's expert
        # count), the router-entropy gauge, and the concentration the
        # engine's MoE-aware admission reads. Unset (None) on MoE-free
        # engines — summary keys stay layout-honest like "pages"
        self._moe_load = self.registry.gauge("serving.moe_expert_load")
        self._moe_entropy = self.registry.gauge(
            "serving.moe_router_entropy")
        self._moe_conc = self.registry.gauge(
            "serving.moe_concentration")
        self._moe_experts = 0            # label-set bound, for summary
        # block diffusion (block-diffusion PR): passes by kind (the
        # denoise program carries committing slots too; "commit" counts
        # passes that ran head-less because every live slot was
        # committing), slot-passes by what the slot did, blocks and
        # tokens a client was shown, and what the expert layers did in
        # those passes and (kind="prefill") in the prefills (rows
        # routed; experts that owned a row, summed over the expert
        # layers that ran: the programs' own counts); and of the
        # pipelined pass (``overlap``): passes dispatched while the one
        # before was still unread, and rows a slot rode for a request
        # that had ended or left by the time they were read
        self._bd_passes = self.registry.counter("serving.blockdiff_passes")
        self._bd_overlapped = self.registry.counter(
            "serving.blockdiff_passes_overlapped")
        self._bd_discarded = self.registry.counter(
            "serving.blockdiff_slot_passes_discarded")
        self._bd_slot_passes = self.registry.counter(
            "serving.blockdiff_slot_passes")
        self._bd_blocks = self.registry.counter(
            "serving.blockdiff_blocks_committed")
        self._bd_tokens = self.registry.counter(
            "serving.blockdiff_tokens_committed")
        self._bd_rows = self.registry.counter(
            "serving.blockdiff_rows_routed")
        self._bd_experts = self.registry.counter(
            "serving.blockdiff_experts_touched")
        # what the expert layers of the one-token programs did (the
        # programs' own counts, as the block-diffusion ones above):
        # rows routed and experts that owned a row, summed over the
        # expert layers, by kind of program ("decode" / "prefill")
        self._routed_rows = self.registry.counter(
            "serving.moe_rows_routed")
        self._routed_experts = self.registry.counter(
            "serving.moe_experts_touched")
        # the programs that reported, and a held share's rows by where
        # they went (held / absent / zero)
        self._routed_programs = self.registry.counter(
            "serving.moe_programs_counted")
        self._routed_split = self.registry.counter(
            "serving.moe_rows_by_share")
        # page groups by attention kind (a model with window and full
        # layers): per group, gauges of its pages (free, held by slots,
        # shared) and what slots gave back behind their window; None
        # for a pool of one group
        self._kv_group_pages = self.registry.gauge("serving.kv_group_pages")
        self._kv_groups: Optional[Dict] = None
        #: exact (tokens, seconds) aggregation per decoding-slot count —
        #: bounded by the slot count, and authoritative for
        #: ``decode_tokens_per_sec`` (the labeled counters mirror it for
        #: exporters)
        self._decode_agg: Dict[int, List[float]] = {}
        #: recent (n_decoding, dt) samples — a BOUNDED window view
        #: (bench.py reads the warm-up iterations from it)
        self._decode_recent = deque(maxlen=reservoir)
        self._t_first_submit: Optional[float] = None
        self._t_last_finish: Optional[float] = None

    # --- per-request ------------------------------------------------------

    def record_submit(self, rid: int) -> None:
        now_ = self.clock()
        self.submit_ts[rid] = now_
        if self._t_first_submit is None:
            self._t_first_submit = now_

    def record_first_token(self, rid: int) -> None:
        now_ = self.clock()
        t0 = self.submit_ts.get(rid)
        if t0 is not None:
            self._ttft.observe(now_ - t0)
            self.first_ts[rid] = now_

    def record_finish(self, rid: int, n_generated: int) -> None:
        now_ = self.clock()
        # evict the in-flight entries: finished-request state must not
        # accumulate in a long-lived engine
        t0 = self.submit_ts.pop(rid, None)
        if t0 is not None:
            self._latency.observe(now_ - t0)
        t_first = self.first_ts.pop(rid, None)
        if t_first is not None and n_generated > 1:
            # TPOT: mean seconds per generated token AFTER the first
            # (the streaming-cadence number; the first token is TTFT's)
            self._tpot.observe((now_ - t_first) / (n_generated - 1))
        self._finished.inc()
        self._tokens.inc(int(n_generated))
        self._t_last_finish = now_

    def record_rejected(self) -> None:
        """A submit shed by the bounded admission queue (the request
        never entered the engine — no submit timestamp to evict)."""
        self._rejected.inc()

    def record_timeout(self, rid: int) -> None:
        """A request's deadline expired before it finished."""
        self.submit_ts.pop(rid, None)
        self.first_ts.pop(rid, None)
        self._timed_out.inc()

    def record_cancelled(self, rid: int) -> None:
        """A request isolated after a step error (or cancelled by API)."""
        self.submit_ts.pop(rid, None)
        self.first_ts.pop(rid, None)
        self._cancelled.inc()

    def record_preemption(self, rid: int) -> None:
        """A decoding request evicted back to the queue (page-budget
        pressure). NOT terminal: its submit/first-token timestamps
        stay — TTFT already fired and latency measures to the real
        finish, across however many preemptions."""
        self._preempted.inc()

    def record_transfer(self, rid: int) -> None:
        """A request left this engine ALIVE (``transfer_out``: router
        handoff or rebalancing). Its in-flight timestamps are evicted —
        the window must not leak entries for requests that will finish
        on another replica's metrics window."""
        self.submit_ts.pop(rid, None)
        self.first_ts.pop(rid, None)
        self._transferred.inc()

    def record_prefix_lookup(self, hit_tokens: int,
                             total_tokens: int) -> None:
        """One prefix-cache lookup at admission: ``hit_tokens`` of the
        request's ``total_tokens`` context came off shared pages."""
        self._prefix_lookups.inc()
        self._prefix_lookup_toks.inc(int(total_tokens))
        if hit_tokens > 0:
            self._prefix_hits.inc()
            self._prefix_hit_toks.inc(int(hit_tokens))

    def record_prefix_eviction(self, evictions: int, examined: int,
                               queries: int) -> None:
        """The prefix cache's eviction work since the last flush (the
        engine publishes per-window DELTAS of the cache's cumulative
        odometers, as for the host tier's)."""
        self._prefix_evictions.inc(int(evictions))
        self._prefix_evict_examined.inc(int(examined))
        self._prefix_evictable_queries.inc(int(queries))

    def record_pages(self, free: int, shared: int,
                     fragmentation: float) -> None:
        """Per-iteration page-budget gauges (paged engine only)."""
        self._pages_free.set(int(free))
        self._pages_shared.set(int(shared))
        self._page_frag.set(float(fragmentation))

    def record_offload(self, offloaded: int, restored: int,
                       nbytes: int) -> None:
        """Host-tier page movement since the last flush (the engine
        publishes per-window DELTAS of the pool's cumulative
        odometers)."""
        self._pages_offloaded.inc(int(offloaded))
        self._pages_restored.inc(int(restored))
        self._offload_bytes.inc(int(nbytes))

    def record_swap_resume(self, dur_s: float,
                           tokens_avoided: int) -> None:
        """One preemption resume served by a host-page SWAP-IN:
        ``dur_s`` is the H2D copy + table restore wall;
        ``tokens_avoided`` the context tokens a re-prefill resume
        would have recomputed."""
        self._resume_swap.observe(float(dur_s))
        self._reprefill_toks_avoided.inc(int(tokens_avoided))

    def record_reprefill_resume(self, dur_s: float,
                                tokens: int) -> None:
        """One preemption resume served by context RE-PREFILL:
        ``dur_s`` spans first recompute chunk -> rejoining decode,
        ``tokens`` the context positions recomputed (net of shared
        prefix pages)."""
        self._resume_reprefill.observe(float(dur_s))
        self._reprefill_toks.inc(int(tokens))

    def record_spec_verify(self, proposed: int, accepted: int) -> None:
        """One slot's outcome in one speculative verify step:
        ``proposed`` drafts offered (the engine's fixed k), ``accepted``
        of them matched the target's own choices."""
        proposed, accepted = int(proposed), int(accepted)
        self._spec_proposed.inc(proposed)
        self._spec_accepted.inc(accepted)
        if proposed > 0:
            self._spec_rate.observe(accepted / proposed)

    def record_spec_disabled(self) -> None:
        """The acceptance EMA kicked one stream back to plain decode."""
        self._spec_disabled.inc()

    def record_spec_reenabled(self) -> None:
        """A demoted stream's cooldown re-probe won speculation back."""
        self._spec_reenabled.inc()

    def record_spec_tree(self, tree_width: int,
                         accepted_path_len: int) -> None:
        """One slot's outcome in one TREE verify (tree-speculation PR):
        the branch width the stream's adaptive tree ran at and the
        accepted root-path length (0 = only the bonus token emitted)."""
        self._spec_tree_width.observe(float(tree_width))
        self._spec_path_len.observe(float(accepted_path_len))

    def record_moe_route(self, expert_load, entropy: float,
                         concentration: float) -> None:
        """One decode iteration's MoE routing picture: ``expert_load``
        [E] routing-slot assignments per expert (summed over the
        model's MoE layers, live slots only), the mean router entropy
        (nats), and the engine's smoothed concentration (0 = uniform
        routing, 1 = everything on one expert). One gauge series per
        expert id — the label set is bounded by E."""
        load = np.asarray(expert_load, np.float64)
        if len(load) > self._moe_experts:
            self._moe_experts = len(load)
            self._moe_load.reserve_series(len(load) + 1)
        for e, v in enumerate(load):
            self._moe_load.set(float(v), expert=str(e))
        self._moe_entropy.set(float(entropy))
        self._moe_conc.set(float(concentration))

    def record_block_pass(self, kind: str, denoising: int, committing: int,
                          rows_routed: int, experts_touched: int, *,
                          overlapped: bool = False,
                          discarded: int = 0) -> None:
        """One block-diffusion pass: ``kind`` is the program that ran
        (``"denoise"`` or ``"commit"``), ``denoising`` / ``committing``
        the live slots that did either in it, ``rows_routed`` the rows
        its expert layers routed (live or not: every row of the pass
        is computed) and ``experts_touched`` the experts that owned at
        least one of them, summed over the expert layers.
        ``overlapped``: it was dispatched while the pass before it was
        still unread; ``discarded``: how many of its slots' rows were
        thrown away when it was read (the slot's request had ended or
        left since the launch)."""
        self._bd_passes.inc(kind=kind)
        self._bd_overlapped.inc(int(overlapped))
        self._bd_discarded.inc(int(discarded))
        self._bd_slot_passes.inc(int(denoising), kind="denoise")
        self._bd_slot_passes.inc(int(committing), kind="commit")
        self._bd_rows.inc(int(rows_routed), kind="pass")
        self._bd_experts.inc(int(experts_touched), kind="pass")

    def record_block_prefill(self, rows_routed: int,
                             experts_touched: int) -> None:
        """What the expert layers of one block-diffusion prefill
        program did (as ``record_block_pass`` counts a pass's)."""
        self._bd_rows.inc(int(rows_routed), kind="prefill")
        self._bd_experts.inc(int(experts_touched), kind="prefill")

    def record_routing(self, kind: str, rows_routed: int,
                       experts_touched: int, rows_held: int = 0,
                       rows_absent: int = 0, rows_zero: int = 0) -> None:
        """What the expert layers of one one-token program did
        (``kind``: ``"decode"`` or ``"prefill"``): the rows they routed
        and the experts that owned at least one, summed over the
        expert layers, as the program returned them. Layers that hold a
        share of their experts (``MoE(experts_held=)``) split the rows
        besides: to experts held here, to experts that are not here
        (their part is left out), to identity experts; a layer that
        holds all its experts reports none of the three."""
        self._routed_rows.inc(int(rows_routed), kind=kind)
        self._routed_experts.inc(int(experts_touched), kind=kind)
        self._routed_programs.inc(1, kind=kind)
        for where, n in (("held", rows_held), ("absent", rows_absent),
                         ("zero", rows_zero)):
            if n:
                self._routed_split.inc(int(n), kind=kind, where=where)

    def record_kv_groups(self, groups: Dict[str, Dict]) -> None:
        """The page groups' state at this flush (``ServingEngine
        ._kv_groups``): kept whole for ``summary()["kv_groups"]``, the
        page counts also as gauges by group and kind of count."""
        self._kv_groups = {name: dict(g) for name, g in groups.items()}
        for name, g in groups.items():
            for key, v in g.items():
                if key.startswith("pages_"):
                    self._kv_group_pages.set(float(v), group=name,
                                             count=key[len("pages_"):])

    def _routing(self) -> Optional[Dict]:
        rows = {k: int(self._routed_rows.value(kind=k))
                for k in ("decode", "prefill")}
        if not sum(rows.values()):
            return None
        touched = {k: int(self._routed_experts.value(kind=k))
                   for k in ("decode", "prefill")}
        out = {"rows_routed": rows["decode"],
               "experts_touched": touched["decode"],
               "prefill_rows_routed": rows["prefill"],
               "prefill_experts_touched": touched["prefill"],
               "decode_programs": int(
                   self._routed_programs.value(kind="decode"))}
        for where in ("held", "absent", "zero"):
            for kind, prefix in (("decode", ""), ("prefill", "prefill_")):
                out[f"{prefix}rows_{where}"] = int(
                    self._routed_split.value(kind=kind, where=where))
        return out

    def record_block_commit(self, n_tokens: int) -> None:
        """One whole block became visible to its client: ``n_tokens``
        new tokens on ``Request.generated`` (under the block length
        where the prompt opened the block or the budget closed it)."""
        self._bd_blocks.inc()
        self._bd_tokens.inc(int(n_tokens))

    # --- per-iteration ----------------------------------------------------

    def record_prefill_chunk(self) -> None:
        self._chunks.inc()

    def record_iteration(self, queue_depth: int, occupied: int,
                         num_slots: int) -> None:
        self._qdepth.observe(int(queue_depth))
        self._occ.observe(occupied / num_slots)

    def record_decode(self, n_decoding: int, dt: float,
                      n_tokens: Optional[int] = None) -> None:
        """One decode iteration over ``n_decoding`` slots taking ``dt``
        seconds. ``n_tokens`` is the tokens actually emitted — it
        defaults to one per decoding slot (the plain step) and exceeds
        it under speculation (a verify step emits ``1 + accepted`` per
        slot), so ``decode_tokens_per_sec`` prices speculation's win
        without any caller-side special-casing."""
        n, dt = int(n_decoding), float(dt)
        toks = n if n_tokens is None else int(n_tokens)
        agg = self._decode_agg.setdefault(n, [0.0, 0.0])
        agg[0] += toks
        agg[1] += dt
        self._decode_toks.inc(toks, slots=n)
        self._decode_secs.inc(dt, slots=n)
        self._decode_recent.append((n, dt))

    # --- properties kept for existing callers -----------------------------

    @property
    def requests_finished(self) -> int:
        return int(self._finished.value())

    @property
    def tokens_generated(self) -> int:
        return int(self._tokens.value())

    @property
    def prefill_chunks(self) -> int:
        return int(self._chunks.value())

    @property
    def requests_rejected(self) -> int:
        return int(self._rejected.value())

    @property
    def requests_timed_out(self) -> int:
        return int(self._timed_out.value())

    @property
    def requests_cancelled(self) -> int:
        return int(self._cancelled.value())

    @property
    def requests_preempted(self) -> int:
        return int(self._preempted.value())

    @property
    def requests_transferred(self) -> int:
        return int(self._transferred.value())

    @property
    def pages_offloaded(self) -> int:
        return int(self._pages_offloaded.value())

    @property
    def pages_restored(self) -> int:
        return int(self._pages_restored.value())

    def resume_swap_samples(self) -> List[float]:
        """Swap-in resume durations (histogram reservoir) — the
        offload bench reduces these to p50/p99."""
        return self._resume_swap.samples()

    def resume_reprefill_samples(self) -> List[float]:
        return self._resume_reprefill.samples()

    @property
    def spec_proposed(self) -> int:
        return int(self._spec_proposed.value())

    @property
    def spec_accepted(self) -> int:
        return int(self._spec_accepted.value())

    @property
    def acceptance_rate(self) -> Optional[float]:
        """Fraction of proposed draft tokens the target accepted (None
        before any speculative verify ran)."""
        prop = self._spec_proposed.value()
        if prop <= 0:
            return None
        return self._spec_accepted.value() / prop

    @property
    def moe_expert_load(self) -> Optional[List[float]]:
        """Last-iteration per-expert routing load (None on MoE-free
        engines or before the first MoE decode step)."""
        if not self._moe_experts:
            return None
        return [self._moe_load.value(expert=str(e)) or 0.0
                for e in range(self._moe_experts)]

    @property
    def prefix_hit_rate(self) -> Optional[float]:
        """Fraction of looked-up context tokens served off shared
        pages (None before any lookup)."""
        total = self._prefix_lookup_toks.value()
        if total <= 0:
            return None
        return self._prefix_hit_toks.value() / total

    @property
    def decode_samples(self) -> List:
        """Recent ``(n_decoding, dt)`` pairs (bounded window)."""
        return list(self._decode_recent)

    # --- reductions -------------------------------------------------------

    def ttfts(self) -> List[float]:
        """TTFT samples (the histogram reservoir — exact until
        ``reservoir`` requests, a uniform sample after)."""
        return self._ttft.samples()

    def latencies(self) -> List[float]:
        return self._latency.samples()

    def spec_accept_rates(self) -> List[float]:
        """Per-slot per-iteration draft acceptance-rate samples (the
        histogram reservoir) — bench reduces these to percentiles."""
        return self._spec_rate.samples()

    def decode_tokens_per_sec(self,
                              min_occupancy: int = 0) -> Optional[float]:
        """Marginal decode throughput over iterations with at least
        ``min_occupancy`` decoding slots — ``min_occupancy = S`` is the
        steady-state full-batch rate the acceptance criterion compares
        against a raw batched decode loop. Exact over ALL iterations
        (streaming per-slot-count aggregation, not the sample window).
        """
        toks = sum(a[0] for n, a in self._decode_agg.items()
                   if n >= min_occupancy)
        secs = sum(a[1] for n, a in self._decode_agg.items()
                   if n >= min_occupancy)
        return toks / secs if secs > 0 else None

    def _block_diffusion(self) -> Optional[Dict]:
        passes = {k: int(self._bd_passes.value(kind=k))
                  for k in ("denoise", "commit")}
        if not sum(passes.values()):
            return None
        return {"passes": passes,
                "passes_overlapped": int(self._bd_overlapped.value()),
                "slot_passes_discarded": int(self._bd_discarded.value()),
                "slot_passes": {
                    k: int(self._bd_slot_passes.value(kind=k))
                    for k in ("denoise", "commit")},
                "blocks_committed": int(self._bd_blocks.value()),
                "tokens_committed": int(self._bd_tokens.value()),
                "rows_routed": int(self._bd_rows.value(kind="pass")),
                "experts_touched": int(
                    self._bd_experts.value(kind="pass")),
                "prefill_rows_routed": int(
                    self._bd_rows.value(kind="prefill")),
                "prefill_experts_touched": int(
                    self._bd_experts.value(kind="prefill"))}

    @staticmethod
    def _pcts(hist) -> Optional[Dict[str, float]]:
        stats = hist.stats()
        if stats is None:
            return None
        return {"p50": stats["p50"], "p99": stats["p99"]}

    def summary(self) -> Dict:
        """The metrics glossary of docs/observability.md, as one dict —
        keys unchanged across the registry migration."""
        elapsed = (self._t_last_finish - self._t_first_submit
                   if self._t_first_submit is not None
                   and self._t_last_finish is not None else 0.0)
        qd = self._qdepth.stats()
        occ = self._occ.stats()
        tokens = self.tokens_generated
        pages_free = self._pages_free.value()
        return {
            "requests_finished": self.requests_finished,
            # degradation tally (keys ADDED by the resilience PR; all
            # pre-existing keys unchanged)
            "requests_rejected": self.requests_rejected,
            "requests_timed_out": self.requests_timed_out,
            "requests_cancelled": self.requests_cancelled,
            # paged-KV tally (keys ADDED by the paged-cache PR): page
            # budget at the last iteration, prefix-cache hit rate,
            # preemption count; "pages" is None before the first
            # recorded iteration
            "requests_preempted": self.requests_preempted,
            # serving-router tally (key ADDED by the router PR):
            # live departures to another replica
            "requests_transferred": self.requests_transferred,
            "pages": (None if pages_free is None else {
                "free": int(pages_free),
                "shared": int(self._pages_shared.value() or 0),
                "fragmentation": self._page_frag.value()}),
            # host KV offload tier (keys ADDED by the offload PR):
            # page-swap traffic and the per-path resume latencies —
            # the swap-vs-re-prefill crossover, measured
            "offload": {
                "pages_offloaded": self.pages_offloaded,
                "pages_restored": self.pages_restored,
                "offload_bytes": int(self._offload_bytes.value()),
                "reprefill_tokens": int(self._reprefill_toks.value()),
                "reprefill_tokens_avoided": int(
                    self._reprefill_toks_avoided.value()),
                "resume_swap_s": self._pcts(self._resume_swap),
                "resume_reprefill_s": self._pcts(
                    self._resume_reprefill)},
            "prefix_cache": {
                "lookups": int(self._prefix_lookups.value()),
                "hits": int(self._prefix_hits.value()),
                "hit_rate": self.prefix_hit_rate,
                # keys ADDED with the eviction index: examined per
                # eviction stays in single digits at any trie size
                "evictions": int(self._prefix_evictions.value()),
                "evict_examined": int(
                    self._prefix_evict_examined.value()),
                "evictable_queries": int(
                    self._prefix_evictable_queries.value())},
            # speculative decoding (keys ADDED by the spec-decode PR):
            # aggregate acceptance plus the per-slot-per-iteration
            # acceptance-rate percentiles bench records
            # MoE serving (keys ADDED by the MoE-serving PR): the
            # last iteration's expert-load picture; None on MoE-free
            # engines
            "moe": (None if not self._moe_experts else {
                "expert_load": self.moe_expert_load,
                "router_entropy": self._moe_entropy.value(),
                "concentration": self._moe_conc.value()}),
            # block diffusion (keys ADDED by the block-diffusion PR):
            # None until a pass ran
            "block_diffusion": self._block_diffusion(),
            # keys ADDED with the page groups by attention kind: what
            # the one-token programs' expert layers routed (None until
            # a program reported), and the groups' pages (None for a
            # pool of one group)
            "routing": self._routing(),
            "kv_groups": self._kv_groups,
            "acceptance_rate": self.acceptance_rate,
            "speculation": {
                "proposed": self.spec_proposed,
                "accepted": self.spec_accepted,
                "disabled_streams": int(self._spec_disabled.value()),
                # key ADDED by the loadgen/timeseries PR: re-probe wins
                "reenabled_streams": int(self._spec_reenabled.value()),
                "accept_rate": self._pcts(self._spec_rate),
                # tree keys (ADDED by the tree-speculation PR): None
                # until a tree verify ran
                "tree_width": self._pcts(self._spec_tree_width),
                "accepted_path_len": self._pcts(self._spec_path_len)},
            "tokens_generated": tokens,
            # request-level throughput: all generated tokens over the
            # first-submit -> last-finish span (includes queueing +
            # prefill)
            "tokens_per_sec": (tokens / elapsed if elapsed > 0 else None),
            # marginal decode rate, all iterations / full batch only
            "decode_tokens_per_sec": self.decode_tokens_per_sec(),
            "ttft_s": self._pcts(self._ttft),
            # key ADDED by the tracing/SLO PR (pre-existing keys
            # unchanged): per-token decode cadence of finished requests
            "tpot_s": self._pcts(self._tpot),
            "latency_s": self._pcts(self._latency),
            "queue_depth": ({"mean": qd["mean"], "max": qd["max"]}
                            if qd else None),
            "slot_occupancy": ({"mean": occ["mean"], "max": occ["max"]}
                               if occ else None),
            "prefill_chunks": self.prefill_chunks,
            "phases": self.timer.summary(),
        }
