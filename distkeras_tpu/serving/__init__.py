"""Continuous-batching serving engine: iteration-level scheduling over
``generate()``'s prefill/decode machinery, on a paged KV cache.

The single-call ``generate()`` path decodes one fixed batch to
completion: a straggler request holds every batch row until
``max_new_tokens``, and new arrivals wait for the whole batch to drain.
This package is the Orca/vLLM-style fix — the missing layer between the
per-step decode kernels and an actual serving workload:

    kv_pool.py     ``PagedKVPool`` — fixed pool of per-layer KV pages,
                   per-slot page tables, refcounted on-demand
                   allocation, and (``host_pages=``) the HOST offload
                   tier: async D2H/H2D page copies that turn
                   preemption into a swap and multiply prefix-cache
                   capacity — plus ``PrefixCache`` (hash-consed
                   shared prompt prefixes, copy-on-write partial
                   pages, spill-to-host eviction)
    scheduler.py   admission queue + per-request state machine
                   (queued -> prefilling -> decoding -> finished) with
                   slot allocation/release; ``PriorityScheduler`` adds
                   priority classes and preemption back to the queue
    engine.py      the slot-based decode loop: ONE compiled
                   ``decode_step_slots_paged`` over all slots per
                   iteration (static shapes, the page table is a
                   traced argument, jit compiled once; on TPU the
                   readout is the ``ops.paged_attention`` page-table
                   Pallas kernel — ``decode_kernel=``), chunked
                   prefill interleaved between decode iterations with
                   shared prefixes skipped, page-budget admission and
                   preemption/resume (a page SWAP through the host
                   tier when ``host_kv_pages=`` is set, a recompute
                   prefill otherwise), per-slot sampling state; MoE
                   models decode through the drop-free dispatched
                   path (optionally shard_map expert-parallel over
                   ``ep_mesh``) with expert-load telemetry and a
                   routing-concentration admission cost
    speculation.py ``DraftSource`` draft proposers for speculative
                   decoding — ``NgramDraft`` (prompt-lookup
                   self-drafting, zero extra weights) and
                   ``DraftModel`` (a small LM with its own paged KV) —
                   verified k-at-a-time by one batched target pass
                   (``models.decoding.verify_step_slots_paged``),
                   linearly or as per-slot token TREES
                   (``propose_tree`` + the ancestor-mask window,
                   ``ServingEngine(spec_tree=)``)
    metrics.py     TTFT, TPOT, request latency, queue depth, slot
                   occupancy, tokens/s, page-budget gauges and
                   prefix-cache hit rates — the numbers ``bench.py
                   --model serving`` records; request-level timelines,
                   the flight-recorder ring and declarative SLOs live
                   in ``distkeras_tpu.obs`` (tracing/recorder/slo) and
                   are wired through the engine
    loadgen.py     production-shaped traffic: seeded phased arrivals
                   (diurnal ramps, bursts, flash crowds), heavy-tail
                   lengths, template/tenant mixes — synthesized into a
                   replayable JSONL ``Trace`` and driven open-loop
                   through an engine or router fleet on the iteration
                   clock (deterministic; ``obs.report`` turns the
                   result into the per-phase scenario SLO report)
    router/        the horizontal tier: N engine replicas behind a
                   prefix-affinity/least-loaded ``Router`` with
                   lifecycle-managed ``EngineReplica``s, disaggregated
                   prefill/decode pools (handoff = the engine's
                   ``transfer_out``/``transfer_in`` re-entry path),
                   replica-death mass failover, the elastic
                   ``add_replica``/``remove_replica`` surface, an
                   ``SLOBurnController`` drain loop and the
                   ``AutoscaleController`` closed-loop fleet sizer

See ``docs/serving.md`` for the architecture, the paged-KV design,
the scheduling policy and the router tier.
"""

from distkeras_tpu.serving.engine import (DegradedRequest,  # noqa: F401
                                          ServingEngine)
from distkeras_tpu.serving.loadgen import (ChaosSpec,  # noqa: F401
                                           IterationClock,
                                           PhaseSpec, PhaseResult,
                                           ReplayResult, TenantSpec,
                                           Trace, TraceRequest,
                                           WorkloadSpec,
                                           diurnal_burst_scenario,
                                           flash_crowd_chaos_scenario,
                                           replay, synthesize)
from distkeras_tpu.serving.kv_pool import (PagedKVPool,  # noqa: F401
                                           PrefixCache)
from distkeras_tpu.serving.metrics import ServingMetrics  # noqa: F401
from distkeras_tpu.serving.scheduler import (AdmissionRejected,  # noqa: F401
                                             FIFOScheduler,
                                             PriorityScheduler, Request,
                                             RequestState, TERMINAL_STATES)
from distkeras_tpu.serving.speculation import (DraftModel,  # noqa: F401
                                               DraftSource, NgramDraft)
from distkeras_tpu.serving.router import (AutoscaleController,  # noqa: F401
                                          ControllerChain,
                                          EngineReplica,
                                          LeastLoaded, PlacementPolicy,
                                          PrefixAffinity, ReplicaDead,
                                          ReplicaState,
                                          ReplicaUnavailable, Router,
                                          RouterClient,
                                          SLOBurnController)
