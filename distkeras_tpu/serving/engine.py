"""Slot-based continuous-batching engine over the LM decode path.

The Orca/vLLM iteration-level serving pattern on this repo's
prefill/decode machinery:

  * A paged KV cache (``kv_pool.PagedKVPool``) stays resident on
    device: fixed-size pages allocated on demand per request, per-slot
    page tables driving one compiled
    ``decode_step_slots_paged`` over ALL slots — shapes are
    static (the table is a traced argument), the jit compiles once per
    engine per sampler variant (argmax-only for all-greedy batches,
    the full per-slot sampler for mixed ones), and requests at
    different sequence positions coexist because ``t`` is a per-slot
    vector. Admission is COST-AWARE (``PriorityScheduler``): a request
    admits when its prompt's pages fit the free-page budget (priority
    classes first, FCFS within), and a decode step that outgrows the
    pool preempts the youngest lowest-priority stream back to the
    queue — its context re-prefills on re-admission via the resumable
    ``prefill_chunk_step``, token-identically. Identical prompt
    prefixes hash-cons onto shared read-only pages
    (``kv_pool.PrefixCache``): prefill skips the shared positions, a
    partially matched page is served copy-on-write.
  * Requests admit into free slots; a new request's prompt prefills
    into a batch-1 staging cache — chunked (``prefill_chunk``), one
    chunk per engine iteration, interleaved between decode steps so a
    long prompt never stalls in-flight streams — then the filled pages
    INSERT into the request's pool pages (only the pages the prompt
    actually fills, minus the shared-prefix pages) and it joins the
    decode batch.
  * Per-slot sampling state (temperature / top_k / top_p / stop_token
    vectors through ``_sample_vec``, per-slot PRNG keys) lets greedy
    and sampled requests with different stop tokens share one batch.
  * MoE models decode DISPATCHED (``moe_decode="dispatched"``, the
    default): drop-free by construction (``MoE.decode_apply``), so a
    stream's tokens are independent of its batch neighbours; optional
    shard_map expert parallelism (``ep_mesh``) shards expert weights
    over the mesh; expert-load/entropy telemetry and a routing-
    concentration admission cost ride along (docs/serving.md §MoE
    serving).
  * ``ServingMetrics`` records TTFT, TPOT, request latency, queue
    depth, slot occupancy and the per-iteration decode rate; the
    request-level layer rides along — per-request timelines
    (``obs.tracing``, Chrome-trace exportable), a flight-recorder ring
    of recent iterations (``obs.recorder``, auto-dumped on failures)
    and declarative SLOs (``obs.slo``) reported by ``health()``.

Greedy outputs are token-identical per request to a standalone
``generate()`` call on the same prompt (the oracle contract:
``tests/test_serving.py``): prefill runs the very same ``prefill`` /
``prefill_chunk_step`` programs at batch 1, and the per-slot decode
step is the same storage-dtype einsum attention with a per-slot mask.

Zero-bubble loop (this PR, docs/serving.md §Zero-bubble loop): the
decode path no longer blocks on next-token ids every iteration.
``overlap=True`` (the default) pipelines dispatch — iteration i+1's
step is launched with iteration i's token ids fed back DEVICE-side
(JAX async dispatch keeps the device busy) while the host consumes a
LAGGED fetch of iteration i's tokens; ``fuse_steps=K`` additionally
compiles K consecutive decode iterations as one ``lax.scan`` program
(``models.decoding.decode_fused_slots`` — in-program per-slot stop
masks, engaged only when the scheduler is quiescent), eliminating
per-iteration dispatch entirely in steady state. Host-side per-request
bookkeeping (tracer ticks, metrics, recorder-ring composition) is
batched onto a deferred per-window cadence. Outputs stay
token-identical (byte-identical for sampled streams) to the
synchronous loop (``overlap=False``) — the oracle suite pins it.

One pool on the device: every program that takes a KV cache and
returns its successor DONATES it (``_jit_serving``; the pool's own
insert/restore programs in ``kv_pool`` and the draft model's in
``speculation`` too), so a step writes the page pool in place and the
value passed in is deleted — every call site rebinds ``pool.cache`` /
``_staging`` from the result before anything reads it. ``decode_logits()``
alone keeps its pool. ``health()["programs"]`` says which
(``kv_cache=donated`` / ``kv_cache=kept``).

Remaining deliberate scope: weight trees support
``weights_dtype="auto"``-style pre-casting but not int8; prompts longer
than ``max_len - max_new_tokens`` are rejected at submit.
"""

from __future__ import annotations

import itertools
import re
import weakref
import zlib
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import obs
from distkeras_tpu.compat import record_paths, shard_map
from distkeras_tpu.obs.recorder import resolve_recorder
from distkeras_tpu.obs.slo import SLOEngine
from distkeras_tpu.obs.timeseries import TimeSeries
from distkeras_tpu.obs.tracing import resolve_tracer
from distkeras_tpu.models.core import Model, Sequential
from distkeras_tpu.models.decoding import (_attn_compute_dtype,
                                           _decode_block_of,
                                           block_denoise_slots_paged,
                                           block_len_of,
                                           block_pass_slots_paged,
                                           _resolve_head_dims,
                                           _sample_vec, _serving_params,
                                           commit_tree_path,
                                           decode_fused_slots,
                                           decode_step_slots_paged,
                                           prefill, prefill_chunk_step,
                                           routing_counts, state_layers,
                                           tree_walk,
                                           verify_step_slots_paged)
from distkeras_tpu.models.moe import MoE
from distkeras_tpu.resilience import faults
from distkeras_tpu.serving.kv_pool import PagedKVPool, PrefixCache
from distkeras_tpu.serving.speculation import (DraftSource,
                                               tree_ancestors)
from distkeras_tpu.serving.metrics import ServingMetrics
from distkeras_tpu.serving.scheduler import (AdmissionRejected,
                                             PriorityScheduler, Request,
                                             RequestState,
                                             TERMINAL_STATES)


class DegradedRequest(RuntimeError):
    """``run()`` drained a request that did NOT finish normally
    (TIMED_OUT / CANCELLED). Raised by default so a degraded result can
    never masquerade as a complete one in ``run()``'s plain
    ``{rid: tokens}`` return; the terminal ``Request`` (state, partial
    tokens, ``error`` cause) rides on ``.request``."""

    def __init__(self, request: Request):
        cause = (f": {request.error!r}" if request.error is not None
                 else "")
        super().__init__(
            f"request {request.rid} ended {request.state.value}{cause} "
            "— drive with step() to observe terminal states, or "
            "run(on_degraded='return') to accept partial tokens")
        self.request = request


def _snap(a: np.ndarray):
    """Device snapshot of a host mirror for an ASYNC launch. The CPU
    client zero-copy aliases suitably aligned numpy buffers into device
    arguments (the round-6 checkpoint-aliasing finding, reproduced for
    jit call arguments: ~half of fresh small-int32 allocations alias),
    and the zero-bubble loop mutates mirrors while the launched program
    is still executing — so the program must read a private copy. The
    copy is a few dozen bytes per mirror per launch; the temp is owned
    by the runtime from here and never mutated."""
    return jnp.asarray(a.copy())


class _PendingStep:
    """One launched-but-unfetched decode step (the pipelined-dispatch
    in-flight record): the device futures its program returned plus the
    host snapshot needed to consume them later. ``nxt`` is the [S]
    token array of a single step or the [S, K] block of a fused
    window; ``last`` is the [S] device-side feedback array the NEXT
    launch chains from; ``slots`` pins (slot, rid) pairs at launch so a
    slot recycled in the meantime discards its stale tokens."""

    __slots__ = ("nxt", "last", "keys", "moe", "slots", "covers",
                 "count", "launch_t", "prefills")

    def __init__(self, nxt, last, keys, moe, slots, count, launch_t,
                 prefills=()):
        #: routing counts of the prefill programs dispatched before
        #: this step (device arrays, read with the step's own)
        self.prefills = prefills
        self.nxt = nxt
        self.last = last
        self.keys = keys
        self.moe = moe
        self.slots = slots                   # tuple of (slot, rid)
        self.covers = {s: r for s, r in slots}
        self.count = count                   # tokens per covered slot
        self.launch_t = launch_t


class _PendingPass:
    """One launched-but-unread block-diffusion pass: the block state
    its program returned (``blk``: tokens and the pass that fixed each,
    arrays of the device; None for a head-less commit pass), its and
    the earlier prefills' routing counts, and what the host knew at
    launch: the program that ran, the ``(slot, rid)`` pairs that rode
    it (a slot recycled in the meantime discards its rows), the live
    slots that denoised and committed, the slots whose block this pass
    makes whole (``shown``), and whether the pass before it was still
    unread when this one was dispatched."""

    __slots__ = ("kind", "blk", "routed", "prefills", "slots", "shown",
                 "denoising", "committing", "overlapped", "launch_t")

    def __init__(self, kind, blk, routed, prefills, slots, shown,
                 denoising, committing, overlapped, launch_t):
        self.kind = kind
        self.blk = blk
        self.routed = routed
        self.prefills = prefills
        self.slots = slots                   # tuple of (slot, rid)
        self.shown = shown                   # tuple of (slot, rid)
        self.denoising = denoising
        self.committing = committing
        self.overlapped = overlapped
        self.launch_t = launch_t


class ServingEngine:
    """Continuous-batching serving over one ``zoo.transformer_lm``-shaped
    model. ``submit()`` enqueues requests; ``step()`` advances the world
    one scheduler iteration; ``run()`` drains to completion (the
    synchronous driver — an async transport wraps these two calls).

    ``max_len`` is the per-request cache capacity: every request needs
    ``len(prompt) + max_new_tokens <= max_len``.

    Paged-cache knobs:

    * ``page_len`` — positions per KV page. Smaller pages waste less
      tail (fragmentation is < ``page_len`` positions per request) and
      share prefixes at finer grain; larger pages mean fewer
      table entries and scatter/gather indices. 16 is the vLLM-era
      sweet spot for the einsum path (docs/serving.md §Paged KV).
    * ``num_pages`` — the HBM budget, in pages. Default
      ``num_slots * ceil(max_len / page_len)`` (every slot's worst
      case at once); size it DOWN to actual traffic and
      let cost-aware admission + preemption absorb the tail. A model
      whose layers are of several attention kinds (window and full
      mixed) gets a page group per kind (docs/serving.md §Page groups):
      this count is then the full layers' group, and a tuple ``(full,
      window, ...)`` also states the window groups' (default: two
      rings a slot less a page, a ring being one window's pages).
    * ``host_kv_pages`` — the HOST page pool (offload tier, docs/
      serving.md §Host KV offload). When > 0, preemption victims swap
      their pages out D2H (resume = H2D copy + table restore, token-
      identical, no re-prefill — an order of magnitude cheaper, which
      is what makes sizing ``num_pages`` aggressively down safe) and
      cold prefix-cache chains spill to host before LRU-evicting
      outright (effective prefix capacity = device + host pages).
      0 (default) disables; size it to spare host RAM — pages cost
      ``2 * Hkv * page_len * Dh * dtype_bytes`` per layer.
    * ``decode_kernel`` — the paged decode readout: ``"auto"``
      (default) runs the Pallas paged-attention kernel on TPU (K/V
      gathered HBM->VMEM through the page table IN-KERNEL — no
      materialized logical view, docs/serving.md §Paged-attention
      kernel) and the ``_gather_pages`` reference elsewhere;
      ``"paged"`` forces the kernel (interpreter mode off-TPU — the
      oracle hook tier-1 uses); ``"off"`` forces the gather path
      (the A/B baseline). Pools whose ``page_len`` breaks the
      kernel's tiling rule (% 8 float, % 32 int8) keep the gather
      path; ``health()["programs"]`` says which one each compiled
      program took.
    * ``prefix_cache`` — hash-cons identical prompt prefixes onto
      shared pages (on by default; sharing is exact up to
      chunked-prefill fp reassociation — see ``kv_pool.PrefixCache``).
    * ``prefix_granularity`` — round PARTIAL-page (copy-on-write)
      matches down to a multiple of this many tokens (full-page
      matches are unaffected). The default 1 shares maximally, but
      every distinct matched length makes the residual prefill chunk a
      novel ragged shape — an inline XLA compile the first time it
      appears (the same hazard as novel prompt lengths,
      docs/serving.md follow-ups). Set to ``page_len`` to keep
      sharing page-granular and the program set bounded.

    Speculative-decoding knobs (docs/serving.md §Speculative decoding):

    * ``draft`` — a ``DraftSource`` (``NgramDraft()`` for zero-weight
      prompt-lookup self-drafting, ``DraftModel(small_lm)`` for a
      learned drafter). None (default) disables speculation.
    * ``spec_k`` — drafts proposed per slot per iteration (STATIC: one
      compiled ``[S, k+1]`` verify program per sampler variant). Each
      verify emits 1..k+1 tokens per slot; the sweet spot tracks the
      workload's acceptance rate (≈2-4 for mixed traffic, higher for
      templated/repetitive streams).
    * ``spec_disable_below`` / ``spec_warmup`` — per-request acceptance
      EMA floor: after ``spec_warmup`` verifies, a stream whose EMA
      acceptance is below the floor stops speculating (the verify
      window costs a (k+1)-wide forward; on a never-accepting stream
      that is pure overhead). Sticky per request.
    * ``spec_tree`` / ``spec_width`` — TREE speculation (docs/
      serving.md §Tree speculation): drafts arrive as a per-slot token
      TREE (``DraftSource.propose_tree`` — branching n-gram
      continuations or a beam-style draft-model tree) and ONE
      tree-masked verify window scores every branch; the in-program
      walk accepts the longest root path (exact multi-draft rejection
      sampling for sampled streams — byte-identical to plain decode)
      and the cache commits only the accepted path. The window is
      ``1 + spec_k * spec_width`` columns (STATIC); an adaptive
      per-stream controller sizes each request's actual depth/width
      inside it from the acceptance EMA (hot streams widen toward the
      caps, cold streams narrow toward a plain chain and ultimately
      the existing EMA kill switch). ``spec_tree=False`` (default)
      keeps the landed linear verify path byte-for-byte; with
      ``spec_width=1`` the tree path IS the linear chain (oracle
      tests pin the identity).

    Zero-bubble knobs (docs/serving.md §Zero-bubble loop):

    * ``overlap`` — pipelined dispatch (default True): each decode
      step's token ids feed back into the NEXT step device-side and
      the host consumes a lagged fetch one iteration behind, so the
      device never waits on per-iteration Python. Host-visible state
      (``req.generated``, metrics, timelines) lags by at most one
      iteration while a stream decodes; outputs are token-identical
      (byte-identical sampled) to ``overlap=False``, the synchronous
      loop kept as the A/B baseline (``bench.py --model
      serving_overlap`` prices the gap). Host bookkeeping batches onto
      a deferred per-``_HOST_WINDOW`` cadence (counts stay exact).
    * ``fuse_steps`` — fused multi-step decode: when >= 2, a QUIESCENT
      iteration (no queued or prefilling requests, no speculating
      slot, no slot within ``fuse_steps`` of its budget, no deadline
      in the batch) runs ``fuse_steps`` plain decode iterations as ONE
      compiled ``lax.scan`` program with in-program per-slot stop
      masks — zero per-iteration dispatch in steady state. Pages for
      the whole window are pre-grown; if that growth preempts a
      stream, the iteration falls back to single-step and fused decode
      rejoins when quiescence returns. 0 (default) disables. Pick K so
      a window is a few ms of device time (4-8 typical): larger K
      amortizes more dispatch but coarsens deadline/SLO checks and
      admission latency to K-step granularity.

    MoE knobs (docs/serving.md §MoE serving):

    * ``moe_decode`` — how the decode/verify steps run MoE MLPs:
      ``"dispatched"`` (default) takes the decode-specialized
      dispatched path (``MoE.decode_apply`` — capacity = the
      slot-token count, DROP-FREE by construction, fused Pallas kernel
      on TPU, tokens path elsewhere), regardless of each layer's
      configured training ``dispatch``; ``"dense"`` opts back into the
      layers' own ``apply`` (the dense-routing baseline the
      ``serving_moe`` bench prices the dispatch against). Either way
      greedy outputs are token-identical to the dense-routing
      ``generate()`` oracle — the drop-free capacity is what makes a
      slot's tokens independent of its batch neighbours.
    * ``ep_mesh`` — expert-parallel decode: REQUIRED when the model's
      MoE layers were built with ``expert_axis_name`` (they cannot run
      outside a shard_map). Every compiled serving program is wrapped
      in ``shard_map`` over this mesh with the stacked expert weights
      sharded on the expert axis (everything else replicated), so
      per-chip expert-weight traffic shrinks with mesh size; the MoE
      combine psums over the axis inside the program.

    Block diffusion (docs/serving.md §Block diffusion): a model built
    block-causal (``zoo.transformer_lm(block_len=B)``) is decoded a
    block of ``B`` tokens at a time. The whole blocks of the prompt are
    prefilled; each pass runs a stream's current block (mask tokens
    where nothing is fixed) against its cached blocks and fixes the
    most confident masked positions; once no mask is left the block is
    appended to ``Request.generated`` (whole, cut at the budget or the
    stop token) and one more pass, the commit pass, writes the K/V
    that later blocks read. Slots sit at different passes of
    different blocks in one batched program.

    * ``denoising_steps`` — passes that fix tokens, per block
      (default: the block length, one token a pass); the ``B`` tokens
      are spread evenly over them, earlier passes taking the remainder
      (``low_confidence_static`` remasking).
    * ``mask_token`` — the id a not-yet-fixed position is fed as;
      required for a block-causal model.

    Greedy only, no speculation, fused windows or host offload; the
    prefix cache shares at block boundaries.

    State-space layers (docs/serving.md §State groups): a model whose
    blocks include ``models.mamba.Mamba2Mixer`` layers keeps, for each,
    a state and a convolution window a slot beside the K/V pages of its
    attention layers (``PagedKVPool``'s state group). A slot's rows are
    zeroed when a request takes it (``serving.ssm.reset``), written
    whole by the last chunk of its prefill, and updated in place by
    each decode step, which leaves the rows of slots still prefilling
    as they were; a preempted request re-prefills prompt + generated.
    Refused by name, there being no snapshot of a state yet: the
    prefix cache (``prefix_cache=False`` is required), ``draft``,
    ``fuse_steps``, ``host_kv_pages``, block diffusion, ``ep_mesh``
    and ``weight_quant``. ``ssm_counters`` counts what the state
    group did (slot updates by decode, positions scanned by prefill,
    resets).

    A dispatched-MoE engine also feeds MoE telemetry: per-expert load
    and router-entropy gauges (``ServingMetrics.record_moe_route``), a
    ``moe_route`` tracer event on the decode cadence, and a smoothed
    routing-concentration estimate the paged admission consults
    (concentrated routing makes the marginal stream more expensive, so
    admission demands spare-page headroom proportional to it —
    ``_moe_admit_extra``).
    """

    @obs.span("serving.init")
    def __init__(self, model: Model, *, num_slots: int = 4,
                 max_len: int = 256,
                 prefill_chunk: Optional[int] = None,
                 cache_dtype=None, weights_dtype="auto",
                 weight_quant: Optional[str] = None,
                 hbm_budget: Optional[int] = None,
                 metrics: Optional[ServingMetrics] = None,
                 max_queue: Optional[int] = None,
                 tracer=None, slo=None,
                 page_len: int = 16,
                 num_pages=None,
                 host_kv_pages: int = 0,
                 decode_kernel: str = "auto",
                 prefix_cache: bool = True,
                 prefix_granularity: int = 1,
                 draft: Optional[DraftSource] = None, spec_k: int = 4,
                 spec_disable_below: float = 0.1,
                 spec_warmup: int = 8,
                 spec_reprobe: Optional[int] = None,
                 spec_tree: bool = False, spec_width: int = 1,
                 timeseries=None,
                 moe_decode: str = "dispatched",
                 ep_mesh=None,
                 overlap: bool = True, fuse_steps: int = 0,
                 fused_sampling: bool = False,
                 engine_id: Optional[str] = None,
                 denoising_steps: Optional[int] = None,
                 mask_token: Optional[int] = None):
        module = model.module
        if not isinstance(module, Sequential):
            raise TypeError("ServingEngine expects a Sequential LM "
                            f"(got {type(module).__name__})")
        #: the state-space layers (class doc: the state group)
        self._ssm_layers = state_layers(module)
        if self._ssm_layers:
            refused = [name for name, on in (
                ("prefix_cache", prefix_cache),
                ("draft", draft is not None), ("fuse_steps", fuse_steps),
                ("host_kv_pages", host_kv_pages),
                ("block diffusion", block_len_of(module) is not None),
                ("ep_mesh", ep_mesh is not None),
                ("weight_quant", weight_quant is not None)) if on]
            if refused:
                raise ValueError(
                    "a model with state-space layers is served without "
                    f"{', '.join(refused)}: a slot's state has no snapshot "
                    "to share, roll back or swap yet")
        self.ssm_counters = dict.fromkeys(
            ("ssm_state_slot_steps", "ssm_prefill_positions",
             "ssm_state_resets"), 0)
        self.model = model
        self.module = module
        _resolve_head_dims(module, model.params)
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.prefill_chunk = prefill_chunk

        compute_dt = _attn_compute_dtype(module)
        if cache_dtype is None:
            cache_dtype = (compute_dt if compute_dt is not None
                           else jnp.float32)
        # same "auto" weight policy as generate(): pre-cast matrix
        # weights to the compute dtype once (free for bf16 models, a
        # no-op for f32)
        if weights_dtype == "auto":
            weights_dtype = compute_dt if (
                compute_dt is not None
                and compute_dt != jnp.dtype(jnp.float32)) else None

        # --- quantized decode-GEMM weights (quantized-decode PR) --------
        # weight_quant replaces the float weight tree with per-channel
        # int8/int4 qdicts (``ops.quant_matmul``): every compiled
        # serving program dequantizes IN-GRAPH as its first op (the
        # int bytes are what lives in HBM; XLA fuses the dequant into
        # each consumer), and the decode/fused programs additionally
        # keep the attention projections quantized for the fused
        # dequant-matmul kernel when the backend gate is open.
        if weight_quant not in (None, "int8", "int4"):
            raise ValueError(
                f"weight_quant must be None, 'int8' or 'int4', "
                f"got {weight_quant!r}")
        if weight_quant is not None and ep_mesh is not None:
            raise ValueError(
                "weight_quant does not compose with expert parallelism "
                "(the per-leaf expert shardings assume float leaves, "
                "not qdicts) — serve EP models unquantized")
        self.weight_quant = weight_quant
        #: path-keyed per-leaf quantization error (max_abs_err /
        #: rel_rms) — ``obs.report.weight_quant_report`` renders it
        self.weight_quant_error = None
        self._wq_keep_attn = False
        self._wq_dequant_dt = (compute_dt if compute_dt is not None
                               else jnp.float32)
        with obs.span("serving.init.weights"):
            if weight_quant is not None:
                from distkeras_tpu.ops import quant_matmul as _qm
                qtree = _qm.quantize_params_tree(
                    model.params,
                    bits=4 if weight_quant == "int4" else 8)
                self.weight_quant_error = _qm.tree_quant_errors(
                    model.params, qtree)
                self._params = qtree
                # shape misalignments degrade per-leaf to the XLA
                # reference inside quant_matmul, so the keep-attn
                # decision only needs the backend gate (TPU, or a test
                # forcing interpreter mode at construction+trace time)
                self._wq_keep_attn = _qm.kernel_enabled()
            else:
                self._params = (model.params if weights_dtype is None
                                else _serving_params(model.params,
                                                     weights_dtype))
        self._state = model.state

        # --- MoE serving (MoE-serving PR) -------------------------------
        if moe_decode not in ("dispatched", "dense"):
            raise ValueError(
                f"moe_decode must be 'dispatched' or 'dense', "
                f"got {moe_decode!r}")
        self.moe_decode = moe_decode
        #: the model's MoE MLPs (inside TransformerBlocks: in the MLP's
        #: place, or a shortcut-connected one beside it), in layer order
        self._moe = [m for blk in
                     (_decode_block_of(layer) for layer in module.layers)
                     if blk is not None
                     for m in (blk.mlp, blk.shortcut)
                     if isinstance(m, MoE)]
        self._moe_dispatched = bool(self._moe) and \
            moe_decode == "dispatched"
        # expert telemetry rides only on the dispatched path (the dense
        # baseline keeps generate()'s exact program shape)
        self._moe_stats_on = self._moe_dispatched
        # --- block diffusion (block-diffusion PR) -----------------------
        #: block length of a block-causal model (None: causal, one
        #: token a step)
        self.block_len = block_len_of(module)
        if self.block_len is None:
            if denoising_steps is not None or mask_token is not None:
                raise ValueError(
                    "denoising_steps / mask_token are for a block-causal "
                    "model (zoo.transformer_lm(block_len=...))")
        else:
            b_len = self.block_len
            steps = b_len if denoising_steps is None \
                else int(denoising_steps)
            if not 1 <= steps <= b_len:
                raise ValueError(
                    f"denoising_steps must be in [1, block_len={b_len}], "
                    f"got {denoising_steps}")
            if mask_token is None:
                raise ValueError(
                    "a block-causal model needs mask_token (the id a "
                    "not-yet-fixed position is fed as)")
            if draft is not None or fuse_steps \
                    or host_kv_pages or self.max_len % b_len \
                    or page_len % b_len:
                raise ValueError(
                    "block diffusion needs max_len and page_len whole "
                    "multiples of the block length, and runs "
                    "without draft, fuse_steps or host_kv_pages")
            self.denoising_steps = steps
            self.mask_token = int(mask_token)
            #: tokens fixed by each denoising pass of a block
            #: (``low_confidence_static``: even, remainder first)
            self._fix_schedule = np.array(
                [b_len // steps + (i < b_len % steps)
                 for i in range(steps)], np.int64)
            # the expert telemetry of the one-token step has no reader
            # here: a pass reports its own counts (rows, experts)
            self._moe_stats_on = False
        #: routing counts of prefill programs not yet read
        self._prefill_routed: list = []
        #: the one-token programs report what their expert layers
        #: routed (rows, experts touched) where every expert layer is
        #: ``dispatch="grouped"``: its prefill and decode paths are one
        self._count_routing = (
            self._moe_dispatched and self.block_len is None
            and all(m.dispatch == "grouped" for m in self._moe))
        self._moe_conc: Optional[float] = None   # routing-concentration EMA
        self._moe_iter = 0                       # stats-throttle counter
        with obs.span("serving.init.weights"):
            self._setup_expert_parallel(ep_mesh)

        # paged-attention decode kernel (decode-kernel PR): "auto" =
        # the Pallas page-table kernel on TPU, the _gather_pages
        # reference elsewhere; "paged" forces the kernel (interpreter
        # mode off-TPU — the oracle/test hook); "off" forces the
        # gather path (the A/B baseline the bench rider prices)
        if decode_kernel not in ("auto", "paged", "off"):
            raise ValueError(
                f"decode_kernel must be 'auto', 'paged' or 'off', "
                f"got {decode_kernel!r}")
        self.decode_kernel = decode_kernel
        self._paged_kernel = {"auto": None, "paged": True,
                              "off": False}[decode_kernel]
        # hbm_budget sizes the page pool from a device-memory
        # envelope: the resident WEIGHT bytes (quantized or not —
        # this is where int4 weights + int4 KV pages compound into
        # more admitted streams) are reserved off the top and the
        # remainder becomes whole pages
        reserve = (sum(np.asarray(l).nbytes for l in
                       jax.tree_util.tree_leaves(self._params))
                   if hbm_budget is not None else 0)
        with obs.span("serving.init.pool"):
            self.pool = PagedKVPool(module, self.num_slots, self.max_len,
                                    page_len=page_len,
                                    num_pages=num_pages,
                                    host_pages=host_kv_pages,
                                    dtype=cache_dtype,
                                    hbm_budget=hbm_budget,
                                    reserve_bytes=reserve)
        self.page_len = self.pool.page_len
        if self.pool.latent and (
                draft is not None or fuse_steps or ep_mesh is not None
                or weight_quant is not None or self.block_len is not None):
            # (host_kv_pages, hbm_budget and int8 / int4 pages: the pool
            # has refused them already)
            raise ValueError(
                "latent attention is served one token a step from float "
                "latent pages: no draft, fuse_steps, ep_mesh, "
                "weight_quant or block diffusion")
        #: per layer ``(page group, ring)`` where the pool has a group
        #: per attention kind (``PagedKVPool.layer_groups``), else None
        self._groups = self.pool.layer_groups
        if self.pool.aux:
            if draft is not None or fuse_steps \
                    or self.block_len is not None or ep_mesh is not None:
                raise ValueError(
                    "a model with window and full attention layers is "
                    "served one token a step: no draft, fuse_steps, "
                    "block diffusion or ep_mesh")
            # a prefix hit resumes at a page boundary, where every
            # group's pages begin (no copy-on-write donor)
            prefix_granularity = int(np.lcm(int(prefix_granularity),
                                            self.page_len))
        self.prefix = PrefixCache(self.pool) if prefix_cache else None
        if prefix_granularity < 1:
            raise ValueError(
                f"prefix_granularity must be >= 1, "
                f"got {prefix_granularity}")
        self._prefix_granularity = int(prefix_granularity)
        if self.block_len is not None:
            # K/V inside a block depend on the whole block: a
            # partial-page match is only valid in whole blocks
            self._prefix_granularity = int(np.lcm(
                self._prefix_granularity, self.block_len))
        # ONE reusable batch-1 prefill staging cache: positions past the
        # current prompt hold a previous request's stale entries, which
        # is safe — insert copies only the pages/rows the prompt filled,
        # and the occupant's decode writes position t before the mask
        # ever admits it
        with obs.span("serving.init.pool"):
            self._staging = self.pool.make_request_cache()
        #: host-offload odometer snapshot (pool counts cumulatively;
        #: _flush_host_window publishes per-window deltas)
        self._off_seen = (0, 0, 0)
        #: likewise for the prefix cache's eviction odometers
        self._evict_seen = (0, 0, 0)
        # cost-aware scheduling: priority classes + preemption; the
        # engine gates admission on the free-page budget (_admit).
        # bounded admission (load shedding): submits past max_queue
        # raise AdmissionRejected instead of growing the queue without
        # bound under overload; None keeps the open-queue behavior
        self.scheduler = PriorityScheduler(self.num_slots,
                                           max_queue=max_queue)

        # --- zero-bubble loop state (zero-bubble PR) --------------------
        self.overlap = bool(overlap)
        fuse_steps = int(fuse_steps)
        if fuse_steps < 0:
            raise ValueError(
                f"fuse_steps must be >= 0, got {fuse_steps}")
        #: fused multi-step decode window (engaged when >= 2)
        self.fuse_steps = fuse_steps
        #: fused sampling epilogue (quantized-decode PR): sampled
        #: decode steps draw through ``ops.sampling.sample_tokens`` —
        #: the in-kernel mask+gumbel epilogue on TPU, the
        #: byte-identical reference factorization elsewhere (either
        #: way the token streams match the unfused sampler exactly)
        self.fused_sampling = bool(fused_sampling)
        self._fused_fns = {}                 # greedy_only -> jit scan
        #: the launched-but-unfetched decode step (lag-1 pipeline)
        self._pending: Optional[_PendingStep] = None
        #: slots whose next input token the HOST owns (True) vs the
        #: in-flight step's device output (False)
        self._chain_dirty = np.ones(int(num_slots), bool)
        #: terminal requests produced by out-of-band pipeline flushes
        #: (preemption, cancel); drained by the next step()
        self._finish_buf: List[Request] = []
        #: cumulative seconds blocked in the sanctioned lagged fetch —
        #: the bench's host_loop_us_per_iter rider subtracts this
        self.fetch_seconds = 0.0
        # deferred host work (flushed every _HOST_WINDOW iterations and
        # at every composition change — counts are exact, only their
        # recording is batched off the critical path)
        self._host_window = self._HOST_WINDOW if self.overlap else 1
        self._decode_buf: List = []          # (n_slots, dt, n_tokens)
        self._iter_buf: List = []            # (queue_depth, occupied)
        self._spec_buf: List = []            # (k, accepted) replay
        self._trace_decode: Dict[int, int] = {}   # rid -> decode ticks
        self._trace_decode_t0: Optional[float] = None
        self._trace_spec: Dict[int, List[int]] = {}  # rid -> [prop, acc]
        #: batch-composition version: bumped on admit / to-decoding /
        #: finish / preempt / terminate so steady-state iterations skip
        #: rebuilding the recorder's per-iteration rid lists
        self._comp_ver = 0
        self._rec_cache = (-1, None)

        # --- engine identity (serving-router PR) ------------------------
        # ``engine_id`` tags every process-global record this engine
        # emits — flight-recorder ring entries, tracer timelines — and
        # names its telemetry_snapshot() component: with N live engines
        # behind a router the records would otherwise interleave
        # indistinguishably. Default keeps the single-engine contract:
        # the first live engine is plain "serving", later ones get a
        # unique suffix.
        if engine_id is None:
            name = "serving"
            if name in obs.components():
                name = f"serving[{id(self):x}]"
            self.engine_id = name
        else:
            self.engine_id = str(engine_id)
            name = f"serving[{self.engine_id}]"
            if name in obs.components():
                # an alive engine already owns this id: disambiguate
                # the id ITSELF (not just the component name) — two
                # engines sharing a record tag is exactly the
                # indistinguishable interleaving engine_id exists to
                # prevent
                self.engine_id = f"{self.engine_id}#{id(self):x}"
                name = f"serving[{self.engine_id}]"
        self._component_name = name

        self.metrics = metrics if metrics is not None else ServingMetrics()
        # request-level observability (obs.tracing / obs.recorder /
        # obs.slo): the tracer shares the metrics clock so timeline
        # durations and measured latencies are directly comparable;
        # the scheduler records admissions where they happen; the
        # flight recorder is the process-global ring (NULL when obs is
        # disabled); ``slo`` takes an SLOEngine or a sequence of
        # Objectives (evaluated every _SLO_EVAL_EVERY iterations and
        # reported by health())
        self.tracer = resolve_tracer(tracer, clock=self.metrics.clock,
                                     engine=self.engine_id)
        self.scheduler.tracer = (self.tracer if self.tracer.enabled
                                 else None)
        self.recorder = resolve_recorder()
        if slo is None or isinstance(slo, SLOEngine):
            self.slo = slo
        else:
            self.slo = SLOEngine(list(slo), clock=self.metrics.clock)
        # windowed time-series telemetry (obs.timeseries): scraped on
        # the existing deferred host-window cadence in step() — pure
        # host-side Python over the live registry, zero new device
        # syncs. ``timeseries=None`` (default) builds a scraper that
        # follows the CURRENT metrics window across per-interval swaps
        # (the weakref provider — the scraper must not keep the engine
        # alive); ``False`` disables; a ``TimeSeries`` instance is used
        # as-is (the replay harness installs one on a virtual clock).
        if timeseries is False:
            self.timeseries = None
        elif isinstance(timeseries, TimeSeries):
            self.timeseries = timeseries
        else:
            _wref = weakref.ref(self)

            def _live_registry():
                eng = _wref()
                return None if eng is None else eng._metrics.registry

            self.timeseries = TimeSeries(
                _live_registry, clock=self.metrics.clock,
                interval_s=0.0 if timeseries is None else float(timeseries),
                tags={"engine": self.engine_id})
        self._requests: Dict[int, Request] = {}
        self._rid = itertools.count()

        # per-slot decode vectors (host mirrors of the traced args)
        s = self.num_slots
        self._tok = np.zeros(s, np.int32)
        #: max_len is the free-slot sentinel: the one-hot cache write
        #: misses every position and the slot's logits are discarded
        self._t = np.full(s, self.max_len, np.int32)
        self._temp = np.zeros(s, np.float32)
        self._topk = np.zeros(s, np.int32)
        self._topp = np.ones(s, np.float32)
        #: per-slot stop tokens (-1 = never): the fused window's
        #: in-program done masks read these
        self._stop = np.full(s, -1, np.int32)
        self._keys = np.stack(
            [np.array(jax.random.PRNGKey(0))] * s)       # [S, key]

        self._step_fns = {}                  # greedy_only -> jit
        self._block_fns = {}                 # with head? -> jit
        if self.block_len is not None:
            # per-slot block state. The blocks as they stand live on the
            # DEVICE (``_blk_dev``: tokens, which positions are still
            # masked, which pass fixed each): every denoise program
            # takes the last one's and returns its successor, so a pass
            # is queued on the one before it without a read. The host
            # keeps what it knows without a result, the schedule being
            # static: the denoising passes a block has had, the masked
            # positions left, whether the slot's request ends once its
            # block is shown; and the rows
            # only it knows (a block opened by a join or after a commit),
            # which override the device's at the next launch
            bl = self.block_len
            self._blk_tok = np.full((s, bl), self.mask_token, np.int32)
            self._blk_masked = np.zeros((s, bl), bool)
            self._blk_ovr = np.zeros(s, bool)
            self._blk_step = np.zeros(s, np.int32)
            self._blk_left = np.zeros(s, np.int32)
            self._blk_ended = np.zeros(s, bool)
            self._blk_dev = (jnp.asarray(self._blk_tok),
                             jnp.asarray(self._blk_masked),
                             jnp.asarray(np.full((s, bl), -1, np.int32)))
        #: program name -> the kernel-or-reference choices made while
        #: it was traced (``_jit_serving``); read it in ``health()``
        self.program_paths: Dict[str, str] = {}
        self._logits_fns = {}                # decode_logits variants
        self._prefill_fns = {}
        self._first_fn = None

        # speculative decoding (spec-decode PR): a DraftSource proposes
        # k candidate tokens per slot; ONE compiled verify step scores
        # the whole [S, k+1] window (fixed k — static shapes, one
        # program per sampler variant). A per-request acceptance EMA
        # (spec_disable_below / spec_warmup) kicks streams the draft
        # cannot predict back to plain decode — speculation is an
        # accelerator, never a correctness or admission dependency.
        if draft is not None and not isinstance(draft, DraftSource):
            raise TypeError(
                f"draft must be a DraftSource (NgramDraft / DraftModel "
                f"/ custom), got {type(draft).__name__}")
        self._draft = draft
        self.spec_k = int(spec_k)
        if self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if not 0.0 <= float(spec_disable_below) <= 1.0:
            raise ValueError(
                f"spec_disable_below must be in [0, 1], "
                f"got {spec_disable_below}")
        self.spec_disable_below = float(spec_disable_below)
        self.spec_warmup = int(spec_warmup)
        # adaptive re-enable: the EMA kill switch above is sticky by
        # default (the adversarial-stream contract several tests pin);
        # with ``spec_reprobe=N`` a demoted stream gets a probabilistic
        # re-probe after generating N more tokens, so a workload shift
        # (the draft starts predicting again) can win speculation back
        if spec_reprobe is not None:
            spec_reprobe = int(spec_reprobe)
            if spec_reprobe < 1:
                raise ValueError(
                    f"spec_reprobe must be >= 1, got {spec_reprobe}")
        self.spec_reprobe = spec_reprobe
        self._spec_fns = {}                  # greedy_only -> jit verify
        # tree speculation (tree-speculation PR): the verify window
        # widens to 1 + spec_k * spec_width TREE nodes; per-stream
        # depth/width adapt inside the static window
        self.spec_tree = bool(spec_tree)
        self.spec_width = int(spec_width)
        if self.spec_width < 1:
            raise ValueError(
                f"spec_width must be >= 1, got {spec_width}")
        if self.spec_width > 1 and not self.spec_tree:
            raise ValueError(
                "spec_width > 1 needs spec_tree=True (the linear "
                "verify window has no branch columns)")
        if self.spec_tree and draft is None:
            raise ValueError(
                "spec_tree=True needs a draft source "
                "(ServingEngine(draft=...))")
        #: verify-window width: tree windows hold the full node budget
        self.spec_window = (1 + self.spec_k * self.spec_width
                            if self.spec_tree else self.spec_k + 1)
        self._tree_fns = {}                  # greedy_only -> jit tree fn
        self._spec_tree_buf: List = []       # (tree_width, path_len)
        if draft is not None:
            with obs.span("serving.init.pool"):      # the draft's own pool
                draft.bind(self)

        # telemetry: the CURRENT metrics window joins the unified
        # obs.telemetry_snapshot() under "serving" (weakref-bound, so a
        # dropped engine detaches itself); the decode steps — compiled
        # once per sampler variant BY DESIGN — are recompile-watched,
        # catching shape/dtype leaks that would silently recompile the
        # hot loop (checked every _RECOMPILE_CHECK_EVERY iterations)
        self._recompile = obs.RecompileDetector()
        self._warmed = set()                 # decode variants marked warm
        self._iters = 0
        # component name resolved in the engine-identity block above
        # (first live engine owns plain "serving"; explicit engine_id
        # attaches as "serving[<id>]"). The bound method is
        # WeakMethod-held by attach, so the registry never keeps this
        # engine (and its KV pool) alive.
        obs.attach(self._component_name, self._telemetry_summary,
                   owner=self)

    #: engine iterations between recompile-detector polls
    _RECOMPILE_CHECK_EVERY = 64
    #: engine iterations between deferred host-work flushes (tracer
    #: ticks, metrics samples, spec counters) in overlap mode; 1 (the
    #: synchronous loop) flushes every iteration. Composition changes
    #: (finish/preempt/terminal) always flush immediately, so counts
    #: are exact — only their RECORDING is batched off the hot loop.
    _HOST_WINDOW = 8
    #: engine iterations between SLO evaluations (when ``slo`` is set)
    _SLO_EVAL_EVERY = 32
    #: EMA smoothing for the router-concentration estimate
    _MOE_CONC_ALPHA = 0.25
    #: decode iterations between MoE routing-stats reads. The stats are
    #: computed IN-PROGRAM every step (negligible), but pulling them to
    #: the host costs extra device syncs per iteration — measured 4x on
    #: the CPU smoke step when done every iteration. Sampling every
    #: 16th step keeps the gauges/EMA fresh at decode-agg cadence while
    #: the hot loop pays one sync set per 16 steps. The FIRST decode
    #: step always reports (tests and short runs see the picture).
    _MOE_STATS_EVERY = 16
    #: admission headroom per unit concentration (pages, as a fraction
    #: of the request's context pages) — see ``_moe_admit_extra``
    _MOE_ADMIT_ALPHA = 0.5

    # --- expert-parallel decode (MoE-serving PR) -------------------------

    def _setup_expert_parallel(self, ep_mesh) -> None:
        """Wire shard_map expert parallelism: models whose MoE layers
        carry ``expert_axis_name`` must run inside a shard_map, so the
        engine wraps every compiled program over ``ep_mesh`` with the
        stacked expert weights sharded on that axis (pre-placed here —
        each chip holds its E/A experts; everything else replicated).
        Outputs are replicated: the MoE combine psums over the axis
        in-program, exactly the layer's existing EP contract."""
        axes = {m.expert_axis_name for m in self._moe
                if m.expert_axis_name is not None}
        self._ep_mesh = self._ep_axis = self._ep_pspec = None
        if not axes:
            if ep_mesh is not None:
                raise ValueError(
                    "ep_mesh given but no MoE layer carries "
                    "expert_axis_name — build the model with "
                    "moe_expert_axis=<axis> to serve expert-parallel")
            return
        if len(axes) > 1:
            raise ValueError(
                f"MoE layers disagree on expert_axis_name: {axes}")
        axis = axes.pop()
        if ep_mesh is None:
            raise ValueError(
                f"MoE layers carry expert_axis_name={axis!r}: they can "
                "only run inside a shard_map — pass "
                "ServingEngine(ep_mesh=Mesh(...)) carrying that axis")
        if axis not in ep_mesh.axis_names:
            raise ValueError(
                f"ep_mesh axes {ep_mesh.axis_names} do not include the "
                f"model's expert axis {axis!r}")
        n_dev = ep_mesh.shape[axis]
        for m in self._moe:
            if m.num_experts % n_dev:
                raise ValueError(
                    f"num_experts {m.num_experts} not divisible by the "
                    f"{axis!r} mesh axis size {n_dev}")
        from jax.sharding import NamedSharding, PartitionSpec as P
        pspec = jax.tree_util.tree_map(lambda _: P(), self._params)
        shardings = jax.tree_util.tree_map(
            lambda _: NamedSharding(ep_mesh, P()), self._params)
        for i, layer in enumerate(self.module.layers):
            blk = _decode_block_of(layer)
            if blk is None or not isinstance(blk.mlp, MoE) \
                    or blk.mlp.expert_axis_name is None:
                continue
            for kk in ("w1", "b1", "w2", "b2", "w3"):
                if kk not in pspec[i]["mlp"]:
                    continue             # bias-free / ungated experts
                pspec[i]["mlp"][kk] = P(axis)
                shardings[i]["mlp"][kk] = NamedSharding(ep_mesh, P(axis))
        self._ep_mesh, self._ep_axis, self._ep_pspec = ep_mesh, axis, pspec
        # pre-slice the expert weights onto their chips once — the
        # whole point: per-chip weight traffic shrinks with the mesh
        self._params = jax.device_put(self._params, shardings)

    def _jit_serving(self, f, n_args: int, name: str,
                     keep_attn: bool = False, donate_cache: bool = True):
        """Compile one serving program: plain ``jax.jit``, or — under
        expert parallelism — ``jit(shard_map(f))`` with the params
        (always argument 0) split by the expert specs and every other
        argument/output replicated (the MoE psum makes outputs agree
        across the axis). Every program's signature is ``(params,
        state, cache, ...)`` and the KV cache — argument 2, the page
        pool or the batch-1 staging cache — is DONATED:
        the program writes its successor into the same buffers, the
        value passed in is deleted, and the caller rebinds it from the
        result before anything reads it. The one exception is
        ``decode_logits()``, which drops the step's cache writes and so
        must find the pool alive afterwards (``donate_cache=False``).
        Under ``weight_quant`` every program first
        dequantizes the qdict tree in-graph; ``keep_attn`` (the
        decode/fused programs, whose only attention-weight consumers
        are ``_project_qkv`` / ``_attn_out``) leaves the attention
        projections quantized for the fused dequant-matmul kernel.
        While the program is traced, every kernel-or-reference choice
        inside it (``compat.note_path``) lands in
        ``program_paths[name]`` — ``health()["programs"]`` — beside
        ``kv_cache=donated`` (or ``kv_cache=kept``). The
        program itself is named ``serving_<name>`` (anything outside
        ``[A-Za-z0-9_]`` becomes ``_``): what a profiler's ``XLA
        Modules`` line and the compile log call it."""
        traced = f

        def f(*args):
            with record_paths() as paths:
                out = traced(*args)
            paths.add("kv_cache=donated" if donate_cache
                      else "kv_cache=kept")
            self.program_paths[name] = ", ".join(sorted(paths))
            return out

        if self.weight_quant is not None:
            from distkeras_tpu.ops.quant_matmul import dequant_params_tree
            inner, dt = f, self._wq_dequant_dt
            keep = keep_attn and self._wq_keep_attn

            def f(params, *rest):
                return inner(
                    dequant_params_tree(params, dt, keep_attn=keep),
                    *rest)
        if self._ep_mesh is not None:
            from jax.sharding import PartitionSpec as P
            f = shard_map(
                f, mesh=self._ep_mesh,
                in_specs=(self._ep_pspec,) + (P(),) * (n_args - 1),
                out_specs=P())
        f.__name__ = f.__qualname__ = re.sub(
            "[^A-Za-z0-9_]", "_", "serving_" + name)
        return jax.jit(f, donate_argnums=(2,) if donate_cache else ())

    # --- MoE routing telemetry / admission cost ---------------------------

    def _note_moe_route(self, stats) -> None:
        """Host-side sink for one step's MoE routing stats (the extra
        output of the dispatched decode/verify programs): update the
        expert-load/entropy gauges, the concentration EMA the paged
        admission reads, and the per-request ``moe_route`` tracer
        aggregation (decode cadence). THROTTLED to every
        ``_MOE_STATS_EVERY``-th decode iteration — reading the device
        stats costs host syncs the hot loop must not pay per step."""
        if stats is None:
            return
        n = self._moe_iter
        self._moe_iter = n + 1
        if n % self._MOE_STATS_EVERY:
            return                       # unread device arrays just drop
        load = np.asarray(stats["expert_load"], np.float64)
        entropy = float(np.asarray(stats["router_entropy"]))
        total = float(load.sum())
        e = len(load)
        share = float(load.max()) / total if total > 0 else 0.0
        if total > 0 and e > 1:
            # normalize against uniform routing: 0 = balanced, 1 = all
            # assignments on one expert
            conc = max(0.0, (share - 1.0 / e) / (1.0 - 1.0 / e))
            a = self._MOE_CONC_ALPHA
            self._moe_conc = (conc if self._moe_conc is None
                              else (1.0 - a) * self._moe_conc + a * conc)
        self.metrics.record_moe_route(load, entropy,
                                      self._moe_conc or 0.0)
        if self.tracer.enabled:
            self.tracer.on_moe_route(
                [r.rid for r in self.scheduler.running.values()],
                entropy, share)

    def _moe_admit_extra(self, req: Request, n_logical: int) -> int:
        """MoE-aware admission cost: pages of HEADROOM (beyond the
        request's own context pages) the free-page budget must show
        before this admission, proportional to the smoothed router
        concentration. Rationale: under concentrated routing the
        dispatched decode's per-expert rows pile onto few experts (and,
        expert-parallel, onto few CHIPS), so the marginal stream buys
        less throughput — admitting to the last page then forces the
        preemption churn the budget exists to avoid. Capped so a
        feasible request can ALWAYS admit into an idle pool: worst-case
        context + headroom never exceeds the pool (no starvation)."""
        if not self._moe_stats_on or not self._moe_conc:
            return 0
        import math
        extra = int(math.ceil(
            self._MOE_ADMIT_ALPHA * self._moe_conc * n_logical))
        worst = self.pool.pages_for(len(req.prompt) + req.max_new_tokens)
        return max(0, min(extra, self.pool.num_pages - worst))

    def _telemetry_summary(self):
        """obs.attach provider: the CURRENT metrics window's summary
        (``self.metrics`` is swapped per reporting interval), plus the
        compact per-request timelines and the latest SLO status —
        additive keys on the established component shape."""
        self._flush_host_window()    # deferred samples land first
        snap = self.metrics.summary()
        if self.tracer.enabled:
            snap["requests"] = self.tracer.summaries()
        if self.slo is not None:
            snap["slo"] = self.slo.status()
        if self.timeseries is not None:
            snap["timeseries"] = self.timeseries.summary()
        return snap

    # --- zero-bubble loop: pipelined dispatch + deferred host work --------

    @property
    def metrics(self) -> ServingMetrics:
        return self._metrics

    @metrics.setter
    def metrics(self, value: ServingMetrics) -> None:
        """Swapping the metrics window (the per-reporting-interval
        pattern) first drains the pipeline and the deferred host-work
        buffers into the OLD window, so no sample leaks across."""
        old = getattr(self, "_metrics", None)
        if old is not None:
            self._flush_pending(self._finish_buf)
            self._flush_host_window()
        self._metrics = value

    def _fetch(self, *arrays):
        """THE serving loop's single sanctioned device->host sync: the
        lagged fetch of a completed decode/verify step's outputs (and
        the spec path's in-iteration verify fetch). Every other sync in
        the step/decode path is a lint finding
        (``tools/lint_host_sync.py``). Accumulates blocking time in
        ``fetch_seconds`` for the bench's host-loop rider."""
        with obs.span("serving.decode.fetch"):
            t0 = self._metrics.clock()
            if len(arrays) > 1:
                # several outputs of one step: start every copy before
                # waiting for the first, or each waits out a round trip
                # of its own with the device idle behind it
                for a in arrays:
                    a.copy_to_host_async()
            out = [np.asarray(a) for a in arrays]  # lint: allow-host-sync (the lagged fetch)
            self.fetch_seconds += self._metrics.clock() - t0
        return out

    def _flush_pending(self, out: Optional[List[Request]] = None) -> None:
        """Consume the in-flight decode step (if any): fetch its
        tokens, append them to their requests, finish what completed.
        After this the HOST owns every slot's next input token."""
        p = self._pending
        if p is None:
            return
        self._pending = None
        if out is None:
            out = self._finish_buf
        if self.block_len is not None:
            # the block state stays on the device: nothing to hand back
            self._consume_pass(p, out)
            return
        self._process_step(p, out)
        self._chain_dirty[:] = True

    def _process_step(self, p: _PendingStep, finished: List[Request],
                      t0: Optional[float] = None) -> None:
        """Consume one launched step's outputs. Slots whose request
        changed since launch (finished by an earlier flush, preempted,
        recycled) discard their tokens — the overshoot contract: at
        lag 1 a stream is stepped at most once past its stop token,
        and the extra token/KV write is never consumed.

        ``t0`` is the CONSUMING iteration's decode-phase start: the
        recorded decode sample spans this phase (dispatch + lagged
        fetch + consume), matching the synchronous loop's attribution.
        Without it (out-of-band flushes: preempt, cancel, metrics
        swap) the sample falls back to launch-to-consume wall, which
        overstates dt by whatever ran in between — rare enough not to
        skew the steady-state rate."""
        running = self.scheduler.running
        if not any(running.get(s) is not None and running[s].rid == r
                   for s, r in p.slots):
            return      # every covered stream retired: drop wholesale
        counts = ()
        if self._count_routing and p.count == 1:
            counts = (p.moe["routed"], *p.prefills)
        fetched = self._fetch(*((p.nxt,) if p.keys is None
                                else (p.nxt, p.keys)), *counts)
        nxt = fetched[0]
        if counts:
            routed, *prefills = fetched[-len(counts):]
            self.metrics.record_routing("decode", *map(int, routed))
            for counts in prefills:
                self.metrics.record_routing("prefill", *map(int, counts))
        if p.keys is not None:
            # chain-live slots take the program's post-split keys; a
            # slot the host overrode since launch (fresh admission)
            # keeps its host mirror — the launch never consumed it
            live = ~self._chain_dirty
            self._keys[live] = fetched[1][live]
        with obs.span("serving.decode.consume"):
            toks = nxt if nxt.ndim == 2 else nxt[:, None]    # [S, count]
            self._note_moe_route(p.moe)
            now_ = self._metrics.clock()
            trace_on = self.tracer.enabled
            done_reqs: List[Request] = []
            n_emitted = 0
            for slot, rid in p.slots:
                req = running.get(slot)
                if req is None or req.rid != rid:
                    continue                     # recycled slot: discard
                n_app = 0
                for j in range(p.count):
                    req.generated.append(int(toks[slot, j]))
                    n_app += 1
                    if req.done:
                        break                    # stop / budget mid-window
                n_emitted += n_app
                self._tok[slot] = req.generated[-1]
                if trace_on and n_app:
                    self._trace_decode[rid] = \
                        self._trace_decode.get(rid, 0) + n_app
                    if self._trace_decode_t0 is None:
                        self._trace_decode_t0 = now_
                if req.done:
                    done_reqs.append(req)
            self._decode_buf.append(
                (len(p.slots),
                 now_ - (p.launch_t if t0 is None else t0), n_emitted))
            if done_reqs:
                self._flush_host_window()        # ticks precede terminals
                for req in done_reqs:
                    self._finish(req, finished)

    def _flush_host_window(self) -> None:
        """Apply the deferred host-work buffers to the live metrics
        window and tracer: per-iteration queue/occupancy samples, exact
        decode token/time aggregation, spec verify counters, and the
        batched per-request decode ticks. Runs every ``_HOST_WINDOW``
        iterations, before every terminal transition, and on
        metrics-window swaps — so every count is exact, just recorded
        off the per-iteration critical path."""
        m = self._metrics
        if self._iter_buf:
            for qd, occ in self._iter_buf:
                m.record_iteration(qd, occ, self.num_slots)
            self._iter_buf.clear()
            m.record_pages(self.pool.free_pages,
                           self.pool.shared_pages,
                           self._fragmentation())
            # host-tier odometers: the pool counts cumulatively;
            # the metrics WINDOW gets deltas so window swaps stay
            # honest (the record_pages gauge discipline)
            po, pr, ob = (self.pool.pages_offloaded,
                          self.pool.pages_restored,
                          self.pool.offload_bytes)
            so, sr, sb = self._off_seen
            if po > so or pr > sr:
                m.record_offload(po - so, pr - sr, ob - sb)
                self._off_seen = (po, pr, ob)
            if self.pool.aux or self.pool.latent or self.pool.state_layers:
                m.record_kv_groups(self._kv_groups())
            if self.prefix is not None:
                now = (self.prefix.evictions,
                       self.prefix.evict_examined,
                       self.prefix.evictable_queries)
                if now != self._evict_seen:
                    m.record_prefix_eviction(*(
                        a - b for a, b in zip(now, self._evict_seen)))
                    self._evict_seen = now
        if self._decode_buf:
            for n, dt, toks in self._decode_buf:
                m.record_decode(n, dt, n_tokens=toks)
            self._decode_buf.clear()
        if self._spec_buf:
            for k, acc in self._spec_buf:
                m.record_spec_verify(k, acc)
            self._spec_buf.clear()
        if self._spec_tree_buf:
            for width, path_len in self._spec_tree_buf:
                m.record_spec_tree(width, path_len)
            self._spec_tree_buf.clear()
        if self._trace_decode:
            if self.tracer.enabled:
                self.tracer.on_decode_batch(self._trace_decode,
                                            t0=self._trace_decode_t0)
            self._trace_decode = {}
            self._trace_decode_t0 = None
        if self._trace_spec:
            if self.tracer.enabled:
                # linear entries are [proposed, accepted]; tree entries
                # append [tree_width, accepted_path_len]
                self.tracer.on_spec_verify(
                    [(rid, *pa)
                     for rid, pa in self._trace_spec.items()])
            self._trace_spec = {}

    def _kv_groups(self) -> Dict[str, Dict]:
        """Per page group (``"full"``, or ``"latent"`` where the pool's
        planes hold one latent a token; then each window group by its
        name): pages in all, free, held by slots (``pages_live``),
        held more than once (``pages_shared``), given back by slots
        behind their window since the engine began
        (``pages_released``; the full group gives none back) and by
        the prefix cache under that group's pressure."""
        pool = self.pool
        out = {"latent" if pool.latent else "full": {
            "window": None, "pages_total": pool.num_pages,
            "pages_free": pool.free_pages,
            "pages_live": int((pool.tables < pool.num_pages).sum()),
            "pages_shared": pool.shared_pages, "pages_released": 0}}
        if pool.state_layers:
            # the state group: rows a slot, not pages (class doc)
            out["ssm"] = {"window": None, "layers": len(pool.state_layers),
                          "slots": pool.num_slots,
                          "bytes": pool.state_bytes,
                          "resets": self.ssm_counters["ssm_state_resets"]}
        for g, grp in enumerate(pool.aux):
            out[grp.name] = {
                "window": grp.window, "pages_total": grp.num_pages,
                "pages_free": grp.free_pages,
                "pages_live": grp.live_pages,
                "pages_shared": grp.shared_pages,
                "pages_released": grp.pages_released,
                "pages_evicted": (0 if self.prefix is None
                                  else self.prefix.aux_evictions[g])}
        return out

    def _inflight(self) -> Dict[int, int]:
        """slot -> tokens in flight for the slot's CURRENT request (0
        when the pending step predates the occupant)."""
        p = self._pending
        if p is None:
            return {}
        running = self.scheduler.running
        out = {}
        for slot, rid in p.slots:
            req = running.get(slot)
            if req is not None and req.rid == rid:
                out[slot] = p.count
        return out

    def _merge_keys(self, prev: Optional[_PendingStep], dirty):
        """Per-slot PRNG keys for the next launch: the in-flight
        step's post-split keys wherever the chain is live, the host
        mirror where the host overrode the slot since. Mirrors are
        snapshotted (``_snap``) — the launched program reads them
        after dispatch returns."""
        if prev is None or prev.keys is None:
            return _snap(self._keys)
        if dirty.any():
            return jnp.where(_snap(dirty)[:, None],
                             _snap(self._keys), prev.keys)
        return prev.keys

    def _fuse_window(self) -> int:
        """Fused-window size for THIS iteration: ``fuse_steps`` when
        the scheduler is quiescent, else 0 (single-step). Quiescent =
        nothing queued or prefilling (admission latency would coarsen
        to K steps), no deadline in the batch (expiry checks are
        per-iteration), and every stream's remaining budget — net of
        in-flight tokens — covers a whole window (the in-program stop
        masks handle stop tokens; the budget has no in-program
        analogue, so the window must fit under it)."""
        k = self.fuse_steps
        if k < 2:
            return 0
        sch = self.scheduler
        if sch.queue_depth or sch.prefilling:
            return 0
        running = sch.running
        if not running:
            return 0
        infl = self._inflight()
        for slot, r in running.items():
            if r.deadline_s is not None:
                return 0
            if r.max_new_tokens - len(r.generated) \
                    - infl.get(slot, 0) < k:
                return 0
        return k

    def _launch_step(self, greedy_only: bool, tables, fuse: int,
                     prev: Optional[_PendingStep],
                     t0: float) -> _PendingStep:
        """Dispatch one decode unit — a single step, or a ``fuse``-wide
        fused window — WITHOUT waiting on its outputs. The input token
        vector chains device-side from the in-flight step's feedback
        (``prev.last``) wherever the chain is live, falling back to the
        host mirror for slots the host overrode since (fresh
        admissions, post-flush iterations). Host mirrors advance
        eagerly: ``_t`` moves past the positions this launch writes, so
        page growth and the next launch see the true frontier."""
        running = self.scheduler.running
        dirty = self._chain_dirty
        # every host mirror crossing the device boundary here is
        # snapshotted (_snap): dispatch returns while the program still
        # READS its arguments, and the CPU client zero-copy aliases
        # aligned numpy buffers — the eager mirror updates below (and
        # later iterations' bookkeeping) must not race the in-flight
        # read. The synchronous loop never saw this: it blocked on the
        # step's outputs before touching any mirror.
        t_dev = _snap(self._t)
        if prev is None:
            tok = _snap(self._tok)
        elif dirty.any():
            tok = jnp.where(_snap(dirty), _snap(self._tok), prev.last)
        else:
            tok = prev.last
        keys = None
        if fuse:
            if greedy_only:
                nxt, cache, moe = self._fused_fn(True)(
                    self._params, self._state, self.pool.cache, tok,
                    t_dev, _snap(self._stop), tables)
            else:
                nxt, cache, keys, moe = self._fused_fn(False)(
                    self._params, self._state, self.pool.cache, tok,
                    t_dev, _snap(self._stop), _snap(self._temp),
                    _snap(self._topk), _snap(self._topp),
                    self._merge_keys(prev, dirty), tables)
            last, count = nxt[:, -1], fuse
            warm = ("serving.decode_fused_greedy" if greedy_only
                    else "serving.decode_fused_sampled")
        else:
            if greedy_only:
                nxt, cache, moe = self._decode_fn(True)(
                    self._params, self._state, self.pool.cache, tok,
                    t_dev, tables)
            else:
                nxt, cache, keys, moe = self._decode_fn(False)(
                    self._params, self._state, self.pool.cache, tok,
                    t_dev, _snap(self._temp), _snap(self._topk),
                    _snap(self._topp),
                    self._merge_keys(prev, dirty), tables)
            last, count = nxt, 1
            warm = ("serving.decode_greedy" if greedy_only
                    else "serving.decode_sampled")
        self.pool.cache = cache
        # warm baseline AFTER a variant's first call (its one
        # legitimate compile); cache growth past it is a shape leak
        if warm not in self._warmed:
            self._warmed.add(warm)
            self._recompile.mark_warm(warm)
        slots = tuple((slot, r.rid) for slot, r in running.items())
        for slot, _ in slots:
            self._t[slot] += count
            dirty[slot] = False          # chain live until overridden
        self.ssm_counters["ssm_state_slot_steps"] += \
            count * len(slots) * len(self._ssm_layers)
        prefills, self._prefill_routed = self._prefill_routed, []
        return _PendingStep(nxt, last, keys, moe, slots, count, t0,
                            tuple(prefills))

    def _record_iteration(self, admitted: List[Request]) -> None:
        """Flight-recorder iteration entry, written BEFORE
        prefill/decode run so a mid-iteration fault dump contains the
        failing iteration itself. The per-iteration rid lists rebuild
        only when the batch composition changed (``_comp_ver``);
        steady-state iterations reuse the cached lists and, in overlap
        mode, only write a ring entry on the host-window cadence."""
        if not self.recorder.enabled:
            return
        sch = self.scheduler
        ver = self._rec_cache[0]
        if self._comp_ver != ver:
            self._rec_cache = (self._comp_ver, (
                [r.rid for r in sch.running.values()],
                [r.rid for r in sch.prefilling]))
        elif self._iters % self._host_window:
            return                      # steady state: window cadence
        decoding, prefilling = self._rec_cache[1]
        extra = {"pages_free": self.pool.free_pages}
        if self.pool.host_cache is not None:
            # host-pool occupancy in the flight-recorder ring: a
            # post-mortem distinguishes "swaps stopped because the
            # host tier filled" from "preemptions stopped"
            extra["host_pages_free"] = self.pool.host_free_pages
        self.recorder.record(
            "serving.iteration", engine=self.engine_id,
            iter=self._iters,
            queue_depth=sch.queue_depth, occupied=sch.occupied,
            decoding=decoding, prefilling=prefilling,
            admitted=[r.rid for r in admitted], **extra)

    # --- request intake ---------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, *,
               temperature: float = 0.0, top_k: Optional[int] = None,
               top_p: Optional[float] = None,
               stop_token: Optional[int] = None, seed: int = 0,
               deadline_s: Optional[float] = None,
               priority: int = 1,
               speculate: Optional[bool] = None) -> int:
        """Enqueue one request; returns its id. Sampling defaults match
        ``generate()`` (greedy); ``None`` knobs mean disabled.

        ``deadline_s`` is a submit→finish budget on the engine clock: a
        request still unfinished when it expires is terminated
        ``TIMED_OUT`` at the next ``step()`` (partial tokens kept on the
        returned request). Raises ``AdmissionRejected`` when the engine
        was built with ``max_queue`` and the wait queue is full.

        ``priority``: lower admits first — 0 interactive, 1 standard
        (default), 2 batch. A queued priority-0 request may PREEMPT
        lower-priority decoding streams when the page budget is short.

        ``speculate`` (engines built with ``draft=``): whether this
        request joins draft-and-verify decode iterations. ``None``
        (default) means yes whenever the engine has a draft source;
        ``False`` opts out; ``True`` on a draftless engine raises.
        Greedy speculative output is token-identical to plain decode
        (and to ``generate()``); sampled streams keep their exact
        per-request key stream either way."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if prompt.size + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the slot capacity "
                f"max_len={self.max_len}")
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if deadline_s is not None and float(deadline_s) <= 0:
            raise ValueError(
                f"deadline_s must be > 0, got {deadline_s}")
        if self.block_len is not None and float(temperature) > 0.0:
            raise ValueError(
                "block diffusion decodes greedily (temperature 0): a "
                "position is fixed to its most probable token")
        # a request whose worst case exceeds the whole pool could
        # never finish — even after preempting everything else
        worst = self.pool.pages_for(prompt.size + max_new_tokens)
        if worst > self.pool.num_pages:
            raise ValueError(
                f"request needs up to {worst} pages but the pool "
                f"holds {self.pool.num_pages}; raise num_pages or "
                "lower max_new_tokens")
        if speculate and self._draft is None:
            raise ValueError(
                "speculate=True needs an engine built with a draft "
                "source (ServingEngine(draft=NgramDraft()) or "
                "DraftModel(...))")
        req = Request(
            rid=next(self._rid), prompt=prompt,
            max_new_tokens=max_new_tokens,
            temperature=float(temperature),
            top_k=0 if top_k is None else int(top_k),
            top_p=1.0 if top_p is None else float(top_p),
            stop_token=-1 if stop_token is None else int(stop_token),
            seed=int(seed), priority=int(priority),
            deadline_s=None if deadline_s is None else float(deadline_s),
            speculate=(self._draft is not None if speculate is None
                       else bool(speculate)))
        req.rng = jax.random.PRNGKey(req.seed)
        req.submit_t = self.metrics.clock()
        try:
            self.scheduler.submit(req)    # may shed (AdmissionRejected)
        except AdmissionRejected:
            self.metrics.record_rejected()
            self.tracer.on_reject()
            # storm detection lives in the recorder: enough sheds since
            # the last dump auto-snapshot the ring (overload forensics)
            self.recorder.note_rejection(
                rid=req.rid, engine=self.engine_id,
                queue_depth=self.scheduler.queue_depth,
                max_queue=self.scheduler.max_queue)
            raise
        self._requests[req.rid] = req
        self.metrics.record_submit(req.rid)
        self.tracer.on_submit(req.rid, self.scheduler.queue_depth)
        return req.rid

    def __getitem__(self, rid: int) -> Request:
        """IN-FLIGHT request lookup (queued/prefilling/decoding).
        Finished requests are returned by ``step()``/``run()`` and
        evicted from the engine — a long-lived server must not
        accumulate one prompt array per request ever served."""
        return self._requests[rid]

    # --- compiled programs ------------------------------------------------

    def _decode_fn(self, greedy_only: bool):
        """Two compiled step variants, chosen per iteration by the
        host: ALL-GREEDY batches (the common serving default) take a
        pure-argmax step — the vector sampler's rank/nucleus masks cost
        two [S, V] argsorts plus a sort per step that greedy never
        needs, a material tax at real vocab sizes. A mixed batch takes
        the full per-slot sampler; sampled requests only ever decode
        under the mixed variant (their temperature forces it while they
        occupy a slot), so their per-request key streams stay
        schedule-independent."""
        fn = self._step_fns.get(greedy_only)
        if fn is None:
            module = self.module
            page_len = self.page_len
            moe_kw = dict(
                moe_dispatched=self._moe_dispatched,
                moe_stats=self.max_len if self._moe_stats_on else None,
                live_len=self.max_len)
            stats_on = self._moe_stats_on
            pk = self._paged_kernel
            if self._groups is not None:
                moe_kw["groups"] = self._groups

            def step(params, state, cache, tok, t, tables):
                out = decode_step_slots_paged(
                    module, params, state, cache, tok, t, tables,
                    page_len, paged_kernel=pk, **moe_kw)
                # every variant returns a routing-stats slot (None on
                # MoE-free / dense-baseline engines) so call sites
                # unpack one shape
                return out if stats_on else (out + (None,))

            if greedy_only:
                def fn(params, state, cache, tok, t, tables):
                    logits, cache, moe = step(params, state, cache,
                                              tok, t, tables)
                    with jax.named_scope("sample"):
                        nxt = jnp.argmax(logits, axis=-1)
                    return nxt, cache, moe
                n_args = 6
            else:
                if self.fused_sampling:
                    from distkeras_tpu.ops.sampling import sample_tokens
                    sampler = sample_tokens
                else:
                    sampler = _sample_vec

                def fn(params, state, cache, tok, t, temp, topk, topp,
                       keys, tables):
                    logits, cache, moe = step(params, state, cache,
                                              tok, t, tables)
                    # per-slot key streams: a request's draws depend
                    # only on its own seed, not on which neighbours
                    # share the batch
                    with jax.named_scope("sample"):
                        split = jax.vmap(jax.random.split)(keys)
                        nxt = sampler(logits, temp, topk, topp,
                                      split[:, 1])
                    return nxt, cache, split[:, 0], moe
                n_args = 10

            fn = self._jit_serving(
                fn, n_args, "decode_greedy" if greedy_only
                else "decode_sampled", keep_attn=True)
            self._step_fns[greedy_only] = fn
            self._recompile.watch(
                "serving.decode_greedy" if greedy_only
                else "serving.decode_sampled", fn)
        return fn

    def _fused_fn(self, greedy_only: bool):
        """The fused multi-step window: ``fuse_steps`` plain decode
        iterations as ONE compiled ``lax.scan``
        (``decoding.decode_fused_slots``), mirroring ``_decode_fn``'s
        greedy/sampled split. Returns ``(toks [S, K], cache, keys?,
        moe?)`` with the same routing-stats slot convention."""
        fn = self._fused_fns.get(greedy_only)
        if fn is None:
            module = self.module
            page_len = self.page_len
            k = self.fuse_steps
            moe_kw = dict(
                moe_dispatched=self._moe_dispatched,
                moe_stats=self.max_len if self._moe_stats_on else None,
                paged_kernel=self._paged_kernel)
            stats_on = self._moe_stats_on

            if greedy_only:
                def fn(params, state, cache, tok, t, stop, tables):
                    toks, cache, _, moe = decode_fused_slots(
                        module, params, state, cache, tok, t, stop, k,
                        table=tables, page_len=page_len, **moe_kw)
                    return toks, cache, (moe if stats_on else None)
                n_args = 7
            else:
                if self.fused_sampling:
                    from distkeras_tpu.ops.sampling import sample_tokens
                    moe_kw = dict(moe_kw, sampler=sample_tokens)

                def fn(params, state, cache, tok, t, stop, temp,
                       topk, topp, keys, tables):
                    toks, cache, keys, moe = decode_fused_slots(
                        module, params, state, cache, tok, t, stop, k,
                        table=tables, page_len=page_len,
                        temperature=temp, top_k=topk, top_p=topp,
                        keys=keys, **moe_kw)
                    return toks, cache, keys, \
                        (moe if stats_on else None)
                n_args = 11

            fn = self._jit_serving(
                fn, n_args, "decode_fused_greedy" if greedy_only
                else "decode_fused_sampled", keep_attn=True)
            self._fused_fns[greedy_only] = fn
            self._recompile.watch(
                "serving.decode_fused_greedy" if greedy_only
                else "serving.decode_fused_sampled", fn)
        return fn

    def _verify_fn(self, greedy_only: bool):
        """Two compiled speculative-verify variants, mirroring
        ``_decode_fn``'s greedy/sampled split. Each scores the whole
        ``[S, k+1]`` window ``[tok, d_1 .. d_k]`` in one target
        forward and computes acceptance IN-PROGRAM:

        * greedy — candidates are per-position argmaxes; accepted
          count = the longest prefix where the target's own choice
          equals the draft (exact match, so the emitted stream is the
          plain greedy stream by construction);
        * sampled — one PRNG split per potentially emitted token, in
          the exact order plain decode would split (one per emitted
          token), with the slot's post-step key selected by the
          accepted count. Sampling from the target and accepting while
          it equals the (deterministic) draft IS exact rejection
          sampling for a point-mass draft distribution — and, unlike
          the general-q rule, keeps sampled streams byte-identical to
          plain decode, not merely distribution-equivalent.

        ``active`` force-rejects rows (accepted = 0), which makes a
        verify step exactly a plain decode step for opted-out /
        EMA-disabled slots — one program serves mixed batches."""
        fn = self._spec_fns.get(greedy_only)
        if fn is None:
            module = self.module
            page_len = self.page_len
            k = self.spec_k
            moe_kw = dict(
                moe_dispatched=self._moe_dispatched,
                moe_stats=self.max_len if self._moe_stats_on else None)
            stats_on = self._moe_stats_on
            pk = self._paged_kernel

            def vstep(params, state, cache, toks, t, tables):
                out = verify_step_slots_paged(
                    module, params, state, cache, toks, t, tables,
                    page_len, paged_kernel=pk, **moe_kw)
                return out if stats_on else (out + (None,))

            def accept(cand, toks, active):
                # longest prefix of drafts matching the target's own
                # choices: cand[:, j] continues window position j, so
                # draft toks[:, j+1] is accepted iff it equals cand[:, j]
                match = (cand[:, :-1] == toks[:, 1:]).astype(jnp.int32)
                n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                return jnp.where(active, n_acc, 0)

            if greedy_only:
                def fn(params, state, cache, toks, t, active, tables):
                    logits, cache, moe = vstep(params, state, cache,
                                               toks, t, tables)
                    cand = jnp.argmax(logits, axis=-1)     # [S, k+1]
                    return cand, accept(cand, toks, active), cache, moe
                n_args = 7
            else:
                def fn(params, state, cache, toks, t, active, temp,
                       topk, topp, keys, tables):
                    logits, cache, moe = vstep(params, state, cache,
                                               toks, t, tables)
                    cands, carries = [], []
                    cur = keys
                    for j in range(k + 1):
                        split = jax.vmap(jax.random.split)(cur)
                        cur = split[:, 0]
                        cands.append(_sample_vec(
                            logits[:, j], temp, topk, topp,
                            split[:, 1]))
                        carries.append(cur)
                    cand = jnp.stack(cands, axis=1)        # [S, k+1]
                    n_acc = accept(cand, toks, active)
                    # the slot emitted n_acc + 1 tokens, so its key
                    # advanced n_acc + 1 splits — exactly what n_acc+1
                    # plain decode iterations would have done
                    new_keys = jnp.stack(carries, axis=1)[
                        jnp.arange(cand.shape[0]), n_acc]
                    return cand, n_acc, cache, new_keys, moe
                n_args = 11

            fn = self._jit_serving(
                fn, n_args, "verify_greedy" if greedy_only
                else "verify_sampled")
            self._spec_fns[greedy_only] = fn
            self._recompile.watch(
                "serving.verify_greedy" if greedy_only
                else "serving.verify_sampled", fn)
        return fn

    def _verify_tree_fn(self, greedy_only: bool):
        """The TREE counterparts of ``_verify_fn``'s two variants: one
        program runs the tree-masked verify forward
        (``verify_step_slots_paged`` with the ancestor mask), the
        in-program acceptance walk (``tree_walk`` — greedy argmax
        descent, or the exact point-mass rejection-sampling walk with
        one PRNG split per emitted token), and the accepted-path cache
        commit (``commit_tree_path``) — returning ``(emitted, n_emit,
        cache[, keys], moe)``. Slots whose tree has no draft nodes
        (opted out, EMA-disabled, clamped to depth 0) walk exactly one
        root step — a plain decode step — so mixed batches share the
        program, the linear path's ``active`` contract re-expressed as
        tree shape."""
        fn = self._tree_fns.get(greedy_only)
        if fn is None:
            module = self.module
            page_len = self.page_len
            moe_kw = dict(
                moe_dispatched=self._moe_dispatched,
                moe_stats=self.max_len if self._moe_stats_on else None)
            stats_on = self._moe_stats_on
            pk = self._paged_kernel

            def vstep(params, state, cache, toks, t, depth, anc,
                      tables):
                tree = {"depth": depth, "anc": anc}
                out = verify_step_slots_paged(
                    module, params, state, cache, toks, t, tables,
                    page_len, tree=tree, paged_kernel=pk, **moe_kw)
                if stats_on:
                    logits, cache, kvw, moe = out
                else:
                    (logits, cache, kvw), moe = out, None
                return logits, cache, kvw, moe

            if greedy_only:
                def fn(params, state, cache, toks, t, parents, depth,
                       anc, tables):
                    logits, cache, kvw, moe = vstep(
                        params, state, cache, toks, t, depth, anc,
                        tables)
                    emitted, n_emit, path, _ = tree_walk(
                        logits, toks, parents)
                    cache = commit_tree_path(
                        cache, kvw, path, t, n_emit, table=tables,
                        page_len=page_len)
                    return emitted, n_emit, cache, moe
                n_args = 9
            else:
                def fn(params, state, cache, toks, t, parents, depth,
                       anc, temp, topk, topp, keys, tables):
                    logits, cache, kvw, moe = vstep(
                        params, state, cache, toks, t, depth, anc,
                        tables)
                    emitted, n_emit, path, new_keys = tree_walk(
                        logits, toks, parents, temperature=temp,
                        top_k=topk, top_p=topp, keys=keys)
                    cache = commit_tree_path(
                        cache, kvw, path, t, n_emit, table=tables,
                        page_len=page_len)
                    return emitted, n_emit, cache, new_keys, moe
                n_args = 13
            fn = self._jit_serving(
                fn, n_args, "verify_tree_greedy" if greedy_only
                else "verify_tree_sampled")
            self._tree_fns[greedy_only] = fn
            self._recompile.watch(
                "serving.verify_tree_greedy" if greedy_only
                else "serving.verify_tree_sampled", fn)
        return fn

    # --- speculation bookkeeping ------------------------------------------

    def _spec_eligible(self, req: Request) -> bool:
        """Could this request speculate (knob on, EMA has not killed
        it)? Slot-independent — used at begin_slot time too."""
        return (self._draft is not None and req.speculate
                and not req.spec_disabled)

    def _spec_slots(self):
        """Decoding slots that speculate THIS iteration. Demoted
        streams get their re-probe chance here (``spec_reprobe``) —
        the one place every decode iteration already inspects them."""
        out = []
        for slot, r in self.scheduler.running.items():
            if r.spec_disabled and self.spec_reprobe is not None:
                self._maybe_reprobe(r)
            if self._spec_eligible(r):
                out.append(slot)
        return out

    def _spec_disable(self, req: Request) -> None:
        """Per-request kill switch (adversarial-stream escape hatch):
        the stream decodes plainly from here on — sticky unless the
        engine was built with ``spec_reprobe``."""
        req.spec_disabled = True
        req.spec_disabled_at = len(req.generated)
        self.metrics.record_spec_disabled()
        if self._draft is not None and req.slot is not None:
            self._draft.end_slot(req.slot)

    #: re-probe coin odds: one in this many eligible positions fires
    #: (deterministic — a crc32 of (seed, rid, position), not an RNG
    #: draw, so replays reproduce the exact re-enable points)
    _SPEC_REPROBE_ONE_IN = 8

    def _maybe_reprobe(self, req: Request) -> None:
        """Probabilistic speculation re-enable (``spec_reprobe``): once
        a demoted stream has generated ``spec_reprobe`` further tokens,
        each position flips a deterministic coin; on success the stream
        rejoins speculation with a FRESH warm-up (EMA and check count
        reset — the kill switch gets a clean window to re-judge). If
        the draft cannot re-adopt the slot the stream re-demotes and
        the cooldown restarts. Token identity is untouched either way:
        verify accepts only target-matching tokens."""
        if (self._draft is None or not req.speculate
                or req.slot is None):
            return
        since = len(req.generated) - (req.spec_disabled_at or 0)
        if since < self.spec_reprobe:
            return
        coin = zlib.crc32(
            f"{req.seed}:{req.rid}:{len(req.generated)}".encode())
        if coin % self._SPEC_REPROBE_ONE_IN:
            return
        req.spec_disabled = False
        req.spec_disabled_at = None
        req.spec_ema = None
        req.spec_checks = 0
        if self._draft.begin_slot(req.slot, req.context_tokens):
            self.metrics.record_spec_reenabled()
        else:
            self._spec_disable(req)

    def _observe_acceptance(self, req: Request, rate: float) -> None:
        """Update the per-request acceptance EMA; below the floor after
        warm-up, speculation stops paying for itself (every verify
        step costs a (k+1)-wide forward to emit ~1 token) and the
        stream is kicked back to plain decode."""
        a = self._SPEC_EMA_ALPHA
        req.spec_ema = (rate if req.spec_ema is None
                        else (1.0 - a) * req.spec_ema + a * rate)
        req.spec_checks += 1
        if req.spec_checks >= self.spec_warmup \
                and req.spec_ema < self.spec_disable_below:
            self._spec_disable(req)

    #: EMA smoothing for per-request draft acceptance
    _SPEC_EMA_ALPHA = 0.25
    #: adaptive tree controller (spec_tree): EMA at-or-above widens a
    #: stream toward (spec_k, spec_width); below the demote line it
    #: narrows toward a depth-1 chain — full demotion to plain decode
    #: stays the existing spec_disable_below kill switch's job
    _TREE_PROMOTE_EMA = 0.6
    _TREE_DEMOTE_EMA = 0.25

    def _tree_shape(self, req: Request):
        """This request's tree shape for the NEXT verify: the adaptive
        controller's (depth, width), depth clamped so no accepted path
        can outrun the remaining token budget (``remaining - 1`` — the
        final emitted token is always the free bonus). Depth < 1 means
        the stream rides the window as a plain decode step this
        iteration."""
        if req.tree_depth is None:
            req.tree_depth = self.spec_k
            req.tree_width = self.spec_width
        remaining = req.max_new_tokens - len(req.generated)
        return min(req.tree_depth, remaining - 1), req.tree_width

    def _adapt_tree(self, req: Request) -> None:
        """Resize a stream's tree from its acceptance EMA: hot streams
        (EMA >= ``_TREE_PROMOTE_EMA``) deepen first, then widen — depth
        compounds on a well-predicted stream, width only pays at
        divergence points; cold streams (< ``_TREE_DEMOTE_EMA``) shed
        width first (side branches are the cheapest columns to stop
        wasting), then depth, demoting toward a 1-deep chain — the
        sticky EMA floor (``_observe_acceptance``) handles the final
        drop to plain decode. Gated on the SAME ``spec_warmup`` as the
        kill switch: a fresh stream's first verifies routinely miss
        (its n-gram history is still forming), and resizing off that
        transient collapsed trees the steady state would have kept
        wide."""
        ema = req.spec_ema
        if ema is None or req.spec_checks < self.spec_warmup:
            return
        if ema >= self._TREE_PROMOTE_EMA:
            if req.tree_depth < self.spec_k:
                req.tree_depth += 1
            elif req.tree_width < self.spec_width:
                req.tree_width += 1
        elif ema < self._TREE_DEMOTE_EMA:
            if req.tree_width > 1:
                req.tree_width -= 1
            elif req.tree_depth > 1:
                req.tree_depth -= 1

    #: prefill-program cache cap: every DISTINCT (q_len, t0, final)
    #: triple is its own XLA program (the final chunk's key differs for
    #: every prompt length, so a varied-length workload compiles one
    #: program per novel length — compilation runs inline in ``step()``
    #: and does stall in-flight streams for that iteration; production
    #: deployments should pre-warm or bucket prompt lengths,
    #: docs/serving.md follow-ups). The LRU cap bounds host memory at
    #: O(cap) retained executables instead of O(distinct lengths).
    MAX_PREFILL_PROGRAMS = 64

    def _prefill_fn(self, q_len: int, t0: int, final: bool):
        """Jitted prefill unit. A whole-prompt chunk (t0=0, final) is
        the SAME one-pass ``prefill`` program ``generate()`` runs, so
        staging caches match generate's bit-for-bit; interior chunks are
        ``prefill_chunk_step``. With a fixed ``prefill_chunk`` the
        interior chunks share ceil(max_len/chunk) programs; the ragged
        FINAL chunk is per-prompt-length (see MAX_PREFILL_PROGRAMS). A
        block-diffusion engine's prefill returns a third value, the
        ``routing_counts`` of the expert layers it ran."""
        key = (q_len, t0, final)
        fn = self._prefill_fns.pop(key, None)
        if fn is None:
            module = self.module
            if self.block_len is not None:
                # block diffusion: never a head (``final`` is False), the
                # expert layers drop-free as in the passes, and what they
                # routed beside the cache
                dispatched = self._moe_dispatched

                def f(params, state, cache, chunk):
                    routing = [] if dispatched else None
                    _, cache = prefill_chunk_step(
                        module, params, state, cache, chunk, t0,
                        final=final, routing=routing)
                    return None, cache, routing_counts(routing or [])
            elif self._count_routing:
                # as below, with what the expert layers routed beside
                # the logits and the cache
                def f(params, state, cache, chunk):
                    routing = []
                    if t0 == 0 and final:
                        logits, cache = prefill(module, params, state,
                                                cache, chunk, routing)
                    else:
                        logits, cache = prefill_chunk_step(
                            module, params, state, cache, chunk, t0,
                            final=final, routing=routing)
                    return logits, cache, routing_counts(routing)
            elif t0 == 0 and final:
                def f(params, state, cache, chunk):
                    return prefill(module, params, state, cache, chunk)
            else:
                def f(params, state, cache, chunk):
                    return prefill_chunk_step(module, params, state,
                                              cache, chunk, t0,
                                              final=final)
            # EP models shard_map-wrap here too: prefill runs the MoE
            # layers' own apply, which psums over the expert axis
            fn = self._jit_serving(f, 4, "prefill")
        # re-insert at the back: dict order is the LRU order
        self._prefill_fns[key] = fn
        while len(self._prefill_fns) > self.MAX_PREFILL_PROGRAMS:
            self._prefill_fns.pop(next(iter(self._prefill_fns)))
        return fn

    def _sample_first_fn(self):
        """First-token sampler from prefill logits — mirrors generate's
        ``rng, sub = split(rng)`` order so a request's key stream does
        not depend on engine scheduling."""
        if self._first_fn is None:
            @jax.jit
            def serving_sample_first(logits, temp, topk, topp, rng):
                rng, sub = jax.random.split(rng)
                with jax.named_scope("sample"):
                    tok = _sample_vec(logits, temp[None], topk[None],
                                      topp[None], sub)
                return tok[0], rng

            self._first_fn = serving_sample_first
        return self._first_fn

    # --- paged admission / page budget ------------------------------------

    def _admit(self) -> List[Request]:
        """Admission for this iteration, cost-aware: the
        highest-priority queued request admits
        while a slot AND its context's page budget are available
        (prefix-cache hits cost nothing: shared pages are reused, not
        allocated); when the budget is short, a strictly-higher-
        priority arrival preempts lower-priority decoding streams."""
        admitted: List[Request] = []
        sch = self.scheduler
        while sch.free_slots:
            req = sch.peek()
            if req is None:
                break
            plan = self._page_plan(req)
            if plan is not None:
                sch.admit_one(req)
                self._comp_ver += 1
                self._apply_page_plan(req, plan)
                if self._ssm_layers:
                    # the slot's state rows start from zero (a
                    # previous occupant's, or garbage, are there)
                    with obs.span("serving.ssm.reset"):
                        self.pool.reset_state(req.slot)
                    self.ssm_counters["ssm_state_resets"] += 1
                admitted.append(req)
                continue
            if not self._preempt_victim(beneficiary=req,
                                        strict_priority=True):
                break
        return admitted

    def _context_of(self, req: Request) -> np.ndarray:
        """The tokens whose KV must be in cache before ``req`` (re)joins
        decode. One token a step: ``Request.context_tokens``. Block
        diffusion: the WHOLE blocks of prompt + generated (every shown
        block included: a block is shown when it is whole, a pass
        before its K/V are committed); what is left over, under a
        block of the prompt, opens the first generated block."""
        if self.block_len is None:
            return req.context_tokens
        toks = req.tokens
        return toks[:len(toks) // self.block_len * self.block_len]

    def _page_plan(self, req: Request) -> Optional[Dict]:
        """Fund ``req``'s (re)admission from the page budget: prefix-
        match its context, reclaim cache-only pages if the private
        remainder does not fit, allocate. None when it cannot be
        funded (the caller may preempt and retry next iteration).

        Matched pages are incref'd HERE, before any reclaim — the
        reclaim sweep frees cache-only (ref == 1) pages and must never
        eat the chain this very plan is about to use.

        A preemption victim whose pages were SWAPPED OUT (offload PR)
        is funded differently: it needs exactly its swapped page
        count back (no prefix match, no +1 growth page — the snapshot
        already covers the next write), and its resume is an H2D copy
        instead of a re-prefill."""
        pool = self.pool
        swap = getattr(req, "_swap", None)
        if swap is not None:
            n = len(swap["host"])
            need = n + self._moe_admit_extra(req, n)
            if pool.free_pages < need and self.prefix is not None:
                deficit = need - pool.free_pages
                if self.prefix.evictable_pages() >= deficit:
                    self.prefix.reclaim(deficit)
            if pool.free_pages < need:
                return None
            priv = [pool.alloc_page() for _ in range(n)]
            return {"restore": True, "full": [], "priv": priv,
                    "shared_len": 0, "donor": None}
        toks = self._context_of(req)
        # context + 1: the first decode write (position len(toks))
        # must land on an allocated page (block diffusion: the page
        # holds the whole first block, page_len % block_len == 0)
        n_logical = pool.pages_for(len(toks) + 1)
        aux_load, reach = [{} for _ in pool.aux], 0
        if self.prefix is not None and len(toks):
            if pool.aux:
                full, shared_len, donor, aux_load, reach = \
                    self.prefix.match_groups(toks)
            else:
                full, shared_len, donor = self._match_prefix(toks)
        else:
            full, shared_len, donor = [], 0, None
        for pid in full:
            pool.incref(pid)             # the slot's hold, owned early
        if donor is not None:
            pool.incref(donor)           # held until loaded to staging
        self._hold_aux(aux_load)         # held until loaded to staging
        n_private = n_logical - len(full)
        # MoE-aware admission cost: under concentrated routing the
        # free-page budget must also show headroom pages (never
        # allocated — just required free) before this stream admits
        need = n_private + self._moe_admit_extra(req, n_logical)
        if pool.free_pages < need and self.prefix is not None:
            deficit = need - pool.free_pages
            # reclaim ONLY when it can actually close the gap: an
            # unfundable admission must not drain the reusable prefix
            # cache for nothing (it would strip sharing from every
            # later same-template request while the head stays queued)
            if self.prefix.evictable_pages() >= deficit:
                self.prefix.reclaim(deficit)
        # each window group's ring: the pages the window of the first
        # decode write reaches (``WindowPages.span``)
        rings = []
        if pool.free_pages >= need:
            for g, grp in enumerate(pool.aux):
                got = self._aux_alloc(g, len(grp.span(len(toks))))
                if got is None:
                    break
                rings.append(got)
        if pool.free_pages < need or len(rings) < len(pool.aux):
            for pid in full:
                pool.decref(pid)
            if donor is not None:
                pool.decref(donor)
            for grp, pages, ring in itertools.zip_longest(
                    pool.aux, aux_load, rings, fillvalue=()):
                for pid in (*pages.values(), *ring):
                    grp.decref(pid)
            return None
        priv = [pool.alloc_page() for _ in range(n_private)]
        plan = {"full": full, "priv": priv, "shared_len": shared_len,
                "donor": donor}
        if pool.aux:
            plan.update(aux_load=aux_load, aux_ring=rings, reach=reach)
        return plan

    def _match_prefix(self, toks):
        """``PrefixCache.match`` with the engine's partial-match
        granularity applied: the copy-on-write match length rounds
        down to a multiple of ``prefix_granularity`` (0 drops the
        donor), bounding how many distinct residual-chunk shapes —
        each an inline compile on first sight — sharing can mint."""
        full, shared_len, donor = self.prefix.match(toks)
        g = self._prefix_granularity
        if donor is not None and g > 1:
            base = len(full) * self.pool.page_len
            m = ((shared_len - base) // g) * g
            shared_len = base + m
            if m == 0:
                donor = None
        return full, shared_len, donor

    def _rematch_at_prefill(self, req: Request) -> None:
        """Prefix pages REGISTERED between this request's admission and
        its prefill turn (requests ahead of it in the single prefill
        stream — the common case in a burst of same-template arrivals)
        are adopted late: re-match, swap the private pages the longer
        chain now covers for the shared ones, return the privates to
        the budget."""
        pool = self.pool
        toks = self._context_of(req)
        if pool.aux:
            full, shared_len, donor, aux_load, reach = \
                self.prefix.match_groups(toks)
            req._reach = max(reach, getattr(req, "_reach", 0))
        else:
            full, shared_len, donor = self._match_prefix(toks)
        if shared_len <= getattr(req, "_shared_len", 0):
            return
        if pool.aux:
            self._drop_aux_holds(req)
            self._hold_aux(aux_load)
            req._aux_load = aux_load
        old_full = getattr(req, "_n_shared_full", 0)
        slot = req.slot
        for j in range(old_full, len(full)):
            old = int(pool.tables[slot, j])
            pool.incref(full[j])
            pool.assign(slot, j, full[j])
            pool.decref(old)                  # private page, freed
        old_donor = getattr(req, "_donor_ref", None)
        if old_donor is not None:
            pool.decref(old_donor)
            req._donor_ref = None
        if donor is not None:
            pool.incref(donor)
            req._donor_ref = donor
        req._shared_len = shared_len
        req._n_shared_full = len(full)
        req._load_pages = list(full) + (
            [donor] if donor is not None else [])

    def _apply_page_plan(self, req: Request, plan: Dict) -> None:
        slot = req.slot
        pool = self.pool
        if plan.get("restore"):
            # swap resume: the fresh pages land on the SAME logical
            # indices the snapshot captured — the table restore half
            # of the swap-in (the H2D payload copy runs at the
            # request's prefill turn, _advance_prefill). Prefix-
            # resident pages re-link in place: the snapshot's refcount
            # hold becomes the slot's table hold (released like any
            # slot page at the next release_slot)
            for lp, pid in zip(req._swap["logical"], plan["priv"]):
                pool.assign(slot, int(lp), pid)
            for lp, pid in req._swap.get("shared", ()):
                pool.assign(slot, int(lp), int(pid))
            req._shared_len = 0
            req._n_shared_full = 0
            req._load_pages = []
            req._donor_ref = None
            return
        for j, pid in enumerate(plan["full"]):
            pool.assign(slot, j, pid)    # ref taken in _page_plan
        for i, pid in enumerate(plan["priv"]):
            pool.assign(slot, len(plan["full"]) + i, pid)
        req._shared_len = plan["shared_len"]
        req._n_shared_full = len(plan["full"])
        # pages to materialize into the staging cache before prefill:
        # the full shared chain plus the copy-on-write donor (whose
        # temporary ref drops once the load has happened)
        req._donor_ref = plan["donor"]
        req._load_pages = list(plan["full"]) + (
            [plan["donor"]] if plan["donor"] is not None else [])
        if pool.aux:
            n_ctx = len(self._context_of(req))
            for grp, ring in zip(pool.aux, plan["aux_ring"]):
                for lp, pid in zip(grp.span(n_ctx), ring):
                    grp.assign(slot, lp, pid)
            req._aux_load = plan["aux_load"]
            req._reach = plan["reach"]

    # --- window page groups (kv_pool.WindowPages) --------------------------

    def _aux_alloc(self, g: int, n: int) -> Optional[List[int]]:
        """``n`` pages of window group ``g``, giving back pages of it
        that only the prefix cache holds where the free ones do not
        reach; None (and nothing taken) where that does not either."""
        grp = self.pool.aux[g]
        short = n - grp.free_pages
        if short > 0 and self.prefix is not None \
                and self.prefix.aux_evictable(g) >= short:
            for _ in range(short):
                self.prefix.aux_evict_one(g)
        if grp.free_pages < n:
            return None
        return [grp.alloc_page() for _ in range(n)]

    def _hold_aux(self, aux_load) -> None:
        """Take a hold on the window-group pages a prefix hit will load
        (per group ``{logical page: page id}``), so that nothing gives
        them back before the load."""
        for grp, pages in zip(self.pool.aux, aux_load):
            for pid in pages.values():
                grp.incref(pid)

    def _drop_aux_holds(self, req: Request) -> None:
        """Release the holds a page plan took on the window-group pages
        a prefix hit loads (taken so nothing gives them back first)."""
        for grp, pages in zip(self.pool.aux,
                              getattr(req, "_aux_load", None) or ()):
            for pid in pages.values():
                grp.decref(pid)
        req._aux_load = None

    def _aux_insert_plan(self, req: Request, p_len: int):
        """What a finished prefill writes into the window groups, per
        group: ``{logical page: page id}`` to write from staging (the
        slot's ring, as far as the context fills it), the pages to
        register with the prefix cache, and pages taken here for that
        alone. Registered are the pages one window behind the boundary
        at which this prompt stopped matching the trie, and only where
        its own hit was cut short of it for lack of them
        (``PrefixCache``'s class doc): the request that first finds
        prompts parting there pays one prefill and leaves them."""
        pl = self.page_len
        cut = getattr(req, "_shared_len", 0) // pl
        reach = getattr(req, "_reach", 0)
        n_filled = self.pool.pages_for(p_len)
        writes, rows, taken = [], [], []
        for g, grp in enumerate(self.pool.aux):
            held = grp.slot_pages(req.slot)
            write = {lp: pid for lp, pid in held.items() if lp < n_filled}
            row, mine = {}, []
            if self.prefix is not None and cut < reach:
                for lp in range(grp.first_needed(reach * pl), reach):
                    pid = held.get(lp)
                    if pid is None:
                        got = self._aux_alloc(g, 1)
                        if got is None:
                            continue
                        pid = got[0]
                        mine.append(pid)
                        write[lp] = pid
                    row[lp] = pid
            writes.append(write)
            rows.append(row)
            taken.append(mine)
        return writes, rows, taken

    def _ensure_window_pages(self) -> None:
        """Before a decode step, per window group: every running slot
        gives back the pages that lie wholly behind the window of the
        position it writes next, and one whose write crosses into a new
        logical page gets that page (free, else given back by the
        prefix cache, else by preempting as ``_ensure_decode_pages``
        does)."""
        running = self.scheduler.running
        slots = np.fromiter(running.keys(), np.int64, len(running))
        t = np.minimum(self._t[slots].astype(np.int64),
                       self.pool.pages_per_slot * self.page_len - 1)
        for g, grp in enumerate(self.pool.aux):
            held = grp.logical[slots]
            first = np.maximum(t - grp.window + 1, 0) // grp.page_len
            stale = ((held >= 0) & (held < first[:, None])).any(axis=1)
            for slot, at in zip(slots[stale].tolist(), t[stale].tolist()):
                grp.release_behind(slot, at)
            missing = ~(grp.logical[slots]
                        == (t // grp.page_len)[:, None]).any(axis=1)
            if not missing.any():
                continue
            at = dict(zip(slots.tolist(), t.tolist()))
            for req in sorted((running[s] for s in slots[missing].tolist()),
                              key=lambda r: (r.priority, r.rid)):
                while req.state is RequestState.DECODING:
                    got = self._aux_alloc(g, 1)
                    if got is not None:
                        grp.assign(req.slot,
                                   at[req.slot] // grp.page_len, got[0])
                        break
                    if not self._preempt_victim(beneficiary=req,
                                                strict_priority=False):
                        raise RuntimeError(
                            f"page group {grp.name!r} exhausted: no free "
                            "page, nothing the prefix cache can give "
                            "back, no preemptable stream")

    def _preempt_victim(self, beneficiary: Request,
                        strict_priority: bool) -> bool:
        """Preempt ONE admitted request (decoding or mid-prefill —
        both hold budget pages) to free pages for ``beneficiary``:
        the lowest-priority, youngest victim. ``strict_priority``
        (admission path) only sacrifices strictly lower-priority
        streams. The decode-growth path also preempts within the
        class (youngest first) and INCLUDES the beneficiary itself:
        when the beneficiary is the worst-ranked stream alive, it is
        the one evicted — growing it at a higher-priority neighbour's
        expense would invert the priority the scheduler promises.
        Either way the best-ranked stream is never a victim, so it
        runs to completion — the progress guarantee that makes
        preemption deadlock-free."""
        victim = None
        candidates = list(self.scheduler.running.values()) \
            + list(self.scheduler.prefilling)
        for r in candidates:
            if strict_priority and (
                    r is beneficiary
                    or r.priority <= beneficiary.priority):
                continue
            if victim is None \
                    or (r.priority, r.rid) > (victim.priority, victim.rid):
                victim = r
        if victim is None:
            return False
        self._preempt(victim)
        return True

    def _preempt(self, victim: Request) -> None:
        """Evict an admitted request's pages back to the queue. Its
        generated tokens stay (the re-prefill context); a decoding
        victim's per-slot sampling key is snapshotted so a sampled
        stream resumes EXACTLY where it left off (schedule-independent
        draws); a prefilling victim keeps its submit-time key (its
        first token has not been sampled yet)."""
        # the snapshot below (generated tokens, sampling key) must see
        # the in-flight step's outputs — drain the pipeline first
        self._flush_pending()
        if victim.state in TERMINAL_STATES:
            return               # the flush finished (or expired) it
        slot = victim.slot
        if victim.state is RequestState.DECODING:
            victim.rng = np.array(self._keys[slot])
        # host KV offload (offload PR): a DECODING victim's pages swap
        # out D2H before release, so resume is an H2D page copy +
        # table restore instead of a full context re-prefill — byte-
        # identical (the pages move, nothing recomputes). Prefilling
        # victims hold no written pool pages (prefill writes staging);
        # they keep the re-prefill path. Falls through silently when
        # the host tier is off or full — the swap is an accelerator,
        # never a correctness dependency.
        #
        # PREFIX-AWARE snapshot (tree-speculation PR satellite,
        # closing the PR-17 trade-off): pages still RESIDENT in the
        # prefix cache are not copied at all — the snapshot takes a
        # refcount hold instead (pinning them against spill/drop: both
        # need ref == 1) and resume re-links them into the table, the
        # hold becoming the slot's. Only the private remainder moves
        # D2H, so a shared-prefix-heavy victim swaps a fraction of its
        # context and duplicates nothing on resume.
        swapped = 0
        if victim.state is RequestState.DECODING \
                and self.pool.host_cache is not None:
            row = self.pool.tables[slot]
            logical = np.where(row < self.pool.num_pages)[0]
            shared, priv = [], []
            for lp in logical.tolist():
                pid = int(row[lp])
                if self.prefix is not None and self.prefix.resident(pid):
                    shared.append((lp, pid))
                else:
                    priv.append(lp)
            hids = (self.pool.offload_pages(row[priv].tolist())
                    if priv else [])
            if hids is not None:
                for _lp, pid in shared:
                    self.pool.incref(pid)       # the snapshot's hold
                victim._swap = {"host": hids, "logical": priv,
                                "shared": shared,
                                "t": int(self._t[slot])}
                swapped = len(hids)
                self.tracer.on_swap_out(victim.rid, swapped)
        self.scheduler.preempt(victim)
        self._comp_ver += 1
        self._chain_dirty[slot] = True
        if self._draft is not None:
            self._draft.end_slot(slot)   # draft KV freed with the slot
        freed = self.pool.release_slot(slot)
        self._t[slot] = self.max_len          # sentinel: slot inert
        if getattr(victim, "_donor_ref", None) is not None:
            # admitted with a copy-on-write donor hold that prefill
            # never consumed
            self.pool.decref(victim._donor_ref)
            victim._donor_ref = None
        self._drop_aux_holds(victim)
        victim._shared_len = 0
        victim._n_shared_full = 0
        victim._load_pages = []
        self.metrics.record_preemption(victim.rid)
        self.tracer.on_preempt(victim.rid, len(victim.generated))
        if self.recorder.enabled:
            self.recorder.record(
                "serving.preempted", engine=self.engine_id,
                rid=victim.rid, slot=slot,
                n_generated=len(victim.generated), pages_freed=freed,
                pages_free=self.pool.free_pages,
                pages_swapped=swapped)

    def _ensure_decode_pages(self, lookahead=None) -> None:
        """Before a decode step: every running slot whose next write
        position crosses into an unallocated logical page gets one —
        from the free list, then by reclaiming cache-only prefix
        pages, then by preempting the youngest lowest-priority OTHER
        stream. Serviced oldest-highest-priority first, so pressure
        lands on the back of the line.

        ``lookahead`` ([S] ints, speculative iterations): the verify
        step also writes positions ``t+1 .. t+lookahead[slot]``, so
        every logical page under that span must be allocated — a
        dropped write there would silently corrupt an ACCEPTED draft's
        KV. The engine passes ``min(spec_k, remaining_budget - 1)``
        per speculating slot: pages are only ever demanded for
        positions the slot could actually consume (verify writes
        beyond that may drop — their candidates are discarded
        host-side)."""
        pool = self.pool
        running = self.scheduler.running
        if not running:
            return
        if pool.aux:
            self._ensure_window_pages()
            if not running:
                return
        # steady-state fast path (zero-bubble PR): ONE vectorized scan
        # over the numpy table/position mirrors decides "no growth
        # needed" — the common case — without the per-slot int() loop
        # that used to cost O(num_slots) Python per iteration
        slots = np.fromiter(running.keys(), np.int64, len(running))
        t = self._t[slots].astype(np.int64)
        hi = t if lookahead is None else t + lookahead[slots]
        hi = np.minimum(hi, pool.pages_per_slot * pool.page_len - 1)
        lp = pool.page_index
        span = (lp >= (t // pool.page_len)[:, None]) \
            & (lp <= (hi // pool.page_len)[:, None])
        if not (span & (pool.tables[slots] >= pool.num_pages)).any():
            return
        by_rank = sorted(running.values(),
                         key=lambda r: (r.priority, r.rid))
        for req in by_rank:
            if req.state is not RequestState.DECODING:
                continue                      # preempted this pass
            slot = req.slot
            t = int(self._t[slot])
            hi = t if lookahead is None else t + int(lookahead[slot])
            hi = min(hi, pool.pages_per_slot * pool.page_len - 1)
            for lp in range(t // pool.page_len,
                            hi // pool.page_len + 1):
                if req.state is not RequestState.DECODING:
                    break                     # self-preempted below
                if pool.tables[slot, lp] < pool.num_pages:
                    continue                  # page already allocated
                while True:
                    pid = pool.alloc_page()
                    if pid is not None:
                        pool.assign(slot, lp, pid)
                        break
                    if self.prefix is not None \
                            and self.prefix.evict_one():
                        continue
                    if not self._preempt_victim(beneficiary=req,
                                                strict_priority=False):
                        raise RuntimeError(
                            "page pool exhausted: no free page, nothing "
                            "evictable, no preemptable stream (submit "
                            "validation should have prevented this)")
                    if req.state is not RequestState.DECODING:
                        break    # the beneficiary was the worst-ranked
                        #          stream and preempted ITSELF; its
                        #          pages are back in the budget

    def _fragmentation(self) -> float:
        """Wasted tail positions across live slots: 1 - used/allocated
        (an allocated page holds ``page_len`` positions; the slot uses
        ``t`` of them so far). 0 = perfectly packed."""
        pool = self.pool
        sch = self.scheduler
        used = alloc = 0
        if sch.running:
            # vector numpy over the table/position mirrors — no
            # per-slot python loop (zero-bubble PR)
            slots = np.fromiter(sch.running.keys(), np.int64,
                                len(sch.running))
            alloc += int((pool.tables[slots] < pool.num_pages).sum())
            used += int(self._t[slots].sum())
        if sch.prefilling:
            pslots = np.fromiter((r.slot for r in sch.prefilling),
                                 np.int64, len(sch.prefilling))
            alloc += int((pool.tables[pslots] < pool.num_pages).sum())
            used += sum(r.prefill_pos for r in sch.prefilling)
        if alloc == 0:
            return 0.0
        return max(0.0, 1.0 - used / (alloc * pool.page_len))

    # --- the scheduler iteration ------------------------------------------

    def step(self) -> List[Request]:
        """One iteration: expire deadlines, admit, advance ONE prefill
        chunk, run one decode step over all slots. Returns requests that
        reached a terminal state during this iteration (FINISHED,
        TIMED_OUT or CANCELLED — check ``req.state``).

        What a step yields a stream: one token (plain decode; with
        ``overlap`` seen one iteration late), ``fuse_steps`` tokens (a
        fused window), 1..k+1 (a speculative verify), or — a
        block-causal model — nothing for ``denoising_steps - 1`` passes
        and then a whole block of ``block_len`` tokens at once
        (``_block_step``): ``Request.generated`` grows by whole blocks.

        Error isolation: an exception while advancing ONE request's
        prefill (a poisoned prompt, an injected ``serving.prefill``
        fault) cancels that request and recycles its slot; in-flight
        decode streams are untouched and keep emitting token-identical
        output. A decode-step error is batch-wide and not attributable
        to one request, so it propagates — but it is raised before any
        engine state mutates, so ``step()`` can simply be called again
        (the failed iteration retries wholesale).

        Both hold for errors raised BEFORE a program is dispatched
        (tracing, argument checks, the ``serving.prefill`` /
        ``serving.decode`` fault points): every program donates its
        cache, so one that fails once dispatched has consumed it. A
        consumed staging cache is rebuilt (it held one request's
        prefill, and that request is the one cancelled); a consumed
        pool held every stream's KV and cannot be, so ``step()`` raises
        a ``RuntimeError`` that says so rather than serve from deleted
        buffers."""
        with obs.span("serving.step", step=self._iters):
            finished: List[Request] = []
            with obs.span("serving.admit"):
                if self._finish_buf:
                    # terminals produced by out-of-band pipeline flushes
                    # (cancel, preemption, metrics swap) since the last step
                    finished.extend(self._finish_buf)
                    self._finish_buf.clear()
                self._expire_deadlines(finished)
                admitted = self._admit()
                # flight-recorder ring entry (composition-cached, window
                # cadence in steady state — see _record_iteration). Paged
                # engines add the free-page count: an admission stall in a
                # post-mortem dump reads directly as "queue grew while pages
                # sat at N" (budget starvation) vs "pages free, slots full"
                self._record_iteration(admitted)

            req = self.scheduler.next_prefill()
            if req is not None:
                with self.metrics.timer.phase("prefill"), \
                        obs.span("serving.prefill"):
                    try:
                        self._advance_prefill(req, finished)
                    except Exception as e:
                        self._poison(req, e, finished)

            running = self.scheduler.running
            if running:
                with self.metrics.timer.phase("decode"), \
                        obs.span("serving.decode"):
                    try:
                        self._advance_decode(finished)
                    except Exception as e:
                        self._recover_donated(e)
                        raise

            with obs.span("serving.flush"):
                # per-iteration samples land in the deferred buffers; the live
                # window sees them on the host-window cadence (every iteration
                # when overlap is off) and whenever the engine drains idle
                self._iter_buf.append((self.scheduler.queue_depth,
                                       self.scheduler.occupied))
                self._iters += 1
                if self._iters % self._host_window == 0 \
                        or not self.scheduler.pending:
                    self._flush_host_window()
                    if self.timeseries is not None:
                        # piggybacks on the flush cadence just paid: pure
                        # host-side registry reads, zero added device syncs
                        self.timeseries.maybe_sample(iteration=self._iters)
                if self._iters % self._RECOMPILE_CHECK_EVERY == 0:
                    self._recompile.check()
                if self.slo is not None \
                        and self._iters % self._SLO_EVAL_EVERY == 0:
                    self._flush_host_window()
                    self.slo.evaluate(self.metrics)
                if self._finish_buf:
                    # a mid-iteration flush (preemption funding, deadline
                    # sweep) finished requests: return them from THIS step
                    finished.extend(self._finish_buf)
                    self._finish_buf.clear()
            return finished

    def run(self, max_steps: Optional[int] = None,
            on_degraded: str = "raise") -> Dict[int, np.ndarray]:
        """Drive ``step()`` until every submitted request reaches a
        terminal state; returns ``{rid: tokens}`` for requests drained
        during this call.

        A request that ends TIMED_OUT or CANCELLED raises
        ``DegradedRequest`` (default) — its empty/partial token array
        must not be indistinguishable from a finished one in the plain
        tokens dict. Pass ``on_degraded="return"`` to include partial
        tokens instead, or drive ``step()`` directly to observe
        per-request terminal states."""
        if on_degraded not in ("raise", "return"):
            raise ValueError(
                f"on_degraded must be 'raise' or 'return', "
                f"got {on_degraded!r}")
        out: Dict[int, np.ndarray] = {}
        steps = 0
        while self.scheduler.pending:
            for r in self.step():
                if r.state is not RequestState.FINISHED \
                        and on_degraded == "raise":
                    # crash forensics: snapshot the ring before the
                    # degraded drain surfaces to the caller
                    self.recorder.auto_dump(
                        f"degraded_request:{r.state.value}")
                    raise DegradedRequest(r)
                out[r.rid] = r.tokens
            steps += 1
            if max_steps is not None and steps >= max_steps \
                    and self.scheduler.pending:
                raise RuntimeError(
                    f"engine made no full drain in {max_steps} steps "
                    f"(queue={self.scheduler.queue_depth}, "
                    f"occupied={self.scheduler.occupied})")
        return out

    # --- degradation paths ------------------------------------------------

    def _expire_deadlines(self, finished: List[Request]) -> None:
        """Terminate every in-flight request whose ``deadline_s`` has
        expired (engine clock), freeing its slot for queued work. A
        timed-out request keeps the tokens it generated so far."""
        now_ = self.metrics.clock()
        expired = [r for r in self._requests.values()
                   if r.deadline_s is not None
                   and now_ - r.submit_t >= r.deadline_s]
        if not expired:
            return
        # the expiring requests' in-flight tokens must land first (a
        # timed-out request keeps everything it generated) — and the
        # flush may FINISH one of them, beating the deadline
        self._flush_pending(finished)
        for r in expired:
            if r.rid not in self._requests:
                continue                 # finished during the flush
            self._terminate(r, RequestState.TIMED_OUT, finished)
            self.metrics.record_timeout(r.rid)

    def _poison(self, req: Request, err: Exception,
                finished: List[Request]) -> None:
        """Per-request work failed: quarantine THIS request (CANCELLED,
        ``req.error`` holds the cause), recycle its slot, leave every
        other stream untouched."""
        if req.state in TERMINAL_STATES:
            raise err    # already terminal — nothing to isolate
        self._recover_donated(err)
        self._terminate(req, RequestState.CANCELLED, finished, error=err)
        self.metrics.record_cancelled(req.rid)

    def _recover_donated(self, err: Exception) -> None:
        """After a failed program: a donated cache that the failure
        consumed (dispatched, never rebound) reads ``is_deleted()``.
        The staging cache is rebuilt; a lost pool is fatal."""
        def lost(cache):
            return any(isinstance(x, jax.Array) and x.is_deleted()
                       for x in jax.tree_util.tree_leaves(cache))
        if lost(self.pool.cache):
            raise RuntimeError(
                "a serving program failed after it was dispatched and "
                "took the donated KV pool with it: every stream's cache "
                "is gone, build a new engine") from err
        if lost(self._staging):
            self._staging = self.pool.make_request_cache()

    def cancel(self, rid: int) -> Request:
        """Cancel an in-flight request by id (client disconnect etc.);
        returns the terminal Request (evicted from the engine)."""
        req = self._requests[rid]
        # land the in-flight tokens first (partial output is part of
        # the cancel contract); the flush may FINISH the request, in
        # which case the terminal FINISHED record wins
        self._flush_pending()
        if rid not in self._requests:
            for i, r in enumerate(self._finish_buf):
                if r.rid == rid:
                    return self._finish_buf.pop(i)
            raise KeyError(rid)          # unreachable: flush owns it
        out: List[Request] = []
        self._terminate(req, RequestState.CANCELLED, out)
        self.metrics.record_cancelled(rid)
        return out[0]

    # --- replica handoff (serving-router PR) ------------------------------

    def transfer_out(self, rid: int) -> Optional[Request]:
        """Detach a LIVE request from this engine so another engine can
        ``transfer_in`` it — the serving router's handoff primitive
        (prefill→decode disaggregation, drain rebalancing). An admitted
        request first leaves through the proven preempt path (pipeline
        drained, pages freed, sampling key snapshotted on ``req.rng``),
        then exits the queue and the engine entirely; a queued request
        just leaves the queue. Returns the detached ``Request``
        (QUEUED, slotless — ready for ``transfer_in``), or None when
        draining the pipeline FINISHED the request instead (it will be
        returned by this engine's next ``step()`` like any terminal)."""
        req = self._requests[rid]
        if req.state in (RequestState.PREFILLING,
                         RequestState.DECODING):
            self._preempt(req)
            if req.state in TERMINAL_STATES:
                return None          # the pipeline flush finished it
        # any swap record — from the preempt above OR from an
        # earlier preemption while the request sat QUEUED — holds
        # pages in THIS engine's host pool (and refcount holds on
        # prefix-resident pages), which a foreign engine cannot use:
        # drop them so the handoff rides the re-prefill resume (page
        # SHIPPING over a transport is the router follow-up this
        # machinery is built for; docs/serving.md §Router)
        self._drop_swap(req)
        if req.state is not RequestState.QUEUED:
            raise RuntimeError(
                f"cannot transfer request {rid} in state "
                f"{req.state.value!r}")
        self.scheduler.waiting.remove(req)
        del self._requests[rid]
        self.metrics.record_transfer(rid)
        # ticks precede terminals (the _finish rule): the deferred
        # host-window buffers may hold this request's decode ticks,
        # and on_terminal retires its timeline — flush first or the
        # transferred timeline undercounts decode_iters
        self._flush_host_window()
        self.tracer.on_terminal(rid, "transferred", len(req.generated))
        if self.recorder.enabled:
            self.recorder.record(
                "serving.transferred", engine=self.engine_id, rid=rid,
                n_generated=len(req.generated))
        return req

    def transfer_in(self, req: Request) -> int:
        """Admit a request detached from another engine
        (``transfer_out``) or reconstructed by the router after a
        replica death. Re-entry is exactly the preemption-resume
        contract: the context (``prompt + generated[:-1]``) re-prefills
        HEAD-LESS here and decode continues from ``req.rng`` — token-
        identically (byte-identically for sampled streams) to an
        uninterrupted single-engine run. Mints a fresh LOCAL rid
        (returned; the router keeps the stable fleet-wide id). A
        ``deadline_s`` restarts on this engine's clock — cross-replica
        deadline budgets are the router's concern. Raises
        ``AdmissionRejected`` when this engine's bounded queue is full
        (the router then tries the next replica)."""
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("request prompt is empty")
        if prompt.size + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds the slot capacity "
                f"max_len={self.max_len}")
        worst = self.pool.pages_for(prompt.size + req.max_new_tokens)
        if worst > self.pool.num_pages:
            raise ValueError(
                f"request needs up to {worst} pages but the pool "
                f"holds {self.pool.num_pages}")
        req.prompt = prompt
        req.rid = next(self._rid)
        req.slot = None
        req.prefill_pos = 0
        req.error = None
        # scrub SOURCE-engine-local bookkeeping: shared-prefix lengths
        # and page ids refer to the other engine's pool — stale values
        # here would make this engine's prefill load foreign page ids
        req._shared_len = 0
        req._n_shared_full = 0
        req._load_pages = []
        req._donor_ref = None
        req._aux_load = None
        # a swap record refers to the SOURCE engine's host pool
        # (transfer_out frees it; a router death-failover request may
        # still carry one from its dead engine) — restoring it here
        # would read THIS pool's unrelated host rows
        req._swap = None
        if req.rng is None:
            req.rng = jax.random.PRNGKey(req.seed)
        try:
            self.scheduler.submit(req)
        except AdmissionRejected:
            self.metrics.record_rejected()
            self.tracer.on_reject()
            self.recorder.note_rejection(
                rid=req.rid, engine=self.engine_id,
                queue_depth=self.scheduler.queue_depth,
                max_queue=self.scheduler.max_queue)
            raise
        self._requests[req.rid] = req
        req.submit_t = self.metrics.clock()
        self.metrics.record_submit(req.rid)
        self.tracer.on_submit(req.rid, self.scheduler.queue_depth)
        return req.rid

    def _terminate(self, req: Request, state, finished: List[Request],
                   error: Optional[BaseException] = None) -> None:
        """Shared terminal transition for the degradation paths: move
        the request out of the scheduler (freeing its slot when it holds
        one), park the slot's decode vector on the inert sentinel, and
        evict the request from the engine — the caller owns it from
        here, exactly like ``_finish``."""
        had_slot = req.state in (RequestState.PREFILLING,
                                 RequestState.DECODING)
        self.scheduler.cancel(req, state)
        self._comp_ver += 1
        if had_slot:
            self._t[req.slot] = self.max_len   # sentinel: slot inert
            self._chain_dirty[req.slot] = True
            if self._draft is not None:
                self._draft.end_slot(req.slot)
            self.pool.release_slot(req.slot)
        if getattr(req, "_donor_ref", None) is not None:
            # admitted with a copy-on-write donor hold but terminated
            # before its prefill turn consumed it
            self.pool.decref(req._donor_ref)
            req._donor_ref = None
        self._drop_aux_holds(req)
        # preempted-and-swapped but terminated (deadline, cancel)
        # before the swap-in consumed the host copy / shared holds
        self._drop_swap(req)
        req.error = error
        self.tracer.on_terminal(req.rid, state.value,
                                len(req.generated))
        del self._requests[req.rid]
        finished.append(req)

    def health(self) -> Dict:
        """Readiness snapshot for load balancers / probes, built on the
        unified ``obs.telemetry_snapshot()``: is the engine accepting
        work, how deep is the queue, and the degradation tally of the
        CURRENT metrics window. ``status`` is ``"ok"`` while admission
        is open, ``"saturated"`` once the bounded queue is full (a
        probe should stop routing new traffic here until it drains),
        and ``"degraded"`` while accepting but in breach of a declared
        SLO (``slo=`` objectives; the principled load-shed/reroute
        trigger — a probe keeps the instance but weights traffic
        away). The ``slo`` key carries the freshly evaluated
        per-objective status (None without objectives)."""
        self._flush_host_window()    # deferred samples land first
        sch = self.scheduler
        accepting = (sch.max_queue is None
                     or sch.queue_depth < sch.max_queue)
        m = self.metrics
        # record=False: a probe is a READ — it must not append to the
        # SLO history, restamp gauges or count breach transitions, or
        # the numbers would depend on how often a balancer polls
        slo_status = (None if self.slo is None
                      else self.slo.evaluate(m, record=False))
        breaching = bool(slo_status) and any(
            st["breach"] for st in slo_status.values())
        status = ("saturated" if not accepting
                  else "degraded" if breaching else "ok")
        out = {
            "status": status,
            "accepting": accepting,
            "slo": slo_status,
            "queue_depth": sch.queue_depth,
            "max_queue": sch.max_queue,
            "slots": {"total": self.num_slots, "occupied": sch.occupied,
                      "free": self.num_slots - sch.occupied},
            "requests": {"in_flight": len(self._requests),
                         "finished": m.requests_finished,
                         "rejected": m.requests_rejected,
                         "timed_out": m.requests_timed_out,
                         "cancelled": m.requests_cancelled,
                         "preempted": m.requests_preempted},
            "telemetry": obs.telemetry_snapshot(),
            "programs": dict(self.program_paths),
            # what the watched programs that grew past their warm
            # size cost (program, seconds, cache, the span it fell
            # in); the process's totals: ["telemetry"]["compile"]
            "compiles": {"after_warm": self._recompile.after_warm()},
        }
        if self._moe:
            out["moe"] = {
                "decode": self.moe_decode,
                "layers": len(self._moe),
                "concentration": (None if self._moe_conc is None
                                  else round(self._moe_conc, 4)),
                "expert_parallel": (None if self._ep_mesh is None
                                    else int(self._ep_mesh.shape[
                                        self._ep_axis]))}
        if self.block_len is not None:
            passes = m.summary()["block_diffusion"] or {}
            out["block_diffusion"] = {
                "block_len": self.block_len,
                "denoising_steps": self.denoising_steps,
                "mask_token": self.mask_token,
                **{k: passes.get(k, 0) for k in (
                    "passes_overlapped", "slot_passes_discarded")}}
        pool = self.pool
        out["pages"] = {
            "total": pool.num_pages, "free": pool.free_pages,
            "shared": pool.shared_pages,
            "page_len": pool.page_len,
            "fragmentation": round(self._fragmentation(), 4),
            # host offload tier (additive key): None when off
            "host": (None if pool.host_cache is None else {
                "total": pool.host_pages,
                "free": pool.host_free_pages,
                "offloaded": pool.pages_offloaded,
                "restored": pool.pages_restored})}
        if pool.aux or pool.latent or pool.state_layers:
            out["kv_groups"] = self._kv_groups()
        out["prefix_cache"] = (
            None if self.prefix is None else {
                "nodes": len(self.prefix),
                "hit_rate": m.prefix_hit_rate})
        return out

    def decode_logits(self, decode_kernel: Optional[str] = None,
                      moe_decode: Optional[str] = None) -> np.ndarray:
        """Diagnostic read (``chip_smoke.py``, tests): the ``[S, V]``
        logits ONE plain decode step produces for every slot from the
        current paged cache. The stream state does not advance — the
        in-flight step is consumed first so the host owns every slot's
        input token, the pages that step would write are allocated as
        the next ``step()`` would allocate them, and the step's cache
        writes are dropped. ``decode_kernel`` (``"paged"``/``"off"``)
        and ``moe_decode`` (``"dispatched"``/``"dense"``) override this
        engine's own choices, which is the point: a kernel and its
        reference read the SAME state. Rows of free slots are
        garbage."""
        if self.block_len is not None:
            raise ValueError("decode_logits is a one-token step; a "
                             "block-causal model has none")
        self._flush_pending()
        self._ensure_decode_pages()
        fn = self._logits_fns.get((decode_kernel, moe_decode))
        if fn is None:
            pk = (self._paged_kernel if decode_kernel is None
                  else {"paged": True, "off": False}[decode_kernel])
            dispatched = (self._moe_dispatched if moe_decode is None
                          else bool(self._moe)
                          and moe_decode == "dispatched")
            module, page_len = self.module, self.page_len
            kw = {"live_len": self.max_len}
            if self._groups is not None:
                kw["groups"] = self._groups

            def f(params, state, cache, tok, t, tables):
                return decode_step_slots_paged(
                    module, params, state, cache, tok, t, tables,
                    page_len, paged_kernel=pk,
                    moe_dispatched=dispatched, **kw)[0]

            fn = self._logits_fns[decode_kernel, moe_decode] = \
                self._jit_serving(
                    f, 6, f"decode_logits[{decode_kernel},{moe_decode}]",
                    keep_attn=True, donate_cache=False)
        return np.asarray(fn(
            self._params, self._state, self.pool.cache,
            _snap(self._tok), _snap(self._t), self.pool.device_tables()))

    # --- internals --------------------------------------------------------

    def _advance_prefill(self, req: Request, finished: List[Request]):
        # chaos hook: an injected raise here exercises the
        # poisoned-request isolation in step(); an injected stall is the
        # slow-prefill scenario (queue grows, deadlines/shedding engage)
        faults.point("serving.prefill")
        swap = getattr(req, "_swap", None)
        if swap is not None:
            # swap-in resume (offload PR): the preemption snapshot
            # copies H2D into the pages _apply_page_plan already wired
            # into the table — token-identical BY CONSTRUCTION (the
            # exact cache bytes return; nothing is recomputed), where
            # the re-prefill path below is token-identical by the
            # chunked-prefill oracle. No prefill chunk ever runs: the
            # whole resume is this one copy + the vector restores.
            t0_ = self.metrics.clock()
            row = self.pool.tables[req.slot]
            dev = [int(row[int(lp)]) for lp in swap["logical"]]
            self.pool.restore_pages(swap["host"], dev)
            self.pool.free_host(swap["host"])
            req._swap = None
            s = req.slot
            self.scheduler.to_decoding(req)
            self._comp_ver += 1
            self._tok[s] = req.generated[-1]
            self._t[s] = swap["t"]
            self._temp[s] = req.temperature
            self._topk[s] = req.top_k
            self._topp[s] = req.top_p
            self._stop[s] = req.stop_token
            self._keys[s] = np.array(req.rng)
            self._chain_dirty[s] = True    # host owns the next input
            self._begin_draft(req, req.context_tokens)
            self.metrics.record_swap_resume(
                self.metrics.clock() - t0_, len(req.context_tokens))
            self.tracer.on_swap_in(req.rid, len(dev))
            self.tracer.on_resume(req.rid)
            return
        # context = prompt, or prompt + generated[:-1] after a
        # preemption (the resumable-prefill recompute path)
        toks = self._context_of(req)
        p_len = len(toks)
        blockdiff = self.block_len is not None
        if blockdiff and p_len == 0:
            # the prompt is under one block: nothing to prefill, the
            # whole of it opens the first generated block
            self._open_block(req, 0)
            return
        resume = bool(req.generated)
        if resume and req.prefill_pos == 0 \
                and getattr(req, "_resume_t0", None) is None:
            # re-prefill resume clock: first recompute chunk ->
            # rejoining the decode batch (the number the offload
            # bench's resume-latency rider compares against swap-in)
            req._resume_t0 = self.metrics.clock()
        with obs.span("serving.prefill.stage"):
            if req.prefill_pos == 0:
                if self.prefix is not None:
                    # pages registered since this request's admission plan
                    # (by requests ahead of it in the prefill stream) are
                    # adopted here — the burst-of-identical-prompts case
                    self._rematch_at_prefill(req)
                    self.metrics.record_prefix_lookup(
                        getattr(req, "_shared_len", 0), p_len)
                if getattr(req, "_shared_len", 0):
                    # prefix-cache hit: materialize the shared pages (and
                    # the copy-on-write donor) into the staging cache once,
                    # then skip straight to the first non-shared position —
                    # the shared tokens' prefill compute never runs
                    self._staging = self.pool.load_prefix(
                        self._staging, req._load_pages, req._shared_len,
                        aux_pages=getattr(req, "_aux_load", None))
                    req.prefill_pos = req._shared_len
                    self.tracer.on_prefix_hit(req.rid, req._shared_len)
                if getattr(req, "_donor_ref", None) is not None:
                    # the donor's content is in staging now; its hold
                    # (taken at planning so reclaim/eviction could not
                    # free it first) is no longer needed
                    self.pool.decref(req._donor_ref)
                    req._donor_ref = None
                self._drop_aux_holds(req)    # loaded (or nothing to load)
            t0 = req.prefill_pos
            chunk = self.prefill_chunk
            if chunk is None:
                q_len, final = p_len - t0, True
            else:
                q_len = min(chunk, p_len - t0)
                final = t0 + q_len >= p_len
            # a resume re-prefill never needs logits (its tokens are
            # already decided), so every chunk runs head-less; nor does
            # block diffusion (position i predicts token i: the first
            # denoising pass reads the block's own logits)
            fn = self._prefill_fn(q_len, t0,
                                  final and not resume and not blockdiff)
            chunk_toks = jnp.asarray(toks[None, t0:t0 + q_len])
        with obs.span("serving.prefill.dispatch"):
            logits, self._staging, *routed = fn(
                self._params, self._state, self._staging, chunk_toks)
        # a block-diffusion prefill's routing counts: read with the next
        # pass's results (``_block_step``), not waited for here
        self._prefill_routed += routed
        req.prefill_pos = t0 + q_len
        self.ssm_counters["ssm_prefill_positions"] += \
            q_len * len(self._ssm_layers)
        self.metrics.record_prefill_chunk()
        self.tracer.on_prefill_chunk(req.rid, t0, q_len)
        if not final:
            return
        with obs.span("serving.prefill.insert"):
            # write ONLY the pages the context fills, minus the shared
            # prefix pages that already hold identical data (the
            # copy-on-write donor's logical page IS written — into the
            # request's private copy)
            writes, rows, taken = self._aux_insert_plan(req, p_len)
            self.pool.insert_pages(self._staging, req.slot,
                                   getattr(req, "_n_shared_full", 0),
                                   p_len, aux_pages=writes)
            if self.prefix is not None:
                # full context pages are immutable from here (decode
                # writes start at p_len): share them forward
                self.prefix.register(toks, self.pool.tables[req.slot],
                                     aux_rows=rows)
            for grp, pids in zip(self.pool.aux, taken):
                for pid in pids:
                    grp.decref(pid)          # the cache's, or free again
        s = req.slot
        if blockdiff:
            self._open_block(req, p_len)
            return
        if resume:
            # re-admission after preemption: skip first-token sampling
            # (TTFT fired long ago), restore the decode vectors and the
            # snapshotted sampling key, rejoin the batch
            self.scheduler.to_decoding(req)
            self._comp_ver += 1
            self._tok[s] = req.generated[-1]
            self._t[s] = p_len
            self._temp[s] = req.temperature
            self._topk[s] = req.top_k
            self._topp[s] = req.top_p
            self._stop[s] = req.stop_token
            self._keys[s] = np.array(req.rng)
            self._chain_dirty[s] = True    # host owns the next input
            self._begin_draft(req, toks)
            t0_ = getattr(req, "_resume_t0", None)
            if t0_ is not None:
                self.metrics.record_reprefill_resume(
                    self.metrics.clock() - t0_,
                    p_len - getattr(req, "_shared_len", 0))
                req._resume_t0 = None
            self.tracer.on_resume(req.rid)
            return
        with obs.span("serving.prefill.first_token"):
            first, req.rng = self._sample_first_fn()(
                logits, jnp.float32(req.temperature),
                jnp.int32(req.top_k), jnp.float32(req.top_p), req.rng)
            token = int(first)
        req.generated.append(token)
        self.metrics.record_first_token(req.rid)
        self.tracer.on_first_token(req.rid)
        if req.done:
            self._finish(req, finished)
            return
        self.scheduler.to_decoding(req)
        self._comp_ver += 1
        self._tok[s] = token
        self._t[s] = p_len          # where the next decode step writes it
        self._temp[s] = req.temperature
        self._topk[s] = req.top_k
        self._topp[s] = req.top_p
        self._stop[s] = req.stop_token
        self._keys[s] = np.array(req.rng)
        self._chain_dirty[s] = True        # host owns the next input
        self._begin_draft(req, toks)

    # --- block diffusion (block-diffusion PR) -----------------------------

    def _open_block(self, req: Request, cached: int) -> None:
        """``req`` joins the decode batch with ``cached`` positions (whole
        blocks) in its pages: its slot's block starts there, holding
        what is left of prompt + generated (under a block, and only
        before the first generated block) and mask tokens after it.
        Only the host knows these rows: they override the slot's rows
        of the device's block state at the next launch."""
        s = req.slot
        rest = req.tokens[cached:]
        self.scheduler.to_decoding(req)
        self._comp_ver += 1
        self._t[s] = cached
        self._fresh_block(s, rest)
        if req.generated:
            self.tracer.on_resume(req.rid)

    def _fresh_block(self, slot: int, rest=()) -> None:
        """Slot ``slot``'s block opens: ``rest`` first, masks after."""
        bl = self.block_len
        self._blk_tok[slot] = self.mask_token
        self._blk_tok[slot, :len(rest)] = rest
        self._blk_masked[slot] = np.arange(bl) >= len(rest)
        self._blk_ovr[slot] = True
        self._blk_step[slot] = 0
        self._blk_left[slot] = bl - len(rest)
        self._blk_ended[slot] = False

    def block_positions(self) -> Dict[int, tuple]:
        """Per slot that rides the next pass of a block-diffusion
        engine: ``(first position of its current block, masked
        positions left in it)``; 0 left means the slot's next pass is
        its commit pass. (A slot whose request ends with the block in
        flight rides no further pass and is left out.)"""
        return {slot: (int(self._t[slot]), int(self._blk_left[slot]))
                for slot in self.scheduler.running
                if not self._blk_ended[slot]}

    def _block_fn(self, head: bool):
        """The two block-pass programs. ``denoise`` (with the
        vocabulary head) takes the block state of the device and, in
        two arrays, what the host knows (``ctl [4, S]``: each slot's
        ``t``, positions to fix, denoising step, and whether the host
        overrides its rows; ``rows [2, S, B]``: the host's tokens and
        masks for those slots), lays the overriding rows over the
        state, runs the pass and makes its choice in the program
        (``block_denoise_slots_paged``): the state after the fix is its
        result; slots that only commit ride it with nothing to fix.
        ``commit`` (no head) runs when every live slot only commits.
        Both write the block's K/V in place."""
        fn = self._block_fns.get(head)
        if fn is None:
            module, page_len = self.module, self.page_len
            kw = dict(moe_dispatched=self._moe_dispatched,
                      paged_kernel=self._paged_kernel)

            def denoise(params, state, cache, toks, masked, fixed_pass,
                        ctl, rows, tables):
                t, n_fix, step, ovr = ctl
                over = (ovr != 0)[:, None]
                return block_denoise_slots_paged(
                    module, params, state, cache,
                    jnp.where(over, rows[0], toks),
                    jnp.where(over, rows[1] != 0, masked),
                    jnp.where(over, -1, fixed_pass),
                    n_fix, step, t, tables, page_len, **kw)

            def commit(params, state, cache, toks, t, tables):
                return block_pass_slots_paged(
                    module, params, state, cache, toks, t, tables,
                    page_len, head=False, **kw)

            name = "denoise" if head else "commit"
            fn = self._jit_serving(denoise, 9, name) if head \
                else self._jit_serving(commit, 6, name)
            self._block_fns[head] = fn
            self._recompile.watch("serving." + name, fn)
        return fn

    def _block_step(self, finished: List[Request]) -> None:
        """One block-diffusion pass over the decode batch. A slot
        whose block still has masked positions DENOISES: the pass's
        most confident masked positions (``_fix_schedule``) are fixed
        to their most probable tokens, in the program; when none is
        left the block is shown to its client (appended to
        ``Request.generated`` with the pass that fixed each token in
        ``Request.fixed_pass``). A slot whose block is whole COMMITS:
        the pass's K/V writes are the block's cache entries, and the
        next block opens. A request that meets its budget or stop token
        when its block is shown finishes without a commit pass: nothing
        will read that block.

        The choice is the program's and the schedule is static, so the
        host steers by what it knows without a result (``_launch_pass``)
        and, with ``overlap``, dispatches this pass BEFORE it reads the
        last one (``_consume_pass``): the device goes from pass to pass
        while the host fetches and books one pass behind, as the
        one-token loop does. A budget's end is known ahead, so such a
        stream rides no pass it does not need; a stop token is seen one
        pass late, and the rows of the pass its slot then rode are
        thrown away. Without ``overlap`` the pass is read as soon as it
        is launched (the launch-and-wait reference)."""
        running = self.scheduler.running
        with obs.span("serving.decode.pages"):
            look = np.zeros(self.num_slots, np.int64)
            look[[s for s in running if not self._blk_ended[s]]] = \
                self.block_len - 1
            self._ensure_decode_pages(look)
        # (funding the pages may have preempted a stream, and that
        # reads the pass in flight first: re-read both)
        prev = self._pending
        t0 = self.metrics.clock()
        pend = self._launch_pass(prev is not None, t0)
        if self.overlap:
            self._pending = pend
            if prev is not None:
                self._consume_pass(prev, finished, t0)
            if pend is not None and not any(
                    s in running and running[s].rid == rid
                    for s, rid in pend.slots):
                # every stream the new pass carries ended with the one
                # just read: nothing will come back for it, book it now
                self._flush_pending(finished)
        elif pend is not None:
            self._consume_pass(pend, finished, t0)

    def _launch_pass(self, overlapped: bool,
                     t0: float) -> Optional[_PendingPass]:
        """Dispatch one pass over the slots that need one, WITHOUT
        waiting on it, and move the host's view of every such slot past
        it: a denoising slot has ``_fix_schedule[step]`` positions
        fewer to fix, and if that makes its block whole the block is
        this pass's to show (and the request's budget says whether the
        slot rides another); a committing slot's ``_t`` moves on a
        block and a fresh block opens. Returns None where no slot needs
        a pass (every stream waits for its last block to be read)."""
        running = self.scheduler.running
        live = np.fromiter((s for s in running if not self._blk_ended[s]),
                           np.int64)
        if not live.size:
            return None
        with obs.span("serving.decode.tables"):
            tables = self.pool.device_tables()
        left = self._blk_left[live]
        denoising = live[left > 0]
        committing = live[left == 0]
        head = bool(denoising.size)
        kind = "denoise" if head else "commit"
        # idle slots, and those that only wait to be read, sit at the
        # sentinel: they write nothing and nothing of theirs is read
        t = np.full(self.num_slots, self.max_len, np.int32)
        t[live] = self._t[live]
        with obs.span("serving.decode." + kind):
            with obs.span("serving.decode.dispatch"):
                if head:
                    n_fix = np.zeros(self.num_slots, np.int32)
                    n_fix[denoising] = np.minimum(
                        self._fix_schedule[self._blk_step[denoising]],
                        self._blk_left[denoising])
                    # the host's view crosses in two fresh arrays (the
                    # program reads them after dispatch returns, and
                    # the mirrors move on below)
                    toks, masked, fixed_pass, self.pool.cache, routed = \
                        self._block_fn(True)(
                            self._params, self._state, self.pool.cache,
                            *self._blk_dev,
                            jnp.asarray(np.stack([
                                t, n_fix, self._blk_step, self._blk_ovr])),
                            jnp.asarray(np.stack([
                                self._blk_tok, self._blk_masked])),
                            tables)
                    self._blk_dev = (toks, masked, fixed_pass)
                    self._blk_ovr[:] = False
                    blk = (toks, fixed_pass)
                else:
                    blk = None
                    self.pool.cache, routed = self._block_fn(False)(
                        self._params, self._state, self.pool.cache,
                        self._blk_dev[0], jnp.asarray(t), tables)
        if "serving." + kind not in self._warmed:
            self._warmed.add("serving." + kind)
            self._recompile.mark_warm("serving." + kind)
        prefills, self._prefill_routed = self._prefill_routed, []
        shown = []
        if head:
            self._blk_left[denoising] -= n_fix[denoising]
            self._blk_step[denoising] += 1
            for slot in denoising[self._blk_left[denoising] == 0].tolist():
                # whole after this pass: a client may see it, and a
                # budget met at the block's end ends the stream with it
                req = running[slot]
                shown.append((slot, req.rid))
                self._blk_ended[slot] = \
                    self._t[slot] + self.block_len - len(req.prompt) \
                    >= req.max_new_tokens
        for slot in committing.tolist():
            # committed by this pass: the block's K/V are cache now
            self._t[slot] += self.block_len
            self._fresh_block(slot)
        return _PendingPass(
            kind, blk, routed, prefills,
            tuple((s, running[s].rid) for s in live.tolist()),
            tuple(shown), int(denoising.size), int(committing.size),
            overlapped, t0)

    def _consume_pass(self, p: _PendingPass, finished: List[Request],
                      t0: Optional[float] = None) -> None:
        """Read one launched pass and book it: its routing counts (and
        those of the prefills before it), the tokens of the blocks it
        made whole onto their requests, first-token and commit
        counters, and the requests that are done. A slot whose request
        changed since the launch (finished by a stop token the pass
        before showed, preempted, recycled) rode it for nothing: its
        rows are discarded and counted. ``t0`` as in
        ``_process_step``."""
        running = self.scheduler.running

        def stale(slot, rid):
            req = running.get(slot)
            return req is None or req.rid != rid

        shown = [(slot, rid) for slot, rid in p.shown
                 if not stale(slot, rid)]
        if shown:
            toks, fixed_pass, routed, *prefills = self._fetch(
                *p.blk, p.routed, *p.prefills)
        else:
            routed, *prefills = self._fetch(p.routed, *p.prefills)
        with obs.span("serving.decode.consume"):
            # what the program says its expert layers did: every row of
            # the pass is routed, idle slots' too
            self.metrics.record_block_pass(
                p.kind, p.denoising, p.committing, *map(int, routed),
                overlapped=p.overlapped,
                discarded=sum(stale(*sr) for sr in p.slots))
            for rows, touched in prefills:
                self.metrics.record_block_prefill(int(rows), int(touched))
            if t0 is None:
                t0 = p.launch_t
            n_emitted = 0
            done_reqs = []
            for slot, rid in shown:
                req = running[slot]
                first = not req.generated
                appended = 0
                for pos in np.nonzero(fixed_pass[slot] >= 0)[0]:
                    req.generated.append(int(toks[slot, pos]))
                    req.fixed_pass.append(int(fixed_pass[slot, pos]))
                    appended += 1
                    if req.done:
                        break           # budget / stop token mid-block
                n_emitted += appended
                self.metrics.record_block_commit(appended)
                if first:
                    self.metrics.record_first_token(req.rid)
                    self.tracer.on_first_token(req.rid)
                if self.tracer.enabled:
                    self._trace_decode[rid] = \
                        self._trace_decode.get(rid, 0) + appended
                    if self._trace_decode_t0 is None:
                        self._trace_decode_t0 = t0
                if req.done:
                    done_reqs.append(req)
            self._decode_buf.append(
                (len(p.slots), self._metrics.clock() - t0, n_emitted))
            if done_reqs:
                self._flush_host_window()        # ticks precede terminals
                for req in done_reqs:
                    self._finish(req, finished)

    def _begin_draft(self, req: Request, context) -> None:
        """Hand the draft source this request's context the moment it
        joins decode. A source that cannot serve the slot (its own
        pool is dry) disables speculation for THIS request only —
        admission and decode proceed untouched."""
        if not self._spec_eligible(req):
            return
        if not self._draft.begin_slot(req.slot, context):
            self._spec_disable(req)

    def _advance_decode(self, finished: List[Request]):
        # chaos hook: fires BEFORE any state mutates THIS iteration
        # (the in-flight step, if any, was launched by a prior
        # iteration and stays consumable), so an injected decode-step
        # error leaves the iteration wholesale-retryable (see step()
        # docstring)
        faults.point("serving.decode")
        if self.block_len is not None:
            self._block_step(finished)
            return
        spec = bool(self._spec_slots())
        if spec:
            # draft proposals read host-side token state, so a
            # speculative iteration is synchronous: drain the pipeline
            # first, then the verify fetch below is the sanctioned
            # in-iteration sync
            self._flush_pending(finished)
            if not self.scheduler.running:
                return                  # the flush drained the batch
        fuse = 0 if spec else self._fuse_window()
        if spec and self.spec_tree:
            # tree speculation: the page lookahead depends on the
            # PROPOSED node span, so proposal must precede page growth
            # — the whole iteration lives in _spec_tree_step
            self._spec_tree_step(finished)
            return
        # page growth happens BEFORE the step (a write with no page
        # would silently drop); may preempt streams out of
        # ``running``, so the batch composition reads after it.
        # Speculating slots demand pages for their whole verify
        # window up front (only as far as their budget can
        # consume); a fused window demands pages for all
        # ``fuse_steps`` write positions
        look = None
        if spec:
            look = np.zeros(self.num_slots, np.int64)
            for slot, r in self.scheduler.running.items():
                if self._spec_eligible(r):
                    look[slot] = min(
                        self.spec_k,
                        r.max_new_tokens - len(r.generated) - 1)
        elif fuse:
            look = np.zeros(self.num_slots, np.int64)
            for slot in self.scheduler.running:
                look[slot] = fuse - 1
        with obs.span("serving.decode.pages"):
            self._ensure_decode_pages(look)
        if not self.scheduler.running:
            return
        if spec:
            spec = bool(self._spec_slots())  # preemption may have
            #                                  evicted speculators
        elif fuse and self.scheduler.queue_depth:
            # funding the window preempted a stream: quiescence is
            # gone, fall back to single-step and rejoin later (the
            # pre-grown pages stay — they are legitimate write
            # positions)
            fuse = 0
        t0 = self.metrics.clock()
        greedy_only = all(r.temperature <= 0.0
                          for r in self.scheduler.running.values())
        with obs.span("serving.decode.tables"):
            tables = self.pool.device_tables()
        if spec:
            self._spec_step(greedy_only, tables, finished, t0)
            return
        prev = self._pending
        with obs.span("serving.decode.dispatch"):
            pend = self._launch_step(greedy_only, tables, fuse, prev, t0)
        if self.overlap:
            # pipelined dispatch: the new step runs on device while the
            # host consumes the LAGGED fetch of the previous one (its
            # decode sample covers THIS phase, t0 onward)
            self._pending = pend
            if prev is not None:
                self._process_step(prev, finished, t0)
        else:
            # the synchronous A/B baseline: launch-and-wait, exactly
            # the pre-zero-bubble loop
            self._process_step(pend, finished, t0)

    def _spec_step(self, greedy_only: bool, tables,
                   finished: List[Request], t0: float) -> None:
        """One speculative draft-and-verify iteration over the decode
        batch. Non-speculating slots ride the same program with their
        drafts force-rejected — for them the verify step IS a plain
        decode step. Host bookkeeping (metrics, tracer items, the
        acceptance EMA) defers onto the host-window buffers."""
        k = self.spec_k
        running = self.scheduler.running
        active = np.zeros(self.num_slots, bool)
        for slot, r in running.items():
            if self._spec_eligible(r):
                active[slot] = True
        drafts = np.zeros((self.num_slots, k), np.int32)
        self._draft.propose(dict(running), self._tok, self._t, drafts,
                            active)
        toks = np.concatenate([self._tok[:, None], drafts],
                              axis=1).astype(np.int32)
        active_dev = jnp.asarray(active)
        if greedy_only:
            with obs.span("serving.decode.dispatch"):
                cand, n_acc, self.pool.cache, moe = self._verify_fn(True)(
                    self._params, self._state, self.pool.cache, toks,
                    self._t, active_dev, tables)
            cand, n_acc = self._fetch(cand, n_acc)
        else:
            with obs.span("serving.decode.dispatch"):
                (cand, n_acc, self.pool.cache, keys,
                 moe) = self._verify_fn(False)(
                    self._params, self._state, self.pool.cache, toks,
                    self._t, active_dev, self._temp, self._topk,
                    self._topp, self._keys, tables)
            cand, n_acc, new_keys = self._fetch(cand, n_acc, keys)
            # the fetch hands back read-only views of device memory;
            # the key mirror stays host-writable (per-slot restores on
            # admission/resume write into it)
            self._keys = new_keys.copy()
        name = ("serving.verify_greedy" if greedy_only
                else "serving.verify_sampled")
        if name not in self._warmed:
            self._warmed.add(name)
            self._recompile.mark_warm(name)
        self._note_moe_route(moe)

        def note(slot, req, trace_on):
            m = int(n_acc[slot])
            self._spec_buf.append((k, m))
            # the EMA updates INLINE (not on the host-window
            # cadence): a spec iteration is already synchronous —
            # the verify fetch above paid the sync — and the
            # warm-up/kill-switch contract (spec_warmup checks,
            # then disable) is exact-count, not windowed
            self._observe_acceptance(req, m / k)
            if trace_on:
                pa = self._trace_spec.setdefault(req.rid, [0, 0])
                pa[0] += k
                pa[1] += m

        self._consume_spec(running, cand, n_acc + 1, active, note,
                           finished, t0)

    def _consume_spec(self, running, emitted, n_emit, active, note,
                      finished: List[Request], t0: float) -> None:
        """Shared host-consume loop of the linear and tree spec steps:
        append ``emitted[slot, :n_emit[slot]]`` until each request's
        stop/budget, advance the ``_tok``/``_t`` mirrors, batch the
        trace-decode ticks, run ``note(slot, req, trace_on)`` for each
        ACTIVE slot's speculation bookkeeping, and flush deferred host
        work BEFORE any terminal transition (on_terminal retires the
        timeline, and the final verify's outcome belongs on it). One
        copy of these contracts — the two call sites diverge only in
        their ``note`` closures."""
        with obs.span("serving.decode.consume"):
            now_ = self._metrics.clock()
            trace_on = self.tracer.enabled
            n_emitted = 0
            done_reqs = []
            for slot, req in list(running.items()):
                ne = int(n_emit[slot])
                appended = 0
                for token in emitted[slot, :ne]:
                    req.generated.append(int(token))
                    appended += 1
                    if req.done:
                        break           # stop token / budget mid-window
                n_emitted += appended
                self._tok[slot] = req.generated[-1]
                self._t[slot] += appended
                if trace_on:
                    self._trace_decode[req.rid] = \
                        self._trace_decode.get(req.rid, 0) + appended
                    if self._trace_decode_t0 is None:
                        self._trace_decode_t0 = now_
                if active[slot]:
                    note(slot, req, trace_on)
                if req.done:
                    done_reqs.append(req)
            self._decode_buf.append((len(running), now_ - t0, n_emitted))
            if done_reqs:
                self._flush_host_window()
                for req in done_reqs:
                    self._finish(req, finished)

    def _spec_tree_step(self, finished: List[Request]) -> None:
        """One TREE draft-and-verify iteration (tree-speculation PR).

        Order matters: (1) build each eligible stream's tree via
        ``DraftSource.propose_tree`` under its adaptive (depth, width)
        and a node budget capped by slot capacity; (2) derive
        depth/ancestor arrays and grow pages for the PROPOSED node
        span — the verify forward writes window columns ``t ..
        t + n_nodes - 1`` and an accepted node's missing page would
        silently corrupt its KV, so the lookahead is the worst-case
        tree width, not the chain depth; (3) one compiled
        verify-walk-commit program; (4) host consume: append
        ``emitted[:n_emit]``, update the acceptance EMA (on the
        longest-chain basis ``path_len / depth`` so the kill switch
        threshold means the same thing as the linear path's), resize
        the stream's tree (``_adapt_tree``). Streams whose tree ends
        up empty ride the program as plain decode steps."""
        W = self.spec_window
        running = self.scheduler.running
        s_n = self.num_slots
        toks = np.zeros((s_n, W), np.int32)
        toks[:, 0] = self._tok
        parents = np.full((s_n, W), -1, np.int32)
        active = np.zeros(s_n, bool)
        depth_v = np.zeros(s_n, np.int32)
        width_v = np.ones(s_n, np.int32)
        budget_v = np.zeros(s_n, np.int32)
        for slot, r in running.items():
            if not self._spec_eligible(r):
                continue
            d, w = self._tree_shape(r)
            if d < 1:
                continue
            active[slot] = True
            depth_v[slot] = d
            width_v[slot] = w
            # every node writes its own window column: the span must
            # fit the slot's capacity (>= d always — a chain fits)
            budget_v[slot] = min(d * w,
                                 self.max_len - 1 - int(self._t[slot]))
        if active.any():
            self._draft.propose_tree(dict(running), self._tok, self._t,
                                     toks, parents, active, depth_v,
                                     width_v, budget_v)
        depth, anc, n_nodes = tree_ancestors(parents)
        look = np.where(active, n_nodes - 1, 0).astype(np.int64)
        with obs.span("serving.decode.pages"):
            self._ensure_decode_pages(look)
        if not self.scheduler.running:
            return
        t0 = self.metrics.clock()
        running = self.scheduler.running
        greedy_only = all(r.temperature <= 0.0
                          for r in running.values())
        with obs.span("serving.decode.tables"):
            tables = self.pool.device_tables()
        targs = (toks, self._t, parents, depth, anc)
        if greedy_only:
            with obs.span("serving.decode.dispatch"):
                emitted, n_emit, self.pool.cache, moe = \
                    self._verify_tree_fn(True)(
                        self._params, self._state, self.pool.cache,
                        *targs, tables)
            emitted, n_emit = self._fetch(emitted, n_emit)
        else:
            with obs.span("serving.decode.dispatch"):
                (emitted, n_emit, self.pool.cache, keys, moe) = \
                    self._verify_tree_fn(False)(
                        self._params, self._state, self.pool.cache,
                        *targs, self._temp, self._topk, self._topp,
                        self._keys, tables)
            emitted, n_emit, new_keys = self._fetch(emitted, n_emit,
                                                    keys)
            self._keys = new_keys.copy()
        name = ("serving.verify_tree_greedy" if greedy_only
                else "serving.verify_tree_sampled")
        if name not in self._warmed:
            self._warmed.add(name)
            self._recompile.mark_warm(name)
        self._note_moe_route(moe)

        def note(slot, req, trace_on):
            nd = int(n_nodes[slot]) - 1         # draft nodes offered
            m = int(n_emit[slot]) - 1           # accepted path length
            self._spec_buf.append((nd, m))
            self._spec_tree_buf.append((int(width_v[slot]), m))
            # EMA on the longest-chain basis: m / depth means the
            # same thing the linear path's m / k did, so the
            # warm-up/kill-switch thresholds carry over unchanged
            self._observe_acceptance(
                req, m / max(1, int(depth_v[slot])))
            self._adapt_tree(req)
            if trace_on:
                pa = self._trace_spec.setdefault(req.rid, [0, 0, 0, 0])
                pa[0] += nd
                pa[1] += m
                pa[2] = max(pa[2], int(width_v[slot]))
                pa[3] = max(pa[3], m)

        self._consume_spec(running, emitted, n_emit, active, note,
                           finished, t0)

    def _drop_swap(self, req: Request) -> None:
        """Release an orphaned swap snapshot: free its host pages
        (pending async batches fully covered just drop — never read,
        never fenced) and release the refcount holds on the prefix-
        resident pages the snapshot pinned instead of copying."""
        swap = getattr(req, "_swap", None)
        if swap is None:
            return
        self.pool.free_host(swap["host"])
        for _lp, pid in swap.get("shared", ()):
            self.pool.decref(int(pid))
        req._swap = None

    def _finish(self, req: Request, finished: List[Request]):
        slot = req.slot
        self.scheduler.release(req)
        self._comp_ver += 1
        self._t[slot] = self.max_len          # sentinel: slot inert
        self._chain_dirty[slot] = True
        if self._draft is not None:
            self._draft.end_slot(slot)
        # pages return to the budget; registered prompt-prefix
        # pages survive under the prefix cache's own refcount
        self.pool.release_slot(slot)
        self.metrics.record_finish(req.rid, len(req.generated))
        self.tracer.on_terminal(req.rid, RequestState.FINISHED.value,
                                len(req.generated))
        # evict: the caller owns the finished Request from here —
        # otherwise every prompt ever served stays resident
        del self._requests[req.rid]
        finished.append(req)
