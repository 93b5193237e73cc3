"""Request scheduling for the continuous-batching engine: admission,
per-request state machine, slot allocation/release, preemption.

The scheduler is pure host-side bookkeeping — it never touches device
arrays. Two policies (docs/serving.md; degradation semantics in
docs/resilience.md):

``FIFOScheduler`` (``PriorityScheduler``'s base; no engine uses it
alone):

  * FCFS admission: queued requests take free slots in arrival order.
  * BOUNDED queue: with ``max_queue`` set, a submit past the bound
    raises ``AdmissionRejected`` (explicit load shedding — the queue
    never grows without bound under overload).
  * ONE prefill stream: the oldest admitted-but-not-yet-decoding
    request advances one prompt chunk per engine iteration, interleaved
    between decode steps (long prompts therefore do not stall in-flight
    decode streams; they just take several iterations to come online).
  * Slots release on finish (stop token or length limit) and are
    immediately reusable by the next queued request. A request can also
    leave via ``cancel()`` — deadline timeout (``TIMED_OUT``) or
    poisoned-request isolation (``CANCELLED``) — from ANY live state.
  * Double-release is a loud error, never a silent double-free: two
    requests sharing one KV slot would corrupt both streams.

``PriorityScheduler`` (the engine's cost-aware policy):

  * Priority classes: lower ``Request.priority`` admits first
    (0 = interactive, 1 = standard, 2 = batch by convention; any int
    works). Within a class, FCFS — except preempted requests, which
    resume AT THE FRONT of their class (they hold progress).
  * Admission is budgeted: the engine admits head-of-line requests
    while ``peek()`` fits the free-PAGE budget (plus a free slot),
    not merely while slots exist — admitting by worst-case slot
    count leaves HBM idle.
  * PREEMPTION: ``preempt()`` ejects a DECODING request back to the
    queue (state → QUEUED, slot freed, generated tokens kept). The
    engine preempts when a decode step needs a page and none is free,
    or when a strictly-higher-priority request cannot admit; the
    victim re-prefills its prompt + generated context on re-admission
    (the resumable ``prefill_chunk_step``) and continues
    token-identically.
"""

from __future__ import annotations

import enum
import itertools
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np


class AdmissionRejected(RuntimeError):
    """Submit refused: the bounded admission queue is full (load
    shedding). Callers retry later or route elsewhere — the engine
    sheds explicitly instead of queueing unboundedly."""

    def __init__(self, queue_depth: int, max_queue: int):
        super().__init__(
            f"admission queue full ({queue_depth}/{max_queue} waiting); "
            "request shed")
        self.queue_depth = queue_depth
        self.max_queue = max_queue


class RequestState(enum.Enum):
    QUEUED = "queued"            # submitted, waiting for a slot
    PREFILLING = "prefilling"    # slot assigned, prompt chunks running
    DECODING = "decoding"        # in the slot-batched decode loop
    FINISHED = "finished"        # stop token or length limit reached
    TIMED_OUT = "timed_out"      # per-request deadline_s expired
    CANCELLED = "cancelled"      # isolated after a step error / by API


#: states a request never leaves
TERMINAL_STATES = frozenset(
    {RequestState.FINISHED, RequestState.TIMED_OUT,
     RequestState.CANCELLED})


@dataclass
class Request:
    """One serving request and its mutable progress state. Sampling
    knobs use the engine's per-slot sentinels (``temperature 0`` =
    greedy, ``top_k 0`` = no truncation, ``top_p 1.0`` = no nucleus
    cut, ``stop_token -1`` = never stop) so they can be placed directly
    into the per-slot sampling vectors."""

    rid: int
    prompt: np.ndarray                   # [P] int32
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    stop_token: int = -1
    seed: int = 0
    priority: int = 1                    # lower admits first (0 = most
    #                                      urgent; 1 = standard default)
    state: RequestState = RequestState.QUEUED
    slot: Optional[int] = None
    prefill_pos: int = 0                 # prompt positions ingested
    #: tokens a client may see so far. A plain decode step appends one,
    #: a speculative verify 1..k+1, and a block-diffusion engine
    #: nothing for ``denoising_steps - 1`` passes and then a whole
    #: block at once (cut at ``max_new_tokens`` / the stop token)
    generated: List[int] = field(default_factory=list)
    #: block diffusion only, parallel to ``generated``: the denoising
    #: pass of its block (0-based) that fixed each token
    fixed_pass: List[int] = field(default_factory=list)
    rng: object = None                   # per-request PRNG key (engine)
    deadline_s: Optional[float] = None   # submit->finish budget (engine
    #                                      clock); None = no deadline
    submit_t: float = 0.0                # engine-clock submit timestamp
    error: Optional[BaseException] = None  # why CANCELLED (isolation)
    n_preempted: int = 0                 # times evicted back to queue
    # fleet bookkeeping (router PRs): how many times this stream moved
    # between replicas — stamped by the router when it delivers the
    # terminal request, so replay outcomes can count lost vs replayed
    # vs degraded work per incident
    n_handoffs: int = 0                  # planned moves (disagg/rebalance)
    n_failovers: int = 0                 # replica-death re-admissions
    # speculative decoding (spec-decode PR): whether this request
    # participates in draft-and-verify iterations, the acceptance EMA
    # that decides it keeps paying off, and the sticky kill switch the
    # engine throws for adversarial (never-accepting) streams
    speculate: bool = False
    spec_disabled: bool = False
    spec_ema: Optional[float] = None     # EMA of per-verify accept rate
    spec_checks: int = 0                 # verify steps observed
    spec_disabled_at: Optional[int] = None  # generated-count at demotion
    #                                      (re-probe cooldown anchor)
    # tree speculation (tree-speculation PR): the adaptive controller's
    # per-stream tree shape (None until the engine seeds them from its
    # spec_k/spec_width caps at first use; survives preempt/resume)
    tree_depth: Optional[int] = None
    tree_width: Optional[int] = None

    @property
    def stopped(self) -> bool:
        return (self.stop_token >= 0 and bool(self.generated)
                and self.generated[-1] == self.stop_token)

    @property
    def done(self) -> bool:
        return self.stopped or len(self.generated) >= self.max_new_tokens

    @property
    def context_tokens(self) -> np.ndarray:
        """Every token whose KV must be IN CACHE before this request
        can (re)join decode: the prompt, plus — after a preemption —
        all generated tokens but the last (the last one is the pending
        decode input; its KV is written by the resumed step itself).
        For a fresh request this is just the prompt."""
        if not self.generated:
            return self.prompt
        return np.concatenate(
            [self.prompt,
             np.asarray(self.generated[:-1], self.prompt.dtype)])

    @property
    def tokens(self) -> np.ndarray:
        """Prompt + generated continuation (ends AT the stop token when
        one fired — no padding, unlike ``generate()``'s fixed-shape
        output)."""
        return np.concatenate(
            [self.prompt, np.asarray(self.generated, self.prompt.dtype)])


class FIFOScheduler:
    """FIFO queue + slot allocator + state machine transitions."""

    def __init__(self, num_slots: int, max_queue: Optional[int] = None):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if max_queue is not None and int(max_queue) < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.num_slots = int(num_slots)
        self.max_queue = None if max_queue is None else int(max_queue)
        self.waiting: deque = deque()          # QUEUED, FIFO
        self.prefilling: deque = deque()       # PREFILLING, FIFO
        self.running: Dict[int, Request] = {}  # slot -> DECODING request
        # request-level tracing hook (obs.tracing): the engine binds
        # its tracer here so admission decisions are recorded WHERE
        # they are made; None (standalone scheduler use) records
        # nothing
        self.tracer = None
        # pop() hands out slot 0 first — deterministic placement makes
        # oracle tests and trace reading reproducible
        self._free = list(range(self.num_slots))[::-1]

    # --- queue ------------------------------------------------------------

    def submit(self, req: Request) -> None:
        if self.max_queue is not None \
                and len(self.waiting) >= self.max_queue:
            raise AdmissionRejected(len(self.waiting), self.max_queue)
        req.state = RequestState.QUEUED
        self.waiting.append(req)

    def admit(self) -> List[Request]:
        """Move queued requests into free slots (FCFS) and mark them
        PREFILLING; returns the newly admitted requests."""
        admitted = []
        while self.waiting and self._free:
            req = self.waiting.popleft()
            req.slot = self._free.pop()
            req.state = RequestState.PREFILLING
            req.prefill_pos = 0
            self.prefilling.append(req)
            admitted.append(req)
            if self.tracer is not None:
                # queue depth AT admission: requests still waiting
                # after this one took its slot
                self.tracer.on_admit(req.rid, req.slot,
                                     len(self.waiting))
        return admitted

    def next_prefill(self) -> Optional[Request]:
        """The single request whose prompt chunks currently advance (the
        oldest admitted one; FCFS)."""
        return self.prefilling[0] if self.prefilling else None

    # --- transitions ------------------------------------------------------

    def to_decoding(self, req: Request) -> None:
        assert req is self.prefilling[0], "prefill completes FCFS"
        self.prefilling.popleft()
        req.state = RequestState.DECODING
        self.running[req.slot] = req

    def _evict(self, req: Request) -> None:
        """Remove an in-flight request from its live structure and free
        its slot. Raises on a request that holds no slot — a terminal
        (double-release) or still-QUEUED request — because silently
        appending its slot to the free list would hand the same KV slot
        to two requests."""
        if req.state is RequestState.DECODING:
            del self.running[req.slot]
        elif req.state is RequestState.PREFILLING:
            self.prefilling.remove(req)
        else:
            raise RuntimeError(
                f"cannot release request {req.rid} in state "
                f"{req.state.value!r}: it holds no slot "
                "(double release, or the request was never admitted)")
        self._free.append(req.slot)

    def release(self, req: Request) -> None:
        """Finish a request from either in-flight state and free its
        slot. Releasing twice (or releasing a QUEUED request) raises —
        it would put one slot on the free list twice."""
        self._evict(req)
        req.state = RequestState.FINISHED

    def cancel(self, req: Request,
               state: RequestState = RequestState.CANCELLED) -> None:
        """Terminate a request from ANY live state (degradation paths:
        deadline ``TIMED_OUT``, poisoned-request ``CANCELLED``). A
        queued request just leaves the queue; an admitted one also
        frees its slot. Terminal requests raise (same double-free
        guard as ``release``)."""
        if state not in (RequestState.CANCELLED, RequestState.TIMED_OUT):
            raise ValueError(
                f"cancel() target state must be CANCELLED or TIMED_OUT, "
                f"got {state}")
        if req.state is RequestState.QUEUED:
            self.waiting.remove(req)
        else:
            self._evict(req)
        req.state = state

    # --- introspection ----------------------------------------------------

    @property
    def queue_depth(self) -> int:
        return len(self.waiting)

    @property
    def occupied(self) -> int:
        return self.num_slots - len(self._free)

    @property
    def pending(self) -> bool:
        """Any request not yet FINISHED."""
        return bool(self.waiting or self.prefilling or self.running)

    @property
    def free_slots(self) -> int:
        return len(self._free)


class PriorityScheduler(FIFOScheduler):
    """Cost-aware scheduling over the same state machine: priority
    classes, budgeted admission (the engine gates ``admit_one`` on its
    page budget), and preemption of decoding requests back to the
    queue. ``waiting`` stays the single deque the base class (and its
    bounded-admission / cancel paths) already manage; ordering is by
    ``(priority, order)`` key at ``peek()`` time — queues are short
    (bounded under overload), so the O(n) min costs nothing next to a
    device step."""

    def __init__(self, num_slots: int, max_queue: Optional[int] = None):
        super().__init__(num_slots, max_queue=max_queue)
        self._order = itertools.count()   # arrival order within class
        self._front = itertools.count()   # requeue order (preempted)

    def submit(self, req: Request) -> None:
        # rank 1: fresh arrivals sort after every preempted (rank 0)
        # request of the same class, FCFS within the rank
        req._order = (1, next(self._order))
        super().submit(req)

    def _key(self, req: Request):
        return (req.priority, getattr(req, "_order", (1, 0)))

    def peek(self) -> Optional[Request]:
        """The request admission would take next (highest class, FCFS
        within it, preempted requests first), without taking it."""
        if not self.waiting:
            return None
        return min(self.waiting, key=self._key)

    def admit_one(self, req: Request) -> None:
        """Admit ONE queued request (the engine calls this only after
        reserving its pages) into a free slot."""
        if not self._free:
            raise RuntimeError("admit_one with no free slot")
        self.waiting.remove(req)
        req.slot = self._free.pop()
        req.state = RequestState.PREFILLING
        req.prefill_pos = 0
        self.prefilling.append(req)
        if self.tracer is not None:
            self.tracer.on_admit(req.rid, req.slot, len(self.waiting))

    def admit(self) -> List[Request]:
        """Unbudgeted admission (standalone/scheduler-only use): fill
        free slots in priority order."""
        admitted = []
        while self.waiting and self._free:
            req = self.peek()
            self.admit_one(req)
            admitted.append(req)
        return admitted

    def preempt(self, req: Request) -> None:
        """Evict an admitted request back to the queue: slot freed,
        state → QUEUED, generated tokens kept (its re-prefill context),
        resumed ahead of its class peers. DECODING victims resume
        token-identically (the engine snapshots their sampling key);
        a PREFILLING victim simply discards its staged chunks and
        re-prefills from scratch — its pages are page-budget holders
        too, and leaving them unpreemptable would let one mid-prefill
        request starve a decoding stream into a dead pool."""
        if req.state is RequestState.DECODING:
            del self.running[req.slot]
        elif req.state is RequestState.PREFILLING:
            self.prefilling.remove(req)
        else:
            raise RuntimeError(
                f"cannot preempt request {req.rid} in state "
                f"{req.state.value!r}: it holds no page-backed slot")
        self._free.append(req.slot)
        req.slot = None
        req.state = RequestState.QUEUED
        req.prefill_pos = 0
        req.n_preempted += 1
        req._order = (0, next(self._front))
        self.waiting.append(req)
