"""Engine replicas: one ``ServingEngine`` behind a lifecycle state
machine, the unit the router places work on.

A replica is STARTING until the router (or the caller) ``start()``s it,
SERVING while it accepts work, DRAINING once ``drain()`` closed
admission (in-flight streams finish; new submits shed with
``AdmissionRejected`` so the shedding semantics the engine already has
compose unchanged), and DEAD after a failure — the router treats any
exception escaping ``step()`` as replica death and mass-fails-over the
replica's in-flight requests (``router.Router._on_replica_death``).

``role`` partitions the fleet for disaggregated prefill/decode
serving: a ``"prefill"`` replica takes fresh admissions, runs the
chunked prefill and the first sampled token, and the router then hands
the stream to a ``"decode"`` replica through the engine's
``transfer_out``/``transfer_in`` re-entry path; ``"both"`` (default)
replicas do everything. See ``docs/serving.md`` §Router.

Chaos hook: ``resilience.faults`` point ``replica.die`` fires at the
top of every ``step()`` — arming it (``faults.inject("replica.die",
nth=K)``) kills whichever replica takes the K-th fleet step, which is
how the failover oracle tests drive replica loss deterministically.
"""

from __future__ import annotations

import enum
from typing import Dict, Optional

from distkeras_tpu.resilience import faults
from distkeras_tpu.serving.engine import ServingEngine
from distkeras_tpu.serving.scheduler import AdmissionRejected

__all__ = ["EngineReplica", "ReplicaDead", "ReplicaState",
           "ReplicaUnavailable"]


class ReplicaState(enum.Enum):
    STARTING = "starting"    # constructed, not yet taking traffic
    SERVING = "serving"      # admitting and decoding
    DRAINING = "draining"    # admission closed, in-flight finishing
    DEAD = "dead"            # failed; never stepped again


class ReplicaDead(RuntimeError):
    """The replica has failed and cannot serve (``step()`` after
    death). The router fails its requests over instead of raising."""

    def __init__(self, name: str, cause: Optional[BaseException] = None):
        tail = f": {cause!r}" if cause is not None else ""
        super().__init__(f"replica {name!r} is dead{tail}")
        self.name = name
        self.cause = cause


class ReplicaUnavailable(AdmissionRejected):
    """Submit refused because the replica is not SERVING (draining,
    starting or dead). An ``AdmissionRejected`` subclass so router and
    client shed-handling paths treat it exactly like a full queue."""

    def __init__(self, name: str, state: "ReplicaState",
                 queue_depth: int = 0):
        RuntimeError.__init__(
            self, f"replica {name!r} is {state.value}: admission closed")
        self.queue_depth = queue_depth
        self.max_queue = 0


class EngineReplica:
    """One ``ServingEngine`` + lifecycle + placement signals.

    The router's handoff and failover paths re-enter the engine
    through its resumable re-prefill machinery. ``name`` defaults to the
    engine's ``engine_id`` and becomes the replica's label on every
    process-global record (ring entries, tracer timelines, telemetry
    component ``serving[<name>]`` — pass ``engine_id=<name>`` at engine
    construction to make the component name match)."""

    def __init__(self, engine: ServingEngine, *, name: Optional[str] = None,
                 role: str = "both"):
        if role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"role must be 'both', 'prefill' or 'decode', "
                f"got {role!r}")
        self.engine = engine
        self.role = role
        if name is not None:
            # re-label the engine so its recorder/tracer records carry
            # the replica name (the snapshot component name was fixed
            # at engine construction — pass engine_id= there to align)
            engine.engine_id = str(name)
            if engine.tracer.enabled:
                engine.tracer.engine = str(name)
        self.name = str(name) if name is not None else engine.engine_id
        self.state = ReplicaState.STARTING
        self.error: Optional[BaseException] = None
        #: fleet steps this replica has taken (telemetry)
        self.steps = 0
        #: marked by ``Router.remove_replica``: the retire sweep pops
        #: this replica from the fleet once it drains empty. Controllers
        #: must treat a retiring replica as leaving — never resume it
        #: (``SLOBurnController`` skips it) and never count it toward
        #: serving capacity (``AutoscaleController`` does not).
        self.retiring = False

    def __repr__(self):
        return (f"EngineReplica({self.name!r}, role={self.role!r}, "
                f"state={self.state.value})")

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        """STARTING/DRAINING → SERVING (idempotent; dead replicas stay
        dead — build a new replica instead of resurrecting state the
        failover already re-homed). An explicit ``start()`` also
        cancels a pending retirement — the operator's resume beats the
        router's scheduled removal."""
        if self.state is ReplicaState.DEAD:
            raise ReplicaDead(self.name, self.error)
        self.state = ReplicaState.SERVING
        self.retiring = False

    def drain(self) -> None:
        """Close admission; in-flight streams keep stepping to
        completion. New submits (and router placement) shed with
        ``ReplicaUnavailable`` — an ``AdmissionRejected``."""
        if self.state is ReplicaState.DEAD:
            raise ReplicaDead(self.name, self.error)
        self.state = ReplicaState.DRAINING

    resume = start    # DRAINING → SERVING reads better as resume()

    def mark_dead(self, error: Optional[BaseException] = None) -> None:
        self.state = ReplicaState.DEAD
        if error is not None:
            self.error = error

    @property
    def drained(self) -> bool:
        """DRAINING and empty: safe to stop/recycle."""
        return (self.state is ReplicaState.DRAINING
                and not self.engine.scheduler.pending)

    @property
    def pending(self) -> bool:
        """Anything left to do: scheduler work, or terminals parked by
        an out-of-band pipeline flush (a handoff's preempt may finish a
        NEIGHBOUR stream — the next ``step()`` must run to deliver it
        even though the scheduler is empty)."""
        if self.state is ReplicaState.DEAD:
            return False
        eng = self.engine
        return eng.scheduler.pending or bool(eng._finish_buf)

    # -- placement signals (cheap: no device sync, no full health()) -------

    @property
    def queue_depth(self) -> int:
        return self.engine.scheduler.queue_depth

    @property
    def occupied(self) -> int:
        return self.engine.scheduler.occupied

    @property
    def free_pages(self) -> int:
        return self.engine.pool.free_pages

    @property
    def accepting(self) -> bool:
        """SERVING and the bounded queue has room."""
        if self.state is not ReplicaState.SERVING:
            return False
        sch = self.engine.scheduler
        return sch.max_queue is None or sch.queue_depth < sch.max_queue

    # -- work --------------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, **kw) -> int:
        """Guarded ``engine.submit``: a non-SERVING replica sheds with
        ``ReplicaUnavailable`` (an ``AdmissionRejected``)."""
        if self.state is not ReplicaState.SERVING:
            raise ReplicaUnavailable(self.name, self.state,
                                     self.queue_depth)
        return self.engine.submit(prompt, max_new_tokens, **kw)

    def transfer_in(self, req) -> int:
        """Guarded ``engine.transfer_in`` (same shed contract)."""
        if self.state is not ReplicaState.SERVING:
            raise ReplicaUnavailable(self.name, self.state,
                                     self.queue_depth)
        return self.engine.transfer_in(req)

    def step(self):
        """One engine iteration. ``replica.die`` is the chaos hook: an
        armed fault raising here is indistinguishable (to the router)
        from the engine crashing mid-step — the router marks the
        replica DEAD and fails its in-flight requests over."""
        if self.state is ReplicaState.DEAD:
            raise ReplicaDead(self.name, self.error)
        if self.state is ReplicaState.STARTING:
            self.start()
        faults.point("replica.die")
        self.steps += 1
        return self.engine.step()

    # -- views -------------------------------------------------------------

    def slo_burn(self) -> Optional[float]:
        """Max burn rate across the engine's declared SLO objectives
        (side-effect-free evaluation), or None without objectives /
        before any sample. The drain controller's input."""
        eng = self.engine
        if eng.slo is None:
            return None
        statuses = eng.slo.evaluate(eng.metrics, record=False)
        if not statuses:
            return None
        return max(st["burn_rate"] for st in statuses.values())

    def health(self) -> Dict:
        """The engine's ``health()`` wrapped with replica identity:
        ``status`` becomes ``"dead"``/``"draining"`` when the lifecycle
        overrides the engine view (a draining replica is healthy but
        must receive no traffic)."""
        if self.state is ReplicaState.DEAD:
            return {"status": "dead", "replica": self.name,
                    "role": self.role, "accepting": False,
                    "error": repr(self.error) if self.error else None}
        out = self.engine.health()
        out["replica"] = self.name
        out["role"] = self.role
        if self.state is not ReplicaState.SERVING:
            out["status"] = self.state.value
            out["accepting"] = False
        return out
