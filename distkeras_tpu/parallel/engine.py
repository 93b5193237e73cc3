"""The SPMD distributed-training engine: staggered-window workers + a
replicated center, all inside one jitted ``shard_map``.

This module is the TPU-native replacement for the reference's entire
distributed runtime — the Spark executor loop (``distkeras/workers.py``),
the socket parameter server (``distkeras/parameter_servers.py``) and the
pickled-TCP wire protocol (``distkeras/networking.py``) collapse into a
single compiled program over a device mesh (SURVEY §5.8: the north star is
zero socket-PS traffic, all comms via ICI collectives).

Mapping of reference concepts:

  reference (Spark + socket PS)            here (SPMD mesh)
  ---------------------------------------  --------------------------------
  Spark executor running Worker.train      mesh position along ``workers``
  per-worker minibatch loop                ``lax.scan`` over micro-steps
  PS 'pull' (TCP round-trip)               read of the replicated center
  PS 'commit' (TCP round-trip)             masked ``psum`` over ICI
  communication_window local steps         commit mask every K micro-steps
  PS mutex / commit serialization          staggered per-worker offsets so
                                           commits interleave like async
                                           arrivals (at most ~1/step)
  PS state (center weights, num_updates)   replicated pytrees in the carry

Async semantics on a synchronous mesh (SURVEY §7 "hard parts" (a)): true
async PS arrival order is modeled by giving each worker a commit *phase
offset* within its window. Worker i commits at global micro-steps t where
``(t + 1 + offset_i) % K_i == 0``. With offsets spread uniformly, commits
serialize through the (replicated) center exactly like the reference PS
serialized them through its mutex — a DynSGD worker therefore observes the
same staleness profile (center advanced by ~n-1 foreign commits per window)
as it would against the socket PS. Setting all offsets to 0 recovers the
synchronous barrier-round algorithms (EASGD, averaging).

Everything — local steps, masked collectives, server updates — runs inside
one ``lax.scan`` under ``shard_map`` under ``jit``: per epoch there is ONE
Python dispatch, and XLA overlaps the per-window psum with local compute
where the schedule allows.

Communication amortization (the whole point of ``communication_window``,
SURVEY §2.3): with a uniform window K the epoch compiles to a TWO-LEVEL
scan — outer over ``S // K`` window blocks, inner over K purely-local
steps with ZERO collectives — so a param-sized ``psum`` crosses the ICI
exactly ``ceil(S / K)`` times per epoch, not S times. Per-worker async
staggering survives the restructure: worker i snapshots its params into a
carried buffer at its phase step ``(K - 1 - offset_i) mod K`` inside each
block (a masked select, no comms), the boundary collective commits the
*snapshot*'s contribution, and a tail-carry
``params := post_commit + (params_now - snapshot)`` preserves the local
steps the worker took after its snapshot. For synchronous algorithms
(offsets = 0) the snapshot is the final step of the block, so when K
divides the epoch length the program is step-for-step equivalent to the
per-step path (tail = 0). Deliberate semantic differences from the
per-step path: window phase resets at each epoch (the per-step path's
global step counter carries it across), and a remainder block (S % K
steps) TRUNCATES the final window — every worker commits its residual at
the epoch boundary, like the reference worker committing when its
partition iterator ends. Heterogeneous per-worker windows (DynSGD's K_i
lists) and non-amortizable algorithms (DynSGD's staleness counter, ADAG's
nonlinear accumulator) fall back to the per-step masked path, where
fine-grained commit serialization is the point.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu.compat import shard_map
from distkeras_tpu.ops.optimizers import Optimizer
from distkeras_tpu.parallel.worker import (  # noqa: F401  (re-export)
    TrainCarry, make_train_step, shard_epoch_data)

Pytree = Any


def _tmap(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def host_fetch(tree: Pytree) -> Pytree:
    """``device_get`` that also works under multi-process ``jax.distributed``
    (deploy.Job): leaves whose shards live on other hosts are allgathered to
    every process (DCN), replicated/addressable leaves fetch directly.

    This is THE sanctioned blocking fetch point of the epoch-loop
    modules (tools/lint_host_sync.py): loops route device->host reads
    through here (or ``jax.device_get`` at an allow-marked boundary
    site), never ad hoc mid-step."""
    if jax.process_count() == 1:
        return jax.device_get(tree)  # lint: allow-host-sync (the owner)
    from jax.experimental import multihost_utils

    def fetch(x):
        if not isinstance(x, jax.Array):
            return np.asarray(x)
        if x.is_fully_addressable:
            return np.asarray(jax.device_get(x))  # lint: allow-host-sync
        return np.asarray(multihost_utils.process_allgather(x, tiled=True))

    return _tmap(fetch, tree)


def host_async(tree: Pytree) -> Pytree:
    """Start device->host transfers for every addressable device leaf
    WITHOUT blocking (overlap PR): the epoch loops call this on per-step
    loss/metric arrays right after dispatching the epoch program, so by
    the time the epoch-boundary ``host_fetch`` runs, the copies are
    already on (or through) the wire — the boundary fetch stops costing
    one full D2H round trip per accumulated array. Returns ``tree``
    unchanged (device leaves stay device-resident)."""
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array) and leaf.is_fully_addressable:
            try:
                leaf.copy_to_host_async()
            except Exception:  # lint: allow-swallow — a backend without
                pass           # async D2H just fetches at the boundary
    return tree


def _select(mask, a, b):
    """Pytree-wise ``where(mask, a, b)`` with a scalar bool mask."""
    return _tmap(lambda x, y: jnp.where(mask, x, y), a, b)


# ---------------------------------------------------------------------------
# Algorithm plug-ins (the reference's ParameterServer subclasses, SURVEY §2.1)
# ---------------------------------------------------------------------------

class DistAlgorithm:
    """Commit/serve behavior of one distributed SGD variant.

    Roles map onto the reference's split: ``contrib``/``worker_post`` are the
    worker-side commit protocol (``workers.py :: *Worker.train`` window
    body), ``server_update`` is the PS-side handler
    (``parameter_servers.py :: *ParameterServer.handle_commit``).
    """

    #: async emulation (staggered offsets) vs synchronous barrier rounds
    staggered: bool = True
    #: whether workers track a pull-time snapshot of the center
    needs_pull: bool = False
    #: False: the algorithm's semantics need per-commit serialization
    #: through the center (e.g. DynSGD's staleness counter, which is what
    #: keeps its full-scale deltas stable) — the engine then uses the
    #: per-step masked path even for uniform windows
    amortizable: bool = True

    def init_server(self, params: Pytree) -> Dict[str, Pytree]:
        return {}

    def init_worker_extras(self, num_workers: int) -> Dict[str, jnp.ndarray]:
        return {}

    def contrib(self, w_params, pull, center, server, extras) -> Pytree:
        """Per-worker commit payload (pre-masking), e.g. a delta or an
        elastic difference."""
        raise NotImplementedError

    def server_update(self, center, server, total, n_commits
                      ) -> Tuple[Pytree, Dict]:
        """Apply the psum of masked contributions to the center."""
        raise NotImplementedError

    def worker_post(self, w_params, pull, contrib, new_center, new_server,
                    extras, mask) -> Tuple[Pytree, Pytree, Dict]:
        """Worker-side effect of its own commit (pull fresh center, subtract
        elastic term, record clock, ...). Applied only where ``mask``."""
        return w_params, pull, extras

    def finalize(self, center, workers_stacked, pulls_stacked,
                 num_workers: int) -> Pytree:
        """Host-side flush after the last epoch (uncommitted residual)."""
        return center


@dataclass
class DownpourAlgo(DistAlgorithm):
    """DOWNPOUR (Dean et al. 2012): workers accumulate K local steps, commit
    the accumulated delta, pull a fresh center.

    Reference: ``workers.py :: DOWNPOURWorker`` + ``parameter_servers.py ::
    DeltaParameterServer`` (``handle_commit``: ``center += delta``).
    ``commit_scale`` scales committed deltas (1.0 = the reference's naive
    sum; 1/n tames the effective learning rate when many workers commit).
    """
    commit_scale: float = 1.0
    staggered: bool = True
    needs_pull: bool = True

    def contrib(self, w_params, pull, center, server, extras):
        return _tmap(lambda x, p: (x - p) * self.commit_scale, w_params, pull)

    def server_update(self, center, server, total, n_commits):
        return _tmap(jnp.add, center, total), server

    def worker_post(self, w_params, pull, contrib, new_center, new_server,
                    extras, mask):
        return (_select(mask, new_center, w_params),
                _select(mask, new_center, pull), extras)

    def finalize(self, center, workers, pulls, n):
        # flush each worker's uncommitted delta into the center
        resid = _tmap(lambda w, p: (w - p).sum(axis=0) * self.commit_scale,
                      workers, pulls)
        return _tmap(jnp.add, center, resid)


@dataclass
class ElasticAlgo(DistAlgorithm):
    """EASGD family (Zhang et al. 2015). Elastic difference
    ``e_i = alpha * (x_i - center)`` pulls worker and center toward each
    other: worker does ``x_i -= e_i``, center accumulates ``+e_i``.

    Reference: ``workers.py :: EASGDWorker/AEASGDWorker`` (elastic symmetric
    force, ``alpha = learning_rate * rho``) + the EASGD parameter servers.
    ``synchronous=True`` = barrier rounds (EASGD); ``False`` = staggered
    async emulation (AEASGD).

    ``center_mode``: 'sum' is the paper/reference update
    (``center += sum_i e_i`` — requires ``n * alpha < 1`` for stability);
    'mean' divides by the number of committers that step, stable for any n.
    """
    alpha: float = 0.1
    synchronous: bool = False
    center_mode: str = "sum"
    needs_pull: bool = False

    def __post_init__(self):
        self.staggered = not self.synchronous

    def contrib(self, w_params, pull, center, server, extras):
        return _tmap(lambda x, c: self.alpha * (x - c), w_params, center)

    def server_update(self, center, server, total, n_commits):
        if self.center_mode == "mean":
            denom = jnp.maximum(n_commits, 1.0)
            total = _tmap(lambda t: t / denom, total)
        return _tmap(jnp.add, center, total), server

    def worker_post(self, w_params, pull, contrib, new_center, new_server,
                    extras, mask):
        new_params = _tmap(lambda x, e: x - jnp.where(mask, e, 0.0),
                           w_params, contrib)
        return new_params, pull, extras


@dataclass
class AdagAlgo(DistAlgorithm):
    """ADAG — adaptive per-parameter accumulation on the server.

    Reference: ``parameter_servers.py :: ADAGParameterServer`` keeps a
    per-parameter accumulator over committed deltas (SURVEY §2.1). Concrete
    server rule used here (Adagrad applied to commits; re-verify the exact
    reference formula once the mount is populated):
        acc    += delta^2
        center += adag_lr * delta / (sqrt(acc) + eps)

    Not amortizable: the accumulator is nonlinear in the commits —
    batching a window's n contributions into one server round squares the
    SUM ((Σδ)² ≠ Σδ², cross terms) and divides by sqrt(acc) once instead
    of n serialized times. Like DynSGD, the per-step path's one-at-a-time
    commit ordering IS the algorithm.
    """
    adag_lr: float = 0.05
    epsilon: float = 1e-8
    commit_scale: float = 1.0
    staggered: bool = True
    needs_pull: bool = True
    amortizable: bool = False

    def init_server(self, params):
        return {"acc": _tmap(jnp.zeros_like, params)}

    def contrib(self, w_params, pull, center, server, extras):
        return _tmap(lambda x, p: (x - p) * self.commit_scale, w_params, pull)

    def server_update(self, center, server, total, n_commits):
        acc = _tmap(lambda a, t: a + jnp.square(t), server["acc"], total)
        center = _tmap(
            lambda c, t, a: c + self.adag_lr * t /
            (jnp.sqrt(a) + self.epsilon),
            center, total, acc)
        return center, {"acc": acc}

    def worker_post(self, w_params, pull, contrib, new_center, new_server,
                    extras, mask):
        return (_select(mask, new_center, w_params),
                _select(mask, new_center, pull), extras)


@dataclass
class DynSGDAlgo(DistAlgorithm):
    """DynSGD — staleness-aware delta scaling (Hermans).

    Reference: ``parameter_servers.py :: DynSGDParameterServer`` scales each
    commit by 1/staleness, where staleness = center updates since the
    worker's last pull (SURVEY §3.3). Server clock = ``num_updates``; each
    worker carries its last-pull clock value; commit applies
    ``delta / max(1, clock - last_pull + 1)``.

    Not amortizable: batching a round's commits makes every worker's
    staleness 1 (all pulled at the same boundary), so the 1/staleness
    damping that keeps the full-scale deltas stable vanishes — staleness
    only exists when commits serialize through the center one at a time.
    """
    staggered: bool = True
    needs_pull: bool = True
    amortizable: bool = False

    def init_server(self, params):
        return {"clock": jnp.zeros((), jnp.int32)}

    def init_worker_extras(self, num_workers):
        return {"last_pull": jnp.zeros((num_workers,), jnp.int32)}

    def contrib(self, w_params, pull, center, server, extras):
        staleness = jnp.maximum(
            1, server["clock"] - extras["last_pull"] + 1).astype(jnp.float32)
        return _tmap(lambda x, p: (x - p) / staleness, w_params, pull)

    def server_update(self, center, server, total, n_commits):
        clock = server["clock"] + n_commits.astype(jnp.int32)
        return _tmap(jnp.add, center, total), {"clock": clock}

    def worker_post(self, w_params, pull, contrib, new_center, new_server,
                    extras, mask):
        extras = {"last_pull": jnp.where(mask, new_server["clock"],
                                         extras["last_pull"])}
        return (_select(mask, new_center, w_params),
                _select(mask, new_center, pull), extras)


@dataclass
class AveragingAlgo(DistAlgorithm):
    """Per-round weight averaging: center := mean of worker params; workers
    restart from the average.

    Reference: ``trainers.py :: AveragingTrainer`` (per-epoch averaging of
    independently trained replicas). Here the round length is the commit
    window (set to steps-per-epoch by the trainer for exact parity).
    """
    staggered = False
    needs_pull = False

    def contrib(self, w_params, pull, center, server, extras):
        return w_params

    def server_update(self, center, server, total, n_commits):
        denom = jnp.maximum(n_commits, 1.0)
        avg = _tmap(lambda t: t / denom, total)
        committed = n_commits > 0
        return _select(committed, avg, center), server

    def worker_post(self, w_params, pull, contrib, new_center, new_server,
                    extras, mask):
        return _select(mask, new_center, w_params), pull, extras

    def finalize(self, center, workers, pulls, n):
        return _tmap(lambda w: w.mean(axis=0), workers)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

@dataclass
class EngineConfig:
    num_workers: int
    window: Union[int, Sequence[int]]  # K, scalar or per-worker
    axis_name: str = "workers"
    #: None = auto (two-level amortized scan when the window is uniform,
    #: per-step masked path otherwise). False forces the per-step path —
    #: kept for heterogeneous windows and for equivalence testing.
    amortized: Optional[bool] = None


class DistributedEngine:
    """Compiles and runs the per-epoch SPMD program for one algorithm."""

    def __init__(self, module, loss_fn: Callable, optimizer: Optimizer,
                 algo: DistAlgorithm, mesh: Mesh, config: EngineConfig,
                 metric_fns: Optional[Dict[str, Callable]] = None,
                 param_mask=None, state_mask=None):
        self.param_mask = param_mask  # Keras-style layer freezing
        self.state_mask = state_mask
        self.module = module
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.algo = algo
        self.mesh = mesh
        self.config = config
        self.metric_fns = metric_fns

        n = config.num_workers
        K = config.window
        Ks = np.full((n,), K, np.int32) if np.isscalar(K) \
            else np.asarray(K, np.int32)
        if Ks.shape != (n,):
            raise ValueError(f"window must be scalar or length-{n}")
        if algo.staggered:
            offsets = (np.arange(n) * Ks) // n
        else:
            offsets = np.zeros((n,), np.int32)
        self._Ks = jnp.asarray(Ks)
        self._offsets = jnp.asarray(offsets % np.maximum(Ks, 1))
        uniform = bool((Ks == Ks[0]).all())
        if config.amortized and not uniform:
            raise ValueError(
                "amortized=True requires a uniform window; per-worker "
                f"windows {Ks.tolist()} need the per-step path")
        if config.amortized and not algo.amortizable:
            raise ValueError(
                f"{type(algo).__name__} is not amortizable (needs "
                "per-commit serialization through the center)")
        self.amortized = (uniform and algo.amortizable) \
            if config.amortized is None else bool(config.amortized)
        if (config.amortized is None and self.amortized
                and bool((np.asarray(offsets) != 0).any())):
            # auto-amortization changes staggered-async trajectories:
            # in-window commits are no longer serialized — all workers
            # commit at block boundaries. Opt out with amortized=False.
            import warnings
            warnings.warn(
                "amortized two-level scan auto-enabled with nonzero "
                "stagger offsets: commit interleaving differs from the "
                "per-step path (same fixed point, different trajectory); "
                "pass amortized=False to reproduce per-step numerics",
                stacklevel=3)
        self._uniform_K = int(Ks[0]) if uniform else None
        self._epoch_fn = None  # built lazily (jitted shard_map)
        self._reset_fn = None  # built lazily (parallelism_factor > 1)
        self._recompile = None  # obs detector, bound in _build()
        self._warm_marked = False

    # -- state ------------------------------------------------------------
    def init_state(self, params: Pytree, model_state: Pytree,
                   rng: jax.Array) -> Dict:
        """Build the replicated-center + stacked-worker state pytree."""
        n = self.config.num_workers
        stack = lambda tree: _tmap(
            lambda x: jnp.broadcast_to(x, (n,) + x.shape), tree)
        worker = {
            "params": stack(params),
            "state": stack(model_state),
            "opt": jax.vmap(self.optimizer.init)(stack(params)),
            "rng": jax.random.split(rng, n),
            "pull": stack(params) if self.algo.needs_pull else {},
            "extras": self.algo.init_worker_extras(n),
        }
        server = {
            "aux": self.algo.init_server(params),
            "t": jnp.zeros((), jnp.int32),  # global micro-step counter
        }
        return {"worker": worker,
                "center": {"params": params, "state": model_state},
                "server": server}

    def reset_workers(self, state: Dict) -> Dict:
        """Re-initialize every worker from the CURRENT center: params,
        pull snapshot, optimizer state, and algorithm extras reset; the
        center, server aux, global step counter, and worker rng streams
        carry on.

        This is the reference's task boundary (``workers.py``: each Spark
        partition builds a fresh Keras model + optimizer from the
        serialized center) — used by ``parallelism_factor > 1``, where an
        epoch is ``num_workers x factor`` partitions and each worker
        consumes ``factor`` of them sequentially."""
        if self._reset_fn is None:
            n = self.config.num_workers

            @partial(jax.jit, out_shardings=self.shardings())
            def _reset(state):
                center = state["center"]["params"]
                stack = lambda tree: _tmap(
                    lambda x: jnp.broadcast_to(x, (n,) + x.shape), tree)
                worker = dict(state["worker"])
                worker["params"] = stack(center)
                worker["opt"] = jax.vmap(self.optimizer.init)(stack(center))
                if self.algo.needs_pull:
                    worker["pull"] = stack(center)
                worker["extras"] = self.algo.init_worker_extras(n)
                return {**state, "worker": worker}

            self._reset_fn = _reset
        return self._reset_fn(state)

    def shardings(self) -> Dict:
        """NamedShardings matching ``init_state`` for explicit device_put."""
        ws = NamedSharding(self.mesh, P(self.config.axis_name))
        rs = NamedSharding(self.mesh, P())
        return {"worker": ws, "center": rs, "server": rs}

    # -- compiled epoch ---------------------------------------------------
    def _build(self):
        inner = self._make_inner_amortized() if self.amortized \
            else self._make_inner_perstep()
        axis = self.config.axis_name
        state_specs = {"worker": P(axis), "center": P(), "server": P()}
        mapped = shard_map(
            inner, mesh=self.mesh,
            in_specs=(state_specs, P(None, axis), P(None, axis)),
            out_specs=(state_specs, P(None, axis)))
        self._epoch_fn = jax.jit(mapped, donate_argnums=(0,))
        # detector bound HERE, with the function it watches — callers
        # (and tests) invoke _build() directly, so run_epoch cannot
        # assume it created the epoch fn itself
        from distkeras_tpu import obs
        self._recompile = obs.RecompileDetector()
        self._recompile.watch("engine.epoch", self._epoch_fn)

    def _make_inner_amortized(self):
        """Two-level epoch program: a param-sized collective once per
        window block (``ceil(S/K)`` per epoch), never per micro-step."""
        axis = self.config.axis_name
        train_step = make_train_step(self.module, self.loss_fn,
                                     self.optimizer, self.metric_fns,
                                     param_mask=self.param_mask,
                                     state_mask=self.state_mask)
        algo = self.algo
        K = self._uniform_K
        offsets = self._offsets

        def inner(state, X, Y):
            w = _tmap(lambda a: a[0], state["worker"])
            center = state["center"]
            server_aux = state["server"]["aux"]
            gt0 = state["server"]["t"]
            widx = lax.axis_index(axis)
            # local step within a block at which this worker's commit
            # snapshot is taken: solves (lt + 1 + offset) % K == 0
            snap_step = (K - 1 - offsets[widx]) % K

            X0, Y0 = X[:, 0], Y[:, 0]
            S = X0.shape[0]
            nblocks, rem = divmod(S, K)

            def make_local_step(target):
                def local_step(carry, batch):
                    w, snap = carry
                    xb, yb, lt = batch
                    tc = TrainCarry(w["params"], w["state"], w["opt"],
                                    w["rng"])
                    tc, outs = train_step(tc, (xb, yb))
                    w = {**w, "params": tc.params, "state": tc.state,
                         "opt": tc.opt_state, "rng": tc.rng}
                    snap = _select(lt == target, w["params"], snap)
                    return (w, snap), outs
                return local_step

            def commit(w, snap, center, server_aux):
                """One boundary exchange: psum every worker's snapshot
                contribution (all workers commit at every boundary — a
                short remainder block clamps the snapshot to its last
                step), update the center, and re-join each worker with its
                post-snapshot tail."""
                contrib = algo.contrib(snap, w["pull"], center["params"],
                                       server_aux, w["extras"])
                total = lax.psum(contrib, axis)
                n_commits = lax.psum(jnp.float32(1.0), axis)
                new_cparams, new_aux = algo.server_update(
                    center["params"], server_aux, total, n_commits)
                post, new_pull, new_extras = algo.worker_post(
                    snap, w["pull"], contrib, new_cparams, new_aux,
                    w["extras"], jnp.bool_(True))
                # tail-carry: local steps taken after the snapshot survive
                # the commit and fold into the next window's contribution
                new_params = _tmap(lambda q, s, p: q + (p - s),
                                   post, snap, w["params"])
                w = {**w, "params": new_params, "pull": new_pull,
                     "extras": new_extras}
                return w, {**center, "params": new_cparams}, new_aux

            def block(carry, block_data):
                w, center, server_aux = carry
                xb, yb = block_data  # [K, batch, ...]
                (w, snap), outs = lax.scan(
                    make_local_step(snap_step), (w, w["params"]),
                    (xb, yb, jnp.arange(K, dtype=jnp.int32)))
                w, center, server_aux = commit(w, snap, center, server_aux)
                return (w, center, server_aux), outs

            carry = (w, center, server_aux)
            outs_parts = []
            if nblocks:
                Xb = X0[:nblocks * K].reshape((nblocks, K) + X0.shape[1:])
                Yb = Y0[:nblocks * K].reshape((nblocks, K) + Y0.shape[1:])
                carry, outs_b = lax.scan(block, carry, (Xb, Yb))
                # [nblocks, K] per-step scalars -> [nblocks*K]
                outs_parts.append(_tmap(
                    lambda a: a.reshape((nblocks * K,) + a.shape[2:]),
                    outs_b))
            if rem:
                w, center, server_aux = carry
                # the final window TRUNCATES at the epoch boundary (the
                # reference's worker commits its residual when its
                # partition iterator ends): snapshot at the phase step if
                # it fits, else at the block's last step, and every worker
                # commits — a worker whose phase never arrives (K > S sync
                # cases) must not sit out the epoch entirely
                (w, snap), outs_r = lax.scan(
                    make_local_step(jnp.minimum(snap_step, rem - 1)),
                    (w, w["params"]),
                    (X0[nblocks * K:], Y0[nblocks * K:],
                     jnp.arange(rem, dtype=jnp.int32)))
                carry = commit(w, snap, center, server_aux)
                outs_parts.append(outs_r)
            w, center, server_aux = carry
            outs = outs_parts[0] if len(outs_parts) == 1 else _tmap(
                lambda *xs: jnp.concatenate(xs, axis=0), *outs_parts)

            new_state = {
                "worker": _tmap(lambda a: a[None], w),
                "center": center,
                "server": {"aux": server_aux, "t": gt0 + S},
            }
            return new_state, _tmap(lambda a: a[:, None], outs)

        return inner

    def _make_inner_perstep(self):
        """Per-micro-step masked-psum path: exact fine-grained commit
        interleaving, param-sized collective every step. Retained for
        heterogeneous per-worker windows and as the equivalence oracle for
        the amortized program."""
        axis = self.config.axis_name
        train_step = make_train_step(self.module, self.loss_fn,
                                     self.optimizer, self.metric_fns,
                                     param_mask=self.param_mask,
                                     state_mask=self.state_mask)
        algo = self.algo
        Ks, offsets = self._Ks, self._offsets

        def inner(state, X, Y):
            # per-device blocks: worker leaves [1, ...] -> [...]
            w = _tmap(lambda a: a[0], state["worker"])
            center = state["center"]
            server_aux = state["server"]["aux"]
            gt0 = state["server"]["t"]
            widx = lax.axis_index(axis)
            K = Ks[widx]
            offset = offsets[widx]

            def body(carry, batch):
                w, center, server_aux, gt = carry
                xb, yb = batch
                tc = TrainCarry(w["params"], w["state"], w["opt"], w["rng"])
                tc, outs = train_step(tc, (xb, yb))
                w = {**w, "params": tc.params, "state": tc.state,
                     "opt": tc.opt_state, "rng": tc.rng}

                mask = ((gt + 1 + offset) % jnp.maximum(K, 1)) == 0
                maskf = mask.astype(jnp.float32)
                contrib = algo.contrib(w["params"], w["pull"],
                                       center["params"], server_aux,
                                       w["extras"])
                masked = _tmap(lambda c: c * maskf, contrib)
                total = lax.psum(masked, axis)
                n_commits = lax.psum(maskf, axis)
                new_cparams, new_aux = algo.server_update(
                    center["params"], server_aux, total, n_commits)
                new_params, new_pull, new_extras = algo.worker_post(
                    w["params"], w["pull"], contrib, new_cparams, new_aux,
                    w["extras"], mask)
                w = {**w, "params": new_params, "pull": new_pull,
                     "extras": new_extras}
                center2 = {**center, "params": new_cparams}
                return (w, center2, new_aux, gt + 1), outs

            (w, center, server_aux, gt), outs = lax.scan(
                body, (w, center, server_aux, gt0), (X[:, 0], Y[:, 0]))

            new_state = {
                "worker": _tmap(lambda a: a[None], w),
                "center": center,
                "server": {"aux": server_aux, "t": gt},
            }
            # per-step scalars ([S] loss, and metric values when enabled)
            # gain the worker axis back: [S] -> [S, 1]
            return new_state, _tmap(lambda a: a[:, None], outs)

        return inner

    def run_epoch(self, state: Dict, Xs, Ys):
        """Run S micro-steps. ``Xs``/``Ys``: ``[S, W, batch, ...]``."""
        from distkeras_tpu import obs
        if self._epoch_fn is None:
            self._build()
        with obs.span("engine.epoch"):
            out = self._epoch_fn(state, Xs, Ys)
        # the epoch program compiles ONCE per engine by design (static
        # shapes): after the first call's legitimate compile, any cache
        # growth is a shape leak
        if self._warm_marked:
            self._recompile.check()
        else:
            self._recompile.mark_warm("engine.epoch")
            self._warm_marked = True
        return out

    # -- final model ------------------------------------------------------
    def extract_model(self, state: Dict) -> Tuple[Pytree, Pytree]:
        """Final (params, model_state): algorithm-flushed center params +
        worker-averaged model state (BN stats etc.)."""
        host = host_fetch(state)
        center = self.algo.finalize(
            host["center"]["params"], host["worker"]["params"],
            host["worker"]["pull"], self.config.num_workers)
        # float leaves (BN stats) average over workers; integer leaves
        # (step counters) keep worker 0's value — averaging would silently
        # turn them into float64
        mstate = _tmap(
            lambda s: s.mean(axis=0)
            if (hasattr(s, "dtype") and np.issubdtype(s.dtype, np.floating))
            else (s[0] if hasattr(s, "__getitem__") else s),
            host["worker"]["state"])
        return center, mstate


