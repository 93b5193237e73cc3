"""Distributed trainer family: DOWNPOUR, EASGD, AEASGD, ADAG, DynSGD,
AveragingTrainer.

Reference parity: ``distkeras/trainers.py`` concrete classes (SURVEY §2.1).
Constructor surfaces mirror the reference (``num_workers``, ``batch_size``,
``communication_window``, ``num_epoch``, ``features_col``, ``label_col``,
algorithm hyper-parameters), but training runs on a ``jax.sharding.Mesh``
via the SPMD engine in ``parallel/engine.py`` instead of Spark executors +
a socket parameter server — see that module's docstring for the mapping.

Notable surface differences from the reference, by design:
  * no ``master_host``/``master_port`` — there is no socket PS;
  * ``parallelism_factor`` (round 3) keeps the reference's PARTITION
    semantics rather than oversubscribing devices: the epoch splits into
    ``num_workers x factor`` partitions and each worker consumes
    ``factor`` of them sequentially, re-initialized from the center at
    every partition start (fresh-Spark-task dynamics: more, smaller
    commit windows + a center re-sync per partition);
  * ``trainer.parameter_server`` is replaced by the replicated center state
    inside the engine.
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.data.dataset import Dataset
from distkeras_tpu.models.core import Model
from distkeras_tpu.parallel.engine import (
    AdagAlgo, AveragingAlgo, DistAlgorithm, DistributedEngine, DownpourAlgo,
    DynSGDAlgo, ElasticAlgo, EngineConfig, host_fetch, shard_epoch_data)
from distkeras_tpu.parallel.mesh import make_mesh
from distkeras_tpu.parallel.trainers import Trainer, val_logs
from distkeras_tpu.resilience import faults


class DistributedTrainer(Trainer):
    """Base for all mesh-distributed trainers.

    Reference: ``trainers.py :: DistributedTrainer`` (adds num_workers,
    communication_window, the PS service and worker allocation). Here
    ``allocate_algorithm()`` plays the role of the reference's
    ``allocate_worker()`` + ``allocate_parameter_server()`` pair: it fixes
    the commit protocol both sides of the (now compiled-in) exchange.
    """

    def __init__(self, keras_model: Model, num_workers: Optional[int] = None,
                 communication_window: int = 5,
                 parallelism_factor: int = 1, mesh=None, **kwargs):
        super().__init__(keras_model, **kwargs)
        self.num_workers = int(num_workers or len(jax.devices()))
        self.communication_window = communication_window
        # Reference semantics (trainers.py ctor): the epoch is
        # ``num_workers x parallelism_factor`` partitions; each worker
        # consumes ``parallelism_factor`` of them SEQUENTIALLY, starting
        # every partition as a fresh task from the current center (more,
        # smaller commit windows per epoch + a center re-sync per
        # partition). factor 1 = the persistent-worker engine default.
        self.parallelism_factor = int(parallelism_factor)
        if self.parallelism_factor < 1:
            raise ValueError(
                f"parallelism_factor must be >= 1, got {parallelism_factor}")
        self.mesh = mesh

    def allocate_algorithm(self) -> DistAlgorithm:
        raise NotImplementedError

    # window may be overridden per-train (AveragingTrainer binds it to the
    # epoch length)
    def _window(self, steps_per_epoch: int) -> Union[int, Sequence[int]]:
        return self.communication_window

    def train(self, dataset: Dataset) -> Model:
        self._reject_step_options()
        model = self.master_model
        X, y = self._training_arrays(dataset)

        mesh = self.mesh or make_mesh(self.num_workers)
        # probe epoch shape once to size the window (and fail fast on tiny
        # datasets)
        _, _, S = shard_epoch_data(X, y, self.num_workers, self.batch_size)
        engine = DistributedEngine(
            model.module, self.loss, self.worker_optimizer,
            self.allocate_algorithm(), mesh,
            EngineConfig(num_workers=self.num_workers,
                         window=self._window(S)),
            metric_fns=self._metric_fns(),
            param_mask=self._param_mask(model),
            state_mask=self._state_mask(model))

        # resume restores the CENTER; workers restart from it — the same
        # semantic as the reference's Spark task retry, which re-trains a
        # partition from the current PS center (SURVEY §5.3)
        manager = self._checkpoint_manager()
        tree, start_epoch = self._maybe_resume(
            manager, {"params": model.params, "state": model.state})
        state = engine.init_state(tree["params"], tree["state"],
                                  jax.random.PRNGKey(self.seed))
        state = jax.device_put(state, engine.shardings())
        self._record_placement("worker_state", state["worker"])

        from distkeras_tpu.utils.prefetch import Prefetcher
        assemble = lambda epoch: shard_epoch_data(
            X, y, self.num_workers, self.batch_size,
            self._epoch_perm(epoch, len(X)))
        self.record_training_start()
        extracted = None  # (params, state) pulled on the final-epoch save
        # next epoch's shuffle gather + [S, W, B, ...] stacking overlaps
        # with this epoch's device step (utils/prefetch.py)
        validator = self._make_validator(model.module)
        if validator is not None:
            # center model STATE never advances in the engine (only params
            # do); validate with the worker-averaged state, the same thing
            # extract_model ships (float leaves averaged, counters from
            # worker 0)
            @jax.jit
            def _val_state(wstate):
                return jax.tree_util.tree_map(
                    lambda s: s.mean(axis=0)
                    if jnp.issubdtype(s.dtype, jnp.floating) else s[0],
                    wstate)
        cbs = self._cb_list(lambda: engine.extract_model(state))
        try:
            with self._profile_ctx():
                for epoch, (Xs, Ys, S) in Prefetcher(
                        assemble, range(start_epoch, self.num_epoch)):
                    # chaos hook: mid-training crash; note the engine
                    # family resumes from the CENTER only (the documented
                    # PS-retry semantic), not bitwise like Single/SPMD
                    faults.point("train.epoch")
                    pf = self.parallelism_factor
                    if pf > 1:
                        # reference partition loop: each worker consumes
                        # pf sequential partitions, re-initialized from
                        # the center at every partition start (fresh
                        # Spark-task semantics)
                        if S < pf:
                            raise ValueError(
                                f"epoch has {S} steps/worker but "
                                f"parallelism_factor={pf} needs >= {pf}")
                        # equal-length partitions; the remainder steps are
                        # DROPPED (a shorter final chunk would recompile
                        # the epoch program for a second shape — minutes
                        # on a big model), matching shard_epoch_data's
                        # drop_remainder batching policy
                        chunk = S // pf
                        if chunk * pf < S:
                            import warnings
                            warnings.warn(
                                f"parallelism_factor={pf}: epoch has {S} "
                                f"steps/worker; the trailing "
                                f"{S - chunk * pf} steps are dropped every "
                                "epoch (equal-length partitions avoid a "
                                "second epoch-program compile). Size the "
                                "dataset so steps/worker divides by "
                                "parallelism_factor to train on all of "
                                "it.", stacklevel=2)
                        l_acc, m_acc = [], []
                        for j in range(pf):
                            lo, hi = j * chunk, (j + 1) * chunk
                            state = engine.reset_workers(state)
                            state, outs_j = engine.run_epoch(
                                state, Xs[lo:hi], Ys[lo:hi])
                            lj, mj = self._split_outs(outs_j)
                            l_acc.append(lj)
                            m_acc.append(mj)
                        losses = jnp.concatenate(l_acc)
                        mets = {k: jnp.concatenate([m[k] for m in m_acc])
                                for k in (m_acc[0] if m_acc else {})}
                    else:
                        state, outs = engine.run_epoch(state, Xs, Ys)
                        losses, mets = self._split_outs(outs)
                    extra = {}
                    if validator is not None:
                        # evaluate the CENTER (the model a user would ship)
                        extra = val_logs(host_fetch(validator(
                            state["center"]["params"],
                            _val_state(state["worker"]["state"]))))
                    losses, mets = host_fetch(losses), host_fetch(mets)
                    self.history.append_epoch(loss=losses, **mets, **extra)
                    # cadence check BEFORE extract_model: the full-state
                    # device->host transfer is expensive and must only
                    # happen on save epochs
                    extracted = None

                    def save_center(epoch):
                        nonlocal extracted
                        extracted = engine.extract_model(state)
                        if jax.process_index() == 0:  # one writer per ckpt
                            manager.save(epoch, {"params": extracted[0],
                                                 "state": extracted[1]},
                                         metadata={"epoch": epoch})

                    saved = False
                    if manager is not None and self._should_checkpoint(epoch):
                        save_center(epoch)
                        saved = True
                    cbs.epoch_end(epoch,
                                  self._epoch_logs(losses, mets, extra))
                    # stop_training stops ALL workers: the center is shared
                    # — there is no per-worker early stop in the engine
                    # protocol; a preemption request checkpoints the center
                    # first (same save-on-exit rule as the other trainers)
                    if self._epoch_exit(
                            epoch, saved,
                            save_center if manager is not None else None):
                        break
        finally:
            self.record_training_stop()
            cbs.train_end()  # closes callback resources on exceptions too
        if manager is not None:
            manager.wait()  # async snapshots durable before return

        # the forced last-epoch save already pulled the final state
        params, mstate = extracted if extracted is not None \
            else engine.extract_model(state)
        trained = model.replace(params=params, state=mstate)
        trained = self._apply_pending_weights(trained)
        self.master_model = trained
        return trained


class DOWNPOUR(DistributedTrainer):
    """Asynchronous DOWNPOUR SGD (Dean et al. 2012).

    Reference: ``trainers.py :: DOWNPOUR`` with ``DOWNPOURWorker`` +
    ``DeltaParameterServer`` (SURVEY §3.3): accumulate
    ``communication_window`` local steps, commit the delta, pull fresh
    center. Commits are staggered across workers to reproduce async PS
    arrival order (engine docstring).
    """

    def __init__(self, keras_model: Model, communication_window: int = 5,
                 commit_scale: float = 1.0, **kwargs):
        super().__init__(keras_model,
                         communication_window=communication_window, **kwargs)
        self.commit_scale = float(commit_scale)

    def allocate_algorithm(self):
        return DownpourAlgo(commit_scale=self.commit_scale)


class EASGD(DistributedTrainer):
    """Synchronous Elastic Averaging SGD (Zhang et al. 2015).

    Reference: ``trainers.py :: EASGD`` — barrier rounds: every worker
    exchanges an elastic difference with the center every
    ``communication_window`` steps, simultaneously. ``alpha = rho *
    learning_rate`` as in the reference worker; ``learning_rate`` here is
    the elastic/exploration rate (the worker optimizer's own learning rate
    is configured via ``worker_optimizer``/``optimizer_kwargs``).
    """

    def __init__(self, keras_model: Model, rho: float = 5.0,
                 learning_rate: float = 0.01, communication_window: int = 5,
                 center_mode: str = "sum", **kwargs):
        # learning_rate is the ELASTIC rate, not the worker optimizer's —
        # do not forward it to the base (which would configure the optimizer)
        super().__init__(keras_model,
                         communication_window=communication_window, **kwargs)
        self.rho = float(rho)
        self.learning_rate = float(learning_rate)
        self.center_mode = center_mode

    @property
    def alpha(self) -> float:
        return self.rho * self.learning_rate

    def allocate_algorithm(self):
        if (self.center_mode == "sum"
                and self.alpha * self.num_workers >= 1.0):
            import warnings
            warnings.warn(
                f"EASGD stability: num_workers * alpha = "
                f"{self.alpha * self.num_workers:.2f} >= 1 with "
                f"center_mode='sum'; the center update can oscillate. "
                f"Lower rho/learning_rate or use center_mode='mean'.",
                stacklevel=2)
        return ElasticAlgo(alpha=self.alpha, synchronous=True,
                           center_mode=self.center_mode)


class AEASGD(EASGD):
    """Asynchronous EASGD — the reference's flagship algorithm (SURVEY §3.2).

    Reference: ``trainers.py :: AEASGD`` with ``AEASGDWorker``: each worker
    elastic-exchanges with the center at its own cadence. Emulated by
    staggered commit offsets; each commit is a masked psum touching only
    that worker's elastic difference.
    """

    def __init__(self, keras_model: Model, rho: float = 5.0,
                 learning_rate: float = 0.01, communication_window: int = 32,
                 center_mode: str = "sum", **kwargs):
        super().__init__(keras_model, rho=rho, learning_rate=learning_rate,
                         communication_window=communication_window,
                         center_mode=center_mode, **kwargs)

    def allocate_algorithm(self):
        return ElasticAlgo(alpha=self.alpha, synchronous=False,
                           center_mode=self.center_mode)


class ADAG(DistributedTrainer):
    """ADAG — asynchronous commits with adaptive per-parameter server
    accumulation (reference: ``trainers.py :: ADAG`` +
    ``ADAGParameterServer``)."""

    def __init__(self, keras_model: Model, communication_window: int = 5,
                 adag_learning_rate: float = 0.05, epsilon: float = 1e-8,
                 **kwargs):
        super().__init__(keras_model,
                         communication_window=communication_window, **kwargs)
        self.adag_learning_rate = float(adag_learning_rate)
        self.epsilon = float(epsilon)

    def allocate_algorithm(self):
        return AdagAlgo(adag_lr=self.adag_learning_rate,
                        epsilon=self.epsilon)


class DynSGD(DistributedTrainer):
    """DynSGD — staleness-scaled asynchronous SGD (reference:
    ``trainers.py :: DynSGD`` + ``DynSGDParameterServer``; SURVEY §3.3:
    commit tagged with last-pull ``num_updates``, server scales delta by
    1/staleness).

    ``communication_window`` may be per-worker (a list of K_i) to model
    heterogeneous worker speeds — the scenario DynSGD exists for.
    """

    def __init__(self, keras_model: Model,
                 communication_window: Union[int, Sequence[int]] = 5,
                 **kwargs):
        super().__init__(keras_model,
                         communication_window=communication_window, **kwargs)

    def allocate_algorithm(self):
        return DynSGDAlgo()


class AveragingTrainer(DistributedTrainer):
    """Per-epoch weight averaging over independently training workers.

    Reference: ``trainers.py :: AveragingTrainer`` (SURVEY §2.1). The commit
    window is bound to the epoch length, so workers train a full epoch shard
    independently and then synchronously average — exactly the reference's
    per-epoch semantics, as one compiled program.
    """

    def __init__(self, keras_model: Model, **kwargs):
        kwargs.setdefault("communication_window", 0)  # bound at train time
        super().__init__(keras_model, **kwargs)

    def _window(self, steps_per_epoch: int):
        return steps_per_epoch

    def allocate_algorithm(self):
        return AveragingAlgo()
