"""Trainer hierarchy — orchestration layer.

Reference parity: ``distkeras/trainers.py`` (SURVEY §2.1): ``Trainer`` base
(master model, loss, worker optimizer, history/time bookkeeping, serialize),
``SingleTrainer``, ``AveragingTrainer``, ``EnsembleTrainer``, and the
distributed family (``DOWNPOUR``, ``EASGD``, ``AEASGD``, ``ADAG``,
``DynSGD``) — those distributed trainers live in
``distkeras_tpu/parallel/distributed.py`` and share this base.

API ergonomics match the reference: constructor kwargs
``(model, worker_optimizer, loss, batch_size, num_epoch, features_col,
label_col, ...)`` and ``trainer.train(dataset) -> Model``.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Callable, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.data.dataset import Dataset
from distkeras_tpu.models.core import Model
from distkeras_tpu.models.serialization import serialize_model
from distkeras_tpu.ops.losses import get_loss
from distkeras_tpu.ops.optimizers import Optimizer, get_optimizer
from distkeras_tpu.parallel.worker import (
    TrainCarry, make_epoch_runner, make_train_step, stack_batches)
from distkeras_tpu.resilience import faults
from distkeras_tpu.utils.history import History


def val_logs(fetched_or_device) -> dict:
    """Validator outputs -> the ``extra`` logs dict (``{key: [scalar]}``
    float arrays) every epoch loop records. The device->host read of the
    validation scalars happens HERE — the ONE sanctioned validation
    fetch point shared by the whole trainer family (it runs once per
    epoch, at the boundary, after the epoch program was dispatched)."""
    fetched = jax.device_get(fetched_or_device)  # lint: allow-host-sync
    return {k: np.asarray([float(v)])            # lint: allow-host-sync
            for k, v in fetched.items()}


def cache_validation_on_device(trainer, Xv, yv):
    """Device-resident validation arrays, cached on ``trainer`` ACROSS
    ``train()`` calls keyed on the ``validation_data`` object's identity
    (plus shape/dtype): a supervised run restarting after a crash — or
    any repeated ``train()`` on one trainer — stops re-paying the full
    validation-set H2D copy every attempt. Shared by the ``Trainer``
    family AND the duck-typed ``PipelineTrainer`` (one copy of the
    invalidation rule). The cache holds the key object itself, so
    identity can't be recycled; swapping ``validation_data`` (or a
    shape/dtype change) invalidates. In-place mutation of a kept
    ``validation_data`` is not detected — replace the object to change
    the data."""
    key = (Xv.shape, str(Xv.dtype), yv.shape, str(yv.dtype))
    cached = getattr(trainer, "_val_device_cache", None)
    if cached is not None and cached[0] is trainer.validation_data \
            and cached[1] == key:
        return cached[2]
    arrs = (jnp.asarray(Xv), jnp.asarray(yv))
    trainer._val_device_cache = (trainer.validation_data, key, arrs)
    return arrs


def epoch_exit(trainer, epoch: int, saved: bool, save_fn) -> bool:
    """Shared end-of-epoch stop logic for every epoch-loop trainer
    (``Trainer`` subclasses AND the duck-typed ``PipelineTrainer`` —
    ONE copy so the exit rule cannot drift between loops): on callback
    stop OR a preemption request, make sure THIS epoch is checkpointed
    (or resume would silently lose it) and tell the loop to break.

    Also the step-ring hook: every epoch lands one record in the
    flight recorder (``obs.recorder``), so a crash dump shows the
    recent training timeline next to the serving iterations — a no-op
    NULL object when telemetry is disabled.

    The preempt Event is consumed HERE, when it is acted on — not
    cleared at train() entry — so a SIGTERM landing between a
    supervisor's restart attempts (after the crash, before the resumed
    run installs its loop) still stops the resumed run at its first
    epoch instead of being silently dropped."""
    trainer.preempted = trainer._preempt.is_set()
    from distkeras_tpu.obs.recorder import resolve_recorder
    resolve_recorder().record(
        "train.epoch", trainer=type(trainer).__name__, epoch=int(epoch),
        saved=bool(saved), stop=bool(trainer.stop_training),
        preempted=bool(trainer.preempted))
    if not (trainer.stop_training or trainer.preempted):
        return False
    if trainer.preempted:
        trainer._preempt.clear()   # consumed: acted on exactly once
    if save_fn is not None and not saved:
        save_fn(epoch)
    return True


class Trainer:
    """Base trainer: holds the master model + loss/optimizer spec + history.

    Reference: ``trainers.py :: Trainer`` (serialized master model, loss,
    worker_optimizer, history, training-time bookkeeping).
    """

    def __init__(self, keras_model: Model,
                 worker_optimizer: Union[str, Optimizer] = "sgd",
                 loss: Union[str, Callable] = "categorical_crossentropy",
                 metrics: Optional[List[str]] = None,
                 features_col: str = "features", label_col: str = "label",
                 batch_size: int = 32, num_epoch: int = 1,
                 learning_rate: Optional[float] = None, seed: int = 0,
                 shuffle_each_epoch: bool = True,
                 optimizer_kwargs: Optional[dict] = None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 1, resume: bool = False,
                 checkpoint_async: bool = False,
                 profile_dir: Optional[str] = None,
                 grad_accum_steps: int = 1,
                 validation_data=None,
                 callbacks: Optional[Sequence] = None,
                 clip_grad_norm: Optional[float] = None,
                 class_weight: Optional[dict] = None,
                 fused_vocab_head: bool = False,
                 telemetry=None):
        self.master_model = keras_model
        opt_kwargs = dict(optimizer_kwargs or {})
        if learning_rate is not None and not isinstance(worker_optimizer,
                                                        Optimizer):
            opt_kwargs.setdefault("learning_rate", learning_rate)
        self.worker_optimizer = get_optimizer(worker_optimizer, **opt_kwargs)
        # global-norm gradient clipping as a pure optimizer wrapper — works
        # identically under jit/vmap/shard_map on every trainer
        if clip_grad_norm is not None:
            from distkeras_tpu.ops.optimizers import clip_by_global_norm
            self.worker_optimizer = clip_by_global_norm(
                self.worker_optimizer, clip_grad_norm)
        # eval_loss stays UNWEIGHTED (Keras semantics: class_weight shapes
        # the TRAINING objective only — val_loss must remain comparable
        # across weighted and unweighted runs)
        self.eval_loss = get_loss(loss)
        if class_weight is not None:
            # Keras class_weight: per-sample losses scaled by the true
            # class's weight (pure loss wrapper — every trainer inherits)
            from distkeras_tpu.ops.losses import with_class_weight
            self.loss = with_class_weight(loss, class_weight)
        else:
            self.loss = self.eval_loss
        self.metrics = metrics or []
        self.features_col = features_col
        self.label_col = label_col
        self.batch_size = int(batch_size)
        self.num_epoch = int(num_epoch)
        self.seed = int(seed)
        self.shuffle_each_epoch = bool(shuffle_each_epoch)
        self.history = History()
        #: name -> sorted ids of the devices that held that piece of
        #: training state ("params", "batch", "worker_state") in the
        #: last ``train()`` — mesh trainers record it so a run that
        #: silently sat on one chip is read, not inferred
        self.placement: dict = {}
        # checkpoint/resume (capability ADD over the reference, which has
        # none — SURVEY §5.4); snapshots the master/center model per epoch
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = int(checkpoint_every)
        if self.checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every must be >= 1, got {checkpoint_every}")
        self.resume = bool(resume)
        # background-thread checkpoint writes (big snapshots stop stalling
        # the step loop); the final wait() happens at train() end
        self.checkpoint_async = bool(checkpoint_async)
        # XLA/device trace of the whole run, viewable in XProf/TensorBoard
        # (SURVEY §5.1: the reference has wall-clock bookkeeping only)
        self.profile_dir = profile_dir
        # microbatch gradient accumulation inside each step (memory lever;
        # honored by SingleTrainer and SPMDTrainer)
        self.grad_accum_steps = int(grad_accum_steps)
        # per-epoch held-out evaluation: a Dataset (features/label cols as
        # configured) or an (X, y) pair; records val_loss / val_<metric>
        # scalars per epoch in History
        self.validation_data = validation_data
        # Keras-style per-epoch callbacks (utils/callbacks.py) — a
        # capability ADD; the reference leaves all of this to Keras, which
        # its bare train_on_batch worker loop never invokes
        self.callbacks = list(callbacks or [])
        # fuse the final vocab projection into a chunked cross-entropy
        # (ops.losses.fused_linear_cross_entropy) — the large-vocab LM
        # memory lever; honored by SingleTrainer and SPMDTrainer (the
        # trainers that train LM-shaped models), rejected loudly by the
        # rest (mirrors grad_accum_steps). True = default chunking; an
        # int picks the token-chunk count (passed through verbatim to
        # make_train_step, same contract).
        if fused_vocab_head and class_weight is not None:
            raise ValueError(
                "fused_vocab_head does not compose with class_weight: "
                "the fused loss never materializes the per-sample logits "
                "the class-weight wrapper scales. Drop one of the two.")
        self.fused_vocab_head = fused_vocab_head
        # telemetry (obs subsystem): None = auto-tape when obs is
        # enabled; False = off for this trainer; or pass a configured
        # obs.TrainingTape (e.g. with flops_per_example for MFU). The
        # live tape is exposed as ``self.tape`` during/after train();
        # its per-epoch logs (examples_per_sec, data_wait_s, device_s,
        # host_s, goodput, mfu, ...) merge into the callback logs.
        self.telemetry = telemetry
        self.tape = None
        self.stop_training = False
        self._weights_fn = None       # bound by trainers during train()
        self._pending_weights = None  # set via set_weights()
        # preemption (resilience PR): request_preempt() — signal-handler
        # safe (an Event set is async-signal tolerable) — asks the epoch
        # loop to checkpoint the CURRENT epoch and return cleanly;
        # ``preempted`` reports whether the last train() ended that way
        self._preempt = threading.Event()
        self.preempted = False

    def request_preempt(self) -> None:
        """Ask the running epoch loop to checkpoint and stop at the end
        of the current epoch (SIGTERM/preemption-notice path — see
        ``resilience.TrainingSupervisor``). Safe to call from a signal
        handler or another thread. The notice STANDS until an epoch
        loop acts on it (``epoch_exit`` consumes it), so a preemption
        delivered between a crash and the supervisor's resumed run is
        honored by that run's first epoch, never dropped."""
        self._preempt.set()

    def _epoch_exit(self, epoch: int, saved: bool, save_fn) -> bool:
        return epoch_exit(self, epoch, saved, save_fn)

    def _reject_step_options(self):
        """Trainers whose step semantics don't compose with the
        SingleTrainer/SPMDTrainer-only step options (gradient
        accumulation, the fused vocab head) must fail loudly rather than
        silently ignore them — the engine family counts WINDOW steps;
        ensembles/host-async have their own loops."""
        if self.grad_accum_steps != 1:
            raise ValueError(
                f"{type(self).__name__} does not support grad_accum_steps "
                "(only SingleTrainer and SPMDTrainer do)")
        if self.fused_vocab_head:
            raise ValueError(
                f"{type(self).__name__} does not support fused_vocab_head "
                "(only SingleTrainer and SPMDTrainer do)")

    def _param_mask(self, model):
        """Boolean mask honoring Keras-style ``layer.trainable = False``
        (``models.core.trainable_mask``); None when nothing is frozen."""
        from distkeras_tpu.models.core import trainable_mask
        return trainable_mask(model.module, model.params)

    def _state_mask(self, model):
        """Same, over the STATE tree (frozen BatchNorm keeps its running
        stats — Keras inference-mode semantics)."""
        from distkeras_tpu.models.core import trainable_mask
        return trainable_mask(model.module, model.state)

    def _checkpoint_manager(self):
        if self.checkpoint_dir is None:
            return None
        from distkeras_tpu.utils.checkpoint import CheckpointManager
        return CheckpointManager(self.checkpoint_dir,
                                 async_writes=self.checkpoint_async)

    def _maybe_resume(self, manager, template):
        """Restore the checkpointed tree (same structure as ``template``).
        Returns ``(tree, start_epoch)``; the step is fixed once so weights
        and metadata always come from the SAME checkpoint.

        Multi-process: only process 0 reads (it is also the only writer —
        see the save path), and the restored tree + start epoch broadcast
        to every process, so resume stays consistent even when
        ``checkpoint_dir`` is host-local disk."""
        if manager is None or not self.resume:
            return template, 0
        if jax.process_count() > 1:
            tree, start = template, 0
            if jax.process_index() == 0:
                tree, start = self._restore_local(manager, template)
            from jax.experimental import multihost_utils
            tree = multihost_utils.broadcast_one_to_all(tree)
            start = int(multihost_utils.broadcast_one_to_all(
                np.int32(start)))
            # resume path, runs once before the loop starts
            return jax.device_get(tree), start  # lint: allow-host-sync
        return self._restore_local(manager, template)

    @staticmethod
    def _restore_local(manager, template):
        latest = manager.latest_step()
        if latest is None:
            return template, 0
        tree = manager.restore(template, step=latest)
        meta = manager.metadata(step=latest)
        return tree, int(meta.get("epoch", -1)) + 1

    def _should_checkpoint(self, epoch: int) -> bool:
        return ((epoch + 1) % self.checkpoint_every == 0
                or epoch == self.num_epoch - 1)

    def _profile_ctx(self):
        if self.profile_dir is None:
            import contextlib
            return contextlib.nullcontext()
        from distkeras_tpu.utils.profiling import trace
        return trace(self.profile_dir)

    def _make_tape(self, unit: str = "examples"):
        """Bind this run's telemetry tape (obs.NULL_TAPE when disabled:
        every hook is a no-op, so the epoch loops stay branch-free)."""
        from distkeras_tpu.obs import resolve_tape
        self.tape = resolve_tape(self.telemetry, type(self).__name__,
                                 unit)
        return self.tape

    # -- reference-parity bookkeeping -------------------------------------
    def record_training_start(self):
        self.history.record_training_start()

    def record_training_stop(self):
        self.history.record_training_stop()

    def get_training_time(self) -> float:
        return self.history.get_training_time()

    def get_history(self) -> History:
        return self.history

    def get_averaged_history(self) -> np.ndarray:
        """Per-step losses averaged over workers (scalar per step)."""
        losses = self.history.losses()
        return losses.mean(axis=-1) if losses.ndim > 1 else losses

    def serialize(self):
        """Reference: ``Trainer.serialize`` — serialized master model."""
        return serialize_model(self.master_model)

    def _metric_fns(self):
        """{name: fn} for the constructor's ``metrics`` list (reference:
        Keras ``model.compile(metrics=...)`` per worker), or None."""
        if not self.metrics:
            return None
        from distkeras_tpu.ops.metrics import get_metric, metric_name
        return {metric_name(m): get_metric(m) for m in self.metrics}

    @staticmethod
    def _split_outs(outs):
        """Scan outputs -> (losses, metrics_dict) for either step shape."""
        if isinstance(outs, tuple):
            return outs[0], outs[1]
        return outs, {}

    # -- callbacks ----------------------------------------------------------
    def _cb_list(self, weights_fn: Optional[Callable] = None):
        """Bind callbacks for a fresh train() run. ``weights_fn`` returns
        host-side ``(params, state)`` of the CURRENT training weights (each
        trainer supplies its own view — carry, engine center, ...)."""
        from distkeras_tpu.utils.callbacks import CallbackList
        self.stop_training = False
        # NOT clearing self._preempt here: a standing preemption notice
        # (e.g. SIGTERM delivered while the supervisor was mid-restart)
        # must stop the next run; epoch_exit consumes it when acted on
        self.preempted = False
        self._pending_weights = None
        self._weights_fn = weights_fn
        cbs = CallbackList(self.callbacks, self)
        cbs.train_begin()
        return cbs

    def _epoch_logs(self, losses, mets, extra) -> dict:
        """Per-epoch scalar logs for callbacks: epoch-mean loss/metrics +
        validation scalars. Inputs are host arrays (already fetched)."""
        logs = {"loss": float(np.mean(np.asarray(losses)))}
        for k, v in mets.items():
            logs[k] = float(np.mean(np.asarray(v)))
        for k, v in extra.items():
            logs[k] = float(np.asarray(v).ravel()[0])
        return logs

    def get_weights(self):
        """Host-side ``(params, state)`` of the in-progress training weights
        (callback API; only valid while train() is running)."""
        if self._weights_fn is None:
            raise RuntimeError(
                "get_weights() is only available to callbacks while "
                "train() is running")
        return self._weights_fn()

    def set_weights(self, params, state) -> None:
        """Replace the weights the trainer will return (callback API —
        e.g. EarlyStopping(restore_best_weights=True))."""
        self._pending_weights = (params, state)

    def snapshot_model(self) -> Model:
        """A Model carrying the current training weights (callback API)."""
        params, state = self.get_weights()
        m = self.master_model
        return Model(m.module, params, state, m.input_shape, m.output_shape)

    def _apply_pending_weights(self, trained: Model) -> Model:
        if self._pending_weights is None:
            return trained
        params, state = self._pending_weights
        return trained.replace(params=params, state=state)

    def _reject_callbacks(self):
        if self.callbacks:
            raise ValueError(
                f"{type(self).__name__} does not support callbacks (no "
                "single evolving model to monitor)")

    # -- validation ---------------------------------------------------------
    def _validation_arrays(self):
        if self.validation_data is None:
            return None
        vd = self.validation_data
        if isinstance(vd, Dataset):
            return vd.arrays(self.features_col, self.label_col)
        X, y = vd
        from distkeras_tpu.data.dataset import coerce_column
        return coerce_column(X), coerce_column(y)

    def _device_validation_arrays(self, Xv, yv):
        return cache_validation_on_device(self, Xv, yv)

    def _record_placement(self, name: str, tree) -> None:
        self.placement[name] = sorted(
            {shard.device.id for leaf in jax.tree_util.tree_leaves(tree)
             for shard in leaf.addressable_shards})

    def _trace_scope(self):
        """Context the trainer's jitted programs are TRACED under.
        Trainers whose programs GSPMD partitions over a mesh override
        it (``SPMDTrainer``: kernels run per shard)."""
        return contextlib.nullcontext()

    def _make_validator(self, module):
        """Jitted full-set eval: ``validator(params, state) ->
        {"val_loss": ..., "val_<metric>": ...}`` (scalars). Built once; the
        validation set must fit device memory (use a subsample otherwise).
        """
        val = self._validation_arrays()
        if val is None:
            return None
        Xv, yv = val
        loss_fn = self.eval_loss  # unweighted even under class_weight
        metric_fns = self._metric_fns() or {}

        # the arrays are jit ARGUMENTS (not closure captures) so the whole
        # validation set is not constant-folded into the executable; the
        # device cache places them ONCE per dataset — across epochs AND
        # across train() calls (supervisor restarts)
        Xv, yv = self._device_validation_arrays(Xv, yv)

        @jax.jit
        def evalf(params, state, Xv, yv):
            with self._trace_scope():
                out, _ = module.apply(params, state, Xv, training=False)
            res = {"val_loss": loss_fn(yv, out)}
            for name, fn in metric_fns.items():
                res[f"val_{name}"] = fn(yv, out)
            return res

        return lambda params, state: evalf(params, state, Xv, yv)

    # -- out-of-core plumbing ----------------------------------------------
    def _sharded_stream(self, sds, start_epoch: int, place=None):
        """ONE Prefetcher over the flattened (epoch, shard) sequence of a
        ``ShardedDataset`` (``ShardedDataset.epoch_items``): yields
        ``((epoch, shard_idx, is_epoch_last), (Xs, Ys, n_steps))``. A
        single flat stream keeps the background loader busy ACROSS epoch
        boundaries (a per-epoch prefetcher would stall one shard-load at
        every boundary), and one definition keeps the shuffle determinism
        formula shared by every sharded trainer. ``place`` stages each
        stacked chunk onto device ON THE LOADER THREAD
        (``prefetch.device_stager``) with a 2-deep device buffer —
        consumers receive device-resident batches (docs/overlap.md)."""
        from distkeras_tpu.utils.prefetch import Prefetcher
        items = sds.epoch_items(start_epoch, self.num_epoch, self.seed,
                                self.shuffle_each_epoch)

        from distkeras_tpu.resilience.retry import io_retry
        fetch_retry = io_retry()

        def assemble(item):
            epoch, si, _ = item

            def fetch():
                # chaos hook + transient-IO retry: a flaky shard read
                # (NFS blip, injected "data.fetch" fault) costs a
                # jittered backoff on the loader thread, not the run
                faults.point("data.fetch")
                return sds.load_shard(si)

            Xc, yc = self._training_arrays(
                fetch_retry.call(fetch, op="data.fetch"))
            perm = None
            if self.shuffle_each_epoch:
                perm = np.random.RandomState(
                    self.seed + 1000 * epoch + 31 * si).permutation(len(Xc))
            return stack_batches(Xc, yc, self.batch_size, perm)

        return Prefetcher(assemble, items, depth=2 if place else 1,
                          place=place)

    # -- data plumbing -----------------------------------------------------
    def _training_arrays(self, dataset: Dataset):
        from distkeras_tpu.data.sharded import ShardedDataset
        if isinstance(dataset, ShardedDataset):
            raise ValueError(
                f"{type(self).__name__} does not support ShardedDataset "
                "(out-of-core training is a SingleTrainer/SPMDTrainer "
                "capability); load shards into one Dataset, or switch "
                "trainer")
        X, y = dataset.arrays(self.features_col, self.label_col)
        if y is None:
            raise ValueError(
                f"label column {self.label_col!r} not in dataset "
                f"(columns: {dataset.columns})")
        return X, y

    def _epoch_perm(self, epoch: int, n: int):
        if not self.shuffle_each_epoch:
            return None
        return np.random.RandomState(self.seed + 1000 * epoch).permutation(n)

    def train(self, dataset: Dataset) -> Model:
        raise NotImplementedError


class SingleTrainer(Trainer):
    """Single-device training — the minimum end-to-end slice.

    Reference: ``trainers.py :: SingleTrainer.train`` coalesces the DataFrame
    to one partition and runs a SequentialWorker's per-batch Keras loop there
    (SURVEY §3.1). Here the whole epoch is ONE jitted ``lax.scan`` over
    ``[steps, batch, ...]`` stacked columnar data.
    """

    def train(self, dataset: Dataset) -> Model:
        from distkeras_tpu.data.sharded import ShardedDataset
        from distkeras_tpu.utils.prefetch import Prefetcher
        model = self.master_model
        sharded = isinstance(dataset, ShardedDataset)
        tape = self._make_tape()
        # train.setup: everything before the first train.dispatch
        # (staging, the epoch runner, the starting carry and its copy);
        # the first dispatch compiles or loads the epoch program, and
        # obs.compile_log() says in which span
        with tape.span("setup"):
            if not sharded:
                X, y = self._training_arrays(dataset)
            step = make_train_step(
                model.module, self.loss, self.worker_optimizer,
                self._metric_fns(), self.grad_accum_steps,
                param_mask=self._param_mask(model),
                state_mask=self._state_mask(model),
                fused_vocab_head=self.fused_vocab_head)
            runner = make_epoch_runner(step)

            # SingleTrainer checkpoints the FULL carry (params + model state +
            # optimizer state + rng), so a resumed run is bitwise-identical to
            # an uninterrupted one. (Distributed trainers checkpoint the center
            # only — the documented PS-retry semantic.)
            manager = self._checkpoint_manager()
            fresh = {"params": model.params, "state": model.state,
                     "opt": self.worker_optimizer.init(model.params),
                     "rng": jax.random.PRNGKey(self.seed)}
            tree, start_epoch = self._maybe_resume(manager, fresh)
            # The runner DONATES its carry, and the trainer owns neither
            # carry it starts from: a fresh one holds the caller's
            # ``model.params``, a resumed one np.load'd host memory that a
            # zero-copy placement would alias (spmd.py, at its own copy, has
            # what donating that does to the heap). ONE jitted copy puts
            # every leaf into a device buffer XLA owns, before anything is
            # donated: the caller's Model stays readable, and the first
            # epoch's runner signature equals every later one's (a numpy
            # carry first and a device carry next would add a second
            # jit-cache entry and false-positive the recompile detector).
            # From here on every epoch updates the carry in place; nothing
            # may keep a carry, or a leaf of one, across a ``runner`` call.
            carry = jax.jit(lambda c: jax.tree_util.tree_map(jnp.copy, c))(
                TrainCarry(params=tree["params"], state=tree["state"],
                           opt_state=tree["opt"], rng=tree["rng"]))
            del fresh, tree   # the uncopied optimizer state goes now
            # after the first epoch's legitimate compiles, any cache growth
            # on the epoch program is a shape leak (warned via check() in
            # tape.epoch_end)
            tape.watch("SingleTrainer.epoch", runner, donated=carry)

            from distkeras_tpu.utils.prefetch import device_stager
            if sharded:
                # out-of-core: compiled scan per shard; ONE flat prefetch
                # stream spans epoch boundaries so the loader never idles
                # (Trainer._sharded_stream; reference analogue: Spark workers
                # iterate HDFS partition rows — workers.py :: Worker.train);
                # the loader thread also stages each chunk onto device
                stream = self._sharded_stream(dataset, start_epoch,
                                              place=device_stager())
            else:
                # in-memory: ONE chunk per epoch; epoch e+1's shuffle gather,
                # stacking AND device staging run while the device trains
                # epoch e. depth=1 here — a chunk is the WHOLE stacked
                # epoch, and one-ahead already gives full overlap; deeper
                # buffering would only multiply dataset copies in device
                # memory (docs/overlap.md)
                stream = (((e, 0, True), chunk) for e, chunk in Prefetcher(
                    lambda e: stack_batches(X, y, self.batch_size,
                                            self._epoch_perm(e, len(X))),
                    range(start_epoch, self.num_epoch), depth=1,
                    place=device_stager()))

            validator = self._make_validator(model.module)
            cbs = self._cb_list(  # callback API: an explicit user-facing fetch
                lambda: jax.device_get(  # lint: allow-host-sync
                    (carry.params, carry.state)))
        self.record_training_start()
        tape.train_begin()
        try:
            with self._profile_ctx():
                from distkeras_tpu.obs import timed_stream
                l_acc, m_acc = [], []
                examples = 0

                def save_now(epoch):
                    with tape.phase("checkpoint"):
                        manager.save(
                            epoch,
                            {"params": carry.params,
                             "state": carry.state,
                             "opt": carry.opt_state, "rng": carry.rng},
                            metadata={"epoch": epoch})

                from distkeras_tpu.parallel.engine import host_async
                for (epoch, _, last), (Xs, Ys, S) in timed_stream(stream,
                                                                  tape):
                    # chaos hook: a mid-training crash at an arbitrary
                    # loop iteration (tests/test_resilience.py)
                    faults.point("train.epoch")
                    with tape.phase("device", "dispatch"):
                        carry, outs = runner(carry, Xs, Ys)
                        # per-step loss/metric arrays STAY ON DEVICE for
                        # the whole epoch — only the D2H transfer is
                        # started here (non-blocking), so a multi-shard
                        # epoch no longer pays one blocking round trip
                        # per shard (overlap PR)
                        losses, mets = self._split_outs(outs)
                        host_async((losses, mets))
                        l_acc.append(losses)
                        m_acc.append(mets)
                    examples += int(S) * self.batch_size
                    if not last:
                        continue
                    with tape.phase("device", "fetch"):
                        # ONE epoch-boundary fetch of everything the
                        # epoch accumulated (transfers already in
                        # flight); blocking here also bounds the device
                        # phase through the last dispatched program
                        l_acc, m_acc = jax.device_get(  # lint: allow-host-sync
                            (l_acc, m_acc))
                    # history, logs and callbacks: what the tape derives as
                    # ``host_s``; validation and checkpoint nest inside
                    with tape.span("epoch_end"):
                        # chaos hook: NaN-poison the epoch losses the
                        # anomaly guard watches (history/logs downstream)
                        losses = faults.corrupt(
                            "train.loss", np.concatenate(l_acc))
                        mets = {k: np.concatenate([m[k] for m in m_acc])
                                for k in (m_acc[0] if m_acc else {})}
                        l_acc, m_acc = [], []
                        extra = {}
                        if validator is not None:
                            with tape.phase("validation"):
                                extra = val_logs(validator(carry.params,
                                                           carry.state))
                        self.history.append_epoch(loss=losses, **mets,
                                                  **extra)
                        saved = False
                        if manager is not None \
                                and self._should_checkpoint(epoch):
                            save_now(epoch)
                            saved = True
                        logs = self._epoch_logs(losses, mets, extra)
                        logs.update(tape.epoch_end(examples))
                        examples = 0
                        if epoch == start_epoch:
                            # first full epoch saw every legitimate shape
                            tape.mark_warm()
                        cbs.epoch_end(epoch, logs)
                    if self._epoch_exit(
                            epoch, saved,
                            save_now if manager is not None else None):
                        break
        finally:
            self.record_training_stop()
            tape.train_end()
            cbs.train_end()  # closes callback resources on exceptions too
        if manager is not None:
            manager.wait()  # async snapshots durable before return

        trained = model.replace(  # end-of-train fetch of the result
            params=jax.device_get(carry.params),  # lint: allow-host-sync
            state=jax.device_get(carry.state))    # lint: allow-host-sync
        trained = self._apply_pending_weights(trained)
        self.master_model = trained
        return trained


class EnsembleTrainer(Trainer):
    """Trains ``num_models`` independent models in parallel via ``vmap``.

    Reference: ``trainers.py :: EnsembleTrainer`` trains k independent Keras
    models on k Spark partition groups. TPU-native: the k model replicas are
    ONE stacked pytree trained by a vmapped scan — XLA batches the k small
    matmuls into bigger MXU ops. Each replica gets its own init seed, its own
    dropout stream, and its own per-epoch data permutation.
    """

    def __init__(self, keras_model: Model, num_models: int = 2, **kwargs):
        super().__init__(keras_model, **kwargs)
        self.num_models = int(num_models)
        self.models_: List[Model] = []

    def train(self, dataset: Dataset) -> List[Model]:
        self._reject_step_options()
        self._reject_callbacks()
        if self.validation_data is not None:
            raise ValueError(
                "EnsembleTrainer does not support validation_data (k "
                "independent members have no single validation score); "
                "evaluate members individually after train()")
        base = self.master_model
        X, y = self._training_arrays(dataset)
        k = self.num_models

        # independent inits: re-init the module with k different seeds
        inits = [Model.build(base.module, base.input_shape, seed=self.seed + i)
                 for i in range(k)]
        params = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[m.params for m in inits])
        state = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[m.state for m in inits])
        opt_state = jax.vmap(self.worker_optimizer.init)(params)
        rngs = jax.random.split(jax.random.PRNGKey(self.seed), k)

        step = make_train_step(base.module, self.loss, self.worker_optimizer,
                               self._metric_fns(),
                               param_mask=self._param_mask(base),
                               state_mask=self._state_mask(base))

        @jax.jit
        def run_epoch(carry, Xk, Yk):
            def per_model(c, xy):
                return jax.lax.scan(step, c, xy)
            return jax.vmap(per_model)(carry, (Xk, Yk))

        carry = TrainCarry(params, state, opt_state, rngs)
        self.record_training_start()
        for epoch in range(self.num_epoch):
            stacked = [stack_batches(
                X, y, self.batch_size,
                np.random.RandomState(self.seed + 1000 * epoch + i)
                .permutation(len(X)) if self.shuffle_each_epoch else None)
                for i in range(k)]
            Xk = np.stack([s[0] for s in stacked])  # [k, steps, bs, ...]
            Yk = np.stack([s[1] for s in stacked])
            carry, outs = run_epoch(carry, Xk, Yk)
            losses, mets = self._split_outs(outs)
            # [k, steps] -> record as [steps, k]; epoch-boundary fetch
            self.history.append_epoch(
                loss=jax.device_get(losses).T,  # lint: allow-host-sync
                **{n: jax.device_get(v).T       # lint: allow-host-sync
                   for n, v in mets.items()})
        self.record_training_stop()

        # end-of-train result fetch
        params_h = jax.device_get(carry.params)  # lint: allow-host-sync
        state_h = jax.device_get(carry.state)    # lint: allow-host-sync
        self.models_ = [
            base.replace(
                params=jax.tree_util.tree_map(lambda p: p[i], params_h),
                state=jax.tree_util.tree_map(lambda s: s[i], state_h))
            for i in range(k)]
        # master model = first member (reference returns the model list; we
        # keep both: return list, stash members on .models_)
        self.master_model = self.models_[0]
        return self.models_
