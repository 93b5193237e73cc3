"""SPMDTrainer — synchronous data×tensor×expert-parallel training via GSPMD.

No reference equivalent: dist-keras workers each hold a full model replica
(SURVEY §2.3 — TP/EP rows are "absent in the reference"). This trainer is
the capability ADD that trains models larger than one chip's HBM, and the
scaling path for the north-star config: params are sharded by the rules in
``parallel/sharding.py`` (Megatron column→row TP, expert-axis EP, optional
ZeRO/FSDP), the batch is sharded over the data axes, and ONE ``jax.jit``
over the whole epoch scan lets XLA's GSPMD partitioner place every
collective (all-reduce of grads over data axes, all-gather/reduce-scatter
around TP matmuls) on ICI.

Contrast with ``parallel/engine.py``: the engine reproduces the reference's
*algorithm family* (async PS semantics) with replicated models under
``shard_map``; SPMDTrainer is plain synchronous SGD but composes every
sharding dimension. Use the engine for DOWNPOUR/EASGD parity, SPMDTrainer
for big models.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu.data.dataset import Dataset
from distkeras_tpu.models.core import Model
from distkeras_tpu.parallel.engine import host_fetch
from distkeras_tpu.resilience import faults
from distkeras_tpu.parallel.sharding import named_shardings, param_specs
from distkeras_tpu.parallel.trainers import Trainer
from distkeras_tpu.parallel.worker import (TrainCarry, make_train_step,
                                           stack_batches)


class SPMDTrainer(Trainer):
    """Synchronous large-model trainer over an N-D mesh.

    ``mesh`` axes: data axes (``data_axes``, default ``("workers",)``) shard
    the batch; ``tp_axis``/``ep_axis`` shard params per
    ``sharding.ShardingRules``; ``fsdp_axis`` (usually the data axis itself)
    ZeRO-shards remaining large kernels. ``batch_size`` is the GLOBAL batch.
    """

    def __init__(self, keras_model: Model, mesh: Optional[Mesh] = None,
                 data_axes: Union[str, Sequence[str]] = ("workers",),
                 tp_axis: Optional[str] = "tp",
                 ep_axis: Optional[str] = None,
                 fsdp_axis: Optional[str] = None,
                 sharded_checkpoints: bool = True, **kwargs):
        super().__init__(keras_model, **kwargs)
        #: per-shard checkpoint files (utils.checkpoint.
        #: ShardedCheckpointManager): saves write only addressable shards,
        #: restores device_put shard-by-shard — the full tree never lands
        #: on one host (this trainer exists for models where it can't).
        #: Requires checkpoint_dir on SHARED storage under multi-process.
        self.sharded_checkpoints = bool(sharded_checkpoints)
        if mesh is None:
            from distkeras_tpu.parallel.mesh import make_mesh
            mesh = make_mesh()
        self.mesh = mesh
        if isinstance(data_axes, str):
            data_axes = (data_axes,)
        unknown = [a for a in data_axes if a not in mesh.shape]
        if unknown:
            # unlike tp/ep (where replicated fallback is documented), a
            # missing data axis silently disables data parallelism — fail
            raise ValueError(
                f"data_axes {unknown} not in mesh axes "
                f"{tuple(mesh.shape)}")
        self.data_axes = tuple(data_axes)
        self._epoch_fn = self._epoch_args = None     # see lower_epoch
        self.tp_axis = tp_axis
        self.ep_axis = ep_axis
        self.fsdp_axis = fsdp_axis
        dp = int(np.prod([mesh.shape[a] for a in self.data_axes])) \
            if self.data_axes else 1
        if self.batch_size % max(dp, 1):
            raise ValueError(
                f"global batch_size {self.batch_size} must divide evenly "
                f"over data axes {self.data_axes} (size {dp})")

    def _trace_scope(self):
        # XLA cannot partition a Mosaic kernel: the flash kernel runs
        # per shard of the batch (data axes) and heads (tp axis)
        from distkeras_tpu.ops.flash_attention import partitioned
        return partitioned(self.mesh, self.data_axes, self.tp_axis)

    def lower_epoch(self):
        """The epoch program of the last ``train()`` as it was called
        (``jax.stages.Lowered``, arguments by shape and sharding):
        ``.compile().as_text()`` shows what each partition holds — the
        kernels and the collectives GSPMD placed."""
        if self._epoch_fn is None:
            raise RuntimeError("lower_epoch() needs a train() first")
        return self._epoch_fn.lower(*self._epoch_args)

    # -- sharding plumbing --------------------------------------------------
    def _placements(self, model: Model):
        specs = param_specs(model.module, model.params, self.mesh,
                            tp_axis=self.tp_axis, ep_axis=self.ep_axis,
                            fsdp_axis=self.fsdp_axis)
        param_sh = named_shardings(specs, self.mesh)
        repl = NamedSharding(self.mesh, P())
        data_sh = NamedSharding(
            self.mesh, P(None, self.data_axes or None))  # [S, B, ...]
        return param_sh, repl, data_sh

    def param_partition_specs(self, model: Optional[Model] = None):
        """The PartitionSpec tree this trainer uses (introspection/tests)."""
        model = model or self.master_model
        return param_specs(model.module, model.params, self.mesh,
                           tp_axis=self.tp_axis, ep_axis=self.ep_axis,
                           fsdp_axis=self.fsdp_axis)

    # -- resume plumbing ----------------------------------------------------
    def _checkpoint_manager(self):
        if self.checkpoint_dir is None:
            return None
        if self.sharded_checkpoints:
            if self.checkpoint_async:
                raise ValueError(
                    "checkpoint_async is not supported with "
                    "sharded_checkpoints: the sharded save runs "
                    "multi-process barriers that must stay on the training "
                    "thread. Pass sharded_checkpoints=False to keep async "
                    "dense snapshots.")
            from distkeras_tpu.utils.checkpoint import \
                ShardedCheckpointManager
            return ShardedCheckpointManager(self.checkpoint_dir)
        return super()._checkpoint_manager()

    def _opt_shardings(self, params_host, param_sh, repl):
        """Shardings for the optimizer state: moment subtrees that mirror
        the params tree get the params' shardings (moments live WITH their
        params); anything else (step counters) replicates. Used both to
        constrain the fresh ``jit(init)`` (GSPMD would otherwise be free to
        shard unconstrained zeros however it likes) and to place restored
        checkpoint shards — keeping save and restore layouts identical."""
        opt_shapes = jax.eval_shape(self.worker_optimizer.init, params_host)
        pstruct = jax.tree_util.tree_structure(params_host)
        rmap = lambda tree: jax.tree_util.tree_map(lambda _: repl, tree)
        mirror = lambda sub: param_sh if jax.tree_util.tree_structure(
            sub) == pstruct else rmap(sub)
        if isinstance(opt_shapes, dict):
            return {k: mirror(v) for k, v in opt_shapes.items()}
        return rmap(opt_shapes)

    def _restore_sharded(self, manager, model: Model, param_sh, repl):
        """Device-direct resume: build the sharding tree matching the saved
        carry and let the manager place every stored shard. Returns
        ``(device carry tree | None, start_epoch)``. Old dense or
        params-only checkpoints restore too (full-copy slicing / fresh
        moments)."""
        if manager is None or not self.resume:
            return None, 0
        latest = manager.latest_step()
        if latest is None:
            return None, 0
        keys = manager.keys(latest) or []
        full_carry = any(k == "rng" or k.startswith("rng/") for k in keys)

        rmap = lambda tree: jax.tree_util.tree_map(lambda _: repl, tree)
        shardings = {"params": param_sh, "state": rmap(model.state)}
        if full_carry:
            shardings["opt"] = self._opt_shardings(model.params, param_sh,
                                                   repl)
            shardings["rng"] = repl
        else:
            import warnings
            warnings.warn(
                "checkpoint predates the full-carry format; restoring "
                "params/state only (optimizer moments and rng restart "
                "fresh)", stacklevel=2)
        tree = manager.restore_sharded(shardings, step=latest)
        meta = manager.metadata(step=latest)
        start = int(meta.get("epoch", -1)) + 1
        return (tree if start > 0 else None), start

    def _ckpt_format(self, manager) -> int:
        """0: no checkpoint; 1: old params/state-only; 2: full carry.

        Detected by the rng key, not the opt keys: an EMPTY optimizer state
        (plain sgd) flattens to no ``opt/`` entries at all, but every
        full-carry snapshot stores ``rng``."""
        latest = manager.latest_step()
        if latest is None:
            return 0
        ks = manager.keys(latest) or []
        return 2 if any(k == "rng" or k.startswith("rng/") or k == "opt"
                        or k.startswith("opt/") for k in ks) else 1

    def _restore_full_carry(self, manager, model: Model):
        """Returns ``(restored_host_tree | None, start_epoch)``.

        The restore template's optimizer slot is host-numpy zeros built from
        ``jax.eval_shape`` — nothing touches a device until placement. Old
        checkpoints written before the full-carry format (params/state only)
        restore with a warning and fresh optimizer moments. The format is
        detected from the manifest and broadcast BEFORE the collective
        restore, so every process enters ``_maybe_resume`` with the SAME
        template structure (detecting via try/except on process 0 alone
        would desynchronize the broadcast).
        """
        if manager is None or not self.resume:
            return None, 0
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            flag = np.int32(self._ckpt_format(manager)
                            if jax.process_index() == 0 else 0)
            flag = int(multihost_utils.broadcast_one_to_all(flag))
        else:
            flag = self._ckpt_format(manager)
        if flag == 0:
            return None, 0

        host_zeros = jax.tree_util.tree_map(
            lambda s: np.zeros(s.shape, s.dtype),
            jax.eval_shape(self.worker_optimizer.init, model.params))
        fresh_rng = np.asarray(jax.random.PRNGKey(self.seed))
        template = {"params": model.params, "state": model.state}
        if flag == 2:
            template.update(opt=host_zeros, rng=fresh_rng)
        else:
            import warnings
            warnings.warn(
                "checkpoint predates the full-carry format; restoring "
                "params/state only (optimizer moments and rng restart "
                "fresh)", stacklevel=2)
        tree, start_epoch = self._maybe_resume(manager, template)
        if flag == 1:
            # fresh moments are zeros for every optimizer in the registry,
            # so the host-zeros stand-in IS the fresh state
            tree = {**tree, "opt": host_zeros, "rng": fresh_rng}
        return (tree if start_epoch > 0 else None), start_epoch

    def _place_opt(self, opt_host, host_params, param_sh):
        """Place restored optimizer state: subtrees that mirror the params
        structure (momentum/adam moments) are device_put shard-by-shard with
        the params' shardings; anything else (step counters) goes up as
        uncommitted scalars."""
        pstruct = jax.tree_util.tree_structure(host_params)

        def place(sub):
            if jax.tree_util.tree_structure(sub) == pstruct:
                return jax.tree_util.tree_map(jax.device_put, sub, param_sh)
            return jax.tree_util.tree_map(jnp.asarray, sub)

        if isinstance(opt_host, dict):
            return {k: place(v) for k, v in opt_host.items()}
        return jax.tree_util.tree_map(jnp.asarray, opt_host)

    # -- training -----------------------------------------------------------
    def train(self, dataset: Dataset) -> Model:
        from distkeras_tpu.data.sharded import ShardedDataset
        model = self.master_model
        sharded = isinstance(dataset, ShardedDataset)
        tape = self._make_tape()
        # train.setup: everything before the first train.dispatch (as
        # in SingleTrainer.train)
        with tape.span("setup"):
            if not sharded:
                X, y = self._training_arrays(dataset)
            param_sh, repl, data_sh = self._placements(model)

            # full-carry checkpoint (params + model state + optimizer moments +
            # rng) so a resumed run is bitwise-identical to an uninterrupted
            # one — same contract as SingleTrainer
            manager = self._checkpoint_manager()
            if self.sharded_checkpoints:
                restored, start_epoch = self._restore_sharded(
                    manager, model, param_sh, repl)
            else:
                restored, start_epoch = self._restore_full_carry(
                    manager, model)

            if restored is None:
                # fresh start: shard params first, then init the optimizer
                # UNDER jit so the moments are created already sharded/lazy —
                # never materialized whole on one device
                params = jax.tree_util.tree_map(jax.device_put, model.params,
                                                param_sh)
                state = jax.device_put(model.state, repl)
                opt_state = jax.jit(
                    self.worker_optimizer.init,
                    out_shardings=self._opt_shardings(model.params, param_sh,
                                                      repl))(params)
                rng = jax.device_put(jax.random.PRNGKey(self.seed), repl)
            elif self.sharded_checkpoints:
                # already device-resident with the right shardings; fill any
                # missing slots (params-only legacy checkpoints)
                params = restored["params"]
                state = restored["state"]
                opt_state = restored.get("opt")
                if opt_state is None:
                    opt_state = jax.jit(
                        self.worker_optimizer.init,
                        out_shardings=self._opt_shardings(
                            model.params, param_sh, repl))(params)
                rng = restored.get("rng")
                if rng is None:
                    rng = jax.device_put(jax.random.PRNGKey(self.seed), repl)
            else:
                params = jax.tree_util.tree_map(jax.device_put,
                                                restored["params"], param_sh)
                state = jax.device_put(restored["state"], repl)
                opt_state = self._place_opt(restored["opt"], model.params,
                                            param_sh)
                rng = jax.device_put(jnp.asarray(restored["rng"]), repl)
            carry = TrainCarry(params, state, opt_state, rng)
            self._record_placement("params", params)

            step = make_train_step(
                model.module, self.loss, self.worker_optimizer,
                self._metric_fns(), self.grad_accum_steps,
                param_mask=self._param_mask(model),
                state_mask=self._state_mask(model),
                fused_vocab_head=self.fused_vocab_head)

            # pin the carry's layout across epochs: GSPMD is otherwise free to
            # re-shard unconstrained outputs (e.g. row-shard a replicated
            # param's adam moment), which would drift the layout away from
            # what _opt_shardings promised the checkpoint format
            rmap = lambda tree: jax.tree_util.tree_map(lambda _: repl, tree)
            carry_sh = TrainCarry(
                param_sh, rmap(model.state),
                self._opt_shardings(model.params, param_sh, repl), repl)

            if restored is not None:
                # A restored carry can hold leaves whose device buffers ALIAS
                # host numpy memory: a sharded device_put of a host array
                # zero-copy-aliases the numpy buffer on this CPU client (each
                # shard's device pointer is a slice of the host allocation —
                # verified), and both restore paths device_put np.load'd
                # trees. run_epoch donates the carry, so XLA would reuse/free
                # buffers it does not own — intermittent heap corruption
                # (`free(): corrupted unsorted chunks` aborts on the resume
                # path; ~3-in-4 before this copy, 0 after). A non-donated
                # jitted copy rematerializes every leaf into XLA-owned
                # buffers once, before anything is donated.
                carry = jax.jit(
                    lambda c: jax.tree_util.tree_map(jnp.copy, c),
                    out_shardings=carry_sh)(carry)

            @partial(jax.jit, donate_argnums=(0,),
                     out_shardings=(carry_sh, None))
            def run_epoch(carry, Xs, Ys):
                with self._trace_scope():
                    return jax.lax.scan(step, carry, (Xs, Ys))

            tape.watch("SPMDTrainer.epoch", run_epoch)

            from distkeras_tpu.utils.prefetch import Prefetcher, device_stager
            validator = self._make_validator(model.module)
            cbs = self._cb_list(
                lambda: host_fetch((carry.params, carry.state)))

            # loader-thread staging with the TRAINER'S data sharding: the
            # epoch loop consumes batches already resident (or streaming)
            # across the data axes — no inline device_put on the training
            # thread (docs/overlap.md)
            stage = device_stager(data_sh)
            if sharded:
                # out-of-core (data.sharded.ShardedDataset): compiled scan per
                # shard; ONE flat prefetch stream spans epoch boundaries so the
                # loader thread never idles (Trainer._sharded_stream)
                stream = self._sharded_stream(dataset, start_epoch,
                                              place=stage)
            else:
                # in-memory: ONE chunk per epoch; the Prefetcher overlaps the
                # next epoch's shuffle+stack+H2D with this epoch's device
                # scan. depth=1: a chunk is the whole stacked epoch, and
                # one-ahead is full overlap — deeper only multiplies the
                # dataset's device-memory footprint
                stream = (((e, 0, True), chunk) for e, chunk in Prefetcher(
                    lambda e: stack_batches(X, y, self.batch_size,
                                            self._epoch_perm(e, len(X))),
                    range(start_epoch, self.num_epoch), depth=1, place=stage))

        self.record_training_start()
        tape.train_begin()
        try:
            with self._profile_ctx():
                from distkeras_tpu.obs import timed_stream
                l_acc, m_acc = [], []
                examples = 0

                def save_now(epoch):
                    carry_tree = {"params": carry.params,
                                  "state": carry.state,
                                  "opt": carry.opt_state,
                                  "rng": carry.rng}
                    with tape.phase("checkpoint"):
                        if self.sharded_checkpoints \
                                or jax.process_count() == 1:
                            # sharded: every process writes ITS shards
                            # (barriers inside), no host gather. Dense
                            # single-process: the manager's async-D2H
                            # snapshot fences the device tree itself
                            # (overlap PR) — transfers run concurrently,
                            # and with checkpoint_async the
                            # serialize+rename overlaps the next scan
                            manager.save(epoch, carry_tree,
                                         metadata={"epoch": epoch})
                        else:
                            # host_fetch is a COLLECTIVE under
                            # multi-process (allgather of
                            # non-addressable shards) — every process
                            # must enter it; only the write is gated
                            # on process 0
                            snapshot = host_fetch(carry_tree)
                            if jax.process_index() == 0:
                                manager.save(epoch, snapshot,
                                             metadata={"epoch": epoch})

                from distkeras_tpu.parallel.engine import host_async
                from distkeras_tpu.parallel.trainers import val_logs
                for (epoch, _, last), (Xs, Ys, S) in timed_stream(stream,
                                                                  tape):
                    # chaos hook: a mid-training crash at an arbitrary
                    # loop iteration (tests/test_resilience.py)
                    faults.point("train.epoch")
                    with tape.phase("device", "dispatch"):
                        # batches arrive device-resident from the
                        # loader thread (device_stager above); per-step
                        # losses/metrics stay on device until the
                        # epoch-boundary fetch (overlap PR)
                        self._record_placement("batch", Xs)
                        self._epoch_fn, self._epoch_args = run_epoch, \
                            jax.tree_util.tree_map(
                                lambda a: jax.ShapeDtypeStruct(
                                    a.shape, a.dtype, sharding=a.sharding),
                                (carry, Xs, Ys))
                        carry, outs = run_epoch(carry, Xs, Ys)
                        losses, mets = self._split_outs(outs)
                        host_async((losses, mets))
                        l_acc.append(losses)
                        m_acc.append(mets)
                    examples += int(S) * self.batch_size
                    if not last:
                        continue
                    with tape.phase("device", "fetch"):
                        # ONE boundary fetch (collective allgather under
                        # multi-process — same count/order on every
                        # process as the per-shard fetches it replaces)
                        l_acc, m_acc = host_fetch((l_acc, m_acc))
                    # history, logs and callbacks: what the tape derives as
                    # ``host_s``; validation and checkpoint nest inside
                    with tape.span("epoch_end"):
                        # chaos hook: NaN-poison the epoch losses the
                        # anomaly guard watches
                        losses = faults.corrupt(
                            "train.loss", np.concatenate(l_acc))
                        mets = {k: np.concatenate([m[k] for m in m_acc])
                                for k in (m_acc[0] if m_acc else {})}
                        l_acc, m_acc = [], []
                        extra = {}
                        if validator is not None:
                            with tape.phase("validation"):
                                extra = val_logs(host_fetch(validator(
                                    carry.params, carry.state)))
                        self.history.append_epoch(loss=losses, **mets,
                                                  **extra)
                        saved = False
                        if manager is not None \
                                and self._should_checkpoint(epoch):
                            save_now(epoch)
                            saved = True
                        # logs derive from replicated values, so every
                        # process sees identical callback decisions (incl.
                        # stop_training and any collective get_weights
                        # fetch inside a callback)
                        logs = self._epoch_logs(losses, mets, extra)
                        logs.update(tape.epoch_end(examples))
                        examples = 0
                        if epoch == start_epoch:
                            tape.mark_warm()
                        cbs.epoch_end(epoch, logs)
                    # preemption is delivered per-process (SIGTERM to the
                    # job hits every worker); the stop decision below
                    # must stay consistent across processes, which holds
                    # when the preemption notice reaches all of them
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

        trained = model.replace(params=host_fetch(carry.params),
                                state=host_fetch(carry.state))
        trained = self._apply_pending_weights(trained)
        self.master_model = trained
        return trained
