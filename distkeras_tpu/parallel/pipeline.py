"""Pipeline parallelism: GPipe microbatch schedule over a ``pp`` mesh axis.

No reference equivalent (SURVEY §2.3: PP is "absent in the reference" — a
dist-keras worker always holds the whole model). This is the TPU-native
capability ADD for models deeper than one chip: the repeated trunk of a
network (N identical transformer blocks) is stacked into one
``[num_layers, ...]`` params pytree and sharded over the ``pp`` axis, so
each device owns ``num_layers / pp`` consecutive layers. Microbatches flow
through the stages on a ``ppermute`` ring under ``shard_map``:

  tick t:  device 0 injects microbatch t; device i processes the activation
           it received at tick t-1 through its local layers (a ``lax.scan``
           over the stacked params); every device then permutes its output
           to device i+1. After ``M + P - 1`` ticks all M microbatches have
           drained; the last stage's outputs are psum-broadcast to the ring.

Everything — schedule, stage compute, collectives — is ONE jitted program;
the schedule is a ``lax.scan`` over ticks, so there is no per-tick Python.
The whole pipeline is differentiable (``ppermute``'s transpose is the
reverse permute), so the same function serves forward and backward; XLA
overlaps the permute with stage compute where possible.

Composes with the other axes: batch sharded over ``workers`` (dp), sequence
sharded over ``sp`` with ring attention inside the blocks, giving dp×pp×sp
in one program (see ``PipelinedLM.make_train_step`` and
``__graft_entry__.dryrun_multichip``).
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu.compat import shard_map
from distkeras_tpu.models.core import Layer
from distkeras_tpu.ops.optimizers import Optimizer, apply_updates

Pytree = Any


def init_stacked_blocks(block: Layer, rng: jax.Array,
                        input_shape: Tuple[int, ...], num_layers: int):
    """Init ``num_layers`` copies of ``block`` and stack the params along a
    leading layer axis. Blocks must be shape-preserving and stateless (no
    BatchNorm-style running stats) — the pipeline scan carries activations
    only."""
    ps, state = [], {}
    for k in jax.random.split(rng, num_layers):
        p, s, out_shape = block.init(k, tuple(input_shape))
        if tuple(out_shape) != tuple(input_shape):
            raise ValueError(
                f"pipeline blocks must preserve shape: {input_shape} -> "
                f"{out_shape}")
        if jax.tree_util.tree_leaves(s):
            raise ValueError(
                "pipeline blocks must be stateless (found non-empty state; "
                "BatchNorm-style layers are unsupported in the pipelined "
                "trunk — use LayerNorm/RMSNorm)")
        ps.append(p)
        state = s  # leafless structure template, passed back into apply
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ps), state


def make_pipeline_fn(block: Layer, axis_name: str = "pp",
                     state: Optional[Pytree] = None,
                     remat: bool = False,
                     virtual_stages: int = 1) -> Callable:
    """Returns ``fn(stacked_local_params, x_mb) -> y_mb`` for use under
    ``shard_map``: ``x_mb`` is ``[M, mb, ...]`` microbatched input
    (replicated over the pp axis), result likewise. ``state`` is the block's
    (leafless) state-structure template from ``init_stacked_blocks``.
    ``remat=True`` recomputes each layer's activations in the backward pass
    (peak memory O(1) per stage instead of O(layers/stage)).

    ``virtual_stages`` = v (round 4): the INTERLEAVED schedule. Each
    device's layers split into v chunks; global chunk j lives on device
    ``j % P``, so consecutive chunks are ring neighbors and the SAME
    ppermute ring carries the flow. Chunk j of microbatch m (grouped
    g = m//P, r = m%P; q = j//P) runs at tick

        T(m, j) = g*v*P + q*P + r + (j % P)

    — each activation is produced exactly one tick before its consumer
    needs it (T(m, j+1) - T(m, j) = 1 for both same-device wrap and
    cross-device hops), so no waiting buffers exist anywhere. Ticks
    total ``M*v + P - 1`` with each tick 1/v of a GPipe stage, giving
    bubble ``(P-1)/(M*v + P - 1)`` vs GPipe's ``(P-1)/(M + P - 1)``.
    v=1 IS the GPipe schedule (the formulas degenerate: q=0, m=t-d) —
    one code path serves both. Requires ``M % P == 0`` for v > 1
    (microbatches inject in groups of P; validated in make_train_step).

    **Params layout contract for v > 1** (advisor r4): GSPMD tiles the
    stacked layer axis CONTIGUOUSLY over the pp axis, so the stacked
    params this function receives must already be permuted into
    device-major/chunk-minor order — device d's slice holds its v chunks
    back to back, NOT the canonical layer order. Build the permutation
    with :func:`interleaved_params_perm` (``PipelinedLM.make_train_step``
    applies it at the jit boundary); passing canonically ordered stacked
    params with v > 1 silently assigns the wrong layers to each chunk.
    """
    state = {} if state is None else state
    v = int(virtual_stages)
    if v < 1:
        raise ValueError(f"virtual_stages must be >= 1, got {v}")

    def layer_apply(p, h):
        return block.apply(p, state, h, training=False)[0]

    if remat:
        layer_apply = jax.checkpoint(layer_apply)

    def stage(chunk_params, h):
        def body(h, p):
            return layer_apply(p, h), None
        h, _ = lax.scan(body, h, chunk_params)
        return h

    def fn(local_params, x_mb):
        nstages = lax.axis_size(axis_name)
        idx = lax.axis_index(axis_name)
        M = x_mb.shape[0]
        ticks = M * v + nstages - 1
        ring = [(j, (j + 1) % nstages) for j in range(nstages)]
        if v > 1 and M % nstages:
            raise ValueError(
                f"interleaved schedule (virtual_stages={v}) injects "
                f"microbatches in groups of P: M={M} must divide by the "
                f"pp axis size {nstages} (trailing microbatches would "
                "silently drain as zeros)")
        layers_local = jax.tree_util.tree_leaves(local_params)[0].shape[0]
        if layers_local % v:
            raise ValueError(
                f"per-device layer count {layers_local} must divide by "
                f"virtual_stages={v} (trailing layers would be silently "
                "skipped)")
        lpc = layers_local // v                       # layers per chunk

        def chunk_of(p, q):
            return jax.tree_util.tree_map(
                lambda leaf: lax.dynamic_slice_in_dim(leaf, q * lpc, lpc,
                                                      axis=0), p)

        def tick(carry, t):
            buf, outs = carry
            s = t - idx
            # mixed-radix decode of s = (g*v + q)*P + r  (garbage for the
            # bubble slots s < 0 / m >= M; masked below, and the clamps
            # keep every index in range)
            r = jnp.where(s >= 0, s % nstages, 0)
            gq = jnp.where(s >= 0, s // nstages, 0)
            q = gq % v
            m = (gq // v) * nstages + r
            inject = (idx == 0) & (q == 0)
            inp = jnp.where(inject, x_mb[jnp.clip(m, 0, M - 1)], buf)
            h = stage(chunk_of(local_params, q), inp)
            valid = ((s >= 0) & (m < M) & (q == v - 1)
                     & (idx == nstages - 1))
            cidx = jnp.clip(m, 0, M - 1)
            outs = outs.at[cidx].set(jnp.where(valid, h, outs[cidx]))
            buf = lax.ppermute(h, axis_name, ring)
            return (buf, outs), None

        buf = jnp.zeros_like(x_mb[0])
        outs = jnp.zeros_like(x_mb)
        (buf, outs), _ = lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # broadcast the drained outputs from the last stage to the ring
        outs = lax.psum(jnp.where(idx == nstages - 1, outs, 0.), axis_name)
        return outs

    return fn


def interleaved_params_perm(num_layers: int, pp: int,
                            virtual_stages: int) -> "np.ndarray":
    """Index permutation taking CANONICALLY stacked layer params (layer 0
    first) into the device-major/chunk-minor order
    :func:`make_pipeline_fn` requires when ``virtual_stages > 1``:
    position ``(d, q, l)`` of the permuted stack holds canonical layer
    ``(q*pp + d)*lpc + l`` (global chunk ``j = q*pp + d`` lives on device
    ``j % pp``; ``lpc = num_layers // (pp*virtual_stages)``). Apply with
    ``jnp.take(leaf, perm, axis=0)``; invert with ``np.argsort(perm)``
    for the gradient scatter. Exposed (advisor r4) so direct shard_map
    callers of ``make_pipeline_fn`` can honor the layout contract —
    ``PipelinedLM.make_train_step`` applies it at the jit boundary."""
    v = int(virtual_stages)
    if num_layers % (pp * v):
        raise ValueError(
            f"num_layers {num_layers} must divide evenly over pp={pp} x "
            f"virtual_stages={v}")
    lpc = num_layers // (pp * v)
    return np.array([(q * pp + d) * lpc + l
                     for d in range(pp)
                     for q in range(v)
                     for l in range(lpc)])


class PipelinedLM:
    """Embed -> pp-sharded block stack -> head, with a dp×pp(×sp) train step.

    ``embed``/``head`` are replicated (their grads psum over the pp axis —
    contributions are zero except on the inject/drain stages); the trunk is
    ``num_layers`` copies of ``block`` sharded over ``pp``.

    ``num_microbatches`` default changed 2 → 4 in round 3 (GPipe bubble
    at P=2: 33% → 20%; see ``bubble_fraction``). Per-worker batches must
    divide by it — callers relying on the old default with per-worker
    batch 2 should pass ``num_microbatches=2`` explicitly.
    """

    def __init__(self, embed: Layer, block: Layer, head: Layer,
                 num_layers: int, num_microbatches: int = 4,
                 remat: bool = False, virtual_stages: int = 1):
        self.embed = embed
        self.block = block
        self.head = head
        self.num_layers = int(num_layers)
        self.num_microbatches = int(num_microbatches)
        self.remat = bool(remat)
        self.virtual_stages = int(virtual_stages)
        if self.virtual_stages < 1:
            raise ValueError(
                f"virtual_stages must be >= 1, got {virtual_stages}")
        self._estate = self._bstate = self._hstate = {}  # set by init()

    def bubble_fraction(self, pp: int) -> float:
        """Idle fraction of the schedule: (P-1)/(M*v + P-1) — with v
        virtual stages per device each tick is 1/v of a full stage, so
        the (P-1)-tick fill/drain shrinks accordingly (round 4; at v=1
        this is GPipe's (P-1)/(M+P-1)). The same fraction applies to the
        forward and backward sweeps (autodiff replays the tick scan in
        reverse). A 1F1B reordering at v=1 would NOT shrink the bubble
        (it equals GPipe's at equal M) — 1F1B's real advantage is O(P)
        activation memory, which ``remat=True`` already provides at O(1)
        per stage; interleaving attacks the bubble itself at the price
        of one params-permutation gather per step and P | M. See
        docs/parallelism.md."""
        m = self.num_microbatches
        return (pp - 1) / (m * self.virtual_stages + pp - 1)

    # -- init ---------------------------------------------------------------
    def init(self, rng: jax.Array, input_shape: Tuple[int, ...]):
        k1, k2, k3 = jax.random.split(rng, 3)
        pe, se, shape = self.embed.init(k1, tuple(input_shape))
        if jax.tree_util.tree_leaves(se):
            raise ValueError("embed must be stateless")
        blocks, bstate = init_stacked_blocks(self.block, k2, shape,
                                             self.num_layers)
        ph, sh, out_shape = self.head.init(k3, shape)
        if jax.tree_util.tree_leaves(sh):
            raise ValueError("head must be stateless")
        # leafless state-structure templates for the pure applies
        self._estate, self._bstate, self._hstate = se, bstate, sh
        return {"embed": pe, "blocks": blocks, "head": ph}, out_shape

    # -- unsharded reference forward (host inference / tests) ---------------
    def apply(self, params, x):
        h, _ = self.embed.apply(params["embed"], self._estate, x,
                                training=False)

        def body(h, p):
            y, _ = self.block.apply(p, self._bstate, h, training=False)
            return y, None

        h, _ = lax.scan(body, h, params["blocks"])
        y, _ = self.head.apply(params["head"], self._hstate, h,
                               training=False)
        return y

    # -- sharded step -------------------------------------------------------
    def make_train_step(self, loss_fn: Callable, optimizer: Optimizer,
                        mesh: Mesh, data_axes: Sequence[str] = ("workers",),
                        pp_axis: str = "pp",
                        seq_axis: Optional[str] = None,
                        metric_fns: Optional[dict] = None) -> Callable:
        """Build ``step((params, opt_state), (x, y)) -> ((params, opt),
        loss)`` — or ``((params, opt), (loss, metrics_dict))`` when
        ``metric_fns`` is non-empty.

        ``data_axes``: mesh axes the batch dim is sharded over (dp).
        ``seq_axis``: mesh axis the sequence dim is sharded over (sp, ring
        attention inside the blocks); None for no sequence parallelism.
        ``metric_fns``: {name: fn(y, logits)} evaluated on the training
        batch (same psum accounting as the loss).
        """
        M = self.num_microbatches
        v = self.virtual_stages
        pp = mesh.shape[pp_axis]
        if self.num_layers % (pp * v):
            raise ValueError(
                f"num_layers {self.num_layers} must divide evenly over "
                f"pp axis {pp_axis!r} (size {pp}) x virtual_stages {v}")
        if v > 1 and M % pp:
            raise ValueError(
                f"the interleaved schedule injects microbatches in groups "
                f"of P: num_microbatches {M} must divide by the pp axis "
                f"size {pp} when virtual_stages > 1")
        pipeline = make_pipeline_fn(self.block, pp_axis, self._bstate,
                                    remat=self.remat, virtual_stages=v)
        # interleaved layer->device map: global chunk j (layers
        # [j*lpc, (j+1)*lpc)) lives on device j % P, but GSPMD tiles the
        # stacked axis CONTIGUOUSLY — so the step permutes the canonical
        # layer order into device-major/chunk-minor order at the jit
        # boundary (params and optimizer state stay canonical; the
        # gather + its scatter transpose cost one params-shuffle per
        # step, noise next to a pipelined batch)
        if v > 1:
            perm = interleaved_params_perm(self.num_layers, pp, v)
            inv_perm = np.argsort(perm)
        else:
            perm = inv_perm = None
        embed, head = self.embed, self.head
        estate, hstate = self._estate, self._hstate
        d_axes = tuple(data_axes)
        loss_div_axes = d_axes + ((seq_axis,) if seq_axis else ())
        div = int(np.prod([mesh.shape[a] for a in loss_div_axes])) or 1
        metric_fns = metric_fns or {}

        def local_grads(params, x, y):
            def obj(params):
                h, _ = embed.apply(params["embed"], estate, x,
                                   training=False)
                mb = h.reshape((M, h.shape[0] // M) + h.shape[1:])
                out = pipeline(params["blocks"], mb)
                out = out.reshape(h.shape[:-1] + out.shape[-1:])
                logits, _ = head.apply(params["head"], hstate, out,
                                       training=False)
                # The pipeline broadcast the outputs to every pp rank, so
                # every rank computes the same loss; count it ONCE (last
                # stage) or replicated-param grads would be pp-times too
                # large after the psum. Cross-rank grad flow (last rank's
                # loss -> ring -> stage params -> first rank's embed) is
                # handled by the collective transposes inside jax.grad.
                is_last = (lax.axis_index(pp_axis)
                           == lax.axis_size(pp_axis) - 1)
                # scaled so that psum over data+pp axes == global mean loss
                return loss_fn(y, logits) * is_last / div, (logits, is_last)

            (loss, (logits, is_last)), grads = \
                jax.value_and_grad(obj, has_aux=True)(params)
            all_axes = loss_div_axes + (pp_axis,)
            grads = {
                # replicated components: nonzero on one rank; sum everywhere
                "embed": lax.psum(grads["embed"], all_axes),
                "head": lax.psum(grads["head"], all_axes),
                # pp-sharded trunk: each rank already holds the full grad of
                # its own stage; reduce over data axes only
                "blocks": lax.psum(grads["blocks"], loss_div_axes),
            }
            mets = {name: lax.psum(fn(y, logits) * is_last / div, all_axes)
                    for name, fn in metric_fns.items()}
            return grads, lax.psum(loss, all_axes), mets

        # x/y: [B, S] -> batch over dp axes, sequence over sp
        seq_entry = (seq_axis,) if seq_axis else (None,)
        data_spec = P(d_axes, *seq_entry)
        pspecs = {"embed": P(), "blocks": P(pp_axis), "head": P()}
        grads_fn = shard_map(
            local_grads, mesh=mesh,
            in_specs=(pspecs, data_spec, data_spec),
            out_specs=(pspecs, P(), {n: P() for n in metric_fns}))

        def step(carry, batch):
            params, opt_state = carry
            x, y = batch
            if perm is not None:
                px = dict(params, blocks=jax.tree_util.tree_map(
                    lambda l: jnp.take(l, perm, axis=0), params["blocks"]))
            else:
                px = params
            grads, loss, mets = grads_fn(px, x, y)
            if perm is not None:
                grads = dict(grads, blocks=jax.tree_util.tree_map(
                    lambda g: jnp.take(g, inv_perm, axis=0),
                    grads["blocks"]))
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = apply_updates(params, updates)
            return (params, opt_state), (loss, mets) if metric_fns else loss

        return jax.jit(step, donate_argnums=(0,))

    def shard_variables(self, params: Pytree, mesh: Mesh,
                        pp_axis: str = "pp") -> Pytree:
        """device_put the params tree: trunk layer-sharded over pp, embed and
        head replicated."""
        repl = NamedSharding(mesh, P())
        blk = NamedSharding(mesh, P(pp_axis))
        put = jax.tree_util.tree_map
        return {"embed": put(lambda x: jax.device_put(x, repl),
                             params["embed"]),
                "blocks": put(lambda x: jax.device_put(x, blk),
                              params["blocks"]),
                "head": put(lambda x: jax.device_put(x, repl),
                            params["head"])}


class PipelineTrainer:
    """Trainer-style wrapper: epoch loop + history over a ``PipelinedLM``.

    Mirrors the ``Trainer.train(dataset)`` ergonomics of the rest of the
    family (reference: ``distkeras/trainers.py`` constructor-kwargs style)
    for the language-model shape: ``features_col`` holds token ids
    ``[N, S]``, ``label_col`` the per-token targets ``[N, S]``.

    Family-parity services (round 3; previously a feature island): the
    epoch is ONE jitted ``lax.scan`` over stacked batches (no per-step
    Python dispatch), training ``metrics``, held-out ``validation_data``
    scalars per epoch, Keras-style ``callbacks`` (EarlyStopping &co.), and
    full-carry checkpoint/resume (params + optimizer state), all matching
    ``Trainer``'s semantics. ``snapshot_model`` is the one deliberate
    exception: a pipelined trunk is not a ``Model`` (stacked-layer params
    over a mesh), so ``ModelCheckpoint`` does not apply — use
    ``checkpoint_dir``.
    """

    def __init__(self, lm: PipelinedLM, mesh: Mesh,
                 data_axes: Sequence[str] = ("workers",),
                 pp_axis: str = "pp", seq_axis: Optional[str] = None,
                 worker_optimizer="sgd", optimizer_kwargs=None,
                 loss="sparse_categorical_crossentropy_from_logits",
                 batch_size: int = 32, num_epoch: int = 1,
                 features_col: str = "features", label_col: str = "label",
                 seed: int = 0, shuffle_each_epoch: bool = True,
                 clip_grad_norm: Optional[float] = None,
                 class_weight: Optional[dict] = None,
                 metrics: Optional[Sequence] = None,
                 validation_data=None,
                 callbacks: Optional[Sequence] = None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 1, resume: bool = False,
                 checkpoint_async: bool = False,
                 telemetry=None):
        from distkeras_tpu.ops.losses import get_loss, with_class_weight
        from distkeras_tpu.ops.optimizers import (clip_by_global_norm,
                                                  get_optimizer)
        from distkeras_tpu.utils.history import History

        self.lm = lm
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        self.pp_axis = pp_axis
        self.seq_axis = seq_axis
        self.optimizer = get_optimizer(worker_optimizer,
                                       **(optimizer_kwargs or {}))
        if clip_grad_norm is not None:
            self.optimizer = clip_by_global_norm(self.optimizer,
                                                 clip_grad_norm)
        self.eval_loss = get_loss(loss)
        self.loss = (with_class_weight(loss, class_weight)
                     if class_weight is not None else self.eval_loss)
        self.batch_size = int(batch_size)
        self.num_epoch = int(num_epoch)
        self.features_col = features_col
        self.label_col = label_col
        self.seed = int(seed)
        self.shuffle_each_epoch = bool(shuffle_each_epoch)
        self.metrics = list(metrics or [])
        self.validation_data = validation_data
        self.callbacks = list(callbacks or [])
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = int(checkpoint_every)
        if self.checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every must be >= 1, got {checkpoint_every}")
        self.resume = bool(resume)
        self.checkpoint_async = bool(checkpoint_async)
        # same telemetry contract as Trainer: None = auto-tape, False =
        # off, or a configured obs.TrainingTape (tokens are this
        # trainer's example unit: one example row = one [S] sequence)
        self.telemetry = telemetry
        self.tape = None
        self.stop_training = False
        self.history = History()
        self.params_ = None
        self._fwd = None  # cached jitted forward for predict()
        self._weights_fn = None
        self._pending_weights = None
        # preemption contract shared with the Trainer family (the
        # supervisor drives it duck-typed; trainers.epoch_exit is the
        # ONE copy of the stop/consume/save-on-exit rule): a standing
        # request_preempt() asks the loop to checkpoint the current
        # epoch and return cleanly
        self._preempt = threading.Event()
        self.preempted = False

    def request_preempt(self) -> None:
        """See ``Trainer.request_preempt`` — same contract (the notice
        stands until an epoch loop consumes it)."""
        self._preempt.set()

    def get_history(self):
        return self.history

    # -- callback API (Trainer-compatible surface) -------------------------
    def get_weights(self):
        """Host-side ``(params, state)`` of the in-progress weights
        (callback API; the pipeline has no layer state, so state is {})."""
        if self._weights_fn is None:
            raise RuntimeError(
                "get_weights() is only available to callbacks while "
                "train() is running")
        return self._weights_fn()

    def set_weights(self, params, state=None) -> None:
        self._pending_weights = (params, state or {})

    def snapshot_model(self):
        raise RuntimeError(
            "PipelineTrainer has no single-device Model to snapshot "
            "(pp-sharded stacked trunk); use checkpoint_dir for "
            "durable snapshots")

    def _metric_fns(self):
        if not self.metrics:
            return None
        from distkeras_tpu.ops.metrics import get_metric, metric_name
        return {metric_name(m): get_metric(m) for m in self.metrics}

    def _make_validator(self):
        """Jitted full-set eval: ``validator(params) -> {"val_loss": ...,
        "val_<metric>": ...}``. Runs under ``shard_map`` over the
        training mesh — batch over the data axes, sequence over
        ``seq_axis`` — because sequence-parallel blocks (ring/ulysses)
        contain collectives that need their axis bound; the pp-sharded
        trunk is viewed replicated for the reference forward (an
        all-gather per validation pass, not per step)."""
        if self.validation_data is None:
            return None
        vd = self.validation_data
        if isinstance(vd, tuple):
            Xv, yv = vd
        else:
            Xv = np.asarray(vd[self.features_col])
            yv = np.asarray(vd[self.label_col])
        # device-cached across epochs AND train() calls (supervisor
        # restarts), keyed on dataset identity — trainers.py holds the
        # one copy of the invalidation rule
        from distkeras_tpu.parallel.trainers import cache_validation_on_device
        Xv, yv = cache_validation_on_device(self, np.asarray(Xv),
                                            np.asarray(yv))
        loss_fn = self.eval_loss
        metric_fns = self._metric_fns() or {}
        lm = self.lm

        if self.seq_axis is None:
            # no collectives in the blocks: plain unsharded eval (any
            # validation-set size; the pre-round-3 behavior)
            @jax.jit
            def evalf_plain(params, Xv, yv):
                logits = lm.apply(params, Xv)
                res = {"val_loss": loss_fn(yv, logits)}
                for name, fn in metric_fns.items():
                    res[f"val_{name}"] = fn(yv, logits)
                return res

            return lambda params: evalf_plain(params, Xv, yv)

        # sequence-parallel blocks (ring/ulysses) contain collectives that
        # need their axis bound — run under shard_map over the mesh
        dp = int(np.prod([self.mesh.shape[a] for a in self.data_axes])) or 1
        if len(Xv) % dp:
            raise ValueError(
                f"validation set size {len(Xv)} must divide over data "
                f"axes {self.data_axes} (size {dp}) for the "
                f"sequence-parallel validator")
        mean_axes = self.data_axes + (self.seq_axis,)

        def evalf(params, Xv, yv):
            logits = lm.apply(params, Xv)
            res = {"val_loss": lax.pmean(loss_fn(yv, logits), mean_axes)}
            for name, fn in metric_fns.items():
                res[f"val_{name}"] = lax.pmean(fn(yv, logits), mean_axes)
            return res

        data_spec = P(self.data_axes, self.seq_axis)
        pspecs = {"embed": P(), "blocks": P(), "head": P()}
        sharded = jax.jit(shard_map(
            evalf, mesh=self.mesh,
            in_specs=(pspecs, data_spec, data_spec),
            out_specs={"val_loss": P(),
                       **{f"val_{n}": P() for n in metric_fns}}))
        return lambda params: sharded(params, Xv, yv)

    def _validate(self, X, Y):
        """Fail fast with microbatch/sharding-aware messages instead of a
        reshape error from deep inside shard_map tracing."""
        dp = int(np.prod([self.mesh.shape[a] for a in self.data_axes])) or 1
        if self.batch_size % dp:
            raise ValueError(
                f"batch_size {self.batch_size} must divide evenly over "
                f"data axes {self.data_axes} (size {dp})")
        local_b = self.batch_size // dp
        if local_b % self.lm.num_microbatches:
            hint = ""
            if self.lm.num_microbatches == 4 and local_b % 2 == 0:
                # targeted migration error: the default changed 2 -> 4 in
                # round 3 (ADVICE r3) — callers sized for the old default
                # get told exactly what to pass instead of a bare reshape
                hint = (" (note: PipelinedLM's num_microbatches DEFAULT "
                        "changed 2 -> 4; pass num_microbatches=2 to keep "
                        "the old behavior)")
            raise ValueError(
                f"per-worker batch {local_b} (batch_size {self.batch_size} "
                f"/ dp {dp}) must divide into num_microbatches="
                f"{self.lm.num_microbatches}{hint}")
        if self.seq_axis:
            sp = self.mesh.shape[self.seq_axis]
            if X.shape[1] % sp:
                raise ValueError(
                    f"sequence length {X.shape[1]} must divide over "
                    f"seq axis {self.seq_axis!r} (size {sp})")
        if len(X) < self.batch_size:
            raise ValueError(f"dataset ({len(X)}) smaller than one batch")

    def train(self, dataset) -> Pytree:
        from distkeras_tpu.data.sharded import ShardedDataset
        from distkeras_tpu.utils.callbacks import CallbackList
        if isinstance(dataset, ShardedDataset):
            raise ValueError(
                "PipelineTrainer does not support ShardedDataset "
                "(out-of-core training is a SingleTrainer/SPMDTrainer "
                "capability); load shards into one Dataset, or switch "
                "trainer")
        X = np.asarray(dataset[self.features_col])
        Y = np.asarray(dataset[self.label_col])
        lm = self.lm
        self._validate(X, Y)

        params, _ = lm.init(jax.random.PRNGKey(self.seed), X.shape[1:])
        manager = None
        start_epoch = 0
        if self.checkpoint_dir is not None:
            from distkeras_tpu.utils.checkpoint import CheckpointManager
            manager = CheckpointManager(self.checkpoint_dir,
                                        async_writes=self.checkpoint_async)
        opt_state = None
        resumed = False
        if manager is not None and self.resume:
            latest = manager.latest_step()
            if latest is not None:
                # restore template from eval_shape (host zeros) — a real
                # optimizer.init here would materialize full unsharded
                # moments on one device, the very allocation pipeline
                # parallelism exists to avoid
                opt_template = jax.tree_util.tree_map(
                    lambda s: np.zeros(s.shape, s.dtype),
                    jax.eval_shape(self.optimizer.init, params))
                tree = manager.restore(
                    {"params": params, "opt": opt_template}, step=latest)
                params, opt_state = tree["params"], tree["opt"]
                start_epoch = int(
                    manager.metadata(step=latest).get("epoch", -1)) + 1
                resumed = True
        # opt state sharded LIKE the params (trunk moments on pp, not
        # replicated — replicating Adam m+v would defeat the memory point
        # of pipeline parallelism). Same mirror rule as SPMDTrainer: moment
        # subtrees shaped like the params tree take the params' shardings;
        # anything else (step counters) replicates.
        repl = NamedSharding(self.mesh, P())
        param_sh = {
            "embed": jax.tree_util.tree_map(lambda _: repl,
                                            params["embed"]),
            "blocks": jax.tree_util.tree_map(
                lambda _: NamedSharding(self.mesh, P(self.pp_axis)),
                params["blocks"]),
            "head": jax.tree_util.tree_map(lambda _: repl, params["head"]),
        }
        pstruct = jax.tree_util.tree_structure(params)
        opt_shapes = jax.eval_shape(self.optimizer.init, params)
        rmap = lambda tree: jax.tree_util.tree_map(lambda _: repl, tree)
        mirror = lambda sub: param_sh if jax.tree_util.tree_structure(
            sub) == pstruct else rmap(sub)
        opt_sh = ({k: mirror(v) for k, v in opt_shapes.items()}
                  if isinstance(opt_shapes, dict) else rmap(opt_shapes))
        params = lm.shard_variables(params, self.mesh, self.pp_axis)
        if resumed:
            # REMATERIALIZE the restored trees through a non-donated
            # jitted copy before anything donates them: a SHARDED
            # device_put of a host numpy array zero-copy-aliases the
            # numpy buffer on this CPU client (each shard's device
            # pointer is a slice of the host allocation — verified), so
            # the np.load'd checkpoint tree would enter the donating
            # run_epoch backed by memory XLA does not own; reuse then
            # corrupts the values nondeterministically (resume-exactness
            # drifted run to run before this copy; same hazard class as
            # SPMDTrainer's restored carry, see spmd.py). The jitted
            # copy's outputs are XLA-allocated, which makes the first
            # donation safe. One-time cost at resume.
            params = jax.jit(
                lambda t: jax.tree_util.tree_map(jnp.copy, t),
                out_shardings=param_sh)(params)
            opt_state = jax.tree_util.tree_map(
                lambda host, sh: jax.device_put(host, sh),
                opt_state, opt_sh)
            opt_state = jax.jit(
                lambda t: jax.tree_util.tree_map(jnp.copy, t),
                out_shardings=opt_sh)(opt_state)
        else:
            opt_state = jax.jit(self.optimizer.init,
                                out_shardings=opt_sh)(params)
        step = lm.make_train_step(self.loss, self.optimizer, self.mesh,
                                  data_axes=self.data_axes,
                                  pp_axis=self.pp_axis,
                                  seq_axis=self.seq_axis,
                                  metric_fns=self._metric_fns())

        have_mets = bool(self._metric_fns())

        # whole epoch = ONE jitted scan over [steps, ...] stacked batches
        # (family parity with make_epoch_runner; no per-step Python)
        @partial(jax.jit, donate_argnums=(0,))
        def run_epoch(carry, Xs, Ys):
            def body(c, xy):
                c, out = step(c, xy)
                return c, out if have_mets else (out, {})
            return lax.scan(body, carry, (Xs, Ys))

        seq_entry = (self.seq_axis,) if self.seq_axis else (None,)
        data_sh = NamedSharding(self.mesh,
                                P(None, self.data_axes, *seq_entry))

        from distkeras_tpu.parallel.worker import stack_batches

        from distkeras_tpu.obs import resolve_tape
        tape = self.tape = resolve_tape(self.telemetry, "PipelineTrainer",
                                        unit="tokens")
        tape.watch("PipelineTrainer.epoch", run_epoch)

        validator = self._make_validator()
        carry = (params, opt_state)
        carry_box = [carry]
        self.stop_training = False
        # standing preemption notices survive train() entry (see
        # trainers.epoch_exit: consumed when acted on)
        self.preempted = False
        self._pending_weights = None
        self._weights_fn = lambda: (  # callback API: explicit user fetch
            jax.device_get(carry_box[0][0]), {})  # lint: allow-host-sync
        cbs = CallbackList(self.callbacks, self)
        cbs.train_begin()
        self.history.record_training_start()
        tape.train_begin()
        try:
            from distkeras_tpu.obs import timed_stream
            from distkeras_tpu.parallel.trainers import epoch_exit, val_logs
            from distkeras_tpu.resilience import faults
            from distkeras_tpu.utils.prefetch import Prefetcher, \
                device_stager

            def assemble(epoch):
                # same shuffle-seed convention as Trainer._epoch_perm
                perm = (np.random.RandomState(self.seed + 1000 * epoch)
                        .permutation(len(X))
                        if self.shuffle_each_epoch else None)
                return stack_batches(X, Y, self.batch_size, perm)

            # epoch e+1's shuffle gather + stacking + sharded H2D staging
            # run on the loader thread while the device trains epoch e
            # (docs/overlap.md; depth=1 — a chunk is the whole stacked
            # epoch, one-ahead is full overlap). device_put of the
            # numpy stack DIRECTLY with the target sharding — the old
            # jax.device_put(jnp.asarray(Xs)) first materialized a
            # default-device copy, then moved it (double host copy)
            stream = Prefetcher(assemble,
                                range(start_epoch, self.num_epoch),
                                depth=1, place=device_stager(data_sh),
                                name="pipeline-feed")
            for epoch, (xb, yb, nsteps) in timed_stream(stream, tape):
                # chaos hook: a mid-training crash at an arbitrary epoch
                faults.point("train.epoch")
                with tape.phase("device", "dispatch"):
                    carry, (losses, mets) = run_epoch(carry, xb, yb)
                    carry_box[0] = carry
                with tape.phase("device", "fetch"):
                    # the epoch-boundary fetch (one per epoch; device_get
                    # enqueues the per-leaf async copies itself)
                    losses, mets = jax.device_get(  # lint: allow-host-sync
                        (losses, mets))
                # history, logs and callbacks: what the tape derives as
                # ``host_s``; validation and checkpoint nest inside
                with tape.span("epoch_end"):
                    # chaos hook: NaN-poison the epoch losses the
                    # anomaly guard watches
                    losses = faults.corrupt("train.loss", losses)
                    extra = {}
                    if validator is not None:
                        with tape.phase("validation"):
                            extra = val_logs(validator(carry[0]))
                    self.history.append_epoch(loss=np.asarray(losses),
                                              **{k: np.asarray(v)
                                                 for k, v in mets.items()},
                                              **extra)
                    saved = False
                    if manager is not None and (
                            (epoch + 1) % self.checkpoint_every == 0
                            or epoch == self.num_epoch - 1):
                        with tape.phase("checkpoint"):
                            manager.save(
                                epoch,
                                {"params": carry[0], "opt": carry[1]},
                                metadata={"epoch": epoch})
                        saved = True
                    logs = {"loss": float(np.mean(losses))}
                    logs.update({k: float(np.mean(np.asarray(v)))
                                 for k, v in mets.items()})
                    logs.update({k: float(np.asarray(v).ravel()[0])
                                 for k, v in extra.items()})
                    logs.update(tape.epoch_end(
                        nsteps * self.batch_size * X.shape[1]))
                    if epoch == start_epoch:
                        tape.mark_warm()
                    cbs.epoch_end(epoch, logs)
                # early stop / preemption between checkpoint_every
                # boundaries saves the final state, or resume would
                # lose these epochs (trainers.epoch_exit: the shared
                # exit rule, one copy for the whole family)
                if epoch_exit(self, epoch, saved,
                              (lambda ep: manager.save(
                                  ep, {"params": carry[0],
                                       "opt": carry[1]},
                                  metadata={"epoch": ep}))
                              if manager is not None else None):
                    break
        finally:
            self.history.record_training_stop()
            tape.train_end()
            cbs.train_end()
        if manager is not None:
            manager.wait()

        # end-of-train result fetch
        self.params_ = jax.device_get(carry[0])  # lint: allow-host-sync
        if self._pending_weights is not None:
            self.params_ = self._pending_weights[0]
        return self.params_

    def predict(self, x) -> np.ndarray:
        if self.params_ is None:
            raise RuntimeError("call train() first")
        if self._fwd is None:  # built once; params are a traced argument
            self._fwd = jax.jit(self.lm.apply)
        return np.asarray(self._fwd(self.params_, jnp.asarray(x)))
