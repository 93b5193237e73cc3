"""Worker compute: the jitted local training loop.

Reference parity: ``distkeras/workers.py`` — a Worker deserializes the model
in its executor, assembles minibatches from a row iterator and calls Keras
``train_on_batch`` per batch (SURVEY §3.1 hot loop). The TPU-native redesign
collapses that entire per-worker loop into a ``lax.scan`` over a stacked
``[steps, batch, ...]`` array inside ONE jitted call: no per-batch Python
dispatch, no per-row marshalling, static shapes throughout so XLA keeps the
MXU busy.

The same ``train_step`` body is reused by every trainer:
  * SingleTrainer scans it directly,
  * EnsembleTrainer vmaps it over a stacked model axis,
  * the distributed trainers run it under ``shard_map`` with a collective
    exchange spliced between windows (see ``parallel/engine.py``).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from distkeras_tpu.models.core import collect_aux_losses
from distkeras_tpu.ops.optimizers import Optimizer, apply_updates


class TrainCarry(NamedTuple):
    """Scan carry for a local training loop (a pure-pytree 'worker')."""
    params: any
    state: any
    opt_state: any
    rng: jax.Array


def _fused_head_parts(module, loss_fn, metric_fns):
    """Validate + split a model for ``fused_vocab_head`` training.

    Returns ``(trunk, ignore_index, compute_dtype)`` where ``trunk`` is
    the model minus its final vocab projection (whose kernel,
    ``params[-1]["kernel"]``, feeds the fused loss directly).
    """
    from distkeras_tpu.models.core import Sequential
    from distkeras_tpu.models.layers import Dense
    from distkeras_tpu.ops import losses as L

    if metric_fns:
        raise ValueError(
            "fused_vocab_head=True cannot compute per-batch metric_fns: "
            "the logits tensor is never materialized. Evaluate metrics "
            "separately (inference.evaluators) or disable the fusion.")
    if not isinstance(module, Sequential) or not module.layers:
        raise ValueError("fused_vocab_head needs a Sequential model")
    head = module.layers[-1]
    if not (isinstance(head, Dense) and not head.use_bias
            and head.activation is None):
        raise ValueError(
            "fused_vocab_head needs the final layer to be "
            "Dense(use_bias=False, activation=None); got "
            f"{head!r}")
    if loss_fn is L.sparse_categorical_crossentropy_from_logits:
        ignore_index = None
    elif loss_fn is L.masked_sparse_categorical_crossentropy_from_logits:
        ignore_index = -1
    else:
        raise ValueError(
            "fused_vocab_head supports loss="
            "'sparse_categorical_crossentropy_from_logits' or its "
            "masked_ variant; got "
            f"{getattr(loss_fn, '__name__', loss_fn)!r}")
    return Sequential(module.layers[:-1]), ignore_index, head.dtype


def make_train_step(module, loss_fn: Callable, optimizer: Optimizer,
                    metric_fns: Optional[dict] = None,
                    accum_steps: int = 1,
                    param_mask=None, state_mask=None,
                    fused_vocab_head=False) -> Callable:
    """Build the per-minibatch step: grad -> optimizer update -> new carry.

    Equivalent role to one ``model.train_on_batch`` call in the reference
    worker loop, as a pure function usable under scan/vmap/shard_map.

    With ``metric_fns`` ({name: fn(y_true, y_pred)}), the step returns
    ``(carry, (loss, {name: value}))`` — the reference's per-batch Keras
    metrics, computed on-device from the training forward's outputs at
    negligible cost (XLA fuses them into the existing graph).

    ``param_mask`` (a boolean pytree matching params, from
    ``models.core.trainable_mask``) freezes params Keras-style: gradients
    are masked (so optimizer moments stay zero) AND the optimizer's
    updates are masked (so param-coupled terms like adamw/lars/lamb
    weight decay cannot move frozen leaves either) — frozen params are
    bitwise-unchanged through any number of steps. ``state_mask`` (same
    builder over the STATE tree) additionally freezes layer state, the
    Keras inference-mode semantics for frozen BatchNorm: its running
    stats must not drift toward the new data while its frozen
    scale/offset stay matched to the old ones.

    ``accum_steps > 1`` splits the batch into that many microbatches and
    accumulates gradients over an inner ``lax.scan`` before ONE optimizer
    update — the standard memory lever for batches whose activations do
    not fit HBM. Identical math to the full-batch step (the mean of equal
    microbatch means is the batch mean); model state (BN stats) threads
    through the microbatches in order.

    ``fused_vocab_head=True`` (or an int = explicit token-chunk count)
    fuses the model's FINAL bias-free ``Dense``
    projection into a chunked cross-entropy
    (``ops.losses.fused_linear_cross_entropy``) so the ``[B*S, vocab]``
    logits tensor is never materialized — the memory/bandwidth lever for
    large-vocab LMs. Requires a ``Sequential`` ending in
    ``Dense(use_bias=False, activation=None)`` and a sparse-from-logits
    loss (plain or masked); per-batch ``metric_fns`` are unavailable in
    this mode (there are no logits to evaluate them on).
    """
    accum_steps = int(accum_steps)
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")

    fused = None
    if fused_vocab_head:
        fused = _fused_head_parts(module, loss_fn, metric_fns)
        # fused_vocab_head=True -> default chunking; an int picks the
        # token-chunk count explicitly (perf knob, see docs/PERF.md)
        fused_chunks = (8 if fused_vocab_head is True
                        else int(fused_vocab_head))

    def grad_of(params, state, xb, yb, sub):
        def objective(params):
            if fused is not None:
                trunk, ignore_index, cdt = fused
                hidden, t_state = trunk.apply(
                    params[:-1], state[:-1], xb, training=True, rng=sub)
                from distkeras_tpu.ops.losses import \
                    fused_linear_cross_entropy
                with jax.named_scope("loss"):
                    loss = fused_linear_cross_entropy(
                        hidden, params[-1]["kernel"], yb,
                        num_chunks=fused_chunks,
                        ignore_index=ignore_index, compute_dtype=cdt)
                new_state = list(t_state) + [state[-1]]
                return loss + collect_aux_losses(new_state), \
                    (new_state, None)
            out, new_state = module.apply(params, state, xb,
                                          training=True, rng=sub)
            # layer-published auxiliary losses (models.core.AUX_LOSS_KEY,
            # e.g. MoE router balance) join the optimized loss here
            with jax.named_scope("loss"):
                loss = loss_fn(yb, out) + collect_aux_losses(new_state)
            return loss, (new_state, out)

        (loss, (new_state, out)), grads = jax.value_and_grad(
            objective, has_aux=True)(params)
        if param_mask is not None:
            grads = jax.tree_util.tree_map(
                lambda m, g: jnp.where(m, g, 0.0), param_mask, grads)
        mets = ({name: fn(yb, out) for name, fn in metric_fns.items()}
                if metric_fns else {})
        return loss, grads, new_state, mets

    def train_step(carry: TrainCarry, batch) -> Tuple[TrainCarry, jax.Array]:
        xb, yb = batch
        rng, sub = jax.random.split(carry.rng)

        if accum_steps == 1:
            loss, grads, new_state, mets = grad_of(
                carry.params, carry.state, xb, yb, sub)
        else:
            if xb.shape[0] % accum_steps:
                raise ValueError(
                    f"batch of {xb.shape[0]} must divide into "
                    f"accum_steps={accum_steps} microbatches")
            micro = xb.shape[0] // accum_steps
            # STRIDED split (microbatch j = rows j, j+accum, ...): under a
            # data-parallel batch sharding each microbatch then still spans
            # every dp shard — a contiguous split would concentrate each
            # microbatch on a shard subset and serialize the dp axis
            xs = xb.reshape((micro, accum_steps) + xb.shape[1:]) \
                .swapaxes(0, 1)
            ys = yb.reshape((micro, accum_steps) + yb.shape[1:]) \
                .swapaxes(0, 1)
            subs = jax.random.split(sub, accum_steps)

            def body(c, inp):
                state, gacc = c
                x_, y_, r_ = inp
                loss, grads, state, mets = grad_of(carry.params, state,
                                                   x_, y_, r_)
                gacc = jax.tree_util.tree_map(jnp.add, gacc, grads)
                return (state, gacc), (loss, mets)

            zeros = jax.tree_util.tree_map(jnp.zeros_like, carry.params)
            (new_state, gsum), (losses, mets_s) = lax.scan(
                body, (carry.state, zeros), (xs, ys, subs))
            grads = jax.tree_util.tree_map(lambda g: g / accum_steps, gsum)
            loss = losses.mean()
            mets = jax.tree_util.tree_map(lambda m: m.mean(), mets_s)

        with jax.named_scope("optimizer"):
            updates, new_opt_state = optimizer.update(
                grads, carry.opt_state, carry.params)
            if param_mask is not None:
                updates = jax.tree_util.tree_map(
                    lambda m, u: jnp.where(m, u, 0.0), param_mask, updates)
        if state_mask is not None:
            # mask leaves are static Python bools: frozen state keeps the
            # carried value with zero compute
            new_state = jax.tree_util.tree_map(
                lambda m, old, new: new if m else old,
                state_mask, carry.state, new_state)
        with jax.named_scope("optimizer"):
            new_params = apply_updates(carry.params, updates)
        new_carry = TrainCarry(new_params, new_state, new_opt_state, rng)
        if metric_fns:
            return new_carry, (loss, mets)
        return new_carry, loss

    return train_step


def make_epoch_runner(train_step: Callable) -> Callable:
    """Jitted scan of ``train_step`` over ``[steps, batch, ...]`` data.

    The carry is DONATED: parameters, model state, optimizer state and
    key are updated in the buffers they came in, the value passed in is
    deleted, and the caller rebinds it from the result (a caller that
    wants the old carry copies it first). It must therefore own every
    leaf it passes (``SingleTrainer.train`` copies once, before the
    first call). ``X``/``Y`` are not donated: the ``Prefetcher`` owns
    them."""

    @partial(jax.jit, donate_argnums=(0,))
    def train_epoch(carry: TrainCarry, X: jax.Array, Y: jax.Array):
        carry, losses = lax.scan(train_step, carry, (X, Y))
        return carry, losses

    return train_epoch


def shard_epoch_data(X, Y, num_workers: int, batch_size: int, perm=None):
    """Host-side: shape one epoch into ``[S, num_workers, batch, ...]``.

    Plays the role of the reference's ``df.rdd.repartition(num_workers *
    parallelism_factor)`` — but as a zero-copy reshape of the columnar
    arrays, not a cluster shuffle. Drops the remainder (drop_remainder
    batching). The single-device path is the same contract with
    ``num_workers=1`` (see ``stack_batches``).
    """
    if perm is not None:
        from distkeras_tpu.data import native
        X, Y = native.gather(X, perm), native.gather(Y, perm)
    per_step = num_workers * batch_size
    S = len(X) // per_step
    n = S * per_step
    if S == 0:
        raise ValueError(
            f"dataset ({len(X)} rows) smaller than one global step "
            f"({num_workers} workers x batch_size {batch_size})")
    Xs = X[:n].reshape((S, num_workers, batch_size) + X.shape[1:])
    Ys = Y[:n].reshape((S, num_workers, batch_size) + Y.shape[1:])
    return Xs, Ys, S


def stack_batches(X, Y, batch_size: int, perm=None):
    """Single-worker epoch stacking: ``[n_steps, batch_size, ...]``."""
    Xs, Ys, S = shard_epoch_data(X, Y, 1, batch_size, perm)
    return Xs[:, 0], Ys[:, 0], S
