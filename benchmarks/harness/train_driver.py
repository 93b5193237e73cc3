"""The training driver: one ``Trainer.train(Dataset)`` call, timed from the
outside by a callback of the benchmark's own.

Epoch 1 compiles and is set-up; the window runs from the end of epoch 1 to the
first epoch end past ``--seconds``, when the callback sets
``trainer.stop_training``. The same call, the same compiled epoch program and
the same carry serve both: what the check compares is what epoch 1 of that
call left in the carry.
"""

from __future__ import annotations

import gc
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import check, common, traffic as traffic_mod, weights


def _carry_of(trainer):
    """The live training carry. ``Trainer.get_weights`` would copy every
    parameter to the host; the carry is read where the trainer keeps it for
    that call, in the closure of its weights function."""
    fn = trainer._weights_fn
    cells = dict(zip(fn.__code__.co_freevars, fn.__closure__))
    return cells["carry"].cell_contents


@jax.jit
def _read_carry(p0, carry_params, moment):
    """Per leaf: the norm of Adam's first moment, the norm of the parameters'
    change and a sample of it (``check.sample``)."""
    tm = jax.tree_util.tree_map
    change = tm(jnp.subtract, carry_params, p0)
    return {"moment": tm(check.leaf_norm, moment),
            "change": tm(check.leaf_norm, change),
            "change_samples": tm(check.sample, change)}


def _callback_class():
    from distkeras_tpu.utils.callbacks import Callback

    class WindowCallback(Callback):
        """Stamps every epoch end, reads the carry after epoch 1, opens the
        window, runs the traced part and stops the trainer."""

        def __init__(self, seconds, trace_seconds, profiler, p0):
            self.seconds, self.trace_seconds = seconds, trace_seconds
            self.profiler, self.p0 = profiler, p0
            self.stamps = []            # perf_counter at each epoch end
            self.t_open = None
            self.observed = None
            self.compiles_at_open = None
            self.traced_epochs = 0

        def on_epoch_end(self, epoch, logs=None):
            from distkeras_tpu import obs
            now = time.perf_counter()
            if self.t_open is None:
                carry = _carry_of(self.trainer)
                read = _read_carry(self.p0, carry.params, carry.opt_state["m"])
                self.observed = {k: check.program_leaves(v) for k, v in read.items()}
                self.p0 = None
                totals = obs.compile_totals()
                self.compiles_at_open = totals["count"]
                self.compile_s_in_setup = totals["seconds"]
                self.profiler.start()
                self.t_open = time.perf_counter()
                return
            self.stamps.append(now)
            if self.profiler.running:
                self.traced_epochs += 1
                if now - self.profiler.t0 >= self.trace_seconds:
                    self.profiler.stop()
            if now - self.t_open >= self.seconds:
                self.profiler.stop()
                self.trainer.stop_training = True

    return WindowCallback


def _kernel_counts(trainer, model, batch, seq_len) -> dict:
    """Pallas kernels in the step the trainer builds, by their ``name=``,
    read from the lowered step at the cell's shapes (as ``chip_smoke.py``)."""
    from distkeras_tpu.parallel.worker import TrainCarry, make_train_step
    spec = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    opt = trainer.worker_optimizer
    carry = TrainCarry(spec(model.params), spec(model.state),
                       jax.eval_shape(opt.init, model.params),
                       jax.ShapeDtypeStruct((2,), np.uint32))
    xb = jax.ShapeDtypeStruct((batch, seq_len), np.int32)
    text = jax.jit(make_train_step(model.module, trainer.loss, opt)).lower(
        carry, (xb, xb)).as_text()
    names: dict = {}
    for n in re.findall(r'kernel_name = "([^"]+)"', text):
        names[n] = names.get(n, 0) + 1
    return names


def run(cell, cfg, traffic, args, t_start, trace_dir) -> common.RunRecord:
    from distkeras_tpu import obs
    from distkeras_tpu.data import Dataset
    from distkeras_tpu.parallel import SingleTrainer

    if traffic["trainer"] != "SingleTrainer" or cell["chips"] != 1:
        raise NotImplementedError("only SingleTrainer on one chip is driven yet")
    s = weights.sizes(cfg)
    seq, batch, steps = (traffic["seq_len"], traffic["sequences_per_chip_step"],
                         traffic["steps_per_epoch"])
    x, y = traffic_mod.train_rows(traffic, s["vocab"], args.seed)
    model = common.build_model(cfg, args.seed, seq)
    profiler = common.Profiler(bool(args.trace), trace_dir)
    cb = _callback_class()(args.seconds, traffic["trace_seconds"], profiler,
                           model.params)
    trainer = SingleTrainer(
        model, worker_optimizer=traffic["optimizer"],
        optimizer_kwargs=dict(traffic["optimizer_kwargs"]), loss=traffic["loss"],
        batch_size=batch, num_epoch=10 ** 9, seed=args.seed % (2 ** 31 - 10 ** 6),
        shuffle_each_epoch=bool(traffic["shuffle_each_epoch"]), callbacks=[cb])
    rec = common.RunRecord()
    if not args.rehearse:
        found = _kernel_counts(trainer, model, batch, seq)
        for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
            if found.get(k) != s["layers"]:
                raise RuntimeError(f"the train step holds {found}, not "
                                   f"{s['layers']} of {k}: a reference path ran")
        rec.notes["train_step_kernels"] = found

    trainer.train(Dataset({"features": x, "label": y}))
    compiles_in_window = obs.compile_totals()["count"] - cb.compiles_at_open
    rec.memory_peak_bytes = common.memory_peak_bytes()
    losses = np.ravel(trainer.get_history().losses())
    observed = dict(cb.observed, losses=[float(v) for v in losses[:steps]])

    epochs = len(cb.stamps)
    wall = cb.stamps[-1] - cb.t_open
    tokens_per_epoch = steps * batch * seq
    rec.end_to_end = {"train_tokens_per_s": epochs * tokens_per_epoch / wall,
                      "setup_s": cb.t_open - t_start}
    shape = {"batch": batch, "seq_len": seq, "chips": cell["chips"]}
    rec.counters = {"steps": epochs * steps, "tokens": epochs * tokens_per_epoch,
                    "window_s": wall, "epochs": epochs, **shape}
    rec.trace_counters = {"steps": cb.traced_epochs * steps,
                          "tokens": cb.traced_epochs * tokens_per_epoch,
                          "epochs": cb.traced_epochs, **shape}
    rec.trace_window_s, rec.trace_dir = profiler.window_s, trace_dir
    rec.notes["trace_stop_s"] = profiler.stop_s
    rec.attempted, rec.failed = epochs * steps, 0
    rec.notes.update(compiles_in_window=compiles_in_window,
                     compiles_in_setup=cb.compiles_at_open,
                     compile_s_in_setup=cb.compile_s_in_setup,
                     final_loss=float(losses[-1]))
    if compiles_in_window:
        raise RuntimeError(f"{compiles_in_window} compilations inside the window")
    if not np.isfinite(losses).all():
        rec.failed = int((~np.isfinite(losses)).sum())

    # the program's state goes before the reference comes
    del trainer, model, cb
    gc.collect()
    t_check = time.perf_counter()
    ref = check.reference_observed(cfg, traffic, x, y, args.seed)
    rec.numbers = check.train_numbers(observed, ref)
    rec.notes["check_s"] = time.perf_counter() - t_check
    return rec

