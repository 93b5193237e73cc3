"""The data-parallel training driver: one ``SPMDTrainer(model, mesh=dp).train(
Dataset)`` call over the cell's chips, timed from the outside like
``train_driver`` (epoch 1 compiles and is set-up; the window runs from the end
of epoch 1 to the first epoch end past ``--seconds``; what the check compares
is what epoch 1 left in the carry).

The window's callback is ``train_driver``'s own: ``SPMDTrainer``'s weights
function closes over its carry as ``SingleTrainer``'s does, so ``_carry_of``
reads it alike. What differs from one chip: the rows of a step are
``sequences_per_chip_step`` for EVERY chip (``traffic.train_rows(...,
chips=)``), the carry is replicated over the mesh (so the parameters it is
compared with are put there first), and
the counters say which numbers are a chip's and which the step's:
``trace_counters["batch"]`` is the per-chip batch (``flops.flash_train_cost``
counts kernel calls from it while ``trace_reduce.kernel_seconds`` averages
over chips) and ``tokens`` the whole step's.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from harness import check, common, traffic as traffic_mod, weights
from harness.train_driver import _callback_class, _kernel_counts


def run(cell, cfg, traffic, args, t_start, trace_dir) -> common.RunRecord:
    from distkeras_tpu import obs
    from distkeras_tpu.data import Dataset
    from distkeras_tpu.parallel import SPMDTrainer, make_mesh

    if traffic["trainer"] != "SPMDTrainer":
        raise NotImplementedError("this driver drives SPMDTrainer over a dp mesh")
    chips = cell["chips"] if not args.rehearse else min(
        cell["chips"], len(jax.devices()))
    s = weights.sizes(cfg)
    seq, per_chip, steps = (traffic["seq_len"], traffic["sequences_per_chip_step"],
                            traffic["steps_per_epoch"])
    batch = per_chip * chips
    x, y = traffic_mod.train_rows(traffic, s["vocab"], args.seed, chips=chips)
    model = common.build_model(cfg, args.seed, seq)
    mesh = make_mesh(chips)
    profiler = common.Profiler(bool(args.trace), trace_dir)
    # a copy of the first parameters on the whole mesh, in buffers of its own:
    # a device_put may share the chip-0 buffer with the trainer's carry, which
    # its epoch program donates
    p0 = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.copy, tree),
                 out_shardings=NamedSharding(mesh, P()))(model.params)
    cb = _callback_class()(args.seconds, traffic["trace_seconds"], profiler, p0)
    del p0
    trainer = SPMDTrainer(
        model, mesh=mesh, worker_optimizer=traffic["optimizer"],
        optimizer_kwargs=dict(traffic["optimizer_kwargs"]), loss=traffic["loss"],
        batch_size=batch, num_epoch=10 ** 9, seed=args.seed % (2 ** 31 - 10 ** 6),
        shuffle_each_epoch=bool(traffic["shuffle_each_epoch"]), callbacks=[cb])
    rec = common.RunRecord()
    if not args.rehearse:
        found = _kernel_counts(trainer, model, per_chip, seq)
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
    rec.notes["placement"] = getattr(trainer, "placement", None)

    epochs = len(cb.stamps)
    wall = cb.stamps[-1] - cb.t_open
    tokens_per_epoch = steps * batch * seq
    rec.end_to_end = {"train_tokens_per_s": epochs * tokens_per_epoch / wall,
                      "setup_s": cb.t_open - t_start}
    shape = {"batch": per_chip, "global_batch": batch, "seq_len": seq,
             "chips": chips}
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
