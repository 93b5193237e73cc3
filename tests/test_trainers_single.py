"""End-to-end tests for SingleTrainer / EnsembleTrainer (BASELINE config 1:
MLP on MNIST-like data, single device, CPU-runnable)."""

import jax
import numpy as np
import pytest

from distkeras_tpu.data import Dataset, OneHotTransformer
from distkeras_tpu.models import Dense, Model, Sequential
from distkeras_tpu.ops.metrics import accuracy
from distkeras_tpu.parallel import EnsembleTrainer, SingleTrainer


def synthetic_classification(n=2048, d=16, classes=4, seed=0):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, d).astype(np.float32)
    W = rs.randn(d, classes)
    y = np.argmax(X @ W + 0.1 * rs.randn(n, classes), axis=1)
    return Dataset({"features": X, "label": y})


def mlp(d=16, classes=4, seed=0):
    return Model.build(Sequential([
        Dense(64, activation="relu"),
        Dense(classes),
    ]), (d,), seed=seed)


def test_single_trainer_converges():
    ds = OneHotTransformer(4, output_col="label_encoded").transform(
        synthetic_classification())
    trainer = SingleTrainer(
        mlp(), worker_optimizer="adam", learning_rate=0.01,
        loss="categorical_crossentropy_from_logits",
        features_col="features", label_col="label_encoded",
        batch_size=64, num_epoch=5)
    model = trainer.train(ds)
    losses = trainer.get_history().losses()
    assert losses.shape == (5 * (2048 // 64),)
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])
    preds = model.predict(ds["features"])
    acc = float(accuracy(ds["label"], preds))
    assert acc > 0.85, acc
    assert trainer.get_training_time() > 0


def test_single_trainer_sparse_loss_and_history_summary():
    ds = synthetic_classification()
    trainer = SingleTrainer(
        mlp(), worker_optimizer="sgd", learning_rate=0.1,
        loss="sparse_categorical_crossentropy_from_logits",
        batch_size=128, num_epoch=3)
    trainer.train(ds)
    s = trainer.get_history().summary()
    assert s["num_epochs"] == 3
    assert s["num_steps"] == 3 * (2048 // 128)
    assert s["steps_per_second"] > 0
    assert np.isfinite(s["final_loss"])


def test_single_trainer_batch_too_large_raises():
    ds = synthetic_classification(n=16)
    trainer = SingleTrainer(mlp(), batch_size=64,
                            loss="sparse_categorical_crossentropy_from_logits")
    with pytest.raises(ValueError, match="batch_size"):
        trainer.train(ds)


def test_single_trainer_missing_label_column():
    ds = Dataset({"features": np.zeros((8, 16), np.float32)})
    trainer = SingleTrainer(mlp())
    with pytest.raises(ValueError, match="label"):
        trainer.train(ds)


def test_ensemble_trainer_trains_independent_models():
    ds = synthetic_classification()
    trainer = EnsembleTrainer(
        mlp(), num_models=3, worker_optimizer="adam", learning_rate=0.01,
        loss="sparse_categorical_crossentropy_from_logits",
        batch_size=128, num_epoch=3)
    models = trainer.train(ds)
    assert len(models) == 3
    # members differ (different seeds) but all learned
    k0 = np.asarray(models[0].params[0]["kernel"])
    k1 = np.asarray(models[1].params[0]["kernel"])
    assert not np.allclose(k0, k1)
    for m in models:
        preds = m.predict(ds["features"])
        assert float(accuracy(ds["label"], preds)) > 0.8
    losses = trainer.get_history().losses()
    assert losses.shape == (3 * (2048 // 128), 3)
    # averaged history is scalar per step
    assert trainer.get_averaged_history().shape == (3 * (2048 // 128),)


def test_profile_dir_writes_trace(tmp_path):
    import os

    import numpy as np

    from distkeras_tpu.data import Dataset
    from distkeras_tpu.models import Dense, Model, Sequential
    from distkeras_tpu.parallel import SingleTrainer

    rs = np.random.RandomState(0)
    X = rs.randn(128, 4).astype(np.float32)
    y = rs.randint(0, 2, 128)
    model = Model.build(Sequential([Dense(2)]), (4,), seed=0)
    pdir = str(tmp_path / "xprof")
    tr = SingleTrainer(model, batch_size=32, num_epoch=1,
                       loss="sparse_categorical_crossentropy_from_logits",
                       profile_dir=pdir)
    tr.train(Dataset({"features": X, "label": y}))
    # a plugin/profile directory with at least one trace artifact appears
    found = [os.path.join(r, f) for r, _, fs in os.walk(pdir) for f in fs]
    assert found, f"no trace files under {pdir}"


def test_model_fit_evaluate_keras_style():
    import numpy as np

    from distkeras_tpu.models import Dense, Model, Sequential

    rs = np.random.RandomState(0)
    X = rs.randn(1024, 8).astype(np.float32)
    y = (X @ rs.randn(8, 3)).argmax(-1)

    model = Model.build(Sequential([Dense(32, activation="relu"),
                                    Dense(3)]), (8,), seed=0)
    hist = model.fit(X, y, optimizer="momentum",
                     loss="sparse_categorical_crossentropy_from_logits",
                     optimizer_kwargs={"learning_rate": 0.1},
                     batch_size=64, epochs=4, metrics=["accuracy"])
    assert hist.losses().shape[0] == 4 * (1024 // 64)
    res = model.evaluate(
        X, y, loss="sparse_categorical_crossentropy_from_logits")
    assert res["accuracy"] > 0.9 and np.isfinite(res["loss"])


def test_fit_validation_split():
    import numpy as np

    from distkeras_tpu.models import Dense, Model, Sequential

    rs = np.random.RandomState(1)
    X = rs.randn(512, 8).astype(np.float32)
    y = (X @ rs.randn(8, 3)).argmax(-1)
    model = Model.build(Sequential([Dense(16, activation="relu"),
                                    Dense(3)]), (8,), seed=0)
    hist = model.fit(X, y, optimizer="adam", learning_rate=1e-2,
                     loss="sparse_categorical_crossentropy_from_logits",
                     batch_size=64, epochs=3, metrics=["accuracy"],
                     validation_split=0.25)
    # 384 train rows -> 6 steps/epoch; val metrics recorded per epoch
    assert hist.losses().shape[0] == 3 * (384 // 64)
    assert hist.metric("val_loss").shape == (3,)
    assert "val_accuracy" in hist.metric_names()

    with pytest.raises(ValueError, match="not both"):
        model.fit(X, y, validation_split=0.2, validation_data=(X, y),
                  loss="sparse_categorical_crossentropy_from_logits")
    with pytest.raises(ValueError, match="in \\(0, 1\\)"):
        model.fit(X, y, validation_split=1.5,
                  loss="sparse_categorical_crossentropy_from_logits")


def test_layer_trainable_false_freezes_params():
    """Keras-style freezing: a frozen layer's params are bitwise unchanged
    after training (and its adam moments stay zero), while the rest of
    the model still learns."""
    rs = np.random.RandomState(0)
    X = rs.randn(1024, 8).astype(np.float32)
    y = (X @ rs.randn(8, 3)).argmax(-1)

    backbone = Dense(32, activation="relu")
    head = Dense(3)
    backbone.trainable = False
    model = Model.build(Sequential([backbone, head]), (8,), seed=0)
    frozen_before = jax.device_get(model.params[0])

    trainer = SingleTrainer(
        model, batch_size=32, num_epoch=4, worker_optimizer="adam",
        optimizer_kwargs={"learning_rate": 1e-2},
        loss="sparse_categorical_crossentropy_from_logits")
    trained = trainer.train(Dataset({"features": X, "label": y}))

    for k in frozen_before:
        np.testing.assert_array_equal(np.asarray(trained.params[0][k]),
                                      frozen_before[k])
    # the head DID move and the model still learns through the frozen
    # random backbone
    assert not np.allclose(np.asarray(trained.params[1]["kernel"]),
                           np.asarray(model.params[1]["kernel"]))
    from distkeras_tpu.ops.metrics import accuracy
    assert float(accuracy(y, trained.predict(X))) > 0.6


def test_frozen_layer_immune_to_weight_decay_optimizers():
    """adamw/lars/lamb apply param-coupled weight-decay terms even with
    zero gradients — frozen params must still be bitwise unchanged (the
    updates are masked too, not just the gradients)."""
    rs = np.random.RandomState(0)
    X = rs.randn(256, 8).astype(np.float32)
    y = (X @ rs.randn(8, 3)).argmax(-1)
    for opt in ("adamw", "lars", "lamb"):
        backbone = Dense(16, activation="relu")
        backbone.trainable = False
        model = Model.build(Sequential([backbone, Dense(3)]), (8,), seed=0)
        before = jax.device_get(model.params[0])
        trainer = SingleTrainer(
            model, batch_size=32, num_epoch=2, worker_optimizer=opt,
            optimizer_kwargs={"learning_rate": 1e-2},
            loss="sparse_categorical_crossentropy_from_logits")
        trained = trainer.train(Dataset({"features": X, "label": y}))
        for k in before:
            np.testing.assert_array_equal(
                np.asarray(trained.params[0][k]), before[k],
                err_msg=f"{opt} moved frozen param {k!r}")


def test_frozen_batchnorm_keeps_running_stats():
    """Keras inference-mode semantics: a frozen BatchNorm's running
    mean/var must not drift toward the new data distribution."""
    from distkeras_tpu.models.layers import BatchNorm

    rs = np.random.RandomState(0)
    X = (rs.randn(512, 8) * 5 + 3).astype(np.float32)  # shifted data
    y = (X @ rs.randn(8, 3)).argmax(-1)
    bn = BatchNorm()
    bn.trainable = False
    model = Model.build(Sequential([Dense(16), bn, Dense(3)]), (8,), seed=0)
    state_before = jax.device_get(model.state[1])
    trainer = SingleTrainer(
        model, batch_size=32, num_epoch=2, worker_optimizer="sgd",
        learning_rate=0.05,
        loss="sparse_categorical_crossentropy_from_logits")
    trained = trainer.train(Dataset({"features": X, "label": y}))
    for k in state_before:
        np.testing.assert_array_equal(np.asarray(trained.state[1][k]),
                                      state_before[k])


def test_freeze_sublayer_inside_transformer_block():
    """Containers with sub_layers() recurse: freezing only a block's
    attention leaves its MLP trainable."""
    from distkeras_tpu.models import zoo

    rs = np.random.RandomState(0)
    toks = rs.randint(0, 16, (128, 8))
    module = zoo.transformer_lm(16, d_model=16, num_heads=2, num_layers=1,
                                mlp_ratio=2)
    blk = next(l for l in module.layers
               if type(l).__name__ == "TransformerBlock")
    blk.attn.trainable = False
    model = Model.build(module, (8,), seed=0)
    i = module.layers.index(blk)
    attn_before = jax.device_get(model.params[i]["attn"])
    mlp_before = jax.device_get(model.params[i]["mlp"])

    trainer = SingleTrainer(
        model, batch_size=16, num_epoch=2, worker_optimizer="adam",
        optimizer_kwargs={"learning_rate": 1e-2},
        loss="sparse_categorical_crossentropy_from_logits")
    trained = trainer.train(Dataset({"features": toks, "label": toks}))
    for k in attn_before:
        np.testing.assert_array_equal(
            np.asarray(trained.params[i]["attn"][k]), attn_before[k])
    assert not np.allclose(np.asarray(trained.params[i]["mlp"]["w1"]),
                           mlp_before["w1"])


# --- the donated carry -------------------------------------------------------
# ``make_epoch_runner`` donates its carry: every epoch updates parameters,
# optimizer state and key in place. The trainer copies the carry once before
# the first epoch, so nothing the caller owns is ever donated.

def _adam_trainer(model, num_epoch=3, **kw):
    return SingleTrainer(
        model, worker_optimizer="adam", learning_rate=0.01,
        loss="sparse_categorical_crossentropy_from_logits",
        batch_size=128, num_epoch=num_epoch, **kw)


def _leaves(tree):
    return [np.array(a) for a in jax.tree_util.tree_leaves(tree)]


def _checkpointed_epoch(ckpt_dir, ds):
    """Leave one epoch's checkpoint behind; returns the arguments that make
    the next trainer start from it."""
    _adam_trainer(mlp(), num_epoch=1, checkpoint_dir=ckpt_dir).train(ds)
    return dict(checkpoint_dir=ckpt_dir, resume=True)


@pytest.mark.parametrize("path", ["fresh", "resumed", "raises"])
def test_donation_never_reaches_the_callers_model(tmp_path, path):
    """Every leaf of the ``Model`` handed in is still readable, and bitwise
    what it was, after ``train()`` returns (from a fresh and from a restored
    carry) and after it raises mid-run."""
    from distkeras_tpu.resilience import faults
    ds = synthetic_classification(n=512)
    model = mlp()
    assert all(isinstance(a, jax.Array)
               for a in jax.tree_util.tree_leaves(model.params))
    before = _leaves((model.params, model.state))
    kw = _checkpointed_epoch(str(tmp_path), ds) if path == "resumed" else {}
    tr = _adam_trainer(model, **kw)
    if path == "raises":
        faults.inject("train.epoch", nth=2)
        try:
            with pytest.raises(faults.InjectedFault):
                tr.train(ds)
        finally:
            faults.reset()
        assert tr.master_model is model
    else:
        trained = tr.train(ds)
        assert len(tr.get_history().epochs) == (2 if path == "resumed" else 3)
        assert any((a != b).any()
                   for a, b in zip(_leaves(trained.params), before))
    leaves = jax.tree_util.tree_leaves((model.params, model.state))
    assert not any(a.is_deleted() for a in leaves)
    for a, b in zip(_leaves(leaves), before):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("ckpt_async", [False, True], ids=["sync", "async"])
def test_readers_between_epochs_see_the_new_carry_only(tmp_path, ckpt_async):
    """A callback fetching the weights at every epoch end, validation and a
    checkpoint every epoch neither disturb training (same losses as a bare
    run) nor are disturbed by it: what each read at epoch e is still epoch
    e's after epoch e+1 has updated the carry in place."""
    from distkeras_tpu.utils.callbacks import LambdaCallback
    from distkeras_tpu.utils.checkpoint import CheckpointManager
    ds = synthetic_classification(n=512)
    bare = _adam_trainer(mlp())
    bare.train(ds)

    seen = []
    tr = _adam_trainer(
        mlp(), checkpoint_dir=str(tmp_path), checkpoint_every=1,
        checkpoint_async=ckpt_async,
        validation_data=(ds["features"][:64], ds["label"][:64]),
        callbacks=[LambdaCallback(on_epoch_end=lambda e, logs: seen.append(
            (tr.get_weights(), logs["val_loss"])))])
    trained = tr.train(ds)
    np.testing.assert_array_equal(tr.get_history().losses(),
                                  bare.get_history().losses())
    assert len(seen) == 3
    mgr = CheckpointManager(str(tmp_path))
    # keep_last bounds what is still on disk: compare every epoch that is
    steps = mgr.all_steps()
    assert steps[-1] == 2 and len(steps) >= 2
    template = {"params": trained.params, "state": trained.state}
    for e in steps:
        (params, _), _ = seen[e]
        stored = mgr.restore(dict(template), step=e)["params"]
        for a, b in zip(_leaves(stored), _leaves(params)):
            np.testing.assert_array_equal(a, b)
    # epoch e's weights are not epoch e+1's, and the last are the result
    assert any((a != b).any() for a, b in zip(_leaves(seen[0][0][0]),
                                              _leaves(seen[1][0][0])))
    for a, b in zip(_leaves(seen[-1][0][0]), _leaves(trained.params)):
        np.testing.assert_array_equal(a, b)
    assert len({v for _, v in seen}) == 3       # validation saw each epoch


@pytest.mark.parametrize("resumed", [False, True], ids=["fresh", "resumed"])
def test_epoch_program_compiles_once_and_tape_says_donated(tmp_path, resumed):
    """The one copy before the first epoch gives epoch 1 the signature of
    every later epoch: one cache entry, the recompile detector silent from
    the first epoch on. The tape says what the program does with its carry
    and how large that is."""
    import warnings

    from distkeras_tpu import obs
    ds = synthetic_classification(n=512)
    kw = _checkpointed_epoch(str(tmp_path), ds) if resumed else {}
    model = mlp()
    tr = _adam_trainer(model, **kw)
    with warnings.catch_warnings():
        warnings.simplefilter("error", obs.RecompileWarning)
        tr.train(ds)
    snap = tr.tape.snapshot()
    assert snap["recompiles"] == {"SingleTrainer.epoch": 1}
    assert tr.tape.check_recompiles() == {}
    assert snap["programs"] == {"SingleTrainer.epoch": "carry=donated"}
    # parameters and Adam's two moments, its step count and the key
    param_bytes = sum(a.nbytes
                      for a in jax.tree_util.tree_leaves(model.params))
    assert snap["carry_bytes"] == 3 * param_bytes + 4 + 8
    assert tr.tape.registry.gauge("SingleTrainer.carry_bytes").value() \
        == snap["carry_bytes"]
