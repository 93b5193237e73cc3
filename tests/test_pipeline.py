"""Pipeline parallelism (GPipe ppermute ring) on the 8-device virtual mesh.

Correctness bar: the pipelined program is the SAME math as the unsharded
layer stack — forward outputs match, and one full dp×pp training step
produces the same loss trajectory as a hand-rolled single-device reference.
The sp composition runs ring attention inside pipelined blocks.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from distkeras_tpu.compat import shard_map
from distkeras_tpu.data import Dataset
from distkeras_tpu.models.attention import TransformerBlock
from distkeras_tpu.models.layers import Dense, Embedding
from distkeras_tpu.ops.losses import get_loss
from distkeras_tpu.ops.optimizers import apply_updates, get_optimizer
from distkeras_tpu.parallel.mesh import make_mesh_2d
from distkeras_tpu.parallel.pipeline import (PipelinedLM, PipelineTrainer,
                                             init_stacked_blocks,
                                             make_pipeline_fn)

V, D, S = 16, 16, 8


def lm(num_layers=4, num_microbatches=2, attn_impl="xla", seq_axis=None):
    return PipelinedLM(
        embed=Embedding(V, D),
        block=TransformerBlock(num_heads=4, mlp_ratio=2, causal=True,
                               attn_impl=attn_impl, seq_axis_name=seq_axis),
        head=Dense(V, use_bias=False),
        num_layers=num_layers, num_microbatches=num_microbatches)


def test_pipeline_forward_matches_sequential():
    mesh = make_mesh_2d({"pp": 4})
    block = TransformerBlock(num_heads=4, mlp_ratio=2, causal=True)
    _, _, shape = Embedding(V, D).init(jax.random.PRNGKey(0), (S,))
    stacked, bstate = init_stacked_blocks(block, jax.random.PRNGKey(1),
                                          shape, 8)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 4, S, D))  # [M, mb,...]

    # sequential reference
    def seq_apply(h):
        def body(h, p):
            y, _ = block.apply(p, bstate, h, training=False)
            return y, None
        return lax.scan(body, h, stacked)[0]

    y_ref = np.asarray(jax.vmap(seq_apply)(x))

    pipe = make_pipeline_fn(block, "pp", bstate)
    fn = jax.jit(shard_map(
        pipe, mesh=mesh, in_specs=(P("pp"), P()), out_specs=P()))
    y_pipe = np.asarray(fn(stacked, x))
    np.testing.assert_allclose(y_ref, y_pipe, rtol=2e-5, atol=2e-5)


def test_pipeline_train_step_matches_reference():
    """One dp×pp train step == single-device step on the same global batch."""
    mesh = make_mesh_2d({"workers": 2, "pp": 4})
    model = lm(num_layers=4, num_microbatches=2)
    params, _ = model.init(jax.random.PRNGKey(0), (S,))
    loss_fn = get_loss("sparse_categorical_crossentropy_from_logits")
    opt = get_optimizer("sgd", learning_rate=0.1)

    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randint(0, V, (8, S)))
    y = jnp.asarray(rs.randint(0, V, (8, S)))

    # reference: grad through the unsharded forward
    def ref_obj(p):
        return loss_fn(y, model.apply(p, x))

    ref_loss, ref_grads = jax.value_and_grad(ref_obj)(params)
    ref_updates, _ = opt.update(ref_grads, opt.init(params), params)
    ref_params = apply_updates(params, ref_updates)

    step = model.make_train_step(loss_fn, opt, mesh)
    sharded = model.shard_variables(params, mesh)
    (new_params, _), loss = step((sharded, jax.jit(opt.init)(sharded)),
                                 (x, y))
    assert np.allclose(float(loss), float(ref_loss), rtol=1e-5)
    for ref_leaf, leaf in zip(jax.tree_util.tree_leaves(ref_params),
                              jax.tree_util.tree_leaves(
                                  jax.device_get(new_params))):
        np.testing.assert_allclose(ref_leaf, leaf, rtol=1e-4, atol=1e-5)


def test_pipeline_trainer_learns():
    """Copy-task LM over dp×pp: predict the current token (easy), loss must
    collapse."""
    rs = np.random.RandomState(0)
    X = rs.randint(0, V, (512, S))
    ds = Dataset({"features": X, "label": X})

    mesh = make_mesh_2d({"workers": 2, "pp": 4})
    trainer = PipelineTrainer(
        lm(num_layers=4, num_microbatches=2), mesh,
        worker_optimizer="adam", optimizer_kwargs={"learning_rate": 0.01},
        batch_size=64, num_epoch=6)
    trainer.train(ds)
    losses = trainer.get_history().losses()
    assert np.isfinite(losses).all()
    assert losses[-4:].mean() < 0.3 * losses[:4].mean(), losses

    # predictions actually copy
    logits = trainer.predict(X[:16])
    acc = (logits.argmax(-1) == X[:16]).mean()
    assert acc > 0.9, acc


def test_pipeline_with_ring_attention_sp():
    """dp×pp×sp: ring attention inside pipelined blocks, sequence sharded."""
    mesh = make_mesh_2d({"workers": 2, "pp": 2, "sp": 2})
    rs = np.random.RandomState(1)
    X = rs.randint(0, V, (256, S))
    ds = Dataset({"features": X, "label": X})

    trainer = PipelineTrainer(
        lm(num_layers=2, num_microbatches=2, attn_impl="ring",
           seq_axis="sp"),
        mesh, seq_axis="sp",
        worker_optimizer="adam", optimizer_kwargs={"learning_rate": 0.01},
        batch_size=64, num_epoch=6,
        # sequence-parallel validation: the validator must bind the sp
        # axis (round-3 regression: it used to run unsharded and crash)
        validation_data=(X[:32], X[:32]))
    trainer.train(ds)
    losses = trainer.get_history().losses()
    assert np.isfinite(losses).all()
    assert losses[-4:].mean() < 0.5 * losses[:4].mean(), losses


def test_pipeline_with_ulysses_attention_sp():
    """dp×pp×sp with the all-to-all (Ulysses) sequence-parallel path."""
    mesh = make_mesh_2d({"workers": 2, "pp": 2, "sp": 2})
    rs = np.random.RandomState(2)
    X = rs.randint(0, V, (256, S))
    ds = Dataset({"features": X, "label": X})

    trainer = PipelineTrainer(
        lm(num_layers=2, num_microbatches=2, attn_impl="ulysses",
           seq_axis="sp"),
        mesh, seq_axis="sp",
        worker_optimizer="adam", optimizer_kwargs={"learning_rate": 0.01},
        batch_size=64, num_epoch=6)
    trainer.train(ds)
    losses = trainer.get_history().losses()
    assert np.isfinite(losses).all()
    assert losses[-4:].mean() < 0.5 * losses[:4].mean(), losses


def test_pipeline_trainer_metrics_validation_and_callbacks(tmp_path):
    """Family parity (round 3): training metrics, per-epoch validation
    scalars, and EarlyStopping through the shared callback machinery."""
    from distkeras_tpu.utils.callbacks import CSVLogger, EarlyStopping

    rs = np.random.RandomState(3)
    X = rs.randint(0, V, (256, S))
    ds = Dataset({"features": X, "label": X})
    Xv = rs.randint(0, V, (64, S))

    csv = str(tmp_path / "log.csv")
    trainer = PipelineTrainer(
        lm(num_layers=2, num_microbatches=2),
        make_mesh_2d({"workers": 4, "pp": 2}),
        worker_optimizer="adam", optimizer_kwargs={"learning_rate": 0.01},
        batch_size=64, num_epoch=30,
        metrics=["accuracy"],
        validation_data=(Xv, Xv),
        callbacks=[EarlyStopping(monitor="loss", patience=2,
                                 min_delta=0.5),
                   CSVLogger(csv)])
    trainer.train(ds)
    ep = trainer.get_history().epochs
    assert len(ep) < 30  # early stopping fired before the epoch cap
    assert "accuracy" in ep[0]
    assert "val_loss" in ep[-1] and "val_accuracy" in ep[-1]
    # training accuracy on the copy task climbs
    first = float(np.mean(ep[0]["accuracy"]))
    last = float(np.mean(ep[-1]["accuracy"]))
    assert last > first
    import csv as _csv
    rows = list(_csv.DictReader(open(csv)))
    assert rows and "val_loss" in rows[0]


def test_pipeline_trainer_resume_exact(tmp_path):
    """Full-carry checkpoint/resume: train 4 epochs straight vs 2 + resume
    2 — identical final params (the Single/SPMD-trainer guarantee)."""
    rs = np.random.RandomState(4)
    X = rs.randint(0, V, (128, S))
    ds = Dataset({"features": X, "label": X})

    def make(num_epoch, ckpt, resume):
        return PipelineTrainer(
            lm(num_layers=2, num_microbatches=2),
            make_mesh_2d({"workers": 4, "pp": 2}),
            worker_optimizer="adam",
            optimizer_kwargs={"learning_rate": 0.01},
            batch_size=64, num_epoch=num_epoch, seed=7,
            checkpoint_dir=ckpt, resume=resume)

    p_straight = make(4, None, False).train(ds)

    ck = str(tmp_path / "ck")
    make(2, ck, False).train(ds)
    p_resumed = make(4, ck, True).train(ds)

    for a, b in zip(jax.tree_util.tree_leaves(p_straight),
                    jax.tree_util.tree_leaves(p_resumed)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_pipeline_bubble_fraction_accounting():
    """GPipe bubble: (P-1)/(M+P-1); num_microbatches is the lever (a 1F1B
    reordering matches GPipe's bubble at equal M — docs/parallelism.md)."""
    m = lm(num_layers=4, num_microbatches=4)
    assert m.bubble_fraction(pp=2) == 1 / 5
    assert m.bubble_fraction(pp=4) == 3 / 7
    m8 = lm(num_layers=4, num_microbatches=8)
    assert m8.bubble_fraction(pp=2) == 1 / 9  # more microbatches -> less
    assert lm(num_layers=4, num_microbatches=1).bubble_fraction(1) == 0.0


def _interleave_perm(num_layers, pp, v):
    lpc = num_layers // (pp * v)
    return np.array([(q * pp + d) * lpc + l
                     for d in range(pp) for q in range(v)
                     for l in range(lpc)])


def test_interleaved_forward_matches_sequential():
    """virtual_stages=2 (round 4): the interleaved schedule is the SAME
    math as the sequential stack — chunk j on device j%P, params permuted
    device-major/chunk-minor to match GSPMD's contiguous tiling."""
    mesh = make_mesh_2d({"pp": 4})
    block = TransformerBlock(num_heads=4, mlp_ratio=2, causal=True)
    _, _, shape = Embedding(V, D).init(jax.random.PRNGKey(0), (S,))
    stacked, bstate = init_stacked_blocks(block, jax.random.PRNGKey(1),
                                          shape, 8)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 4, S, D))

    def seq_apply(h):
        def body(h, p):
            y, _ = block.apply(p, bstate, h, training=False)
            return y, None
        return lax.scan(body, h, stacked)[0]

    y_ref = np.asarray(jax.vmap(seq_apply)(x))

    perm = _interleave_perm(8, 4, 2)
    permuted = jax.tree_util.tree_map(lambda l: l[perm], stacked)
    pipe = make_pipeline_fn(block, "pp", bstate, virtual_stages=2)
    fn = jax.jit(shard_map(
        pipe, mesh=mesh, in_specs=(P("pp"), P()), out_specs=P()))
    y_pipe = np.asarray(fn(permuted, x))
    np.testing.assert_allclose(y_ref, y_pipe, rtol=2e-5, atol=2e-5)


def test_interleaved_train_step_matches_gpipe():
    """virtual_stages=2 produces the same loss and updated params as the
    v=1 GPipe schedule at equal microbatches (schedule changes the tick
    order, never the math)."""
    mesh = make_mesh_2d({"workers": 2, "pp": 2})
    loss_fn = get_loss("sparse_categorical_crossentropy_from_logits")
    opt = get_optimizer("sgd", learning_rate=0.1)
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randint(0, V, (8, S)))
    y = jnp.asarray(rs.randint(0, V, (8, S)))

    results = {}
    for v in (1, 2):
        model = PipelinedLM(
            embed=Embedding(V, D),
            block=TransformerBlock(num_heads=4, mlp_ratio=2, causal=True),
            head=Dense(V, use_bias=False),
            num_layers=4, num_microbatches=2, virtual_stages=v)
        params, _ = model.init(jax.random.PRNGKey(0), (S,))
        step = model.make_train_step(loss_fn, opt, mesh)
        sharded = model.shard_variables(params, mesh)
        (new_params, _), loss = step((sharded, jax.jit(opt.init)(sharded)),
                                     (x, y))
        results[v] = (float(loss), jax.device_get(new_params))

    assert np.allclose(results[1][0], results[2][0], rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(results[1][1]),
                    jax.tree_util.tree_leaves(results[2][1])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_interleaved_bubble_and_validation():
    m = PipelinedLM(embed=Embedding(V, D),
                    block=TransformerBlock(num_heads=4, mlp_ratio=2),
                    head=Dense(V), num_layers=8, num_microbatches=4,
                    virtual_stages=2)
    # (P-1)/(M*v + P-1)
    assert m.bubble_fraction(pp=2) == 1 / 9
    assert m.bubble_fraction(pp=4) == 3 / 11
    import pytest as _pytest
    mesh = make_mesh_2d({"workers": 2, "pp": 4})
    loss_fn = get_loss("sparse_categorical_crossentropy_from_logits")
    opt = get_optimizer("sgd", learning_rate=0.1)
    m.init(jax.random.PRNGKey(0), (S,))
    bad = PipelinedLM(embed=Embedding(V, D),
                      block=TransformerBlock(num_heads=4, mlp_ratio=2),
                      head=Dense(V), num_layers=8, num_microbatches=2,
                      virtual_stages=2)
    bad.init(jax.random.PRNGKey(0), (S,))
    with _pytest.raises(ValueError, match="groups of P"):
        bad.make_train_step(loss_fn, opt, mesh)
    worse = PipelinedLM(embed=Embedding(V, D),
                        block=TransformerBlock(num_heads=4, mlp_ratio=2),
                        head=Dense(V), num_layers=6, num_microbatches=4,
                        virtual_stages=2)
    worse.init(jax.random.PRNGKey(0), (S,))
    with _pytest.raises(ValueError, match="virtual_stages"):
        worse.make_train_step(loss_fn, opt, mesh)
    with _pytest.raises(ValueError, match="virtual_stages"):
        PipelinedLM(embed=Embedding(V, D),
                    block=TransformerBlock(num_heads=4, mlp_ratio=2),
                    head=Dense(V), num_layers=8, virtual_stages=0)
