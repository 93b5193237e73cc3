"""Tensor/expert-parallel sharding rules + SPMDTrainer on the 8-device mesh.

Covers the capability-ADD parallelism rows of SURVEY §2.3 (TP/EP/FSDP — all
absent in the reference): spec generation over the layer tree, GSPMD forward
parity between replicated and sharded placements, and end-to-end dp×tp
training that actually learns.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distkeras_tpu.data import Dataset
from distkeras_tpu.models import Dense, Model, Sequential, zoo
from distkeras_tpu.models.attention import TransformerBlock
from distkeras_tpu.models.layers import Embedding
from distkeras_tpu.models.moe import MoE
from distkeras_tpu.ops.metrics import accuracy
from distkeras_tpu.parallel import (SPMDTrainer, make_mesh_2d, param_specs,
                                    shard_params)


def tiny_lm(vocab=32, d=16, heads=4, blocks=2, mlp_layer=None):
    layers = [Embedding(vocab, d)]
    for _ in range(blocks):
        layers.append(TransformerBlock(num_heads=heads, mlp_ratio=2,
                                       causal=True,
                                       mlp_layer=mlp_layer))
    layers.append(Dense(vocab, use_bias=False))
    return Sequential(layers)


# ---------------------------------------------------------------------------
# spec generation
# ---------------------------------------------------------------------------

def test_param_specs_transformer_megatron_split():
    mesh = make_mesh_2d({"workers": 2, "tp": 4})
    module = tiny_lm()
    model = Model.build(module, (8,), seed=0)
    specs = param_specs(module, model.params, mesh, tp_axis="tp")
    # structure mirrors params
    assert jax.tree_util.tree_structure(specs) == \
        jax.tree_util.tree_structure(
            jax.tree_util.tree_map(lambda x: P(), model.params))
    blk = specs[1]
    assert blk["attn"]["wq"] == P(None, "tp", None)
    assert blk["attn"]["wo"] == P("tp", None, None)
    assert blk["mlp"]["w1"] == P(None, "tp")
    assert blk["mlp"]["w2"] == P("tp", None)
    assert blk["norm1"]["scale"] == P()
    assert specs[0]["embeddings"] == P(None, "tp")  # embed dim sharded
    assert specs[-1]["kernel"] == P(None, "tp")     # vocab head sharded


def test_param_specs_indivisible_falls_back_replicated():
    mesh = make_mesh_2d({"tp": 8})
    module = Sequential([Dense(6), Dense(3)])  # 6, 3 not divisible by 8
    model = Model.build(module, (5,), seed=0)
    specs = param_specs(module, model.params, mesh, tp_axis="tp")
    assert specs[0]["kernel"] == P(None, None)
    assert specs[1]["bias"] == P(None)


def test_param_specs_moe_expert_parallel():
    mesh = make_mesh_2d({"ep": 4, "tp": 2})
    moe = MoE(num_experts=8, hidden_dim=32, top_k=2)
    module = tiny_lm(mlp_layer=moe)
    model = Model.build(module, (8,), seed=0)
    specs = param_specs(module, model.params, mesh, tp_axis="tp",
                        ep_axis="ep")
    m = specs[1]["mlp"]
    assert m["gate"] == P()
    assert m["w1"] == P("ep", None, "tp")
    assert m["w2"] == P("ep", "tp", None)


def test_fsdp_shards_large_replicated_kernels():
    mesh = make_mesh_2d({"workers": 8})
    module = Sequential([Dense(512), Dense(10)])
    model = Model.build(module, (256,), seed=0)
    specs = param_specs(module, model.params, mesh, tp_axis=None,
                        fsdp_axis="workers")
    # 256x512 kernel: biggest divisible dim gets the fsdp axis
    assert "workers" in tuple(specs[0]["kernel"])
    # 512x10 kernel (5120 < min_fsdp_size) stays fully replicated
    assert tuple(specs[1]["kernel"]) in ((None, None), ())


# ---------------------------------------------------------------------------
# GSPMD numerical parity
# ---------------------------------------------------------------------------

def test_tp_sharded_forward_matches_replicated():
    mesh = make_mesh_2d({"workers": 2, "tp": 4})
    module = tiny_lm()
    model = Model.build(module, (8,), seed=3)
    x = np.random.RandomState(0).randint(0, 32, (4, 8))

    fwd = jax.jit(lambda p, s, b: module.apply(p, s, b, training=False)[0])
    y_ref = np.asarray(fwd(model.params, model.state, x))

    specs = param_specs(module, model.params, mesh, tp_axis="tp")
    sharded = shard_params(model.params, specs, mesh)
    xb = jax.device_put(jnp.asarray(x),
                        NamedSharding(mesh, P("workers")))
    y_tp = np.asarray(fwd(sharded, model.state, xb))
    np.testing.assert_allclose(y_ref, y_tp, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# end-to-end training
# ---------------------------------------------------------------------------

def test_spmd_trainer_learns_dp_tp():
    rs = np.random.RandomState(0)
    N, D, C = 2048, 16, 4
    X = rs.randn(N, D).astype(np.float32)
    W = rs.randn(D, C)
    y = np.argmax(X @ W, axis=1)
    ds = Dataset({"features": X, "label": y})

    mesh = make_mesh_2d({"workers": 2, "tp": 4})
    model = Model.build(Sequential([Dense(64, activation="relu"),
                                    Dense(C)]), (D,), seed=0)
    trainer = SPMDTrainer(
        model, mesh=mesh, data_axes=("workers",), tp_axis="tp",
        batch_size=128, num_epoch=6, worker_optimizer="momentum",
        optimizer_kwargs={"learning_rate": 0.1},
        loss="sparse_categorical_crossentropy_from_logits")
    trained = trainer.train(ds)
    acc = float(accuracy(y, trained.predict(X)))
    assert acc > 0.85, acc
    losses = trainer.get_history().losses()
    assert np.isfinite(losses).all()
    assert losses[-8:].mean() < losses[:8].mean() * 0.7


def test_spmd_trainer_matches_single_device_sgd():
    """dp×tp sharding must not change the math: same data order, no
    shuffling, plain SGD ⇒ losses match an unsharded run step-for-step."""
    rs = np.random.RandomState(1)
    N, D, C = 512, 8, 3
    X = rs.randn(N, D).astype(np.float32)
    y = rs.randint(0, C, N)
    ds = Dataset({"features": X, "label": y})
    kwargs = dict(batch_size=64, num_epoch=2, worker_optimizer="sgd",
                  optimizer_kwargs={"learning_rate": 0.05},
                  loss="sparse_categorical_crossentropy_from_logits",
                  shuffle_each_epoch=False)

    from distkeras_tpu.parallel import SingleTrainer
    m1 = Model.build(Sequential([Dense(32, activation="tanh"), Dense(C)]),
                     (D,), seed=7)
    single = SingleTrainer(m1, **kwargs)
    single.train(ds)
    ref_losses = single.get_history().losses()

    mesh = make_mesh_2d({"workers": 4, "tp": 2})
    m2 = Model.build(Sequential([Dense(32, activation="tanh"), Dense(C)]),
                     (D,), seed=7)
    spmd = SPMDTrainer(m2, mesh=mesh, tp_axis="tp", **kwargs)
    spmd.train(ds)
    np.testing.assert_allclose(ref_losses, spmd.get_history().losses(),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("mesh_shape", [{"workers": 4, "tp": 2},
                                        {"workers": 8}])
def test_spmd_trainer_flash_kernel_runs_per_shard(mesh_shape):
    """XLA cannot partition a Mosaic kernel, so under SPMDTrainer the
    flash kernel (interpreter here) runs inside a shard_map over the
    data and tp axes: the step must hold one kernel call per shard of
    batch/heads — no q/k/v all-gather — and train step-for-step like
    the unsharded run."""
    from distkeras_tpu.ops import flash_attention as fa
    from distkeras_tpu.parallel import SingleTrainer
    rs = np.random.RandomState(3)
    vocab, seq = 32, 16
    X = rs.randint(0, vocab, (64, seq)).astype(np.int32)
    ds = Dataset({"features": X, "label": np.roll(X, -1, axis=1)})
    kwargs = dict(batch_size=16, num_epoch=1, worker_optimizer="sgd",
                  optimizer_kwargs={"learning_rate": 0.05},
                  loss="sparse_categorical_crossentropy_from_logits",
                  shuffle_each_epoch=False)

    def lm():
        return Model.build(
            zoo.transformer_lm(vocab, d_model=16, num_heads=4,
                               num_layers=1, mlp_ratio=2,
                               attn_impl="flash"), (seq,), seed=5)

    single = SingleTrainer(lm(), **kwargs)
    single.train(ds)
    seen = []
    real = fa._flash_forward
    def spy(q, *a, **k):
        seen.append(q.shape)
        return real(q, *a, **k)
    fa._flash_forward = spy
    try:
        spmd = SPMDTrainer(lm(), mesh=make_mesh_2d(mesh_shape),
                           tp_axis="tp", **kwargs)
        spmd.train(ds)
    finally:
        fa._flash_forward = real
    # the kernel saw ONE SHARD: batch / workers, heads / tp
    want = (16 // mesh_shape["workers"], 4 // mesh_shape.get("tp", 1),
            seq, 4)
    assert seen and all(sh == want for sh in seen), (seen, want)
    # state and batch really sat on all eight devices, and the epoch
    # program can be read back: under pure data parallelism nothing in
    # it all-gathers (q/k/v reach the kernel as the shards they are)
    assert spmd.placement == {"params": list(range(8)),
                              "batch": list(range(8))}
    if "tp" not in mesh_shape:
        assert "all-gather" not in spmd.lower_epoch().compile().as_text()
    np.testing.assert_allclose(single.get_history().losses(),
                               spmd.get_history().losses(),
                               rtol=1e-4, atol=1e-5)


def test_spmd_trainer_moe_ep():
    """MoE classification over dp×ep×tp axes (expert parallelism)."""
    rs = np.random.RandomState(2)
    N, D, C = 1024, 12, 3
    X = rs.randn(N, D).astype(np.float32)
    W = rs.randn(D, C)
    y = np.argmax(X @ W, axis=1)
    ds = Dataset({"features": X, "label": y})

    # MoE operates on [B, S, d]; reshape features to a length-3 sequence
    from distkeras_tpu.models.layers import Reshape, Flatten
    module = Sequential([
        Reshape((3, 4)),
        MoE(num_experts=4, hidden_dim=16, top_k=2),
        Flatten(),
        Dense(C),
    ])
    model = Model.build(module, (D,), seed=0)

    mesh = make_mesh_2d({"workers": 2, "ep": 2, "tp": 2})
    trainer = SPMDTrainer(
        model, mesh=mesh, data_axes=("workers",), tp_axis="tp", ep_axis="ep",
        batch_size=128, num_epoch=8, worker_optimizer="adam",
        optimizer_kwargs={"learning_rate": 0.01},
        loss="sparse_categorical_crossentropy_from_logits")
    trained = trainer.train(ds)
    acc = float(accuracy(y, trained.predict(X)))
    assert acc > 0.8, acc


def test_spmd_trainer_resume_exact(tmp_path):
    """Full-carry checkpointing: interrupted+resumed == uninterrupted."""
    rs = np.random.RandomState(3)
    N, D, C = 512, 8, 3
    X = rs.randn(N, D).astype(np.float32)
    y = rs.randint(0, C, N)
    ds = Dataset({"features": X, "label": y})
    mesh = make_mesh_2d({"workers": 2, "tp": 2})
    kwargs = dict(mesh=mesh, tp_axis="tp", batch_size=64,
                  worker_optimizer="adam",
                  optimizer_kwargs={"learning_rate": 0.01},
                  loss="sparse_categorical_crossentropy_from_logits")

    def fresh_model():
        return Model.build(Sequential([Dense(32, activation="relu"),
                                       Dense(C)]), (D,), seed=5)

    ref = SPMDTrainer(fresh_model(), num_epoch=4, **kwargs)
    ref.train(ds)

    cdir = str(tmp_path / "ckpt")
    part = SPMDTrainer(fresh_model(), num_epoch=2, checkpoint_dir=cdir,
                       **kwargs)
    part.train(ds)
    resumed = SPMDTrainer(fresh_model(), num_epoch=4, checkpoint_dir=cdir,
                          resume=True, **kwargs)
    m2 = resumed.train(ds)

    # adam moments + rng restored => identical continuation
    np.testing.assert_allclose(ref.get_history().losses()[-4:],
                               resumed.get_history().losses()[-4:],
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(ref.master_model.params),
                    jax.tree_util.tree_leaves(m2.params)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_spmd_trainer_rejects_unknown_data_axis():
    mesh = make_mesh_2d({"workers": 8})
    model = Model.build(Sequential([Dense(4)]), (8,), seed=0)
    with pytest.raises(ValueError, match="data_axes"):
        SPMDTrainer(model, mesh=mesh, data_axes=("worker",), batch_size=8)


def test_spmd_trainer_resumes_old_format_checkpoint(tmp_path):
    """Checkpoints written before the full-carry format (params/state only)
    must restore with a warning, not a KeyError."""
    from distkeras_tpu.utils.checkpoint import CheckpointManager

    rs = np.random.RandomState(4)
    X = rs.randn(256, 8).astype(np.float32)
    y = rs.randint(0, 3, 256)
    ds = Dataset({"features": X, "label": y})
    model = Model.build(Sequential([Dense(16, activation="relu"),
                                    Dense(3)]), (8,), seed=0)

    cdir = str(tmp_path / "old")
    CheckpointManager(cdir).save(
        0, {"params": model.params, "state": model.state},
        metadata={"epoch": 0})

    mesh = make_mesh_2d({"workers": 2, "tp": 2})
    trainer = SPMDTrainer(
        model, mesh=mesh, tp_axis="tp", batch_size=64, num_epoch=3,
        checkpoint_dir=cdir, resume=True, worker_optimizer="adam",
        optimizer_kwargs={"learning_rate": 0.01},
        loss="sparse_categorical_crossentropy_from_logits")
    with pytest.warns(UserWarning, match="full-carry"):
        trainer.train(ds)
    # resumed at epoch 1, trained the remaining 2
    assert trainer.get_history().losses().shape[0] == 2 * (256 // 64)


def test_predictor_tp_sharded_params():
    """Sharded inference: tp-sharded placement == replicated numerics."""
    from distkeras_tpu.inference import Predictor

    mesh = make_mesh_2d({"workers": 2, "tp": 4})
    module = tiny_lm()
    model = Model.build(module, (8,), seed=3)
    X = np.random.RandomState(0).randint(0, 32, (40, 8))
    ds = Dataset({"features": X})

    ref = Predictor(model, batch_size_per_device=8).predict(ds)["prediction"]
    tp = Predictor(model, mesh=mesh, tp_axis="tp",
                   batch_size_per_device=8).predict(ds)["prediction"]
    assert tp.shape == (40, 8, 32)  # [rows, seq, vocab]
    np.testing.assert_allclose(ref, tp, rtol=2e-5, atol=2e-5)


def test_distributed_resume_with_different_worker_count(tmp_path):
    """Elastic recovery: the center checkpoint restores under a DIFFERENT
    worker count (workers restart from the center, so the mesh shape is
    free to change between runs — the hardware-failure/resize story)."""
    from distkeras_tpu.parallel import ADAG

    rs = np.random.RandomState(0)
    X = rs.randn(512, 8).astype(np.float32)
    y = (X @ rs.randn(8, 3)).argmax(-1)
    ds = Dataset({"features": X, "label": y})
    cdir = str(tmp_path / "ck")
    kwargs = dict(batch_size=16, communication_window=2,
                  worker_optimizer="sgd",
                  optimizer_kwargs={"learning_rate": 0.1},
                  loss="sparse_categorical_crossentropy_from_logits",
                  checkpoint_dir=cdir)

    def fresh():
        return Model.build(Sequential([Dense(16, activation="relu"),
                                       Dense(3)]), (8,), seed=0)

    ADAG(fresh(), num_workers=8, num_epoch=2, **kwargs).train(ds)
    resumed = ADAG(fresh(), num_workers=4, num_epoch=5, resume=True,
                   **kwargs)
    m = resumed.train(ds)
    losses = resumed.get_history().losses()
    assert losses.shape == (3 * (512 // (4 * 16)), 4)  # 3 epochs, 4 workers
    from distkeras_tpu.ops.metrics import accuracy
    assert float(accuracy(y, m.predict(X))) > 0.8


def test_gqa_tp_sharding_degrades_kv_to_replicated():
    """tp divides num_heads but not num_kv_heads: wq/wo shard on heads,
    wk/wv degrade to replicated (never an error)."""
    from distkeras_tpu.models import Model, zoo

    mesh = make_mesh_2d({"workers": 2, "tp": 4})
    module = zoo.transformer_lm(16, d_model=32, num_heads=8,
                                num_kv_heads=2, num_layers=1, mlp_ratio=2)
    model = Model.build(module, (8,), seed=0)
    specs = param_specs(module, model.params, mesh, tp_axis="tp")
    blk = next(i for i, l in enumerate(module.layers)
               if type(l).__name__ == "TransformerBlock")
    attn = specs[blk]["attn"]
    assert attn["wq"] == P(None, "tp", None)
    assert attn["wo"] == P("tp", None, None)
    assert attn["wk"] == P(None, None, None)   # 2 kv heads, tp=4
    assert attn["wv"] == P(None, None, None)
    # and the placement actually works end-to-end
    shard_params(model.params, specs, mesh)
