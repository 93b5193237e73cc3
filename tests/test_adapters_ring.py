"""Data adapters (torch/iterable ingest) + blocked ring attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distkeras_tpu.compat import shard_map
from distkeras_tpu.data import Dataset, from_iterable, from_torch
from distkeras_tpu.ops.attention import dot_product_attention
from distkeras_tpu.ops.ring_attention import ring_attention
from distkeras_tpu.parallel.mesh import make_mesh


# ---------------------------------------------------------------------------
# adapters
# ---------------------------------------------------------------------------

def test_from_iterable_pairs_and_dicts():
    rs = np.random.RandomState(0)
    rows = [(rs.randn(4), i % 3) for i in range(10)]
    ds = from_iterable(rows)
    assert ds["features"].shape == (10, 4)
    assert ds["label"].shape == (10,)

    ds2 = from_iterable([{"a": rs.randn(2), "b": 1} for _ in range(5)])
    assert ds2["a"].shape == (5, 2) and ds2["b"].shape == (5,)

    with pytest.raises(ValueError, match="empty"):
        from_iterable([])


def test_from_torch_dataset_and_loader():
    torch = pytest.importorskip("torch")
    from torch.utils.data import DataLoader, TensorDataset

    X = torch.randn(32, 6)
    y = torch.randint(0, 3, (32,))
    tds = TensorDataset(X, y)

    ds = from_torch(tds)
    assert ds["features"].shape == (32, 6)
    np.testing.assert_allclose(ds["features"], X.numpy(), rtol=1e-6)

    loader = DataLoader(tds, batch_size=10)  # ragged final batch
    ds2 = from_torch(loader)
    assert ds2["features"].shape == (32, 6)
    np.testing.assert_allclose(ds2["label"], y.numpy())

    ds3 = from_torch(tds, limit=7)
    assert len(ds3["features"]) == 7

    # batch_size=None DataLoader yields SAMPLES, not batches
    ds4 = from_torch(DataLoader(tds, batch_size=None))
    assert ds4["features"].shape == (32, 6)
    np.testing.assert_allclose(ds4["label"], y.numpy())

    # adapters feed trainers directly
    from distkeras_tpu.models import Dense, Model, Sequential
    from distkeras_tpu.parallel import SingleTrainer
    model = Model.build(Sequential([Dense(3)]), (6,), seed=0)
    tr = SingleTrainer(model, batch_size=8, num_epoch=1,
                       loss="sparse_categorical_crossentropy_from_logits")
    tr.train(ds2)
    assert np.isfinite(tr.get_history().losses()).all()


# ---------------------------------------------------------------------------
# blocked ring attention
# ---------------------------------------------------------------------------

def ring_out(q, k, v, causal, block_size):
    mesh = make_mesh(4, axis_name="sp")
    fn = shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp",
                                       causal=causal,
                                       block_size=block_size),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"))
    return np.asarray(jax.jit(fn)(q, k, v))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_size", [None, 4, 8])
def test_ring_attention_blocked_matches_dense(causal, block_size):
    rs = np.random.RandomState(0)
    B, S, H, D = 2, 32, 2, 8  # S=32 over 4 shards -> Sl=8
    q, k, v = (jnp.asarray(rs.randn(B, S, H, D), jnp.float32)
               for _ in range(3))
    ref = np.asarray(jax.jit(
        lambda q, k, v: dot_product_attention(q, k, v, causal=causal)
    )(q, k, v))
    out = ring_out(q, k, v, causal, block_size)
    np.testing.assert_allclose(ref, out, rtol=2e-5, atol=2e-5)


def test_ring_attention_bad_block_size():
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 32, 2, 8), jnp.float32)
    with pytest.raises(ValueError, match="divide"):
        ring_out(q, q, q, False, 3)  # 3 does not divide Sl=8


def test_mha_ring_block_size_roundtrip():
    from distkeras_tpu.models.attention import MultiHeadAttention
    from distkeras_tpu.models.core import layer_from_spec, layer_spec
    mha = MultiHeadAttention(num_heads=4, attn_impl="ring",
                             seq_axis_name="sp", ring_block_size=16)
    rebuilt = layer_from_spec(layer_spec(mha))
    assert rebuilt.ring_block_size == 16


def test_ring_attention_rejects_nonpositive_block_size():
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 32, 2, 8), jnp.float32)
    for bad in (0, -4):
        with pytest.raises(ValueError, match=">= 1"):
            ring_out(q, q, q, False, bad)


def test_from_torch_batch_sampler_loader():
    torch = pytest.importorskip("torch")
    from torch.utils.data import (BatchSampler, DataLoader,
                                  SequentialSampler, TensorDataset)

    X = torch.randn(32, 6)
    y = torch.randint(0, 3, (32,))
    tds = TensorDataset(X, y)
    loader = DataLoader(tds, batch_sampler=BatchSampler(
        SequentialSampler(tds), 4, False))
    ds = from_torch(loader)
    assert ds["features"].shape == (32, 6)
    np.testing.assert_allclose(ds["label"], y.numpy())
