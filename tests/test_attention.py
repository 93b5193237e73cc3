"""Attention stack: SDPA reference, Pallas flash kernel (interpreter mode),
ring attention on the 8-device CPU mesh, RoPE, MoE, transformer LM."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from distkeras_tpu.compat import shard_map

from distkeras_tpu.models import Model, Sequential, TransformerBlock, zoo
from distkeras_tpu.models.attention import MultiHeadAttention
from distkeras_tpu.models.moe import MoE
from distkeras_tpu.ops.attention import (apply_rope, causal_mask,
                                         dot_product_attention)
from distkeras_tpu.ops.flash_attention import flash_attention
from distkeras_tpu.ops.ring_attention import ring_attention


def _rand_qkv(rng, b=2, s=16, h=2, d=8):
    ks = jax.random.split(rng, 3)
    return tuple(jax.random.normal(k, (b, s, h, d)) for k in ks)


def _naive_attention(q, k, v, causal=False):
    scale = q.shape[-1] ** -0.5
    s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q, np.float64),
                  np.asarray(k, np.float64)) * scale
    if causal:
        mask = np.tril(np.ones((q.shape[1], k.shape[1]), bool))
        s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, np.asarray(v, np.float64))


@pytest.mark.parametrize("causal", [False, True])
def test_sdpa_matches_naive(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0))
    out = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out),
                               _naive_attention(q, k, v, causal), atol=1e-5)


def test_causal_mask_offsets():
    m = causal_mask(4, 4, q_offset=4, k_offset=0)
    assert bool(m.all())  # queries strictly after all keys
    m2 = causal_mask(4, 4, q_offset=0, k_offset=4)
    assert not bool(m2.any())


#: (sequence, block_q, block_k, the plain causal call's counter): tiles
#: above the diagonal leave the grid, diagonal tiles split into sub-blocks
#: (half the smaller tile, a multiple of 8), and a length no tile divides
#: pads its keys
FLASH_TILES = [(32, 8, 8, "live10of16,sub8/8"),
               (48, 16, 16, "live6of9,sub8/8"),
               (100, 32, 64, "live6of8,sub16/16"),
               (100, 64, 32, "live6of8,sub16/16")]


@pytest.mark.parametrize("s,block_q,block_k,counter", FLASH_TILES)
@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_sdpa(causal, s, block_q, block_k, counter):
    from distkeras_tpu.compat import record_paths
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), b=1, s=s, h=2, d=8)
    with record_paths() as paths:
        out = flash_attention(q, k, v, causal=causal, block_q=block_q,
                              block_k=block_k, interpret=True)
    ref = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    noted = {p for p in paths if p.startswith("flash_causal=")}
    assert noted == ({f"flash_causal={counter}"} if causal else set())


@pytest.mark.parametrize("causal", [False, True])
def test_flash_nondivisible_seq_padded(causal):
    # seq length 20 does not divide block 8 — exercised via the pad path
    q, k, v = _rand_qkv(jax.random.PRNGKey(12), b=1, s=20, h=1, d=8)
    out = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8,
                          interpret=True)
    ref = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    g1 = jax.grad(lambda a, b, c: jnp.sum(jnp.square(flash_attention(
        a, b, c, causal=causal, block_q=8, block_k=8,
        interpret=True))), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda a, b, c: jnp.sum(jnp.square(
        dot_product_attention(a, b, c, causal=causal))),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_gradients_match():
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), b=1, s=16, h=1, d=8)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(flash_attention(
            q, k, v, causal=True, block_q=8, block_k=8, interpret=True)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(
            dot_product_attention(q, k, v, causal=True)))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal, devices):
    n = len(devices)
    mesh = Mesh(np.array(devices), ("seq",))
    b, s, h, d = 2, 8 * n, 2, 8
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), b=b, s=s, h=h, d=d)

    ring = shard_map(
        functools.partial(ring_attention, axis_name="seq", causal=causal),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"))
    out = jax.jit(ring)(q, k, v)
    ref = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_size", [None, 8])
def test_ring_gradients_match_full(causal, block_size, devices):
    """Custom-VJP ring backward vs dense-attention autodiff oracle."""
    n = len(devices)
    mesh = Mesh(np.array(devices), ("seq",))
    b, s, h, d = 2, 16 * n, 2, 8
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), b=b, s=s, h=h, d=d)

    ring = shard_map(
        functools.partial(ring_attention, axis_name="seq", causal=causal,
                          block_size=block_size),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"))

    def loss_ring(q, k, v):
        return jnp.sum(jnp.square(ring(q, k, v)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(
            dot_product_attention(q, k, v, causal=causal)))

    g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4)


def test_ring_gradients_match_loop_autodiff(devices):
    """Custom backward vs plain autodiff through the same ring loop."""
    n = len(devices)
    mesh = Mesh(np.array(devices), ("seq",))
    q, k, v = _rand_qkv(jax.random.PRNGKey(8), b=1, s=8 * n, h=2, d=8)

    def make(use_custom):
        ring = shard_map(
            functools.partial(ring_attention, axis_name="seq", causal=True,
                              use_custom_vjp=use_custom),
            mesh=mesh, in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"))
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(jnp.square(ring(q, k, v))),
            argnums=(0, 1, 2)))

    for a, b_ in zip(make(True)(q, k, v), make(False)(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-5)


def test_ring_backward_residuals_ring_independent(devices):
    """The saved-for-backward bytes per device must not scale with the
    ring size (the point of the custom VJP: autodiff through the ppermute
    loop would stash one rotated K/V copy per hop)."""
    from distkeras_tpu.ops.ring_attention import _ring_fwd_rule

    per_device = {}
    for n in (2, 4, 8):
        mesh = Mesh(np.array(devices[:n]), ("seq",))
        b, s_local, h, d = 2, 16, 2, 8  # fixed LOCAL shard size

        def fwd(q, k, v):
            out, res = _ring_fwd_rule(q, k, v, None, d ** -0.5, True,
                                      None, "seq")
            return res[:5]   # segment_ids residual is None here

        specs = (P(None, "seq"),) * 3
        shp = jax.ShapeDtypeStruct((b, s_local * n, h, d), jnp.float32)
        res = jax.eval_shape(
            shard_map(fwd, mesh=mesh, in_specs=specs,
                      out_specs=(P(None, "seq"),) * 4
                      + (P(None, None, "seq"),)),
            shp, shp, shp)
        total = sum(int(np.prod(r.shape)) * r.dtype.itemsize
                    for r in jax.tree_util.tree_leaves(res))
        per_device[n] = total // n
    assert len(set(per_device.values())) == 1, per_device


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_full(causal, devices):
    from distkeras_tpu.ops.ulysses import ulysses_attention
    n = len(devices)
    mesh = Mesh(np.array(devices), ("seq",))
    b, s, h, d = 2, 4 * n, n, 8  # h must divide over the axis
    q, k, v = _rand_qkv(jax.random.PRNGKey(13), b=b, s=s, h=h, d=d)

    uly = shard_map(
        functools.partial(ulysses_attention, axis_name="seq", causal=causal),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"))
    out = jax.jit(uly)(q, k, v)
    ref = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ulysses_grad_matches_full(devices):
    from distkeras_tpu.ops.ulysses import ulysses_attention
    n = len(devices)
    mesh = Mesh(np.array(devices), ("seq",))
    q, k, v = _rand_qkv(jax.random.PRNGKey(14), b=1, s=2 * n, h=n, d=4)

    uly = shard_map(
        functools.partial(ulysses_attention, axis_name="seq", causal=True),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"))
    g1 = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(jnp.square(uly(q, k, v))),
        argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(
        lambda q, k, v: jnp.sum(jnp.square(
            dot_product_attention(q, k, v, causal=True))),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_ulysses_rejects_indivisible_heads(devices):
    from distkeras_tpu.ops.ulysses import ulysses_attention
    n = len(devices)
    mesh = Mesh(np.array(devices), ("seq",))
    q, k, v = _rand_qkv(jax.random.PRNGKey(15), b=1, s=2 * n, h=n + 1, d=4)
    uly = shard_map(
        functools.partial(ulysses_attention, axis_name="seq"),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"))
    with pytest.raises(ValueError, match="divisible"):
        jax.jit(uly)(q, k, v)


def test_mha_ulysses_layer_matches_xla(devices):
    """MultiHeadAttention(attn_impl='ulysses') under shard_map matches the
    single-device xla path, including global RoPE positions."""
    n = len(devices)
    mesh = Mesh(np.array(devices), ("sp",))
    d_model, h, s, b = 16, n, 2 * n, 2
    x = jax.random.normal(jax.random.PRNGKey(16), (b, s, d_model))

    ref_layer = MultiHeadAttention(num_heads=h, causal=True, use_rope=True)
    params, state, _ = ref_layer.init(jax.random.PRNGKey(17),
                                      (b, s, d_model))
    ref, _ = ref_layer.apply(params, state, x)

    sp_layer = MultiHeadAttention(num_heads=h, causal=True, use_rope=True,
                                  attn_impl="ulysses", seq_axis_name="sp")
    fn = shard_map(
        lambda p, xx: sp_layer.apply(p, {}, xx)[0],
        mesh=mesh, in_specs=(P(), P(None, "sp")),
        out_specs=P(None, "sp"))
    out = jax.jit(fn)(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_rope_preserves_norm_and_relative_phase():
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 8, 2, 16))
    y = apply_rope(x)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1), atol=1e-4)
    # relative property: <rope(q)_i, rope(k)_j> depends only on i - j
    q = jnp.tile(x[:, :1], (1, 8, 1, 1))  # same content at all positions
    k = q
    qr, kr = apply_rope(q), apply_rope(k)
    dots = np.einsum("bqhd,bkhd->bqk", np.asarray(qr), np.asarray(kr))
    np.testing.assert_allclose(np.diag(dots[0], k=1),
                               np.full(7, dots[0, 0, 1]), rtol=1e-4)


def test_rope_explicit_positions_match_offset_slice():
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 16, 1, 8))
    full = apply_rope(x)
    shard = apply_rope(x[:, 8:], positions=jnp.arange(8, 16))
    np.testing.assert_allclose(np.asarray(full[:, 8:]), np.asarray(shard),
                               atol=1e-5)


def test_moe_dense_vs_expert_parallel(devices):
    n = len(devices)
    mesh = Mesh(np.array(devices), ("expert",))
    d_model, e = 8, 2 * n
    moe_dense = MoE(e, 16, top_k=2)
    moe_ep = MoE(e, 16, top_k=2, expert_axis_name="expert")
    params, state, _ = moe_dense.init(jax.random.PRNGKey(6), (4, d_model))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 4, d_model))

    ref, _ = moe_dense.apply(params, state, x)

    ep_fn = shard_map(
        lambda p, xx: moe_ep.apply(p, {}, xx)[0],
        mesh=mesh,
        in_specs=({"gate": P(), "w1": P("expert"), "b1": P("expert"),
                   "w2": P("expert"), "b2": P("expert")}, P()),
        out_specs=P())
    out = jax.jit(ep_fn)(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_moe_topk_masks_routing():
    moe = MoE(8, 4, top_k=2)
    params, _, _ = moe.init(jax.random.PRNGKey(8), (4,))
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 4, 4))
    probs, _, _ = moe._gate_probs(x, params["gate"])
    nonzero = (np.asarray(probs) > 0).sum(-1)
    assert (nonzero == 2).all()
    np.testing.assert_allclose(np.asarray(probs).sum(-1), 1.0, atol=1e-6)


def test_moe_balance_loss_math():
    moe = MoE(8, 4, top_k=2, aux_loss_weight=0.01)
    params, state, _ = moe.init(jax.random.PRNGKey(8), (4,))
    assert "__aux_loss__" in state
    # uniform router (zero gate) -> balance loss exactly 1
    params["gate"] = jnp.zeros_like(params["gate"])
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 16, 4))
    _, full, mask = moe._gate_probs(x, params["gate"])
    np.testing.assert_allclose(float(moe._balance_loss(full, mask)), 1.0,
                               atol=1e-5)
    # a collapsed router (expert 0 gets all prob, slots split 0/1)
    # scores E * (0.5*1.0) = 4 — far above the uniform optimum of 1
    full_c = jnp.zeros((2, 16, 8)).at[..., 0].set(1.0)
    mask_c = (jnp.zeros((2, 16, 8), bool).at[..., 0].set(True)
              .at[..., 1].set(True))
    np.testing.assert_allclose(float(moe._balance_loss(full_c, mask_c)),
                               4.0, atol=1e-5)
    # aux only published in TRAINING mode
    _, st_eval = moe.apply(params, state, x, training=False)
    assert float(st_eval["__aux_loss__"]) == 0.0
    _, st_train = moe.apply(params, state, x, training=True)
    assert float(st_train["__aux_loss__"]) > 0.005  # ~0.01 * >=1


def test_moe_aux_loss_joins_training_loss():
    from distkeras_tpu.models.core import collect_aux_losses
    from distkeras_tpu.ops import get_loss, get_optimizer
    from distkeras_tpu.parallel.worker import TrainCarry, make_train_step

    tokens = jax.random.randint(jax.random.PRNGKey(11), (2, 8), 0, 17)

    losses = {}
    states = {}
    for w in (0.0, 0.1):
        spec = zoo.transformer_lm(17, d_model=16, num_heads=2, num_layers=2,
                                  mlp_ratio=2, moe_every=1, num_experts=4,
                                  moe_aux_loss_weight=w)
        model = Model.build(spec, (8,), seed=3)
        opt = get_optimizer("sgd", learning_rate=0.0)
        step = make_train_step(
            spec, get_loss("sparse_categorical_crossentropy_from_logits"),
            opt)
        carry = TrainCarry(model.params, model.state,
                           opt.init(model.params), jax.random.PRNGKey(0))
        new_carry, loss = step(carry, (tokens, tokens))
        losses[w] = float(loss)
        states[w] = new_carry.state
    aux = float(collect_aux_losses(states[0.1]))
    assert aux > 0.05  # two MoE blocks, each >= 0.1 * ~1.0... scaled
    np.testing.assert_allclose(losses[0.1] - losses[0.0], aux, rtol=1e-4)


def test_transformer_lm_forward_and_train_step():
    vocab, s = 31, 16
    spec = zoo.transformer_lm(vocab, d_model=32, num_heads=4, num_layers=2,
                              mlp_ratio=2)
    model = Model.build(spec, (s,), seed=0)
    tokens = jax.random.randint(jax.random.PRNGKey(10), (2, s), 0, vocab)
    logits, _ = spec.apply(model.params, model.state,
                           tokens, training=False)
    assert logits.shape == (2, s, vocab)

    # a couple of SGD steps reduce next-token loss
    from distkeras_tpu.ops import get_loss, get_optimizer
    loss_fn = get_loss("sparse_categorical_crossentropy_from_logits")
    opt = get_optimizer("adam", learning_rate=1e-2)

    def loss(params, x, y):
        out, _ = spec.apply(params, model.state, x, training=False)
        return loss_fn(y, out)

    x, y = tokens[:, :-1], tokens[:, 1:]
    params, opt_state = model.params, opt.init(model.params)
    l0 = float(loss(params, x, y))
    step = jax.jit(lambda p, o: _sgd_step(p, o, x, y, loss, opt))
    for _ in range(10):
        params, opt_state, _ = step(params, opt_state)
    assert float(loss(params, x, y)) < l0


def _sgd_step(params, opt_state, x, y, loss, opt):
    l, g = jax.value_and_grad(loss)(params, x, y)
    updates, opt_state = opt.update(g, opt_state, params)
    from distkeras_tpu.ops import apply_updates
    return apply_updates(params, updates), opt_state, l


def test_transformer_moe_lm_builds():
    spec = zoo.transformer_lm(17, d_model=16, num_heads=2, num_layers=2,
                              mlp_ratio=2, moe_every=2, num_experts=4)
    model = Model.build(spec, (8,), seed=0)
    tokens = jnp.zeros((1, 8), jnp.int32)
    logits, _ = spec.apply(model.params, model.state, tokens)
    assert logits.shape == (1, 8, 17)


def test_transformer_block_serialization_roundtrip():
    from distkeras_tpu.models.serialization import (deserialize_model,
                                                    serialize_model)
    spec = Sequential([TransformerBlock(num_heads=2, mlp_ratio=2)])
    model = Model.build(spec, (8, 16), seed=1)
    blob = serialize_model(model)
    model2 = deserialize_model(blob)
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 8, 16))
    y1, _ = model.module.apply(model.params, model.state, x)
    y2, _ = model2.module.apply(model2.params, model2.state, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-6)


def test_moe_topk_exact_on_tied_logits():
    # tied router logits (zero input -> all logits equal) must still
    # activate exactly top_k experts, not every tied one
    moe = MoE(8, 4, top_k=2)
    params, _, _ = moe.init(jax.random.PRNGKey(8), (4,))
    x = jnp.zeros((1, 4, 4))
    probs, _, _ = moe._gate_probs(x, params["gate"])
    nonzero = (np.asarray(probs) > 0).sum(-1)
    assert (nonzero == 2).all(), nonzero


def test_transformer_block_reinit_tracks_d_model():
    # re-initializing the same block instance at a different width must
    # resize the auto-resolved MLP (regression: stale cached hidden_dim)
    blk = TransformerBlock(num_heads=2, mlp_ratio=4)
    Model.build(Sequential([blk]), (8, 16), seed=0)
    assert blk.mlp.hidden_dim == 64
    m2 = Model.build(Sequential([blk]), (8, 32), seed=0)
    assert blk.mlp.hidden_dim == 128
    assert m2.params[0]["mlp"]["w1"].shape == (32, 128)


def test_positional_embedding_global_under_seq_sharding(devices):
    from distkeras_tpu.models.attention import PositionalEmbedding
    from jax.sharding import Mesh, PartitionSpec as P

    d, s, n = 4, 16, 8
    pe_global = PositionalEmbedding(s)
    pe_sharded = PositionalEmbedding(s, seq_axis_name="sp")
    params, _, _ = pe_global.init(jax.random.PRNGKey(0), (s, d))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, s, d))
    ref, _ = pe_global.apply(params, {}, x)

    mesh = Mesh(np.array(devices[:n]), ("sp",))
    fn = shard_map(
        lambda p, xx: pe_sharded.apply(p, {}, xx)[0],
        mesh=mesh, in_specs=(P(), P(None, "sp")), out_specs=P(None, "sp"))
    out = jax.jit(fn)(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_attention_init_uses_logical_2d_fans():
    # glorot limit must come from the logical (d_model, H*Dh) matrix, not
    # conv-kernel fan rules over the 3D shape (regression: ~6x-too-small init)
    mha = MultiHeadAttention(num_heads=8, head_dim=64)
    params, _, _ = mha.init(jax.random.PRNGKey(0), (16, 512))
    limit = np.sqrt(6.0 / (512 + 512))
    wq = np.asarray(params["wq"])
    assert wq.max() > 0.9 * limit, (wq.max(), limit)
    assert abs(wq).max() <= limit * 1.0001


def test_positional_embedding_undersized_table_raises(devices):
    from distkeras_tpu.models.attention import PositionalEmbedding
    from jax.sharding import Mesh, PartitionSpec as P

    pe = PositionalEmbedding(16, seq_axis_name="sp")  # global seq is 32
    params, _, _ = pe.init(jax.random.PRNGKey(0), (32, 4))
    x = jnp.zeros((1, 32, 4))
    mesh = Mesh(np.array(devices[:8]), ("sp",))
    fn = shard_map(
        lambda p, xx: pe.apply(p, {}, xx)[0],
        mesh=mesh, in_specs=(P(), P(None, "sp")), out_specs=P(None, "sp"))
    with pytest.raises(ValueError, match="too small"):
        jax.jit(fn)(params, x)


@pytest.mark.parametrize("s,block_q,block_k",
                         [(44, 16, 16), (100, 32, 64), (100, 64, 32)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_pallas_backward_matches_oracles(causal, s, block_q, block_k):
    """The in-kernel backward (TPU default) must match both the XLA-scan
    backward and the reference SDPA gradients — including a sequence that
    doesn't divide the block sizes (pad-row handling in both passes), and
    tiles where the causal ones leave the grid and split the diagonal
    tiles into sub-blocks in both passes (``FLASH_TILES``)."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), b=2, s=s, h=2, d=8)
    co = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def grads(fn):
        return jax.grad(lambda a, b, c: jnp.sum(fn(a, b, c) * co),
                        argnums=(0, 1, 2))(q, k, v)

    ref = grads(lambda a, b, c: dot_product_attention(a, b, c,
                                                      causal=causal))
    pal = grads(lambda a, b, c: flash_attention(
        a, b, c, causal=causal, interpret=True, bwd="pallas",
        block_q=block_q, block_k=block_k))
    xla = grads(lambda a, b, c: flash_attention(
        a, b, c, causal=causal, interpret=True, bwd="xla",
        block_q=block_q, block_k=block_k))
    for p, x, r in zip(pal, xla, ref):
        np.testing.assert_allclose(p, r, atol=2e-5)
        np.testing.assert_allclose(p, x, atol=2e-5)

    with pytest.raises(ValueError, match="bwd must be"):
        flash_attention(q, k, v, interpret=True, bwd="fused")


@pytest.mark.parametrize("kw", [
    dict(causal=True, window=7),
    dict(causal=True, window=24, block_q=8, block_k=16),
    dict(causal=True, segment_ids=True),
    dict(causal=False, segment_ids=True, block_k=8),
    dict(causal=False, block_k=32),
    dict(causal=True, block_len=4),
], ids=["window", "window-remap", "segments", "segments-noncausal",
        "noncausal", "block_len"])
def test_flash_off_the_plain_causal_path_is_unchanged(kw, monkeypatch):
    """Windowed, packed, block-causal and non-causal calls keep the
    whole-tile bodies: leaving the tiles the mask wholly discards out of
    the grid changes no bit of what they compute, forward or backward
    (the dense grid, every tile visited in order, is what the kernels
    ran before PR 38), and none of them notes the plain causal
    counter."""
    from distkeras_tpu.compat import record_paths
    from distkeras_tpu.ops import flash_attention as fa
    kw = dict(kw)
    s = 48 if "block_len" in kw else 44
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), b=2, s=s, h=2, d=8)
    co = jax.random.normal(jax.random.PRNGKey(4), q.shape)
    if kw.pop("segment_ids", False):
        kw["segment_ids"] = jnp.asarray(np.repeat(np.arange(4), 11)[None]
                                        .repeat(2, 0))
    kw = dict(dict(block_q=16, block_k=16, interpret=True, bwd="pallas"),
              **kw)

    def run():
        f = lambda a, b, c: flash_attention(a, b, c, **kw)
        if "block_len" in kw:           # forward only
            return [f(q, k, v)]
        return [f(q, k, v)] + list(jax.grad(
            lambda *a: jnp.sum(f(*a) * co), argnums=(0, 1, 2))(q, k, v))

    with record_paths() as paths:
        live = run()
    assert not any(p.startswith("flash_causal=") for p in paths)
    table = fa._grid_table
    monkeypatch.setattr(fa, "_grid_table", lambda nq, nk, bq, bk, causal,
                        window, kmajor=False: table(nq, nk, bq, bk, False,
                                                    None, kmajor))
    for a, b in zip(live, run()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("causal", [True, False])
def test_flash_bhsd_layout_matches_bshd(causal):
    """layout="bhsd" (the layer's transpose-free path) must match the
    default layout in both passes."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), b=2, s=40, h=2, d=8)
    co = jax.random.normal(jax.random.PRNGKey(4), q.shape)
    t = lambda x: x.transpose(0, 2, 1, 3)

    out_s = flash_attention(q, k, v, causal=causal, interpret=True,
                            block_q=16, block_k=16)
    out_h = flash_attention(t(q), t(k), t(v), causal=causal,
                            layout="bhsd", interpret=True,
                            block_q=16, block_k=16)
    np.testing.assert_allclose(t(out_h), out_s, atol=1e-6)

    gs = jax.grad(lambda a, b, c: jnp.sum(flash_attention(
        a, b, c, causal=causal, interpret=True, bwd="pallas",
        block_q=16, block_k=16) * co), argnums=(0, 1, 2))(q, k, v)
    gh = jax.grad(lambda a, b, c: jnp.sum(flash_attention(
        a, b, c, causal=causal, layout="bhsd", interpret=True,
        bwd="pallas", block_q=16, block_k=16) * t(co)),
        argnums=(0, 1, 2))(t(q), t(k), t(v))
    for a, b in zip(gh, gs):
        np.testing.assert_allclose(t(a), b, atol=2e-5)

    with pytest.raises(ValueError, match="layout must be"):
        flash_attention(q, k, v, layout="hbsd")


def test_gqa_trains_and_roundtrips(tmp_path):
    """GQA model family: k/v project to fewer heads, training works on
    every attention path, and the config serializes."""
    from distkeras_tpu.data import Dataset
    from distkeras_tpu.models import (Model, load_model, save_model, zoo)
    from distkeras_tpu.parallel import SingleTrainer

    rs = np.random.RandomState(0)
    toks = rs.randint(0, 16, (128, 8))
    m = Model.build(
        zoo.transformer_lm(16, d_model=16, num_heads=4, num_kv_heads=1,
                           num_layers=1, mlp_ratio=2), (8,), seed=0)
    tr = SingleTrainer(m, batch_size=16, num_epoch=2,
                       worker_optimizer="adam",
                       optimizer_kwargs={"learning_rate": 1e-2},
                       loss="sparse_categorical_crossentropy_from_logits")
    trained = tr.train(Dataset({"features": toks, "label": toks}))
    assert np.isfinite(tr.get_history().losses()).all()

    p = str(tmp_path / "gqa")
    save_model(trained, p)
    loaded = load_model(p)
    np.testing.assert_allclose(loaded.predict(toks[:4]),
                               trained.predict(toks[:4]), atol=1e-5)


def test_gqa_rejects_nonpositive_kv_heads():
    from distkeras_tpu.models.attention import MultiHeadAttention

    with pytest.raises(ValueError, match="positive divisor"):
        MultiHeadAttention(num_heads=8, num_kv_heads=0)
    with pytest.raises(ValueError, match="positive divisor"):
        MultiHeadAttention(num_heads=8, num_kv_heads=-4)


def test_rope_scale_interpolates_positions():
    """Linear position interpolation: scale=2 at position 2t equals
    scale=1 at position t, and a scaled model decodes consistently."""
    from distkeras_tpu.ops.attention import apply_rope

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 2, 8))
    a = apply_rope(x, positions=jnp.asarray([0, 2, 4, 6]), scale=2.0)
    b = apply_rope(x, positions=jnp.asarray([0, 1, 2, 3]), scale=1.0)
    np.testing.assert_allclose(a, b, atol=1e-6)

    from distkeras_tpu.models import Model, zoo
    from distkeras_tpu.models.decoding import generate

    m = Model.build(zoo.transformer_lm(16, d_model=16, num_heads=2,
                                       num_layers=1, mlp_ratio=2,
                                       rope_scale=4.0), (8,), seed=0)
    out = generate(m, np.zeros((1, 4), np.int32), max_new_tokens=4)
    assert out.shape == (1, 8)
    # config roundtrip carries the scale
    blk = next(l for l in m.module.layers
               if type(l).__name__ == "TransformerBlock")
    assert blk.get_config()["rope_scale"] == 4.0


@pytest.mark.parametrize("window", [1, 7, 16, 100])
def test_sliding_window_matches_banded_reference(window):
    """Causal sliding-window attention (fwd + both backwards) must equal
    an explicitly band-masked softmax reference, including windows larger
    than the sequence (== full causal) and non-divisible lengths."""
    from distkeras_tpu.ops.attention import NEG_INF

    B, S, H, D = 2, 44, 2, 8
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), b=B, s=S, h=H, d=D)
    co = jax.random.normal(jax.random.PRNGKey(8), q.shape)

    def banded(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (D ** -0.5)
        qp, kp = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        allowed = (qp >= kp) & (kp > qp - window)
        w = jax.nn.softmax(jnp.where(allowed[None, None], s, NEG_INF), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v)

    out = flash_attention(q, k, v, causal=True, window=window,
                          interpret=True, block_q=16, block_k=16)
    np.testing.assert_allclose(out, banded(q, k, v), atol=1e-5)

    gr = jax.grad(lambda a, b, c: jnp.sum(banded(a, b, c) * co),
                  argnums=(0, 1, 2))(q, k, v)
    for bwd in ("pallas", "xla"):
        gw = jax.grad(lambda a, b, c: jnp.sum(flash_attention(
            a, b, c, causal=True, window=window, interpret=True, bwd=bwd,
            block_q=16, block_k=16) * co), argnums=(0, 1, 2))(q, k, v)
        for x, y in zip(gw, gr):
            np.testing.assert_allclose(x, y, atol=2e-5)

    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=window,
                        interpret=True)


@pytest.mark.parametrize("window,block_q,block_k",
                         [(8, 16, 8), (24, 8, 16), (3, 8, 8)])
def test_sliding_window_grid_remap_exact(window, block_q, block_k):
    """W << S exercises the shrunken grids: each q block's k sweep (and
    each k block's q sweep) holds only the tiles within the window's
    reach, so correctness here proves the grid table never drops or
    double-counts a tile (fwd, dq, and the mirrored dk/dv sweeps)."""
    from distkeras_tpu.ops.attention import NEG_INF
    from distkeras_tpu.ops.flash_attention import _grid_table

    B, S, H, D = 1, 128, 2, 8
    nq, nk = S // block_q, S // block_k
    for kmajor, outer, n_in in ((False, 0, nk), (True, 1, nq)):
        tab = _grid_table(nq, nk, block_q, block_k, True, window, kmajor)
        assert np.bincount(tab.reshape(-1, 3)[:, outer]).max() < n_in
    q, k, v = _rand_qkv(jax.random.PRNGKey(17), b=B, s=S, h=H, d=D)
    co = jax.random.normal(jax.random.PRNGKey(18), q.shape)

    def banded(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (D ** -0.5)
        qp, kp = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        allowed = (qp >= kp) & (kp > qp - window)
        w = jax.nn.softmax(jnp.where(allowed[None, None], s, NEG_INF), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v)

    out = flash_attention(q, k, v, causal=True, window=window,
                          interpret=True, block_q=block_q,
                          block_k=block_k)
    np.testing.assert_allclose(out, banded(q, k, v), atol=1e-5)
    gr = jax.grad(lambda a, b, c: jnp.sum(banded(a, b, c) * co),
                  argnums=(0, 1, 2))(q, k, v)
    gw = jax.grad(lambda a, b, c: jnp.sum(flash_attention(
        a, b, c, causal=True, window=window, interpret=True, bwd="pallas",
        block_q=block_q, block_k=block_k) * co), argnums=(0, 1, 2))(q, k, v)
    for x, y in zip(gw, gr):
        np.testing.assert_allclose(x, y, atol=2e-5)


def test_sliding_window_model_trains_and_decodes():
    """attn_window on the LM family: training runs, decode_step masks the
    cache to the window and matches the full forward."""
    from distkeras_tpu.models import Model, zoo
    from distkeras_tpu.models.decoding import (decode_step, init_cache,
                                               _resolve_head_dims)

    S = 10
    m = Model.build(zoo.transformer_lm(16, d_model=16, num_heads=2,
                                       num_layers=1, mlp_ratio=2,
                                       attn_window=4), (S,), seed=0)
    _resolve_head_dims(m.module, m.params)
    rs = np.random.RandomState(0)
    toks = rs.randint(0, 16, (2, S))
    full = m.predict(toks)
    cache = init_cache(m.module, 2, S)
    steps = []
    for t in range(S):
        lg, cache = decode_step(m.module, m.params, m.state, cache,
                                jnp.asarray(toks[:, t]), t)
        steps.append(np.asarray(lg))
    np.testing.assert_allclose(np.stack(steps, axis=1), full, atol=2e-4)

    with pytest.raises(ValueError, match="causal"):
        from distkeras_tpu.models.attention import MultiHeadAttention
        MultiHeadAttention(num_heads=2, causal=False, attn_window=4)
