"""The plain reference against ``zoo.transformer_lm`` at a tiny size in
float32: same weights (the benchmark's), same logits, same loss gradient; and
the weights the benchmark makes have the shapes the program makes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import common, weights
from reference import gpt

CFG = {"builder": {"function": "distkeras_tpu.models.zoo.transformer_lm",
                   "kwargs": {"vocab_size": 97, "d_model": 32, "num_heads": 2,
                              "num_layers": 3, "mlp_ratio": 4, "max_len": 24,
                              "use_rope": False, "norm": "layernorm",
                              "dtype": "float32", "attn_impl": "xla"}}}


@pytest.fixture(scope="module")
def pair():
    jax.config.update("jax_default_matmul_precision", "highest")
    model = common.build_model(CFG, seed=2 ** 31 + 3, seq_len=24)
    w = weights.make_canonical(CFG, 2 ** 31 + 3)
    tokens = np.random.default_rng(0).integers(0, 97, (2, 24), dtype=np.int32)
    return model, w, jnp.asarray(tokens)


def test_weights_are_seeded_and_shaped(pair):
    model, w, _ = pair
    again = weights.make_canonical(CFG, 2 ** 31 + 3)
    other = weights.make_canonical(CFG, 4)
    assert all(np.array_equal(w[k], again[k]) for k in w)
    assert not np.array_equal(w["wq"], other["wq"])
    assert np.array_equal(model.params[2 + 1]["attn"]["wq"], w["wq"][1])
    assert float(jnp.std(w["wo"])) == pytest.approx(0.02 / np.sqrt(6), rel=0.1)
    served = weights.make_program_params(CFG, 5, served_dtype=jnp.bfloat16)
    assert served[0]["embeddings"].dtype == jnp.bfloat16
    assert served[2]["norm1"]["scale"].dtype == jnp.float32


def test_logits_match_the_program(pair):
    model, w, tokens = pair
    got, _ = model.module.apply(model.params, model.state, tokens, training=False)
    ref = gpt.logits(w, tokens)
    assert np.abs(np.asarray(got) - np.asarray(ref)).max() < 2e-5
    one = gpt.served_logits(w, tokens[0], jnp.asarray([3, 23]))
    assert np.abs(np.asarray(one) - np.asarray(ref[0, [3, 23]])).max() < 2e-5


def test_loss_gradient_matches_the_program(pair):
    from distkeras_tpu.ops.losses import get_loss
    model, w, tokens = pair
    labels = jnp.roll(tokens, -1, axis=1)
    loss_fn = get_loss("sparse_categorical_crossentropy_from_logits")

    def program_loss(params):
        out, _ = model.module.apply(params, model.state, tokens, training=True)
        return loss_fn(labels, out)

    l_prog, g_prog = jax.value_and_grad(program_loss)(model.params)
    l_ref, g_ref = gpt.loss_and_grad(w, tokens, labels)
    assert float(l_prog) == pytest.approx(float(l_ref), rel=1e-6)
    np.testing.assert_allclose(g_prog[2 + 2]["mlp"]["w1"], g_ref["w1"][2], atol=1e-7, rtol=1e-4)
    np.testing.assert_allclose(g_prog[0]["embeddings"], g_ref["wte"], atol=1e-7, rtol=1e-4)
    half = gpt.loss_and_grad(w, tokens, labels, rows=slice(0, 1))[0]
    assert float(half) != pytest.approx(float(l_ref), rel=1e-6)


def test_lower_precisions_differ_in_order(pair):
    _, w, tokens = pair
    ref = np.asarray(gpt.logits(w, tokens))
    bf16 = np.abs(np.asarray(gpt.logits(w, tokens, "bfloat16")) - ref).max()
    int8 = np.abs(np.asarray(gpt.logits(w, tokens, "int8")) - ref).max()
    assert 0 < bf16 < int8
