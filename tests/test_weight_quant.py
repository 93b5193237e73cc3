"""Quantized decode-GEMM weights (quantized-decode PR).

``ops.quant_matmul``: per-channel int8/int4 weight quantization with a
fused dequant-matmul Pallas kernel, pinned against the XLA reference
under ``interpret=True`` (the tier-1 CPU oracle convention), plus the
``ServingEngine(weight_quant=)`` wiring — in-graph dequant for the
non-attention leaves, the kernel path for the attention projections —
and the ``obs.report`` accuracy-drift hook.

Documented tolerance: symmetric per-channel quantization bounds the
per-entry weight error by half a quantization step
(``scale / 2 = absmax / (2 * qmax)``); the matmul tests below assert
kernel == reference to f32 round-off (both compute the SAME factored
``(x @ q) * scale``), and the engine tests assert greedy token
identity on the overfit pattern LM (margins far exceed int4 drift).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distkeras_tpu.models import Model, zoo
from distkeras_tpu.models.decoding import generate
from distkeras_tpu.ops import quant_matmul as qm
from distkeras_tpu.serving.engine import ServingEngine


# --- quantize_weight / pack format -----------------------------------------


def test_pack_rows_roundtrip():
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randint(-7, 8, size=(64, 3, 5)), jnp.int8)
    np.testing.assert_array_equal(
        np.asarray(qm.unpack_rows(qm.pack_rows(q))), np.asarray(q))


@pytest.mark.parametrize("shape,reduce_axes,bits", [
    ((128, 4, 32), (0,), 8),        # wq layout, per-(h, e) channels
    ((128, 4, 32), (0,), 4),
    ((4, 32, 128), (0, 1), 4),      # wo layout, per-d channels
    ((256, 384), None, 8),          # MLP default (all-but-last)
    ((255, 384), None, 4),          # odd axis 0: int4 stays unpacked
])
def test_quantize_weight_error_within_half_step(shape, reduce_axes, bits):
    rs = np.random.RandomState(1)
    w = rs.randn(*shape).astype(np.float32)
    wq = qm.quantize_weight(w, bits, reduce_axes=reduce_axes)
    # the packing contract: int4 nibble-packs along axis 0 iff even
    assert ("q4" in wq) == (bits == 4 and shape[0] % 2 == 0)
    deq = np.asarray(qm.dequant_weight(wq)).reshape(shape)
    red = reduce_axes if reduce_axes else tuple(range(w.ndim - 1))
    step = np.abs(w).max(axis=red, keepdims=True) / (7 if bits == 4
                                                     else 127)
    assert np.all(np.abs(deq - w) <= step * 0.5 + 1e-6)


def test_quantize_weight_validates():
    with pytest.raises(ValueError, match="bits"):
        qm.quantize_weight(np.ones((4, 4), np.float32), 3)
    with pytest.raises(ValueError, match="matrix"):
        qm.quantize_weight(np.ones(4, np.float32))
    with pytest.raises(ValueError, match="prefix"):
        qm.quantize_weight(np.ones((4, 4, 4), np.float32),
                           reduce_axes=(1,))


def test_zero_channel_dequantizes_to_zero():
    w = np.zeros((16, 8), np.float32)
    w[:, 0] = 3.0
    wq = qm.quantize_weight(w, 4)
    np.testing.assert_allclose(np.asarray(qm.dequant_weight(wq)), w,
                               atol=3 / 14 + 1e-6)
    assert np.asarray(qm.dequant_weight(wq))[:, 1:].max() == 0.0


# --- the kernel vs the reference (interpret-mode oracle) -------------------


@pytest.mark.parametrize("bits,layout", [
    (8, "proj"), (4, "proj"), (8, "out"), (4, "out")])
def test_kernel_matches_reference(bits, layout):
    """The Pallas kernel (interpreter mode — the CI oracle) computes
    the same factored ``(x @ q) * scale`` as ``reference_matmul``."""
    rs = np.random.RandomState(2)
    if layout == "proj":
        w = rs.randn(128, 4, 32).astype(np.float32)     # [d, h, e]
        wq = qm.quantize_weight(w, bits, reduce_axes=(0,))
        x = jnp.asarray(rs.randn(3, 5, 128), jnp.float32)
    else:
        w = rs.randn(4, 32, 256).astype(np.float32)     # [h, e, d]
        wq = qm.quantize_weight(w, bits, reduce_axes=(0, 1))
        x = jnp.asarray(rs.randn(7, 128), jnp.float32)  # odd M: pad path
    with qm.force_interpret():
        assert qm.fused_supported(128, 128)
        out = qm.quant_matmul(x, wq)
    ref = qm.reference_matmul(x, wq)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # and the factored product equals dequant-then-matmul exactly in
    # f32 math terms (scale is constant along the contraction)
    k = x.shape[-1]
    deq = np.asarray(qm.dequant_weight(wq)).reshape(k, -1)
    want = np.asarray(x).reshape(-1, k) @ deq
    np.testing.assert_allclose(
        np.asarray(ref).reshape(want.shape), want, rtol=1e-4, atol=1e-4)


def test_alignment_and_backend_gate():
    assert not qm.fused_supported(128, 128)   # CPU, no force: closed
    with qm.force_interpret():
        assert qm.fused_supported(128, 128)
        assert qm.fused_supported(128, 640)   # 640 = 5 * 128
        assert not qm.fused_supported(96, 128)    # K % 128
        assert not qm.fused_supported(128, 100)   # no 128-divisor of N
    assert qm.choose_block_n(512) == 512
    assert qm.choose_block_n(1024) == 512     # capped
    assert qm.choose_block_n(100) is None


def test_misaligned_shapes_fall_back_to_reference():
    rs = np.random.RandomState(3)
    wq = qm.quantize_weight(rs.randn(128, 100).astype(np.float32), 8)
    x = jnp.asarray(rs.randn(4, 128), jnp.float32)
    with qm.force_interpret():
        out = qm.quant_matmul(x, wq)          # N=100: silently reference
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(qm.reference_matmul(x, wq)),
                               rtol=1e-6)


def test_resolve_rejects_mismatched_contraction():
    wq = qm.quantize_weight(np.ones((64, 8), np.float32), 8)
    with pytest.raises(ValueError, match="contract"):
        qm.quant_matmul(jnp.ones((2, 100), jnp.float32), wq)


# --- params-tree plumbing --------------------------------------------------


def _tiny_lm(vocab=29, d=32, seed=2):
    return Model.build(
        zoo.transformer_lm(vocab, d_model=d, num_heads=4, num_layers=2,
                           mlp_ratio=2, use_rope=True), (12,), seed=seed)


def test_tree_roundtrip_preserves_shapes_and_error_bound():
    m = _tiny_lm()
    qt = qm.quantize_params_tree(m.params, 4)
    deq = qm.dequant_params_tree(qt, jnp.float32)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(m.params)[0],
            jax.tree_util.tree_flatten_with_path(deq)[0]):
        assert np.asarray(a).shape == np.asarray(b).shape, pa
    errs = qm.tree_quant_errors(m.params, qt)
    assert errs and all(e["rel_rms"] < 0.2 for e in errs.values())
    # keep_attn leaves exactly the projection qdicts quantized
    keep = qm.dequant_params_tree(qt, jnp.float32, keep_attn=True)
    attn = keep[1]["attn"]
    assert all(qm.is_qdict(attn[k]) for k in ("wq", "wk", "wv", "wo"))
    flat = jax.tree_util.tree_leaves(
        {k: v for k, v in keep[1].items() if k != "attn"})
    assert all(np.issubdtype(np.asarray(l).dtype, np.floating)
               or np.asarray(l).ndim < 2 for l in flat)


# --- engine wiring ---------------------------------------------------------


@pytest.fixture(scope="module")
def memorized_lm(pattern_lm):
    return pattern_lm


PATTERN = np.array([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8])


def _run(eng, prompt, budget):
    rid = eng.submit(prompt, budget)
    return eng.run(max_steps=300)[rid]


@pytest.mark.parametrize("wq", ["int8", "int4"])
def test_engine_weight_quant_matches_baseline_tokens(memorized_lm, wq):
    m = memorized_lm
    base = _run(ServingEngine(m, num_slots=2, max_len=32), PATTERN[:4], 7)
    eng = ServingEngine(m, num_slots=2, max_len=32, weight_quant=wq)
    np.testing.assert_array_equal(_run(eng, PATTERN[:4], 7), base)
    errs = eng.weight_quant_error
    assert errs and all(
        v["rel_rms"] < (0.25 if wq == "int4" else 0.05)
        for v in errs.values())


def test_engine_weight_quant_composes_with_int4_kv(memorized_lm):
    """The full quantization ladder at once: int4 weights over int4 KV
    pages still reproduce the baseline greedy stream."""
    m = memorized_lm
    base = _run(ServingEngine(m, num_slots=2, max_len=32), PATTERN[:4], 7)
    eng = ServingEngine(m, num_slots=2, max_len=128, page_len=64,
                        weight_quant="int4", cache_dtype="int4")
    np.testing.assert_array_equal(_run(eng, PATTERN[:4], 7), base)


def test_engine_kernel_path_matches_reference_path():
    """d_model=128 aligns the projections with the kernel gate: the
    decode programs route QKV/out through the fused dequant-matmul
    (interpreter mode) and must emit the same tokens as the pure
    in-graph-dequant reference engine over the SAME qdicts."""
    m = Model.build(
        zoo.transformer_lm(31, d_model=128, num_heads=4, num_layers=2,
                           mlp_ratio=2, use_rope=True), (8,), seed=0)
    prompt = np.array([1, 2, 3, 4])
    ref_eng = ServingEngine(m, num_slots=1, max_len=16,
                            weight_quant="int4")
    assert not ref_eng._wq_keep_attn          # CPU: gate closed
    ref = _run(ref_eng, prompt, 5)
    with qm.force_interpret():
        k_eng = ServingEngine(m, num_slots=1, max_len=16,
                              weight_quant="int4")
        assert k_eng._wq_keep_attn
        got = _run(k_eng, prompt, 5)
    np.testing.assert_array_equal(got, ref)


def test_engine_weight_quant_validates():
    m = _tiny_lm()
    with pytest.raises(ValueError, match="weight_quant"):
        ServingEngine(m, num_slots=1, max_len=32, weight_quant="fp8")
    with pytest.raises(ValueError, match="does not fit one"):
        ServingEngine(m, num_slots=1, max_len=32, hbm_budget=1)


def test_generate_int4_weights_close_to_float(memorized_lm):
    """generate()'s weights_dtype ladder gained the int4 rung (unpacked
    4-bit grid via models.quantize): greedy tokens match f32 on the
    overfit LM."""
    m = memorized_lm
    ref = generate(m, PATTERN[None, :4], max_new_tokens=7,
                   temperature=0.0)
    got = generate(m, PATTERN[None, :4], max_new_tokens=7,
                   temperature=0.0, weights_dtype="int4")
    np.testing.assert_array_equal(got, ref)


# --- the obs report hook ---------------------------------------------------


def test_weight_quant_report(memorized_lm):
    from distkeras_tpu.obs.report import (weight_quant_markdown,
                                          weight_quant_report)
    eng = ServingEngine(memorized_lm, num_slots=1, max_len=32,
                        weight_quant="int4")
    rep = weight_quant_report(eng)
    assert rep["weight_quant"] == "int4"
    assert rep["num_leaves"] == len(eng.weight_quant_error)
    assert rep["worst_leaf"] in eng.weight_quant_error
    assert 0 < rep["worst_rel_rms"] < 0.25
    md = weight_quant_markdown(rep)
    assert "Weight quantization accuracy (int4)" in md
    assert rep["worst_leaf"] in md
    with pytest.raises(ValueError, match="weight_quant"):
        weight_quant_report(
            ServingEngine(memorized_lm, num_slots=1, max_len=32))
