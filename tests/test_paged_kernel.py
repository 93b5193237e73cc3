"""Paged-attention decode kernel vs the ``_gather_pages`` reference
(decode-kernel PR), in interpreter mode on the CPU mesh — the same
oracle pattern as ``test_decode_kernel.py``/``test_moe_fused.py``: the
kernel must reproduce the gather + masked-softmax readout the off-TPU
serving path runs, across GQA, int8, scrambled physical page order,
sentinel table entries and W > 1 verify windows, and end-to-end
through the serving engine (greedy token-identical, sampled
byte-identical to the gather engine)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distkeras_tpu.models import Model, zoo
from distkeras_tpu.models.decoding import (_gather_pages, _quantize_kv,
                                           generate,
                                           verify_step_slots_paged)
from distkeras_tpu.ops.attention import NEG_INF
from distkeras_tpu.ops.paged_attention import (page_aligned,
                                               paged_decode_attention)
from distkeras_tpu.serving import ServingEngine


def _pool(rs, n_pages, hkv, page_len, d, int8=False):
    k = jnp.asarray(rs.randn(n_pages, hkv, page_len, d), jnp.float32)
    v = jnp.asarray(rs.randn(n_pages, hkv, page_len, d), jnp.float32)
    if not int8:
        return {"k": k, "v": v}
    qk, ks = _quantize_kv(k)
    qv, vs = _quantize_kv(v)
    return {"k": qk, "v": qv, "k_scale": ks, "v_scale": vs}


def _reference(q, kv, table, t, scale, window=None):
    """The gather-path readout: ``_gather_pages`` + the exact masked
    softmax of ``_slot_attn_readout`` (dequantized for int8), without
    the output projection."""
    view = _gather_pages(kv, jnp.asarray(table))
    k, v = view["k"], view["v"]
    if "k_scale" in view:
        k = k.astype(jnp.float32) * view["k_scale"][..., None]
        v = v.astype(jnp.float32) * view["v_scale"][..., None]
    L = k.shape[2]
    w_len = q.shape[1]
    qg = q.astype(jnp.float32) * scale               # [S, W, H, G, D]
    s = jnp.einsum("bqhgd,bhkd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32)
    pos = t[:, None] + jnp.arange(w_len)
    valid = jnp.arange(L)[None, None, :] <= pos[:, :, None]
    if window is not None:
        valid &= jnp.arange(L)[None, None, :] > (pos - window)[:, :, None]
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgqk,bhkd->bqhgd", w, v,
                      preferred_element_type=jnp.float32)


#: scrambled physical placement with sentinel (unallocated) entries —
#: logical page order must come from the TABLE, never from page ids
TABLE = np.array([[7, 2, 9, 10], [0, 5, 10, 10], [3, 1, 4, 6]],
                 np.int32)
T = np.array([20, 11, 30], np.int32)


@pytest.mark.parametrize("g", [1, 4])
@pytest.mark.parametrize("w_len", [1, 3])
def test_kernel_matches_gather_reference(g, w_len):
    rs = np.random.RandomState(0)
    kv = _pool(rs, 10, 2, 8, 16)
    q = jnp.asarray(rs.randn(3, w_len, 2, g, 16), jnp.float32)
    scale = 16 ** -0.5
    out = paged_decode_attention(q, kv["k"], kv["v"], T, TABLE,
                                 scale=scale, interpret=True)
    ref = _reference(q, kv, TABLE, T, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)


def test_kernel_window_masking():
    rs = np.random.RandomState(1)
    kv = _pool(rs, 10, 2, 8, 16)
    q = jnp.asarray(rs.randn(3, 2, 2, 2, 16), jnp.float32)
    scale = 16 ** -0.5
    out = paged_decode_attention(q, kv["k"], kv["v"], T, TABLE,
                                 scale=scale, window=6, interpret=True)
    ref = _reference(q, kv, TABLE, T, scale, window=6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)


def test_kernel_int8_dequant_matches_dequantized_reference():
    # int8 page blocks need page_len % 32 (Mosaic sublane rule)
    rs = np.random.RandomState(2)
    kv = _pool(rs, 6, 2, 32, 16, int8=True)
    table = np.array([[4, 1, 6], [2, 0, 5]], np.int32)
    t = np.array([40, 70], np.int32)
    q = jnp.asarray(rs.randn(2, 3, 2, 2, 16), jnp.float32)
    scale = 16 ** -0.5
    out = paged_decode_attention(
        q, kv["k"], kv["v"], t, table, scale=scale,
        k_scale=kv["k_scale"], v_scale=kv["v_scale"], interpret=True)
    ref = _reference(q, kv, table, t, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4)


def test_kernel_under_jit_with_traced_inputs():
    """t and table are traced arguments inside the engine's compiled
    step — the scalar-prefetch operands must accept them."""
    rs = np.random.RandomState(3)
    kv = _pool(rs, 10, 2, 8, 16)
    q = jnp.asarray(rs.randn(3, 1, 2, 2, 16), jnp.float32)
    scale = 16 ** -0.5

    @jax.jit
    def run(t, table):
        return paged_decode_attention(q, kv["k"], kv["v"], t, table,
                                      scale=scale, interpret=True)

    out = run(jnp.asarray(T), jnp.asarray(TABLE))
    ref = _reference(q, kv, TABLE, T, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)


def test_alignment_gate():
    """The tiling gate: unaligned page_len raises on the direct call
    (callers pre-check ``page_aligned`` and keep the gather path)."""
    assert page_aligned(16, quantized=False)
    assert not page_aligned(4, quantized=False)
    assert page_aligned(32, quantized=True)
    assert not page_aligned(16, quantized=True)
    rs = np.random.RandomState(4)
    kv = _pool(rs, 4, 2, 4, 16)
    q = jnp.asarray(rs.randn(1, 1, 2, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="kernel-tileable"):
        paged_decode_attention(q, kv["k"], kv["v"], np.array([3]),
                               np.array([[0]]), interpret=True)


# --- end-to-end: the serving engine with the kernel forced ----------------


V, S = 29, 12
PATTERN = np.array([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8])


@pytest.fixture(scope="module")
def memorized_lm(pattern_lm):
    """The shared session-scoped overfit-PATTERN LM (conftest pattern_lm): huge greedy argmax margins keep token-identity assertions robust; trained once per test session."""
    return pattern_lm


def test_engine_kernel_greedy_matches_generate(memorized_lm):
    """decode_kernel="paged" (interpreter mode on CPU): greedy engine
    output through the kernel readout is token-identical to
    standalone generate() — the serving oracle, kernel edition."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, page_len=8,
                        decode_kernel="paged")
    r0 = eng.submit(PATTERN[:4], 7)
    r1 = eng.submit(PATTERN[:6], 5)
    out = eng.run(max_steps=500)
    np.testing.assert_array_equal(
        out[r0], generate(m, PATTERN[None, :4], 7, temperature=0.0)[0])
    np.testing.assert_array_equal(
        out[r1], generate(m, PATTERN[None, :6], 5, temperature=0.0)[0])


@pytest.mark.parametrize("decode_kernel,page_len,path", [
    ("paged", 8, "paged_attention=kernel"),
    ("off", 8, "paged_attention=gather_reference"),
    ("auto", 8, "paged_attention=gather_reference"),    # off-TPU
    ("paged", 4, "paged_attention=gather_reference"),   # tiling gate
])
def test_health_reports_the_path_each_program_took(
        memorized_lm, decode_kernel, page_len, path):
    """Kernel or reference is READ from ``health()["programs"]``, not
    inferred from the backend or the option: the shape gate's quiet
    gather fallback (page_len 4 breaks the sublane rule) shows up as
    what it is."""
    eng = ServingEngine(memorized_lm, num_slots=2, max_len=32,
                        page_len=page_len, decode_kernel=decode_kernel)
    assert eng.health()["programs"] == {}       # nothing traced yet
    eng.submit(PATTERN[:4], 3)
    eng.run(max_steps=100)
    programs = eng.health()["programs"]
    assert programs["decode_greedy"] == f"kv_cache=donated, {path}"
    assert programs["prefill"] == \
        "flash_attention=xla_reference, kv_cache=donated"


def test_decode_logits_kernel_and_gather_read_the_same_state(
        memorized_lm):
    """``decode_logits``: one decode step's logits for every slot on
    the CURRENT cache, kernel vs gather on identical state, without
    advancing any stream (the comparison ``chip_smoke.py`` makes on
    the chip)."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, page_len=8,
                        decode_kernel="paged")
    rids = [eng.submit(PATTERN[:4], 7), eng.submit(PATTERN[:6], 5)]
    while len(eng.scheduler.running) < 2:
        eng.step()
    kernel = eng.decode_logits()
    gather = eng.decode_logits(decode_kernel="off")
    assert kernel.shape == (2, V)
    np.testing.assert_allclose(kernel, gather, atol=1e-4)
    np.testing.assert_array_equal(kernel, eng.decode_logits())  # a read
    out = eng.run(max_steps=500)
    for rid, n, k in zip(rids, (4, 6), (7, 5)):
        np.testing.assert_array_equal(
            out[rid], generate(m, PATTERN[None, :n], k,
                               temperature=0.0)[0])
    assert eng.health()["programs"]["decode_logits[off,None]"] == \
        "kv_cache=kept, paged_attention=gather_reference"


def test_engine_kernel_sampled_matches_gather_engine(memorized_lm):
    """A sampled stream decoded through the kernel draws the same
    bytes as through the gather path (the logits agree far inside
    the categorical draw's decision margins on this fixture)."""
    m = memorized_lm

    def drive(kernel):
        eng = ServingEngine(m, num_slots=2, max_len=32, page_len=8,
                            decode_kernel=kernel)
        rid = eng.submit(PATTERN[:4], 8, temperature=0.9, top_p=0.95,
                         seed=7)
        return eng.run(max_steps=500)[rid]

    np.testing.assert_array_equal(drive("paged"), drive("off"))


def test_verify_window_kernel_matches_gather(memorized_lm):
    """The speculative verify step ([S, W] window-causal) through the
    kernel equals the gather path on the same paged cache — the W > 1
    generalization the spec engine rides."""
    m = memorized_lm
    from distkeras_tpu.models.decoding import _resolve_head_dims
    from distkeras_tpu.serving.kv_pool import PagedKVPool
    _resolve_head_dims(m.module, m.params)
    pool = PagedKVPool(m.module, num_slots=2, max_len=32, page_len=8)
    # allocate every slot's pages so window writes land
    for slot in range(2):
        for lp in range(pool.pages_per_slot):
            pool.assign(slot, lp, pool.alloc_page())
    toks = jnp.asarray(np.array([[3, 1, 4], [5, 9, 2]], np.int32))
    t = jnp.asarray(np.array([5, 9], np.int32))
    outs = {}
    for kernel in (True, False):
        logits, _ = verify_step_slots_paged(
            m.module, m.params, m.state, pool.cache, toks, t,
            pool.device_tables(), pool.page_len, paged_kernel=kernel)
        outs[kernel] = np.asarray(logits)
    np.testing.assert_allclose(outs[True], outs[False], atol=1e-4)
    np.testing.assert_array_equal(outs[True].argmax(-1),
                                  outs[False].argmax(-1))
