"""Layers of several attention kinds in one model (window and full side by
side, their own head counts and RoPEs, a leading dense layer, sigmoid-routed
experts beside a shared expert) and the page groups ``ServingEngine`` gives
them: the served path against the plain reference
(``benchmarks/reference/laguna.py``: float32 ``jax.numpy``, no kernel, no
cache) at the ``rehearse`` sizes of ``benchmarks/configs/laguna-xs.2.json``
(two periods, window 8, 8 experts top-2, a shared expert, a dense first
layer), on seeded random weights. Logits, not tokens.

Tolerances. Model and reference compute in float32 here, on the same
float32 weights (a bfloat16 embedding table would make the program's whole
residual stream bfloat16); what is left is the order of sums
(chunked and paged softmax against one softmax, the grouped expert layout
against a dense sum), which moves a logit by under 3e-7 at this size.
``TOL`` is seventy times that, and far under what bfloat16 products move
(``test_tolerance_would_catch_bfloat16``).
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmarks")]

from harness import common, laguna_family as fam  # noqa: E402
from reference import laguna  # noqa: E402

from distkeras_tpu.models import Model, zoo  # noqa: E402
from distkeras_tpu.models.decoding import generate  # noqa: E402
from distkeras_tpu.models.moe import MoE  # noqa: E402
from distkeras_tpu.ops.attention import (apply_rope, yarn_attention_factor,
                                         yarn_inv_freq)  # noqa: E402
from distkeras_tpu.serving import ServingEngine  # noqa: E402
from distkeras_tpu.serving.kv_pool import PagedKVPool, WindowPages  # noqa: E402
from distkeras_tpu.serving.scheduler import RequestState  # noqa: E402

TOL = 2e-5
SEED = 11

with open(os.path.join(ROOT, "benchmarks", "configs", "laguna-xs.2.json")) as f:
    CFG = common.config_for(json.load(f), rehearse=True)
S = fam.sizes(CFG)
WINDOW = 8


@pytest.fixture(scope="module")
def model():
    return fam.build_model(CFG, SEED, 256, dtype=jnp.float32)


@pytest.fixture(scope="module")
def ref_weights():
    return fam.reference_tree(fam.make_leaves(CFG, SEED, jnp.float32), S)


def _engine(model, **kw):
    kw = {"num_slots": 3, "max_len": 256, "page_len": 8,
          "prefill_chunk": 32, **kw}
    return ServingEngine(model, **kw)


def _prompt(n, seed=0, head=()):
    rng = np.random.default_rng(seed)
    out = rng.integers(0, S["vocab"], n).astype(np.int32)
    out[:len(head)] = head
    return out


def _reference_logits(w, tokens, positions, **kw):
    seq = np.zeros(-(-len(tokens) // laguna.Q_BLOCK) * laguna.Q_BLOCK,
                   np.int32)
    seq[:len(tokens)] = tokens
    return np.asarray(laguna.logits_at(w, fam.reference_cfg(CFG), seq,
                                       np.asarray(positions), **kw))


def _to_decoding(eng, rid):
    while eng[rid].state is not RequestState.DECODING:
        eng.step()
    return eng[rid]


def _decode_logits(eng, req):
    """Logits of the step ``req`` takes next, and the tokens they follow."""
    lg = eng.decode_logits()[req.slot]       # drains the step in flight
    return lg, np.concatenate([req.prompt, req.generated])


def test_model_is_the_configuration_at_its_rehearse_sizes(model):
    blocks = model.module.layers[1:-2]
    assert [b.attn.attn_window for b in blocks] \
        == [None, 8, 8, 8, None, 8, 8, 8, None]
    assert [b.attn.num_heads for b in blocks] == [2, 4, 4, 4, 2, 4, 4, 4, 2]
    assert [isinstance(b.mlp, MoE) for b in blocks] == [False] + [True] * 8
    full, sliding = blocks[0].attn, blocks[1].attn
    assert full.rotary_dim == 8 and full.rope_yarn["factor"] == 64
    assert sliding.rotary_dim is None and sliding.rope_base == 10000.0
    moe = blocks[1].mlp
    assert (moe.score, moe.route_scale, moe.shared_dim, moe.dispatch) \
        == ("sigmoid", 2.5, 32, "grouped")


@pytest.mark.parametrize("kernel", ["off", "paged"],
                         ids=["gather", "paged_kernel"])
def test_prefill_then_decode_equals_the_reference(model, ref_weights, kernel):
    """A prompt of 5 windows, chunked prefill, then 4 windows of decode
    through both page groups: at every checked step the served logits are
    the reference's full forward over the tokens so far; by then the slot
    has given back the window pages of all but the last window."""
    eng = _engine(model, decode_kernel=kernel)
    rid = eng.submit(_prompt(40, 1), 36)
    req = _to_decoding(eng, rid)
    grp = eng.pool.aux[0]
    worst = 0.0
    for step in range(33):
        if step % 4 == 0:
            got, toks = _decode_logits(eng, req)
            want = _reference_logits(ref_weights, toks, [len(toks) - 1])[0]
            worst = max(worst, float(np.abs(got - want).max()))
            assert len(grp.slot_pages(req.slot)) <= grp.ring
        eng.step()
    assert worst < TOL, worst
    held = grp.slot_pages(req.slot)
    # the ring began at the prompt's last window (pages 4 and 5 of 9)
    assert min(held) >= grp.first_needed(len(toks)) >= 7
    assert grp.pages_released >= 4
    groups = eng.health()["kv_groups"]
    assert set(groups) == {"full", "window8"}
    assert groups["window8"]["pages_released"] == grp.pages_released
    assert groups["window8"]["pages_live"] == len(held)
    assert eng.metrics.summary()["kv_groups"]["full"]["pages_live"] \
        == eng.pool.pages_for(len(toks) + 1)
    path = "kernel" if kernel == "paged" else "gather_reference"
    assert f"paged_attention={path}" in eng.health()["programs"]["decode_greedy"]


def test_tolerance_would_catch_bfloat16(ref_weights):
    toks = _prompt(72, 1)
    exact = _reference_logits(ref_weights, toks, [71])
    rounded = _reference_logits(ref_weights, toks, [71],
                                precision="bfloat16")
    assert np.abs(rounded - exact).max() > 10 * TOL


def test_chunked_prefill_equals_whole_prefill(model):
    prompt = _prompt(70, 2)
    read = []
    for chunk in (None, 16):
        eng = _engine(model, prefill_chunk=chunk)
        req = _to_decoding(eng, eng.submit(prompt, 4))
        read.append(_decode_logits(eng, req)[0])
    assert np.abs(read[0] - read[1]).max() < TOL


def test_prefix_hit_hands_a_slot_what_prefill_would_have_written(model):
    """Three prompts behind one 24-token template (three pages, three
    windows). The first registers it; the second finds prompts parting at
    its end, cannot resume there (the window layers' pages of the window
    before it were given back long ago) and leaves them; the third hits,
    loads both groups' pages, and reads what a cold engine reads."""
    template = _prompt(24, 3)
    prompts = [_prompt(60, 10 + i, head=template) for i in range(3)]
    eng = _engine(model)
    shared = []
    for p in prompts:
        req = _to_decoding(eng, eng.submit(p, 12))
        shared.append(req._shared_len)
        hot = _decode_logits(eng, req)[0]
        eng.run()
    assert shared == [0, 0, 24]
    cold_eng = _engine(model, prefix_cache=False)
    cold = _decode_logits(
        cold_eng, _to_decoding(cold_eng, cold_eng.submit(prompts[2], 12)))[0]
    assert np.abs(hot - cold).max() < TOL
    assert eng.metrics.summary()["prefix_cache"]["hits"] == 1
    # the window group kept the template's last window (page 2 reaches
    # back to position 17: pages 2 and, for position 17 to 23... page 2)
    chain = eng.prefix._walk(prompts[0], 0)
    kept = [n.aux is not None and n.aux[0] is not None for n in chain[:4]]
    assert kept == [False, False, True, False]
    # a prompt that IS the template parts from it a page earlier (the last
    # position is always recomputed): another boundary, learned likewise
    for _ in range(2):
        req = _to_decoding(eng, eng.submit(template, 4))
        shared.append(req._shared_len)
        eng.run()
    assert shared[3:] == [0, 16]


def test_preempted_slot_resumes_after_its_window_pages_were_released(model):
    """A pool of 14 full pages under two streams that grow to 10 each: the
    younger is preempted after it has given window pages back, re-prefills
    its context (prompt + generated) into a fresh ring, and both drain
    token-identical to ``generate()``."""
    eng = _engine(model, num_slots=2, num_pages=(14, None),
                  prefix_cache=False)
    prompts = [_prompt(30, 20), _prompt(34, 21)]
    rids = [eng.submit(p, 44) for p in prompts]
    released_at_preemption = None
    out = {}
    while eng.scheduler.pending:
        before = eng.metrics.summary()["requests_preempted"]
        for r in eng.step():
            out[r.rid] = r.tokens
        if released_at_preemption is None \
                and eng.metrics.summary()["requests_preempted"] > before:
            released_at_preemption = eng.pool.aux[0].pages_released
    assert eng.metrics.summary()["requests_preempted"] >= 1
    assert released_at_preemption and released_at_preemption >= 4
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(out[rid], generate(model, p[None], 44)[0])
    assert eng.pool.aux[0].free_pages == eng.pool.aux[0].num_pages
    assert eng.pool.free_pages == eng.pool.num_pages


# --- RoPE ------------------------------------------------------------------

def _yarn_transcribed(dim, theta, factor, original, beta_fast, beta_slow):
    """``transformers``' ``_compute_yarn_parameters`` in numpy."""
    import math

    def correction_dim(rot):
        return dim * math.log(original / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = theta ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extrapolation = 1 - ramp
    return (1 / (factor * pos_freqs)) * (1 - extrapolation) \
        + (1 / pos_freqs) * extrapolation


@pytest.mark.parametrize("dim,theta,factor,original,fast,slow", [
    (64, 500000.0, 64, 4096, 64, 1),          # Laguna-XS.2's full layers
    (64, 500000.0, 128, 8192, 32, 1),
    (8, 500000.0, 64, 16, 64, 1)])            # the rehearse size
def test_yarn_frequencies_are_the_published_formula(dim, theta, factor,
                                                    original, fast, slow):
    got = yarn_inv_freq(dim, theta, factor, original, fast, slow)
    want = _yarn_transcribed(dim, theta, factor, original, fast, slow)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(
        laguna.yarn_parameters(dim, theta, factor, original, fast, slow)[0],
        want, rtol=1e-6)
    # fast dimensions keep their frequency, slow ones are interpolated
    plain = 1 / theta ** (np.arange(0, dim, 2) / dim)
    assert got[0] == pytest.approx(plain[0]) \
        and got[-1] == pytest.approx(plain[-1] / factor)
    assert yarn_attention_factor(64) == pytest.approx(1.4158883083359672)


@pytest.mark.parametrize("layout", ["bshd", "bhsd"])
@pytest.mark.parametrize("rotary_dim,scaled", [(None, False), (8, False),
                                               (8, True)])
def test_partial_and_scaled_rope_against_the_formula(layout, rotary_dim,
                                                     scaled):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 5, 3, 16)).astype(np.float32)   # b s h d
    pos = np.array([3, 4, 9, 100, 1000])
    rot = rotary_dim or 16
    inv = _yarn_transcribed(rot, 5e5, 64, 16, 64, 1) if scaled \
        else 1 / 5e5 ** (np.arange(0, rot, 2) / rot)
    mscale = 1.4158883083359672 if scaled else 1.0
    ang = pos[:, None] * inv[None]
    cos, sin = np.cos(ang) * mscale, np.sin(ang) * mscale
    want = x.copy()
    for i in range(rot // 2):
        a, b = x[..., 2 * i], x[..., 2 * i + 1]
        want[..., 2 * i] = a * cos[:, i][None, :, None] \
            - b * sin[:, i][None, :, None]
        want[..., 2 * i + 1] = b * cos[:, i][None, :, None] \
            + a * sin[:, i][None, :, None]
    xin = x if layout == "bshd" else x.transpose(0, 2, 1, 3)
    got = np.asarray(apply_rope(
        jnp.asarray(xin), pos, base=5e5, layout=layout,
        rotary_dim=rotary_dim, mscale=mscale,
        inv_freq=inv.astype(np.float32) if scaled else None))
    if layout == "bhsd":
        got = got.transpose(0, 2, 1, 3)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    if rotary_dim:
        np.testing.assert_array_equal(got[..., rot:], x[..., rot:])


# --- the expert layer ---------------------------------------------------------

@pytest.mark.parametrize("dispatch", ["dense", "tokens", "grouped"])
@pytest.mark.parametrize("score,norm,scale", [("sigmoid", True, 2.5),
                                              ("sigmoid", False, 1.0),
                                              ("softmax", True, 1.0)])
def test_routing_with_scale_and_shared_expert_is_the_dense_sum(
        dispatch, score, norm, scale):
    """``y = sum_{e in top-k} w_e E_e(x) + S(x)``, ``w`` from sigmoid
    scores normalised over the chosen k and scaled, every expert and the
    shared one gated: each dispatch against the sum written out."""
    d, hid, e, k = 16, 12, 8, 2
    layer = MoE(e, hid, top_k=k, activation="silu", dispatch=dispatch,
                gated=True, use_bias=False, score=score, norm_topk=norm,
                route_scale=scale, shared_dim=20, capacity_factor=float(e))
    params, state, _ = layer.init(jax.random.PRNGKey(0), (7, d))
    assert params["shared"]["w1"].shape == (d, 20)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 7, d))
    got, _ = layer.apply(params, state, x)
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    xs = np.asarray(x, np.float64).reshape(-1, d)
    logits = xs @ p["gate"]
    silu = lambda a: a / (1 + np.exp(-a))
    want = np.zeros_like(xs)
    for n, row in enumerate(xs):
        top = np.argsort(-logits[n], kind="stable")[:k]
        if score == "sigmoid":
            w = 1 / (1 + np.exp(-logits[n][top]))
            w = w / w.sum() if norm else w
        else:
            w = np.exp(logits[n][top] - logits[n][top].max())
            w = w / w.sum()
        for wi, ei in zip(w * scale, top):
            want[n] += wi * (silu(row @ p["w1"][ei]) * (row @ p["w3"][ei])) \
                @ p["w2"][ei]
        sh = p["shared"]
        want[n] += (silu(row @ sh["w1"]) * (row @ sh["w3"])) @ sh["w2"]
    np.testing.assert_allclose(np.asarray(got).reshape(-1, d), want,
                               rtol=2e-4, atol=2e-5)
    again = MoE.from_config(layer.get_config())
    assert (again.score, again.route_scale, again.shared_dim) \
        == (score, scale, 20)


# --- page groups ---------------------------------------------------------------

@pytest.mark.parametrize("window,page_len", [(8, 8), (8, 4), (512, 128),
                                             (512, 16), (512, 64), (7, 3)])
def test_ring_is_as_wide_as_a_window_spans(window, page_len):
    class _Pool:
        num_slots, max_len = 2, 4096
    _Pool.page_len = page_len
    grp = WindowPages(_Pool, "w", [1], window)
    assert grp.ring == -(-(window - 1) // page_len) + 1
    widest = 0
    for t in range(0, 3 * window + 4 * page_len):
        span = grp.span(t)
        assert span[-1] == t // page_len
        assert span[0] * page_len <= max(0, t - window + 1) \
            < (span[0] + 1) * page_len
        assert len({lp % grp.ring for lp in span}) == len(span)
        widest = max(widest, len(span))
    assert widest == grp.ring


def test_window_pages_are_assigned_released_and_counted():
    class _Pool:
        num_slots, max_len, page_len = 2, 64, 4
    grp = WindowPages(_Pool, "w", [1], 8, num_pages=6)
    assert grp.ring == 3
    for lp in grp.span(9):                       # positions 2..9: pages 0-2
        grp.assign(0, lp, grp.alloc_page())
    assert grp.slot_pages(0) == {0: 0, 1: 1, 2: 2} and grp.live_pages == 3
    with pytest.raises(RuntimeError, match="still holds"):
        grp.assign(0, 3, 5)                      # page 0 not yet released
    assert grp.release_behind(0, 12) == 1        # window 5..12: page 0 goes
    grp.assign(0, 3, grp.alloc_page())
    assert grp.slot_pages(0) == {1: 1, 2: 2, 3: 0}
    assert grp.page_of(0, 3) == 0 and grp.page_of(0, 0) is None
    assert grp.release_slot(0) == 3 and grp.free_pages == 6
    assert grp.pages_released == 1


def test_a_model_of_one_kind_builds_one_group(model):
    """One attention kind (all causal, or all one window): no window group,
    one table, nothing new in the metrics; mixed kinds: the full layers are
    the pool, each window a group with planes for its own layers only."""
    for window in (None, 8):
        lm = zoo.transformer_lm(97, d_model=32, num_heads=4, num_layers=2,
                                max_len=64, attn_window=window)
        m = Model.build(lm, (8,), seed=0)
        eng = ServingEngine(m, num_slots=2, max_len=64, page_len=8)
        assert eng.pool.aux == [] and eng.pool.layer_groups is None
        assert eng._groups is None
        assert isinstance(eng.pool.device_tables(), jax.Array)
        eng.submit(np.arange(20, dtype=np.int32), 4)
        eng.run()
        assert eng.metrics.summary()["kv_groups"] is None
        assert "kv_groups" not in eng.health()
        with pytest.raises(ValueError, match="more than one attention kind"):
            PagedKVPool(lm, 2, 64, page_len=8, num_pages=(8, 8))
    eng = _engine(model)
    pool = eng.pool
    assert [g.name for g in pool.aux] == ["window8"]
    assert pool.aux[0].num_pages == 3 * (2 * pool.aux[0].ring - 1)
    tables = pool.device_tables()
    assert [t.shape for t in tables] == [(3, 32), (3, 2)]
    planes = [kv["k"].shape[0] for kv in pool.cache if kv is not None]
    assert planes == [96, 9, 9, 9, 96, 9, 9, 9, 96]
    assert pool.page_bytes == 3 * 2 * 2 * 8 * 16 * 4     # full layers only


def test_what_a_mixed_model_is_not_served_with(model):
    from distkeras_tpu.serving import NgramDraft
    for kw in ({"draft": NgramDraft()}, {"fuse_steps": 4},
               {"host_kv_pages": 8}, {"cache_dtype": "int4", "page_len": 64},
               {"hbm_budget": 1 << 30}):
        with pytest.raises(ValueError):
            _engine(model, **kw)
    lm = zoo.transformer_lm(
        97, d_model=32, num_heads=4, num_layers=2, max_len=64,
        layer_types=["a", "b"],
        attn_kinds={"a": {"attn_window": 8}, "b": {"attn_window": 16}})
    with pytest.raises(ValueError, match="none without"):
        ServingEngine(Model.build(lm, (8,), seed=0), max_len=64)
    with pytest.raises(ValueError, match="unknown keys"):
        zoo.transformer_lm(97, layer_types=["a"] * 6,
                           attn_kinds={"a": {"heads": 2}})
    with pytest.raises(ValueError, match="names 2 layers"):
        zoo.transformer_lm(97, mlp_layer_types=["dense", "sparse"])


def test_programs_report_what_their_expert_layers_routed(model):
    """Every decode step and prefill program of a grouped-dispatch model
    returns rows routed and experts touched: 3 slots (live or not) x top-2
    x 8 sparse layers a step; a prefill chunk that yields no logits stops
    before the last layer's experts."""
    eng = _engine(model, prefill_chunk=16)
    eng.submit(_prompt(40, 5), 6)
    eng.run()
    r = eng.metrics.summary()["routing"]
    steps = r["rows_routed"] // (3 * 2 * 8)
    assert r["rows_routed"] == steps * 48 and 4 <= steps <= 6
    assert 0 < r["experts_touched"] <= steps * 8 * 6
    assert r["prefill_rows_routed"] == 2 * (16 * 7 + 16 * 7 + 8 * 8)
    assert 0 < r["prefill_experts_touched"] <= 8 * 8 * 3


def test_expert_load_gauge_holds_every_expert_of_a_wide_router():
    """256 experts are 256 series of one gauge: the registry's guard
    against per-request labels (64 series) is not a limit on a label the
    model bounds, and no expert folds into the overflow series."""
    import warnings
    from distkeras_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        m.record_moe_route(np.arange(256.0), 1.0, 0.1)
    assert m.summary()["moe"]["expert_load"] == list(np.arange(256.0))
