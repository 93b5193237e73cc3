"""Speculative decoding in the continuous-batching engine (spec-decode
PR): the oracle contract — greedy speculative outputs token-identical
per request to standalone ``generate()`` across BOTH draft sources,
sampled streams byte-identical to plain decode — plus
verify-step units, n-gram lookup units, acceptance-EMA degradation,
draft-pool starvation isolation, and metrics/tracer coverage."""

import numpy as np
import pytest

import jax.numpy as jnp

from distkeras_tpu.models import Model, zoo
from distkeras_tpu.models.decoding import (_resolve_head_dims,
                                           decode_step_slots, generate,
                                           init_cache,
                                           verify_step_slots,
                                           verify_step_slots_paged)
from distkeras_tpu.serving import (DraftModel, DraftSource, NgramDraft,
                                   ServingEngine)
from distkeras_tpu.serving.speculation import tree_ancestors
from paged_layout import assert_same_cache, scrambled_tables, to_pages

V, S = 29, 12
PATTERN = np.array([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8])


@pytest.fixture(scope="module")
def memorized_lm(pattern_lm):
    """The shared session-scoped overfit-PATTERN LM (conftest pattern_lm): huge greedy argmax margins keep token-identity assertions robust; trained once per test session."""
    return pattern_lm


class WrongDraft(DraftSource):
    """Adversarial draft: always proposes token 0 (PATTERN never
    contains it, so the memorized model never accepts)."""

    def propose(self, requests, tok, t, out, active):
        out[:] = 0


def _tree(spec_tree):
    """Engine kwargs for the spec_tree parametrization: tree width 1
    must be byte-identical to the landed linear path (the tree-masked
    verify walk degenerates to the chain — tree-speculation PR)."""
    return {"spec_tree": True, "spec_width": 1} if spec_tree else {}



# --- verify-step unit: one window pass == W sequential decode steps ---------


def test_verify_step_slots_matches_sequential_decode():
    """verify_step_slots over a [S, W] window must agree with W
    sequential decode_step_slots calls — logits at every window
    position AND the final cache."""
    m = Model.build(
        zoo.transformer_lm(V, d_model=32, num_heads=4, num_layers=2,
                           mlp_ratio=2, use_rope=True), (16,), seed=4)
    _resolve_head_dims(m.module, m.params)
    rs = np.random.RandomState(0)
    toks = rs.randint(0, V, (2, 10)).astype(np.int32)
    hist = [3, 2]                       # staggered per-slot depths
    cache = init_cache(m.module, 2, 16)
    for step in range(max(hist)):
        tk = np.array([toks[i, min(step, hist[i] - 1)]
                       for i in range(2)], np.int32)
        tv = np.array([step if step < hist[i] else 16
                       for i in range(2)], np.int32)
        _, cache = decode_step_slots(m.module, m.params, m.state, cache,
                                     jnp.asarray(tk), jnp.asarray(tv))
    W = 4
    seq_cache = cache
    ref = []
    for j in range(W):
        tk = np.array([toks[0, hist[0] + j], toks[1, hist[1] + j]],
                      np.int32)
        tv = np.array([hist[0] + j, hist[1] + j], np.int32)
        lg, seq_cache = decode_step_slots(
            m.module, m.params, m.state, seq_cache, jnp.asarray(tk),
            jnp.asarray(tv))
        ref.append(np.asarray(lg))
    win = np.stack([toks[0, hist[0]:hist[0] + W],
                    toks[1, hist[1]:hist[1] + W]], 0)
    lg, ver_cache = verify_step_slots(
        m.module, m.params, m.state, cache, jnp.asarray(win),
        jnp.asarray(np.array(hist, np.int32)))
    np.testing.assert_allclose(np.asarray(lg), np.stack(ref, 1),
                               atol=3e-5)
    for a, b in zip(seq_cache, ver_cache):
        if a is None:
            continue
        for key in a:
            np.testing.assert_allclose(np.asarray(a[key]),
                                       np.asarray(b[key]), atol=3e-5)


@pytest.mark.parametrize("cache_dtype", ["float32", "int8"])
@pytest.mark.parametrize("window", ["linear", "tree"])
def test_verify_step_slots_paged_matches_contiguous(window, cache_dtype):
    """The engine's verify, ``verify_step_slots_paged`` over scattered
    physical pages, against its reference ``verify_step_slots`` on the
    same values in one contiguous cache: logits at every window
    position, the cache in logical order after the window's writes
    and, for a tree, the roped window K/V the commit reads."""
    L, page_len, W = 16, 4, 4
    m = Model.build(
        zoo.transformer_lm(V, d_model=32, num_heads=4, num_layers=2,
                           mlp_ratio=2, use_rope=True), (L,), seed=4)
    _resolve_head_dims(m.module, m.params)
    rs = np.random.RandomState(0)
    toks = rs.randint(0, V, (2, 10)).astype(np.int32)
    hist = [3, 2]                       # staggered per-slot depths
    cache = init_cache(m.module, 2, L, cache_dtype)
    for step in range(max(hist)):
        tk = np.array([toks[i, min(step, hist[i] - 1)]
                       for i in range(2)], np.int32)
        tv = np.array([step if step < hist[i] else L
                       for i in range(2)], np.int32)
        _, cache = decode_step_slots(m.module, m.params, m.state, cache,
                                     jnp.asarray(tk), jnp.asarray(tv))
    win = np.stack([toks[0, hist[0]:hist[0] + W],
                    toks[1, hist[1]:hist[1] + W]], 0)
    kw = {}
    if window == "tree":
        # root(0) -> 1 -> 2, root -> 3: a branch no chain expresses
        parents = np.tile(np.array([-1, 0, 1, 0], np.int32), (2, 1))
        depth, anc, _ = tree_ancestors(parents)
        kw["tree"] = {"depth": jnp.asarray(depth),
                      "anc": jnp.asarray(anc)}
    t = jnp.asarray(np.array(hist, np.int32))
    tables, n_pages = scrambled_tables(2, L // page_len, seed=5)
    ref = verify_step_slots(m.module, m.params, m.state, cache,
                            jnp.asarray(win), t, **kw)
    got = verify_step_slots_paged(
        m.module, m.params, m.state,
        to_pages(cache, tables, page_len, n_pages), jnp.asarray(win), t,
        jnp.asarray(tables), page_len, **kw)
    assert len(ref) == len(got) == (3 if window == "tree" else 2)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]),
                               atol=3e-5)
    assert_same_cache(ref[1], got[1], tables)
    if window == "tree":
        for a, b in zip(ref[2], got[2]):
            assert (a is None) == (b is None)
            if a is not None:
                for x, y in zip(a, b):
                    np.testing.assert_array_equal(np.asarray(x),
                                                  np.asarray(y))


def test_verify_step_sentinel_slot_writes_nothing():
    """A slot at the inert sentinel position must not touch the cache
    through a whole verify window (the free-slot contract of
    decode_step_slots, window-sized)."""
    m = Model.build(
        zoo.transformer_lm(V, d_model=16, num_heads=2, num_layers=1,
                           mlp_ratio=2, use_rope=True), (16,), seed=0)
    _resolve_head_dims(m.module, m.params)
    cache = init_cache(m.module, 2, 16)
    kv0 = next(c for c in cache if c is not None)
    before = np.array(kv0["k"])
    win = np.array([[3, 5, 1], [2, 4, 6]], np.int32)
    _, cache2 = verify_step_slots(
        m.module, m.params, m.state, cache, jnp.asarray(win),
        jnp.asarray(np.array([16, 16], np.int32)))
    kv1 = next(c for c in cache2 if c is not None)
    np.testing.assert_array_equal(np.asarray(kv1["k"]), before)


# --- n-gram lookup unit -----------------------------------------------------


def test_ngram_lookup_proposes_continuation():
    d = NgramDraft(max_ngram=3, min_ngram=1)
    ctx = np.array([7, 1, 2, 3, 9, 9, 1, 2, 3], np.int32)
    # suffix [1, 2, 3] occurred at position 1; continuation was [9, 9, 1]
    np.testing.assert_array_equal(d.lookup(ctx, 3), [9, 9, 1])
    # periodic stream: prefers the occurrence with a full-k continuation
    per = np.tile([4, 5, 6], 4).astype(np.int32)
    np.testing.assert_array_equal(d.lookup(per, 4), [4, 5, 6, 4])
    # no re-occurrence at any n: filler zeros
    fresh = np.array([1, 2, 3, 4, 5], np.int32)
    np.testing.assert_array_equal(d.lookup(fresh, 3), [0, 0, 0])
    # falls back from max_ngram to shorter suffixes
    short = np.array([8, 3, 9, 1, 3], np.int32)   # only n=1 matches
    assert d.lookup(short, 2)[0] == 9             # token after the 3
    with pytest.raises(ValueError, match="min_ngram"):
        NgramDraft(max_ngram=2, min_ngram=3)


# --- the oracle: greedy speculation == generate(), per request --------------


@pytest.mark.parametrize("spec_tree", [False, True])
def test_greedy_ngram_spec_matches_generate_paged(memorized_lm, spec_tree):
    """N-gram self-drafting on the paged engine: staggered arrivals,
    mixed lengths/budgets, more requests than slots. Every request's
    greedy tokens equal standalone generate(), and speculation really
    fired (drafts were accepted)."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=3, max_len=48, page_len=4,
                        draft=NgramDraft(), spec_k=3, **_tree(spec_tree))
    prompts = [np.tile(PATTERN, 2)[:10], np.tile(PATTERN, 2)[:14],
               PATTERN[:6], np.tile(PATTERN, 2)[:13]]
    budgets = [12, 9, 14, 10]
    rids = [eng.submit(prompts[i], budgets[i]) for i in range(2)]
    eng.step()
    eng.step()
    rids += [eng.submit(prompts[i], budgets[i]) for i in range(2, 4)]
    out = eng.run(max_steps=800)
    for i, rid in enumerate(rids):
        ref = generate(m, prompts[i][None], max_new_tokens=budgets[i],
                       temperature=0.0)
        np.testing.assert_array_equal(out[rid], ref[0])
    s = eng.metrics.summary()
    assert s["speculation"]["accepted"] > 0
    assert 0.0 < s["acceptance_rate"] <= 1.0


def test_greedy_draft_model_spec_matches_generate(memorized_lm):
    """A DraftModel (here: the target itself, the perfect-drafter
    limit) through its own paged KV: outputs equal generate() and
    acceptance is near 1 — most iterations emit k+1 tokens."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=48, page_len=4,
                        draft=DraftModel(m, page_len=4), spec_k=3)
    r0 = eng.submit(np.tile(PATTERN, 2)[:10], 12)
    r1 = eng.submit(PATTERN[:5], 10)
    out = eng.run(max_steps=800)
    np.testing.assert_array_equal(
        out[r0],
        generate(m, np.tile(PATTERN, 2)[None, :10], 12,
                 temperature=0.0)[0])
    np.testing.assert_array_equal(
        out[r1], generate(m, PATTERN[None, :5], 10, temperature=0.0)[0])
    assert eng.metrics.summary()["acceptance_rate"] > 0.8


@pytest.mark.parametrize("spec_tree", [False, True])
def test_greedy_spec_int8_cache_matches_generate(memorized_lm, spec_tree):
    """Speculation composes with the int8 quantized cache: window
    writes quantize per position, scale planes ride the same tables."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=48, page_len=4,
                        cache_dtype="int8", draft=NgramDraft(),
                        spec_k=3, **_tree(spec_tree))
    prompt = np.tile(PATTERN, 2)[:13]
    rid = eng.submit(prompt, 9)
    out = eng.run(max_steps=800)
    ref = generate(m, prompt[None], max_new_tokens=9, temperature=0.0,
                   cache_dtype="int8")
    np.testing.assert_array_equal(out[rid], ref[0])


def test_spec_stop_token_mid_window(memorized_lm):
    """A stop token landing INSIDE an accepted window ends the request
    there — the result matches generate()'s stop semantics with no
    overshoot past the stop."""
    m = memorized_lm
    prompt = np.tile(PATTERN, 2)[:10]
    # pick the stop token from the model's OWN greedy continuation (the
    # 3rd new token) so the stop provably fires inside the first few
    # positions regardless of how the model extrapolates
    free = generate(m, prompt[None], max_new_tokens=12, temperature=0.0)
    stop = int(free[0, len(prompt) + 2])
    eng = ServingEngine(m, num_slots=1, max_len=48,
                        draft=NgramDraft(), spec_k=3)
    rid = eng.submit(prompt, 12, stop_token=stop)
    out = eng.run(max_steps=400)
    ref = generate(m, prompt[None], max_new_tokens=12, temperature=0.0,
                   stop_token=stop)
    got = out[rid]
    assert got[-1] == stop and len(got) <= len(prompt) + 3
    np.testing.assert_array_equal(got, ref[0, :len(got)])
    assert (ref[0, len(got):] == stop).all()


# --- sampled streams: byte-identical, not merely distribution-equal ---------


def test_sampled_spec_stream_byte_identical_to_plain(memorized_lm):
    """A sampled request under speculation draws the EXACT tokens it
    draws under plain decode: one PRNG split per emitted token, the
    deterministic-draft accept rule never consumes extra entropy."""
    m = memorized_lm

    def run(draft):
        eng = ServingEngine(m, num_slots=2, max_len=48,
                            draft=draft, spec_k=3)
        g = eng.submit(np.tile(PATTERN, 2)[:10], 10)
        srid = eng.submit(PATTERN[:5], 9, temperature=0.9, top_p=0.95,
                          seed=7, speculate=draft is not None)
        out = eng.run(max_steps=800)
        return out[g], out[srid]

    g_plain, s_plain = run(None)
    g_spec, s_spec = run(NgramDraft())
    np.testing.assert_array_equal(g_plain, g_spec)
    np.testing.assert_array_equal(s_plain, s_spec)
    # and the greedy neighbour still matches the standalone oracle
    np.testing.assert_array_equal(
        g_spec,
        generate(m, np.tile(PATTERN, 2)[None, :10], 10,
                 temperature=0.0)[0])


# --- preemption interaction -------------------------------------------------


@pytest.mark.parametrize("spec_tree", [False, True])
def test_spec_preempt_resume_token_identity(memorized_lm, spec_tree):
    """Streams speculating in a deliberately tiny page pool: the
    younger is preempted mid-speculation, resumes via the recompute
    prefill (draft KV re-ingested), and BOTH stay token-identical to
    generate()."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, page_len=4,
                        num_pages=8, prefix_cache=False,
                        draft=NgramDraft(), spec_k=3, **_tree(spec_tree))
    r0 = eng.submit(np.tile(PATTERN, 2)[:5], 16)
    eng.step()
    eng.step()
    r1 = eng.submit(np.tile(PATTERN, 2)[:6], 15)
    out = eng.run(max_steps=2000)
    assert eng.metrics.requests_preempted >= 1
    np.testing.assert_array_equal(
        out[r0],
        generate(m, np.tile(PATTERN, 2)[None, :5], 16,
                 temperature=0.0)[0])
    np.testing.assert_array_equal(
        out[r1],
        generate(m, np.tile(PATTERN, 2)[None, :6], 15,
                 temperature=0.0)[0])


def test_spec_preempted_sampled_stream_resumes_key_stream(memorized_lm):
    """Sampled + speculating + preempted: the per-slot key snapshot
    (taken AFTER the verify step advanced it by the emitted count)
    restores the exact draw stream on resume."""
    m = memorized_lm

    def run(num_pages):
        eng = ServingEngine(m, num_slots=2, max_len=32, page_len=4,
                            num_pages=num_pages, prefix_cache=False,
                            draft=NgramDraft(), spec_k=3)
        eng.submit(np.tile(PATTERN, 2)[:5], 16)          # greedy hog
        srid = eng.submit(np.tile(PATTERN, 2)[:4], 14,
                          temperature=0.9, top_p=0.95, seed=7)
        out = eng.run(max_steps=3000)
        return out[srid], eng.metrics.requests_preempted

    ample, p_ample = run(num_pages=16)
    tight, p_tight = run(num_pages=8)
    assert p_ample == 0 and p_tight >= 1
    np.testing.assert_array_equal(ample, tight)


# --- degradation: EMA kill switch, knobs, draft-pool starvation -------------


def test_acceptance_ema_kicks_degenerate_stream(memorized_lm):
    """An adversarial draft (never matches) must be demoted to plain
    decode after the EMA warm-up — and the output stays correct."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=1, max_len=64, draft=WrongDraft(),
                        spec_k=2, spec_warmup=4)
    prompt = np.tile(PATTERN, 2)[:8]
    rid = eng.submit(prompt, 20)
    done = {}
    while eng.scheduler.pending:
        for r in eng.step():
            done[r.rid] = r
    req = done[rid]
    assert req.spec_disabled and req.spec_checks >= 4
    s = eng.metrics.summary()
    assert s["speculation"]["disabled_streams"] == 1
    # after the kill switch, proposals stopped: exactly warm-up many
    assert s["speculation"]["proposed"] == 4 * 2
    assert s["acceptance_rate"] == 0.0
    np.testing.assert_array_equal(
        req.tokens, generate(m, prompt[None], 20, temperature=0.0)[0])


def test_speculate_knob_validation_and_opt_out(memorized_lm):
    """speculate=True without a draft source raises; speculate=False on
    a drafted engine runs plainly (zero proposals)."""
    m = memorized_lm
    plain = ServingEngine(m, num_slots=1, max_len=32)
    with pytest.raises(ValueError, match="draft"):
        plain.submit(PATTERN[:4], 4, speculate=True)
    eng = ServingEngine(m, num_slots=1, max_len=32,
                        draft=NgramDraft(), spec_k=3)
    rid = eng.submit(np.tile(PATTERN, 2)[:10], 8, speculate=False)
    out = eng.run(max_steps=400)
    assert eng.metrics.summary()["speculation"]["proposed"] == 0
    assert eng.metrics.summary()["acceptance_rate"] is None
    np.testing.assert_array_equal(
        out[rid],
        generate(m, np.tile(PATTERN, 2)[None, :10], 8,
                 temperature=0.0)[0])
    with pytest.raises(ValueError, match="spec_k"):
        ServingEngine(m, num_slots=1, max_len=32, draft=NgramDraft(),
                      spec_k=0)
    with pytest.raises(TypeError, match="DraftSource"):
        ServingEngine(m, num_slots=1, max_len=32, draft=object())


def test_draft_pool_starvation_disables_not_blocks(memorized_lm):
    """A DraftModel whose own pool cannot hold a slot's worst case
    reports failure at begin_slot: the request decodes UNSPECULATED
    but admission, decode and the oracle contract are untouched —
    drafting never gates serving."""
    m = memorized_lm
    draft = DraftModel(m, page_len=4, num_pages=2)   # far too small
    eng = ServingEngine(m, num_slots=1, max_len=48, page_len=4,
                        draft=draft, spec_k=3)
    prompt = np.tile(PATTERN, 2)[:10]
    rid = eng.submit(prompt, 8)
    done = {}
    while eng.scheduler.pending:
        for r in eng.step():
            done[r.rid] = r
    req = done[rid]
    assert req.spec_disabled
    assert eng.metrics.summary()["speculation"]["proposed"] == 0
    np.testing.assert_array_equal(
        req.tokens, generate(m, prompt[None], 8, temperature=0.0)[0])


class FlippingDraft(DraftSource):
    """Adversarial-then-helpful draft: garbage (token 0) for the first
    ``bad_calls`` propose() calls, then delegates to prompt-lookup —
    the transient-degradation shape the re-probe knob exists for."""

    def __init__(self, bad_calls):
        self.inner = NgramDraft()
        self.bad = bad_calls
        self.calls = 0

    def begin_slot(self, slot, context):
        return self.inner.begin_slot(slot, context)

    def end_slot(self, slot):
        return self.inner.end_slot(slot)

    def propose(self, requests, tok, t, out, active):
        self.calls += 1
        if self.calls <= self.bad:
            out[:] = 0
        else:
            self.inner.propose(requests, tok, t, out, active)


def test_spec_reprobe_reenables_after_cooldown(memorized_lm):
    """``spec_reprobe=N``: a stream demoted by the acceptance EMA gets
    deterministic re-probe coins after an N-token cooldown; once the
    draft recovers, speculation re-enables (counter moves, EMA warm-up
    restarts) and the output stays token-identical to the oracle."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=1, max_len=64,
                        draft=FlippingDraft(6), spec_k=2, spec_warmup=4,
                        spec_reprobe=4)
    prompt = np.tile(PATTERN, 4)[:8]
    rid = eng.submit(prompt, 40)
    done = {}
    steps = 0
    while eng.scheduler.pending:
        for r in eng.step():
            done[r.rid] = r
        steps += 1
        assert steps < 2000
    req = done[rid]
    s = eng.metrics.summary()["speculation"]
    assert s["disabled_streams"] >= 1        # the EMA demotion fired
    assert s["reenabled_streams"] >= 1       # ...and the re-probe took
    assert not req.spec_disabled             # speculating again at end
    assert s["accepted"] > 0                 # recovered draft accepted
    np.testing.assert_array_equal(
        req.tokens, generate(m, prompt[None], 40, temperature=0.0)[0])


def test_spec_reprobe_default_is_sticky(memorized_lm):
    """Without the knob the EMA demotion stays sticky — the pinned
    pre-existing contract — even when the draft recovers."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=1, max_len=64,
                        draft=FlippingDraft(6), spec_k=2, spec_warmup=4)
    rid = eng.submit(np.tile(PATTERN, 4)[:8], 40)
    done = {}
    while eng.scheduler.pending:
        for r in eng.step():
            done[r.rid] = r
    assert done[rid].spec_disabled
    s = eng.metrics.summary()["speculation"]
    assert s["reenabled_streams"] == 0
    with pytest.raises(ValueError, match="spec_reprobe"):
        ServingEngine(m, num_slots=1, max_len=64, draft=NgramDraft(),
                      spec_k=2, spec_reprobe=0)


# --- observability ----------------------------------------------------------


def test_spec_metrics_and_tracer_coverage(memorized_lm):
    """serving.spec_* counters move, acceptance_rate lands in
    summary(), and the request timeline carries aggregated
    spec_verify events with per-request proposed/accepted totals."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=1, max_len=48,
                        draft=NgramDraft(), spec_k=3)
    rid = eng.submit(np.tile(PATTERN, 2)[:12], 10)
    eng.run(max_steps=400)
    s = eng.metrics.summary()
    assert s["speculation"]["proposed"] > 0
    assert s["speculation"]["accepted"] >= 0
    assert s["acceptance_rate"] == pytest.approx(
        s["speculation"]["accepted"] / s["speculation"]["proposed"])
    assert s["speculation"]["accept_rate"] is not None
    rates = eng.metrics.spec_accept_rates()
    assert rates and all(0.0 <= r <= 1.0 for r in rates)
    tl = [t for t in eng.tracer.timelines() if t.rid == rid][0]
    assert tl.spec_proposed == s["speculation"]["proposed"]
    assert tl.spec_accepted == s["speculation"]["accepted"]
    ev = [e for e in tl.events if e["name"] == "spec_verify"]
    assert ev and sum(e["proposed"] for e in ev) == tl.spec_proposed
    assert sum(e["accepted"] for e in ev) == tl.spec_accepted
    summ = tl.summary()
    assert summ["spec_proposed"] == tl.spec_proposed
