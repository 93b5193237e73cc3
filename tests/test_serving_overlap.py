"""Zero-bubble serving loop (this PR): the oracle contract — pipelined
dispatch (``overlap=True``, the engine default) and the fused
multi-step window (``fuse_steps=K``) must produce TOKEN-IDENTICAL
outputs (byte-identical for sampled streams) to the synchronous
launch-and-wait loop and to standalone ``generate()`` — across
int8 cache, speculation, MoE dispatched decode and
preempt/resume — plus the lagged-fetch edge cases: stop tokens
mid-window and mid-fused-scan, preemption during a fused window
(fall back to single-step, rejoin identically), cancel/metrics-swap
pipeline flushes, fault injection inside a fused window, and the
deferred host-window tracer/metrics cadence staying exact-count."""

import numpy as np
import pytest

from distkeras_tpu.models import Model, zoo
from distkeras_tpu.models.decoding import generate
from distkeras_tpu.resilience import InjectedFault, faults
from distkeras_tpu.serving import (NgramDraft, ServingEngine,
                                   ServingMetrics)

V, S = 29, 12
PATTERN = np.array([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8])


@pytest.fixture(scope="module")
def memorized_lm(pattern_lm):
    """The shared session-scoped overfit-PATTERN LM (conftest pattern_lm): huge greedy argmax margins keep token-identity assertions robust; trained once per test session."""
    return pattern_lm


@pytest.fixture(scope="module")
def memorized_moe_lm(pattern_moe_lm):
    """The shared session-scoped all-MoE overfit-PATTERN LM
    (conftest pattern_moe_lm); trained once per session."""
    return pattern_moe_lm


def _drive(eng, subs, stagger=0):
    """Submit ``subs`` (kwargs for ``submit``), optionally stepping
    ``stagger`` iterations between arrivals, then drain. Returns
    ``{rid: tokens}`` in submit order alongside the rid list."""
    out = {}

    def tick():
        for r in eng.step():
            out[r.rid] = np.asarray(r.tokens)

    rids = []
    for kw in subs:
        rids.append(eng.submit(**kw))
        for _ in range(stagger):
            tick()
    steps = 0
    while eng.scheduler.pending:
        tick()
        steps += 1
        assert steps < 5000, "engine failed to drain"
    return out, rids


def _paged_kw(paged):
    return (dict(page_len=4, num_pages=24, prefix_cache=False)
            if paged else {})


# --- pipelined dispatch: token identity --------------------------------------


@pytest.mark.parametrize("paged", [False, True])
def test_pipelined_staggered_arrivals_match_generate(memorized_lm,
                                                     paged):
    """Staggered arrivals with mixed prompt lengths/budgets through
    the overlap engine (slots recycle mid-pipeline): every request's
    greedy tokens equal standalone generate()."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=3, max_len=32, overlap=True,
                        **_paged_kw(paged))
    prompts = [PATTERN[:4], PATTERN[:6], PATTERN[:3], PATTERN[:5],
               PATTERN[:7]]
    budgets = [7, 5, 9, 6, 4]
    subs = [dict(prompt=p, max_new_tokens=b)
            for p, b in zip(prompts, budgets)]
    out, rids = _drive(eng, subs, stagger=2)
    for i, rid in enumerate(rids):
        ref = generate(m, prompts[i][None], budgets[i],
                       temperature=0.0)
        np.testing.assert_array_equal(out[rid], ref[0])


@pytest.mark.parametrize("paged", [False, True])
def test_pipelined_stop_token_mid_stream_matches_generate(memorized_lm,
                                                          paged):
    """A stop token that fires while the NEXT step is already in
    flight (the overshoot contract: the stream is stepped at most once
    past its stop, the extra token never consumed)."""
    m = memorized_lm
    prompt = PATTERN[:5]
    ref = generate(m, prompt[None], 16, temperature=0.0,
                   stop_token=9)[0]
    assert 9 in np.asarray(ref)[len(prompt):], \
        "fixture drift: 9 must appear in the greedy continuation"
    eng = ServingEngine(m, num_slots=2, max_len=32, overlap=True,
                        **_paged_kw(paged))
    out, rids = _drive(eng, [
        dict(prompt=prompt, max_new_tokens=16, stop_token=9),
        dict(prompt=PATTERN[:4], max_new_tokens=8)])
    got = out[rids[0]]
    assert got[-1] == 9 and len(got) < len(prompt) + 16
    np.testing.assert_array_equal(got, np.asarray(ref)[:len(got)])
    assert (np.asarray(ref)[len(got):] == 9).all()   # generate()'s pad
    np.testing.assert_array_equal(
        out[rids[1]],
        generate(m, PATTERN[None, :4], 8, temperature=0.0)[0])


@pytest.mark.parametrize("paged", [False, True])
def test_sampled_byte_identity_vs_synchronous_engine(memorized_lm,
                                                     paged):
    """Sampled streams: the pipelined engine's draws must be
    BYTE-identical to the synchronous engine's — key chaining through
    the device-side feedback path replays the same per-slot splits."""
    m = memorized_lm
    subs = [dict(prompt=PATTERN[:5], max_new_tokens=10,
                 temperature=0.9, top_p=0.95, seed=7),
            dict(prompt=PATTERN[:4], max_new_tokens=12,
                 temperature=0.7, top_k=8, seed=11),
            dict(prompt=PATTERN[:6], max_new_tokens=8)]   # greedy rider
    outs = {}
    for overlap in (False, True):
        eng = ServingEngine(m, num_slots=2, max_len=32,
                            overlap=overlap, **_paged_kw(paged))
        outs[overlap], rids = _drive(eng, subs, stagger=1)
    for a, b in zip(sorted(outs[False]), sorted(outs[True])):
        np.testing.assert_array_equal(outs[False][a], outs[True][b])


def test_int8_cache_overlap_matches_generate(memorized_lm):
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, cache_dtype="int8",
                        overlap=True)
    out, rids = _drive(eng, [dict(prompt=PATTERN[:6], max_new_tokens=8),
                             dict(prompt=PATTERN[:4], max_new_tokens=6)])
    for rid, p, b in zip(rids, (PATTERN[:6], PATTERN[:4]), (8, 6)):
        ref = generate(m, p[None], b, temperature=0.0,
                       cache_dtype="int8")
        np.testing.assert_array_equal(out[rid], ref[0])


def test_spec_decode_with_pipelined_plain_iterations(memorized_lm):
    """A drafted engine: speculative iterations stay synchronous (the
    in-iteration verify fetch) but plain iterations around them
    pipeline — the mix must stay token-identical to generate()."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=48, overlap=True,
                        draft=NgramDraft(), spec_k=3)
    prompt = np.tile(PATTERN, 3)[:10]
    out, rids = _drive(eng, [
        dict(prompt=prompt, max_new_tokens=16),
        dict(prompt=PATTERN[:5], max_new_tokens=8, speculate=False)])
    np.testing.assert_array_equal(
        out[rids[0]], generate(m, prompt[None], 16, temperature=0.0)[0])
    np.testing.assert_array_equal(
        out[rids[1]],
        generate(m, PATTERN[None, :5], 8, temperature=0.0)[0])


def test_moe_dispatched_overlap_and_fused_match_generate(
        memorized_moe_lm):
    """MoE dispatched decode under the zero-bubble loop: overlap and
    fused engines both equal dense-routing generate()."""
    m = memorized_moe_lm
    prompt, budget = PATTERN[:5], 10
    ref = generate(m, prompt[None], budget, temperature=0.0)[0]
    for kw in (dict(overlap=True),
               dict(overlap=True, fuse_steps=4)):
        eng = ServingEngine(m, num_slots=2, max_len=32, **kw)
        out, rids = _drive(eng, [dict(prompt=prompt,
                                      max_new_tokens=budget)])
        np.testing.assert_array_equal(out[rids[0]], ref)


# --- fused multi-step windows ------------------------------------------------


@pytest.mark.parametrize("paged", [False, True])
def test_fused_steady_state_matches_generate(memorized_lm, paged):
    """Closed-loop quiescent batch on a fuse_steps=4 engine: fused
    windows engage after the prefill ramp and outputs equal
    generate() per request."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, overlap=True,
                        fuse_steps=4, **_paged_kw(paged))
    prompts = [PATTERN[:5], PATTERN[:4]]
    budgets = [14, 11]
    subs = [dict(prompt=p, max_new_tokens=b)
            for p, b in zip(prompts, budgets)]
    out, rids = _drive(eng, subs)
    assert eng._fused_fns, "fused window never compiled/engaged"
    for i, rid in enumerate(rids):
        ref = generate(m, prompts[i][None], budgets[i],
                       temperature=0.0)
        np.testing.assert_array_equal(out[rid], ref[0])


@pytest.mark.parametrize("paged", [False, True])
def test_fused_stop_token_mid_scan(memorized_lm, paged):
    """A stop token firing INSIDE a fused window: the in-program done
    mask pads the rest of the window with the stop token and the host
    truncates — output equals generate() with the same stop."""
    m = memorized_lm
    prompt = PATTERN[:5]
    ref = generate(m, prompt[None], 16, temperature=0.0,
                   stop_token=9)[0]
    eng = ServingEngine(m, num_slots=2, max_len=40, overlap=True,
                        fuse_steps=4, **_paged_kw(paged))
    out, rids = _drive(eng, [
        dict(prompt=prompt, max_new_tokens=16, stop_token=9),
        dict(prompt=PATTERN[:4], max_new_tokens=16)])
    got = out[rids[0]]
    assert got[-1] == 9 and len(got) < len(prompt) + 16
    np.testing.assert_array_equal(got, np.asarray(ref)[:len(got)])
    np.testing.assert_array_equal(
        out[rids[1]],
        generate(m, PATTERN[None, :4], 16, temperature=0.0)[0])


def test_fused_sampled_byte_identity_vs_synchronous(memorized_lm):
    """Sampled fused windows (keys split in-program, once per window
    step) must replay the synchronous engine's exact draw stream."""
    m = memorized_lm
    subs = [dict(prompt=PATTERN[:5], max_new_tokens=12,
                 temperature=0.9, top_p=0.95, seed=7),
            dict(prompt=PATTERN[:4], max_new_tokens=12,
                 temperature=0.7, top_k=8, seed=3)]
    sync = ServingEngine(m, num_slots=2, max_len=32, overlap=False)
    out_s, rids_s = _drive(sync, subs)
    fused = ServingEngine(m, num_slots=2, max_len=32, overlap=True,
                          fuse_steps=4)
    out_f, rids_f = _drive(fused, subs)
    assert fused._fused_fns
    for a, b in zip(rids_s, rids_f):
        np.testing.assert_array_equal(out_s[a], out_f[b])


def test_arrival_mid_fused_run_breaks_quiescence_and_matches(
        memorized_lm):
    """A request arriving while fused windows run: the next iteration
    sees the queue, falls back to single-step, admits, and rejoins
    fused later — all streams still equal generate()."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=40, overlap=True,
                        fuse_steps=4)
    r0 = eng.submit(PATTERN[:5], 20)
    for _ in range(6):                     # into fused steady state
        eng.step()
    r1 = eng.submit(PATTERN[:4], 10)
    out = eng.run(max_steps=2000)
    np.testing.assert_array_equal(
        out[r0], generate(m, PATTERN[None, :5], 20, temperature=0.0)[0])
    np.testing.assert_array_equal(
        out[r1], generate(m, PATTERN[None, :4], 10, temperature=0.0)[0])


def test_preemption_during_fused_run_falls_back_and_rejoins(
        memorized_lm):
    """Paged fuse engine under page pressure: funding a window (or an
    admission) preempts a stream mid-run — the engine must fall back
    to single-step, resume the victim via recompute prefill, and BOTH
    streams stay token-identical to generate()."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, page_len=4,
                        num_pages=8, prefix_cache=False, overlap=True,
                        fuse_steps=4)
    r0 = eng.submit(PATTERN[:5], 16)
    eng.step()
    eng.step()
    r1 = eng.submit(PATTERN[:6], 15)
    out = eng.run(max_steps=2000)
    assert eng.metrics.requests_preempted >= 1
    assert eng._fused_fns, "fused window never engaged"
    np.testing.assert_array_equal(
        out[r0], generate(m, PATTERN[None, :5], 16, temperature=0.0)[0])
    np.testing.assert_array_equal(
        out[r1], generate(m, PATTERN[None, :6], 15, temperature=0.0)[0])


def test_fault_inside_fused_run_is_retryable(memorized_lm):
    """``serving.decode`` fault injection while fused windows run: the
    chaos hook fires BEFORE the iteration mutates state, so step()
    raises, the next step() retries wholesale, and the final output is
    unaffected."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=1, max_len=40, overlap=True,
                        fuse_steps=4)
    rid = eng.submit(PATTERN[:5], 20)
    for _ in range(4):                     # past prefill, into fused
        eng.step()
    faults.inject("serving.decode", nth=1)
    with pytest.raises(InjectedFault):
        eng.step()
    out = eng.run(max_steps=2000)
    np.testing.assert_array_equal(
        out[rid], generate(m, PATTERN[None, :5], 20, temperature=0.0)[0])


def test_fuse_steps_validation(memorized_lm):
    with pytest.raises(ValueError, match="fuse_steps"):
        ServingEngine(memorized_lm, num_slots=1, max_len=16,
                      fuse_steps=-1)


# --- pipeline flush points ---------------------------------------------------


def test_cancel_mid_flight_lands_inflight_tokens(memorized_lm):
    """cancel() drains the pipeline first: the returned request holds
    every token generated up to the cancel, a prefix of generate()."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=1, max_len=32, overlap=True)
    rid = eng.submit(PATTERN[:5], 16)
    for _ in range(6):
        eng.step()
    req = eng.cancel(rid)
    got = np.asarray(req.tokens)
    ref = generate(m, PATTERN[None, :5], 16, temperature=0.0)[0]
    assert len(got) > len(PATTERN[:5])     # some decode landed
    np.testing.assert_array_equal(got, np.asarray(ref)[:len(got)])


def test_metrics_window_swap_drains_deferred_host_work(memorized_lm):
    """Swapping the metrics window mid-flight (the reporting-interval
    pattern) flushes the pipeline and the deferred buffers into the
    OLD window: token counts across windows sum to exactly the tokens
    generated, none lost or double-counted."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, overlap=True)
    r0 = eng.submit(PATTERN[:5], 12)
    for _ in range(5):
        eng.step()
    w0 = eng.metrics
    eng.metrics = ServingMetrics()
    out = eng.run(max_steps=2000)
    w1 = eng.metrics

    def toks(w):
        return sum(a[0] for a in w._decode_agg.values())

    # 12 budgeted: 1 from prefill + 11 decode, split across windows
    assert toks(w0) + toks(w1) == 11
    assert toks(w0) > 0 and toks(w1) > 0
    assert len(out[r0]) == len(PATTERN[:5]) + 12


def test_tracer_decode_ticks_exact_under_deferred_cadence(memorized_lm):
    """The deferred on_decode_batch cadence keeps per-request decode
    tick TOTALS exact: one tick per emitted token (the first token is
    the prefill's), same as the synchronous per-iteration path."""
    m = memorized_lm
    for kw in (dict(overlap=False), dict(overlap=True),
               dict(overlap=True, fuse_steps=4)):
        eng = ServingEngine(m, num_slots=2, max_len=32, **kw)
        out, rids = _drive(eng, [
            dict(prompt=PATTERN[:5], max_new_tokens=10),
            dict(prompt=PATTERN[:4], max_new_tokens=7)])
        summaries = eng.tracer.summaries()
        for rid, (p, b) in zip(rids, ((PATTERN[:5], 10),
                                      (PATTERN[:4], 7))):
            assert summaries[rid]["decode_iters"] == b - 1, kw
