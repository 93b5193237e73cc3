"""Continuous-batching serving engine (this PR): the oracle contract —
greedy outputs under iteration-level batching must be token-identical
per request to standalone ``generate()`` — plus scheduler/state-machine,
pooled-cache, per-slot-sampling, interleaved-prefill and metrics
coverage."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distkeras_tpu.models import Model, zoo
from distkeras_tpu.models.decoding import (decode_step, decode_step_slots,
                                           generate, init_cache,
                                           _resolve_head_dims)
from distkeras_tpu.serving import (FIFOScheduler, PagedKVPool,
                                   PriorityScheduler, Request,
                                   RequestState, ServingEngine,
                                   ServingMetrics)
from paged_layout import assert_same_cache, scrambled_tables, to_pages

V, S = 29, 12
PATTERN = np.array([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8])


@pytest.fixture(scope="module")
def memorized_lm(pattern_lm):
    """The shared session-scoped overfit-PATTERN LM (conftest pattern_lm): huge greedy argmax margins keep token-identity assertions robust; trained once per test session."""
    return pattern_lm


# --- the oracle: continuous batching == generate(), per request ------------


def test_oracle_staggered_arrivals_match_generate(memorized_lm):
    """Requests arriving at staggered times with mixed prompt lengths
    and budgets, more requests than slots (so slots recycle and the
    queue is exercised): every request's greedy tokens must equal its
    own standalone generate() call."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=3, max_len=32)
    prompts = [PATTERN[:4], PATTERN[:6], PATTERN[:3], PATTERN[:5],
               PATTERN[:4], PATTERN[:7]]
    budgets = [7, 5, 9, 6, 8, 4]
    rids = [eng.submit(prompts[i], budgets[i]) for i in range(2)]
    eng.step()
    eng.step()                     # in-flight work before later arrivals
    rids += [eng.submit(prompts[i], budgets[i]) for i in range(2, 6)]
    out = eng.run(max_steps=500)
    assert sorted(out) == sorted(rids)
    for i, rid in enumerate(rids):
        ref = generate(m, prompts[i][None], max_new_tokens=budgets[i],
                       temperature=0.0)
        np.testing.assert_array_equal(out[rid], ref[0])


def test_oracle_chunked_prefill_matches_generate(memorized_lm):
    """The interleaved chunked prefill must hand decode the same cache
    the one-shot path builds: greedy tokens equal generate() with the
    matching prefill_chunk (prompt not a multiple of the chunk)."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=48, prefill_chunk=4)
    prompt = np.tile(PATTERN, 3)[:26]
    rid = eng.submit(prompt, 6)
    out = eng.run(max_steps=300)
    ref = generate(m, prompt[None], max_new_tokens=6, temperature=0.0,
                   prefill_chunk=4)
    np.testing.assert_array_equal(out[rid], ref[0])


def test_oracle_int8_pooled_cache_matches_generate(memorized_lm):
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, cache_dtype="int8")
    rid = eng.submit(PATTERN[:4], 7)
    out = eng.run(max_steps=300)
    ref = generate(m, PATTERN[None, :4], max_new_tokens=7,
                   temperature=0.0, cache_dtype="int8")
    np.testing.assert_array_equal(out[rid], ref[0])


def test_stop_token_frees_slot_early(memorized_lm):
    """A stop-token request releases its slot before max_new_tokens;
    the engine result ends AT the stop token (no padding — unlike
    generate()'s static-shape tail fill)."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=1, max_len=32)
    rid = eng.submit(PATTERN[:4], 7, stop_token=9)     # pattern hits 9
    out = eng.run(max_steps=300)
    ref = generate(m, PATTERN[None, :4], max_new_tokens=7,
                   temperature=0.0, stop_token=9)
    got = out[rid]
    assert got[-1] == 9 and len(got) < 4 + 7
    np.testing.assert_array_equal(got, ref[0, :len(got)])
    # the tail generate() padded must be exactly the stop token — the
    # engine simply does not emit it
    assert (ref[0, len(got):] == 9).all()


def test_heterogeneous_sampling_coexists(memorized_lm):
    """Per-slot sampling state: a greedy request sharing the batch with
    sampled neighbours must produce exactly its solo-greedy tokens, and
    a sampled request must be reproducible from its seed regardless of
    neighbours."""
    m = memorized_lm

    def run_engine(extra_first):
        eng = ServingEngine(m, num_slots=3, max_len=32)
        if extra_first:
            eng.submit(PATTERN[:3], 8, temperature=1.3, top_k=4, seed=11)
        g = eng.submit(PATTERN[:4], 7)                   # greedy
        s = eng.submit(PATTERN[:5], 6, temperature=0.9, top_p=0.95,
                       seed=5)
        out = eng.run(max_steps=500)
        return out[g], out[s]

    greedy_a, sampled_a = run_engine(extra_first=False)
    greedy_b, sampled_b = run_engine(extra_first=True)
    ref = generate(m, PATTERN[None, :4], max_new_tokens=7,
                   temperature=0.0)
    np.testing.assert_array_equal(greedy_a, ref[0])
    np.testing.assert_array_equal(greedy_b, ref[0])
    # per-slot PRNG keys: the sampled request's draws depend only on its
    # own seed, not on which neighbours shared the batch
    np.testing.assert_array_equal(sampled_a, sampled_b)
    assert (sampled_a[5:] < V).all() and (sampled_a[5:] >= 0).all()


def test_long_prefill_does_not_stall_inflight_decode(memorized_lm):
    """The scheduling property chunked prefill exists for: while a long
    prompt ingests chunk-by-chunk, an already-decoding request keeps
    emitting tokens every iteration."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=64, prefill_chunk=2)
    fast = eng.submit(PATTERN[:3], 20)
    while not eng.scheduler.running:                     # fast decoding
        eng.step()
    tokens_before = len(eng[fast].generated)
    slow = eng.submit(np.tile(PATTERN, 3)[:24], 4)       # 12 chunks
    for _ in range(6):                                   # mid-prefill
        eng.step()
    assert eng[slow].state is RequestState.PREFILLING
    assert 0 < eng[slow].prefill_pos < 24
    # the in-flight stream advanced ~1 token per iteration, not zero
    assert len(eng[fast].generated) >= tokens_before + 6
    out = eng.run(max_steps=500)
    ref = generate(m, np.tile(PATTERN, 3)[None, :24], max_new_tokens=4,
                   temperature=0.0, prefill_chunk=2)
    np.testing.assert_array_equal(out[slow], ref[0])


def test_decode_jit_compiles_once_across_requests(memorized_lm):
    """The engine's whole point: static shapes, compiled decode
    programs reused across every request mix — one argmax variant for
    all-greedy batches, one sampler variant for mixed batches, each
    traced exactly once."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=32)
    eng.submit(PATTERN[:4], 5)
    eng.run(max_steps=300)
    assert set(eng._step_fns) == {True}          # all-greedy so far
    fn = eng._step_fns[True]
    assert fn._cache_size() == 1
    eng.submit(PATTERN[:6], 7, temperature=1.0, top_k=3, seed=1)
    eng.submit(PATTERN[:2], 4, stop_token=9)
    eng.run(max_steps=300)
    assert eng._step_fns[True] is fn and fn._cache_size() == 1
    assert eng._step_fns[False]._cache_size() == 1  # mixed variant


# --- slot-level decode path -------------------------------------------------


def test_decode_step_slots_staggered_positions_match_scalar():
    """decode_step_slots at HETEROGENEOUS positions must agree with
    per-sequence scalar decode_step runs: two sequences advanced to
    different depths, stepped together with a vector t."""
    m = Model.build(
        zoo.transformer_lm(V, d_model=32, num_heads=4, num_layers=2,
                           mlp_ratio=2, use_rope=True), (S,), seed=4)
    _resolve_head_dims(m.module, m.params)
    rs = np.random.RandomState(0)
    toks = rs.randint(0, V, (2, 8)).astype(np.int32)

    # scalar oracle: advance sequence 0 to position 5, sequence 1 to 3
    caches = []
    refs = []
    for row, depth in ((0, 5), (1, 3)):
        c = init_cache(m.module, 1, S)
        logits = None
        for t in range(depth):
            logits, c = decode_step(m.module, m.params, m.state, c,
                                    jnp.asarray(toks[row:row + 1, t]), t)
        caches.append(c)
        refs.append(np.asarray(logits))

    # pooled: same per-row caches side by side, one vector-t step
    pool = [None if a is None else
            {k: jnp.concatenate([a[k], b[k]], axis=0) for k in a}
            for a, b in zip(*caches)]
    t_prev = np.array([4, 2])          # the last written positions were
    tok_prev = np.stack([toks[0, 4], toks[1, 2]])
    # re-run the LAST step of each row in pooled form to compare logits
    pool_before = [None if a is None else
                   {k: jnp.concatenate([a[k], b[k]], axis=0) for k in a}
                   for a, b in zip(*[
                       _advance(m, toks[r:r + 1], d - 1)
                       for r, d in ((0, 5), (1, 3))])]
    logits, _ = decode_step_slots(m.module, m.params, m.state,
                                  pool_before, jnp.asarray(tok_prev),
                                  jnp.asarray(t_prev))
    np.testing.assert_allclose(np.asarray(logits),
                               np.concatenate(refs, axis=0), atol=2e-5)


def _advance(m, row_toks, depth, cap=S, dtype=jnp.float32):
    """Scalar-decode a single row ``depth`` steps; returns its cache."""
    c = init_cache(m.module, 1, cap, dtype)
    for t in range(depth):
        _, c = decode_step(m.module, m.params, m.state, c,
                           jnp.asarray(row_toks[:, t]), t)
    return c


def test_decode_step_slots_sentinel_t_writes_nothing():
    """A slot whose t is out of range (the engine's free-slot sentinel)
    must not touch the cache — the one-hot write misses everywhere."""
    m = Model.build(
        zoo.transformer_lm(V, d_model=16, num_heads=2, num_layers=1,
                           mlp_ratio=2, use_rope=True), (S,), seed=0)
    _resolve_head_dims(m.module, m.params)
    cache = init_cache(m.module, 2, S)
    kv0 = next(c for c in cache if c is not None)
    before = np.array(kv0["k"])
    _, cache2 = decode_step_slots(
        m.module, m.params, m.state, cache,
        jnp.asarray([3, 5], jnp.int32), jnp.asarray([S, S], jnp.int32))
    kv1 = next(c for c in cache2 if c is not None)
    np.testing.assert_array_equal(np.asarray(kv1["k"]), before)


def test_prefill_program_cache_is_lru_capped(memorized_lm):
    """Varied prompt lengths each compile their own ragged-tail prefill
    program; the engine must bound how many it retains."""
    eng = ServingEngine(memorized_lm, num_slots=1, max_len=32)
    eng.MAX_PREFILL_PROGRAMS = 3
    for n in (2, 3, 4, 5, 6):                  # 5 distinct lengths
        eng.submit(PATTERN[:n], 2)
        eng.run(max_steps=200)
    assert len(eng._prefill_fns) == 3
    # most-recent lengths retained (dict order = LRU order)
    assert sorted(k[0] for k in eng._prefill_fns) == [4, 5, 6]
    # reuse refreshes recency and does not recompile
    fn6 = eng._prefill_fns[(6, 0, True)]
    eng.submit(PATTERN[:6], 2)
    eng.run(max_steps=200)
    assert eng._prefill_fns[(6, 0, True)] is fn6


# --- kv pool ----------------------------------------------------------------


def test_kv_pool_rejects_capacity_beyond_position_table():
    m = Model.build(
        zoo.transformer_lm(V, d_model=16, num_heads=2, num_layers=1,
                           mlp_ratio=2, use_rope=False, max_len=16),
        (S,), seed=1)
    _resolve_head_dims(m.module, m.params)
    with pytest.raises(ValueError, match="too small"):
        PagedKVPool(m.module, num_slots=2, max_len=17, page_len=4)


# --- scheduler --------------------------------------------------------------


def _req(rid, p_len=4, budget=5, **kw):
    return Request(rid=rid, prompt=PATTERN[:p_len].copy(),
                   max_new_tokens=budget, **kw)


def test_scheduler_fifo_admission_and_slot_reuse():
    sched = FIFOScheduler(2)
    reqs = [_req(i) for i in range(4)]
    for r in reqs:
        sched.submit(r)
    admitted = sched.admit()
    assert [r.rid for r in admitted] == [0, 1]
    assert [r.slot for r in admitted] == [0, 1]        # deterministic
    assert sched.queue_depth == 2 and sched.occupied == 2
    assert not sched.admit()                           # no free slots
    # finish 0 from PREFILLING; its slot goes to request 2
    sched.release(reqs[0])
    assert reqs[0].state is RequestState.FINISHED
    assert sched.admit()[0] is reqs[2] and reqs[2].slot == 0
    # request 1 finishes from DECODING
    sched.to_decoding(reqs[1])
    assert sched.running == {1: reqs[1]}
    sched.release(reqs[1])
    assert sched.admit()[0] is reqs[3] and reqs[3].slot == 1
    assert sched.queue_depth == 0


def test_scheduler_single_prefill_stream_is_fcfs():
    sched = FIFOScheduler(3)
    reqs = [_req(i) for i in range(3)]
    for r in reqs:
        sched.submit(r)
    sched.admit()
    assert sched.next_prefill() is reqs[0]
    sched.to_decoding(reqs[0])
    assert sched.next_prefill() is reqs[1]
    with pytest.raises(AssertionError):
        sched.to_decoding(reqs[2])                     # FCFS enforced


def test_request_done_semantics():
    r = _req(0, budget=2, stop_token=9)
    assert not r.done
    r.generated.append(3)
    assert not r.done and not r.stopped
    r.generated.append(9)
    assert r.stopped and r.done
    r2 = _req(1, budget=1)
    r2.generated.append(9)                             # no stop_token set
    assert r2.done and not r2.stopped
    np.testing.assert_array_equal(r2.tokens,
                                  np.concatenate([PATTERN[:4], [9]]))


# --- engine validation ------------------------------------------------------


def test_submit_validation(memorized_lm):
    eng = ServingEngine(memorized_lm, num_slots=1, max_len=16)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(PATTERN[:10], 7)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(PATTERN[:4], 0)
    with pytest.raises(ValueError, match="top_p"):
        eng.submit(PATTERN[:4], 2, top_p=1.5)
    with pytest.raises(ValueError, match="at least one"):
        eng.submit(np.zeros((0,), np.int32), 2)


def test_engine_rejects_non_sequential():
    class Fake:
        module = object()
    with pytest.raises(TypeError, match="Sequential"):
        ServingEngine(Fake())


# --- metrics ----------------------------------------------------------------


def test_metrics_lifecycle_and_summary():
    clock = iter(np.arange(0.0, 100.0, 0.5))
    mtr = ServingMetrics(clock=lambda: float(next(clock)))
    mtr.record_submit(0)                   # t=0.0
    mtr.record_first_token(0)              # t=0.5 -> ttft 0.5
    mtr.record_iteration(queue_depth=2, occupied=1, num_slots=2)
    mtr.record_decode(n_decoding=2, dt=0.25)
    mtr.record_decode(n_decoding=1, dt=0.25)
    mtr.record_finish(0, n_generated=5)    # t=1.0 -> latency 1.0
    s = mtr.summary()
    assert s["requests_finished"] == 1
    assert s["tokens_generated"] == 5
    assert s["ttft_s"]["p50"] == pytest.approx(0.5)
    assert s["latency_s"]["p50"] == pytest.approx(1.0)
    assert s["queue_depth"]["max"] == 2
    assert s["slot_occupancy"]["mean"] == pytest.approx(0.5)
    # all-iterations marginal decode rate: 3 tokens / 0.5 s
    assert s["decode_tokens_per_sec"] == pytest.approx(6.0)
    # full-occupancy steady state: 2 tokens / 0.25 s
    assert mtr.decode_tokens_per_sec(min_occupancy=2) \
        == pytest.approx(8.0)


# --- paged KV cache ---------------------------------------------------------
#
# The default engine layout since the paged-cache PR: every oracle test
# above already runs through the paged data plane (page_len 16 covers
# those short prompts in one page). The tests below force multi-page
# requests, prefix sharing, copy-on-write and preemption explicitly.


def test_paged_small_pages_oracle_matches_generate(memorized_lm):
    """Pages far smaller than the prompt (crossing mid-prompt and
    mid-decode): greedy tokens equal standalone generate()."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=3, max_len=32, page_len=4)
    prompts = [PATTERN[:5], PATTERN[:7], PATTERN[:3], PATTERN[:6]]
    budgets = [9, 5, 8, 7]
    rids = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
    out = eng.run(max_steps=500)
    for i, rid in enumerate(rids):
        ref = generate(m, prompts[i][None], max_new_tokens=budgets[i],
                       temperature=0.0)
        np.testing.assert_array_equal(out[rid], ref[0])


def test_paged_int8_cache_shares_tables_with_scales(memorized_lm):
    """int8 quantized cache x paged pool: payload AND scale planes move
    through the same page tables — token-identical to generate() with
    the int8 cache, across page boundaries."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, cache_dtype="int8",
                        page_len=4)
    prompt = np.tile(PATTERN, 2)[:13]
    rid = eng.submit(prompt, 7)
    rid2 = eng.submit(PATTERN[:5], 6)
    out = eng.run(max_steps=300)
    ref = generate(m, prompt[None], max_new_tokens=7, temperature=0.0,
                   cache_dtype="int8")
    np.testing.assert_array_equal(out[rid], ref[0])
    ref2 = generate(m, PATTERN[None, :5], max_new_tokens=6,
                    temperature=0.0, cache_dtype="int8")
    np.testing.assert_array_equal(out[rid2], ref2[0])


@pytest.mark.parametrize("page_len", [4, 8])
@pytest.mark.parametrize("cache_dtype", ["float32", "int8"])
def test_decode_step_slots_paged_matches_contiguous_logits(cache_dtype,
                                                           page_len):
    """The paged decode step over scattered physical pages against its
    reference, ``decode_step_slots`` on the same values in one
    contiguous cache: the same logits, and the same cache in logical
    order after the step's write (payload and, for int8, scales)."""
    from distkeras_tpu.models.decoding import decode_step_slots_paged
    L = 16
    m = Model.build(
        zoo.transformer_lm(V, d_model=32, num_heads=4, num_layers=2,
                           mlp_ratio=2, use_rope=True), (L,), seed=4)
    _resolve_head_dims(m.module, m.params)
    rs = np.random.RandomState(1)
    toks = rs.randint(0, V, (2, 8)).astype(np.int32)
    contiguous = [None if a is None else
                  {k: jnp.concatenate([a[k], b[k]], axis=0) for k in a}
                  for a, b in zip(
                      _advance(m, toks[0:1], 4, L, cache_dtype),
                      _advance(m, toks[1:2], 2, L, cache_dtype))]
    tables, n_pages = scrambled_tables(2, L // page_len, seed=3)
    paged = to_pages(contiguous, tables, page_len, n_pages)
    tok = jnp.asarray(np.stack([toks[0, 4], toks[1, 2]]))
    t = jnp.asarray(np.array([4, 2], np.int32))
    ref_logits, ref_cache = decode_step_slots(
        m.module, m.params, m.state, contiguous, tok, t)
    got_logits, got_cache = decode_step_slots_paged(
        m.module, m.params, m.state, paged, tok,
        t, jnp.asarray(tables), page_len)
    np.testing.assert_allclose(np.asarray(got_logits),
                               np.asarray(ref_logits), atol=1e-5)
    assert_same_cache(ref_cache, got_cache, tables)


def test_prefix_sharing_skips_prefill_and_matches_generate(memorized_lm):
    """A second request with an identical prompt reuses the first's
    registered pages: its prefill runs a single ragged chunk (the
    recomputed final position), the hit counters move, and both
    outputs equal standalone generate()."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=48, page_len=4,
                        prefill_chunk=4)
    prompt = np.tile(PATTERN, 2)[:12]            # 3 full pages
    r0 = eng.submit(prompt, 5)
    out0 = eng.run(max_steps=300)
    chunks_before = eng.metrics.prefill_chunks
    r1 = eng.submit(prompt, 5)
    out1 = eng.run(max_steps=300)
    ref = generate(m, prompt[None], max_new_tokens=5, temperature=0.0,
                   prefill_chunk=4)
    np.testing.assert_array_equal(out0[r0], ref[0])
    np.testing.assert_array_equal(out1[r1], ref[0])
    s = eng.metrics.summary()
    assert s["prefix_cache"]["hits"] == 1        # r1 hit, r0 missed
    assert s["prefix_cache"]["hit_rate"] > 0.4
    # 11 of r1's 12 prompt positions came off shared pages: one chunk
    # (position 11) vs r0's three
    assert eng.metrics.prefill_chunks - chunks_before == 1


def test_prefix_partial_page_copy_on_write(memorized_lm):
    """A prompt that diverges INSIDE a cached page: the matched head of
    the donor page is reused (copy-on-write into the new request's
    private page), the divergent tail is recomputed, and the donor's
    original content stays valid for its own chain."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=48, page_len=4)
    a = np.tile(PATTERN, 2)[:12]                 # 3 full cached pages
    b = a.copy()
    b[10] = (a[10] + 1) % V                      # diverge inside page 2
    ra = eng.submit(a, 5)
    out_a = eng.run(max_steps=300)
    rb = eng.submit(b, 5)
    out_b = eng.run(max_steps=300)
    # b shared a's two full pages + two tokens of page 2 via the donor
    assert eng.metrics.summary()["prefix_cache"]["hits"] == 1
    tl = [t for t in eng.tracer.timelines() if t.rid == rb][0]
    assert tl.prefix_hit_tokens == 10            # 8 full + 2 donor
    np.testing.assert_array_equal(
        out_a[ra], generate(m, a[None], 5, temperature=0.0)[0])
    np.testing.assert_array_equal(
        out_b[rb], generate(m, b[None], 5, temperature=0.0)[0])
    # the donor chain is uncorrupted: a re-run of prompt a (full hit
    # on its own pages now) still matches
    ra2 = eng.submit(a, 5)
    out_a2 = eng.run(max_steps=300)
    np.testing.assert_array_equal(
        out_a2[ra2], generate(m, a[None], 5, temperature=0.0)[0])


@pytest.mark.parametrize("host_pages", [0, 16])
def test_preemption_resume_token_identity(memorized_lm, host_pages):
    """Two streams outgrow a deliberately small page pool: the younger
    is preempted mid-decode, resumes — via the recompute prefill
    (``host_pages=0``) or the host-page SWAP (offload PR: D2H at
    eviction, H2D + table restore at re-admission, no re-prefill) —
    and BOTH stay token-identical to standalone generate() — the
    acceptance bar for preemption correctness. Staggered arrivals."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, page_len=4,
                        num_pages=8, prefix_cache=False,
                        host_kv_pages=host_pages)
    r0 = eng.submit(PATTERN[:5], 16)
    eng.step()
    eng.step()
    r1 = eng.submit(PATTERN[:6], 15)
    out = eng.run(max_steps=2000)
    assert eng.metrics.requests_preempted >= 1
    assert eng.metrics.summary()["requests_preempted"] >= 1
    if host_pages:
        # the victim's resume really was a page swap, not a re-prefill
        assert eng.pool.pages_offloaded >= 1
        assert eng.pool.pages_restored == eng.pool.pages_offloaded
        off = eng.metrics.summary()["offload"]
        assert off["pages_restored"] >= 1
        assert off["resume_swap_s"] is not None
        assert off["reprefill_tokens_avoided"] > 0
    np.testing.assert_array_equal(
        out[r0], generate(m, PATTERN[None, :5], 16, temperature=0.0)[0])
    np.testing.assert_array_equal(
        out[r1], generate(m, PATTERN[None, :6], 15, temperature=0.0)[0])


@pytest.mark.parametrize("host_pages", [0, 16])
def test_preempted_sampled_request_resumes_key_stream(memorized_lm,
                                                      host_pages):
    """A SAMPLED request preempted mid-decode must draw the same
    tokens as under an ample page budget: its per-slot PRNG key is
    snapshotted at eviction and restored at resume, so the draw
    stream depends only on its own seed and step count. With the
    host tier on, the swap resume must be BYTE-identical too — the
    cache pages return bit-for-bit, so this also pins swap-resume ==
    re-prefill-resume == uninterrupted run (the offload acceptance
    criterion: the ample run IS the uninterrupted stream)."""
    m = memorized_lm

    def run(num_pages, host):
        eng = ServingEngine(m, num_slots=2, max_len=32, page_len=4,
                            num_pages=num_pages, prefix_cache=False,
                            host_kv_pages=host)
        eng.submit(PATTERN[:5], 16)              # greedy page hog
        srid = eng.submit(PATTERN[:4], 14, temperature=0.9,
                          top_p=0.95, seed=7)
        out = eng.run(max_steps=3000)
        return (out[srid], eng.metrics.requests_preempted,
                eng.pool.pages_offloaded)

    ample, p_ample, _ = run(num_pages=16, host=0)
    tight, p_tight, offloaded = run(num_pages=8, host=host_pages)
    assert p_ample == 0 and p_tight >= 1
    assert bool(offloaded) == bool(host_pages)
    np.testing.assert_array_equal(ample, tight)


def test_offload_swap_events_and_recorder(memorized_lm):
    """The swap lifecycle is observable: swap_out/swap_in timeline
    events on the preempted request, the iteration ring carries the
    host-pool occupancy, and health() exposes the host tier."""
    from distkeras_tpu.obs.recorder import get_recorder, reset_recorder
    m = memorized_lm
    reset_recorder()
    try:
        eng = ServingEngine(m, num_slots=2, max_len=32, page_len=4,
                            num_pages=8, prefix_cache=False,
                            host_kv_pages=16)
        eng.submit(PATTERN[:5], 16)
        eng.step()
        eng.step()
        eng.submit(PATTERN[:6], 15)
        eng.run(max_steps=2000)
        assert eng.metrics.requests_preempted >= 1
        kinds = [e["name"] for t in eng.tracer.timelines()
                 for e in t.events]
        assert "swap_out" in kinds and "swap_in" in kinds
        recs = get_recorder().records()
        pre = [r for r in recs if r["kind"] == "serving.preempted"]
        assert pre and pre[0]["pages_swapped"] >= 1
        iters = [r for r in recs if r["kind"] == "serving.iteration"]
        assert any("host_pages_free" in r for r in iters)
        h = eng.health()
        assert h["pages"]["host"]["total"] == 16
        assert h["pages"]["host"]["restored"] >= 1
    finally:
        reset_recorder()


def test_prefix_cache_spills_to_host_and_restores(memorized_lm):
    """Cold prefix chains spill D2H instead of dropping: after a full
    reclaim, a same-template request still HITS the cache (the chain
    restores H2D page by page) and stays token-identical."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=48, page_len=4,
                        host_kv_pages=32)
    prompt = np.tile(PATTERN, 2)[:12]            # 3 full cached pages
    ra = eng.submit(prompt, 5)
    out_a = eng.run(max_steps=300)
    np.testing.assert_array_equal(
        out_a[ra], generate(m, prompt[None], 5, temperature=0.0)[0])
    n_nodes = len(eng.prefix)
    assert n_nodes >= 3
    # pressure: reclaim everything — with a host tier this SPILLS
    # (nodes stay matchable) rather than dropping
    freed = eng.prefix.reclaim(eng.pool.num_pages)
    assert freed >= n_nodes
    assert eng.pool.pages_offloaded >= n_nodes
    assert len(eng.prefix) == n_nodes            # chain survived
    restored_before = eng.pool.pages_restored
    rb = eng.submit(prompt, 5)
    out_b = eng.run(max_steps=300)
    assert eng.pool.pages_restored > restored_before
    assert eng.metrics.summary()["prefix_cache"]["hits"] >= 1
    np.testing.assert_array_equal(
        out_b[rb], generate(m, prompt[None], 5, temperature=0.0)[0])


def test_transfer_of_swapped_queued_request_drops_swap(memorized_lm):
    """Review fix: a QUEUED preempted-and-swapped request leaving via
    transfer_out must release its host pages and shed the swap record
    — the record names the SOURCE engine's host pool, which the
    adopting engine cannot read (a stale one would restore garbage
    or raise on a host-less target). The handoff then rides the
    re-prefill resume, token-identical."""
    m = memorized_lm
    src = ServingEngine(m, num_slots=1, max_len=32, page_len=4,
                        num_pages=8, prefix_cache=False,
                        host_kv_pages=16)
    rid = src.submit(PATTERN[:5], 10)
    # bring it to DECODING, then preempt via a higher-priority arrival
    while src.scheduler.running.get(0) is None \
            or src.scheduler.running[0].rid != rid:
        src.step()
    for _ in range(2):
        src.step()
    req = src[rid]
    src._preempt(req)
    assert req._swap is not None and src.pool.host_free_pages < 16
    out = src.transfer_out(rid)
    assert out is req and req._swap is None
    assert src.pool.host_free_pages == 16      # host pages released
    dst = ServingEngine(m, num_slots=1, max_len=32, page_len=4)
    new_rid = dst.transfer_in(req)
    res = dst.run(max_steps=500)
    np.testing.assert_array_equal(
        res[new_rid],
        generate(m, PATTERN[None, :5], 10, temperature=0.0)[0])


def test_pool_offload_roundtrip_and_host_accounting(memorized_lm):
    """PagedKVPool host-tier unit contract: D2H/H2D round trip is
    byte-identical, capacity exhaustion returns None (callers fall
    back to discard), and host double-free is loud."""
    from distkeras_tpu.serving import PagedKVPool
    m = memorized_lm
    pool = PagedKVPool(m.module, num_slots=2, max_len=32, page_len=4,
                       host_pages=3)
    # write recognizable content into pages 0..2 via direct scatter
    rs = np.random.RandomState(0)
    pool.cache = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rs.randn(*a.shape).astype(a.dtype)),
        pool.cache)
    before = jax.tree_util.tree_map(np.asarray, pool.cache)
    hids = pool.offload_pages([0, 2])
    assert hids is not None and len(hids) == 2
    assert pool.host_free_pages == 1
    assert pool.offload_pages([0, 1]) is None    # capacity: only 1 left
    # scramble the device pages, then restore onto different ids
    pool.cache = jax.tree_util.tree_map(jnp.zeros_like, pool.cache)
    pool.restore_pages(hids, [5, 7])
    after = jax.tree_util.tree_map(np.asarray, pool.cache)
    for b, a in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        np.testing.assert_array_equal(b[0], a[5])
        np.testing.assert_array_equal(b[2], a[7])
    pool.free_host(hids)
    assert pool.host_free_pages == 3
    with pytest.raises(RuntimeError, match="double-freed"):
        pool.free_host([hids[0]])
    assert pool.pages_offloaded == 2 and pool.pages_restored == 2
    assert pool.offload_bytes > 0


def test_priority_scheduler_order_and_preempt():
    sched = PriorityScheduler(2)
    reqs = [_req(0, priority=2), _req(1, priority=0),
            _req(2, priority=1)]
    for r in reqs:
        sched.submit(r)
    assert sched.peek() is reqs[1]               # class before arrival
    admitted = sched.admit()
    assert [r.rid for r in admitted] == [1, 2]
    sched.to_decoding(reqs[1])
    sched.preempt(reqs[1])
    assert reqs[1].state is RequestState.QUEUED
    assert reqs[1].slot is None and reqs[1].n_preempted == 1
    # preempted requests resume ahead of their class peers
    sched.submit(_req(3, priority=0))
    assert sched.peek() is reqs[1]
    # PREFILLING requests are preemptable too (they hold budget pages)
    sched.preempt(reqs[2])
    assert reqs[2].state is RequestState.QUEUED and reqs[2].slot is None
    with pytest.raises(RuntimeError, match="preempt"):
        sched.preempt(reqs[0])                   # QUEUED: holds nothing


def test_engine_priority_admission_preempts_lower_class(memorized_lm):
    """A priority-0 arrival that cannot fit the page budget preempts a
    decoding batch-class stream; both finish token-identically."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, page_len=4,
                        num_pages=4, prefix_cache=False)
    low = eng.submit(PATTERN[:9], 6, priority=2)   # 3 admission pages
    while not eng.scheduler.running:
        eng.step()
    high = eng.submit(PATTERN[:6], 4, priority=0)  # needs 2, 1 free
    out = eng.run(max_steps=2000)
    assert eng.metrics.requests_preempted >= 1
    np.testing.assert_array_equal(
        out[low], generate(m, PATTERN[None, :9], 6, temperature=0.0)[0])
    np.testing.assert_array_equal(
        out[high], generate(m, PATTERN[None, :6], 4, temperature=0.0)[0])


def test_paged_pool_refcounts_and_partial_insert():
    """PagedKVPool unit contract: alloc/incref/decref accounting,
    release returns pages, and insert touches ONLY the pages the
    prompt fills."""
    m = Model.build(
        zoo.transformer_lm(V, d_model=16, num_heads=2, num_layers=2,
                           mlp_ratio=2, use_rope=True), (S,), seed=1)
    _resolve_head_dims(m.module, m.params)
    pool = PagedKVPool(m.module, num_slots=2, max_len=12, page_len=4)
    assert pool.num_pages == 6 and pool.free_pages == 6
    pool.cache = jax.tree_util.tree_map(
        lambda x: jnp.full_like(x, 9.0), pool.cache)
    staging = jax.tree_util.tree_map(
        lambda x: jnp.full_like(x, 7.0), pool.make_request_cache())
    p0, p1 = pool.alloc_page(), pool.alloc_page()
    pool.assign(0, 0, p0)
    pool.assign(0, 1, p1)
    assert pool.free_pages == 4
    # 6 positions -> exactly 2 pages written; the other 4 untouched
    pool.insert_pages(staging, 0, skip_pages=0, n_pos=6)
    for layer in pool.cache:
        if layer is None:
            continue
        arr = np.asarray(layer["k"])
        for pid in range(pool.num_pages):
            want = 7.0 if pid in (p0, p1) else 9.0
            assert (arr[pid] == want).all(), pid
    # sharing: second holder keeps the page alive past one release
    pool.incref(p0)
    assert pool.shared_pages == 1
    assert pool.release_slot(0) == 2
    assert pool.free_pages == 5                  # p1 freed, p0 held
    pool.decref(p0)
    assert pool.free_pages == 6
    with pytest.raises(RuntimeError, match="refcount"):
        pool.decref(p1)


def test_page_metrics_summary_and_health(memorized_lm):
    """Satellite: page-accounting gauges + prefix hit counters land in
    summary() and health(), with or without a prefix cache."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, page_len=4)
    eng.submit(np.tile(PATTERN, 2)[:9], 5)
    eng.submit(np.tile(PATTERN, 2)[:9], 5)
    eng.run(max_steps=500)
    s = eng.metrics.summary()
    assert s["pages"] is not None
    assert s["pages"]["free"] == eng.pool.free_pages
    assert 0.0 <= s["pages"]["fragmentation"] <= 1.0
    assert s["prefix_cache"]["lookups"] == 2
    h = eng.health()
    assert h["pages"]["total"] == eng.pool.num_pages
    assert h["pages"]["page_len"] == 4
    assert h["prefix_cache"]["nodes"] == len(eng.prefix)
    assert h["requests"]["preempted"] == 0
    bare = ServingEngine(m, num_slots=1, max_len=16, prefix_cache=False)
    assert bare.metrics.summary()["pages"] is None   # no iteration yet
    bare.submit(PATTERN[:4], 3)
    bare.run(max_steps=200)
    assert bare.metrics.summary()["pages"]["free"] == bare.pool.num_pages
    h = bare.health()
    assert h["pages"]["free"] == bare.pool.num_pages
    assert h["prefix_cache"] is None


def test_preemption_lands_in_flight_recorder(memorized_lm):
    """Satellite: iteration records carry the free-page count and
    preemptions write their own record — admission stalls are
    explainable post-mortem."""
    from distkeras_tpu.obs.recorder import get_recorder, reset_recorder
    m = memorized_lm
    reset_recorder()
    try:
        eng = ServingEngine(m, num_slots=2, max_len=32, page_len=4,
                            num_pages=8, prefix_cache=False)
        eng.submit(PATTERN[:5], 16)
        eng.submit(PATTERN[:6], 15)
        eng.run(max_steps=2000)
        assert eng.metrics.requests_preempted >= 1
        recs = get_recorder().records()
        iters = [r for r in recs if r["kind"] == "serving.iteration"]
        assert iters and all("pages_free" in r for r in iters)
        pre = [r for r in recs if r["kind"] == "serving.preempted"]
        assert pre and {"rid", "slot", "pages_freed",
                        "pages_free"} <= set(pre[0])
    finally:
        reset_recorder()


def test_prefilling_hog_is_preemptable_not_deadlock(memorized_lm):
    """Review fix: pages held by a MID-PREFILL request are page-budget
    holders too — a decoding stream that outgrows the pool preempts
    the prefilling hog instead of crashing the serve loop with 'page
    pool exhausted'."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=24, page_len=4,
                        num_pages=6, prefill_chunk=2,
                        prefix_cache=False)
    a = eng.submit(PATTERN[:4], 20)              # worst 6 pages == pool
    while not eng.scheduler.running:
        eng.step()
    b = eng.submit(np.tile(PATTERN, 2)[:13], 4)  # 4 admission pages
    out = eng.run(max_steps=3000)
    assert eng.metrics.requests_preempted >= 1
    np.testing.assert_array_equal(
        out[a], generate(m, PATTERN[None, :4], 20, temperature=0.0)[0])
    np.testing.assert_array_equal(
        out[b], generate(m, np.tile(PATTERN, 2)[None, :13], 4,
                         temperature=0.0)[0])


def test_growth_preemption_never_evicts_higher_priority(memorized_lm):
    """Review fix: when a LOW-priority stream outgrows the pool and
    the only other stream is higher-priority, the low stream preempts
    ITSELF — growing it at the interactive stream's expense would
    invert the promised priority."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=20, page_len=4,
                        num_pages=7, prefix_cache=False)
    hi = eng.submit(PATTERN[:5], 10, priority=0)
    lo = eng.submit(PATTERN[:5], 10, priority=2)
    done = {}
    steps = 0
    while eng.scheduler.pending:
        for r in eng.step():
            done[r.rid] = r
        steps += 1
        assert steps < 3000
    assert done[hi].n_preempted == 0
    assert done[lo].n_preempted >= 1
    ref = generate(m, PATTERN[None, :5], 10, temperature=0.0)
    np.testing.assert_array_equal(done[hi].tokens, ref[0])
    np.testing.assert_array_equal(done[lo].tokens, ref[0])


def test_unfundable_admission_preserves_prefix_cache(memorized_lm):
    """Review fix: an admission whose page deficit exceeds free +
    evictable must NOT drain the prefix cache on the way to failing —
    later same-template requests would lose all sharing for nothing."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=24, page_len=4,
                        num_pages=6)
    big_prompt = np.tile(PATTERN, 2)[:17]
    r0 = eng.submit(np.tile(PATTERN, 2)[:9], 3)  # registers 2 pages
    out0 = eng.run(max_steps=300)
    assert len(eng.prefix) == 2
    hog = eng.submit(PATTERN[:4], 19)            # decoding page hog
    while not eng.scheduler.running:
        eng.step()
    big = eng.submit(big_prompt, 5)              # needs 5 private now
    eng.step()
    # unfundable (free 2 + evictable 2 < 5): cache must survive
    assert len(eng.prefix) == 2
    assert eng[big].state is RequestState.QUEUED
    out = eng.run(max_steps=3000)
    np.testing.assert_array_equal(
        out[big], generate(m, big_prompt[None], 5, temperature=0.0)[0])
    np.testing.assert_array_equal(
        out[hog], generate(m, PATTERN[None, :4], 19, temperature=0.0)[0])


def test_paged_submit_rejects_impossible_request(memorized_lm):
    """A request whose worst case exceeds the whole pool can never
    finish — refused at submit, not deadlocked at runtime."""
    eng = ServingEngine(memorized_lm, num_slots=2, max_len=32,
                        page_len=4, num_pages=4)
    with pytest.raises(ValueError, match="pages"):
        eng.submit(PATTERN[:8], 12)              # 5 pages > 4
    with pytest.raises(ValueError, match="num_pages"):
        PagedKVPool(memorized_lm.module, num_slots=1, max_len=32,
                    page_len=4, num_pages=0)


def test_engine_records_serving_metrics(memorized_lm):
    eng = ServingEngine(memorized_lm, num_slots=2, max_len=32,
                        prefill_chunk=4)
    rids = [eng.submit(PATTERN[:6], 5), eng.submit(PATTERN[:4], 6),
            eng.submit(PATTERN[:5], 4)]
    eng.run(max_steps=500)
    s = eng.metrics.summary()
    assert s["requests_finished"] == 3
    assert s["tokens_generated"] == 5 + 6 + 4
    assert s["ttft_s"] is not None and s["ttft_s"]["p99"] >= \
        s["ttft_s"]["p50"] >= 0
    assert s["latency_s"]["p50"] > 0
    assert s["prefill_chunks"] >= 2 + 1 + 2    # ceil(6/4)+ceil(4/4)+...
    assert s["slot_occupancy"]["max"] == 1.0   # both slots ran together
    assert s["queue_depth"]["max"] >= 1        # third request queued
    assert s["phases"]["prefill"]["count"] == s["prefill_chunks"]
    assert s["decode_tokens_per_sec"] > 0


# --- one KV layout ----------------------------------------------------------


def _refuses_a_layout_choice(m):
    """No option selects a layout, and none is accepted and ignored."""
    import distkeras_tpu.serving as serving
    for layout in ("slab", "paged"):
        with pytest.raises(TypeError, match="kv_layout"):
            ServingEngine(m, num_slots=1, max_len=16, kv_layout=layout)
    assert not hasattr(serving, "KVPool")
    eng = ServingEngine(m, num_slots=1, max_len=16)
    assert isinstance(eng.pool, PagedKVPool)
    assert isinstance(eng.scheduler, PriorityScheduler)
    assert not hasattr(eng, "kv_layout")


def _engine_cannot_reach_the_reference(m):
    """The contiguous step functions are the tests' reference: nothing
    in ``serving/engine.py`` imports or names them."""
    import ast
    import distkeras_tpu.serving.engine as engine
    tree = ast.parse(open(engine.__file__).read())
    named = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)} \
        | {n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)} \
        | {a.name for n in ast.walk(tree)
           if isinstance(n, (ast.Import, ast.ImportFrom))
           for a in n.names}
    assert "decode_step_slots_paged" in named        # the lint can see
    assert not named & {"decode_step_slots", "verify_step_slots",
                        "KVPool", "FIFOScheduler"}


@pytest.mark.parametrize("check", [_refuses_a_layout_choice,
                                   _engine_cannot_reach_the_reference],
                         ids=["behaviour", "imports"])
def test_engine_has_one_kv_layout(memorized_lm, check):
    check(memorized_lm)
