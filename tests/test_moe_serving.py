"""MoE-native serving (MoE-serving PR): the dispatched decode path's
token-identity oracles against dense-routing ``generate()`` — int8
cache, speculative verify windows, preempt/resume —
plus the drop-free ``MoE.decode_apply`` unit contract, shard_map
expert-parallel decode on the 8-device CPU mesh, expert-load telemetry
and the MoE-aware admission headroom."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from distkeras_tpu.models import Model, zoo
from distkeras_tpu.models.decoding import (decode_step_slots,
                                           decode_step_slots_paged,
                                           generate, init_cache,
                                           _resolve_head_dims)
from distkeras_tpu.models.moe import MoE
from distkeras_tpu.ops import moe_kernels
from distkeras_tpu.serving import (NgramDraft, Request, ServingEngine,
                                   ServingMetrics)
from paged_layout import assert_same_cache, scrambled_tables, to_pages

V, S = 29, 12
PATTERN = np.array([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8])


def _moe_lm(expert_axis=None, seed=2):
    """2-layer all-MoE LM, dense dispatch (the oracle semantics for
    generate(); the ENGINE's decode dispatch is its own knob)."""
    return Model.build(
        zoo.transformer_lm(V, d_model=32, num_heads=4, num_layers=2,
                           mlp_ratio=2, use_rope=True, moe_every=1,
                           num_experts=8, moe_expert_axis=expert_axis),
        (S,), seed=seed)


@pytest.fixture(scope="module")
def memorized_moe_lm(pattern_moe_lm):
    """The shared session-scoped all-MoE overfit-PATTERN LM
    (conftest pattern_moe_lm); trained once per session."""
    return pattern_moe_lm


# --- MoE.decode_apply unit contract -----------------------------------------


@pytest.mark.parametrize("top_k", [1, 2, 4])
@pytest.mark.parametrize("path", ["tokens", "fused"])
def test_decode_apply_matches_dense_routing(top_k, path):
    """The decode-specialized dispatch equals dense routing (same
    router, drop-free capacity) on both execution paths — the XLA
    tokens floor and the Pallas kernel (interpreter on CPU)."""
    e, d = 8, 16
    moe = MoE(e, 32, top_k=top_k)
    params, _, _ = moe.init(jax.random.PRNGKey(0), (4, d))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, d))
    ref, _ = moe.apply(params, {}, x)
    ctx = (moe_kernels.force_interpret() if path == "fused"
           else __import__("contextlib").nullcontext())
    with ctx:
        out = moe.decode_apply(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)


def test_decode_apply_drop_free_under_concentrated_routing():
    """Adversarial routing: a gate that sends EVERY token to one
    expert. The training-capacity dispatch would drop most slots; the
    decode dispatch (capacity = token count) must still equal dense
    routing exactly — the drop-free-by-construction contract."""
    e, d = 4, 8
    moe = MoE(e, 16, top_k=2)
    params, _, _ = moe.init(jax.random.PRNGKey(2), (4, d))
    gate = np.zeros((d, e), np.float32)
    gate[:, 0] = 50.0                      # expert 0 wins every token
    gate[:, 1] = 25.0                      # expert 1 is every 2nd choice
    params = dict(params, gate=jnp.asarray(gate))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 6, d))
    ref, _ = moe.apply(params, {}, x)
    out = moe.decode_apply(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)
    # the training-capacity path DOES diverge here (drops), which is
    # exactly why decode must not use it
    droppy = MoE(e, 16, top_k=2, dispatch="tokens", capacity_factor=1.0)
    out_droppy, _ = droppy.apply(params, {}, x)
    assert not np.allclose(np.asarray(out_droppy), np.asarray(ref))


def test_decode_apply_routing_stats_shapes():
    moe = MoE(8, 32, top_k=2)
    params, _, _ = moe.init(jax.random.PRNGKey(4), (4, 16))
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 5, 16))
    out, (topi, full) = moe.decode_apply(params, x, return_routing=True)
    assert out.shape == (3, 5, 16)
    assert topi.shape == (3, 5, 2) and full.shape == (3, 5, 8)


# --- engine oracles: dispatched decode == dense-routing generate() ----------


def test_oracle_paged_staggered_arrivals(memorized_moe_lm):
    """Dispatched MoE decode through the paged engine under staggered
    arrivals with slot reuse: every request token-identical to its own
    dense-routing generate() call."""
    m = memorized_moe_lm
    eng = ServingEngine(m, num_slots=3, max_len=32)
    assert eng.moe_decode == "dispatched" and len(eng._moe) == 2
    prompts = [PATTERN[:4], PATTERN[:6], PATTERN[:3], PATTERN[:5]]
    budgets = [7, 5, 9, 6]
    rids = [eng.submit(prompts[i], budgets[i]) for i in range(2)]
    eng.step()
    eng.step()
    rids += [eng.submit(prompts[i], budgets[i]) for i in range(2, 4)]
    out = eng.run(max_steps=500)
    for i, rid in enumerate(rids):
        ref = generate(m, prompts[i][None], max_new_tokens=budgets[i],
                       temperature=0.0)
        np.testing.assert_array_equal(out[rid], ref[0])


def test_oracle_int8_cache(memorized_moe_lm):
    m = memorized_moe_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, cache_dtype="int8")
    rid = eng.submit(PATTERN[:4], 7)
    out = eng.run(max_steps=300)
    ref = generate(m, PATTERN[None, :4], max_new_tokens=7,
                   temperature=0.0, cache_dtype="int8")
    np.testing.assert_array_equal(out[rid], ref[0])


def test_dense_baseline_engine_matches_too(memorized_moe_lm):
    """The moe_decode='dense' baseline (what the serving_moe bench
    prices the dispatch against) is ALSO oracle-exact — the comparison
    is speed-only."""
    m = memorized_moe_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, moe_decode="dense")
    rid = eng.submit(PATTERN[:5], 6)
    out = eng.run(max_steps=300)
    ref = generate(m, PATTERN[None, :5], max_new_tokens=6,
                   temperature=0.0)
    np.testing.assert_array_equal(out[rid], ref[0])
    # the dense baseline records no MoE telemetry (generate's program)
    assert eng.metrics.summary()["moe"] is None


def test_oracle_spec_verify_window(memorized_moe_lm):
    """The [S, W] speculative verify window runs MoE blocks through the
    dispatched path (capacity = S*W) — greedy output stays
    token-identical to generate() with drafts in play."""
    m = memorized_moe_lm
    eng = ServingEngine(m, num_slots=2, max_len=48, draft=NgramDraft(),
                        spec_k=3)
    prompt = np.tile(PATTERN, 2)[:10]
    rid = eng.submit(prompt, 12)
    out = eng.run(max_steps=500)
    ref = generate(m, prompt[None], max_new_tokens=12, temperature=0.0)
    np.testing.assert_array_equal(out[rid], ref[0])
    assert eng.metrics.spec_proposed > 0


def test_oracle_preempt_resume(memorized_moe_lm):
    """Two streams outgrow a deliberately small page pool: the MoE
    model's preempted stream resumes via the recompute prefill and both
    stay token-identical to generate() — routing is batch-composition
    independent (drop-free), so eviction/resume cannot perturb it."""
    m = memorized_moe_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, page_len=4,
                        num_pages=8, prefix_cache=False)
    r0 = eng.submit(PATTERN[:5], 16)
    eng.step()
    eng.step()
    r1 = eng.submit(PATTERN[:6], 15)
    out = eng.run(max_steps=2000)
    assert eng.metrics.requests_preempted >= 1
    np.testing.assert_array_equal(
        out[r0], generate(m, PATTERN[None, :5], 16, temperature=0.0)[0])
    np.testing.assert_array_equal(
        out[r1], generate(m, PATTERN[None, :6], 15, temperature=0.0)[0])


# --- expert-parallel decode -------------------------------------------------


def test_ep_decode_matches_generate(memorized_moe_lm, devices):
    """shard_map expert-parallel decode on the 8-device CPU mesh:
    expert weights sharded E/A per device, outputs token-identical to
    the single-device dense-routing oracle."""
    m = memorized_moe_lm
    m_ep = _moe_lm(expert_axis="expert").replace(params=m.params,
                                                 state=m.state)
    mesh = Mesh(np.array(devices), ("expert",))
    eng = ServingEngine(m_ep, num_slots=2, max_len=32, ep_mesh=mesh)
    rids = [eng.submit(PATTERN[:5], 6), eng.submit(PATTERN[:4], 7)]
    out = eng.run(max_steps=500)
    for rid, p, b in zip(rids, [PATTERN[:5], PATTERN[:4]], [6, 7]):
        ref = generate(m, p[None], max_new_tokens=b, temperature=0.0)
        np.testing.assert_array_equal(out[rid], ref[0])
    assert eng.health()["moe"]["expert_parallel"] == len(devices)


def test_ep_validation(devices):
    """EP misconfiguration fails loudly at engine construction: an
    expert-axis model without a mesh (it cannot run outside shard_map),
    and a mesh without an expert-axis model."""
    mesh = Mesh(np.array(devices), ("expert",))
    with pytest.raises(ValueError, match="ep_mesh"):
        ServingEngine(_moe_lm(expert_axis="expert"), num_slots=2,
                      max_len=32)
    with pytest.raises(ValueError, match="expert_axis_name"):
        ServingEngine(_moe_lm(), num_slots=2, max_len=32, ep_mesh=mesh)
    with pytest.raises(ValueError, match="axes"):
        ServingEngine(_moe_lm(expert_axis="expert"), num_slots=2,
                      max_len=32,
                      ep_mesh=Mesh(np.array(devices), ("other",)))


def test_moe_decode_validation(memorized_moe_lm):
    with pytest.raises(ValueError, match="moe_decode"):
        ServingEngine(memorized_moe_lm, num_slots=2, max_len=32,
                      moe_decode="bogus")


# --- expert-load telemetry --------------------------------------------------


def test_moe_metrics_gauges_and_summary(memorized_moe_lm):
    m = memorized_moe_lm
    eng = ServingEngine(m, num_slots=2, max_len=32)
    eng.submit(PATTERN[:4], 8)
    eng.run(max_steps=300)
    moe = eng.metrics.summary()["moe"]
    assert moe is not None
    load = moe["expert_load"]
    assert len(load) == 8 and sum(load) > 0
    # one decode step = 2 MoE layers x live tokens x top-2 assignments
    assert moe["router_entropy"] >= 0.0
    assert 0.0 <= moe["concentration"] <= 1.0
    assert eng.health()["moe"]["decode"] == "dispatched"
    # the gauges live on the metrics registry under literal names
    reg = eng.metrics.registry.snapshot()
    assert "serving.moe_expert_load" in reg["gauges"]
    assert "serving.moe_router_entropy" in reg["gauges"]


def test_moe_route_tracer_event(memorized_moe_lm):
    """The moe_route event rides the decode-event cadence: mean
    entropy + max top-expert share since the last flush, on each
    decoding request's timeline."""
    m = memorized_moe_lm
    eng = ServingEngine(m, num_slots=2, max_len=32)
    rid = eng.submit(PATTERN[:4], 8)
    eng.run(max_steps=300)
    tl = [t for t in eng.tracer.timelines() if t.rid == rid]
    assert tl, "timeline retired"
    events = [ev for ev in tl[0].events if ev["name"] == "moe_route"]
    assert events, [ev["name"] for ev in tl[0].events]
    ev = events[0]
    assert ev["entropy"] >= 0.0 and 0.0 <= ev["top_share"] <= 1.0
    assert ev["iters"] >= 1


def test_moe_stats_survive_throttling(memorized_moe_lm):
    """The stats read is throttled (_MOE_STATS_EVERY) but the FIRST
    decode iteration always reports — a short run still produces the
    expert-load picture."""
    m = memorized_moe_lm
    eng = ServingEngine(m, num_slots=1, max_len=32)
    eng.submit(PATTERN[:4], 2)             # 2 decode iterations total
    eng.run(max_steps=100)
    assert eng.metrics.summary()["moe"] is not None
    assert eng._moe_iter >= 1


# --- MoE-aware admission ----------------------------------------------------


def test_moe_admit_extra_scales_and_caps(memorized_moe_lm):
    m = memorized_moe_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, page_len=4)
    req = Request(rid=0, prompt=PATTERN[:8].astype(np.int32),
                  max_new_tokens=8)
    n_logical = eng.pool.pages_for(len(req.prompt) + 1)
    assert eng._moe_admit_extra(req, n_logical) == 0   # no signal yet
    eng._moe_conc = 1.0
    extra = eng._moe_admit_extra(req, n_logical)
    assert extra >= 1
    # capped: worst-case context + headroom never exceeds the pool, so
    # a feasible request always admits into an idle pool
    worst = eng.pool.pages_for(len(req.prompt) + req.max_new_tokens)
    assert worst + extra <= eng.pool.num_pages
    # a dense-baseline engine never charges headroom
    eng_dense = ServingEngine(m, num_slots=2, max_len=32, page_len=4,
                              moe_decode="dense")
    eng_dense._moe_conc = 1.0
    assert eng_dense._moe_admit_extra(req, n_logical) == 0


def test_concentration_defers_admission_under_page_pressure(
        memorized_moe_lm):
    """The admission cost model in action: with the same free-page
    budget, a concentrated router defers the admission a balanced one
    would grant (the plan demands headroom), and admission proceeds
    once concentration clears — never a deadlock."""
    m = memorized_moe_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, page_len=4,
                        num_pages=8, prefix_cache=False)
    # drain the free list so exactly the request's pages remain
    req = Request(rid=99, prompt=PATTERN[:8].astype(np.int32),
                  max_new_tokens=4)
    n_logical = eng.pool.pages_for(len(req.prompt) + 1)   # 3 pages
    held = [eng.pool.alloc_page()
            for _ in range(eng.pool.free_pages - n_logical)]
    assert eng.pool.free_pages == n_logical
    eng._moe_conc = 1.0
    assert eng._page_plan(req) is None        # headroom not available
    eng._moe_conc = 0.0
    plan = eng._page_plan(req)                # balanced router admits
    assert plan is not None and len(plan["priv"]) == n_logical
    for pid in plan["priv"] + held:
        eng.pool.decref(pid)


# --- raw step-level checks --------------------------------------------------


def test_decode_step_slots_moe_stats_mask_sentinels():
    """Sentinel slots (t at the live bound) must not pollute the
    expert-load picture: a batch of one live + one inert slot counts
    only the live slot's assignments."""
    m = _moe_lm(seed=4)
    _resolve_head_dims(m.module, m.params)
    cache = init_cache(m.module, 2, S)
    tok = jnp.asarray(np.array([3, 1], np.int32))
    t = jnp.asarray(np.array([0, S], np.int32))   # slot 1 inert
    _, _, stats = decode_step_slots(m.module, m.params, m.state, cache,
                                    tok, t, moe_stats=S)
    load = np.asarray(stats["expert_load"])
    # 2 MoE layers x 1 live token x top-2 = 4 assignments
    assert load.sum() == 4.0


def test_decode_step_slots_paged_moe_matches_contiguous():
    """The dispatched MoE step the engine runs (paged, scattered
    physical pages, one inert slot) against its reference
    ``decode_step_slots``: logits of the live slots, the routing
    stats, and the cache in logical order."""
    L, page_len = 16, 4
    m = _moe_lm(seed=4)
    _resolve_head_dims(m.module, m.params)
    cache = init_cache(m.module, 3, L)
    rs = np.random.RandomState(2)
    for step in range(3):              # slots 0 and 1 at depths 3 and 2
        tok = jnp.asarray(rs.randint(0, V, 3).astype(np.int32))
        t = jnp.asarray(np.array([step, step if step < 2 else L, L],
                                 np.int32))
        _, cache = decode_step_slots(m.module, m.params, m.state, cache,
                                     tok, t)
    tok = jnp.asarray(np.array([3, 1, 4], np.int32))
    t = jnp.asarray(np.array([3, 2, L], np.int32))    # slot 2 inert
    tables, n_pages = scrambled_tables(3, L // page_len, seed=9)
    ref_lg, ref_cache, ref_st = decode_step_slots(
        m.module, m.params, m.state, cache, tok, t, moe_stats=L)
    got_lg, got_cache, got_st = decode_step_slots_paged(
        m.module, m.params, m.state,
        to_pages(cache, tables, page_len, n_pages), tok, t,
        jnp.asarray(tables), page_len, moe_stats=L)
    np.testing.assert_allclose(np.asarray(got_lg)[:2],
                               np.asarray(ref_lg)[:2], atol=1e-5)
    load = np.asarray(got_st["expert_load"])
    assert load.sum() == 2 * 2 * 2      # 2 layers x 2 live x top-2
    np.testing.assert_array_equal(load,
                                  np.asarray(ref_st["expert_load"]))
    np.testing.assert_allclose(np.asarray(got_st["router_entropy"]),
                               np.asarray(ref_st["router_entropy"]),
                               rtol=1e-5)
    assert_same_cache(ref_cache, got_cache, tables)
