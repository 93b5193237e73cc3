"""Tree speculation (tree-speculation PR): the tree-masked verify
window, the in-program acceptance walk + accepted-path commit, the
tree draft sources (per-divergence branching n-gram, beam-style draft
model), the adaptive per-stream depth/width controller, and the
Pallas kernel's ancestor-mask path — pinned against the sequential
decode oracle and the landed linear speculation path.

The WIDTH-1 byte-identity contract (tree == linear, bit for bit) is
parametrized into the existing linear oracle suite
(``tests/test_spec_decode.py``); this file owns everything the chain
cannot express."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distkeras_tpu.models import Model, zoo
from distkeras_tpu.models.decoding import (_resolve_head_dims,
                                           commit_tree_path,
                                           decode_step_slots, generate,
                                           init_cache, tree_walk,
                                           verify_step_slots,
                                           verify_step_slots_paged)
from distkeras_tpu.serving import (DraftModel, DraftSource, NgramDraft,
                                   ServingEngine)
from distkeras_tpu.serving.speculation import (build_token_tree,
                                               tree_ancestors)
from paged_layout import assert_same_cache, scrambled_tables, to_pages

V, S = 29, 12
PATTERN = np.array([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8])


@pytest.fixture(scope="module")
def memorized_lm(pattern_lm):
    """The shared session-scoped overfit-PATTERN LM (conftest pattern_lm): huge greedy argmax margins keep token-identity assertions robust; trained once per test session."""
    return pattern_lm


@pytest.fixture(scope="module")
def small_lm():
    """An untrained model for the numerical window units (no
    memorization needed — they compare against sequential decode)."""
    m = Model.build(
        zoo.transformer_lm(V, d_model=32, num_heads=4, num_layers=2,
                           mlp_ratio=2, use_rope=True), (16,), seed=4)
    _resolve_head_dims(m.module, m.params)
    return m


def _warm_cache(m, toks, hist, cap=16):
    cache = init_cache(m.module, len(hist), cap)
    for step in range(max(hist)):
        tk = np.array([toks[i, min(step, hist[i] - 1)]
                       for i in range(len(hist))], np.int32)
        tv = np.array([step if step < hist[i] else cap
                       for i in range(len(hist))], np.int32)
        _, cache = decode_step_slots(m.module, m.params, m.state, cache,
                                     jnp.asarray(tk), jnp.asarray(tv))
    return cache


# --- window units -----------------------------------------------------------


def test_tree_ancestors_units():
    parents = np.array([[-1, 0, 1, 0, -1],       # root -> 1 -> 2; root -> 3
                        [-1, 0, -1, -1, -1]], np.int32)
    depth, anc, n_nodes = tree_ancestors(parents)
    np.testing.assert_array_equal(depth[0], [0, 1, 2, 1, 0])
    np.testing.assert_array_equal(n_nodes, [4, 2])
    assert anc[0, 2, 0] and anc[0, 2, 1] and anc[0, 2, 2]
    assert not anc[0, 2, 3]                      # sibling branch invisible
    assert not anc[0, 1, 2]                      # child not ancestor
    assert not anc[0, 4].any()                   # unused node: no row
    assert not anc[0, :, 4].any()                # ...and no column
    assert anc[1, 1, 0] and anc[1, 1, 1]


def test_branched_tree_logits_match_sequential_root_paths(small_lm):
    """Every tree node's logits equal a sequential decode of its OWN
    root path — the tree mask's correctness statement."""
    m = small_lm
    rs = np.random.RandomState(0)
    toks = rs.randint(0, V, (2, 12)).astype(np.int32)
    hist = [3, 2]
    cache = _warm_cache(m, toks, hist)
    t = np.array(hist, np.int32)
    W = 4
    win = np.stack([toks[0, hist[0]:hist[0] + W],
                    toks[1, hist[1]:hist[1] + W]], 0)
    # root(0) -> 1 -> 2, root -> 3 (a depth-1 sibling with its own token)
    parents = np.tile(np.array([-1, 0, 1, 0], np.int32), (2, 1))
    win2 = win.copy()
    win2[:, 3] = (win[:, 1] + 7) % V
    depth, anc, _ = tree_ancestors(parents)
    lg, _, _ = verify_step_slots(
        m.module, m.params, m.state, cache, jnp.asarray(win2),
        jnp.asarray(t),
        tree={"depth": jnp.asarray(depth), "anc": jnp.asarray(anc)})
    lg = np.asarray(lg)

    def seq(path_cols):
        c, out = cache, None
        for j, col in enumerate(path_cols):
            out, c = decode_step_slots(
                m.module, m.params, m.state, c,
                jnp.asarray(win2[:, col]),
                jnp.asarray((t + j).astype(np.int32)))
        return np.asarray(out)

    np.testing.assert_allclose(lg[:, 2], seq([0, 1, 2]), atol=3e-5)
    np.testing.assert_allclose(lg[:, 3], seq([0, 3]), atol=3e-5)


def test_walk_and_commit_match_sequential_cache(small_lm):
    """Accepting a branch: the walk picks the child carrying the
    target's own argmax, and the committed cache equals a sequential
    decode of the accepted path on every committed position — decode
    then continues identically from either cache."""
    m = small_lm
    rs = np.random.RandomState(0)
    toks = rs.randint(0, V, (2, 12)).astype(np.int32)
    hist = [3, 2]
    cache = _warm_cache(m, toks, hist)
    t = np.array(hist, np.int32)
    lg0, _ = decode_step_slots(m.module, m.params, m.state, cache,
                               jnp.asarray(toks[:, 0]), jnp.asarray(t))
    arg0 = np.asarray(jnp.argmax(lg0, -1)).astype(np.int32)
    W = 4
    win = np.zeros((2, W), np.int32)
    win[:, 0] = toks[:, 0]
    win[:, 1] = (arg0 + 5) % V               # wrong depth-1 branch
    win[:, 2] = arg0                         # the branch the walk takes
    win[:, 3] = 1
    parents = np.tile(np.array([-1, 0, 0, 2], np.int32), (2, 1))
    depth, anc, _ = tree_ancestors(parents)
    lg, c_t, kvw = verify_step_slots(
        m.module, m.params, m.state, cache, jnp.asarray(win),
        jnp.asarray(t),
        tree={"depth": jnp.asarray(depth), "anc": jnp.asarray(anc)})
    em, ne, path, keys = tree_walk(lg, jnp.asarray(win),
                                   jnp.asarray(parents))
    assert keys is None
    em, ne, path = np.asarray(em), np.asarray(ne), np.asarray(path)
    assert (ne >= 2).all() and (path[:, 1] == 2).all()
    committed = commit_tree_path(c_t, kvw, jnp.asarray(path),
                                 jnp.asarray(t), jnp.asarray(ne))
    c_seq = cache
    _, c_seq = decode_step_slots(m.module, m.params, m.state, c_seq,
                                 jnp.asarray(win[:, 0]), jnp.asarray(t))
    _, c_seq = decode_step_slots(
        m.module, m.params, m.state, c_seq, jnp.asarray(arg0),
        jnp.asarray((t + 1).astype(np.int32)))
    for a, b in zip(c_seq, committed):
        if a is None:
            continue
        for kk in a:
            av, bv = np.asarray(a[kk]), np.asarray(b[kk])
            for s in range(2):
                hi = t[s] + 2
                np.testing.assert_allclose(av[s, :, :hi], bv[s, :, :hi],
                                           atol=3e-5)
    bonus = em[np.arange(2), ne - 1].astype(np.int32)
    nxt, _ = decode_step_slots(m.module, m.params, m.state, committed,
                               jnp.asarray(bonus),
                               jnp.asarray((t + ne).astype(np.int32)))
    ref, _ = decode_step_slots(m.module, m.params, m.state, c_seq,
                               jnp.asarray(bonus),
                               jnp.asarray((t + 2).astype(np.int32)))
    np.testing.assert_allclose(np.asarray(nxt), np.asarray(ref),
                               atol=3e-5)


def test_commit_tree_path_paged_matches_contiguous_commit(small_lm):
    """The engine's commit (``commit_tree_path`` through a page table)
    against the table-less reference the test above anchors to
    sequential decode: after a walk that accepts a branch and one that
    stops at the root, the committed K/V in logical order are equal —
    accepted depths re-written, everything past them left as the
    verify window wrote it."""
    m = small_lm
    L, page_len, W = 16, 4, 4
    rs = np.random.RandomState(0)
    toks = rs.randint(0, V, (2, 12)).astype(np.int32)
    hist = [3, 2]
    cache = _warm_cache(m, toks, hist, cap=L)
    t = jnp.asarray(np.array(hist, np.int32))
    lg0, _ = decode_step_slots(m.module, m.params, m.state, cache,
                               jnp.asarray(toks[:, 0]), t)
    arg0 = np.asarray(jnp.argmax(lg0, -1)).astype(np.int32)
    win = np.zeros((2, W), np.int32)
    win[:, 0] = toks[:, 0]
    win[:, 1] = (arg0 + 5) % V               # wrong depth-1 branch
    win[:, 2] = arg0                         # slot 0 walks into it
    win[1, 2] = (arg0[1] + 9) % V            # slot 1 finds no child
    win[:, 3] = 1
    parents = np.tile(np.array([-1, 0, 0, 2], np.int32), (2, 1))
    depth, anc, _ = tree_ancestors(parents)
    tree = {"depth": jnp.asarray(depth), "anc": jnp.asarray(anc)}
    tables, n_pages = scrambled_tables(2, L // page_len, seed=7)
    lg, c_ref, kv_ref = verify_step_slots(
        m.module, m.params, m.state, cache, jnp.asarray(win), t,
        tree=tree)
    _, c_pg, kv_pg = verify_step_slots_paged(
        m.module, m.params, m.state,
        to_pages(cache, tables, page_len, n_pages), jnp.asarray(win), t,
        jnp.asarray(tables), page_len, tree=tree)
    _, ne, path, _ = tree_walk(lg, jnp.asarray(win), jnp.asarray(parents))
    assert np.asarray(ne).tolist()[1] == 1 and np.asarray(ne)[0] >= 2
    ref = commit_tree_path(c_ref, kv_ref, path, t, ne)
    got = commit_tree_path(c_pg, kv_pg, path, t, ne,
                           table=jnp.asarray(tables), page_len=page_len)
    assert_same_cache(ref, got, tables)
    # the commit re-wrote the accepted path
    assert any(not np.array_equal(np.asarray(a[key]),
                                  np.asarray(before[key]))
               for a, before in zip(ref, c_ref) if a is not None
               for key in a)


def test_paged_kernel_tree_mask_matches_gather_reference():
    """The Pallas kernel's ancestor-mask operand (interpret mode)
    against the gather-path tree mask on scrambled page tables with a
    sentinel entry."""
    import distkeras_tpu.models.decoding as dec
    from distkeras_tpu.ops.attention import NEG_INF
    from distkeras_tpu.ops.paged_attention import paged_decode_attention
    rs = np.random.RandomState(1)
    Spg, Wq, Hkv, G, D, page_len, P, N = 2, 4, 2, 2, 8, 8, 3, 7
    q = rs.randn(Spg, Wq, Hkv, G, D).astype(np.float32)
    kp = rs.randn(N, Hkv, page_len, D).astype(np.float32)
    vp = rs.randn(N, Hkv, page_len, D).astype(np.float32)
    t = np.array([5, 9], np.int32)
    table = np.array([[2, 0, 7], [1, 4, 6]], np.int32)   # 7 = sentinel
    parents = np.tile(np.array([-1, 0, 0, 2], np.int32), (Spg, 1))
    depth, anc, _ = tree_ancestors(parents)
    o_kernel = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(t), jnp.asarray(table), anc=jnp.asarray(anc),
        interpret=True)
    kv_view = dec._gather_pages(
        {"k": jnp.asarray(kp), "v": jnp.asarray(vp)}, jnp.asarray(table))
    qg = (q.astype(np.float32) * (D ** -0.5)).reshape(
        Spg, Wq, Hkv, G, D)
    s = dec._decode_scores(jnp.asarray(qg), kv_view)
    valid = dec._window_valid_mask(
        jnp.asarray(t), Wq, P * page_len,
        {"depth": jnp.asarray(depth), "anc": jnp.asarray(anc)}, None)
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    o_ref = dec._decode_mix(jax.nn.softmax(s, axis=-1), kv_view)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)


# --- tree draft sources -----------------------------------------------------


def test_ngram_continuations_surface_distinct_followers():
    d = NgramDraft(max_ngram=3, min_ngram=1)
    # suffix [1, 2] continued by 9 (older) and 7 (most recent)
    ctx = np.array([5, 1, 2, 9, 4, 1, 2, 7, 3, 1, 2], np.int32)
    assert d.continuations(ctx, 2) == [7, 9]
    assert d.continuations(ctx, 1) == [7]
    # nothing re-occurs
    assert d.continuations(np.array([1, 2, 3], np.int32), 2) == []


def test_ngram_grow_branches_at_divergence_points():
    """A context whose suffix has two historical continuations must
    produce a tree with BOTH branches — and the primary chain must be
    the linear draft's exact bet."""
    d = NgramDraft(max_ngram=3, min_ngram=1)
    head = [11, 7, 19]
    ctx = np.array(head + [2] + head + [8] + head, np.int32)
    W = 8
    toks = np.zeros(W, np.int32)
    parents = np.full(W, -1, np.int32)
    used = d._grow(ctx, toks, parents, depth=3, width=2, max_nodes=6)
    assert used >= 4
    # primary chain starts with lookup()'s choice
    chain = d.lookup(ctx, 3)
    assert toks[1] == chain[0]
    # both historical tails appear as children of SOME node
    roots = [toks[j] for j in range(1, used + 1) if parents[j] == 0]
    assert set(roots) == {2, 8}
    # topological parent order
    assert all(parents[j] < j for j in range(1, used + 1))


def test_build_token_tree_merges_prefixes_and_caps_budget():
    toks = np.zeros(8, np.int32)
    parents = np.full(8, -1, np.int32)
    chains = [np.array([5, 6, 7]), np.array([5, 9]), np.array([5, 6, 8])]
    used = build_token_tree(chains, toks, parents, max_nodes=7)
    # shared prefix [5] and [5, 6] hash-cons: 5,6,7,9,8 -> 5 nodes
    assert used == 5
    assert (parents[1:used + 1] < np.arange(1, used + 1)).all()
    # budget cap truncates later chains first
    toks2 = np.zeros(8, np.int32)
    parents2 = np.full(8, -1, np.int32)
    assert build_token_tree(chains, toks2, parents2, max_nodes=3) == 3
    np.testing.assert_array_equal(toks2[1:4], [5, 6, 7])


# --- engine oracles ---------------------------------------------------------


def test_tree_width2_ngram_matches_generate_paged(memorized_lm):
    """Branching n-gram trees on the paged engine: greedy outputs
    token-identical to generate(), speculation fired, and the tree
    metrics/tracer surfaces carry width/path data."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=3, max_len=48, page_len=4,
                        draft=NgramDraft(), spec_k=3, spec_tree=True,
                        spec_width=2)
    prompts = [np.tile(PATTERN, 2)[:10], np.tile(PATTERN, 2)[:14],
               PATTERN[:6]]
    budgets = [12, 9, 14]
    rids = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
    out = eng.run(max_steps=800)
    for i, rid in enumerate(rids):
        ref = generate(m, prompts[i][None], max_new_tokens=budgets[i],
                       temperature=0.0)
        np.testing.assert_array_equal(out[rid], ref[0])
    s = eng.metrics.summary()["speculation"]
    assert s["accepted"] > 0
    assert s["tree_width"] is not None and s["tree_width"]["p50"] >= 1
    assert s["accepted_path_len"] is not None
    tl = [t for t in eng.tracer.timelines() if t.rid == rids[0]][0]
    ev = [e for e in tl.events if e["name"] == "spec_verify"]
    assert ev and any("tree_width" in e for e in ev)
    assert any(e.get("accepted_path_len", 0) >= 1 for e in ev)


def test_tree_beam_draft_model_matches_generate(memorized_lm):
    """Beam-style DraftModel trees (greedy chain + top-width side
    branches): the perfect-drafter limit keeps token identity and
    near-1 acceptance."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=48, page_len=4,
                        draft=DraftModel(m, page_len=4), spec_k=3,
                        spec_tree=True, spec_width=2)
    r0 = eng.submit(np.tile(PATTERN, 2)[:10], 12)
    out = eng.run(max_steps=800)
    np.testing.assert_array_equal(
        out[r0], generate(m, np.tile(PATTERN, 2)[None, :10], 12,
                          temperature=0.0)[0])
    assert eng.metrics.summary()["acceptance_rate"] >= 0.4


def test_draft_model_heals_kv_after_side_branch_acceptance(memorized_lm):
    """A tree verify can accept a token the draft's greedy chain did
    NOT propose; the draft KV at that position then holds the wrong
    token's K/V. The heal pass must rewrite the divergent positions
    with the ACTUAL accepted tokens before the next draft round —
    byte-identical to feeding those tokens through the draft step
    directly (code-review regression, this PR)."""
    m = memorized_lm

    class Stub:
        num_slots, max_len = 1, 32

    class Req:
        pass

    def begun(ctx):
        d = DraftModel(m, page_len=4)
        d.bind(Stub())
        assert d.begin_slot(0, ctx)
        return d

    import jax.numpy as jnp
    prompt = PATTERN[:6]
    f = int(PATTERN[6])                  # pretend first sampled token
    draft = begun(prompt)
    req = Req()
    req.prompt = prompt
    req.generated = [f]
    toks = np.zeros((1, 7), np.int32)
    toks[0, 0] = f
    parents = np.full((1, 7), -1, np.int32)
    draft.propose_tree({0: req}, np.array([f], np.int32),
                       np.array([6], np.int32), toks, parents,
                       np.array([True]), np.array([3], np.int32),
                       np.array([2], np.int32), np.array([6], np.int32))
    g1 = draft._written[0][1][1]         # the chain token at position 7
    a = int((g1 + 3) % V)                # the "accepted side branch"
    b = int((g1 + 5) % V)
    req.generated = [f, a, b, 1]         # engine committed f,a,b; 1 pends
    draft.propose({0: req}, np.array([1], np.int32),
                  np.array([9], np.int32), np.zeros((1, 3), np.int32),
                  np.array([True]))
    # oracle: a fresh draft fed the SAME actual tokens step by step
    oracle = begun(prompt)
    fn = oracle._decode_fn(1)
    tables = oracle.pool.device_tables()
    for pos, tokv in ((6, f), (7, a), (8, b)):
        _, oracle.pool.cache = fn(
            oracle._params, oracle._state, oracle.pool.cache,
            jnp.asarray(np.array([tokv], np.int32)),
            jnp.asarray(np.array([pos], np.int32)), tables)
    for kv_d, kv_o in zip(draft.pool.cache, oracle.pool.cache):
        if kv_d is None:
            continue
        for key in kv_d:
            # both pools allocate slot 0's logical pages as physical
            # 0..7 in order, so position 7 = page 1 row 3 and position
            # 8 = page 2 row 0 — the healed rows must be byte-exact
            np.testing.assert_array_equal(np.asarray(kv_d[key])[1, :, 3],
                                          np.asarray(kv_o[key])[1, :, 3],
                                          err_msg=key)
            np.testing.assert_array_equal(np.asarray(kv_d[key])[2, :, 0],
                                          np.asarray(kv_o[key])[2, :, 0],
                                          err_msg=key)


def test_tree_sampled_stream_byte_identical_to_plain(memorized_lm):
    """The tree walk's rejection-sampling rule: a sampled stream under
    width-2 tree speculation draws the EXACT tokens plain decode
    draws (one split per emitted token, key selected by path length)."""
    m = memorized_lm

    def run(**kw):
        eng = ServingEngine(m, num_slots=2, max_len=48, **kw)
        g = eng.submit(np.tile(PATTERN, 2)[:10], 10)
        srid = eng.submit(PATTERN[:5], 9, temperature=0.9, top_p=0.95,
                          seed=7, speculate=bool(kw))
        out = eng.run(max_steps=800)
        return out[g], out[srid]

    g_plain, s_plain = run()
    g_tree, s_tree = run(draft=NgramDraft(), spec_k=3, spec_tree=True,
                         spec_width=2)
    np.testing.assert_array_equal(g_plain, g_tree)
    np.testing.assert_array_equal(s_plain, s_tree)


# --- adaptive controller / validation ---------------------------------------


class WrongDraft(DraftSource):
    """Always proposes token 0 — PATTERN never contains it."""

    def propose(self, requests, tok, t, out, active):
        out[:] = 0


def test_tree_paged_kernel_engine_matches_generate(memorized_lm):
    """decode_kernel='paged' (interpret off-TPU) drives the kernel's
    ancestor-mask path end to end — deliberately tiny (the
    interpreted kernel is ~5x slower per step on CPU)."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, page_len=8,
                        decode_kernel="paged", draft=NgramDraft(),
                        spec_k=3, spec_tree=True, spec_width=2)
    rid = eng.submit(np.tile(PATTERN, 2)[:8], 7)
    out = eng.run(max_steps=400)
    np.testing.assert_array_equal(
        out[rid], generate(m, np.tile(PATTERN, 2)[None, :8], 7,
                           temperature=0.0)[0])


def test_adaptive_controller_narrows_then_kill_switch(memorized_lm):
    """An adversarial draft: after warm-up the controller sheds width,
    and the sticky EMA floor demotes the stream to plain decode —
    output still correct."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=1, max_len=64, draft=WrongDraft(),
                        spec_k=3, spec_tree=True, spec_width=2,
                        spec_warmup=4)
    prompt = np.tile(PATTERN, 2)[:8]
    rid = eng.submit(prompt, 18)
    done = {}
    while eng.scheduler.pending:
        for r in eng.step():
            done[r.rid] = r
    req = done[rid]
    assert req.spec_disabled
    assert req.tree_width <= 2 and req.tree_depth <= 3
    np.testing.assert_array_equal(
        req.tokens, generate(m, prompt[None], 18, temperature=0.0)[0])


def test_adaptive_controller_keeps_hot_streams_wide(memorized_lm):
    """A well-predicted stream (memorized pattern, n-gram home turf)
    keeps its full tree shape through the run."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=1, max_len=64, draft=NgramDraft(),
                        spec_k=3, spec_tree=True, spec_width=2,
                        spec_warmup=2)
    rid = eng.submit(np.tile(PATTERN, 3)[:12], 16)
    done = {}
    while eng.scheduler.pending:
        for r in eng.step():
            done[r.rid] = r
    req = done[rid]
    assert not req.spec_disabled
    assert req.tree_depth == 3 and req.tree_width == 2


def test_spec_tree_knob_validation(memorized_lm):
    m = memorized_lm
    with pytest.raises(ValueError, match="spec_width"):
        ServingEngine(m, num_slots=1, max_len=32, draft=NgramDraft(),
                      spec_tree=True, spec_width=0)
    with pytest.raises(ValueError, match="spec_tree"):
        ServingEngine(m, num_slots=1, max_len=32, draft=NgramDraft(),
                      spec_width=2)
    with pytest.raises(ValueError, match="draft"):
        ServingEngine(m, num_slots=1, max_len=32, spec_tree=True)


def test_tree_preempt_resume_token_identity(memorized_lm):
    """Width-2 trees in a deliberately tiny page pool: the tree
    lookahead (worst-case node span) funds pages through preemption,
    and both streams stay token-identical through evict/resume."""
    m = memorized_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, page_len=4,
                        num_pages=8, prefix_cache=False,
                        draft=NgramDraft(), spec_k=3, spec_tree=True,
                        spec_width=2)
    r0 = eng.submit(np.tile(PATTERN, 2)[:5], 12)
    eng.step()
    eng.step()
    r1 = eng.submit(np.tile(PATTERN, 2)[:6], 11)
    out = eng.run(max_steps=2000)
    assert eng.metrics.requests_preempted >= 1
    np.testing.assert_array_equal(
        out[r0], generate(m, np.tile(PATTERN, 2)[None, :5], 12,
                          temperature=0.0)[0])
    np.testing.assert_array_equal(
        out[r1], generate(m, np.tile(PATTERN, 2)[None, :6], 11,
                          temperature=0.0)[0])
