"""Block diffusion end to end, at a small size on the CPU, against the plain
reference (``tests/sdar_reference.py``: float32 ``jax.numpy``, no kernel,
cache or batching) on seeded weights: the gated top-k expert layer under
the drop-free grouped dispatch, block-causal flash prefill and the
full-window paged step, and ``ServingEngine`` serving a block-causal model
by denoising (same tokens fixed in the same passes as the reference's
``generate``).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
import sdar_reference as ref  # noqa: E402

from distkeras_tpu.models import Model, zoo  # noqa: E402
from distkeras_tpu.models.attention import (MultiHeadAttention,  # noqa: E402
                                            TransformerBlock,
                                            TransformerMLP)
from distkeras_tpu import obs  # noqa: E402
from distkeras_tpu.models.decoding import (block_pass_slots_paged,  # noqa: E402
                                           fix_most_confident, generate)
from distkeras_tpu.models.moe import MoE  # noqa: E402
from distkeras_tpu.ops import moe_kernels  # noqa: E402
from distkeras_tpu.ops.attention import dot_product_attention  # noqa: E402
from distkeras_tpu.ops.flash_attention import flash_attention  # noqa: E402
from distkeras_tpu.ops.paged_attention import paged_decode_attention  # noqa: E402
from distkeras_tpu.serving import ServingEngine  # noqa: E402

VOCAB, MASK = 97, 96
CFG = dict(heads=4, kv_heads=2, top_k=2, block_len=4, rope_theta=1e6)


def small_lm(block_len=4, experts=8, top_k=2, layers=2):
    return zoo.transformer_lm(
        VOCAB, d_model=32, num_heads=4, num_layers=layers, max_len=64,
        num_kv_heads=2, head_dim=16, qk_norm=True, rope_base=1e6,
        block_len=block_len, mlp_dim=24, mlp_activation="silu",
        mlp_gated=True, mlp_bias=False, moe_every=1, num_experts=experts,
        moe_top_k=top_k, moe_dispatch="grouped")


def build(seed=0, **kw):
    module = small_lm(**kw)
    model = Model.build(module, (16,), seed=seed)
    # glorot weights give nearly flat logits at this width: widen them so
    # that the most confident position is decided by more than round-off
    params = jax.tree_util.tree_map(
        lambda a: a * 3 if a.ndim >= 2 else a, model.params)
    return Model(module, params, model.state, model.input_shape,
                 model.output_shape)


def reference_tree(params):
    """The program's parameter tree under the reference's names."""
    layers = []
    for p in params[1:-2]:
        a, e = p["attn"], p["mlp"]
        layers.append({
            "n1": p["norm1"]["scale"], "n2": p["norm2"]["scale"],
            "wq": a["wq"], "wk": a["wk"], "wv": a["wv"], "wo": a["wo"],
            "qn": a["q_norm"], "kn": a["k_norm"], "router": e["gate"],
            "wg": e["w1"], "wu": e["w3"], "wd": e["w2"]})
    return {"embed": params[0]["embeddings"], "layers": layers,
            "final_norm": params[-2]["scale"], "head": params[-1]["kernel"]}


# --- the expert layer ---------------------------------------------------------

def _moe(experts, top_k, dispatch, gated=True):
    return MoE(experts, 24, top_k=top_k, activation="silu", gated=gated,
               use_bias=False, dispatch=dispatch)


def _moe_params(experts, top_k, gated=True, seed=0):
    params, _, _ = _moe(experts, top_k, "grouped", gated).init(
        jax.random.PRNGKey(seed), (4, 32))
    return jax.tree_util.tree_map(lambda a: a * 3, params)


@pytest.mark.parametrize("tokens,experts,top_k",
                         [(4, 8, 2), (24, 8, 2), (40, 16, 4), (128, 8, 8),
                          (7, 32, 8)])
def test_grouped_experts_match_the_reference_layer(tokens, experts, top_k):
    """Gated top-k experts under the grouped dispatch against the plain
    reference's expert layer (every expert for every token, gated)."""
    params = _moe_params(experts, top_k)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, tokens, 32))
    got, _ = _moe(experts, top_k, "grouped").apply(params, {}, x)
    lw = {"router": params["gate"], "wg": params["w1"], "wu": params["w3"],
          "wd": params["w2"]}
    want = ref._experts(x[0], lw, top_k, "float32")
    np.testing.assert_allclose(got[0], want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("gated", [True, False])
@pytest.mark.parametrize("tokens", [5, 64])
def test_grouped_equals_dense_routing(tokens, gated):
    params = _moe_params(8, 2, gated)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, tokens, 32))
    got, _ = _moe(8, 2, "grouped", gated).apply(params, {}, x)
    want, _ = _moe(8, 2, "dense", gated).apply(params, {}, x)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("tokens", [1, 3, 17])
def test_grouped_is_drop_free_whatever_shares_the_batch(tokens):
    """A token's output alone equals its output among neighbours that all
    route like it (every row to the same experts: the case in which a
    capacity would drop)."""
    params = _moe_params(8, 2)
    one = jax.random.normal(jax.random.PRNGKey(3), (1, 1, 32))
    crowd = jnp.concatenate([one] * tokens + [
        jax.random.normal(jax.random.PRNGKey(4), (1, 9, 32))], axis=1)
    moe = _moe(8, 2, "grouped")
    alone, _ = moe.apply(params, {}, one)
    among, _ = moe.apply(params, {}, crowd)
    np.testing.assert_allclose(among[0, :tokens],
                               np.repeat(alone[0], tokens, 0),
                               rtol=1e-6, atol=1e-6)
    assert moe.decode_apply(params, crowd).shape == crowd.shape


@pytest.mark.parametrize("gated", [True, False])
@pytest.mark.parametrize("tokens,experts,top_k", [(16, 8, 2), (40, 16, 4)])
def test_grouped_kernel_interpreted_matches_its_xla_form(tokens, experts,
                                                         top_k, gated):
    params = _moe_params(experts, top_k, gated)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, tokens, 32))
    moe = _moe(experts, top_k, "grouped", gated)
    want, _ = moe.apply(params, {}, x)
    with moe_kernels.force_interpret():
        got, _ = moe.apply(params, {}, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("assignments,experts", [(8, 4), (1024, 128),
                                                 (8192, 128), (33, 7)])
def test_grouped_layout_fits_its_stated_padding(assignments, experts):
    """Every assignment gets a row of its own inside a tile of its expert,
    and the live tiles never pass the static count, whatever the routing
    (here: everything on two experts, the worst case for padding)."""
    rows = moe_kernels.grouped_block_rows(assignments, experts)
    tiles = moe_kernels.grouped_tiles(assignments, experts, rows)
    flat = jnp.asarray(np.random.default_rng(0).choice(
        [0, experts - 1], assignments), jnp.int32)
    dest, tile_expert, used, counts = moe_kernels.grouped_layout(
        flat, experts, rows)
    dest = np.asarray(dest)
    assert len(set(dest.tolist())) == assignments
    assert int(used) <= tiles and dest.max() < int(used) * rows
    np.testing.assert_array_equal(np.asarray(tile_expert)[dest // rows],
                                  np.asarray(flat))
    assert int(counts.sum()) == assignments
    assert 16 <= rows <= 128


def test_grouped_refuses_biases_and_an_expert_axis():
    with pytest.raises(ValueError, match="bias-free"):
        MoE(8, 24, dispatch="grouped")
    with pytest.raises(ValueError, match="bias-free"):
        MoE(8, 24, dispatch="grouped", use_bias=False,
            expert_axis_name="ep")


def test_zoo_passes_top_k_and_the_gated_width():
    module = small_lm(experts=8, top_k=4)
    mlp = module.layers[1].mlp
    assert (mlp.top_k, mlp.hidden_dim, mlp.gated, mlp.use_bias,
            mlp.dispatch) == (4, 24, True, False, "grouped")
    attn = module.layers[1].attn
    assert (attn.head_dim, attn.qk_norm, attn.rope_base, attn.block_len) \
        == (16, True, 1e6, 4)
    # a dense gated MLP at a stated width
    dense = zoo.transformer_lm(VOCAB, d_model=32, num_heads=4, num_layers=1,
                               mlp_dim=40, mlp_gated=True, mlp_bias=False,
                               mlp_activation="silu")
    params, _, _ = dense.init(jax.random.PRNGKey(0), (8,))
    assert {k: v.shape for k, v in params[1]["mlp"].items()} == {
        "w1": (32, 40), "w2": (40, 32), "w3": (32, 40)}


@pytest.mark.parametrize("layer", [
    TransformerMLP(24, activation="silu", gated=True, use_bias=False),
    MoE(8, 24, top_k=4, activation="silu", gated=True, use_bias=False,
        dispatch="grouped"),
    MultiHeadAttention(4, head_dim=16, num_kv_heads=2, qk_norm=True,
                       rope_base=1e6, block_len=4),
    TransformerBlock(4, head_dim=16, qk_norm=True, rope_base=1e6,
                     block_len=4, mlp_dim=24, mlp_gated=True,
                     mlp_bias=False)])
def test_new_layer_options_round_trip_their_config(layer):
    again = type(layer).from_config(layer.get_config())
    assert again.get_config() == layer.get_config()


def test_sharding_rules_cover_gated_bias_free_layers():
    from distkeras_tpu.parallel.sharding import param_specs
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("tp",))
    module = small_lm()
    params, _, _ = module.init(jax.random.PRNGKey(0), (8,))
    specs = param_specs(module, params, mesh)
    assert set(specs[1]["mlp"]) == set(params[1]["mlp"])
    assert set(specs[1]["attn"]) == set(params[1]["attn"])


# --- attention masks ----------------------------------------------------------

def _qkv(seq, heads, kv_heads, dh=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, seq, heads, dh))
    k = jax.random.normal(ks[1], (1, seq, kv_heads, dh))
    v = jax.random.normal(ks[2], (1, seq, kv_heads, dh))
    return q, k, v


def _reference_attention(q, k, v, block_len):
    """Block-causal grouped-query attention as the reference's layer
    takes it: [S, H, D] against [S, Hkv, D]."""
    s, h, d = q.shape
    g = h // k.shape[1]
    scores = jnp.einsum("qhge,khe->hgqk", q.reshape(s, -1, g, d), k,
                        precision="highest") / np.sqrt(d)
    blk = jnp.arange(s) // block_len
    scores = jnp.where(blk[:, None] >= blk[None, :], scores, -jnp.inf)
    return jnp.einsum("hgqk,khe->qhge", jax.nn.softmax(scores, -1), v,
                      precision="highest").reshape(s, h, d)


@pytest.mark.parametrize("seq,block_len", [(16, 4), (40, 4), (24, 8),
                                           (12, 4), (64, 32)])
@pytest.mark.parametrize("layout", ["bshd", "bhsd"])
def test_block_causal_flash_prefill_matches_reference_attention(
        seq, block_len, layout):
    q, k, v = _qkv(seq, 4, 2, seed=seq)
    ke, ve = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)  # GQA
    want = _reference_attention(q[0], k[0], v[0], block_len)
    t = (lambda a: a.transpose(0, 2, 1, 3)) if layout == "bhsd" \
        else (lambda a: a)
    got = t(flash_attention(t(q), t(ke), t(ve), causal=True, layout=layout,
                            block_len=block_len, interpret=True,
                            block_q=16, block_k=16))
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-4)
    xla = dot_product_attention(q, ke, ve, causal=True, block_len=block_len)
    np.testing.assert_allclose(xla[0], want, rtol=2e-4, atol=2e-4)


def test_causal_flash_is_untouched_by_the_block_mask():
    """The causal specialisation still is causal attention."""
    q, k, v = _qkv(32, 2, 2)
    got = flash_attention(q, k, v, causal=True, interpret=True,
                          block_q=16, block_k=16)
    want = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    with pytest.raises(ValueError, match="block_len requires"):
        flash_attention(q, k, v, causal=False, block_len=4, interpret=True)


@pytest.mark.parametrize("kv_heads,group", [(2, 2), (1, 4), (4, 1)])
@pytest.mark.parametrize("block_len", [4, 8])
def test_full_window_paged_step_matches_reference_attention(
        kv_heads, group, block_len):
    """The paged kernel's full-window mode (interpreted) on scrambled
    pages: every window query sees the cached prefix and all W window
    keys, as the reference's block-causal attention does for a block."""
    page_len, n_pages, dh, slots = 8, 12, 16, 3
    heads = kv_heads * group
    starts = np.array([8, 0, 16])           # block starts, whole blocks
    rng = np.random.default_rng(0)
    table = np.full((slots, 4), n_pages, np.int32)
    perm = rng.permutation(n_pages)
    k_pages = np.zeros((n_pages, kv_heads, page_len, dh), np.float32)
    v_pages = np.zeros_like(k_pages)
    q = np.zeros((slots, block_len, kv_heads, group, dh), np.float32)
    want = []
    for s in range(slots):
        total = starts[s] + block_len
        qs, ks, vs = _qkv(total, heads, kv_heads, dh, seed=10 + s)
        for lp in range(-(-total // page_len)):
            pid = perm[s * 4 + lp]
            table[s, lp] = pid
            n = min(page_len, total - lp * page_len)
            k_pages[pid, :, :n] = np.asarray(
                ks[0, lp * page_len:lp * page_len + n]).transpose(1, 0, 2)
            v_pages[pid, :, :n] = np.asarray(
                vs[0, lp * page_len:lp * page_len + n]).transpose(1, 0, 2)
        q[s] = np.asarray(qs[0, starts[s]:]).reshape(
            block_len, kv_heads, group, dh)
        want.append(_reference_attention(qs[0], ks[0], vs[0],
                                         block_len)[starts[s]:])
    got = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
        jnp.asarray(starts, jnp.int32), jnp.asarray(table),
        full_window=True, interpret=True)
    got = np.asarray(got).reshape(slots, block_len, heads, dh)
    np.testing.assert_allclose(got, np.stack(want), rtol=2e-4, atol=2e-4)
    with pytest.raises(ValueError, match="full_window"):
        paged_decode_attention(
            jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(starts, jnp.int32), jnp.asarray(table),
            full_window=True, window=4, interpret=True)


def test_model_forward_matches_the_reference_forward():
    model = build()
    tokens = np.random.default_rng(0).integers(0, MASK, 16)
    got, _ = model.module.apply(model.params, model.state,
                                jnp.asarray(tokens)[None])
    want = ref.logits_at(reference_tree(model.params), CFG, tokens,
                         np.arange(16))
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-4)


# --- the engine ---------------------------------------------------------------

#: a pass's log-probabilities against the reference's, float32 on the CPU
#: (the cache and the grouped product reassociate sums; nothing is rounded)
CONF_TOLERANCE = 5e-4


def serve(model, requests, *, steps=4, num_slots=3, page_len=8, num_pages=None,
          submit_at=None, decode_kernel="auto", between=None, overlap=True,
          confidences=False):
    """Serve ``[(prompt, max_new_tokens)]``; returns the engine, the finished
    requests by index and (``confidences``) per request id the
    log-probabilities the engine fixed its tokens at: the most confident
    masked position of each of its denoising passes."""
    eng = ServingEngine(model, num_slots=num_slots, max_len=64,
                        page_len=page_len, num_pages=num_pages,
                        mask_token=MASK, denoising_steps=steps,
                        decode_kernel=decode_kernel, overlap=overlap)
    fixed_conf = _spy_confidences(eng) if confidences else None
    submit_at = submit_at or [0] * len(requests)
    rids, done, it = {}, {}, 0
    while len(done) < len(requests):
        for i, (prompt, n) in enumerate(requests):
            if submit_at[i] == it:
                rids[eng.submit(prompt, n, **(
                    {"priority": 0} if between and i == between else {}))] = i
        for r in eng.step():
            done[rids[r.rid]] = r
        it += 1
        assert it < 500
    assert eng._pending is None            # nothing left in flight
    return eng, done, fixed_conf


def _spy_confidences(eng):
    """The choice is made inside ``serving_denoise`` and its confidences
    never reach the host: recompute them beside every denoise program, from
    the arguments it is called with (the plain pass with the head over the
    same blocks, pool and tables), before the program takes the pool."""
    module, page_len = eng.module, eng.page_len
    conf_of = jax.jit(lambda params, state, cache, toks, t, tables:
                      block_pass_slots_paged(module, params, state, cache,
                                             toks, t, tables, page_len)[1])
    fixed_conf, block_fn = {}, eng._block_fn

    def spied(head):
        fn = block_fn(head)
        if not head:
            return fn

        def denoise(params, state, cache, toks, masked, fixed_pass, ctl,
                    rows, tables):
            t, n_fix, _, ovr = np.asarray(ctl)
            over = (ovr != 0)[:, None]
            now_masked = np.where(over, np.asarray(rows[1]) != 0, masked)
            conf = np.asarray(conf_of(
                params, state, cache, jnp.where(over, rows[0], toks),
                jnp.asarray(t), tables))
            for slot, req in eng.scheduler.running.items():
                if n_fix[slot]:
                    fixed_conf.setdefault(req.rid, []).append(
                        np.where(now_masked[slot], conf[slot], -np.inf).max())
            return fn(params, state, cache, toks, masked, fixed_pass, ctl,
                      rows, tables)
        return denoise

    eng._block_fn = spied
    return fixed_conf


def check_against_reference(model, requests, done, steps=4, fixed_conf=None):
    w = reference_tree(model.params)
    for i, (prompt, n) in enumerate(requests):
        tokens, fixed_pass, trajectory = ref.generate(
            w, CFG, prompt, n, MASK, steps=steps)
        assert done[i].generated == tokens, i
        assert done[i].fixed_pass == fixed_pass, i
        if fixed_conf is not None and 4 % steps == 0 and steps == 4:
            want = [ref.confidences(lg)[1][np.array(pos) - start].max()
                    for start, pos, _, lg in trajectory]
            got = fixed_conf[done[i].rid][:len(want)]
            np.testing.assert_allclose(got, want[:len(got)],
                                       atol=CONF_TOLERANCE)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, MASK, n) for n in lengths]


@pytest.mark.parametrize("name,lengths,outputs", [
    ("whole_blocks", [8, 16], [8, 12]),
    ("prompt_ends_mid_block", [10, 21], [9, 6]),
    ("prompt_under_one_block", [3, 1], [6, 5]),
    ("output_not_a_multiple_of_the_block", [8, 12], [5, 7]),
    ("one_token", [9], [1]),
])
@pytest.mark.parametrize("overlap", [True, False],
                         ids=["overlap", "wait"])
def test_engine_follows_the_reference_trajectory(name, lengths, outputs,
                                                 overlap):
    model = build()
    requests = list(zip(_prompts(lengths), outputs))
    eng, done, conf = serve(model, requests, overlap=overlap,
                            confidences=True)
    check_against_reference(model, requests, done, fixed_conf=conf)
    assert all(len(done[i].generated) == n for i, n in enumerate(outputs))
    paths = eng.health()["programs"]
    assert "moe=grouped_xla_reference" in paths["denoise"]
    assert "kv_cache=donated" in paths["denoise"]


@pytest.mark.parametrize("steps", [1, 2, 3])
@pytest.mark.parametrize("overlap", [True, False],
                         ids=["overlap", "wait"])
def test_engine_follows_the_reference_with_fewer_denoising_steps(steps,
                                                                 overlap):
    model = build()
    requests = list(zip(_prompts([10, 8]), [9, 8]))
    eng, done, _ = serve(model, requests, steps=steps, overlap=overlap)
    check_against_reference(model, requests, done, steps=steps)
    bd = eng.metrics.summary()["block_diffusion"]
    # a block of 4 takes `steps` denoising passes and, unless it is the
    # request's last, one commit pass
    assert bd["tokens_committed"] == 17 and bd["blocks_committed"] == 5
    assert bd["slot_passes"]["denoise"] <= 5 * steps
    assert bd["slot_passes"]["commit"] == 3


@pytest.mark.parametrize("overlap", [True, False],
                         ids=["overlap", "wait"])
def test_slots_sit_at_different_passes_in_one_step(overlap):
    """Requests that arrive two iterations apart denoise different steps of
    different blocks in the same batched pass, and commit beside slots
    that denoise."""
    model = build()
    requests = list(zip(_prompts([8, 12, 16]), [12, 12, 8]))
    eng, done, conf = serve(model, requests, submit_at=[0, 2, 5],
                            overlap=overlap, confidences=True)
    check_against_reference(model, requests, done, fixed_conf=conf)
    bd = eng.metrics.summary()["block_diffusion"]
    assert bd["slot_passes"]["commit"] > bd["passes"]["commit"]


def test_programs_count_the_expert_layers_they_ran():
    """Rows routed and experts touched are the programs' own counts. A
    prefill (no logits) and a pass in which every live slot commits stop
    at the deepest block's K/V write: one expert layer fewer routes."""
    model = build(layers=3)
    eng, done, _ = serve(model, [(_prompts([16])[0], 8)], num_slots=2)
    bd = eng.metrics.summary()["block_diffusion"]
    # two blocks: 4 denoising passes each, and one commit pass between
    assert bd["passes"] == {"denoise": 8, "commit": 1}
    top_k, experts, rows = 2, 8, 2 * 4         # 2 slots of one block each
    assert bd["rows_routed"] == rows * top_k * (8 * 3 + 1 * 2)
    assert bd["prefill_rows_routed"] == 16 * top_k * 2
    assert 2 <= bd["prefill_experts_touched"] <= experts * 2
    assert 8 * 3 + 2 <= bd["experts_touched"] <= experts * (8 * 3 + 2)


@pytest.mark.parametrize("overlap", [True, False],
                         ids=["overlap", "wait"])
def test_prefix_cache_hit_at_a_block_boundary(overlap):
    """A second prompt that shares 18 tokens with a cached one reuses the
    16 of its first page (whole blocks) and matches the reference."""
    model = build()
    first = _prompts([26])[0]
    second = np.concatenate([first[:18], _prompts([7], seed=1)[0]])
    requests = [(first, 6), (second, 7)]
    eng, done, _ = serve(model, requests, submit_at=[0, 12], page_len=8,
                         overlap=overlap)
    check_against_reference(model, requests, done)
    hits = eng.metrics.summary()["prefix_cache"]
    assert hits["hits"] == 1
    assert done[1]._shared_len == 16 and done[1]._shared_len % 4 == 0


@pytest.mark.parametrize("overlap", [True, False],
                         ids=["overlap", "wait"])
def test_preemption_mid_block_resumes_on_the_reference_trajectory(overlap):
    """A pool too small for both streams: the urgent arrival preempts the
    running one mid-block; it re-prefills its whole blocks, opens the
    block again and ends where the reference ends."""
    model = build()
    requests = list(zip(_prompts([16, 24]), [16, 6]))
    eng, done, _ = serve(model, requests, num_slots=2, page_len=4,
                         num_pages=10, submit_at=[0, 6], between=1,
                         overlap=overlap)
    check_against_reference(model, requests, done)
    assert done[0].n_preempted >= 1


def test_engine_serves_through_the_interpreted_kernels():
    """The paged kernel's full-window mode and the grouped kernel,
    interpreted, serve the same tokens as the reference."""
    model = build()
    requests = list(zip(_prompts([10, 8]), [6, 8]))
    with moe_kernels.force_interpret():
        eng, done, _ = serve(model, requests, decode_kernel="paged")
    check_against_reference(model, requests, done)
    paths = eng.health()["programs"]
    assert "paged_attention=kernel" in paths["denoise"]
    assert "moe=grouped_kernel" in paths["denoise"]
    assert "moe=grouped_kernel" in paths["prefill"]


@pytest.mark.parametrize("overlap", [True, False],
                         ids=["overlap", "wait"])
def test_stop_token_ends_a_stream_inside_a_block(overlap):
    model = build()
    prompt = _prompts([8])[0]
    whole = serve(model, [(prompt, 12)], overlap=overlap)[1][0]
    stop = whole.generated[5]
    eng = ServingEngine(model, num_slots=1, max_len=64, page_len=8,
                        mask_token=MASK, overlap=overlap)
    eng.submit(prompt, 12, stop_token=stop)
    (req,) = [r for _ in range(40) for r in eng.step()]
    n = whole.generated.index(stop) + 1
    assert req.generated == whole.generated[:n]
    assert req.fixed_pass == whole.fixed_pass[:n]
    tokens, fixed_pass, _ = ref.generate(reference_tree(model.params), CFG,
                                         prompt, 12, MASK, steps=4)
    assert (req.generated, req.fixed_pass) == (tokens[:n], fixed_pass[:n])


# --- the pipelined pass -------------------------------------------------------

def _host_choice(toks, masked, fixed_pass, best, conf, n_fix, step):
    """The rule as the host applied it: a stable sort of the masked
    positions by falling confidence, the first ``n_fix`` fixed."""
    toks, masked, fixed_pass = toks.copy(), masked.copy(), fixed_pass.copy()
    for s in range(len(toks)):
        order = np.argsort(np.where(masked[s], -conf[s], np.inf),
                           kind="stable")
        for pos in order[:n_fix[s]]:
            toks[s, pos], fixed_pass[s, pos] = best[s, pos], step[s]
            masked[s, pos] = False
    return toks, masked, fixed_pass


@pytest.mark.parametrize("name,conf,masked,n_fix,fixed", [
    ("all_equal", [-1., -1., -1., -1.], [1, 1, 1, 1], 2, [0, 1]),
    ("equal_behind_the_best", [-2., -1., -2., -2.], [1, 1, 1, 1], 3,
     [0, 1, 2]),
    ("equal_among_the_masked", [0., -3., -3., -3.], [0, 1, 0, 1], 1, [1]),
    ("nothing_to_fix", [-1., -1., -1., -1.], [1, 1, 1, 1], 0, []),
    ("certain_and_impossible", [-np.inf, 0., 0., -np.inf], [1, 1, 1, 1], 3,
     [0, 1, 2]),
])
def test_equal_confidences_go_to_the_earliest_position(name, conf, masked,
                                                       n_fix, fixed):
    """The program's choice on planted ties is the stable sort's."""
    args = (np.full((1, 4), MASK, np.int32), np.array([masked], bool),
            np.full((1, 4), -1, np.int32), np.arange(10, 14)[None].astype(
                np.int32), np.array([conf], np.float32),
            np.array([n_fix], np.int32), np.array([2], np.int32))
    got = [np.asarray(a) for a in
           fix_most_confident(*map(jnp.asarray, args))]
    for a, b in zip(got, _host_choice(*args)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.nonzero(got[2][0] == 2)[0], fixed)
    np.testing.assert_array_equal(got[0][0][fixed], 10 + np.array(fixed, int))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_programs_choice_is_the_stable_sorts(seed):
    """Many slots at once, confidences drawn from a handful of values so
    that most rows hold ties, some of them infinite or not a number."""
    rng = np.random.default_rng(seed)
    s, b = 64, 8
    conf = rng.choice(np.array([-0.25, -0.5, -1., -2., -np.inf, np.nan],
                               np.float32), (s, b))
    masked = rng.random((s, b)) < 0.6
    args = (rng.integers(0, 90, (s, b)).astype(np.int32), masked,
            rng.integers(-1, 3, (s, b)).astype(np.int32),
            rng.integers(100, 190, (s, b)).astype(np.int32), conf,
            np.minimum(rng.integers(0, 5, s), masked.sum(1)).astype(np.int32),
            rng.integers(0, 4, s).astype(np.int32))
    got = fix_most_confident(*map(jnp.asarray, args))
    for a, b_ in zip(got, _host_choice(*args)):
        np.testing.assert_array_equal(np.asarray(a), b_)


def _watch(eng):
    """The order of the engine's dispatches and reads: ``("pass", n)`` when
    the n-th block program is called, ``("read", n)`` when the n-th
    ``_fetch`` starts."""
    events, n = [], {"pass": 0, "read": 0}
    block_fn, fetch = eng._block_fn, eng._fetch

    def note(kind, fn):
        def noted(*args):
            events.append((kind, n[kind]))
            n[kind] += 1
            return fn(*args)
        return noted

    eng._block_fn = lambda head: note("pass", block_fn(head))
    eng._fetch = note("read", fetch)
    return events


def test_the_next_pass_is_dispatched_before_the_last_one_is_read():
    """A steady batch: pass N+1 goes to the device before pass N's results
    are asked for, every pass but the first finds its predecessor unread,
    and the synchronous engine reads each pass before the next."""
    model = build()
    requests = list(zip(_prompts([8, 13]), [12, 8]))
    for overlap in (True, False):
        eng = ServingEngine(model, num_slots=2, max_len=64, page_len=8,
                            mask_token=MASK, overlap=overlap)
        events = _watch(eng)
        for prompt, n in requests:
            eng.submit(prompt, n)
        while eng.scheduler.pending:
            eng.step()
        bd = eng.metrics.summary()["block_diffusion"]
        passes = sum(bd["passes"].values())
        at = {e: i for i, e in enumerate(events)}
        assert len(events) == 2 * passes and passes > 8
        if overlap:
            assert all(at["pass", n + 1] < at["read", n]
                       for n in range(passes - 1))
            assert bd["passes_overlapped"] == passes - 1
        else:
            assert all(at["read", n] < at["pass", n + 1]
                       for n in range(passes - 1))
            assert bd["passes_overlapped"] == 0
        assert eng.health()["block_diffusion"]["passes_overlapped"] \
            == bd["passes_overlapped"]


def test_a_stop_token_costs_one_discarded_pass_and_no_stale_rows():
    """Under ``overlap`` a stop token is seen one pass late: the stream ends
    on the same token, its slot rode one more pass (counted, thrown away),
    and the request that takes the slot over in between starts from its
    own block, beside a stream that never stopped."""
    model = build()
    prompts = _prompts([8, 16, 12])
    plain = serve(model, [(prompts[0], 12)], overlap=False)[1][0]
    stop = plain.generated[5]                  # inside the second block
    requests = [(prompts[0], 12), (prompts[1], 24), (prompts[2], 8)]
    served, rides = {}, {}
    for overlap in (True, False):
        eng = ServingEngine(model, num_slots=2, max_len=64, page_len=8,
                            mask_token=MASK, overlap=overlap)
        rids = [eng.submit(p, n, **({"stop_token": stop} if i == 0 else {}))
                for i, (p, n) in enumerate(requests)]
        done = {r.rid: r for _ in range(80) for r in eng.step()}
        assert sorted(done) == rids and eng._pending is None
        served[overlap] = [done[r] for r in rids]
        bd = eng.metrics.summary()["block_diffusion"]
        assert bd["slot_passes_discarded"] == int(overlap)
        rides[overlap] = sum(bd["slot_passes"].values())
        assert eng.health()["block_diffusion"]["slot_passes_discarded"] \
            == int(overlap)
        # the third request waited for the stopped stream's slot
        assert done[rids[2]].slot == done[rids[0]].slot
    n = plain.generated.index(stop) + 1
    for a, b in zip(served[True], served[False]):
        assert (a.generated, a.fixed_pass) == (b.generated, b.fixed_pass)
    assert served[True][0].generated == plain.generated[:n]
    check_against_reference(model, requests[1:], dict(enumerate(
        served[True][1:])))
    # one pass more than the synchronous engine's, and no more
    assert rides[True] == rides[False] + 1


def test_a_budgets_end_is_known_ahead():
    """A request that ends by its budget rides no pass after its last
    block: nothing is discarded, and the slots ride as many passes a token
    as in the synchronous engine."""
    model = build()
    requests = list(zip(_prompts([8, 10, 3]), [12, 9, 5]))
    counts = {}
    for overlap in (True, False):
        eng, done, _ = serve(model, requests, num_slots=2, overlap=overlap)
        bd = eng.metrics.summary()["block_diffusion"]
        assert bd["slot_passes_discarded"] == 0
        counts[overlap] = (bd["slot_passes"], bd["tokens_committed"],
                           bd["blocks_committed"])
        assert not eng.block_positions()
    assert counts[True] == counts[False]
    assert counts[True][1] == 12 + 9 + 5


def test_the_programs_keep_their_paths_and_compile_once():
    """``denoise`` (with the choice inside) and ``commit`` hold what they
    held, and a second run of the same lengths compiles nothing."""
    model = build()
    # each alone in the batch: its commit pass has no slot that needs a head
    requests = [(_prompts([8], seed=seed)[0], 8) for seed in (0, 1)]
    done = {}
    with moe_kernels.force_interpret():
        eng = ServingEngine(model, num_slots=2, max_len=64, page_len=8,
                            mask_token=MASK, decode_kernel="paged")
        for i, request in enumerate(requests):
            compiled = obs.compile_totals()["count"]
            eng.submit(*request)
            (done[i],) = [r for _ in range(40) for r in eng.step()]
            if i:
                assert obs.compile_totals()["count"] == compiled
    check_against_reference(model, requests, done)
    paths = eng.health()["programs"]
    for name in ("denoise", "commit"):
        assert paths[name] == ("kv_cache=donated, moe=grouped_kernel, "
                               "paged_attention=kernel"), paths
    assert eng.metrics.summary()["block_diffusion"]["passes"]["commit"] == 2


@pytest.mark.parametrize("kwargs,match", [
    (dict(), "needs mask_token"),
    (dict(mask_token=MASK, denoising_steps=5), "denoising_steps must be"),
    (dict(mask_token=MASK, page_len=6), "whole multiples"),
    (dict(mask_token=MASK, fuse_steps=2), "without draft, fuse_steps"),
])
def test_engine_states_what_block_diffusion_needs(kwargs, match):
    with pytest.raises(ValueError, match=match):
        ServingEngine(build(), num_slots=1, max_len=64, **kwargs)


def test_causal_engines_and_generate_keep_apart():
    model = build()
    with pytest.raises(ValueError, match="block-causal"):
        generate(model, np.zeros((1, 4), np.int32), 4)
    eng = ServingEngine(model, num_slots=1, max_len=64, mask_token=MASK)
    with pytest.raises(ValueError, match="greedily"):
        eng.submit(np.arange(4), 4, temperature=0.7)
    causal = Model.build(zoo.transformer_lm(VOCAB, d_model=32, num_heads=4,
                                            num_layers=1), (8,), seed=0)
    with pytest.raises(ValueError, match="block-causal model"):
        ServingEngine(causal, num_slots=1, max_len=32, mask_token=MASK)
