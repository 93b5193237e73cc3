"""Latent attention (MLA), the shortcut-connected expert layer and one chip's
share of the experts: the served path against the plain reference
(``benchmarks/reference/longcat.py``: float32 ``jax.numpy``, non-absorbed
attention, no kernel, no cache) at the ``rehearse`` sizes of
``benchmarks/configs/longcat-flash-chat.json`` (3 double layers, 4 heads,
ranks 16 / 8, 8 experts + 4 identity ones of which 2 are held, top-3), on
seeded random weights. Logits, not tokens.

Tolerances. Model and reference compute in float32 here, on the same float32
weights; what is left is the order of sums (absorbed against non-absorbed
attention, chunked and paged softmax against one softmax, the grouped layout
against a dense sum), which moves a logit by under 3e-7 at this size. ``TOL``
is seventy times that.
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

from harness import common, longcat_family as fam  # noqa: E402
from reference import longcat  # noqa: E402

from distkeras_tpu.models import zoo  # noqa: E402
from distkeras_tpu.models.attention import (LatentAttention,  # noqa: E402
                                            TransformerBlock)
from distkeras_tpu.models.decoding import generate  # noqa: E402
from distkeras_tpu.models.moe import MoE  # noqa: E402
from distkeras_tpu.ops import moe_kernels  # noqa: E402
from distkeras_tpu.ops.paged_attention import (  # noqa: E402
    paged_latent_attention, paged_latent_attention_reference)
from distkeras_tpu.serving import NgramDraft, ServingEngine  # noqa: E402
from distkeras_tpu.serving.scheduler import RequestState  # noqa: E402

TOL = 2e-5
SEED = 11

with open(os.path.join(ROOT, "benchmarks", "configs",
                       "longcat-flash-chat.json")) as f:
    CFG = common.config_for(json.load(f), rehearse=True)
S = fam.sizes(CFG)
RCFG = fam.reference_cfg(CFG)


@pytest.fixture(scope="module")
def model():
    return fam.build_model(CFG, SEED, 256, dtype=jnp.float32)


@pytest.fixture(scope="module")
def leaves():
    return fam.make_leaves(CFG, SEED, jnp.float32)


@pytest.fixture(scope="module")
def ref_weights(leaves):
    return fam.reference_tree(leaves, S)


def _engine(model, **kw):
    kw = {"num_slots": 3, "max_len": 256, "page_len": 8,
          "prefill_chunk": 16, **kw}
    return ServingEngine(model, **kw)


def _prompt(n, seed=0, head=()):
    rng = np.random.default_rng(seed)
    out = rng.integers(0, S["vocab"], n).astype(np.int32)
    out[:len(head)] = head
    return out


def _reference_logits(w, tokens, positions, cfg=RCFG, **kw):
    seq = np.zeros(-(-len(tokens) // longcat.Q_BLOCK) * longcat.Q_BLOCK,
                   np.int32)
    seq[:len(tokens)] = tokens
    return np.asarray(longcat.logits_at(w, cfg, seq, np.asarray(positions),
                                        **kw))


def _to_decoding(eng, rid):
    while eng[rid].state is not RequestState.DECODING:
        eng.step()
    return eng[rid]


def _decode_logits(eng, req):
    """Logits of the step ``req`` takes next, and the tokens they follow."""
    lg = eng.decode_logits()[req.slot]       # drains the step in flight
    return lg, np.concatenate([req.prompt, req.generated])


def _worst_gap(eng, req, ref_weights, steps, every=3):
    worst = 0.0
    for step in range(steps):
        if step % every == 0:
            got, toks = _decode_logits(eng, req)
            want = _reference_logits(ref_weights, toks, [len(toks) - 1])[0]
            worst = max(worst, float(np.abs(got - want).max()))
        eng.step()
    return worst


# --- the model is the configuration --------------------------------------------

def test_model_is_the_configuration_at_its_rehearse_sizes(model):
    blocks = model.module.layers[1:-2]
    assert len(blocks) == 6                       # two a double layer
    assert all(isinstance(b.attn, LatentAttention) for b in blocks)
    assert [b.shortcut is not None for b in blocks] == [True, False] * 3
    assert [b.shortcut_add for b in blocks] == [False, True] * 3
    attn = blocks[0].attn
    assert (attn.latent_dim, attn.head_dim, attn.rope_base) == (16, 24, 1e7)
    assert attn.q_scale == 2.0 and attn.kv_scale == pytest.approx(8 ** 0.5)
    moe = blocks[0].shortcut
    assert (moe.router_dim, moe.num_held, moe.top_k, moe.route_scale,
            moe.norm_topk, moe.select_bias, moe.dispatch) \
        == (12, 2, 3, 6.0, False, True, "grouped")
    assert all(b.norm1.epsilon == 1e-5 for b in blocks)


def test_full_forward_equals_the_reference(model, ref_weights):
    """``Sequential.apply`` (non-absorbed attention, the expert layer handed
    from block to block beside the stream) against the reference."""
    toks = _prompt(40, 3)
    got, _ = model.module.apply(model.params, model.state,
                                jnp.asarray(toks)[None])
    want = _reference_logits(ref_weights, toks, np.arange(40))
    assert float(np.abs(np.asarray(got[0]) - want).max()) < TOL


def test_block_config_round_trips(model):
    blk = model.module.layers[1]
    again = TransformerBlock.from_config(blk.get_config())
    assert isinstance(again.attn, LatentAttention)
    assert again.attn.get_config() == blk.attn.get_config()
    assert again.shortcut.get_config() == blk.shortcut.get_config()
    assert again.norm_eps == 1e-5 and not again.shortcut_add
    assert TransformerBlock.from_config(
        model.module.layers[2].get_config()).shortcut_add


# --- served logits -------------------------------------------------------------

@pytest.mark.parametrize("kernel", ["off", "paged"],
                         ids=["gather", "paged_kernel"])
def test_prefill_then_decode_equals_the_reference(model, ref_weights, kernel):
    """Chunked prefill (non-absorbed, over the cached latent prefix), then
    decode (absorbed, through latent pages): at every checked step the
    served logits are the reference's full forward over the tokens so far."""
    eng = _engine(model, decode_kernel=kernel)
    req = _to_decoding(eng, eng.submit(_prompt(40, 1), 24))
    assert _worst_gap(eng, req, ref_weights, 20) < TOL
    path = "kernel" if kernel == "paged" else "gather_reference"
    health = eng.health()
    assert f"paged_attention={path}" in health["programs"]["decode_greedy"]
    # 256 positions in pages of 8: the kernel reads a slot's row in one program
    assert ("latent_pages_per_program=32"
            in health["programs"]["decode_greedy"]) == (kernel == "paged")
    assert list(health["kv_groups"]) == ["latent"]
    planes = {kv["c"].shape for kv in eng.pool.cache if kv is not None}
    assert planes == {(eng.pool.num_pages, S["latent"], 8)}
    assert eng.pool.latent and eng.pool.page_bytes == 6 * S["latent"] * 8 * 4


def test_absorbed_decode_equals_non_absorbed(model):
    """One attention layer, one new token over a cached context: the
    absorbed form over the latent against the layer's own ``apply`` (keys
    and values rebuilt a head) at the last position."""
    blk = model.module.layers[1]
    attn, p = blk.attn, model.params[1]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, S["d"]))
    want, _ = attn.apply(p, {}, x)
    qn, qr, entry = attn.project(p, x)
    q = attn.absorb_q(p, qn, qr)[:, -1:]                  # [1, 1, H, C]
    pages = entry[0].reshape(3, 8, -1).transpose(0, 2, 1)  # 3 pages of 8
    o = paged_latent_attention_reference(
        q, pages, jnp.array([23]), jnp.array([[0, 1, 2]]),
        v_dim=attn.kv_lora_rank, scale=attn.scale)
    got = attn.unabsorb_v(p, o, jnp.float32)
    assert float(jnp.abs(got[0, 0] - want[0, -1]).max()) < 1e-5


def test_chunked_prefill_equals_whole_prefill(model, ref_weights):
    """A prompt in chunks of 16 over the cached latent prefix against the
    one-pass prefill, both against the reference."""
    prompt = _prompt(52, 2)
    want = _reference_logits(ref_weights, prompt, [51])[0]
    for chunk in (16, None):
        eng = _engine(model, prefill_chunk=chunk)
        req = _to_decoding(eng, eng.submit(prompt, 4))
        got = eng.decode_logits()[req.slot]
        toks = np.concatenate([req.prompt, req.generated])
        step_want = _reference_logits(ref_weights, toks, [len(toks) - 1])[0]
        assert float(np.abs(got - step_want).max()) < TOL, chunk
        # the first token came from the prefill's own logits
        assert int(req.generated[0]) == int(want.argmax())


def test_prefix_hit_equals_a_cold_engine(model, ref_weights):
    """Three prompts behind one 32-token document: the later ones take its
    latent pages from the prefix cache and prefill only their question."""
    head = _prompt(32, 7)
    eng = _engine(model, prefix_granularity=8)
    worst = 0.0
    for i in range(3):
        prompt = _prompt(44 + 8 * i, 20 + i, head=head)
        req = _to_decoding(eng, eng.submit(prompt, 6))
        shared = int(getattr(req, "_shared_len", 0) or 0)
        assert shared == (32 if i else 0)
        worst = max(worst, _worst_gap(eng, req, ref_weights, 4, every=2))
        while eng.scheduler.pending:
            eng.step()
    assert worst < TOL
    assert eng.metrics.summary()["prefix_cache"]["hits"] >= 2


def test_preemption_and_resume_on_latent_pages(model, ref_weights):
    """Two requests whose worst cases sum past a tight pool: one is
    preempted, its latents re-prefilled at resume, and what both are served
    is still the reference's argmax at every position."""
    eng = _engine(model, num_slots=2, num_pages=12, prefix_cache=False,
                  prefill_chunk=None)
    prompts = [_prompt(30, 40), _prompt(30, 41)]
    rids = [eng.submit(p, 30) for p in prompts]
    done = {}
    for _ in range(400):
        for r in eng.step():
            done[r.rid] = r
        if len(done) == 2:
            break
    assert eng.metrics.summary()["requests_preempted"] >= 1
    for rid, prompt in zip(rids, prompts):
        r = done[rid]
        assert r.state is RequestState.FINISHED and len(r.generated) == 30
        toks = np.concatenate([prompt, r.generated])
        want = _reference_logits(ref_weights, toks,
                                 np.arange(29, 59))
        gap = want.max(-1) - want[np.arange(30), np.asarray(r.generated)]
        assert float(gap.max()) < TOL


def test_counters_split_the_rows_by_where_they_went(model):
    eng = _engine(model)
    req = _to_decoding(eng, eng.submit(_prompt(40, 5), 10))
    for _ in range(8):
        eng.step()
    eng.decode_logits()
    r = eng.metrics.summary()["routing"]
    for pre in ("", "prefill_"):
        assert r[pre + "rows_held"] + r[pre + "rows_absent"] \
            + r[pre + "rows_zero"] == r[pre + "rows_routed"] > 0
    # the experts touched are HELD ones: at most 2 a layer and program
    assert r["experts_touched"] <= 2 * 3 * r["decode_programs"]
    assert r["prefill_rows_routed"] == 40 * 3 * 3      # tokens x top-k x layers
    assert eng.metrics.summary()["kv_groups"]["latent"]["pages_live"] \
        == eng.pool.pages_for(len(req.prompt) + len(req.generated) + 1)


# --- the router and the share ---------------------------------------------------

def _moe_pair(leaves, dispatch, held=(0, 2), layer=0):
    """The program's expert layer of double layer ``layer`` and the
    reference's weights and configuration for it, holding ``held``."""
    lo, n = held
    moe = MoE(S["experts"], S["ffn"], top_k=S["top_k"], activation="silu",
              dispatch=dispatch, gated=True, use_bias=False, norm_topk=False,
              route_scale=S["route_scale"], zero_experts=S["zero_experts"],
              experts_held=held, select_bias=True,
              # ``apply`` of the tokens dispatch has a capacity (training's
              # rule): wide enough here that nothing is dropped, as the
              # serving path (``decode_apply``) never drops
              capacity_factor=8.0)
    key = jax.random.fold_in(jax.random.PRNGKey(5), 17)
    full = {name: 0.3 * jax.random.normal(jax.random.fold_in(key, i), shape)
            for i, (name, shape) in enumerate(
                [("eg", (S["experts"], S["d"], S["ffn"])),
                 ("eu", (S["experts"], S["d"], S["ffn"])),
                 ("ed", (S["experts"], S["ffn"], S["d"]))])}
    router = 0.5 * jax.random.normal(jax.random.fold_in(key, 9),
                                     (S["d"], 12))
    bias = 0.05 * jax.random.normal(jax.random.fold_in(key, 10), (12,))
    sl = slice(lo, lo + n)
    params = {"gate": router, "select_bias": bias, "w1": full["eg"][sl],
              "w3": full["eu"][sl], "w2": full["ed"][sl]}
    lw = {"router": router, "bias": bias, "eg": full["eg"][sl],
          "eu": full["eu"][sl], "ed": full["ed"][sl]}
    return moe, params, lw, {**RCFG, "held": [lo, n]}


@pytest.mark.parametrize("dispatch", ["dense", "tokens", "grouped"])
def test_router_and_share_equal_the_reference(leaves, dispatch):
    """Softmax over all 12 outputs, the bias chooses and does not weight,
    no normalisation over the chosen, scale 6, identity experts, two
    experts held: ``apply`` and the serving ``decode_apply`` against the
    reference's plain sum."""
    moe, params, lw, rcfg = _moe_pair(leaves, dispatch)
    u = jax.random.normal(jax.random.PRNGKey(8), (2, 24, S["d"]))
    want = np.asarray(longcat._moe(u.reshape(48, -1), lw, rcfg, "float32"))
    got, _ = moe.apply(params, {}, u)
    assert float(np.abs(np.asarray(got).reshape(48, -1) - want).max()) < 1e-4
    served, (topi, full) = moe.decode_apply(params, u, return_routing=True)
    assert float(np.abs(np.asarray(served).reshape(48, -1) - want).max()) \
        < 1e-4
    # the choice is by score + bias; the weight is the score alone
    gates = np.asarray(longcat.route(u.reshape(48, -1), lw["router"],
                                     lw["bias"], rcfg))
    chosen = np.sort(np.asarray(topi).reshape(48, -1), axis=-1)
    assert (chosen == np.sort(np.argsort(-(np.asarray(full).reshape(48, -1)
                                           + np.asarray(lw["bias"])),
                                         axis=-1)[:, :3], axis=-1)).all()
    np.testing.assert_allclose(
        np.take_along_axis(gates, chosen, -1),
        6.0 * np.take_along_axis(np.asarray(full).reshape(48, -1), chosen,
                                 -1), rtol=1e-5)
    assert (gates.sum(-1) < 6.0).all()            # nothing renormalised
    # without the bias other experts are chosen: the control has to see it
    plain = np.asarray(longcat.route(u.reshape(48, -1), lw["router"],
                                     lw["bias"],
                                     {**rcfg, "use_select_bias": False}))
    assert ((plain > 0) != (gates > 0)).any()


@pytest.mark.parametrize("dispatch", ["dense", "tokens", "grouped"])
def test_the_shares_add_up_to_the_uncut_layer(leaves, dispatch):
    """The routed parts of all 8 / 2 shares plus the identity part counted
    once equal the layer that holds every expert."""
    u = jax.random.normal(jax.random.PRNGKey(9), (1, 40, S["d"]))
    uncut, params_all, lw_all, rcfg_all = _moe_pair(leaves, dispatch,
                                                    held=(0, 8))
    want, _ = uncut.apply(params_all, {}, u)
    gates = longcat.route(u[0], lw_all["router"], lw_all["bias"], rcfg_all)
    identity = np.asarray(jnp.sum(gates[:, 8:], -1, keepdims=True) * u[0])
    total = np.zeros_like(identity)
    for lo in range(0, 8, 2):
        moe, params, _lw, _r = _moe_pair(leaves, dispatch, held=(lo, 2))
        out, _ = moe.apply(params, {}, u)
        total += np.asarray(out[0]) - identity       # the share's routed part
    assert float(np.abs(total + identity - np.asarray(want[0])).max()) < 1e-4
    assert float(np.abs(identity).max()) > 1e-3      # and it is not nothing


def test_grouped_layout_drops_rows_of_absent_experts():
    ids = jnp.array([0, 3, 3, 1, 4, 4, 0, 3, 2, 4, 4, 1], jnp.int32)
    held = ids < 4
    dest, tile_expert, used, counts = moe_kernels.grouped_layout(
        ids, 4, 16, valid=held)
    rows = moe_kernels.grouped_tiles(12, 4, 16) * 16
    dest = np.asarray(dest)
    assert (dest[np.asarray(held)] < rows).all()
    assert (dest[~np.asarray(held)] >= rows).all()
    assert len(set(dest.tolist())) == 12             # each its own
    assert np.asarray(counts).tolist() == [2, 2, 1, 3] and int(used) == 4
    assert np.asarray(tile_expert)[:4].tolist() == [0, 1, 2, 3]


def test_grouped_experts_walks_a_wide_expert_in_blocks():
    """An expert too large for VMEM has its hidden width walked in blocks
    by the same tile's further programs (interpreted kernel against the
    plain product), and LongCat's width is such an expert."""
    k = jax.random.PRNGKey(0)
    e, d, f, rows = 4, 32, 256, 16
    ids = jnp.array([0, 3, 3, 1, 0, 3, 2, 1], jnp.int32)
    dest, te, used, _ = moe_kernels.grouped_layout(ids, e, rows)
    m = moe_kernels.grouped_tiles(8, e, rows) * rows
    x = jax.random.normal(k, (m, d))
    w1, w3 = (0.1 * jax.random.normal(jax.random.fold_in(k, i), (e, d, f))
              for i in (1, 2))
    w2 = 0.1 * jax.random.normal(jax.random.fold_in(k, 3), (e, f, d))
    want = moe_kernels.grouped_experts_reference(
        x, te, used, w1, w2, w3, block_rows=rows, activation="silu")
    for block_f in (128, None):
        got = moe_kernels.grouped_experts(
            x, te, used, w1, w2, w3, block_rows=rows, activation="silu",
            interpret=True, block_f=block_f)
        assert float(jnp.abs(got - want).max()) < 1e-5
    assert moe_kernels.grouped_hidden_block(6144, 2048, True,
                                            jnp.bfloat16) == 512
    # the accepted models' experts fit whole: their kernel is as it was
    assert moe_kernels.grouped_hidden_block(2048, 768, True,
                                            jnp.bfloat16) == 768
    assert moe_kernels.grouped_hidden_block(2048, 512, True,
                                            jnp.bfloat16) == 512


# --- kernels ---------------------------------------------------------------------

_SENT = 48          # the table's sentinel: as many pages as the plane has

#: name -> (page_len, table width P, t a slot, pages a slot holds from the
#: front; the rest of its row is the sentinel). At ``page_len`` 128 a program
#: reads G = 8 pages, so P = 11 is a block and three pages of a second one;
#: at 8 the whole row is one program (G = P).
_KERNEL_CASES = {
    # the first test of this kernel: scrambled pages, slots at three depths
    "one_program_a_slot": (8, 4, [5, 17, 28], [2, 3, 4]),
    "table_wider_than_a_block": (8, 11, [5, 60, 85], [1, 8, 11]),
    # P = 11 is no multiple of G = 8: the second block's last five columns
    # are the wrapper's own sentinels
    "table_not_whole_blocks": (128, 11, [200, 1100, 1400], [2, 9, 11]),
    # t in the first page of the second block: its other pages are dead
    "first_page_of_a_block": (128, 11, [1024 + 5, 1024, 3], [9, 9, 1]),
    # t on a page's last position and on the next page's first, inside a
    # block and across two
    "page_edges": (128, 11, [383, 384, 1023, 1024], [4, 4, 9, 9]),
    # allocated pages BEHIND a sentinel in a live block (a row the pool
    # never writes): past the slot's depth, never read
    "sentinel_in_a_live_block": (128, 11, [300, 700], [-3, -6]),
    # a free slot as the engine parks it (t at max_len, the row all
    # sentinels) between two live ones: zeros, no page read
    "slot_with_no_live_page": (128, 11, [130, 11 * 128, 1300], [2, 0, 11]),
}


@pytest.mark.parametrize("w_len", [1, 3])
@pytest.mark.parametrize("case", sorted(_KERNEL_CASES))
def test_paged_latent_attention_equals_its_gather_reference(case, w_len):
    """The kernel in interpreter mode against the gather reference, over
    the shapes a block of G pages a program brings."""
    page_len, width, depths, held = _KERNEL_CASES[case]
    k = jax.random.PRNGKey(0)
    h, c, v = 4, 24, 16
    s = len(depths)
    q = jax.random.normal(k, (s, w_len, h, c))
    pages = jax.random.normal(jax.random.fold_in(k, 1), (_SENT, c, page_len))
    order = np.random.default_rng(3).permutation(_SENT)
    table = np.full((s, width), _SENT, np.int32)
    for i, n in enumerate(held):
        row = order[i * width:i * width + width]
        if n < 0:       # -n pages from the front, a sentinel, pages behind
            table[i] = row
            table[i, -n] = _SENT
        else:
            table[i, :n] = row[:n]
    t = jnp.array(depths, jnp.int32)
    # every window query's position lies in a page the slot holds
    assert all(n <= 0 or (d + w_len - 1) // page_len < n
               for d, n in zip(depths, held))
    got = paged_latent_attention(q, pages, t, jnp.asarray(table), v_dim=v,
                                 scale=0.3, interpret=True)
    want = paged_latent_attention_reference(q, pages, t, jnp.asarray(table),
                                            v_dim=v, scale=0.3)
    assert got.shape == (s, w_len, h, v)
    live = np.array([n != 0 for n in held])
    assert float(jnp.abs(got - want)[live].max()) < 1e-5
    assert not np.asarray(got)[~live].any()


def test_latent_pages_per_program_follows_what_the_kernel_is_handed():
    """G is about 1,024 positions a program, no more than the table is wide
    and no more than fits the VMEM budget at these widths: the cell's shape
    reads 8 pages a program, the rehearsal's one program a slot."""
    from distkeras_tpu.compat import record_paths
    from distkeras_tpu.ops.paged_attention import latent_pages_per_program
    bf16 = jnp.bfloat16
    # the cell: 64 heads, C 576, pages of 128, a table of 128 columns
    assert latent_pages_per_program(128, 128, 576, 64, 512, bf16) == 8
    # its CPU rehearsal (4 heads, C 16, float32, pages of 8, 32 columns)
    # and this file's tiny shapes: the whole row, one program a slot
    assert latent_pages_per_program(8, 32, 16, 8, 8, jnp.float32) == 32
    assert latent_pages_per_program(8, 4, 24, 8, 16, jnp.float32) == 4
    # pages of 1,024 and more: one a program
    assert latent_pages_per_program(2048, 8, 576, 64, 512, bf16) == 1
    # narrow pages are padded to the 128 lanes in VMEM: the budget, not
    # the positions, bounds them, as it does a wide float32 plane's pages
    assert latent_pages_per_program(16, 1024, 576, 64, 512, bf16) == 25
    assert latent_pages_per_program(128, 128, 2048, 64, 512,
                                    jnp.float32) == 3
    # a verify window of 3 x 64 rows still reads 8 pages of 128
    assert latent_pages_per_program(128, 128, 576, 192, 512, bf16) == 8
    with record_paths() as paths:
        paged_latent_attention(
            jnp.zeros((2, 1, 4, 24)), jnp.zeros((6, 24, 128)),
            jnp.array([0, 130]), jnp.array([[0, 6, 6], [1, 2, 6]]),
            v_dim=16, scale=1.0, interpret=True)
    assert paths == {"latent_pages_per_program=3"}


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_with_values_of_another_width(causal):
    from distkeras_tpu.ops.attention import dot_product_attention
    from distkeras_tpu.ops.flash_attention import flash_attention
    k = jax.random.PRNGKey(1)
    q = jax.random.normal(k, (2, 40, 3, 24))
    kk = jax.random.normal(jax.random.fold_in(k, 1), (2, 40, 3, 24))
    v = jax.random.normal(jax.random.fold_in(k, 2), (2, 40, 3, 16))
    got = flash_attention(q, kk, v, causal=causal, interpret=True,
                          block_q=16, block_k=16)
    want = dot_product_attention(q, kk, v, causal=causal)
    assert got.shape == (2, 40, 3, 16)
    assert float(jnp.abs(got - want).max()) < 1e-5


# --- what latent pages are not served with ---------------------------------------

@pytest.mark.parametrize("kw,needle", [
    ({"draft": NgramDraft()}, "draft"),
    ({"fuse_steps": 4}, "fuse_steps"),
    ({"host_kv_pages": 8}, "host_kv_pages"),
    ({"cache_dtype": "int8"}, "int8"),
    ({"cache_dtype": "int4"}, "int4"),
    ({"weight_quant": "int8"}, "weight_quant"),
    ({"hbm_budget": 1 << 30}, "hbm_budget"),
    ({"ep_mesh": object()}, "ep_mesh"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_refusals_name_what_is_refused(model, kw, needle):
    with pytest.raises(ValueError, match=needle):
        _engine(model, **kw)


def test_refusals_outside_the_engine(model):
    with pytest.raises(NotImplementedError, match="ServingEngine"):
        generate(model, _prompt(8)[None], 4)
    latent = dict(q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16)
    with pytest.raises(ValueError, match="block_len"):
        zoo.transformer_lm(64, d_model=32, num_heads=2, num_layers=1,
                           block_len=4, layer_types=["mla"],
                           attn_kinds={"mla": {"latent": latent}})
    with pytest.raises(ValueError, match="experts_held.*needs no axis"):
        MoE(8, 16, dispatch="grouped", use_bias=False,
            expert_axis_name="expert")
    with pytest.raises(ValueError, match="expert_axis_name"):
        MoE(8, 16, experts_held=(0, 2), expert_axis_name="expert")
    with pytest.raises(ValueError, match="not a range"):
        MoE(8, 16, experts_held=(6, 4))
