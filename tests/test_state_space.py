"""State-space (Mamba-2) layers served beside attention, with one chip's
share of the experts and a shared expert: the served path against the
plain reference (``benchmarks/reference/granite.py``: float32 ``jax.numpy``,
the sequential recurrence, no kernel, no cache) at the ``rehearse`` sizes
of ``benchmarks/configs/granite-4.0-h-small.json`` (d_model 64, layers
M M A M, 4 mamba heads of 16 with a state of 16 and scan chunks of 8, 8
experts of which 4 are held, top-2, a vocabulary of 256), on seeded random
weights. Logits, not tokens; and the state group of the page pool: carried
from one prefill chunk to the next, left alone by a decode step while its
slot prefills, zero when a request takes a slot, rebuilt by re-prefill.

Tolerances. Model and reference compute in float32 here, on the same
float32 weights; what is left is the order of sums (the chunked scan
against the recurrence, chunked and paged softmax against one softmax, the
grouped expert layout against a dense sum; the scan kernel's bfloat16
operands where it runs). Logits are of order 5e-3 at this size (the tied
embedding is drawn at 0.002); they move by under 2e-9 on the XLA paths and
by 7.5e-9 through the kernels, and ``TOL`` is thirteen times that.
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

from harness import common, granite_family as fam  # noqa: E402
from reference import granite  # noqa: E402

from distkeras_tpu.models import zoo  # noqa: E402
from distkeras_tpu.models.attention import TransformerBlock  # noqa: E402
from distkeras_tpu.models.decoding import generate  # noqa: E402
from distkeras_tpu.models.layers import Scale, TiedHead  # noqa: E402
from distkeras_tpu.models.mamba import Mamba2Mixer  # noqa: E402
from distkeras_tpu.models.moe import MoE  # noqa: E402
from distkeras_tpu.ops import moe_kernels, ssm_kernels  # noqa: E402
from distkeras_tpu.serving import NgramDraft, ServingEngine  # noqa: E402
from distkeras_tpu.serving.scheduler import RequestState  # noqa: E402

TOL = 1e-7
SEED = 13

with open(os.path.join(ROOT, "benchmarks", "configs",
                       "granite-4.0-h-small.json")) as f:
    CFG = common.config_for(json.load(f), rehearse=True)
S = fam.sizes(CFG)
RCFG = fam.reference_cfg(CFG)


@pytest.fixture(scope="module")
def model():
    return fam.build_model(CFG, SEED, 256, dtype=jnp.float32)


@pytest.fixture(scope="module")
def ref_weights():
    return fam.reference_tree(fam.make_leaves(CFG, SEED, jnp.float32), S)


def _engine(model, **kw):
    kw = {"num_slots": 3, "max_len": 256, "page_len": 8,
          "prefill_chunk": 16, "prefix_cache": False, **kw}
    return ServingEngine(model, **kw)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, S["vocab"], n).astype(np.int32)


def _reference_logits(w, tokens, positions):
    seq = np.zeros(-(-len(tokens) // granite.Q_BLOCK) * granite.Q_BLOCK,
                   np.int32)
    seq[:len(tokens)] = tokens
    return np.asarray(granite.logits_at(w, RCFG, seq, np.asarray(positions)))


def _to_decoding(eng, rid):
    while eng[rid].state is not RequestState.DECODING:
        eng.step()
    return eng[rid]


def _gap(eng, req, ref_weights):
    """Worst logit gap of the step ``req`` takes next."""
    got = eng.decode_logits()[req.slot]       # drains the step in flight
    toks = np.concatenate([req.prompt, req.generated])
    want = _reference_logits(ref_weights, toks, [len(toks) - 1])[0]
    return float(np.abs(got - want).max())


def _rows(eng, slot):
    """A slot's rows of every state plane, on the host."""
    return [np.asarray(kv[key][slot]) for kv in eng.pool.cache
            if kv is not None and "ssm" in kv for key in ("ssm", "conv")]


# --- the model is the configuration ------------------------------------------

def test_model_is_the_configuration_at_its_rehearse_sizes(model):
    layers = model.module.layers
    assert isinstance(layers[1], Scale) and layers[1].factor == 12.0
    assert isinstance(layers[-1], TiedHead) and layers[-1].scale == 1 / 16
    blocks = layers[2:-2]
    kinds = ["mamba" if isinstance(b.attn, Mamba2Mixer) else "attention"
             for b in blocks]
    assert kinds == S["kinds"] == ["mamba", "mamba", "attention", "mamba"]
    assert all(isinstance(b, TransformerBlock) and b.residual_scale == 0.22
               and isinstance(b.mlp, MoE) for b in blocks)
    mixer, attn = blocks[0].attn, blocks[2].attn
    assert (mixer.num_heads, mixer.head_dim, mixer.d_state, mixer.d_conv,
            mixer.chunk, mixer.conv_dim) == (4, 16, 16, 4, 8, 96)
    assert (attn.use_rope, attn.attn_scale, attn.num_heads,
            attn.kv_heads) == (False, 0.0078125, 4, 2)
    moe = blocks[0].mlp
    assert (moe.router_dim, moe.num_held, moe.top_k, moe.score,
            moe.norm_topk, moe.shared_dim) == (8, 4, 2, "softmax", True, 48)
    # the head is the embedding's own table
    assert model.params[-1]["embeddings"] is model.params[0]["embeddings"]


def test_tied_head_shares_the_embedding_from_init():
    module = zoo.transformer_lm(32, d_model=16, num_heads=2, num_layers=1,
                                tie_embeddings=True, logits_scaling=4.0)
    params, _, out = module.init(jax.random.PRNGKey(0), (5,))
    assert params[-1] is params[0] and out == (5, 32)
    with pytest.raises(ValueError, match="tied head"):
        zoo.transformer_lm(32, d_model=16, num_heads=2, num_layers=1,
                           logits_scaling=4.0)


def test_full_forward_equals_the_reference(model, ref_weights):
    """``Sequential.apply`` (the chunked scan from a zero state in every
    mixer) against the reference's recurrence, at every position."""
    toks = _prompt(40, seed=1)
    got = np.asarray(model.apply(model.params, model.state,
                                 jnp.asarray(toks)[None])[0][0])
    want = _reference_logits(ref_weights, toks, np.arange(40))
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_prefill_then_decode_equals_the_reference(model, ref_weights,
                                                  kernels):
    """A 40-token prompt in chunks of 16 (the state carried across two
    chunk boundaries), then decode: the logits of each step against the
    reference's full forward over prompt + generated. ``kernels``: the
    scan, the state update, the paged attention and the grouped experts
    as Pallas kernels in interpreter mode."""
    def run():
        eng = _engine(model, decode_kernel="paged" if kernels else "auto")
        rid = eng.submit(_prompt(40, seed=2), 9)
        req = _to_decoding(eng, rid)
        worst = 0.0
        for step in range(8):
            if step % 3 == 0:
                worst = max(worst, _gap(eng, req, ref_weights))
            eng.step()
        return eng, worst

    if kernels:
        with ssm_kernels.force_interpret(), moe_kernels.force_interpret():
            eng, worst = run()
    else:
        eng, worst = run()
    assert worst < TOL
    programs = eng.health()["programs"]
    want = "kernel" if kernels else "xla_reference"
    assert f"ssd={want}" in programs["prefill"]
    assert f"ssm={want}" in programs["decode_greedy"]
    assert all("kv_cache=donated" in programs[p]
               for p in ("prefill", "decode_greedy"))


def test_state_carried_across_chunks_equals_one_chunk(model):
    """The state and window a prefill leaves in the slot's rows are the
    same whether the prompt went in one chunk or in five."""
    rows = []
    for chunk in (None, 8):
        eng = _engine(model, num_slots=1, prefill_chunk=chunk)
        req = eng[eng.submit(_prompt(37, seed=3), 1)]   # ends at prefill
        while req.state is not RequestState.FINISHED:
            eng.step()
        rows.append(_rows(eng, 0))
    for one, many in zip(*rows):
        assert np.abs(one.astype(np.float32)
                      - many.astype(np.float32)).max() < 1e-5
    assert any(np.abs(r).max() > 0 for r in rows[0])


def test_decode_leaves_a_prefilling_slot_and_a_reused_slot_starts_at_zero(
        model, ref_weights):
    eng = _engine(model, num_slots=2, prefill_chunk=8)
    a = _to_decoding(eng, eng.submit(_prompt(10, seed=4), 4))
    b_rid = eng.submit(_prompt(40, seed=5), 4)
    eng.step()                # b admitted (rows zeroed), its first chunk
    b = eng[b_rid]
    assert b.state is RequestState.PREFILLING and b.slot != a.slot
    before = _rows(eng, b.slot)
    assert all(not r.any() for r in before)
    steps = eng.ssm_counters["ssm_state_slot_steps"]
    eng.step()                # a decodes beside b's second chunk
    assert eng.ssm_counters["ssm_state_slot_steps"] > steps
    for old, new in zip(before, _rows(eng, b.slot)):
        assert np.array_equal(old, new)               # bit for bit
    while a.state is not RequestState.FINISHED:
        eng.step()
    resets = eng.ssm_counters["ssm_state_resets"]
    assert any(r.any() for r in _rows(eng, a.slot))   # a's state is left
    c_rid = eng.submit(_prompt(30, seed=6), 6)
    eng.step()                # c takes a's slot: zero, then its chunk
    c = eng[c_rid]
    assert c.slot == a.slot and eng.ssm_counters["ssm_state_resets"] \
        == resets + 1
    assert all(not r.any() for r in _rows(eng, c.slot))
    c = _to_decoding(eng, c_rid)
    assert _gap(eng, c, ref_weights) < TOL


def test_preempt_and_reprefill_gives_the_same_logits(model, ref_weights):
    """A pool too small for both streams: one is preempted, gives its
    pages and its slot back, re-prefills prompt + generated from a zero
    state and decodes on as the reference does."""
    eng = _engine(model, num_slots=2, num_pages=10, prefill_chunk=8)
    reqs = [eng[eng.submit(_prompt(30, seed=7 + i), 24)] for i in range(2)]
    resumed = None
    for _ in range(200):
        eng.step()
        # a resumed request's re-prefill clock is cleared as it rejoins
        resumed = next((r for r in reqs
                        if r.state is RequestState.DECODING
                        and getattr(r, "_resume_t0", 1) is None), None)
        if resumed is not None:
            break
    assert eng.metrics.requests_preempted >= 1 and resumed is not None
    assert _gap(eng, resumed, ref_weights) < TOL


# --- the kernels ---------------------------------------------------------------

def _mixer_inputs(rng, b, q, h=4, p=16, n=16, k=4):
    c = h * p + 2 * n
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return dict(xbc=f(b, q, c).astype(jnp.bfloat16), dt=f(b, q, h),
                window=f(b, k - 1, c).astype(jnp.bfloat16),
                state=0.1 * f(b, h, p, n)), \
        (0.5 * f(k, c), 0.1 * f(c), 0.1 * f(h), 0.5 * f(h), f(h))


def test_ssd_chunk_scan_kernel_against_its_oracle():
    """The chunked scan (interpreter mode) against the recurrence over 21
    positions in chunks of 8 (the last padded), from a state and a window
    carried in; and one call over 21 positions equals two over 13 and 8."""
    rng = np.random.default_rng(0)
    x, params = _mixer_inputs(rng, 2, 21)
    kw = dict(heads=4, d_state=16, chunk=8, heads_per_block=2)
    args = (x["xbc"], x["dt"], x["window"], x["state"])
    y0, s0, w0 = ssm_kernels.ssd_chunk_scan(*args, *params, kernel=False,
                                            **kw)
    with ssm_kernels.force_interpret():
        y1, s1, w1 = ssm_kernels.ssd_chunk_scan(*args, *params, **kw)
    # the kernel's masked mix takes bfloat16 operands
    assert float(jnp.abs(y1 - y0).max()) < 1e-2 * float(jnp.abs(y0).max())
    assert float(jnp.abs(s1 - s0).max()) < 1e-5
    assert bool((w0 == w1).all())
    ya, sa, wa = ssm_kernels.ssd_chunk_scan(
        x["xbc"][:, :13], x["dt"][:, :13], x["window"], x["state"], *params,
        kernel=False, **kw)
    yb, sb, _ = ssm_kernels.ssd_chunk_scan(
        x["xbc"][:, 13:], x["dt"][:, 13:], wa, sa, *params, kernel=False,
        **kw)
    assert float(jnp.abs(jnp.concatenate([ya, yb], 1) - y0).max()) < 1e-5
    assert float(jnp.abs(sb - s0).max()) < 1e-5


def test_ssm_decode_step_kernel_against_its_oracle():
    """The decode kernel (interpreter mode) against one step of the
    recurrence; slots at or past ``live_len`` get their state and window
    back bit for bit and read 0."""
    rng = np.random.default_rng(1)
    x, params = _mixer_inputs(rng, 6, 1)
    t = jnp.array([0, 5, 100, 3, 250, 7], jnp.int32)
    args = (x["xbc"][:, 0], x["dt"][:, 0], x["window"], x["state"])
    kw = dict(heads=4, d_state=16, live_len=100, heads_per_block=2)
    ref = ssm_kernels.ssm_decode_step(*args, *params, t, kernel=False, **kw)
    with ssm_kernels.force_interpret():
        got = ssm_kernels.ssm_decode_step(*args, *params, t, **kw)
    for a, b in zip(ref, got):
        assert float(jnp.abs(a - b).max()) < 1e-5
    for dead in (2, 4):
        assert bool((got[1][dead] == x["state"][dead]).all())
        assert bool((got[2][dead] == x["window"][dead]).all())
        assert not bool(got[0][dead].any())
    # one decode step is one position of the prefill scan
    y, s, w = ssm_kernels.ssd_chunk_scan(
        x["xbc"], x["dt"], x["window"], x["state"], *params, heads=4,
        d_state=16, chunk=8, kernel=False)
    live = np.array([0, 1, 3, 5])
    assert float(jnp.abs(y[live, 0] - ref[0][live]).max()) < 1e-6
    assert float(jnp.abs(s[live] - ref[1][live]).max()) < 1e-6


# --- one chip's share of the experts --------------------------------------------

def test_held_shares_add_up_to_the_uncut_layer():
    """Experts 0-3 held on one chip and 4-7 on the other, the shared expert
    counted once: their parts add up to the layer that holds all eight,
    in the program and in the reference."""
    d, f, fs = 16, 8, 12
    kw = dict(top_k=2, activation="silu", gated=True, use_bias=False,
              dispatch="grouped", shared_dim=fs)
    full = MoE(8, f, **kw)
    params, state, _ = full.init(jax.random.PRNGKey(3), (5, d))
    u = jax.random.normal(jax.random.PRNGKey(4), (1, 5, d))

    def share(lo):
        layer = MoE(8, f, experts_held=(lo, 4), **kw)
        p = {k: (v[lo:lo + 4] if k in ("w1", "w2", "w3") else v)
             for k, v in params.items()}
        return layer.apply(p, state, u)[0]

    shared = full.shared.apply(params["shared"], {}, u)[0]
    whole = full.apply(params, state, u)[0]
    assert float(jnp.abs(share(0) + share(4) - shared - whole).max()) < 1e-5
    lw = {"router": params["gate"], "e1": params["w1"], "e3": params["w3"],
          "e2": params["w2"], "s1": params["shared"]["w1"],
          "s3": params["shared"]["w3"], "s2": params["shared"]["w2"]}
    rcfg = {"top_k": 2, "held": [0, 8]}
    ref = lambda held: granite._moe(
        u[0], {**lw, **{k: lw[k][held[0]:held[0] + held[1]]
                        for k in ("e1", "e3", "e2")}},
        {**rcfg, "held": list(held)}, "float32")
    ref_shared = granite._gated(u[0], lw["s1"], lw["s3"], lw["s2"], "float32")
    assert float(jnp.abs(ref((0, 4)) + ref((4, 4)) - ref_shared
                         - ref((0, 8))).max()) < 1e-5
    assert float(jnp.abs(ref((0, 8)) - whole[0]).max()) < 1e-5


# --- what is refused, by name -----------------------------------------------------

@pytest.mark.parametrize("name,kw", [
    ("prefix_cache", {"prefix_cache": True}),
    ("draft", {"draft": NgramDraft()}),
    ("fuse_steps", {"fuse_steps": 4}),
    ("host_kv_pages", {"host_kv_pages": 8}),
    ("weight_quant", {"weight_quant": "int8"}),
    ("ep_mesh", {"ep_mesh": "a mesh"}),
])
def test_refusals_name_what_they_refuse(model, name, kw):
    with pytest.raises(ValueError, match=name):
        _engine(model, **kw)


def test_other_refusals(model):
    with pytest.raises(ValueError, match="int8"):
        _engine(model, cache_dtype="int8")
    kw = fam.program_kwargs(CFG)
    with pytest.raises(ValueError, match="block_len"):
        zoo.transformer_lm(kw.pop("vocab_size"), block_len=4, **kw)
    with pytest.raises(NotImplementedError, match="state-space"):
        generate(model, _prompt(8)[None], 2)


def test_health_reports_the_state_group(model):
    eng = _engine(model)
    groups = eng.health()["kv_groups"]
    per_slot = 3 * (4 * 16 * 16 * 4 + 3 * 96 * 4)     # float32 model
    assert groups["ssm"] == {"window": None, "layers": 3, "slots": 3,
                             "bytes": 3 * per_slot, "resets": 0}
    assert eng.pool.layer_groups == (
        None, None, ("ssm", None), ("ssm", None), (0, None), ("ssm", None),
        None, None)
