"""The plain reference of the Granite-4.0-H family
(ibm-granite/granite-4.0-h-small, ``granitemoehybrid``): a decoder whose
layers are Mamba-2 state-space layers or GQA attention with no position
embedding, each followed by a mixture of softmax-routed experts and a
shared expert. Straight-line ``jax.numpy`` in float32 at ``highest``
matrix precision: the Mamba-2 layer as its SEQUENTIAL recurrence
(``lax.scan`` over positions, not the chunked dual form), full causal
attention, every held expert's product under a gate that is zero outside
a token's choice; no cache, no kernel, no batching of requests. It
imports nothing of the program and is handed the benchmark's own weights
(``harness/granite_family.py``) in the type they are served in
(bfloat16), which it widens one matrix, and one expert, as it uses it. A
caller short of memory walks the stack itself, a layer at a time
(``embed`` / ``layer`` / ``head``); ``logits_at`` is that walk.

Residual stream ``h`` (``n`` RMSNorm, epsilon ``cfg["eps"]``, no bias
but the convolution's), layer ``l`` of kind ``M`` (``cfg["kinds"]``)::

    h = cfg["embed_mult"] * E[ids]
    h = h + r * M_l(n1_l(h))                     r = cfg["residual_mult"]
    u = n2_l(h)
    h = h + r * (MoE_l(u) + Shared_l(u))
    logits = (n_f(h) E^T) / cfg["logits_scaling"]         (the head is E)

``Attention(a)``: ``H`` query heads over ``Hkv`` key/value heads of ``Dh``,
no RoPE, scores ``q.k * cfg["attn_scale"]``, causal softmax, ``Wo``.

``Mamba2(a)``, ``Hm`` heads of ``P``, a state of ``N``, one group::

    [z | xBC | dt] = a W_in
    xBC = silu(causal_depthwise_conv(xBC) + b)       [x | B | C]
    dt  = softplus(dt + dt_bias);   A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T       per head
    y_t = S_t C_t + D x_t
    out = RMSNorm_w(y * silu(z)) W_out               (over all Hm * P)

``MoE(u)``: router logits ``u Wr`` over ALL ``experts`` in float32, ``T``
the ``top_k`` largest, gates the softmax over the chosen logits;
``sum_{e in T, e held} g_e E_e(u)`` with ``E_e(u) = W2(silu(W1 u) * W3 u)``.
The experts ``cfg["held"] = (lo, n)`` are the ones whose weights are here
(one chip's share of an expert-parallel deployment); what the absent ones
would add is left out. ``Shared(u)`` is the same form, unweighted.

Planted faults (the controls): ``cfg["resets"]``, positions at which the
state and the convolution's window restart from zero (as if the sequence
began there); ``cfg["gate"] = False`` leaves ``* silu(z)`` out;
``cfg["state_dtype"] = "bfloat16"`` rounds the state to bfloat16 after
every position's update, and the convolution's inputs (its window) before
the convolution.

``precision`` selects how every matrix product is taken: ``"float32"``
(the reference proper), ``"bfloat16"`` (what the configuration states; a
witness), ``"int8"`` (a control: operands rounded to 8-bit integers with
one scale per contracted row). The router's product and the recurrence
are float32 whatever it says.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
#: queries per block of attention (a sequence is padded to a multiple)
Q_BLOCK = 256


def _q8(x, axes):
    """Round to a signed 8-bit grid, one scale per slice along ``axes``."""
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _mm(eq, a, b, precision, a_axes, b_axes):
    """``einsum`` at the stated precision; ``*_axes`` are the contracted
    axes. Operands are widened to float32 here, where they are used."""
    a, b = a.astype(F32), b.astype(F32)
    if precision == "float32":
        return jnp.einsum(eq, a, b, precision=HIGHEST)
    if precision == "bfloat16":
        return jnp.einsum(eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=F32)
    if precision == "int8":
        return jnp.einsum(eq, _q8(a, a_axes), _q8(b, b_axes),
                          precision=HIGHEST)
    raise ValueError(f"unknown precision {precision!r}")


def _rms_norm(x, g, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * g.astype(F32)


def _attention(a, lw, cfg, precision):
    """Causal GQA attention with no position embedding, a block of
    queries at a time against all keys."""
    s = a.shape[0]
    q = _mm("sd,dhe->she", a, lw["wq"], precision, (-1,), (0,))
    k = _mm("sd,dhe->she", a, lw["wk"], precision, (-1,), (0,))
    v = _mm("sd,dhe->she", a, lw["wv"], precision, (-1,), (0,))
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    blocks = q.reshape(s // Q_BLOCK, Q_BLOCK, *q.shape[1:])
    kpos = jnp.arange(s)[None, :]

    def one(args):
        qb, start = args
        scores = _mm("qhe,khe->hqk", qb, k, precision, (-1,), (-1,)) \
            * cfg["attn_scale"]
        qpos = (start + jnp.arange(Q_BLOCK))[:, None]
        probs = jax.nn.softmax(jnp.where(kpos <= qpos, scores, -jnp.inf),
                               axis=-1)
        return _mm("hqk,khe->qhe", probs, v, precision, (-1,), (0,))

    o = jax.lax.map(one, (blocks, jnp.arange(0, s, Q_BLOCK)))
    return _mm("she,hed->sd", o.reshape(q.shape), lw["wo"], precision,
               (1, 2), (0, 1))


def _segments(s, resets):
    """Segment of each position: a reset at ``p`` starts a new one."""
    mark = np.zeros(s, np.int32)
    for p in resets:
        if 0 < p < s:
            mark[p] = 1
    return jnp.asarray(np.cumsum(mark))


def _mamba(a, lw, cfg, precision):
    """The Mamba-2 mixer on ``a`` [S, d] (normed), the recurrence
    position by position from a zero state."""
    s = a.shape[0]
    hm, p, n = cfg["mamba_heads"], cfg["mamba_head_dim"], cfg["d_state"]
    inner = hm * p
    proj = _mm("sd,de->se", a, lw["in_proj"], precision, (-1,), (0,))
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * n],
                  proj[:, 2 * inner + 2 * n:])
    # the causal depthwise convolution: tap K-1 multiplies position t,
    # tap K-1-j position t-j, within the position's segment
    seg = _segments(s, cfg["resets"])
    low = cfg.get("state_dtype", "float32") == "bfloat16"
    if low:
        xbc = xbc.astype(jnp.bfloat16).astype(F32)
    w = lw["conv_w"].astype(F32)
    k = w.shape[0]
    conv = lw["conv_b"].astype(F32) + w[k - 1] * xbc
    for j in range(1, k):
        prev = jnp.pad(xbc, ((j, 0), (0, 0)))[:s]
        same = jnp.pad(seg, (j, 0), constant_values=-1)[:s] == seg
        conv = conv + w[k - 1 - j] * jnp.where(same[:, None], prev, 0.0)
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(s, hm, p)
    bm, cm = xbc[:, inner:inner + n], xbc[:, inner + n:]
    dt = jax.nn.softplus(dt + lw["dt_bias"].astype(F32))
    a_ = -jnp.exp(lw["A_log"].astype(F32))
    starts = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])

    def step(state, inp):
        xt, bt, ct, dtt, start = inp
        state = jnp.where(start, 0.0, state)
        state = state * jnp.exp(dtt * a_)[:, None, None] \
            + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :]
        if low:
            state = state.astype(jnp.bfloat16).astype(F32)
        return state, jnp.einsum("hpn,n->hp", state, ct, precision=HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((hm, p, n), F32),
                        (x, bm, cm, dt, starts))
    y = (y + lw["D"].astype(F32)[:, None] * x).reshape(s, inner)
    if cfg["gate"]:
        y = y * jax.nn.silu(z)
    y = _rms_norm(y, lw["norm"], cfg["eps"])
    return _mm("se,ed->sd", y, lw["out_proj"], precision, (-1,), (0,))


def _gated(u, w1, w3, w2, precision):
    a = _mm("sd,df->sf", u, w1, precision, (-1,), (0,))
    b = _mm("sd,df->sf", u, w3, precision, (-1,), (0,))
    return _mm("sf,fd->sd", jax.nn.silu(a) * b, w2, precision, (-1,), (0,))


def route(u, router, cfg):
    """``gates`` [S, experts] float32: the softmax over each token's
    ``top_k`` largest router logits at those experts, zero elsewhere."""
    logits = jnp.einsum("sd,de->se", u.astype(F32), router.astype(F32),
                        precision=HIGHEST)
    top_v, top_i = jax.lax.top_k(logits, cfg["top_k"])
    rows = jnp.arange(logits.shape[0])[:, None]
    return jnp.zeros_like(logits).at[rows, top_i].set(
        jax.nn.softmax(top_v, axis=-1))


def _moe(u, lw, cfg, precision):
    """The held experts under their gates, and the shared expert."""
    gates = route(u, lw["router"], cfg)
    lo, n = cfg["held"]

    def one(acc, ew):
        w1, w3, w2, gate = ew
        return acc + gate[:, None] * _gated(u, w1, w3, w2, precision), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (lw["e1"], lw["e3"], lw["e2"],
                           gates[:, lo:lo + n].T))
    return out + _gated(u, lw["s1"], lw["s3"], lw["s2"], precision)


def _cfg_key(cfg):
    return json.dumps(cfg, sort_keys=True)       # hashable: a static argument


@functools.partial(jax.jit, static_argnames=("kind", "cfg", "precision"))
def _layer(h, lw, kind, cfg, precision):
    cfg = json.loads(cfg)
    mix = _mamba if kind == "mamba" else _attention
    r = cfg["residual_mult"]
    h = h + r * mix(_rms_norm(h, lw["n1"], cfg["eps"]), lw, cfg, precision)
    u = _rms_norm(h, lw["n2"], cfg["eps"])
    return h + r * _moe(u, lw, cfg, precision)


def embed(table, tokens, cfg):
    return cfg["embed_mult"] * jnp.take(table, jnp.asarray(tokens),
                                        axis=0).astype(F32)


def layer(h, lw, kind, cfg, precision="float32"):
    """One layer of ``kind`` (``"mamba"`` or ``"attention"``) on ``h``
    [S, d] float32. ``lw``: ``n1``, ``n2``, the mixer's leaves (mamba:
    ``in_proj, conv_w, conv_b, dt_bias, A_log, D, norm, out_proj``;
    attention: ``wq, wk, wv, wo``), ``router``, ``e1, e3, e2`` (the held
    experts, stacked; ``e1`` the activated half), ``s1, s3, s2`` (the
    shared expert)."""
    with jax.default_matmul_precision("highest"):
        return _layer(h, lw, kind, _cfg_key(cfg), precision)


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "precision"))
def _head(x, final_norm, table, positions, eps, scaling, precision):
    h = _rms_norm(jnp.take(x, positions, axis=0), final_norm, eps)
    return _mm("sd,vd->sv", h, table, precision, (-1,), (-1,)) / scaling


def head(h, final_norm, table, positions, cfg, precision="float32"):
    with jax.default_matmul_precision("highest"):
        return _head(h, final_norm, table, jnp.asarray(positions),
                     cfg["eps"], float(cfg["logits_scaling"]), precision)


def logits_at(w, cfg, tokens, positions, precision="float32"):
    """Logits [n, V] (float32) at ``positions`` of ONE sequence ``tokens``
    [S] (``S`` a multiple of ``Q_BLOCK``): the full forward pass, a layer
    at a time. ``w``: ``embed`` (also the head), ``layers`` (as ``layer``
    takes them), ``final_norm``. ``cfg``: ``kinds`` (a layer's kind),
    ``eps``, ``embed_mult``, ``residual_mult``, ``logits_scaling``,
    ``attn_scale``, ``mamba_heads``, ``mamba_head_dim``, ``d_state``,
    ``top_k``, ``held`` ``(lo, n)``, ``resets``, ``gate``, and optionally
    ``state_dtype``."""
    if len(tokens) % Q_BLOCK:
        raise ValueError(f"pad the sequence to a multiple of {Q_BLOCK}")
    h = embed(w["embed"], tokens, cfg)
    for kind, lw in zip(cfg["kinds"], w["layers"]):
        h = layer(h, lw, kind, cfg, precision)
    return head(h, w["final_norm"], w["embed"], positions, cfg, precision)
