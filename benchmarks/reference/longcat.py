"""The plain reference of the LongCat-Flash family
(meituan-longcat/LongCat-Flash-Chat): a decoder of DOUBLE layers, each two
latent-attention (MLA) blocks and two dense gated MLPs round one
shortcut-connected expert layer whose router also has zero-compute
(identity) experts. Straight-line ``jax.numpy`` in float32 at ``highest``
matrix precision: the NON-ABSORBED attention only (every head's keys and
values rebuilt from the latent), no cache, no kernel, no batching of
requests. It imports nothing of the program and is handed the benchmark's
own weights (``harness/longcat_family.py``) in the type they are served in
(bfloat16), which it widens one matrix, and one expert, as it uses it. A
caller short of memory walks the stack itself, a double layer at a time
(``embed`` / ``double_layer`` / ``head``); ``logits_at`` is that walk.

Double layer ``l``, block ``i`` in (0, 1), residual stream ``h`` (``n``
RMSNorm, epsilon ``cfg["eps"]``, no bias anywhere)::

    a = h + MLA_{l,i}(n1_{l,i}(h))
    u = n2_{l,i}(a)
    if i == 0:  m = MoE_l(u)              # the shortcut: from block 0's u
    h = a + Wd(act(Wg u) * Wu u)          # the dense MLP
    if i == 1:  h = h + m                 # added after block 1's MLP

``MLA(x)``, ``H`` heads (``q_scale`` / ``kv_scale``: the configuration's
``mla_scale_q_lora`` / ``mla_scale_kv_lora``, ``sqrt(d / rank)``)::

    cq        = q_scale  * n_q(Wqa x)
    [qn | qr] = Wqb cq                  per head   [H, dn | dr]
    [ckv| kr] = Wkva x                  ONE per token, no head axis
    c         = kv_scale * n_kv(ckv)    (kr is not scaled)
    [kn | v]  = Wkvb c                  per head   [H, dn | dv]
    score     = (qn.kn + RoPE(qr).RoPE(kr)) / sqrt(dn + dr), causal
    out       = Wo concat_h(softmax(score) v)

``MoE(u)``: ``s = softmax(Wr u)`` over ALL ``experts + zero_experts``
outputs in float32; ``T`` = the ``top_k`` largest of ``s + b`` (``b`` the
selection bias: it chooses and does not weight); ``w_e = scale * s_e`` (no
normalisation over ``T`` unless ``cfg["norm_topk"]``, which the model is
not: a control); ``m = sum_{e in T, e held} w_e E_e(u) + sum_{e in
T, e >= experts} w_e u``: the experts ``cfg["held"] = (lo, n)`` are the ones
whose weights are here (one chip's share of an expert-parallel deployment;
what the absent ones would add is left out), the outputs past ``experts``
are identity experts. Every held expert's product is taken for every token
under a gate that is zero outside the token's choice: the plainest form.

RoPE rotates the pairs ``(2i, 2i+1)`` of the rope dimensions (as this
repository's ``apply_rope``; stated under ``departures`` in the
configuration file).

``precision`` selects how every matrix product is taken: ``"float32"`` (the
reference proper), ``"bfloat16"`` (what the configuration states; a
witness), ``"int8"`` (a control: operands rounded to 8-bit integers with one
scale per contracted row). The router's product is float32 whatever it says.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: queries per block of attention (a sequence is padded to a multiple)
Q_BLOCK = 256

ACTIVATIONS = {"silu": jax.nn.silu,
               "gelu": functools.partial(jax.nn.gelu, approximate=False)}


def _q8(x, axes):
    """Round to a signed 8-bit grid, one scale per slice along ``axes``."""
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _mm(eq, a, b, precision, a_axes, b_axes):
    """``einsum`` at the stated precision; ``*_axes`` are the contracted
    axes. Operands are widened to float32 here, where they are used."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if precision == "float32":
        return jnp.einsum(eq, a, b, precision=HIGHEST)
    if precision == "bfloat16":
        return jnp.einsum(eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if precision == "int8":
        return jnp.einsum(eq, _q8(a, a_axes), _q8(b, b_axes),
                          precision=HIGHEST)
    raise ValueError(f"unknown precision {precision!r}")


def _rms_norm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, theta):
    """``x`` [S, ..., D] at positions 0..S-1: the pairs ``(2i, 2i+1)`` of
    all ``D`` dimensions."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None]
    shape = (s,) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v, precision):
    """``q``/``k`` [S, H, Dqk], ``v`` [S, H, Dv]: causal attention, a block
    of queries at a time against all keys."""
    s, h, d = q.shape
    blocks = q.reshape(s // Q_BLOCK, Q_BLOCK, h, d)
    kpos = jnp.arange(s)[None, :]

    def one(args):
        qb, start = args
        scores = _mm("qhe,khe->hqk", qb, k, precision, (-1,), (-1,)) \
            / jnp.sqrt(jnp.float32(d))
        qpos = (start + jnp.arange(Q_BLOCK))[:, None]
        probs = jax.nn.softmax(jnp.where(kpos <= qpos, scores, -jnp.inf),
                               axis=-1)
        return _mm("hqk,khe->qhe", probs, v, precision, (-1,), (0,))

    out = jax.lax.map(one, (blocks, jnp.arange(0, s, Q_BLOCK)))
    return out.reshape(s, h, v.shape[-1])


def _mla(x, bw, cfg, precision):
    """Latent attention on ``x`` [S, d] (already normed), non-absorbed."""
    s = x.shape[0]
    dn, r = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["eps"]
    cq = cfg["q_scale"] * _rms_norm(
        _mm("sd,dr->sr", x, bw["wqa"], precision, (-1,), (0,)), bw["qn"], eps)
    q = _mm("sr,rhe->she", cq, bw["wqb"], precision, (-1,), (0,))
    ckv = _mm("sd,dr->sr", x, bw["wkva"], precision, (-1,), (0,))
    c = cfg["kv_scale"] * _rms_norm(ckv[:, :r], bw["kvn"], eps)
    kv = _mm("sr,rhe->she", c, bw["wkvb"], precision, (-1,), (0,))
    kr = _rope(ckv[:, r:], cfg["rope_theta"])              # [S, dr], shared
    qr = _rope(q[..., dn:], cfg["rope_theta"])
    k = jnp.concatenate(
        [kv[..., :dn],
         jnp.broadcast_to(kr[:, None, :], (s, q.shape[1], kr.shape[-1]))],
        axis=-1)
    o = _attention(jnp.concatenate([q[..., :dn], qr], axis=-1), k,
                   kv[..., dn:], precision)
    return _mm("she,hed->sd", o, bw["wo"], precision, (1, 2), (0, 1))


def _gated(h, wg, wu, wd, act, precision):
    a = _mm("sd,df->sf", h, wg, precision, (-1,), (0,))
    u = _mm("sd,df->sf", h, wu, precision, (-1,), (0,))
    return _mm("sf,fd->sd", act(a) * u, wd, precision, (-1,), (0,))


def route(u, router, bias, cfg):
    """``gates`` [S, experts + zero_experts] float32: ``scale * softmax``
    at the token's ``top_k`` choices (chosen with the bias, weighted
    without it), zero elsewhere."""
    logits = jnp.einsum("sd,de->se", u.astype(jnp.float32),
                        router.astype(jnp.float32), precision=HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1)
    choose = scores if bias is None or not cfg["use_select_bias"] \
        else scores + bias.astype(jnp.float32)
    _, top_i = jax.lax.top_k(choose, cfg["top_k"])
    rows = jnp.arange(scores.shape[0])[:, None]
    top_s = scores[rows, top_i]
    if cfg["norm_topk"]:
        top_s = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    return jnp.zeros_like(scores).at[rows, top_i].set(
        top_s * cfg["route_scale"])


def _moe(u, lw, cfg, precision):
    """The expert layer's part that is computed here: the held experts
    under their gates and the identity experts."""
    act = ACTIVATIONS[cfg["act"]]
    gates = route(u, lw["router"], lw.get("bias"), cfg)
    lo, n = cfg["held"]
    e = cfg["experts"]

    def one(acc, ew):
        wg, wu, wd, gate = ew
        return acc + gate[:, None] * _gated(u, wg, wu, wd, act, precision), \
            None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (lw["eg"], lw["eu"], lw["ed"],
                           gates[:, lo:lo + n].T))
    return out + jnp.sum(gates[:, e:], axis=-1, keepdims=True) * u


def _cfg_key(cfg):
    return json.dumps(cfg, sort_keys=True)       # hashable: a static argument


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _attn_part(h, bw, cfg, precision):
    cfg = json.loads(cfg)
    a = h + _mla(_rms_norm(h, bw["n1"], cfg["eps"]), bw, cfg, precision)
    return a, _rms_norm(a, bw["n2"], cfg["eps"])


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _mlp_part(a, u, bw, cfg, precision):
    cfg = json.loads(cfg)
    return a + _gated(u, bw["wg"], bw["wu"], bw["wd"],
                      ACTIVATIONS[cfg["act"]], precision)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _moe_part(u, lw, cfg, precision):
    return _moe(u, lw, json.loads(cfg), precision)


def embed(table, tokens):
    return jnp.take(table, jnp.asarray(tokens), axis=0).astype(jnp.float32)


def double_layer(h, lw, cfg, precision="float32"):
    """One double layer on ``h`` [S, d] float32. ``lw``: ``blocks`` (two of
    ``n1, n2, wqa, qn, wqb, wkva, kvn, wkvb, wo, wg, wu, wd``), ``router``,
    ``bias``, ``eg, eu, ed`` (the held experts, stacked)."""
    key = _cfg_key(cfg)
    moe_w = {k: lw[k] for k in ("router", "bias", "eg", "eu", "ed")
             if k in lw}
    with jax.default_matmul_precision("highest"):
        a, u = _attn_part(h, lw["blocks"][0], key, precision)
        m = _moe_part(u, moe_w, key, precision)
        h = _mlp_part(a, u, lw["blocks"][0], key, precision)
        a, u = _attn_part(h, lw["blocks"][1], key, precision)
        return _mlp_part(a, u, lw["blocks"][1], key, precision) + m


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, final_norm, head_w, positions, eps, precision):
    h = _rms_norm(jnp.take(x, positions, axis=0), final_norm, eps)
    return _mm("sd,dv->sv", h, head_w, precision, (-1,), (0,))


def head(h, final_norm, head_w, positions, cfg, precision="float32"):
    with jax.default_matmul_precision("highest"):
        return _head(h, final_norm, head_w, jnp.asarray(positions),
                     cfg["eps"], precision)


def logits_at(w, cfg, tokens, positions, precision="float32"):
    """Logits [n, V] (float32) at ``positions`` of ONE sequence ``tokens``
    [S] (``S`` a multiple of ``Q_BLOCK``): the full forward pass, a double
    layer at a time. ``w``: ``embed``, ``layers`` (as ``double_layer``
    takes them), ``final_norm``, ``head``. ``cfg``: ``qk_nope_head_dim``,
    ``kv_lora_rank``, ``rope_theta``, ``q_scale``, ``kv_scale``, ``eps``,
    ``act``, ``experts``, ``zero_experts``, ``held`` ``(lo, n)``, ``top_k``,
    ``route_scale``, ``use_select_bias``, ``norm_topk``."""
    if len(tokens) % Q_BLOCK:
        raise ValueError(f"pad the sequence to a multiple of {Q_BLOCK}")
    h = embed(w["embed"], tokens)
    for lw in w["layers"]:
        h = double_layer(h, lw, cfg, precision)
    return head(h, w["final_norm"], w["head"], positions, cfg, precision)
