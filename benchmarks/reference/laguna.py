"""The plain reference of the Laguna family (poolside/Laguna-XS.2,
``model_type`` ``laguna``): a decoder whose layers attend either to the
whole context (``full_attention``) or to a sliding window
(``sliding_attention``), with a different number of query heads and a
different RoPE for each kind, a dense gated MLP in the leading layer and,
in every other, sigmoid-routed gated experts beside one shared expert.
Straight-line ``jax.numpy`` in float32 at ``highest`` matrix precision; no
kernel, no cache, no batching of requests. It imports nothing of the
program and is handed the benchmark's own weights
(``harness/laguna_family.py``), which it keeps in the type they are served
in (bfloat16) and widens one layer, and inside it one expert, as it uses
it. Attention is computed a block of ``Q_BLOCK`` queries at a time against
all keys, so that 16k positions fit.

Layer ``l`` (``n`` RMSNorm over the model width, epsilon ``cfg["eps"]``, no
bias anywhere; ``H_l`` query heads over ``cfg["kv_heads"]`` KV heads)::

    h = x + Wo_l . softmax(q k^T / sqrt(head_dim) + mask_l) v
    q = RoPE_l(Wq_l n(x)), k = RoPE_l(Wk n(x)), v = Wv n(x)
    mask, full: j <= i;  sliding: i - window < j <= i
    dense layer:  y = h + Wd(act(Wg n(h)) * Wu n(h))
    sparse layer: y = h + sum_{e in top-k(s)} w_e E_e(n(h)) + S(n(h))
                  s = sigmoid(Wr n(h)) in float32
                  w_e = scale * s_e / sum_{top-k} s   (cfg["router"])

RoPE rotates the pairs ``(2i, 2i+1)`` of the first ``rotary_dim``
dimensions of a head and passes the rest (as this repository's
``apply_rope``; the source pairs ``(i, i + rotary_dim/2)``: the same
function under a fixed permutation of each head's columns of ``Wq`` and
``Wk``, stated in the configuration file under ``departures``). A kind with
``rope_type`` ``yarn`` takes its inverse frequencies and its attention
factor from ``yarn_parameters``, a transcription of ``transformers``'
``_compute_yarn_parameters``.

Every expert's product is taken for every token and weighted by a gate that
is zero outside the token's top-k: the plainest form, thirty-two times the
routed operations at top-8 of 256, which a reference can afford.

``precision`` selects how every matrix product is taken: ``"float32"`` (the
reference proper), ``"bfloat16"`` (what the configuration states; a
witness), ``"int8"`` (a control: operands rounded to 8-bit integers with
one scale per contracted row).
"""

from __future__ import annotations

import functools
import json
import math

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


# --- rotary position embedding ---------------------------------------------

def yarn_parameters(dim, theta, factor, original_max, beta_fast=32.0,
                    beta_slow=1.0, attention_factor=None, truncate=True):
    """``(inv_freq [dim // 2], attention_factor)`` of YaRN over ``dim``
    rotated dimensions: ``transformers``' ``_compute_yarn_parameters``,
    line by line."""
    def get_mscale(scale):
        return 1.0 if scale <= 1 else 0.1 * math.log(scale) + 1.0

    if attention_factor is None:
        attention_factor = get_mscale(factor)

    def find_correction_dim(num_rotations):
        return (dim * math.log(original_max / (num_rotations * 2 * math.pi))) \
            / (2 * math.log(theta))

    low, high = find_correction_dim(beta_fast), find_correction_dim(beta_slow)
    if truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inv_freq_extrapolation = 1.0 / pos_freqs
    inv_freq_interpolation = 1.0 / (factor * pos_freqs)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    inv_freq = inv_freq_interpolation * (1 - extrapolation_factor) \
        + inv_freq_extrapolation * extrapolation_factor
    return inv_freq.astype(np.float32), float(attention_factor)


def rope_tables(rope: dict, head_dim: int):
    """``(rotary_dim, inv_freq, scale of cosine and sine)`` of one kind of
    layer, from its published ``rope_parameters`` group."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1))
    theta = float(rope["rope_theta"])
    if rope.get("rope_type", "default") == "yarn":
        inv, att = yarn_parameters(
            dim, theta, rope["factor"],
            rope["original_max_position_embeddings"],
            rope.get("beta_fast") or 32.0, rope.get("beta_slow") or 1.0,
            rope.get("attention_factor"), rope.get("truncate", True))
        return dim, inv, att
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"unknown rope_type {rope['rope_type']!r}")
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    return dim, inv.astype(np.float32), 1.0


def _rope(x, dim, inv_freq, scale):
    """``x`` [S, H, D] at positions 0..S-1; pairs ``(2i, 2i+1)`` of the
    first ``dim`` dimensions, the rest passed through."""
    s = x.shape[0]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)[None]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., :dim:2], x[..., 1:dim:2]
    rot = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                    axis=-1).reshape(x.shape[:-1] + (dim,))
    return jnp.concatenate([rot, x[..., dim:]], axis=-1)


# --- the layer -------------------------------------------------------------

def _attention(q, k, v, window, precision):
    """``q`` [S, H, D], ``k``/``v`` [S, Hkv, D]: causal (``window`` None)
    or sliding-window attention, a block of queries at a time."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    blocks = q.reshape(s // Q_BLOCK, Q_BLOCK, hkv, g, d)
    kpos = jnp.arange(s)[None, :]

    def one(args):
        qb, start = args
        scores = _mm("qhge,khe->hgqk", qb, k, precision, (-1,), (-1,)) \
            / jnp.sqrt(jnp.float32(d))
        qpos = (start + jnp.arange(Q_BLOCK))[:, None]
        allowed = kpos <= qpos
        if window is not None:
            allowed &= kpos > qpos - window
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        return _mm("hgqk,khe->qhge", probs, v, precision, (-1,), (0,))

    out = jax.lax.map(one, (blocks, jnp.arange(0, s, Q_BLOCK)))
    return out.reshape(s, hq, d)


def _gated(h, wg, wu, wd, act, precision):
    a = _mm("sd,df->sf", h, wg, precision, (-1,), (0,))
    u = _mm("sd,df->sf", h, wu, precision, (-1,), (0,))
    return _mm("sf,fd->sd", act(a) * u, wd, precision, (-1,), (0,))


def _experts(h, lw, cfg, precision):
    """The sparse layer on ``h`` [S, d] (already normed): every expert for
    every token, gated; the gate is zero outside the token's top-k; the
    shared expert beside them, unweighted."""
    r, act = cfg["router"], ACTIVATIONS[cfg["act"]]
    logits = jnp.einsum("sd,de->se", h, lw["router"].astype(jnp.float32),
                        precision=HIGHEST)                 # float32 router
    if r["score"] == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif r["score"] == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown router score {r['score']!r}")
    top_s, top_i = jax.lax.top_k(scores, cfg["top_k"])
    if r["norm_topk"]:
        top_s = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    gates = jnp.zeros_like(scores).at[
        jnp.arange(scores.shape[0])[:, None], top_i].set(top_s * r["scale"])

    def one(acc, ew):
        wg, wu, wd, gate = ew
        return acc + gate[:, None] * _gated(h, wg, wu, wd, act, precision), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (lw["wg"], lw["wu"], lw["wd"], gates.T))
    return out + _gated(h, lw["sg"], lw["su"], lw["sd"], act, precision)


def _layer(x, lw, cfg, kind, sparse, precision):
    """One decoder layer on ``x`` [S, d] float32; ``kind`` is the layer's
    entry of ``cfg["kinds"]``."""
    s = x.shape[0]
    k_cfg = cfg["kinds"][kind]
    h = _rms_norm(x, lw["n1"], cfg["eps"])
    q = _mm("sd,dhe->she", h, lw["wq"], precision, (-1,), (0,))
    k = _mm("sd,dhe->she", h, lw["wk"], precision, (-1,), (0,))
    v = _mm("sd,dhe->she", h, lw["wv"], precision, (-1,), (0,))
    dim, inv, scale = rope_tables(k_cfg["rope"], q.shape[-1])
    q, k = _rope(q, dim, inv, scale), _rope(k, dim, inv, scale)
    o = _attention(q, k, v, k_cfg["window"], precision)
    x = x + _mm("she,hed->sd", o.reshape(s, q.shape[1], -1), lw["wo"],
                precision, (1, 2), (0, 1))
    h = _rms_norm(x, lw["n2"], cfg["eps"])
    if sparse:
        return x + _experts(h, lw, cfg, precision)
    return x + _gated(h, lw["wg"], lw["wu"], lw["wd"],
                      ACTIVATIONS[cfg["act"]], precision)


@functools.partial(jax.jit, static_argnames=("cfg", "kind", "sparse",
                                             "precision"))
def _layer_jit(x, lw, cfg, kind, sparse, precision):
    return _layer(x, lw, json.loads(cfg), kind, sparse, precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, final_norm, head, positions, eps, precision):
    h = _rms_norm(jnp.take(x, positions, axis=0), final_norm, eps)
    return _mm("sd,dv->sv", h, head, precision, (-1,), (0,))


def logits_at(w, cfg, tokens, positions, precision="float32"):
    """Logits [n, V] (float32) at ``positions`` of ONE sequence ``tokens``
    [S] (``S`` a multiple of ``Q_BLOCK``): the full forward pass, layer by
    layer. ``cfg``: ``layer_types`` and ``sparse`` (one entry a layer),
    ``kinds`` (``{kind: {"window", "rope"}}``), ``kv_heads``, ``top_k``,
    ``router`` (``score``, ``norm_topk``, ``scale``), ``act``, ``eps``."""
    if len(tokens) % Q_BLOCK:
        raise ValueError(f"pad the sequence to a multiple of {Q_BLOCK}")
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], jnp.asarray(tokens), axis=0) \
            .astype(jnp.float32)
        key = json.dumps({k: v for k, v in cfg.items()
                          if k not in ("layer_types", "sparse")},
                         sort_keys=True)         # hashable: a static argument
        for lw, kind, sparse in zip(w["layers"], cfg["layer_types"],
                                    cfg["sparse"]):
            x = _layer_jit(x, lw, key, kind, bool(sparse), precision)
        return _head(x, w["final_norm"], w["head"], jnp.asarray(positions),
                     cfg["eps"], precision)
