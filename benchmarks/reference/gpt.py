"""The plain reference: a GPT-2-style dense decoder (Cerebras-GPT,
arXiv:2304.03208) as this repository builds it, straight-line ``jax.numpy``
in float32 at ``highest`` matrix precision. It imports nothing of the program
and is handed the benchmark's own weights (``harness/weights.py``, stacked
over layers).

Architecture as built (departures from the source are listed in the
configuration files): token + learned position embeddings; per layer
``x += attn(LN(x))`` then ``x += mlp(LN(x))`` with causal softmax attention
scaled by 1/sqrt(d_head), no attention biases, a biased GELU(tanh) MLP; a final
LayerNorm and an untied, bias-free vocabulary head. LayerNorm epsilon 1e-5.

``precision`` selects how every matrix product is taken:

* ``"float32"``: the reference proper.
* ``"bfloat16"``: operands rounded to bfloat16, float32 accumulation: what the
  configurations state. A witness, never the yardstick.
* ``"int8"``, ``"fp8"``: the controls, the steps below bfloat16 that would
  tempt a later PR: operands rounded to 8-bit integers, or to float8 (e4m3),
  with one scale per contracted row (straight-through gradients).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
HIGHEST = jax.lax.Precision.HIGHEST


def _q8(x, axes):
    """Round to a signed 8-bit grid, one scale per slice along ``axes``."""
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.round(x / scale) * scale
    return x + jax.lax.stop_gradient(q - x)


def _q_fp8(x, axes):
    """Round to float8 (e4m3, 3 bits of mantissa), each slice along ``axes``
    scaled so that its largest magnitude is the format's largest, 448."""
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(eq, a, b, precision, a_axes, b_axes):
    """``einsum`` at the stated precision; ``*_axes`` are the contracted axes."""
    if precision == "float32":
        return jnp.einsum(eq, a, b, precision=HIGHEST)
    if precision == "bfloat16":
        return jnp.einsum(eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if precision in ("int8", "fp8"):
        q = _q8 if precision == "int8" else _q_fp8
        return jnp.einsum(eq, q(a, a_axes), q(b, b_axes), precision=HIGHEST)
    raise ValueError(f"unknown precision {precision!r}")


def _layer_norm(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _block(x, lw, precision):
    """One decoder layer on ``x`` [B, S, D]; ``lw`` holds this layer's leaves."""
    s = x.shape[1]
    h = _layer_norm(x, lw["ln1_g"], lw["ln1_b"])
    q = _mm("bsd,dhe->bhse", h, lw["wq"], precision, (-1,), (0,))
    k = _mm("bsd,dhe->bhse", h, lw["wk"], precision, (-1,), (0,))
    v = _mm("bsd,dhe->bhse", h, lw["wv"], precision, (-1,), (0,))
    scores = _mm("bhqe,bhke->bhqk", q, k, precision, (-1,), (-1,))
    scores = scores / jnp.sqrt(jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm("bhqk,bhke->bhqe", probs, v, precision, (-1,), (-2,))
    x = x + _mm("bhse,hed->bsd", o, lw["wo"], precision, (1, 3), (0, 1))
    h = _layer_norm(x, lw["ln2_g"], lw["ln2_b"])
    m = _mm("bsd,df->bsf", h, lw["w1"], precision, (-1,), (0,)) + lw["b1"]
    m = jax.nn.gelu(m, approximate=True)
    return x + _mm("bsf,fd->bsd", m, lw["w2"], precision, (-1,), (0,)) + lw["b2"]


PER_LAYER = ("ln1_g", "ln1_b", "wq", "wk", "wv", "wo",
              "ln2_g", "ln2_b", "w1", "b1", "w2", "b2")


def hidden(w, tokens, precision="float32"):
    """Final-LayerNorm hidden states [B, S, D] for ``tokens`` [B, S]."""
    s = tokens.shape[1]
    x = jnp.take(w["wte"], tokens, axis=0) + w["wpe"][:s][None]
    body = jax.checkpoint(functools.partial(_block, precision=precision))
    x, _ = jax.lax.scan(lambda x, lw: (body(x, lw), None), x,
                        {k: w[k] for k in PER_LAYER})
    return _layer_norm(x, w["lnf_g"], w["lnf_b"])


def logits(w, tokens, precision="float32"):
    """Logits [B, S, V] in float32."""
    return _mm("bsd,dv->bsv", hidden(w, tokens, precision), w["head"],
               precision, (-1,), (0,))


def loss(w, tokens, labels, precision="float32"):
    """Mean cross-entropy over every position of ``tokens`` [B, S]."""
    logp = jax.nn.log_softmax(logits(w, tokens, precision), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def loss_and_grad(w, tokens, labels, precision="float32", rows=None):
    """Loss and gradient of the batch mean, one row at a time so that it fits
    beside nothing: the mean of the rows' gradients is the batch's gradient.
    ``rows`` (a slice) restricts the mean to part of the batch (a planted
    fault uses it)."""
    if rows is not None:
        tokens, labels = tokens[rows], labels[rows]

    def one(acc, xy):
        l, g = jax.value_and_grad(loss)(w, xy[0][None], xy[1][None], precision)
        return jax.tree_util.tree_map(jnp.add, acc, (l, g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree_util.tree_map(jnp.zeros_like, w))
    (l, g), _ = jax.lax.scan(one, zero, (tokens, labels))
    n = tokens.shape[0]
    return l / n, jax.tree_util.tree_map(lambda a: a / n, g)


def adam_init(w):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, w)
    return {"m": zeros, "v": jax.tree_util.tree_map(jnp.zeros_like, w),
            "t": jnp.zeros((), jnp.int32)}


def adam_step(w, opt, grads, lr, b1=0.9, b2=0.999, eps=1e-7):
    """Adam as Keras states it (epsilon outside the root, bias correction in
    the step size), which is what the cell's trainer is configured with."""
    t = opt["t"] + 1
    tf = t.astype(jnp.float32)
    m = jax.tree_util.tree_map(lambda m_, g: b1 * m_ + (1 - b1) * g, opt["m"], grads)
    v = jax.tree_util.tree_map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, opt["v"], grads)
    step = lr * jnp.sqrt(1 - b2 ** tf) / (1 - b1 ** tf)
    w = jax.tree_util.tree_map(
        lambda p, m_, v_: p - step * m_ / (jnp.sqrt(v_) + eps), w, m, v)
    return w, {"m": m, "v": v, "t": t}


@functools.partial(jax.jit, static_argnames=("lr", "precision", "rows"),
                   donate_argnums=(0, 1))
def train_step(w, opt, tokens, labels, lr, precision="float32", rows=None):
    """One step: ``(w, opt, loss)`` after it."""
    rows = None if rows is None else slice(*rows)
    l, g = loss_and_grad(w, tokens, labels, precision, rows)
    w, opt = adam_step(w, opt, g, lr)
    return w, opt, l


@functools.partial(jax.jit, static_argnames=("precision",))
def served_logits(w, tokens, positions, precision="float32"):
    """Logits [n, V] at ``positions`` of one sequence ``tokens`` [S]: the
    full forward pass over the prompt and its served tokens, no cache."""
    h = hidden(w, tokens[None], precision)[0]
    return _mm("sd,dv->sv", jnp.take(h, positions, axis=0), w["head"],
               precision, (-1,), (0,))
