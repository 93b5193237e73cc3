"""The plain reference of the SDAR family (JetLM/SDAR-30B-A3B-Chat,
``model_type`` ``sdar_moe``): a Qwen3-style mixture-of-experts decoder
that generates by diffusion over blocks. Straight-line ``jax.numpy`` in
float32 at ``highest`` matrix precision; no kernel, no cache, no batching
of requests. It imports nothing of the program and is handed the
benchmark's own weights (``harness/sdar_family.py``), which it keeps in the
type they are served in (bfloat16) and widens one layer, and inside it one
expert, as it uses it.

Layer, with ``n1``, ``n2`` RMSNorm over the model width and ``qnorm``,
``knorm`` RMSNorm over each head (epsilon 1e-6 throughout)::

    h = x + Wo . Attn(RoPE(qnorm(Wq n1(x))), RoPE(knorm(Wk n1(x))), Wv n1(x))
    y = h + sum_{e in top8(p)} (p_e / sum_top8 p) . Wd_e(silu(Wg_e n2(h)) * Wu_e n2(h))
    p = softmax(Wr n2(h)) over all experts, in float32

Attention is grouped-query (each KV head serves ``heads / kv_heads`` query
heads), scaled by ``1/sqrt(head_dim)``, under the BLOCK-causal mask: with
block length ``B`` position ``i`` sees key ``j`` iff ``j // B <= i // B``.
RoPE rotates the pairs ``(2i, 2i+1)`` of a head (as this repository's
``apply_rope`` does; the source pairs ``(i, i + head_dim/2)``, which is the
same function under a fixed permutation of each head's columns of ``Wq``
and ``Wk``: stated in the configuration file under ``departures``).

Every expert's product is taken for every token and weighted by a gate that
is zero outside the token's top-k: the plainest form, sixteen times the
routed operations at top-8 of 128, which a reference can afford.

``generate`` is the family's published ``generate.py`` with
``low_confidence_static`` remasking at temperature 0, logits unshifted
(position ``i`` predicts token ``i``).

``precision`` selects how every matrix product is taken: ``"float32"`` (the
reference proper), ``"bfloat16"`` (what the configuration states; a
witness), ``"int8"`` (the control: operands rounded to 8-bit integers with
one scale per contracted row).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
HIGHEST = jax.lax.Precision.HIGHEST


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


def _rms_norm(x, g):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + EPS) \
        * g.astype(jnp.float32)


def _rope(x, theta):
    """``x`` [S, H, D] at positions 0..S-1; pairs ``(2i, 2i+1)``."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _experts(h, lw, top_k, precision):
    """The expert layer on ``h`` [S, d] (already normed): every expert for
    every token, gated; the gate is zero outside the token's top-k."""
    logits = jnp.einsum("sd,de->se", h, lw["router"].astype(jnp.float32),
                        precision=HIGHEST)                 # float32 router
    p = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(p, top_k)
    gates = jnp.zeros_like(p).at[
        jnp.arange(p.shape[0])[:, None], top_i].set(
        top_p / jnp.sum(top_p, axis=-1, keepdims=True))    # norm_topk_prob

    def one(acc, ew):
        wg, wu, wd, gate = ew
        a = _mm("sd,df->sf", h, wg, precision, (-1,), (0,))
        u = _mm("sd,df->sf", h, wu, precision, (-1,), (0,))
        y = _mm("sf,fd->sd", jax.nn.silu(a) * u, wd, precision, (-1,), (0,))
        return acc + gate[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (lw["wg"], lw["wu"], lw["wd"], gates.T))
    return out


def _layer(x, lw, cfg, precision):
    """One decoder layer on ``x`` [S, d] float32."""
    s = x.shape[0]
    hq, hkv, b = cfg["heads"], cfg["kv_heads"], cfg["block_len"]
    h = _rms_norm(x, lw["n1"])
    q = _mm("sd,dhe->she", h, lw["wq"], precision, (-1,), (0,))
    k = _mm("sd,dhe->she", h, lw["wk"], precision, (-1,), (0,))
    v = _mm("sd,dhe->she", h, lw["wv"], precision, (-1,), (0,))
    q = _rope(_rms_norm(q, lw["qn"]), cfg["rope_theta"])
    k = _rope(_rms_norm(k, lw["kn"]), cfg["rope_theta"])
    g = hq // hkv
    qg = q.reshape(s, hkv, g, -1)
    scores = _mm("qhge,khe->hgqk", qg, k, precision, (-1,), (-1,)) \
        / jnp.sqrt(jnp.float32(q.shape[-1]))
    blk = jnp.arange(s) // b
    scores = jnp.where(blk[:, None] >= blk[None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm("hgqk,khe->qhge", probs, v, precision, (-1,), (0,))
    x = x + _mm("she,hed->sd", o.reshape(s, hq, -1), lw["wo"], precision,
                (1, 2), (0, 1))
    return x + _experts(_rms_norm(x, lw["n2"]), lw, cfg["top_k"], precision)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _layer_jit(x, lw, cfg, precision):
    return _layer(x, lw, dict(cfg), precision)


@functools.partial(jax.jit, static_argnames=("precision",))
def _head(x, final_norm, head, positions, precision):
    h = _rms_norm(jnp.take(x, positions, axis=0), final_norm)
    return _mm("sd,dv->sv", h, head, precision, (-1,), (0,))


def logits_at(w, cfg, tokens, positions, precision="float32"):
    """Logits [n, V] (float32) at ``positions`` of ONE sequence ``tokens``
    [S]: the full forward pass under the block-causal mask, layer by layer.
    ``cfg``: ``heads``, ``kv_heads``, ``top_k``, ``block_len``,
    ``rope_theta``."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], jnp.asarray(tokens), axis=0) \
            .astype(jnp.float32)
        key = tuple(sorted(cfg.items()))
        for lw in w["layers"]:
            x = _layer_jit(x, lw, key, precision)
        return _head(x, w["final_norm"], w["head"], jnp.asarray(positions),
                     precision)


def confidences(logits):
    """Per position: the most probable token and its log-probability
    (best logit less the log-sum-exp), from logits [n, V]."""
    logits = np.asarray(logits, np.float64)
    m = logits.max(-1)
    lse = m + np.log(np.exp(logits - m[:, None]).sum(-1))
    return logits.argmax(-1), m - lse


def fix_schedule(block_len: int, steps: int):
    """Tokens fixed by each denoising pass: ``block_len`` spread evenly
    over ``steps``, the earlier passes taking the remainder."""
    return [block_len // steps + (i < block_len % steps)
            for i in range(steps)]


def generate(w, cfg, prompt, max_new_tokens, mask_token, steps=None,
             precision="float32", pad_to=None):
    """Block-diffusion generation of ``max_new_tokens`` tokens after
    ``prompt``. Returns ``(tokens, fixed_pass, trajectory)``: the generated
    tokens; for each the denoising pass (0-based, within its block) that
    fixed it; and one entry per denoising pass,
    ``(block_start, [positions fixed], [tokens], logits [B, V])``.

    Each pass is a full forward over everything up to the block's end (no
    cache): blocks before it hold their final tokens, the block holds mask
    tokens where nothing is fixed yet. ``pad_to`` pads the sequence with
    mask tokens to one length (a later block is invisible under the mask),
    so that one compiled forward serves every pass."""
    b = cfg["block_len"]
    steps = b if steps is None else steps
    sched = fix_schedule(b, steps)
    prompt = [int(t) for t in prompt]
    total = -(-(len(prompt) + max_new_tokens) // b) * b
    seq = np.full(total if pad_to is None else max(pad_to, total),
                  mask_token, np.int64)
    seq[:len(prompt)] = prompt
    masked = np.ones(len(seq), bool)
    masked[:len(prompt)] = False
    fixed_pass = np.full(len(seq), -1)
    trajectory = []
    for start in range(len(prompt) // b * b, total, b):
        span = np.arange(start, start + b)
        for step in range(steps):
            if not masked[span].any():
                break
            lg = np.asarray(logits_at(w, cfg, seq, span, precision))
            best, conf = confidences(lg)
            n_fix = min(sched[step], int(masked[span].sum()))
            order = np.argsort(np.where(masked[span], -conf, np.inf),
                               kind="stable")[:n_fix]
            seq[span[order]] = best[order]
            masked[span[order]] = False
            fixed_pass[span[order]] = step
            trajectory.append((start, span[order].tolist(),
                               best[order].tolist(), lg))
    end = len(prompt) + max_new_tokens
    return (seq[len(prompt):end].tolist(),
            fixed_pass[len(prompt):end].tolist(), trajectory)
