"""Attention ops: scaled dot-product attention, RoPE, causal masking.

The reference has no attention models at all (SURVEY §5.7 — dist-keras
predates transformers; its examples are MLP/CNN/(Bi)LSTM). This module is
part of the TPU build's first-class long-context story: the functional core
consumed by ``models.attention.MultiHeadAttention``, the Pallas flash kernel
(``ops.flash_attention``) and the sequence-parallel ring variant
(``ops.ring_attention``).

Conventions:
  * Layout is **BSHD**: ``q/k/v`` are ``[batch, seq, heads, head_dim]``.
  * Softmax math is float32 regardless of input dtype (bf16-safe).
  * ``NEG_INF`` is a large finite negative instead of ``-inf`` so fully
    masked rows produce zeros, not NaNs.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def causal_mask(q_len: int, k_len: int, q_offset: int = 0,
                k_offset: int = 0) -> jnp.ndarray:
    """Boolean [q_len, k_len] mask, True where attention is allowed.

    Offsets give the global position of the first row/column — used by the
    ring variant where each device holds a sequence shard.
    """
    q_pos = q_offset + jnp.arange(q_len)[:, None]
    k_pos = k_offset + jnp.arange(k_len)[None, :]
    return q_pos >= k_pos


def block_causal_mask(q_len: int, k_len: int, block_len: int) -> jnp.ndarray:
    """Boolean [q_len, k_len] mask of block-causal attention: query ``i``
    sees key ``j`` iff ``j // block_len <= i // block_len``."""
    q_blk = jnp.arange(q_len)[:, None] // block_len
    k_blk = jnp.arange(k_len)[None, :] // block_len
    return q_blk >= k_blk


def dot_product_attention(q, k, v, *, causal: bool = False,
                          mask: Optional[jnp.ndarray] = None,
                          scale: Optional[float] = None,
                          window: Optional[int] = None,
                          segment_ids: Optional[jnp.ndarray] = None,
                          block_len: Optional[int] = None
                          ) -> jnp.ndarray:
    """Reference (pure-XLA) attention. BSHD in, BSHD out.

    XLA fuses this well for moderate sequence lengths; the Pallas flash
    kernel (``ops.flash_attention``) avoids materializing the [S, S] scores
    for long sequences.

    ``window=W`` (requires ``causal``) restricts each query to the last W
    keys — causal sliding-window attention.

    ``segment_ids``: [B, S] int — packed/variable-length sequences.
    Attention is restricted to positions with EQUAL ids (cross-segment
    scores are masked to NEG_INF), composing with ``causal``/``window``.

    ``block_len=B`` (requires ``causal``) makes the mask BLOCK-causal:
    query ``i`` sees key ``j`` iff ``j // B <= i // B`` — bidirectional
    inside a block of B positions, causal over blocks (block-diffusion
    language models).
    The convention: give padding its own id (e.g. -1); padded rows then
    attend only to each other and the loss masks them out
    (``losses.masked_sparse_categorical_crossentropy_from_logits``).
    """
    head_dim = q.shape[-1]
    if scale is None:
        scale = head_dim ** -0.5
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    if block_len is not None and not causal:
        raise ValueError("block_len requires causal=True")
    # [B, H, Sq, Sk] scores in f32
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        if block_len is None:
            allowed = causal_mask(q.shape[1], k.shape[1])
        else:
            allowed = block_causal_mask(q.shape[1], k.shape[1], block_len)
        if window is not None:
            q_pos = jnp.arange(q.shape[1])[:, None]
            k_pos = jnp.arange(k.shape[1])[None, :]
            allowed = allowed & (k_pos > q_pos - window)
        s = jnp.where(allowed[None, None], s, NEG_INF)
    if segment_ids is not None:
        same = segment_ids[:, :, None] == segment_ids[:, None, :]
        s = jnp.where(same[:, None], s, NEG_INF)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, base: float = 10000.0) -> jnp.ndarray:
    """Inverse frequencies for RoPE: [head_dim // 2] float32."""
    return 1.0 / (base ** (jnp.arange(0, head_dim, 2,
                                      dtype=jnp.float32) / head_dim))


def yarn_inv_freq(rotary_dim: int, base: float, factor: float,
                  original_max_len: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0, truncate: bool = True):
    """YaRN's inverse frequencies ``[rotary_dim // 2]`` (numpy float32), as
    ``transformers`` computes them (``_compute_yarn_parameters``):
    dimension ``i`` blends the plain frequency ``base^(-2i/dim)`` with
    that frequency over ``factor`` by a linear ramp between the
    correction dimensions of ``beta_fast`` and ``beta_slow`` rotations
    over ``original_max_len`` positions — fast dimensions keep their
    frequency, slow ones are interpolated."""
    import math

    import numpy as np
    dim = int(rotary_dim)

    def correction_dim(rotations):
        return dim * math.log(original_max_len / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low, high = correction_dim(beta_fast), correction_dim(beta_slow)
    if truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                    # share of the plain frequency
    inv = (1.0 / (factor * pos_freqs)) * (1.0 - keep) \
        + (1.0 / pos_freqs) * keep
    return inv.astype(np.float32)        # numpy: a constant, safe to keep


def yarn_attention_factor(factor: float) -> float:
    """The scale YaRN puts on cosine and sine where the configuration
    states none: ``0.1 ln(factor) + 1`` (1 at ``factor <= 1``)."""
    import math
    return 1.0 if factor <= 1 else 0.1 * math.log(factor) + 1.0


def apply_rope(x, positions=None, base: float = 10000.0,
               layout: str = "bshd", scale: float = 1.0,
               rotary_dim: Optional[int] = None, inv_freq=None,
               mscale: float = 1.0):
    """Rotary position embedding on a BSHD (default) or BHSD tensor.

    ``positions``: optional [S] or [B, S] int array of global token positions
    (defaults to 0..S-1 — pass explicit positions for sequence-sharded
    shards in ring attention).

    ``scale > 1`` is linear position interpolation (Chen et al. 2023):
    positions are divided by ``scale`` so a model trained to length L
    serves length ``scale * L`` inside its trained rotary range — the
    standard cheap long-context extension.

    ``rotary_dim`` rotates only the first ``rotary_dim`` dimensions of
    each head (a partial rotary factor); the rest pass through.
    ``inv_freq`` (``[rotary_dim // 2]``) replaces the frequencies
    ``base`` would give (:func:`yarn_inv_freq`), and ``mscale``
    multiplies cosine and sine (YaRN's attention factor).
    """
    if layout == "bhsd":
        b, h, s, d = x.shape
    else:
        b, s, h, d = x.shape
    if positions is None:
        positions = jnp.arange(s)
    positions = jnp.asarray(positions, jnp.float32)
    if scale != 1.0:
        positions = positions / scale
    if positions.ndim == 1:
        positions = positions[None, :]  # [1, S] broadcasts over batch
    rot = d if rotary_dim is None else int(rotary_dim)
    if rot > d or rot % 2:
        raise ValueError(f"rotary_dim {rot} must be even and at most the "
                         f"head size {d}")
    freqs = rope_frequencies(rot, base) if inv_freq is None \
        else jnp.asarray(inv_freq, jnp.float32)         # [rot/2]
    angles = positions[..., None] * freqs               # [B?, S, rot/2]
    if layout == "bhsd":
        cos = jnp.cos(angles)[:, None, :, :]            # [B?, 1, S, rot/2]
        sin = jnp.sin(angles)[:, None, :, :]
    else:
        cos = jnp.cos(angles)[:, :, None, :]            # [B?, S, 1, rot/2]
        sin = jnp.sin(angles)[:, :, None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    xr = x if rot == d else x[..., :rot]
    x1, x2 = xr[..., ::2].astype(jnp.float32), xr[..., 1::2].astype(jnp.float32)
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(xr.shape).astype(x.dtype)
    if rot == d:
        return out
    return jnp.concatenate([out, x[..., rot:]], axis=-1)
