"""Autoregressive decoding with a KV cache: ``generate()`` for the LM family.

Capability ADD with no reference analogue (dist-keras predates generative
models; its Predictor is batch-scoring only — SURVEY §3.4). TPU-first
design:

  * One compiled program per configuration: a batched PREFILL over the
    whole prompt (one causal flash pass per layer writing all cache
    positions at once — round 4; an 8K prompt is one kernel sweep, not
    8K sequential steps) followed by ONE jitted ``lax.scan`` over the
    new tokens — no per-token Python dispatch, static shapes throughout.
  * The cache is a head-major ``[B, Hkv, cap, Dh]`` buffer created
    INSIDE the compiled program and written with
    ``dynamic_update_slice``; ``cache_dtype="int8"`` stores quantized
    payloads with per-token-per-head scales.
  * Per-step attention is the fused Pallas kernel
    (``ops.decode_attention``) for deep caches on TPU, or a
    storage-dtype einsum with a causal validity mask otherwise — the
    [S, S] score matrix never exists; each step is O(L) like flash
    decoding.

Works on ``zoo.transformer_lm``-shaped models: a ``Sequential`` of
Embedding / PositionalEmbedding / TransformerBlock (optionally
Remat-wrapped) / norm / Dense. MoE blocks: ``generate()``'s scalar path
runs each block's configured routing (dense routing is per-token
already — it is the serving oracle); the SLOT-level steps below default
to the decode-specialized DISPATCHED path (``MoE.decode_apply`` —
drop-free by construction, fused Pallas gather-into-GEMM on TPU, the
XLA tokens floor elsewhere; MoE-serving PR), which equals dense routing
token-for-token while engaging the sparse-dispatch machinery at decode
shapes. Sequence-parallel ``attn_impl`` settings are ignored at decode
time — generation is a single-device (or TP/EP-sharded) path.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from distkeras_tpu.compat import backend_is_tpu, note_path
from distkeras_tpu.models.attention import (LatentAttention,
                                            MultiHeadAttention,
                                            PositionalEmbedding,
                                            TransformerBlock)
from distkeras_tpu.models.core import Model, Sequential, scoped
from distkeras_tpu.models.layers import Dropout
from distkeras_tpu.ops.attention import NEG_INF


def _decode_block_of(layer):
    """The TransformerBlock a decode step should run for ``layer``, or
    None for position-wise layers. Unwraps ``Remat`` (a training-time
    memory policy — decoding reads the inner block directly; round 4:
    before this, a remat-wrapped model silently decoded GARBAGE because
    the wrapper fell through to the position-wise branch, running
    cache-less self-attention on single tokens)."""
    from distkeras_tpu.models.blocks import Remat
    if isinstance(layer, TransformerBlock):
        return layer
    if isinstance(layer, Remat) and isinstance(layer.inner,
                                               TransformerBlock):
        return layer.inner
    return None


def block_len_of(module: Sequential) -> Optional[int]:
    """The block length of a block-causal model (every attention layer
    states the same one), or None for a causal model."""
    lens = {blk.attn.block_len for blk in
            (_decode_block_of(layer) for layer in module.layers)
            if blk is not None}
    if len(lens) > 1:
        raise ValueError(
            f"attention layers disagree on block_len: {sorted(map(str, lens))}")
    return lens.pop() if lens else None


def init_cache(module: Sequential, batch: int, max_len: int,
               dtype=jnp.float32, check_len: int = None):
    """Per-layer KV buffers ([B, H, max_len, Dh]) mirroring the Sequential;
    non-attention layers get ``None``. The HEAD-major layout (round 4)
    keeps each head's [L, Dh] plane contiguous, so the per-step cache
    einsums read full DMA lines — the token-major [B, L, H, Dh] layout
    made every head read a 128-byte strided gather (~1/4 effective HBM
    bandwidth measured at L=2113 on v5e).

    ``dtype="int8"`` (round 4) builds a QUANTIZED cache: int8 k/v plus f32
    per-token-per-head scales ([B, H, max_len]) — each written entry
    stores ``round(x / scale) * scale`` with ``scale = max|x| / 127`` over
    its head vector. At long contexts the cache read dominates the decode
    roofline (docs/PERF.md), so int8 halves the dominant term vs bf16;
    the scale read is Dh=64x smaller than the payload. Composes with GQA
    (scales are per KV head).

    ``dtype="int4"`` (this PR) extends the ladder one more rung: entries
    quantize to 4-bit symmetric (``scale = max|x| / 7``). In THIS
    unpacked contiguous cache the payload still occupies one int8 byte
    per entry holding a value in [-7, 7] — the dequant contract
    (``q * scale``) is byte-for-byte the int8 contract, so every cache
    read path is shared verbatim; the 2x byte saving is realized where
    it matters, in ``PagedKVPool``'s packed page planes (two nibbles
    per byte along the position axis). The empty ``"q4"`` marker leaf
    records the 4-bit grid in the pytree STRUCTURE (jit-static, rides
    through scans/vmaps for free).
    """
    int4 = isinstance(dtype, str) and dtype == "int4"
    int8 = int4 or (isinstance(dtype, str) and dtype == "int8") or \
        (not isinstance(dtype, str) and jnp.dtype(dtype) == jnp.int8)
    cache = []
    for layer in module.layers:
        # custom serving loops enter through here: out-of-range position
        # gathers CLAMP under jit (silently wrong-position logits), so the
        # capacity check must fail loudly at cache construction too
        need = max_len if check_len is None else check_len
        if isinstance(layer, PositionalEmbedding) and need > layer.max_len:
            raise ValueError(
                f"PositionalEmbedding(max_len={layer.max_len}) is too small "
                f"for a {need}-position decode cache")
        block = _decode_block_of(layer)
        if block is not None and isinstance(block.attn, LatentAttention):
            # latent attention keeps ONE latent and one shared rope key
            # a token: a plane with no head axis and no separate V,
            # positions LAST (see the latent section below)
            if int8:
                raise ValueError(
                    "latent attention keeps its cache in a float type: "
                    "no int8 / int4 latent pages")
            cache.append({"c": jnp.zeros(
                (batch, block.attn.latent_dim, max_len), dtype)})
        elif block is not None:
            if block.shortcut is not None or block.shortcut_add:
                raise ValueError(
                    "a shortcut-connected expert layer is decoded round "
                    "latent attention blocks only")
            attn = block.attn
            # GQA: the cache stores only the kv heads — the whole point
            # of grouped queries at serving time
            h = attn.kv_heads
            # head_dim resolves at init; recover it from the layer config
            dh = attn.head_dim
            if dh is None:
                raise ValueError(
                    "init_cache needs head_dim; build the model first "
                    "(Model.build resolves it) or pass head_dim explicitly")
            shape = (batch, h, max_len, dh)
            if int8:
                kv = {
                    "k": jnp.zeros(shape, jnp.int8),
                    "v": jnp.zeros(shape, jnp.int8),
                    "k_scale": jnp.zeros(shape[:3], jnp.float32),
                    "v_scale": jnp.zeros(shape[:3], jnp.float32)}
                if int4:
                    # structural marker, not data: 4-dim so every
                    # blind cache tree_map (row slices, offload
                    # gather/scatter) stays shape-compatible
                    kv["q4"] = jnp.zeros((1, 1, 1, 1), jnp.int8)
                cache.append(kv)
            else:
                cache.append({"k": jnp.zeros(shape, dtype),
                              "v": jnp.zeros(shape, dtype)})
        else:
            if getattr(layer, "accepts_segment_ids", False):
                # the layer contains attention the decode loop does not
                # know how to cache — applying it position-wise would
                # silently decode garbage (each token attending only to
                # itself), so refuse up front
                raise ValueError(
                    f"decode path does not support layer {layer!r}: it "
                    "contains attention but is not a TransformerBlock "
                    "(or Remat-wrapped TransformerBlock)")
            cache.append(None)
    return cache


def _quantize_kv(x, bits: int = 8):
    """[..., Dh] float -> (int8 payload, f32 [...] per-vector scale).
    ``bits=4`` quantizes to the symmetric 4-bit grid (values in
    [-7, 7], ``scale = max|x| / 7``) while still returning one int8
    byte per entry — the dequant contract (``q * scale``) is identical
    across bit widths, so every read path is shared; nibble packing is
    a storage concern owned by the paged pool."""
    qmax = 7.0 if bits == 4 else 127.0
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / qmax
    safe = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.clip(jnp.round(xf / safe[..., None]),
                 -qmax, qmax).astype(jnp.int8)
    return q, jnp.where(scale == 0.0, 0.0, safe)


def _kv_bits(kv) -> int:
    """Quantization bit width of a cache dict: 4 when the ``"q4"``
    marker leaf is present (pytree-structural, jit-static), else 8."""
    return 4 if "q4" in kv else 8


def pack_int4(q):
    """Pack an int4-valued int8 array to nibbles along ``axis=-2``
    (the position axis of a [..., L, D] plane): byte row ``r`` holds
    position ``r`` in the LOW nibble and position ``r + L//2`` in the
    HIGH nibble, halving the sublane extent (L must be even). All
    nibble math runs in int32 for portable two's-complement handling."""
    n = q.shape[-2]
    lo = q[..., : n // 2, :].astype(jnp.int32)
    hi = q[..., n // 2:, :].astype(jnp.int32)
    b = ((hi & 15) << 4) | (lo & 15)
    return (b - 256 * (b > 127)).astype(jnp.int8)


def unpack_int4(b):
    """Inverse of :func:`pack_int4`: [..., L//2, D] packed bytes ->
    [..., L, D] int4-valued int8 (positions in order along axis -2)."""
    b32 = b.astype(jnp.int32) & 255
    lo = b32 & 15
    lo = lo - 16 * (lo > 7)
    hi = (b32 >> 4) & 15
    hi = hi - 16 * (hi > 7)
    return jnp.concatenate([lo, hi], axis=-2).astype(jnp.int8)


def _cache_write(kv, k, v, t):
    """Write one [B, S_w, H, Dh] k/v slab (BSHD, as projected) at
    position ``t`` (S_w = 1 for decode steps, P for prefill) into the
    head-major [B, H, L, Dh] cache, quantizing if it is int8."""
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    if "k_scale" in kv:
        bits = _kv_bits(kv)
        qk, sk = _quantize_kv(kh, bits)
        qv, sv = _quantize_kv(vh, bits)
        out = {
            "k": lax.dynamic_update_slice_in_dim(kv["k"], qk, t, axis=2),
            "v": lax.dynamic_update_slice_in_dim(kv["v"], qv, t, axis=2),
            "k_scale": lax.dynamic_update_slice_in_dim(
                kv["k_scale"], sk, t, axis=2),
            "v_scale": lax.dynamic_update_slice_in_dim(
                kv["v_scale"], sv, t, axis=2)}
        if bits == 4:
            out["q4"] = kv["q4"]
        return out
    return {"k": lax.dynamic_update_slice_in_dim(
                kv["k"], kh.astype(kv["k"].dtype), t, axis=2),
            "v": lax.dynamic_update_slice_in_dim(
                kv["v"], vh.astype(kv["v"].dtype), t, axis=2)}


def _int8_mm_dtype():
    """Matmul dtype for the int8-dequant cache contractions: bf16 on TPU
    (native MXU mode), f32 elsewhere (CPU XLA's dot runtime has no
    bf16xbf16->f32 kernel)."""
    return jnp.bfloat16 if backend_is_tpu() else jnp.float32


def _decode_scores(qg, kv):
    """[B, 1, Hkv, G, D] f32 queries x cache -> [B, Hkv, G, 1, L] f32
    scores, matmul'ing in the cache's STORAGE dtype with f32 accumulation.
    Casting the cache itself up to f32 (the round-3 form) materializes a
    full-cache f32 copy per layer per step — 3x the HBM traffic the
    cache was shrunk to avoid. For int8 the per-token scale factors out
    of the D-contraction (s = kscale_k * <qg, k_int8>), so the payload
    read stays int8 and the scale applies on the tiny [.., L] scores."""
    if "k_scale" in kv:
        mdt = _int8_mm_dtype()
        s = jnp.einsum("bqhgd,bhkd->bhgqk", qg.astype(mdt),
                       kv["k"].astype(mdt),
                       preferred_element_type=jnp.float32)
        return s * kv["k_scale"][:, :, None, None, :]
    cdt = kv["k"].dtype
    return jnp.einsum("bqhgd,bhkd->bhgqk", qg.astype(cdt), kv["k"],
                      preferred_element_type=jnp.float32)


def _decode_mix(w, kv):
    """[B, Hkv, G, 1, L] f32 probabilities x cached values ->
    [B, 1, Hkv, G, D] f32, same storage-dtype contract as
    ``_decode_scores`` (for int8 the value scale folds into the
    probabilities BEFORE the matmul: <w * vscale, v_int8>)."""
    if "v_scale" in kv:
        mdt = _int8_mm_dtype()
        ws = w * kv["v_scale"][:, :, None, None, :]
        return jnp.einsum("bhgqk,bhkd->bqhgd", ws.astype(mdt),
                          kv["v"].astype(mdt),
                          preferred_element_type=jnp.float32)
    cdt = kv["v"].dtype
    return jnp.einsum("bhgqk,bhkd->bqhgd", w.astype(cdt), kv["v"],
                      preferred_element_type=jnp.float32)


def _resolve_head_dims(module: Sequential, params) -> None:
    """Fill in ``head_dim`` on each attention layer from its params (the
    layer leaves it None until init; decode needs it statically)."""
    for layer, p in zip(module.layers, params):
        block = _decode_block_of(layer)
        if block is not None and block.attn.head_dim is None:
            block.attn.head_dim = int(p["attn"]["wq"].shape[-1])


def _is_latent(block: TransformerBlock) -> bool:
    return isinstance(block.attn, LatentAttention)


def _refuse_latent(block: TransformerBlock, what: str) -> None:
    if _is_latent(block):
        raise NotImplementedError(
            f"latent attention is not decoded by {what}: it is served "
            "by ServingEngine (whole and chunked prefill, the paged "
            "one-token step)")


def _decode_attn(attn: MultiHeadAttention, p, kv, x, t):
    """One-token attention against the cache. x: [B, 1, d]; t: step.

    GQA-aware: the cache holds ``kv_heads`` heads; queries are grouped
    ``[B, 1, Hkv, G, D]`` and contracted against the cache directly — the
    shared K/V heads are never materialized ``G`` times."""
    dt = jnp.dtype(attn.dtype)
    xc = x.astype(dt)
    q, k, v = _project_qkv(attn, p, xc)
    if attn.use_rope:
        pos = jnp.full((1,), t)
        q = attn.rope(q, pos)
        k = attn.rope(k, pos)
    kv = _cache_write(kv, k, v, t)
    scale = (attn.head_dim or q.shape[-1]) ** -0.5
    b = q.shape[0]
    hkv = attn.kv_heads
    g = attn.num_heads // hkv
    dh = q.shape[-1]
    L = kv["k"].shape[2]
    from distkeras_tpu.ops.decode_attention import (MIN_KERNEL_LEN,
                                                    block_of,
                                                    decode_attention)
    if backend_is_tpu() and L >= MIN_KERNEL_LEN \
            and block_of(L) is not None:
        # deep caches only: at L < 1024 the per-program overhead of the
        # kernel's grid outweighs its single-pass read (measured — the
        # einsum path wins at the 136-position headline config), while
        # at depth the kernel is a clear multiple over the einsum
        # lowering's materialized broadcast product
        # fused Pallas path (round 4): one kernel per layer streams the
        # cache once — the XLA einsum lowering materializes the f32
        # broadcast product of every cache plane in HBM (~3x the bytes;
        # measured 0.37 ms/layer-step at L=2113). generate() sizes the
        # cache to a block multiple so serving always takes this path.
        note_path("decode_attention", "kernel")
        qr = q[:, 0].astype(dt).reshape(b, hkv, g, dh)             .reshape(b * hkv, g, dh)
        kr = kv["k"].reshape(b * hkv, L, dh)
        vr = kv["v"].reshape(b * hkv, L, dh)
        sc = {}
        if "k_scale" in kv:
            sc = {"k_scale": kv["k_scale"].reshape(b * hkv, L),
                  "v_scale": kv["v_scale"].reshape(b * hkv, L)}
        o = decode_attention(qr, kr, vr, t, scale=scale,
                             window=attn.attn_window, **sc)
        out = o.reshape(b, hkv, g, dh).reshape(b, 1, attn.num_heads, dh)             .astype(dt)
    else:
        note_path("decode_attention", "einsum_reference")
        qg = (q.astype(jnp.float32) * scale).reshape(
            b, 1, hkv, g, dh)                            # [B, 1, Hkv, G, D]
        s = _decode_scores(qg, kv)                       # [B, Hkv, G, 1, L]
        valid = jnp.arange(L) <= t
        if attn.attn_window is not None:
            valid &= jnp.arange(L) > t - attn.attn_window
        s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        out = _decode_mix(w, kv).astype(dt)
        out = out.reshape(b, 1, attn.num_heads, dh)
    y = jnp.einsum("bshe,hed->bsd", out, p["wo"].astype(dt))
    return y.astype(x.dtype), kv


def _decode_block(block: TransformerBlock, p, s, kv, x, t):
    _refuse_latent(block, "generate()'s scalar step")
    with jax.named_scope("attn"):
        h, _ = block.norm1.apply(p["norm1"], s["norm1"], x)
        a, kv = _decode_attn(block.attn, p["attn"], kv, h, t)
    x = x + a
    with jax.named_scope("mlp"):
        h, _ = block.norm2.apply(p["norm2"], s["norm2"], x)
        m, _ = block.mlp.apply(p["mlp"], s["mlp"], h, training=False)
    return x + m, kv


def _prefill_block(block: TransformerBlock, p, s, kv, x, positions,
                   routing=None):
    """Whole-prompt pass through one TransformerBlock: ONE causal
    attention over [B, P] (flash kernel on TPU) instead of P sequential
    decode steps, writing the block's K/V cache entries for every prompt
    position at once. Attention inside the prompt uses the exact
    (unquantized) K/V; an int8 cache quantizes what later DECODE steps
    read — the standard serving contract. ``routing`` as in
    :func:`_prefill_block_chunked`."""
    from distkeras_tpu.models.attention import _attention_compute

    if _is_latent(block):
        return _latent_prefill_block(block, p, s, kv, x, positions, 0,
                                     routing)
    attn = block.attn
    dt = jnp.dtype(attn.dtype)
    with jax.named_scope("attn"):
        h_, _ = block.norm1.apply(p["norm1"], s["norm1"], x)
        xc = h_.astype(dt)
        q, k, v = _project_qkv(attn, p["attn"], xc)
        if attn.use_rope:
            q = attn.rope(q, positions)
            k = attn.rope(k, positions)
        kv = _cache_write(kv, k, v, 0)
        ke, ve = attn._expand_kv(k, 2), attn._expand_kv(v, 2)
        impl = "flash" if backend_is_tpu() else "xla"
        out = _attention_compute(q, ke, ve, causal=True, impl=impl,
                                 window=attn.attn_window,
                                 block_len=attn.block_len)
        y = jnp.einsum("bshe,hed->bsd", out.astype(dt), p["attn"]["wo"]
                       .astype(dt))
    x = x + y.astype(x.dtype)
    with jax.named_scope("mlp"):
        h_, _ = block.norm2.apply(p["norm2"], s["norm2"], x)
        m = _apply_mlp_decode(block.mlp, p["mlp"], s["mlp"], h_,
                              routing is not None, routing)
    return x + m, kv


def _merge_attention(o_a, lse_a, o_b, lse_b):
    """Combine two normalized attention partials over DISJOINT key sets
    via their log-sum-exps (the flash-decoding combine): each partial is
    acc_i / l_i with lse_i = log l_i + m_i, so the exact joint result is
    the l-weighted average, computed through a shared max for stability.
    o: [..., S, D]; lse: [..., S]."""
    m = jnp.maximum(lse_a, lse_b)
    wa = jnp.exp(lse_a - m)[..., None]
    wb = jnp.exp(lse_b - m)[..., None]
    return (o_a.astype(jnp.float32) * wa + o_b.astype(jnp.float32) * wb) \
        / (wa + wb)


def _attn_lse(q, k, v, *, causal: bool, scale: float, layout: str,
              window=None, block_len=None):
    """Attention WITH its log-sum-exp: the real flash kernel on TPU, a
    plain XLA softmax path elsewhere (the chunked-prefill building block;
    interpreter-mode Pallas is too slow for long-prefix CPU tests).
    Layouts as in ``ops.flash_attention`` ('bshd'/'bhsd').
    ``block_len`` makes a causal pass block-causal (the chunk starts on
    a block boundary, so chunk-local positions give the same blocks)."""
    from distkeras_tpu.ops.flash_attention import _flash_forward
    if backend_is_tpu():
        note_path("flash_attention", "kernel")
        # mirror flash_attention's adaptive default: the square 1024
        # tile wins up to d_head 128, causal unwindowed
        bq = 1024 if (q.shape[-1] <= 128 and causal
                      and window is None) else 512
        bk = 1024 if window is None else 512
        return _flash_forward(q, k, v, scale, causal, bq, bk, False,
                              layout == "bhsd", window,
                              block_len=block_len if causal else None)
    note_path("flash_attention", "xla_reference")
    if layout == "bshd":
        qh = q.transpose(0, 2, 1, 3)
        kh = k.transpose(0, 2, 1, 3)
        vh = v.transpose(0, 2, 1, 3)
    else:
        qh, kh, vh = q, k, v
    s = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32) * scale,
                   kh.astype(jnp.float32))
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        qpos = jnp.arange(sq)[:, None] + (sk - sq)
        if block_len is not None:
            qpos = (qpos // block_len) * block_len + (block_len - 1)
        s = jnp.where(qpos >= jnp.arange(sk)[None, :], s, NEG_INF)
        if window is not None:
            s = jnp.where(jnp.arange(sk)[None, :] > qpos - window, s,
                          NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse[..., None]),
                   vh.astype(jnp.float32))
    if layout == "bshd":
        return o.transpose(0, 2, 1, 3).astype(q.dtype), lse
    return o.astype(q.dtype), lse


def _banded_prefix_attn(q, kp, vp, t0: int, lo: int, window: int,
                        scale: float):
    """Chunk queries against the sliding-window PREFIX BAND
    ``[lo, t0)`` (at most ``window - 1`` keys): plain masked attention
    with its lse — global query position ``t0 + i`` attends band key
    ``j`` iff ``j > t0 + i - window`` (causality ``j < t0 <= t0+i`` is
    structural). Queries whose window lies entirely inside the chunk
    get a fully-masked row; with the finite ``NEG_INF`` its lse is
    ~-1e30, so the lse merge weights that partial to exactly 0 — no
    special-casing needed. q: [B, Q, H, D]; kp/vp: [B, H, Lb, D]
    (already head-expanded; the band is < window keys, so the
    expansion is small)."""
    qh = q.transpose(0, 2, 1, 3)                         # [B, H, Q, D]
    s = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32) * scale,
                   kp.astype(jnp.float32))
    jpos = lo + jnp.arange(s.shape[-1])[None, :]         # band keys
    gi = t0 + jnp.arange(s.shape[-2])[:, None]           # global q pos
    s = jnp.where(jpos > gi - window, s, NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse[..., None]),
                   vp.astype(jnp.float32))
    return o.transpose(0, 2, 1, 3).astype(q.dtype), lse


def _cache_prefix(kv, upto: int, dt, lo: int = 0):
    """Cache positions ``[lo, upto)`` as dense [B, Hkv, upto-lo, D] k/v
    in the compute dtype (int8 payloads dequantize here — the chunked
    prefill attends to what later decode steps will read, the standard
    quantized-cache serving contract). Slicing BEFORE the dequant keeps
    the SWA band path O(window), not O(prefix)."""
    k = kv["k"][:, :, lo:upto]
    v = kv["v"][:, :, lo:upto]
    if "k_scale" in kv:
        k = (k.astype(jnp.float32)
             * kv["k_scale"][:, :, lo:upto, None]).astype(dt)
        v = (v.astype(jnp.float32)
             * kv["v_scale"][:, :, lo:upto, None]).astype(dt)
    return k.astype(dt), v.astype(dt)


def _prefill_block_chunked(block: TransformerBlock, p, s, kv, x, positions,
                           t0: int, routing=None, kv_only: bool = False):
    """One chunk of one TransformerBlock (round 5, VERDICT r4 #5): the
    chunk's queries attend to (a) the ALREADY-WRITTEN cache prefix
    [0, t0) — one non-causal flash pass, with the GQA group folded into
    the query rows so the shared K/V heads are never expanded — and (b)
    the chunk itself, causally; the two partials merge exactly through
    their log-sum-exps. Sliding-window models use a windowed diagonal
    pass plus a masked PREFIX BAND of the last ``window - 1`` positions
    (``_banded_prefix_attn``). Activation memory is O(chunk), not O(P):
    the [B, P, H, D] per-layer q/k/v of the one-pass prefill never
    exist.

    ``kv_only``: the block stops once its keys and values are written
    (the deepest block of a chunk whose output nothing reads).
    ``routing`` (a list): expert layers take the drop-free dispatched
    path of the decode steps and append their routing to it
    (``_apply_mlp_decode``); None leaves the layer its own ``apply``."""
    if _is_latent(block):
        return _latent_prefill_block(block, p, s, kv, x, positions, t0,
                                     routing, kv_only)
    attn = block.attn
    dt = jnp.dtype(attn.dtype)
    with jax.named_scope("attn"):
        h_, _ = block.norm1.apply(p["norm1"], s["norm1"], x)
        xc = h_.astype(dt)
        q, k, v = _project_qkv(attn, p["attn"], xc)
        if attn.use_rope:
            q = attn.rope(q, positions)
            k = attn.rope(k, positions)
        kv = _cache_write(kv, k, v, t0)
        if kv_only:
            return x, kv
        b, q_len, nh, dh = q.shape
        hkv = attn.kv_heads
        g = nh // hkv
        scale = (attn.head_dim or dh) ** -0.5
        window = attn.attn_window
        # (b) causal within the chunk (small: kv expansion is chunk-sized);
        # sliding-window models window the diagonal pass too
        ke, ve = attn._expand_kv(k, 2), attn._expand_kv(v, 2)
        if attn.block_len is not None and t0 % attn.block_len:
            raise ValueError(
                f"a block-causal prefill chunk starts on a block boundary "
                f"(t0 {t0}, block_len {attn.block_len})")
        o_diag, lse_diag = _attn_lse(q, ke, ve, causal=True, scale=scale,
                                     layout="bshd", window=window,
                                     block_len=attn.block_len)
        # prefix reach: everything before the chunk for full attention; only
        # the last window-1 positions for SWA (older keys are out of every
        # chunk query's reach)
        lo = 0 if window is None else max(0, t0 - window + 1)
        if t0 > lo:
            kp, vp = _cache_prefix(kv, t0, dt, lo=lo)
            if window is None:
                # (a) chunk vs prefix: no causal structure (every chunk
                # query is newer than every prefix key), so the G query
                # heads sharing one KV head fold into the ROW axis —
                # [B*Hkv, G*Q, D] against [B*Hkv, t0, D] — and the cache is
                # read in its native head-major layout with no expansion
                qg = q.reshape(b, q_len, hkv, g, dh) \
                      .transpose(0, 2, 3, 1, 4) \
                      .reshape(b * hkv, 1, g * q_len, dh)
                o_pre, lse_pre = _attn_lse(
                    qg, kp.reshape(b * hkv, 1, t0, dh),
                    vp.reshape(b * hkv, 1, t0, dh),
                    causal=False, scale=scale, layout="bhsd")
                o_pre = o_pre.reshape(b, hkv, g, q_len, dh) \
                             .transpose(0, 3, 1, 2, 4) \
                             .reshape(b, q_len, nh, dh)
                # (hkv, g) are already adjacent in head order
                # h = hkv_i*g + g_i: flatten directly — a transpose here
                # would scramble (pos, group)
                lse_pre = lse_pre.reshape(b, hkv, g, q_len) \
                                 .reshape(b, nh, q_len)
            else:
                # (a') SWA prefix BAND [lo, t0): the window edge crosses the
                # band per query, so this is masked attention (the GQA fold
                # would break the per-position mask); the band is < window
                # keys, so expanding its kv heads in place (axis 1 of the
                # native [B, Hkv, Lb, D] layout) is small. Round 5: closes
                # the chunked-prefill SWA gap.
                o_pre, lse_pre = _banded_prefix_attn(
                    q, attn._expand_kv(kp, 1), attn._expand_kv(vp, 1),
                    t0, lo, window, scale)
            out = _merge_attention(
                o_pre.transpose(0, 2, 1, 3), lse_pre,
                o_diag.transpose(0, 2, 1, 3), lse_diag).transpose(0, 2, 1, 3)
        else:
            out = o_diag
        y = jnp.einsum("bshe,hed->bsd", out.astype(dt),
                       p["attn"]["wo"].astype(dt))
    x = x + y.astype(x.dtype)
    with jax.named_scope("mlp"):
        h_, _ = block.norm2.apply(p["norm2"], s["norm2"], x)
        m = _apply_mlp_decode(block.mlp, p["mlp"], s["mlp"], h_,
                              routing is not None, routing)
    return x + m, kv

# --- latent attention (MLA) -------------------------------------------------
#
# A ``LatentAttention`` block's cache entry is ``{"c": [B, latent_dim, L]}``
# (the page pool: ``[N, latent_dim, page_len]``): per token the scaled
# normed latent and behind it the roped shared key, no head axis and no
# separate V. The POSITIONS are the last axis: ``latent_dim`` (576 = 4.5
# lane tiles) is no multiple of the chip's 128 lanes, a page length is,
# and the TPU's own default layout of ``[N, page_len, 576]`` puts the
# positions in the lanes whatever the shape says, at the price of a
# relayout of the whole plane round every kernel that wants it the other
# way. Declared as stored, a page is the ``K^T`` block that ``Q K^T``
# wants and nothing is padded. PREFILL (whole, or a chunk over a cached prefix) attends
# per-head keys and values REBUILT from the latent: flash at query/key
# width ``dn + dr`` and value width ``dv``. DECODE takes ``Wkvb`` into the
# query and the output and attends the latent itself: every head's row
# over ONE shared key of ``latent_dim`` whose first ``kv_lora_rank`` values
# are also the value (``ops.paged_attention.paged_latent_attention``). A
# block of a shortcut-connected expert layer (``TransformerBlock``'s class
# doc) hands ``(x, m)`` on or takes it, here as in ``apply``.


def _block_tail(block: TransformerBlock, p, s, x, y, carry,
                moe_dispatched, routing):
    """What follows a block's attention output ``y``: the residual, the
    MLP on the post-attention norm, and the shortcut-connected expert
    layer: computed from that same norm and handed on (``(x, m)``), or
    the one handed in (``carry``) added after the MLP."""
    x = x + y.astype(x.dtype)
    with jax.named_scope("mlp"):
        u, _ = block.norm2.apply(p["norm2"], s["norm2"], x)
        m = _apply_mlp_decode(block.mlp, p["mlp"], s["mlp"], u,
                              moe_dispatched, routing)
        handed = None
        if block.shortcut is not None:
            handed = _apply_mlp_decode(
                block.shortcut, p["shortcut"], s["shortcut"], u,
                moe_dispatched, routing)
    x = x + m
    if carry is not None:
        x = x + carry
    return x if handed is None else (x, handed)


def _latent_prefill_block(block: TransformerBlock, p, s, kv, x, positions,
                          t0: int, routing=None, kv_only: bool = False):
    """One chunk ``[t0, t0 + Q)`` of one latent-attention block (the
    whole prompt: ``t0 = 0``): write the chunk's latents at their
    positions, attend causally inside the chunk over keys and values
    rebuilt from them, and (``t0 > 0``) over those rebuilt from the
    cached prefix ``[0, t0)``, merged through the log-sum-exps as
    ``_prefill_block_chunked`` merges."""
    attn = block.attn
    dt = jnp.dtype(attn.dtype)
    x_in, carry = x, None
    if block.shortcut_add:
        x, carry = x
    with jax.named_scope("attn"):
        h_, _ = block.norm1.apply(p["norm1"], s["norm1"], x)
        qn, qr, entry = attn.project(p["attn"], h_.astype(dt), positions)
        kv = {"c": lax.dynamic_update_slice_in_dim(
            kv["c"], entry.transpose(0, 2, 1).astype(kv["c"].dtype), t0,
            axis=2)}
        if kv_only:
            return x_in, kv
        q = jnp.concatenate([qn, qr], axis=-1).transpose(0, 2, 1, 3)
        k, v = attn.expand_kv(p["attn"], entry, dt)
        out, lse = _attn_lse(q, k, v, causal=True, scale=attn.scale,
                             layout="bhsd")
        if t0 > 0:
            kp, vp = attn.expand_kv(
                p["attn"], kv["c"][:, :, :t0].transpose(0, 2, 1), dt)
            o_pre, lse_pre = _attn_lse(q, kp, vp, causal=False,
                                       scale=attn.scale, layout="bhsd")
            out = _merge_attention(o_pre, lse_pre, out, lse)
        y = jnp.einsum("bhsv,hvd->bsd", out.astype(dt),
                       p["attn"]["wo"].astype(dt))
    return _block_tail(block, p, s, x, y, carry, routing is not None,
                       routing), kv


def _write_latent_rows(plane, pp, off, vals):
    """``plane.at[pp, :, off].set(vals, mode="drop")`` for a latent page
    plane [N, C, page_len] and per-slot vectors [S, C]: a token is a
    COLUMN of its page, so each slot's page is read, the column blended
    in and the page written back whole (S pages of traffic, in place; the
    one-position write of an int4 page does the same). A ``pp`` of N or
    more drops; live slots never share the page they write."""
    n = plane.shape[0]
    pages = plane[jnp.clip(pp, 0, n - 1)]                # [S, C, page_len]
    col = jnp.arange(plane.shape[2])[None, None, :] == off[:, None, None]
    pages = jnp.where(col, vals.astype(plane.dtype)[:, :, None], pages)
    return plane.at[pp].set(pages, mode="drop")


def _latent_decode_block(block: TransformerBlock, p, s, kv, x, t, table,
                         page_len: int, moe_dispatched=True, routing=None,
                         paged_kernel=None):
    """One token a slot through a latent-attention block against the
    PAGED latent plane, in the absorbed form: the token's latent is
    written through the page table, the queries take ``Wkvb``'s key
    half, attend the latent pages (the Pallas kernel, or the gathered
    view: its oracle and the off-TPU path) and the output takes
    ``Wkvb``'s value half and ``Wo``."""
    attn = block.attn
    dt = jnp.dtype(attn.dtype)
    carry = None
    if block.shortcut_add:
        x, carry = x
    with jax.named_scope("attn"):
        h_, _ = block.norm1.apply(p["norm1"], s["norm1"], x)
        qn, qr, entry = attn.project(p["attn"], h_.astype(dt), t[:, None])
        plane = kv["c"]
        n_pages, n_logical = plane.shape[0], table.shape[1]
        lp = t // page_len
        pp = jnp.take_along_axis(
            table, jnp.clip(lp, 0, n_logical - 1)[:, None], axis=1)[:, 0]
        # a position past the slot's pages (the free-slot sentinel) or an
        # unallocated page: the write falls off the end and drops
        pp = jnp.where((lp >= 0) & (lp < n_logical), pp, n_pages)
        plane = _write_latent_rows(plane, pp, t % page_len, entry[:, 0])
        q = attn.absorb_q(p["attn"], qn, qr)         # [S, 1, H, latent_dim]
        from distkeras_tpu.ops import paged_attention as pa
        if (backend_is_tpu() if paged_kernel is None else paged_kernel) \
                and pa.page_aligned(page_len):
            note_path("paged_attention", "kernel")
            o = pa.paged_latent_attention(
                q, plane, t, table, v_dim=attn.kv_lora_rank,
                scale=attn.scale,
                interpret=None if backend_is_tpu() else True)
        else:
            note_path("paged_attention", "gather_reference")
            o = pa.paged_latent_attention_reference(
                q, plane, t, table, v_dim=attn.kv_lora_rank,
                scale=attn.scale)
        y = attn.unabsorb_v(p["attn"], o, dt)
    return _block_tail(block, p, s, x, y, carry, moe_dispatched,
                       routing), {"c": plane}


def prefill_chunk_step(module: Sequential, params, state, cache, chunk,
                       t0: int, *, final: bool, routing=None):
    """ONE ``[B, q_len]`` chunk through the whole stack — the resumable
    unit of :func:`prefill_chunked`, factored out (this PR) so the
    serving engine can interleave prompt chunks between decode
    iterations instead of stalling in-flight streams for a whole
    prompt. ``t0`` is the chunk's global start position (STATIC — the
    per-layer chunk pass branches on it in Python); positions
    ``[0, t0)`` of ``cache`` must already be written. Returns
    ``(last_logits [B, V] if final else None, cache)`` — non-final
    chunks stop at the deepest attention block's K/V write: its
    readout and MLP, the final norm and the vocab head only matter for
    the last chunk's logits (review r5). ``routing`` (a list the
    caller owns; the block-diffusion engine's prefill) makes the expert
    layers drop-free and collects ``(num_experts, (topi, full))`` of
    each one that ran, for :func:`routing_counts`."""
    new_cache = list(cache)
    last_block = max((i for i, l in enumerate(module.layers)
                      if _decode_block_of(l) is not None), default=-1)
    last = len(module.layers) - 1
    q_len = chunk.shape[1]
    x = chunk
    positions = jnp.arange(t0, t0 + q_len)
    for i, layer in enumerate(module.layers):
        if not final and i > last_block:
            break
        p, s = params[i], state[i]
        block = _decode_block_of(layer)
        if block is not None:
            x, new_cache[i] = _prefill_block_chunked(
                block, p, s, new_cache[i], x, positions, t0, routing,
                kv_only=not final and i == last_block)
        elif isinstance(layer, PositionalEmbedding):
            with jax.named_scope("embed"):
                x = x + p["embeddings"][t0:t0 + q_len][None] \
                    .astype(x.dtype)
        elif isinstance(layer, Dropout):
            pass                                         # eval: identity
        else:
            if i == last and x.ndim == 3:
                x = x[:, -1:]        # head on the final position only
            with scoped(module.scope_of(i)):
                x, _ = layer.apply(p, s, x, training=False)
    return (x[:, -1] if final else None), new_cache


def prefill_chunked(module: Sequential, params, state, cache, prompts,
                    chunk_len: int):
    """Block-by-block prompt ingestion (round 5): like :func:`prefill`
    but the prompt streams through the stack in ``chunk_len``-position
    chunks, each attending to the cache prefix written by the chunks
    before it. TTFT stays quadratic-COMPUTE-bound, but peak activation
    memory is flat in P — the regime >= 32K prompts need (the one-pass
    prefill materializes [B, P, H, D] q/k/v per layer and falls over
    around P=32K at d_model 1024). Greedy continuations match the
    one-pass prefill exactly up to blockwise-softmax fp reassociation
    (the merge is algebraically exact)."""
    b, p_len = prompts.shape
    new_cache = cache
    last_logits = None
    for t0 in range(0, p_len, chunk_len):
        q_len = min(chunk_len, p_len - t0)
        last_logits, new_cache = prefill_chunk_step(
            module, params, state, new_cache, prompts[:, t0:t0 + q_len],
            t0, final=t0 + q_len >= p_len)
    return last_logits, new_cache


def prefill(module: Sequential, params, state, cache, prompts,
            routing=None):
    """Batched prompt ingestion (round 4): run the stack ONCE over the
    [B, P] prompt, filling every attention layer's cache at positions
    0..P-1, and return ``(last_logits [B, V], cache)``.

    This replaces replaying the prompt through the sequential decode scan
    — P compute-bound flash steps collapse into one kernel pass, which is
    what makes long-context serving (P = 2048-16384) usable at all: an
    8K-token prompt is ~250x fewer sequential device steps. The vocab
    head is applied to the LAST position only (the [B, P, V] logits
    tensor for a 32k vocab would be ~2 GB at P=8192 and is never
    needed). ``routing`` as in :func:`prefill_chunk_step`."""
    b, p_len = prompts.shape
    x = prompts
    new_cache = list(cache)
    positions = jnp.arange(p_len)
    last = len(module.layers) - 1
    for i, layer in enumerate(module.layers):
        p, s = params[i], state[i]
        block = _decode_block_of(layer)
        if block is not None:
            x, new_cache[i] = _prefill_block(block, p, s, cache[i], x,
                                             positions, routing)
        elif isinstance(layer, PositionalEmbedding):
            with jax.named_scope("embed"):
                x = x + p["embeddings"][:p_len][None].astype(x.dtype)
        elif isinstance(layer, Dropout):
            pass                                         # eval: identity
        else:
            if i == last and x.ndim == 3:
                x = x[:, -1:]        # head on the final position only
            with scoped(module.scope_of(i)):
                x, _ = layer.apply(p, s, x, training=False)
    return x[:, -1], new_cache


def decode_step(module: Sequential, params, state, cache, tok, t):
    """One token through the stack. tok: [B] int; returns ([B, V] logits,
    cache)."""
    x = tok[:, None]                                     # [B, 1]
    new_cache = list(cache)
    for i, layer in enumerate(module.layers):
        p, s, kv = params[i], state[i], cache[i]
        block = _decode_block_of(layer)
        if block is not None:
            x, new_cache[i] = _decode_block(block, p, s, kv, x, t)
        elif isinstance(layer, PositionalEmbedding):
            with jax.named_scope("embed"):
                x = x + p["embeddings"][t][None, None, :].astype(x.dtype)
        elif isinstance(layer, Dropout):
            pass                                         # eval: identity
        else:
            with scoped(module.scope_of(i)):
                x, _ = layer.apply(p, s, x, training=False)
    return x[:, 0], new_cache                            # [B, V]


# --- slot-level decode ------------------------------------------------------
#
# Continuous batching runs ONE compiled step over a fixed pool of S slots
# whose sequences are at DIFFERENT positions: ``t`` becomes a [S] vector.
# The engine runs the PAGED steps further down; the contiguous-cache
# steps here are the reference the tests compare them with. The per-slot
# variants below mirror the scalar-``t`` functions exactly —
# same projections, same storage-dtype contractions — with three changes:
# the cache write selects each slot's own position (a one-hot select, so a
# slot whose ``t`` is out of range, the engine's free-slot sentinel,
# writes NOTHING and cannot corrupt a neighbour), the validity mask is
# per-slot, and rope positions are per-slot. The fused Pallas decode
# kernel takes a scalar step and is not used here; the einsum path's
# per-slot masks cost nothing extra (the mask was already materialized).
#
# MoE blocks (MoE-serving PR): the slot steps run MoE MLPs through the
# decode-specialized dispatched path by default (``moe_dispatched=True``
# -> ``MoE.decode_apply``: capacity = the slot-token count, so routing
# can never drop and a slot's output is independent of its batch
# neighbours; fused kernel on TPU, tokens path elsewhere).
# ``moe_dispatched=False`` opts back into each layer's own ``apply`` —
# the dense-routing baseline the bench prices the dispatch against.
# ``moe_stats`` (an int: the live-position bound, the engine's
# ``max_len``) makes the step ALSO return per-expert load and router
# entropy over live slots — the serving engine's expert telemetry.


def _apply_mlp_decode(mlp, p, s, x, moe_dispatched, routing):
    """MLP application for the slot decode steps: MoE layers take the
    decode-specialized dispatched path (:meth:`MoE.decode_apply` —
    drop-free, fused on TPU) unless the caller opts back into the
    layer's own ``apply`` (``moe_dispatched=False``, the dense-routing
    baseline); plain MLPs are untouched. ``routing`` (a list, or None)
    collects per-MoE-layer ``(num_experts, (topi, full))`` for the
    expert-load telemetry."""
    from distkeras_tpu.models.moe import MoE
    if moe_dispatched and isinstance(mlp, MoE):
        if routing is None:
            return mlp.decode_apply(p, x)
        out, r = mlp.decode_apply(p, x, return_routing=True)
        share = mlp.routing_share()
        routing.append((mlp.num_experts, r) if share is None
                       else (mlp.router_dim, r, share))
        return out
    out, _ = mlp.apply(p, s, x, training=False)
    return out


def _moe_route_stats(routing, t, w_len: int, live_len: int):
    """Reduce the collected per-layer routing to the step's expert
    telemetry: ``expert_load`` [E] (routing-slot assignments per expert,
    summed over MoE layers — layers whose expert count differs from the
    first are skipped) and ``router_entropy`` (mean nats of the full
    router softmax), both masked to LIVE slots (``t < live_len``; the
    engine's free-slot sentinel routes garbage that must not pollute
    the load picture). Returns None when no MoE layer ran."""
    if not routing:
        return None
    live = ((t >= 0) & (t < live_len)).astype(jnp.float32)     # [S]
    e0 = routing[0][0]
    load = jnp.zeros((e0,), jnp.float32)
    ent_sum = jnp.zeros((), jnp.float32)
    n_layers = 0
    for e, (topi, full), *_share in routing:
        if e != e0:
            continue
        oh = jax.nn.one_hot(topi, e0, dtype=jnp.float32).sum(-2)
        load = load + (oh * live[:, None, None]).sum((0, 1))
        p = full.astype(jnp.float32)
        ent = -(p * jnp.log(p + 1e-9)).sum(-1)                 # [S, W]
        ent_sum = ent_sum + (ent * live[:, None]).sum()
        n_layers += 1
    n_tok = jnp.maximum(live.sum() * w_len * n_layers, 1.0)
    return {"expert_load": load, "router_entropy": ent_sum / n_tok,
            "routed": routing_counts(routing)}


def _cache_write_slots(kv, k, v, t):
    """Write one [S, 1, H, D] k/v decode slab at PER-SLOT positions
    ``t`` ([S] int) into the head-major [S, H, L, D] cache. Slot ``s``
    writes position ``t[s]``; ``t[s] >= L`` (the engine's free/prefilling
    sentinel) writes nothing. Contiguous-cache reference; the engine
    does not call this."""
    kh = k.transpose(0, 2, 1, 3)                         # [S, H, 1, D]
    vh = v.transpose(0, 2, 1, 3)
    L = kv["k"].shape[2]
    hit = (jnp.arange(L)[None, :] == t[:, None])         # [S, L]
    hit4 = hit[:, None, :, None]                         # [S, 1, L, 1]
    if "k_scale" in kv:
        bits = _kv_bits(kv)
        qk, sk = _quantize_kv(kh, bits)
        qv, sv = _quantize_kv(vh, bits)
        hit3 = hit[:, None, :]                           # [S, 1, L]
        out = {"k": jnp.where(hit4, qk, kv["k"]),
               "v": jnp.where(hit4, qv, kv["v"]),
               "k_scale": jnp.where(hit3, sk, kv["k_scale"]),
               "v_scale": jnp.where(hit3, sv, kv["v_scale"])}
        if bits == 4:
            out["q4"] = kv["q4"]
        return out
    return {"k": jnp.where(hit4, kh.astype(kv["k"].dtype), kv["k"]),
            "v": jnp.where(hit4, vh.astype(kv["v"].dtype), kv["v"])}


def _window_positions(t, w_len: int, tree):
    """Per-window-query cache positions: ``t + j`` for the causal chain
    (window query j sits j steps past the slot's start), or
    ``t + depth[j]`` for a token TREE (tree-speculation PR — each node's
    position is its depth on its own root path, so siblings share a
    position while occupying distinct window columns)."""
    if tree is None:
        return t[:, None] + jnp.arange(w_len)            # [S, W]
    return t[:, None] + tree["depth"]                    # [S, W]


def _window_valid_mask(t, w_len: int, L: int, tree, window,
                       full_window: bool = False):
    """[S, W, L] attention validity for the windowed readout.

    Chain (``tree`` None): window query j admits cache positions
    ``<= t + j`` — the established window-causal mask.

    Tree: node j was WRITTEN at cache position ``t + j`` (its window
    column), so query i admits (a) the committed prefix ``< t`` and
    (b) window column j's position ``t + j`` iff j is an ancestor of i
    (self included) per ``tree["anc"]`` — rejected/sibling branches
    stay invisible exactly like the chain's future positions. Sentinel
    slots (t out of range) admit garbage either way; their logits are
    discarded by contract. ``window`` adds the SWA band around each
    query's own position (``t + depth``).

    ``full_window`` (block diffusion: the window is one block of a
    block-causal model): every window query admits the committed
    prefix and ALL ``W`` window positions, ``<= t + W - 1``."""
    ar = jnp.arange(L)[None, None, :]                    # [1, 1, L]
    if full_window:
        last = (t + (w_len - 1))[:, None, None]          # [S, 1, 1]
        return jnp.broadcast_to(ar <= last, (t.shape[0], w_len, L))
    if tree is None:
        pos = t[:, None] + jnp.arange(w_len)             # [S, W]
        valid = ar <= pos[:, :, None]
    else:
        anc = tree["anc"]                                # [S, W, W] bool
        s_n = anc.shape[0]
        rel = jnp.arange(L)[None, :] - t[:, None]        # [S, L]
        within = (rel >= 0) & (rel < w_len)
        anc_g = anc[jnp.arange(s_n)[:, None, None],
                    jnp.arange(w_len)[None, :, None],
                    jnp.clip(rel, 0, w_len - 1)[:, None, :]]
        valid = (rel < 0)[:, None, :] | (within[:, None, :] & anc_g)
        pos = t[:, None] + tree["depth"]
    if window is not None:
        valid &= ar > (pos - window)[:, :, None]
    return valid


def _attn_out(p, out, dt):
    """Output projection shared by the serving readouts: the fused
    dequant-matmul when the engine left ``wo`` quantized
    (``ops.quant_matmul`` qdict), the plain einsum otherwise."""
    wo = p["wo"]
    if isinstance(wo, dict):
        from distkeras_tpu.ops.quant_matmul import quant_matmul
        b, s_len = out.shape[:2]
        y = quant_matmul(out.reshape(b * s_len, -1), wo)
        return y.astype(dt).reshape(b, s_len, -1)
    return jnp.einsum("bshe,hed->bsd", out, wo.astype(dt))


def _slot_attn_readout(attn: MultiHeadAttention, p, q, kv, t, dt,
                       tree=None, full_window: bool = False, kpos=None):
    """Masked per-slot attention of the projected decode queries against
    a logically contiguous ``[S, H, L, D]`` kv view — a contiguous cache
    or a page gather in logical-position order — plus the output projection.
    Shared by the reference and the paged gather path so the two are
    bitwise identical wherever the view holds identical values.

    ``q`` is ``[S, W, H, D]`` for a W-position window at per-slot
    positions ``t .. t+W-1`` (the speculative-verify step; W = 1 is the
    plain decode step): window query ``j`` of slot ``s`` attends cache
    positions ``<= t[s] + j`` — causal WITHIN the window too, so the
    drafts just written at ``t+1 .. t+j`` are visible to later window
    positions while rejected-tail garbage stays masked for every query
    that must not see it. ``tree`` (tree-speculation PR: ``{"depth":
    [S, W], "anc": [S, W, W]}``) generalizes the window to a token
    tree — see ``_window_valid_mask``; a chain-shaped tree produces the
    exact mask above, bit for bit.

    ``kpos`` ([S, L] int, a gathered RING of a window layer's pages):
    the position each key of the view holds (negative: none), in place
    of its index; chain windows of a sliding-window layer only."""
    scale = (attn.head_dim or q.shape[-1]) ** -0.5
    b = q.shape[0]
    w_len = q.shape[1]
    hkv = attn.kv_heads
    g = attn.num_heads // hkv
    dh = q.shape[-1]
    L = kv["k"].shape[2]
    qg = (q.astype(jnp.float32) * scale).reshape(
        b, w_len, hkv, g, dh)                        # [S, W, Hkv, G, D]
    s = _decode_scores(qg, kv)                       # [S, Hkv, G, W, L]
    if kpos is None:
        valid = _window_valid_mask(t, w_len, L, tree, attn.attn_window,
                                   full_window)
    else:
        pos = (t[:, None] + jnp.arange(w_len))[:, :, None]   # [S, W, 1]
        kp = kpos[:, None, :]                                # [S, 1, L]
        valid = (kp >= 0) & (kp <= pos) & (kp > pos - attn.attn_window)
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = _decode_mix(w, kv).astype(dt)              # [S, W, Hkv, G, D]
    out = out.reshape(b, w_len, attn.num_heads, dh)
    return _attn_out(p, out, dt)


def _decode_attn_slots(attn: MultiHeadAttention, p, kv, x, t):
    """One-token attention against the contiguous cache at per-slot
    positions. x: [S, 1, d]; t: [S]. The einsum/storage-dtype path of
    ``_decode_attn`` with a [S, L] validity mask. Contiguous-cache
    reference; the engine does not call this."""
    dt = jnp.dtype(attn.dtype)
    xc = x.astype(dt)
    q, k, v = _project_qkv(attn, p, xc)
    if attn.use_rope:
        q = attn.rope(q, t[:, None])
        k = attn.rope(k, t[:, None])
    kv = _cache_write_slots(kv, k, v, t)
    y = _slot_attn_readout(attn, p, q, kv, t, dt)
    return y.astype(x.dtype), kv


def _decode_block_slots(block: TransformerBlock, p, s, kv, x, t,
                        moe_dispatched=True, routing=None):
    """Contiguous-cache reference; the engine does not call this."""
    _refuse_latent(block, "the contiguous-cache reference step")
    with jax.named_scope("attn"):
        h, _ = block.norm1.apply(p["norm1"], s["norm1"], x)
        a, kv = _decode_attn_slots(block.attn, p["attn"], kv, h, t)
    x = x + a
    with jax.named_scope("mlp"):
        h, _ = block.norm2.apply(p["norm2"], s["norm2"], x)
        m = _apply_mlp_decode(block.mlp, p["mlp"], s["mlp"], h,
                              moe_dispatched, routing)
    return x + m, kv


def decode_step_slots(module: Sequential, params, state, cache, tok, t,
                      *, moe_dispatched: bool = True, moe_stats=None):
    """Contiguous-cache reference; the engine does not call this.

    One token through the stack at PER-SLOT positions: tok [S] int,
    t [S] int; returns ([S, V] logits, cache). Slots whose ``t`` is out
    of cache range (the serving engine's free-slot sentinel) produce
    garbage logits and write nothing — the engine discards them
    host-side. The position-table gather clamps for such slots, which
    is safe exactly because their output is never consumed.

    MoE blocks run the decode-specialized dispatched path
    (``moe_dispatched``; see the section comment above). ``moe_stats``
    (an int live-position bound) appends a third return value: the
    ``_moe_route_stats`` dict (None for MoE-free models)."""
    x = tok[:, None]                                     # [S, 1]
    new_cache = list(cache)
    routing = [] if moe_stats is not None else None
    for i, layer in enumerate(module.layers):
        p, s, kv = params[i], state[i], cache[i]
        block = _decode_block_of(layer)
        if block is not None:
            x, new_cache[i] = _decode_block_slots(
                block, p, s, kv, x, t, moe_dispatched, routing)
        elif isinstance(layer, PositionalEmbedding):
            with jax.named_scope("embed"):
                x = x + p["embeddings"][t][:, None, :].astype(x.dtype)
        elif isinstance(layer, Dropout):
            pass                                         # eval: identity
        else:
            with scoped(module.scope_of(i)):
                x, _ = layer.apply(p, s, x, training=False)
    if moe_stats is not None:
        return x[:, 0], new_cache, _moe_route_stats(
            routing, t, 1, int(moe_stats))
    return x[:, 0], new_cache                            # [S, V]


# --- paged decode (serving engine, paged KV cache PR) -----------------------
#
# The paged pool stores every layer's cache as [N, Hkv, page_len, Dh]
# fixed-size pages; a per-slot page table [S, P] maps logical page p of
# slot s to a physical page id (the engine's sentinel — an id >= N —
# marks an unallocated logical page). The decode step is ONE compiled
# program regardless of which pages a slot owns: the table is a traced
# argument, writes scatter through it (out-of-range drops, so the
# free-slot position sentinel writes nothing, exactly like the reference's
# one-hot write), and reads gather the slot's pages back into the same
# logically contiguous [S, H, L, D] view the reference step consumes — the
# shared ``_slot_attn_readout`` epilogue then makes the two paths
# bitwise identical wherever the views hold identical values.


def _page_row_ids(plane, pp, off):
    """[S, H] indices of rows ``[pp, :, off]`` of a pool plane
    [N, H, rows, ...] seen as [N*H*rows, ...]; a ``pp`` of N or more
    (the sentinel) lands past the end."""
    _, h, rows = plane.shape[:3]
    return (pp[:, None] * h + jnp.arange(h)[None, :]) * rows + off[:, None]


def _flat_rows(plane):
    n, h, rows = plane.shape[:3]
    return plane.reshape((n * h * rows,) + plane.shape[3:])


def _read_page_rows(plane, pp, off):
    """``plane[pp, :, off]`` through the flattened plane (see
    ``_write_page_rows``: the two-index gather pays the same relayout
    of the whole plane as the two-index scatter)."""
    return _flat_rows(plane)[_page_row_ids(plane, pp, off)]


def _write_page_rows(plane, pp, off, vals):
    """``plane.at[pp, :, off].set(vals, mode="drop")`` for a plane of
    the page pool — payload [N, H, rows, D] with per-slot values
    [S, H, D], or scales [N, H, rows] with [S, H] — written as ONE
    scatter of S*H rows into the plane seen as [N*H*rows, ...]. Same
    bytes land in the same places (a ``pp`` of N or more still falls
    off the end and drops), but the two-index form makes the TPU
    compiler move the WHOLE plane into a layout with the heads behind
    the rows and back again — two copies of the pool every decode
    step, donated or not — where rows of the flattened plane are
    written in place (``tests/test_tpu_compile.py`` holds the compiled
    step to that for each pool dtype)."""
    ids = _page_row_ids(plane, pp, off).reshape(-1)
    flat = _flat_rows(plane).at[ids].set(
        vals.reshape((-1,) + plane.shape[3:]), mode="drop")
    return flat.reshape(plane.shape)


def _cache_write_pages(kv, k, v, t, table, page_len: int, ring=None):
    """Write one [S, 1, H, D] k/v decode slab at per-slot positions
    ``t`` ([S] int) into the paged pool [N, H, page_len, D] through the
    slot page tables ``table`` ([S, P] int). Slot ``s`` writes physical
    page ``table[s, t[s] // page_len]`` at offset ``t[s] % page_len``;
    a ``t[s]`` past the logical capacity (the engine's free/prefilling
    sentinel) or a sentinel table entry writes nothing (scatter drop).
    ``ring`` (an int: the logical pages a slot spans) says ``table`` is
    a window group's RING, narrower than that: logical page ``p`` sits
    in column ``p % table.shape[1]``."""
    kh = k[:, 0]                                         # [S, H, D]
    vh = v[:, 0]
    n_pages = kv["k"].shape[0]
    n_logical = table.shape[1] if ring is None else int(ring)
    lp = t // page_len                                   # [S] logical page
    off = t % page_len
    col = jnp.clip(lp, 0, n_logical - 1)
    if ring is not None:
        col = col % table.shape[1]
    pp = jnp.take_along_axis(table, col[:, None], axis=1)[:, 0]
    # sentinel: out-of-range t (or an unallocated logical page whose
    # table entry is >= N already) routes the scatter out of bounds,
    # where mode="drop" discards it
    pp = jnp.where((lp >= 0) & (lp < n_logical), pp, n_pages)
    if "q4" in kv:
        # int4 pool pages are nibble-PACKED along the position axis
        # ([N, H, page_len//2, D] bytes — pack_int4's half-split): the
        # one-position write is a read-modify-write of the byte row
        # shared with position off +- page_len//2. The gather clamps
        # sentinel pages to a real page (garbage merged safely — the
        # scatter at the out-of-range pp drops it); scale planes stay
        # per-position, so their write is the int8 write verbatim.
        qk, sk = _quantize_kv(kh, 4)
        qv, sv = _quantize_kv(vh, 4)
        half = page_len // 2
        prow = off % half
        hi = (off >= half)[:, None, None]                # [S, 1, 1]
        gp = jnp.clip(pp, 0, n_pages - 1)
        out = {"k_scale": _write_page_rows(kv["k_scale"], pp, off, sk),
               "v_scale": _write_page_rows(kv["v_scale"], pp, off, sv),
               "q4": kv["q4"]}
        for key, q in (("k", qk), ("v", qv)):
            cur = _read_page_rows(kv[key], gp, prow).astype(jnp.int32) \
                & 255
            nib = q.astype(jnp.int32) & 15
            b = jnp.where(hi, (cur & 0x0F) | (nib << 4),
                          (cur & 0xF0) | nib)
            b = (b - 256 * (b > 127)).astype(jnp.int8)
            out[key] = _write_page_rows(kv[key], pp, prow, b)
        return out
    if "k_scale" in kv:
        qk, sk = _quantize_kv(kh)
        qv, sv = _quantize_kv(vh)
        return {
            "k": _write_page_rows(kv["k"], pp, off, qk),
            "v": _write_page_rows(kv["v"], pp, off, qv),
            "k_scale": _write_page_rows(kv["k_scale"], pp, off, sk),
            "v_scale": _write_page_rows(kv["v_scale"], pp, off, sv)}
    return {"k": _write_page_rows(kv["k"], pp, off,
                                  kh.astype(kv["k"].dtype)),
            "v": _write_page_rows(kv["v"], pp, off,
                                  vh.astype(kv["v"].dtype))}


def _gather_pages(kv, table):
    """The slot page tables' view of the pool: gather each slot's pages
    into a logically contiguous [S, H, P*page_len, D] cache (scale
    planes [S, H, P*page_len]). Sentinel table entries clamp to the
    last physical page — harmless garbage, masked by the ``<= t``
    validity mask exactly like a contiguous row's stale tail."""
    out = {}
    for key in ("k", "v"):
        pg = kv[key][table]                  # [S, P, H, page_len, D]
        if "q4" in kv:
            # packed int4 pages gather as [S, P, H, page_len//2, D]
            # bytes; unpacking along the page-position axis restores
            # the unpacked int4-valued int8 plane the shared
            # readout dequantizes (q * scale — same contract as int8)
            pg = unpack_int4(pg)
        s, p, h, pl, d = pg.shape
        out[key] = pg.transpose(0, 2, 1, 3, 4).reshape(s, h, p * pl, d)
    if "k_scale" in kv:
        for key in ("k_scale", "v_scale"):
            pg = kv[key][table]              # [S, P, H, page_len]
            s, p, h, pl = pg.shape
            out[key] = pg.transpose(0, 2, 1, 3).reshape(s, h, p * pl)
    if "q4" in kv:
        out["q4"] = kv["q4"]
    return out


def _use_paged_kernel(kv, page_len: int, paged_kernel) -> bool:
    """Should the paged readout take the Pallas page-table kernel?
    ``paged_kernel`` is the caller's tri-state: None = the repo-wide
    backend convention (TPU only), True = force (off-TPU the kernel
    runs in interpreter mode — the tier-1 oracle hook), False = the
    ``_gather_pages`` reference path. Either way an unaligned
    ``page_len`` (Mosaic sublane rule — ``paged_attention
    .page_aligned``) falls back to the gather path."""
    from distkeras_tpu.ops.paged_attention import page_aligned
    if paged_kernel is None:
        paged_kernel = backend_is_tpu()
    if "q4" in kv:
        quant = "int4"
    elif "k_scale" in kv:
        quant = "int8"
    else:
        quant = False
    return bool(paged_kernel) and page_aligned(page_len, quant)


def _ring_key_positions(t, w_len: int, table, page_len: int):
    """[S, R * page_len] positions the gathered view of a RING table
    holds: column ``c`` is the newest logical page at or under the
    window's top page congruent to ``c`` (negative where there is
    none yet), as ``ops.paged_attention`` reads it."""
    r = table.shape[1]
    top = (t + (w_len - 1)) // page_len                  # [S]
    cols = jnp.arange(r)[None, :]
    lp = top[:, None] - (top[:, None] - cols) % r        # [S, R]
    pos = lp[:, :, None] * page_len + jnp.arange(page_len)[None, None, :]
    pos = jnp.where(lp[:, :, None] >= 0, pos, -1)
    return pos.reshape(t.shape[0], r * page_len)


def _paged_attn_readout(attn: MultiHeadAttention, p, q, kv, t, table,
                        page_len: int, dt, paged_kernel, tree=None,
                        full_window: bool = False, ring=None):
    """Readout for the paged decode/verify paths: the Pallas
    paged-attention kernel (K/V gathered HBM -> VMEM through the page
    table inside the kernel — no materialized [S, H, L, D] view) when
    enabled, else ``_gather_pages`` + the shared readout (the
    off-TPU/interpret fallback and the kernel's oracle). ``tree``
    forwards the ancestor-mask window (tree-speculation PR) — the
    kernel takes the ``[S, W, W]`` mask as an operand; the gather path
    threads it into the shared mask builder. ``ring``: ``table`` is
    a window group's ring (``_cache_write_pages``); the kernel call is
    then named ``paged_window_attention``."""
    if ring is not None and (tree is not None or full_window
                             or attn.attn_window is None):
        raise ValueError("a ring page table serves chain windows of a "
                         "sliding-window layer only")
    if not _use_paged_kernel(kv, page_len, paged_kernel):
        note_path("paged_attention", "gather_reference")
        kpos = None if ring is None else _ring_key_positions(
            t, q.shape[1], table, page_len)
        return _slot_attn_readout(attn, p, q,
                                  _gather_pages(kv, table), t, dt,
                                  tree=tree, full_window=full_window,
                                  kpos=kpos)
    from distkeras_tpu.ops.paged_attention import paged_decode_attention
    note_path("paged_attention", "kernel")
    b, w_len, nh, dh = q.shape
    hkv = attn.kv_heads
    g = nh // hkv
    scale = (attn.head_dim or dh) ** -0.5
    qg = q.astype(jnp.float32).reshape(b, w_len, hkv, g, dh)
    sc = {}
    if "k_scale" in kv:
        sc = {"k_scale": kv["k_scale"], "v_scale": kv["v_scale"]}
    if ring is not None:
        sc.update(ring=True, name="paged_window_attention")
    o = paged_decode_attention(
        qg, kv["k"], kv["v"], t, table, scale=scale,
        window=attn.attn_window,
        anc=None if tree is None else tree["anc"],
        full_window=full_window,
        interpret=None if backend_is_tpu() else True, **sc)
    out = o.reshape(b, w_len, nh, dh).astype(dt)
    return _attn_out(p, out, dt)


def _decode_attn_slots_paged(attn: MultiHeadAttention, p, kv, x, t,
                             table, page_len: int, paged_kernel=None,
                             ring=None):
    """One-token attention against the PAGED pool at per-slot
    positions: scatter the new k/v through the page tables, then read
    back through the paged kernel (or the gathered per-slot view)."""
    dt = jnp.dtype(attn.dtype)
    xc = x.astype(dt)
    q, k, v = _project_qkv(attn, p, xc)
    if attn.use_rope:
        q = attn.rope(q, t[:, None])
        k = attn.rope(k, t[:, None])
    kv = _cache_write_pages(kv, k, v, t, table, page_len, ring)
    y = _paged_attn_readout(attn, p, q, kv, t, table, page_len, dt,
                            paged_kernel, ring=ring)
    return y.astype(x.dtype), kv


def _decode_block_slots_paged(block: TransformerBlock, p, s, kv, x, t,
                              table, page_len: int,
                              moe_dispatched=True, routing=None,
                              paged_kernel=None, ring=None):
    if _is_latent(block):
        return _latent_decode_block(block, p, s, kv, x, t, table, page_len,
                                    moe_dispatched, routing, paged_kernel)
    with jax.named_scope("attn"):
        h, _ = block.norm1.apply(p["norm1"], s["norm1"], x)
        a, kv = _decode_attn_slots_paged(block.attn, p["attn"], kv, h, t,
                                         table, page_len, paged_kernel,
                                         ring)
    x = x + a
    with jax.named_scope("mlp"):
        h, _ = block.norm2.apply(p["norm2"], s["norm2"], x)
        m = _apply_mlp_decode(block.mlp, p["mlp"], s["mlp"], h,
                              moe_dispatched, routing)
    return x + m, kv


def decode_step_slots_paged(module: Sequential, params, state, cache,
                            tok, t, table, page_len: int,
                            *, moe_dispatched: bool = True,
                            moe_stats=None, paged_kernel=None,
                            groups=None):
    """One token through the stack against a PAGED pooled cache: tok
    [S] int, t [S] int, table [S, P] int page tables; returns
    ([S, V] logits, cache). The paged mirror of ``decode_step_slots``
    — same garbage-logits contract for sentinel slots, same
    ``moe_dispatched``/``moe_stats`` MoE-decode contract.

    ``groups`` (a pool with page groups by attention kind,
    ``PagedKVPool.layer_groups``): per layer ``(g, ring)``; ``table``
    is then the tuple of the groups' tables, layer ``i`` reads
    ``table[g]``, and ``ring`` (the logical pages a slot spans, or
    None) says that table is a window group's ring.

    ``paged_kernel`` selects the readout (decode-kernel PR): None =
    the Pallas page-table kernel on TPU and the ``_gather_pages``
    reference elsewhere; True forces the kernel (interpret mode
    off-TPU — the oracle hook); False forces the gather path."""
    x = tok[:, None]                                     # [S, 1]
    new_cache = list(cache)
    routing = [] if moe_stats is not None else None
    for i, layer in enumerate(module.layers):
        p, s, kv = params[i], state[i], cache[i]
        block = _decode_block_of(layer)
        if block is not None:
            tbl, ring = (table, None) if groups is None \
                else (table[groups[i][0]], groups[i][1])
            x, new_cache[i] = _decode_block_slots_paged(
                block, p, s, kv, x, t, tbl, page_len,
                moe_dispatched, routing, paged_kernel, ring)
        elif isinstance(layer, PositionalEmbedding):
            with jax.named_scope("embed"):
                x = x + p["embeddings"][t][:, None, :].astype(x.dtype)
        elif isinstance(layer, Dropout):
            pass                                         # eval: identity
        else:
            with scoped(module.scope_of(i)):
                x, _ = layer.apply(p, s, x, training=False)
    if moe_stats is not None:
        return x[:, 0], new_cache, _moe_route_stats(
            routing, t, 1, int(moe_stats))
    return x[:, 0], new_cache                            # [S, V]


# --- batched speculative verify (serving engine, spec-decode PR) ------------
#
# Speculative decoding amortizes ONE target forward over k candidate
# tokens: the engine proposes drafts d_1..d_k per slot (n-gram lookup or
# a small draft model), then the verify step runs the [S, W = k+1]
# window [tok, d_1, .., d_k] through the stack at per-slot positions
# t..t+k in one program. logits[:, j] is the target's next-token
# distribution AFTER consuming window token j, so the longest prefix of
# drafts matching the target's own choices is accepted and the
# (m+1)-th candidate comes free — between 1 and k+1 tokens per target
# pass. Cache contract: every window position's K/V is written
# (page-table scatter, same sentinels as the 1-token steps);
# positions past the accepted count hold rejected-draft garbage, which
# is EXACTLY the stale-tail situation — masked (`<= t + j`) until
# the stream's own later writes overwrite them, position by position,
# before the mask ever admits them. No explicit rollback needed; an
# unallocated page simply drops the write (the engine only lets a slot
# CONSUME candidates whose supporting positions have allocated pages).


def _decode_block_slots_window(block: TransformerBlock, p, s, kv, x, t,
                               table=None, page_len: int = 0,
                               moe_dispatched=True, routing=None,
                               paged_kernel=None, tree=None,
                               kv_out=None, kv_only: bool = False):
    """One TransformerBlock over a [S, W, d] window at per-slot
    positions ``t .. t+W-1``: project the window's q/k/v, write ALL W
    positions into the cache (page-table scatters; one-hot writes
    into a contiguous cache when ``table`` is None, the reference
    :func:`verify_step_slots`), then run the shared windowed readout.

    ``tree`` (tree-speculation PR): rope each node at its ROOT-PATH
    position ``t + depth[j]`` (that is where it lands if accepted —
    siblings share a rope position while writing distinct window
    columns ``t + j``) and attend through the ancestor mask. The
    per-layer roped k/v land in ``kv_out`` (a list the caller owns) so
    the post-acceptance ``commit_tree_path`` can re-write the accepted
    path at its contiguous final positions. ``kv_only``: the block
    stops once the window's K/V are written (the deepest block of a
    pass that needs no logits)."""
    _refuse_latent(block, "a verify or block-diffusion window")
    attn = block.attn
    with jax.named_scope("attn"):
        h, _ = block.norm1.apply(p["norm1"], s["norm1"], x)
        dt = jnp.dtype(attn.dtype)
        xc = h.astype(dt)
        q, k, v = _project_qkv(attn, p["attn"], xc)          # [S, W, H, D]
        w_len = q.shape[1]
        if attn.use_rope:
            pos = _window_positions(t, w_len, tree)          # [S, W]
            q = attn.rope(q, pos)
            k = attn.rope(k, pos)
        if kv_out is not None:
            kv_out.append((k, v))
        for j in range(w_len):
            if table is None:
                kv = _cache_write_slots(kv, k[:, j:j + 1], v[:, j:j + 1],
                                        t + j)
            else:
                kv = _cache_write_pages(kv, k[:, j:j + 1], v[:, j:j + 1],
                                        t + j, table, page_len)
        if kv_only:
            return x, kv
        # a block-causal model's window is one whole block (the
        # engine's block-diffusion pass): bidirectional inside it
        full = attn.block_len is not None
        if full and (tree is not None or w_len != attn.block_len):
            raise ValueError(
                f"a block-causal model's window is one block of "
                f"{attn.block_len} positions with no tree (got {w_len})")
        if table is None:
            y = _slot_attn_readout(attn, p["attn"], q, kv, t, dt, tree=tree,
                                   full_window=full)
        else:
            y = _paged_attn_readout(attn, p["attn"], q, kv, t, table,
                                    page_len, dt, paged_kernel, tree=tree,
                                    full_window=full)
    x = x + y.astype(x.dtype)
    with jax.named_scope("mlp"):
        h, _ = block.norm2.apply(p["norm2"], s["norm2"], x)
        m = _apply_mlp_decode(block.mlp, p["mlp"], s["mlp"], h,
                              moe_dispatched, routing)
    return x + m, kv


def _verify_window(module: Sequential, params, state, cache, toks, t,
                   table, page_len: int, moe_dispatched: bool = True,
                   moe_stats=None, paged_kernel=None, tree=None):
    """Shared body of the verify steps: [S, W] window tokens through the
    whole stack at per-slot positions; returns ([S, W, V] logits,
    cache). MoE blocks see the [S, W] window as ONE slot-token batch
    through the dispatched decode path (capacity = S*W: drop-free even
    when every window position routes to one expert).

    ``tree`` (``{"depth": [S, W], "anc": [S, W, W]}``) switches the
    window from a causal chain to a token TREE: every node still
    writes its own window column ``t + j``, but positions (rope +
    positional embedding) come from the node's root-path depth and the
    ancestor mask decides visibility. The return gains a third value —
    the per-layer roped window k/v (None for non-attention layers) —
    which ``commit_tree_path`` consumes after acceptance."""
    x = toks                                             # [S, W] int
    w_len = toks.shape[1]
    new_cache = list(cache)
    routing = [] if moe_stats is not None else None
    kv_win = [] if tree is not None else None
    for i, layer in enumerate(module.layers):
        p, s, kv = params[i], state[i], cache[i]
        block = _decode_block_of(layer)
        if block is not None:
            x, new_cache[i] = _decode_block_slots_window(
                block, p, s, kv, x, t, table, page_len,
                moe_dispatched, routing, paged_kernel, tree, kv_win)
        elif isinstance(layer, PositionalEmbedding):
            with jax.named_scope("embed"):
                pos = _window_positions(t, w_len, tree)      # [S, W]
                x = x + p["embeddings"][pos].astype(x.dtype)
        elif isinstance(layer, Dropout):
            pass                                         # eval: identity
        else:
            with scoped(module.scope_of(i)):
                x, _ = layer.apply(p, s, x, training=False)
    if kv_win is not None:
        # index-align the collected (k, v) pairs with the CACHE list
        # (blocks appended in layer order; everything else is None)
        it = iter(kv_win)
        kv_win = [next(it) if _decode_block_of(layer) is not None
                  else None for layer in module.layers]
    out = (x, new_cache) if tree is None else (x, new_cache, kv_win)
    if moe_stats is not None:
        return out + (_moe_route_stats(routing, t, w_len,
                                       int(moe_stats)),)
    return out                                           # [S, W, V], ..


def verify_step_slots(module: Sequential, params, state, cache, toks, t,
                      *, moe_dispatched: bool = True, moe_stats=None,
                      tree=None):
    """Contiguous-cache reference; the engine does not call this.

    Batched speculative VERIFY against a contiguous cache: toks [S, W]
    int (window token 0 is the slot's pending decode input, tokens
    1..W-1 its drafts), t [S] int per-slot window start positions;
    returns ([S, W, V] logits, cache). ``logits[:, j]`` is the target
    distribution over the token FOLLOWING window position j — the
    greedy accept rule is ``argmax(logits[:, j-1]) == toks[:, j]``.
    Sentinel slots (t out of range) write nothing and produce garbage
    logits, exactly like ``decode_step_slots`` — whose
    ``moe_dispatched``/``moe_stats`` MoE contract also applies.

    ``tree`` (tree-speculation PR: ``{"depth": [S, W] int, "anc":
    [S, W, W] bool}``) generalizes the chain window to a token TREE —
    window column j holds tree node j (node 0 the pending input/root),
    roped and position-embedded at its root-path depth, visible only
    to its descendants via the ancestor mask. With ``tree`` the return
    gains a third value: the per-layer roped window k/v that
    :func:`commit_tree_path` writes back along the accepted path. A
    chain-shaped tree (``depth[j] = j``, lower-triangular ``anc``)
    reproduces the plain window BIT FOR BIT."""
    return _verify_window(module, params, state, cache, toks, t,
                          None, 0, moe_dispatched, moe_stats,
                          tree=tree)


def verify_step_slots_paged(module: Sequential, params, state, cache,
                            toks, t, table, page_len: int,
                            *, moe_dispatched: bool = True,
                            moe_stats=None, paged_kernel=None,
                            tree=None):
    """The paged mirror of :func:`verify_step_slots`: window writes
    scatter through the [S, P] page tables (unallocated logical pages
    drop their writes — the engine pre-allocates pages for every
    position a slot may CONSUME, so dropped writes only ever land on
    the rejected tail). ``paged_kernel`` selects the readout exactly
    as in :func:`decode_step_slots_paged` — the kernel's ``[S, W]``
    window-causal mask generalization is what lets the speculative
    verify ride it too; the tree mask (``tree=``, see
    :func:`verify_step_slots`) rides the kernel as an ``[S, W, W]``
    ancestor-mask operand."""
    return _verify_window(module, params, state, cache, toks, t,
                          table, page_len, moe_dispatched, moe_stats,
                          paged_kernel, tree=tree)


# --- block diffusion (serving engine, block-diffusion PR) -------------------
#
# A block-causal model (``MultiHeadAttention(block_len=B)``) generates
# by denoising one block of B positions at a time. A PASS runs the
# block's B tokens (mask tokens where nothing is fixed yet) through the
# stack at positions ``t .. t+B-1`` against the cached blocks: the same
# [S, W] window machinery as the speculative verify, with the window
# attending bidirectionally to itself (``full_window``). Every pass
# writes the block's K/V at its positions; only the pass that runs once
# no mask is left (the commit pass) leaves values later blocks may
# read, and it needs no logits.


def routing_counts(routing):
    """``int32[2]`` of one program's expert layers, from the routing
    they collected (``(num_experts, (topi, full))`` each): the rows
    they routed, and the experts that owned at least one row, both
    summed over the layers. What the program ran, said by the program:
    a layer that did not run collected nothing.

    Where a layer holds a SHARE of its experts (a third entry ``(lo, n,
    num_experts)``: ``MoE.routing_share``) the vector is ``int32[5]``:
    the rows routed, the HELD experts that owned a row (what the layer
    read), then the rows by where they went: to experts held here, to
    experts that are not here (left out), to identity experts."""
    if any(len(r) > 2 for r in routing):
        return _share_counts(routing)
    rows = sum(topi.size for _e, (topi, _full) in routing)
    touched = jnp.zeros((), jnp.int32)
    for e, (topi, _full) in routing:
        owned = jnp.zeros((e,), jnp.int32).at[topi.reshape(-1)].add(1)
        touched = touched + jnp.sum(owned > 0, dtype=jnp.int32)
    return jnp.stack([jnp.asarray(rows, jnp.int32), touched])


def _share_counts(routing):
    rows = sum(r[1][0].size for r in routing)
    counts = jnp.zeros((4,), jnp.int32)      # touched, held, absent, zero
    for e, (topi, _full), *share in routing:
        lo, n, routed = share[0] if share else (0, e, e)
        ids = topi.reshape(-1)
        held = (ids >= lo) & (ids < lo + n)
        zero = ids >= routed
        owned = jnp.zeros((n,), jnp.int32).at[
            jnp.where(held, ids - lo, n)].add(1, mode="drop")
        counts = counts + jnp.stack([
            jnp.sum(owned > 0, dtype=jnp.int32),
            jnp.sum(held, dtype=jnp.int32),
            jnp.sum(~held & ~zero, dtype=jnp.int32),
            jnp.sum(zero, dtype=jnp.int32)])
    return jnp.concatenate([jnp.asarray([rows], jnp.int32), counts])


def block_pass_slots_paged(module: Sequential, params, state, cache,
                           toks, t, table, page_len: int, *,
                           head: bool = True,
                           moe_dispatched: bool = True,
                           paged_kernel=None):
    """One block-diffusion pass over the paged pool: toks [S, B] int
    (the block as it stands), t [S] int (the block's first position;
    the engine's sentinel for an idle slot writes nothing and yields
    garbage), table [S, P] page tables.

    With ``head`` returns ``(best [S, B] int32, conf [S, B] f32, cache,
    routed)``: at every position the most probable token and its
    log-probability (best logit less the log-sum-exp over the
    vocabulary, float32) — :func:`fix_most_confident` fixes the most
    confident masked positions from them. Without ``head`` (a pass in
    which every live slot only commits) the stack stops at the deepest
    attention block's K/V write and the return is ``(cache, routed)``.
    ``routed`` is :func:`routing_counts` of the expert layers that ran
    (zeros for a model without dispatched experts)."""
    x = toks
    w_len = toks.shape[1]
    new_cache = list(cache)
    routing = []
    last_block = max((i for i, l in enumerate(module.layers)
                      if _decode_block_of(l) is not None), default=-1)
    for i, layer in enumerate(module.layers):
        if not head and i > last_block:
            break
        p, s, kv = params[i], state[i], cache[i]
        block = _decode_block_of(layer)
        if block is not None:
            # without the head nothing reads what the last block makes
            # past its K/V, which are written from the attention's input
            x, new_cache[i] = _decode_block_slots_window(
                block, p, s, kv, x, t, table, page_len, moe_dispatched,
                routing, paged_kernel,
                kv_only=not head and i == last_block)
        elif isinstance(layer, PositionalEmbedding):
            with jax.named_scope("embed"):
                pos = _window_positions(t, w_len, None)
                x = x + p["embeddings"][pos].astype(x.dtype)
        elif isinstance(layer, Dropout):
            pass                                         # eval: identity
        else:
            with scoped(module.scope_of(i)):
                x, _ = layer.apply(p, s, x, training=False)
    routed = routing_counts(routing)
    if not head:
        return new_cache, routed
    with jax.named_scope("sample"):
        logits = x.astype(jnp.float32)                   # [S, B, V]
        best = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        conf = jnp.max(logits, axis=-1) \
            - jax.scipy.special.logsumexp(logits, axis=-1)
    return best, conf, new_cache, routed


def fix_most_confident(toks, masked, fixed_pass, best, conf, n_fix, step):
    """The denoising choice of one pass, per slot: the ``n_fix [S]``
    most confident positions still ``masked [S, B]`` take their most
    probable token (``best``), ``fixed_pass`` records the slot's
    denoising ``step [S]`` there, and they stop being masked. Returns
    ``(toks, masked, fixed_pass)`` after the fix; a slot with ``n_fix``
    0 (one that commits, or an idle one) keeps its rows.

    The order is that of a stable ascending sort of ``-conf`` with
    unmasked positions at ``+inf`` (equal confidences: the earliest
    position first; a NaN last, as numpy sorts it), taken as each
    position's RANK in that order by comparing every pair: B is a
    block, and no sort runs on the device."""
    key = jnp.where(masked, -conf.astype(jnp.float32), jnp.inf)
    a, b = key[:, :, None], key[:, None, :]          # b before a?
    nan_a, nan_b = jnp.isnan(a), jnp.isnan(b)
    idx = jnp.arange(key.shape[1])
    before = (b < a) | (nan_a & ~nan_b) \
        | (((b == a) | (nan_a & nan_b)) & (idx[None, :] < idx[:, None]))
    rank = jnp.sum(before, axis=2, dtype=jnp.int32)
    fix = rank < n_fix[:, None]
    return (jnp.where(fix, best, toks), masked & ~fix,
            jnp.where(fix, step[:, None].astype(fixed_pass.dtype),
                      fixed_pass))


def block_denoise_slots_paged(module: Sequential, params, state, cache,
                              toks, masked, fixed_pass, n_fix, step, t,
                              table, page_len: int, *,
                              moe_dispatched: bool = True,
                              paged_kernel=None):
    """A denoising pass WITH its choice (the serving engine's
    ``denoise`` program): :func:`block_pass_slots_paged` with the
    vocabulary head over the blocks as they stand, then
    :func:`fix_most_confident` on its float32 log-probabilities. Takes
    and returns the block state as arrays of the device, so the next
    pass can be queued on this one's result before the host has read
    anything: ``(toks, masked, fixed_pass, cache, routed)``."""
    best, conf, cache, routed = block_pass_slots_paged(
        module, params, state, cache, toks, t, table, page_len,
        head=True, moe_dispatched=moe_dispatched,
        paged_kernel=paged_kernel)
    with jax.named_scope("sample"):
        toks, masked, fixed_pass = fix_most_confident(
            toks, masked, fixed_pass, best, conf, n_fix, step)
    return toks, masked, fixed_pass, cache, routed


def tree_walk(logits, toks, parents, *, temperature=None, top_k=None,
              top_p=None, keys=None):
    """In-program acceptance over a verified token tree: greedily walk
    the longest accepted root-path.

    ``logits`` [S, W, V] is the verify forward's output (row j = the
    target's next-token distribution AFTER consuming node j's root
    path); ``toks`` [S, W] the window tokens (node 0 = the pending
    input); ``parents`` [S, W] the parent-index vectors (node 0 and
    unused nodes carry -1 — an unused node can never be entered
    because no walk position equals -1).

    The walk starts at the root and repeats: draw the target's choice
    ``x`` at the current node (argmax when ``temperature`` is None,
    else one PRNG split + ``_sample_vec`` — EXACTLY the per-emitted-
    token key discipline of plain decode, so sampled streams stay
    byte-identical); emit ``x``; descend into the lowest-index child
    whose draft token equals ``x``, or stop. Every emitted token is
    either an accepted draft (the child's token) or the final bonus —
    between 1 and W emissions. For a point-mass (deterministic) draft
    this IS the exact multi-draft rejection-sampling rule: each
    candidate child is a distinct point mass, and sampling from the
    target then accepting on equality preserves the plain-decode
    output distribution token for token.

    Returns ``(emitted [S, W], n_emit [S], path [S, W], new_keys)``:
    ``emitted[:, :n_emit]`` are the tokens to append, ``path[:, d]``
    the accepted node at depth d (valid for ``d < n_emit``; the commit
    uses it to place K/V), ``new_keys`` the post-walk per-slot keys
    (None for greedy) — advanced by exactly ``n_emit`` splits, as
    ``n_emit`` plain decode iterations would have."""
    s_n, w_len, _ = logits.shape
    greedy = temperature is None
    rows = jnp.arange(s_n)
    cur = jnp.zeros((s_n,), jnp.int32)
    walking = jnp.ones((s_n,), bool)
    n_emit = jnp.zeros((s_n,), jnp.int32)
    ks = keys
    emitted = []
    path = [cur]
    for _ in range(w_len):
        lg = logits[rows, cur]                           # [S, V]
        if greedy:
            x = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        else:
            split = jax.vmap(jax.random.split)(ks)
            x = _sample_vec(lg, temperature, top_k, top_p,
                            split[:, 1]).astype(jnp.int32)
            # the key advances only on steps that actually emit — a
            # finished walk must not consume entropy plain decode
            # would not have
            ks = jnp.where(walking[:, None], split[:, 0], ks)
        emitted.append(jnp.where(walking, x, -1))
        n_emit = n_emit + walking.astype(jnp.int32)
        is_child = (parents == cur[:, None]) & (toks == x[:, None]) \
            & walking[:, None]                           # [S, W]
        # node 0's parent is -1 and cur >= 0, so the root can never be
        # re-entered; ties (two children with one token) resolve
        # lowest-index — the subtrees are interchangeable up to here
        has = is_child.any(axis=1)
        child = jnp.argmax(is_child, axis=1).astype(jnp.int32)
        walking = walking & has
        cur = jnp.where(walking, child, cur)
        path.append(cur)
    return (jnp.stack(emitted, axis=1),
            n_emit,
            jnp.stack(path[:w_len], axis=1),
            None if greedy else ks)


def commit_tree_path(cache, kv_win, path, t, n_emit, table=None,
                     page_len: int = 0):
    """Post-acceptance cache commit for tree speculation: write the
    accepted root-path's K/V at its CONTIGUOUS final positions.

    The verify forward wrote node j at window column ``t + j``; the
    accepted node at depth d belongs at ``t + d`` (and was roped
    there — ``depth[path[d]] == d`` by construction). This pass
    gathers each layer's window k/v along ``path`` and re-writes
    depths ``0 .. n_emit-1`` through the page writer (``table`` None:
    the tests' contiguous reference); depths past the accepted path
    route to an out-of-range position, where the one-hot write misses
    and the page scatter drops — rejected branches stay exactly the
    stale-tail garbage the masks already cover, healed by the stream's
    own later writes.
    Chain-shaped trees re-write identical bytes (the accepted node AT
    depth d IS window column d), so a width-1 tree's cache equals the
    linear verify's bit for bit."""
    w_len = path.shape[1]
    new_cache = list(cache)
    drop = jnp.int32(2 ** 30)        # past any capacity: writers skip
    for i, kvw in enumerate(kv_win):
        if kvw is None:
            continue
        k, v = kvw                                       # [S, W, H, D]
        kc = jnp.take_along_axis(k, path[:, :, None, None], axis=1)
        vc = jnp.take_along_axis(v, path[:, :, None, None], axis=1)
        kv = new_cache[i]
        for d in range(w_len):
            pos = jnp.where(d < n_emit, t + d, drop)
            if table is None:
                kv = _cache_write_slots(kv, kc[:, d:d + 1],
                                        vc[:, d:d + 1], pos)
            else:
                kv = _cache_write_pages(kv, kc[:, d:d + 1],
                                        vc[:, d:d + 1], pos, table,
                                        page_len)
        new_cache[i] = kv
    return new_cache


# --- fused multi-step decode (zero-bubble serving PR) -----------------------
#
# In steady-state serving (no admissions, no prefill, no speculation)
# every iteration is the SAME per-slot decode step; dispatching them one
# at a time leaves a host gap between device steps — on TPU, where a
# step is ~1-5 ms, that gap is the throughput ceiling. The fused window
# compiles K plain iterations as ONE ``lax.scan`` program: the carry
# feeds each step's sampled token back as the next step's input
# (device-side — the host never sees intermediate tokens), per-slot
# ``done`` masks reproduce ``generate()``'s stop-token padding (a slot
# that emits its stop keeps emitting it for the rest of the window, so
# the host can truncate the emitted buffer at the first stop), and the
# program emits the whole [S, K] token block in one fetch. Every step
# inside the window is bitwise the single-step program's computation —
# same cache writes, same sampler, same per-slot key splits — so fused
# output is token-identical (byte-identical for sampled streams) to K
# separate iterations.


def decode_fused_slots(module: Sequential, params, state, cache, tok, t,
                       stop, num_steps: int, table,
                       page_len: int, *, temperature=None,
                       top_k=None, top_p=None, keys=None,
                       moe_dispatched: bool = True, moe_stats=None,
                       paged_kernel=None, sampler=None):
    """``num_steps`` consecutive ``decode_step_slots_paged``
    iterations as one compiled scan. tok/t: [S] ints (per-slot pending
    input and write position); ``stop``: [S] int per-slot stop tokens
    (-1 = never). Greedy when ``temperature`` is None; otherwise
    ``temperature``/``top_k``/``top_p`` are the [S] per-slot sampling
    vectors and ``keys`` the [S] per-slot PRNG keys, split once per
    step exactly like the single-step sampled program (byte-identical
    streams). Returns ``(toks [S, num_steps], cache, keys_or_None,
    moe_stats_or_None)`` — ``toks[:, j]`` is the token emitted by
    window step j; after a slot's stop token fires, its remaining
    window positions repeat the stop (``generate()``'s padding rule).
    Sentinel slots (t out of range) ride along writing nothing.

    Cache contract: step j writes position ``t + j`` for every slot —
    the caller must have every page under ``t .. t+num_steps-1``
    allocated for positions it intends to CONSUME (paged writes to
    unallocated pages drop; post-stop writes land as stale-tail
    garbage, overwritten before any mask admits them)."""
    greedy = temperature is None
    stats_on = moe_stats is not None
    # fused-sampling PR: the engine routes the per-step draw through
    # ``ops.sampling.sample_tokens`` (same key-split discipline, same
    # byte stream) when its fused_sampling knob is on
    sample = _sample_vec if sampler is None else sampler

    def body(carry, _):
        if greedy:
            cache, cur, tcur, done = carry
        else:
            cache, cur, tcur, done, ks = carry
        out = decode_step_slots_paged(module, params, state, cache,
                                      cur, tcur, table, page_len,
                                      paged_kernel=paged_kernel,
                                      moe_dispatched=moe_dispatched,
                                      moe_stats=moe_stats)
        if stats_on:
            logits, cache, st = out
        else:
            logits, cache = out
        if greedy:
            nxt = jnp.argmax(logits, axis=-1).astype(cur.dtype)
        else:
            split = jax.vmap(jax.random.split)(ks)
            ks = split[:, 0]
            nxt = sample(logits, temperature, top_k, top_p,
                         split[:, 1]).astype(cur.dtype)
        # generate()'s stop rule, per slot: done rows hold the stop
        # token (padding the window), and a freshly emitted stop marks
        # the row done for the remaining steps
        nxt = jnp.where(done, stop.astype(cur.dtype), nxt)
        done = done | ((nxt == stop) & (stop >= 0))
        carry = (cache, nxt, tcur + 1, done) + (() if greedy else (ks,))
        return carry, ((nxt,) if not stats_on else (nxt, st))

    done0 = jnp.zeros(tok.shape, bool)
    carry0 = (cache, tok, t, done0) + (() if greedy else (keys,))
    carry, ys = lax.scan(body, carry0, None, length=int(num_steps))
    toks = jnp.swapaxes(ys[0], 0, 1)                     # [S, K]
    new_cache = carry[0]
    new_keys = None if greedy else carry[4]
    stats = None
    if stats_on:
        # the LAST window step's routing picture (the engine's stats
        # throttle reads at most one sample per window anyway)
        stats = jax.tree_util.tree_map(lambda a: a[-1], ys[1])
    return toks, new_cache, new_keys, stats


def _sample(logits, temperature, top_k, rng, top_p=None):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits.astype(jnp.float32) / temperature
    if top_k is not None:
        # mask from top_k's INDICES, not a value threshold — ties at the
        # k-th logit would otherwise admit more than k candidates (the MoE
        # router masks the same way for the same reason). one_hot keeps
        # this rank-agnostic: any leading batch dims work
        _, idx = lax.top_k(logits, top_k)
        keep = jax.nn.one_hot(idx, logits.shape[-1],
                              dtype=jnp.bool_).any(axis=-2)
        logits = jnp.where(keep, logits, NEG_INF)
    if top_p is not None:
        # nucleus sampling (round 4): keep the smallest probability-sorted
        # prefix whose mass reaches top_p. Token i survives iff the mass
        # STRICTLY ABOVE it is < top_p (so the boundary token that crosses
        # the threshold is included, per the standard construction).
        # Logit-value ties at the boundary admit their whole tie class —
        # the probability-identical analogue of the top_k caveat, accepted
        # because a value threshold keeps this one sort + one compare
        # (composes with top_k: applied after its mask, like HF).
        sorted_logits = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        exclusive = jnp.cumsum(probs, axis=-1) - probs
        keep_sorted = exclusive < top_p
        thresh = jnp.min(
            jnp.where(keep_sorted, sorted_logits, jnp.inf),
            axis=-1, keepdims=True)
        logits = jnp.where(logits >= thresh, logits, NEG_INF)
    return jax.random.categorical(rng, logits, axis=-1)


# --- per-sequence sampling state (serving engine + generate arrays) --------


def _sample_vec(logits, temperature, top_k, top_p, rng):
    """Per-SEQUENCE sampling: every knob is a [B] vector, so requests
    with heterogeneous sampling settings coexist in one batch (the
    serving engine's per-slot sampling state; ``generate()`` routes
    per-sequence arrays here too). Disabled sentinels: ``temperature
    0`` = greedy for that row, ``top_k <= 0`` = no truncation,
    ``top_p >= 1`` = no nucleus cut.

    ``rng`` is either one key (the whole batch draws from it, as in
    ``generate``'s scan) or a [B] batch of per-slot keys (the engine:
    each slot's stream must be reproducible regardless of which other
    requests share the batch).

    top_k here masks by RANK from a stable descending argsort — ties at
    the k-th logit resolve lowest-index-first, the same order
    ``lax.top_k`` uses, so the vector path admits exactly the scalar
    path's candidate set."""
    greedy = jnp.argmax(logits, axis=-1)
    lf = _masked_logits_vec(logits, temperature, top_k, top_p)
    if rng.ndim > 1:                                     # per-slot keys
        sampled = jax.vmap(jax.random.categorical)(rng, lf)
    else:
        sampled = jax.random.categorical(rng, lf, axis=-1)
    return jnp.where(temperature > 0.0, sampled, greedy)


def _masked_logits_vec(logits, temperature, top_k, top_p):
    """The mask half of :func:`_sample_vec`: temperature-scaled f32
    logits with the rank top-k and exclusive-cumsum nucleus cuts
    applied (NEG_INF outside the candidate set). Shared with
    ``ops.sampling.sample_epilogue`` so the fused sampling path admits
    BIT-IDENTICAL candidate sets — ``categorical(key, lf)`` IS
    ``argmax(lf + gumbel(key))``, which is exactly how the fused
    epilogue factors it."""
    lf = logits.astype(jnp.float32)
    safe_t = jnp.where(temperature > 0.0, temperature, 1.0)
    lf = lf / safe_t[:, None]
    # top_k by rank (stable argsort == lax.top_k tie order)
    order = jnp.argsort(-lf, axis=-1)
    ranks = jnp.argsort(order, axis=-1)
    keep = (top_k[:, None] <= 0) | (ranks < top_k[:, None])
    lf = jnp.where(keep, lf, NEG_INF)
    # nucleus, same boundary construction as the scalar path
    sorted_logits = jnp.flip(jnp.sort(lf, axis=-1), axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    exclusive = jnp.cumsum(probs, axis=-1) - probs
    keep_sorted = exclusive < top_p[:, None]
    thresh = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf),
                     axis=-1, keepdims=True)
    return jnp.where((top_p >= 1.0)[:, None] | (lf >= thresh), lf,
                     NEG_INF)


def _per_seq_vec(value, b, dtype, none_sentinel, name):
    """Normalize a scalar-or-[B]-array sampling knob to a [B] vector
    (``None`` -> the disabled sentinel; scalars broadcast)."""
    if value is None:
        value = none_sentinel
    arr = jnp.asarray(value, dtype)
    if arr.ndim == 0:
        return jnp.full((b,), arr)
    if arr.shape != (b,):
        raise ValueError(
            f"per-sequence {name} must have shape ({b},) to match the "
            f"prompt batch, got {arr.shape}")
    return arr


def _is_per_seq(value) -> bool:
    """True when a sampling knob was passed as a per-sequence array
    (list/tuple or an ndarray with a batch dim) rather than a scalar."""
    if value is None or isinstance(value, (int, float)):
        return False
    if isinstance(value, (list, tuple)):
        return True
    return getattr(value, "ndim", 0) >= 1


def _attn_compute_dtype(module: Sequential):
    """The attention compute dtype of the first TransformerBlock (the
    LM-family convention: one dtype across the stack), or None."""
    for layer in module.layers:
        block = _decode_block_of(layer)
        if block is not None:
            return jnp.dtype(block.attn.dtype)
    return None


def _fuse_qkv_params(module: Sequential, params):
    """Serving-tree rewrite (round 5, decode-overhead attack): replace
    each attention layer's ``wq``/``wk``/``wv`` with ONE concatenated
    ``wqkv`` [d, H + 2*Hkv, Dh], so every decode step (and prefill) runs
    one projection matmul instead of three. At small batch the decode
    step is op-launch/latency-bound (docs/PERF.md §Long-context), and
    the three q/k/v einsums are the most mechanical fusion available.
    Exact: each output column of the concatenated matmul is the same
    d-length dot product as in the separate matmuls. Applied to FLOAT
    serving trees only — the int8 path's per-Dh scales differ across
    q/k/v and cannot share one concatenated payload. SHARDED weights
    (GSPMD/Megatron TP: wq/wk/wv split on the head axis) are left
    unfused — concatenating differently-sharded head axes would re-split
    the fused tensor across q/kv shard boundaries and pay resharding
    collectives every step (review r5)."""
    def replicated(leaf):
        sh = getattr(leaf, "sharding", None)
        return sh is None or getattr(sh, "is_fully_replicated", True)

    fused = list(params)
    for i, layer in enumerate(module.layers):
        block = _decode_block_of(layer)
        if block is None:
            continue
        p = dict(fused[i])
        pa = dict(p["attn"])
        if not all(replicated(pa[k]) for k in ("wq", "wk", "wv")):
            continue
        pa["wqkv"] = jnp.concatenate(
            [pa.pop("wq"), pa.pop("wk"), pa.pop("wv")], axis=1)
        p["attn"] = pa
        fused[i] = p
    return fused


def _project_qkv(attn: MultiHeadAttention, p, xc):
    """q/k/v projections for the serving paths: the fused ``wqkv``
    matmul when the tree carries it (see ``_fuse_qkv_params``), the
    fused dequant-matmul when the engine left the projections
    quantized (``ServingEngine(weight_quant=)`` — ``ops.quant_matmul``
    qdicts; the kernel unpacks int8/int4 bytes in-register, so the
    float weights never touch HBM), the three separate einsums
    otherwise. Queries and keys come back with the layer's per-head
    norm applied (``qk_norm``), before RoPE."""
    if "wqkv" in p:
        qkv = jnp.einsum("bsd,dhe->bshe", xc, p["wqkv"].astype(xc.dtype))
        h, hkv = attn.num_heads, attn.kv_heads
        q, k = attn.normed_qk(p, qkv[:, :, :h], qkv[:, :, h:h + hkv])
        return q, k, qkv[:, :, h + hkv:]
    if isinstance(p["wq"], dict):
        from distkeras_tpu.ops.quant_matmul import quant_matmul
        b, s_len, d = xc.shape
        x2 = xc.reshape(b * s_len, d)

        def proj(wdict, heads):
            y = quant_matmul(x2, wdict).astype(xc.dtype)
            return y.reshape(b, s_len, heads, -1)

        q, k = attn.normed_qk(p, proj(p["wq"], attn.num_heads),
                              proj(p["wk"], attn.kv_heads))
        return q, k, proj(p["wv"], attn.kv_heads)
    dt = xc.dtype
    q = jnp.einsum("bsd,dhe->bshe", xc, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhe->bshe", xc, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhe->bshe", xc, p["wv"].astype(dt))
    q, k = attn.normed_qk(p, q, k)
    return q, k, v


def _serving_params(params, dtype):
    """Pre-cast the big (ndim >= 2) weight matrices to the serving dtype
    ONCE, outside the decode scan. For a bf16-compute model this is
    numerically FREE for every matmul weight (apply casts them per-step
    anyway — pre-casting just stops the per-step f32 HBM read, which is
    half the decode byte budget); only the embedding-table gather and the
    un-cast f32 head read change, both below bf16 round-off of the
    surrounding compute. Vectors (biases, norm scales) stay f32: they are
    applied in f32 and cost nothing."""
    return jax.tree_util.tree_map(
        lambda p: p.astype(dtype)
        if (hasattr(p, "ndim") and p.ndim >= 2
            and jnp.issubdtype(p.dtype, jnp.floating)) else p,
        params)


def generate(model: Model, prompts, max_new_tokens: int,
             temperature: float = 0.0, top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             seed: int = 0, cache_dtype=None,
             stop_token: Optional[int] = None,
             weights_dtype="auto", as_numpy: bool = True,
             prefill_chunk: Optional[int] = None) -> np.ndarray:
    """Autoregressive continuation: ``[B, P]`` int prompts ->
    ``[B, P + max_new_tokens]`` tokens. ``temperature=0`` is greedy;
    otherwise softmax sampling (optionally top-k-truncated).

    Sampling: ``temperature=0`` is greedy; otherwise softmax sampling,
    optionally truncated by ``top_k`` (index-exact) and/or ``top_p``
    (nucleus: smallest probability prefix whose mass reaches ``top_p``;
    applied after the top_k mask when both are given).

    ``temperature``/``top_k``/``top_p``/``stop_token`` also accept
    PER-SEQUENCE ``[B]`` arrays (this PR — the same plumbing the serving
    engine's per-slot sampling uses), so heterogeneous requests share
    one batch: row sentinels ``temperature 0`` = greedy, ``top_k 0`` =
    no truncation, ``top_p 1.0`` = no nucleus cut, ``stop_token -1`` =
    never stop. Scalars broadcast (the scalar API compiles the exact
    pre-existing program); when ANY knob is an array, all four become
    traced [B] vectors, so ONE compiled program serves every
    per-sequence sampling configuration at that shape.

    ``stop_token``: once a sequence emits it, every later position is
    filled with it too (the compiled scan always runs ``max_new_tokens``
    steps — static shapes — so "stopping" is per-sequence padding, which
    is also what makes the batch ragged-safe).

    Decode is weight+cache HBM-read bound (docs/PERF.md roofline), so
    storage dtypes are the throughput levers:

    ``cache_dtype=None`` matches the model's attention COMPUTE dtype —
    for a bf16 model the k/v entries were computed in bf16, so an f32
    cache stores no extra information while doubling the dominant read.
    ``weights_dtype="auto"`` pre-casts matrix weights to the same compute
    dtype once before the scan (see ``_serving_params``); ``None``
    disables, a dtype forces, and ``"int8"`` serves weight-only int8
    (``models.quantize`` per-channel symmetric): matrices live in HBM as
    int8 and dequantize inside each step's matmul fusion — another ~2×
    off the weight-read bound, at int8 weight accuracy.

    ``prefill_chunk`` (round 5): ingest the prompt in chunks of this
    many positions (see :func:`prefill_chunked`) — peak prefill
    activation memory becomes O(chunk) instead of O(P), the enabler for
    >= 32K prompts; TTFT stays quadratic-compute-bound. ``None`` (the
    default) is the one-pass prefill.

    Backend contract (``compat.backend_is_tpu`` — the repo-wide
    convention every Pallas-vs-XLA fork follows, including the fused
    MoE dispatch): kernel selection keys off the TRACE-TIME default
    backend, not the runtime device of the inputs. The traced program
    assumes it executes on ``jax.default_backend()``; to serve from a
    non-default device (e.g. CPU inside a TPU-backed process), wrap the
    call in ``jax.default_device(...)`` so trace-time agrees with
    run-time — per-input device dispatch is deliberately NOT supported
    (it would fork every jitted serving program on an attribute jit
    erases)."""
    module = model.module
    if not isinstance(module, Sequential):
        raise TypeError("generate() expects a Sequential LM "
                        f"(got {type(module).__name__})")
    for layer in module.layers:
        blk = _decode_block_of(layer)
        if blk is not None:
            _refuse_latent(blk, "generate()")
    if block_len_of(module) is not None:
        raise ValueError(
            "generate() decodes one token a step; a block-causal "
            "(block-diffusion) model is decoded by ServingEngine, a "
            "block of tokens at a time")
    prompts = jnp.asarray(prompts)
    if prompts.ndim != 2:
        raise ValueError(f"prompts must be [B, P], got {prompts.shape}")
    max_new_tokens = int(max_new_tokens)
    if max_new_tokens < 0:
        raise ValueError(f"max_new_tokens must be >= 0, "
                         f"got {max_new_tokens}")
    per_seq = any(_is_per_seq(v)
                  for v in (temperature, top_k, top_p, stop_token))
    if not per_seq and top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if prefill_chunk is not None:
        prefill_chunk = int(prefill_chunk)
        if prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
    if max_new_tokens == 0:
        # nothing to generate; never run the clamped first-token write
        # (it would overwrite the final prompt position — review r4)
        return np.asarray(prompts) if as_numpy else prompts
    b, p_len = prompts.shape
    total = p_len + max_new_tokens
    samp = {}
    if per_seq:
        samp = {
            "temperature": _per_seq_vec(temperature, b, jnp.float32, 0.0,
                                        "temperature"),
            "top_k": _per_seq_vec(top_k, b, jnp.int32, 0, "top_k"),
            "top_p": _per_seq_vec(top_p, b, jnp.float32, 1.0, "top_p"),
            "stop": _per_seq_vec(stop_token, b, jnp.int32, -1,
                                 "stop_token"),
        }
        topp_h = np.asarray(samp["top_p"])
        if ((topp_h <= 0.0) | (topp_h > 1.0)).any():
            raise ValueError(
                f"top_p entries must be in (0, 1], got {topp_h}")
    _resolve_head_dims(module, model.params)
    for layer in module.layers:
        # out-of-range position gathers CLAMP under jit (silent wrong-
        # position logits) — fail loudly up front instead
        if isinstance(layer, PositionalEmbedding) and total > layer.max_len:
            raise ValueError(
                f"PositionalEmbedding(max_len={layer.max_len}) is too "
                f"small for prompt {p_len} + {max_new_tokens} new tokens "
                f"= {total} positions")
    compute_dt = _attn_compute_dtype(module)
    if cache_dtype is None:
        cache_dtype = compute_dt if compute_dt is not None else jnp.float32
    if weights_dtype == "auto":
        weights_dtype = compute_dt if (
            compute_dt is not None
            and compute_dt != jnp.dtype(jnp.float32)) else None
    # normalize: np.int8/jnp.int8 mean the quantized path, same as "int8"
    # (a raw astype(int8) of float weights would zero them); other int
    # dtypes are meaningless for weights
    if weights_dtype is not None and weights_dtype not in ("int8",
                                                           "int4"):
        dt = jnp.dtype(weights_dtype)
        if dt == jnp.dtype(jnp.int8):
            weights_dtype = "int8"
        elif not jnp.issubdtype(dt, jnp.floating):
            # a raw astype to any non-float dtype would silently destroy
            # sub-unity weights (bool/ints round them to 0/1)
            raise ValueError(
                f"weights_dtype {dt.name!r} unsupported: use a float "
                "dtype, 'int8'/'int4' (weight-only quantized serving), "
                "'auto' or None")
    # serving-weight cache: one entry per dtype, each validated against
    # the SOURCE params by identity (strong ref -> no id()-reuse hazard);
    # a loop alternating dtypes must not re-pay full-tree conversion.
    # Entries whose source tree is no longer model.params are purged on
    # any lookup — without this, a weight update would pin every old
    # params tree (plus its converted copy) in memory forever.
    cache_all = getattr(model, "_serving_params_cache", None)
    if cache_all is None:
        cache_all = model._serving_params_cache = {}
    for k in [k for k, v in cache_all.items()
              if v[0] is not model.params]:
        del cache_all[k]
    scales = None
    if weights_dtype in ("int8", "int4"):
        # weight-only quantized serving (models.quantize): matrices
        # stored as {q: int8, scale: f32[out]}; dequant happens INSIDE
        # the scan body so XLA fuses q*scale into each step's matmul
        # reads — the weight HBM traffic per decoded token is int8,
        # halving the dominant read again vs bf16 (docs/PERF.md
        # roofline). "int4" swaps in the 4-bit grid (bits=4): the
        # accuracy rung below int8 — here it still stores one byte per
        # entry; the serving engine's fused dequant-matmul kernel is
        # where nibble packing pays the extra 2x (ops.quant_matmul)
        from distkeras_tpu.models.quantize import quantize_params
        cached = cache_all.get(weights_dtype)
        if cached is None:
            q, s = quantize_params(
                jax.device_get(model.params),
                bits=4 if weights_dtype == "int4" else 8)
            # scales go to device too: per-call H2D of hundreds of small
            # numpy leaves would reintroduce the per-call overhead this
            # cache exists to avoid (device_put preserves None leaves)
            cached = (model.params,
                      (jax.device_put(q), jax.device_put(s)))
            cache_all[weights_dtype] = cached
        run_params, scales = cached[1]
    elif weights_dtype is None:
        run_params = model.params
    else:
        # fuse q/k/v into one wqkv matmul only for DEEP caches (round 5;
        # same LENGTH threshold as the fused decode kernel, though the
        # fusion applies on every backend — it is exact everywhere): at
        # P=8192/b4 the fusion takes the step 1.59 -> ~1.0 ms, but at a
        # short cache it REGRESSES decode 23% (measured 6,967 -> 5,350
        # tok/s at the 136-position headline config — the three
        # separate projections fuse better with their neighbors there)
        from distkeras_tpu.ops.decode_attention import MIN_KERNEL_LEN
        fuse_qkv = total >= MIN_KERNEL_LEN
        dt_key = jnp.dtype(weights_dtype).name
        base = cache_all.get(dt_key)
        if base is None:
            base = (model.params,
                    _serving_params(model.params, weights_dtype))
            cache_all[dt_key] = base
        if fuse_qkv:
            # derive the fused tree FROM the cached base so every
            # non-attention leaf is shared — a server alternating short
            # and long prompts holds one weight tree plus the fused
            # attention deltas, not two full copies
            fused_key = dt_key + "+wqkv"
            cached = cache_all.get(fused_key)
            if cached is None:
                cached = (model.params,
                          _fuse_qkv_params(module, base[1]))
                cache_all[fused_key] = cached
            run_params = cached[1]
        else:
            run_params = base[1]
    # shape/capacity validation runs eagerly (fail loudly BEFORE tracing);
    # the actual buffers are created inside the compiled program below
    init_cache(module, b, 1, cache_dtype)

    # one compiled program per (model, shape, sampling) configuration —
    # cached on the Model so a serving loop pays trace+compile once, like
    # Model.predict's cached forward. Round 4: the program is a batched
    # PREFILL over the whole prompt (one flash pass; see ``prefill``)
    # followed by a decode-only scan over the new tokens — replaying the
    # prompt through the sequential scan made long prompts O(P) device
    # steps instead of O(1) kernel passes.
    if per_seq:
        # the vectors are TRACED args: one program per shape serves every
        # per-sequence sampling configuration
        samp_key = ("per-seq",)
    else:
        samp_key = (float(temperature), top_k,
                    None if top_p is None else float(top_p), stop_token)
    key = (b, p_len, int(max_new_tokens)) + samp_key + (
        "int4" if (isinstance(cache_dtype, str) and cache_dtype == "int4")
        else jnp.dtype(cache_dtype).name,
        None if weights_dtype is None
        else (weights_dtype if weights_dtype in ("int8", "int4")
              else jnp.dtype(weights_dtype).name),
        prefill_chunk)
    jit_cache = getattr(model, "_jit_generate", None)
    if jit_cache is None:
        jit_cache = model._jit_generate = {}
    run = jit_cache.get(key)
    if run is None:
        int8w = scales is not None

        def live_params(params, run_scales):
            if not int8w:
                return params
            # dequant INSIDE the traced region that consumes it (prefill
            # pass / each scan step): q*scale fuses into the matmul
            # reads, so weight HBM traffic stays int8. scales are TRACED
            # args, not closure constants — re-quantized params after a
            # weight update must not meet a stale baked-in scale tree
            from distkeras_tpu.models.quantize import dequantize_params
            return dequantize_params(params, run_scales)

        def sample_next(logits, run_samp, sub):
            if per_seq:
                return _sample_vec(logits, run_samp["temperature"],
                                   run_samp["top_k"], run_samp["top_p"],
                                   sub)
            return _sample(logits, temperature, top_k, sub, top_p)

        @jax.jit
        def run(params, run_scales, state, prompts, rng, run_samp):
            # the cache is created INSIDE the compiled program (shapes
            # are static): no multi-GB host-side zeros allocation per
            # call, and XLA sees a single dead-on-exit buffer instead of
            # distinct input+output copies — at P=8192 the bf16 cache is
            # 3.2 GB, and the in+out pair was what pushed the long-
            # context MHA program over the compile/memory edge (round 4).
            # Capacity rounds up to the decode kernel's block size on
            # TPU so every serving call takes the fused Pallas path
            # (the margin is masked; models position checks use `total`)
            if backend_is_tpu():
                from distkeras_tpu.ops.decode_attention import \
                    MIN_KERNEL_LEN, choose_block
            if backend_is_tpu() and total >= MIN_KERNEL_LEN:
                bl = choose_block(total)
                cap = -(-total // bl) * bl
            else:
                cap = total
            cache = init_cache(module, b, cap, cache_dtype,
                               check_len=total)
            live = live_params(params, run_scales)
            if prefill_chunk is not None and p_len > prefill_chunk:
                last_logits, cache = prefill_chunked(
                    module, live, state, cache, prompts, prefill_chunk)
            else:
                last_logits, cache = prefill(module, live, state, cache,
                                             prompts)
            rng, sub = jax.random.split(rng)
            first = sample_next(last_logits, run_samp, sub)
            done = jnp.zeros((b,), bool)
            if per_seq:
                stop_v = run_samp["stop"]
                done = (first == stop_v) & (stop_v >= 0)
            elif stop_token is not None:
                done = first == stop_token
            tokens = jnp.concatenate(
                [prompts,
                 jnp.zeros((b, int(max_new_tokens)), prompts.dtype)],
                axis=1)
            tokens = lax.dynamic_update_slice_in_dim(
                tokens, first[:, None].astype(tokens.dtype), p_len, axis=1)

            def body(carry, t):
                tokens, cache, rng, done = carry
                p = live_params(params, run_scales)
                tok = lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)[:, 0]
                logits, cache = decode_step(module, p, state, cache,
                                            tok, t)
                rng, sub = jax.random.split(rng)
                nxt = sample_next(logits, run_samp, sub)
                if per_seq:
                    stop_v = run_samp["stop"]
                    # rows already done have stop_v >= 0 by construction
                    nxt = jnp.where(done, stop_v.astype(nxt.dtype), nxt)
                    done = done | ((nxt == stop_v) & (stop_v >= 0))
                elif stop_token is not None:
                    nxt = jnp.where(done, stop_token, nxt)
                    done = done | (nxt == stop_token)
                tokens = lax.dynamic_update_slice_in_dim(
                    tokens, nxt[:, None].astype(tokens.dtype), t + 1,
                    axis=1)
                return (tokens, cache, rng, done), None

            (tokens, _, _, _), _ = lax.scan(
                body, (tokens, cache, rng, done),
                jnp.arange(p_len, total - 1))
            return tokens

        jit_cache[key] = run

    out = run(run_params, {} if scales is None else scales, model.state,
              prompts, jax.random.PRNGKey(seed), samp)
    # as_numpy=False skips the device->host sync: serving loops that
    # pipeline several generate calls only pay one round trip at the end
    # (bench.py measures both modes)
    return np.asarray(out) if as_numpy else out
