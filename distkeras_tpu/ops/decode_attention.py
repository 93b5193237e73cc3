"""Fused single-step decode attention as a Pallas TPU kernel.

Round 4 (VERDICT r3 weak #7 / next #7). The XLA lowering of the decode
cache contractions (``einsum("bqhgd,bhkd->bhgqk")`` with q-length 1)
is a ``multiply_reduce`` fusion: it materializes the f32 broadcast
product of the whole [L, D] cache plane in HBM before reducing —
measured 0.37 ms per layer-step at L=2113 on v5e (~3x the cache bytes,
~100 GB/s effective). This kernel fuses scores + masking + softmax +
value mixing into ONE pass over the cache per layer: each K/V tile is
read once at streaming rate, the online-softmax carry lives in VMEM
scratch, and nothing intermediate touches HBM.

Two structural lessons are baked in (both measured on v5e):

* **Program granularity.** A first cut used one program per (batch,
  head) row — 128 tiny programs per layer on the single TensorCore,
  whose per-program overhead (~2 us) swamped the 64 KB of useful DMA
  each (short-cache decode regressed 6.4K -> 2.2K tok/s). Programs now
  cover ``bh_block`` (default 8) rows at once, with the per-row math an
  unrolled loop inside the kernel; per-program DMA is bh_block x
  [block_l, D] x 2.
* **Capacity coupling.** The cache length is rounded by ``generate()``
  to the block size this module picks for the TOTAL length
  (``choose_block``): short caches use small blocks so a 136-position
  decode does not stream a 512-padded buffer.

Layout: head-major caches ``[B*Hkv, L, D]`` (matching
``models.decoding.init_cache``); queries ``[B*Hkv, G, D]`` (G = query
heads per KV head — GQA groups are the matmul M dimension, so grouped
queries make the tile MORE efficient, not less). The current decode
position ``t`` is a scalar-prefetch operand: tile columns past ``t``
skip their compute.

int8 caches pass per-token scales ``[B*Hkv, L]``; dequant happens on
the VPU inside the kernel (scores multiply by k_scale AFTER the D
contraction; probabilities multiply by v_scale BEFORE the V
contraction), so HBM traffic stays int8 + scales.

Off-TPU the caller (``models.decoding._decode_attn``) keeps the einsum
path — this kernel also runs in interpreter mode for the CPU test suite
(``tests/test_decode_kernel.py`` pins it against the einsum oracle).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.compat import backend_is_tpu
from distkeras_tpu.ops.attention import NEG_INF

#: candidate L tile sizes, largest first — `choose_block` picks per length
BLOCK_CANDIDATES = (1024, 512, 256, 128)

#: caches shorter than this stay on the einsum path (measured: the
#: kernel's per-program overhead outweighs its single-pass read below
#: ~1K positions). generate()'s capacity rounding and _decode_attn's
#: dispatch share this one gate.
MIN_KERNEL_LEN = 1024


def choose_block(total_len: int) -> int:
    """The L tile size for a cache serving ``total_len`` positions —
    big enough to amortize per-program overhead at depth, small enough
    that a short cache is not rounded far past its real length."""
    if total_len >= 4096:
        return 1024
    if total_len >= 1024:
        return 512
    return 128


def block_of(cache_len: int) -> Optional[int]:
    """The tile size to use for an existing cache length, or None when
    no candidate divides it (caller falls back to the einsum path)."""
    for bl in BLOCK_CANDIDATES:
        if cache_len % bl == 0 and cache_len >= bl:
            return bl
    return None


def _kernel(t_ref, *refs, scale: float, block_l: int, bh_block: int,
            window, quantized: bool):
    if quantized:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        ks_ref = vs_ref = None
    li = pl.program_id(1)
    nl = pl.num_programs(1)
    t = t_ref[0]

    @pl.when(li == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = li * block_l <= t
    if window is not None:
        run = jnp.logical_and(run,
                              li * block_l + block_l - 1 > t - window)

    @pl.when(run)
    def _compute():
        pos = li * block_l + lax.broadcasted_iota(
            jnp.int32, (1, block_l), 1)
        valid = pos <= t
        if window is not None:
            valid = jnp.logical_and(valid, pos > t - window)
        # unrolled per-(batch, head)-row loop: each j is one independent
        # online-softmax update — static Python unroll, bh_block copies
        for j in range(bh_block):
            q = q_ref[j]                               # [G, D]
            kblk = k_ref[j].astype(q.dtype) if quantized else k_ref[j]
            s = lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
                * scale
            if ks_ref is not None:
                s = s * ks_ref[j][None, :]             # dequant scores
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[j]
            l_prev = l_ref[j]
            acc_prev = acc_ref[j]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                     # [G, bl] f32
            m_ref[j] = m_new
            l_ref[j] = l_prev * alpha + jnp.sum(p, axis=-1,
                                                keepdims=True)
            if vs_ref is not None:
                p = p * vs_ref[j][None, :]             # dequant values
            vblk = v_ref[j].astype(q.dtype) if quantized else v_ref[j]
            acc_ref[j] = acc_prev * alpha + lax.dot_general(
                p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(li == nl - 1)
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[:] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def decode_attention(q, k, v, t, *, scale: Optional[float] = None,
                     window: Optional[int] = None,
                     k_scale=None, v_scale=None,
                     block_l: Optional[int] = None,
                     bh_block: int = 8,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """One-step cache attention. q: [BH, G, D]; k/v: [BH, L, D] (L a
    multiple of the chosen ``block_l``; positions > t are masked); t:
    scalar int32 current position. Returns [BH, G, D] f32.
    ``k_scale``/``v_scale`` ([BH, L] f32) mark an int8 cache."""
    bh, g, d = q.shape
    L = k.shape[1]
    if block_l is None:
        block_l = block_of(L)
        if block_l is None:
            raise ValueError(
                f"no supported tile size divides cache length {L}; size "
                "the cache with decode_attention.choose_block")
    if L % block_l:
        raise ValueError(
            f"cache length {L} must be a multiple of block_l {block_l}")
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        interpret = not backend_is_tpu()
    quantized = k_scale is not None
    # Mosaic tiling wants block second-to-last dims % 8 == 0: pad the G
    # row axis to 8 (zero rows cost nothing — the kernel is read-bound)
    g_orig = g
    if g % 8:
        q = jnp.pad(q, ((0, 0), (0, 8 - g % 8), (0, 0)))
        g = q.shape[1]
    # rows per program: amortizes per-program overhead; BH must divide.
    # Round 5 (advisor): validate up front (<=0 used to ZeroDivisionError)
    # and round non-divisors to the LARGEST divisor of bh <= bh_block —
    # the old halving loop silently degraded e.g. bh_block=6, bh=8 to 1,
    # losing the amortization the parameter exists for.
    bh_block = int(bh_block)
    if bh_block < 1:
        raise ValueError(f"bh_block must be >= 1, got {bh_block}")
    bh_block = max(d for d in range(1, min(bh, bh_block) + 1)
                   if bh % d == 0)
    grid = (bh // bh_block, L // block_l)
    kernel = functools.partial(_kernel, scale=float(scale),
                               block_l=int(block_l),
                               bh_block=int(bh_block), window=window,
                               quantized=quantized)
    in_specs = [
        pl.BlockSpec((bh_block, g, d), lambda b, li, *_: (b, 0, 0)),
        pl.BlockSpec((bh_block, block_l, d), lambda b, li, *_: (b, li, 0)),
        pl.BlockSpec((bh_block, block_l, d), lambda b, li, *_: (b, li, 0)),
    ]
    operands = [q, k, v]
    if quantized:
        in_specs += [
            pl.BlockSpec((bh_block, block_l), lambda b, li, *_: (b, li)),
            pl.BlockSpec((bh_block, block_l), lambda b, li, *_: (b, li)),
        ]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bh_block, g, d),
                               lambda b, li, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((bh_block, g, 1), jnp.float32),
            pltpu.VMEM((bh_block, g, 1), jnp.float32),
            pltpu.VMEM((bh_block, g, d), jnp.float32),
        ])
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, g, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="decode_attention", interpret=interpret,
    )(jnp.asarray(t, jnp.int32).reshape(1), *operands)
    return out[:, :g_orig]
