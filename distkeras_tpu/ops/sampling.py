"""Fused sampling epilogue for the serving decode step.

The unfused sampler (``models.decoding._sample_vec``) walks the
[S, V] logits several times at full vocab width: rank argsorts for
top-k, a sort + softmax + cumsum for the nucleus cut, then
``jax.random.categorical`` — each an [S, V] HBM round trip at real
vocab sizes. This module folds the mask construction, the nucleus
threshold and the draw into a single Pallas pass: XLA keeps the one
irreducible sort and the two prefix sums over it (Mosaic lowers no
``cumsum``), and the kernel consumes the temperature-scaled logits,
the top-k-masked sorted row, those prefix sums and an externally
drawn gumbel field, and emits the sampled token ids directly — the
masked logits and perturbed scores live only in VMEM.

Exactness contract (the reason the pieces factor this way):

  * ``jax.random.categorical(key, lf)`` IS
    ``argmax(lf + gumbel(key, lf.shape))`` — :func:`gumbel_noise`
    draws the SAME per-slot threefry gumbel field ``categorical``
    would, so sampling from externally drawn noise changes no bits of
    any request's token stream.
  * the reference path (off-TPU, or any misaligned shape) reuses
    ``decoding._masked_logits_vec`` — the exact mask program of the
    unfused sampler — so fused-vs-unfused is byte-identical on CPU by
    construction; ``tests/test_sampling_fused.py`` pins the kernel
    against it under ``interpret=True`` (the tier-1 oracle
    convention).
  * in-kernel masks mirror the unfused semantics exactly: rank top-k
    with stable lowest-index-first ties (reconstructed from the
    sorted row: ``count_above + tie_prefix_rank <= k``), the nucleus
    cut's exclusive-cumsum threshold over the top-k-masked sorted
    row, and first-index argmax for both the greedy and the gumbel
    winner.

Alignment: vocab % 128 (lane tiling); slot rows pad to 8. Gate:
``fused_supported`` (same backend convention as every Pallas-vs-XLA
fork — ``compat.backend_is_tpu`` or a test forcing interpreter mode);
``sample_epilogue`` takes the reference path where the gate is shut
(on record through ``compat.note_path``), so the engine enables
``fused_sampling`` unconditionally.
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.compat import backend_is_tpu, note_path
from distkeras_tpu.ops.attention import NEG_INF

#: slot-row tile (Mosaic second-to-last-dim rule)
BLOCK_S = 8

_FORCE_INTERPRET = False


@contextlib.contextmanager
def force_interpret():
    """Run the epilogue kernel in Pallas interpreter mode regardless
    of backend — the CPU test suite's hook."""
    global _FORCE_INTERPRET
    prev = _FORCE_INTERPRET
    _FORCE_INTERPRET = True
    try:
        yield
    finally:
        _FORCE_INTERPRET = prev


def fused_supported(vocab: int) -> bool:
    """Whether the epilogue kernel runs for this vocab width."""
    if not (_FORCE_INTERPRET or backend_is_tpu()):
        return False
    return vocab % 128 == 0


def gumbel_noise(keys, vocab: int) -> jnp.ndarray:
    """The per-slot gumbel field ``jax.random.categorical`` would draw
    internally: one threefry ``gumbel(key, (V,), f32)`` per slot key —
    bit-identical to ``vmap(categorical)(keys, lf)``'s noise, which is
    what makes the fused and unfused streams byte-identical."""
    return jax.vmap(
        lambda k: jax.random.gumbel(k, (vocab,), jnp.float32))(keys)


def _kernel(lf_ref, srt_ref, g_ref, tie_ref, excl_ref, t_ref, k_ref, p_ref,
            kth_ref, o_ref):
    lf = lf_ref[...]                     # [bs, V] temp-scaled f32
    srt_m = srt_ref[...]                 # [bs, V] top-k-masked sorted row
    g = g_ref[...]                       # [bs, V] gumbel
    temp = t_ref[...]                    # [bs, 1]
    kk = k_ref[...]                      # [bs, 1] i32
    p = p_ref[...]                       # [bs, 1]
    kth = kth_ref[...]                   # [bs, 1] k-th largest value
    v = lf.shape[-1]
    iota = lax.broadcasted_iota(jnp.int32, lf.shape, 1)

    # rank top-k, stable lowest-index-first ties: admit everything
    # above the k-th largest VALUE plus the leading tied indices up to
    # the remaining budget (``tie_ref``: inclusive prefix count of ties)
    n_gt = jnp.sum((lf > kth).astype(jnp.int32), axis=1, keepdims=True)
    keep_k = (kk <= 0) | (lf > kth) | (
        (lf == kth) & (n_gt + tie_ref[...] <= jnp.clip(kk, 1, v)))
    lfk = jnp.where(keep_k, lf, NEG_INF)

    # nucleus: ``excl_ref`` is the exclusive cumsum of the masked
    # sorted row's softmax; same boundary construction as the unfused
    # path
    thresh = jnp.min(jnp.where(excl_ref[...] < p, srt_m, jnp.inf), axis=1,
                     keepdims=True)
    lfm = jnp.where((p >= 1.0) | (lfk >= thresh), lfk, NEG_INF)

    # fused gumbel-argmax (== categorical) + greedy, first-index ties
    z = lfm + g
    zmax = jnp.max(z, axis=1, keepdims=True)
    samp = jnp.min(jnp.where(z == zmax, iota, v), axis=1)
    gmax = jnp.max(lf, axis=1, keepdims=True)
    greedy = jnp.min(jnp.where(lf == gmax, iota, v), axis=1)
    o_ref[...] = jnp.where(temp[:, 0] > 0.0, samp, greedy)[:, None]


def sample_epilogue(logits, temperature, top_k, top_p, gumbel, *,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Sampled token ids for one decode step: temperature scale,
    rank top-k, nucleus cut, gumbel draw, greedy override — one fused
    pass. ``gumbel`` comes from :func:`gumbel_noise` over the same
    per-slot keys the unfused sampler would consume. Falls back to the
    exact unfused mask program off-TPU or at misaligned vocab widths,
    so the output token stream never depends on which path ran."""
    from distkeras_tpu.models.decoding import _masked_logits_vec

    s, v = logits.shape
    if not fused_supported(v):
        note_path("sampling", f"xla_reference[vocab={v}]")
        lf = _masked_logits_vec(logits, temperature, top_k, top_p)
        sampled = jnp.argmax(lf + gumbel, axis=-1)
        return jnp.where(temperature > 0.0, sampled,
                         jnp.argmax(logits, axis=-1))
    note_path("sampling", "kernel")
    if interpret is None:
        interpret = not backend_is_tpu()
    lf = logits.astype(jnp.float32)
    safe_t = jnp.where(temperature > 0.0, temperature, 1.0)
    lf = lf / safe_t[:, None]
    srt = jnp.flip(jnp.sort(lf, axis=-1), axis=-1)   # the one XLA sort
    # The rank mask keeps exactly the k largest values (ties only
    # shuffle which INDEX survives, never the value multiset), so the
    # top-k-masked sorted row derives from the unmasked sort. The two
    # prefix sums over it stay in XLA: Mosaic has no cumsum
    # ("Unimplemented primitive in Pallas TPU lowering for
    # KernelType.TC: cumsum"), and XLA's is the one the unfused
    # sampler's boundary is defined by.
    kk = top_k.astype(jnp.int32)[:, None]
    kc = jnp.clip(kk, 1, v)
    kth = jnp.take_along_axis(srt, kc - 1, axis=1)
    tie_rank = jnp.cumsum((lf == kth).astype(jnp.int32), axis=1)
    srt_m = jnp.where(jnp.arange(v)[None, :] < jnp.where(kk <= 0, v, kc),
                      srt, NEG_INF)
    probs = jax.nn.softmax(srt_m, axis=-1)
    excl = jnp.cumsum(probs, axis=1) - probs
    sp = -(-s // BLOCK_S) * BLOCK_S
    pad = sp - s

    def prep(a, fill):
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                       constant_values=fill) if pad else a

    args = (prep(lf, NEG_INF), prep(srt_m, NEG_INF),
            prep(gumbel.astype(jnp.float32), 0.0),
            prep(tie_rank, 0), prep(excl, 0.0),
            prep(temperature.astype(jnp.float32)[:, None], 0.0),
            prep(kk, 0),
            prep(top_p.astype(jnp.float32)[:, None], 1.0),
            prep(kth, NEG_INF))
    out = pl.pallas_call(
        _kernel,
        grid=(sp // BLOCK_S,),
        in_specs=[
            *[pl.BlockSpec((BLOCK_S, v), lambda i: (i, 0))] * 5,
            *[pl.BlockSpec((BLOCK_S, 1), lambda i: (i, 0))] * 4,
        ],
        out_specs=pl.BlockSpec((BLOCK_S, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((sp, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="sample_epilogue", interpret=interpret,
    )(*args)
    return out[:s, 0]


def sample_tokens(logits, temperature, top_k, top_p, keys):
    """Drop-in replacement for ``decoding._sample_vec`` with per-slot
    keys: external gumbel + the fused epilogue. The serving engine's
    ``fused_sampling=True`` sampler."""
    g = gumbel_noise(keys, logits.shape[-1])
    return sample_epilogue(logits, temperature, top_k, top_p, g)
