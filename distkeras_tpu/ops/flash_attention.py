"""Flash attention as a Pallas TPU kernel (blockwise, online softmax).

Absent from the reference (no attention models; SURVEY §5.7) — this is the
TPU build's hot-op kernel for the long-context path. The forward pass never
materializes the ``[S, S]`` score matrix: the grid is
``(batch*heads, q_blocks, k_blocks)`` with the K axis innermost ("arbitrary"
= sequential on TPU), so exactly one ``[block_k, D]`` tile of K and V is
resident in VMEM at a time while the online-softmax carry (running max
``m``, normalizer ``l``, accumulator ``acc``) persists in VMEM scratch
across the K sweep. Causal q/k tiles above the diagonal skip their compute
via ``pl.when``. Sequence lengths that don't divide the block sizes are
zero-padded and the pad keys masked off.

The backward pass is in-kernel too (two Pallas kernels: dq sweeps K blocks
innermost; dk/dv sweeps Q blocks innermost, both recomputing probabilities
from the saved log-sum-exp with f32 VMEM accumulators) — the probability
tile never touches HBM. A blockwise XLA-scan backward is retained for
interpreter/CPU runs and as a cross-check oracle (``bwd="xla"``). The
round-5 record (``BENCH_r05.json``: an earlier backend and JAX, not
re-measured) put the 218M LM — B8 H16 S2048 D64 causal bf16, kernel
backward + BHSD layer path + tuned blocks — at 2.15x the fused-XLA
attention path end to end.

Under a mesh XLA cannot partition the kernel; :func:`partitioned` is
the trace-time scope ``SPMDTrainer`` opens so that it runs inside a
``shard_map``, one call per shard of batch and heads.

On non-TPU backends the kernel runs in Pallas interpreter mode (tests) or
falls back to the fused-XLA reference (``ops.attention``) for speed.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from distkeras_tpu.compat import backend_is_tpu, note_path, shard_map
from distkeras_tpu.ops.attention import (NEG_INF, causal_mask,
                                         dot_product_attention)

# Round-4 sweep on a v5e (causal bf16, fwd+bwd, BHSD; an earlier backend
# and JAX, not re-measured): 512/1024 beat 512/512 by ~10-15% at both
# S=2048 (B8 H16) and S=8192 (B2 H8). Score tile at 512x1024 f32 is 2 MB
# of VMEM, safe through D=256.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024

#: every kernel here: (batch*head, outer block) parallel, inner sweep
#: sequential (it carries the VMEM accumulators)
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _window_kblocks(block_q: int, block_k: int, nk: int,
                    window, nq: int) -> int:
    """Number of k-grid steps per q block under a sliding window: the
    reachable key span per q block is ``block_q + window - 1`` positions,
    so the k-axis grid shrinks from ``nk`` to O(window/block_k) — skipped
    tiles then never pay their K/V DMA (they are not in the grid at all),
    instead of being ``pl.when``-skipped compute with full-cost DMA.
    Computed as the EXACT trace-time maximum over q blocks (one fewer
    step than the closed form when window/block_q align to block_k)."""
    if window is None:
        return nk
    best = 1
    for qi in range(nq):
        last = min(nk - 1, (qi * block_q + block_q - 1) // block_k)
        first = max(0, (qi * block_q - window + 1) // block_k)
        best = max(best, last - first + 1)
    return min(nk, best)


def _k_base(qi, block_q: int, block_k: int, nkw: int):
    """First k block visited for q block ``qi`` (window remap): the last
    ``nkw`` blocks ending at the causal diagonal block, clamped at 0.
    Shared by the BlockSpec index maps and the kernels' position math."""
    end = (qi * block_q + block_q - 1) // block_k
    return jnp.maximum(0, end - (nkw - 1))


def _needs_mask(qi, kb, block_q: int, block_k: int, causal: bool,
                window, k_len: int, has_seg: bool):
    """Does the (qi, kb) tile intersect any mask edge? Returns Python
    ``True`` when masking is unconditionally required (segment ids are
    data-dependent), else a traced bool over the program ids. A causal
    tile is mask-free when every query position >= every key position
    (min q_pos >= max k_pos); a windowed tile when every key is within
    every query's reach; the pad mask only touches the final key block.
    """
    if has_seg:
        return True
    need = None
    if causal:
        need = qi * block_q < kb * block_k + block_k - 1
    if window is not None:
        w_edge = kb * block_k <= qi * block_q + block_q - 1 - window
        need = w_edge if need is None else (need | w_edge)
    if k_len % block_k:
        pad_edge = (kb + 1) * block_k > k_len
        need = pad_edge if need is None else (need | pad_edge)
    if need is None:
        return False        # non-causal, no window, no padding: clear
    return need


def _mask_dispatch(run, need, masked_fn, clear_fn):
    """Emit the masked and/or clear tile bodies under ``pl.when`` guards
    per ``_needs_mask``'s verdict (Python bool = one static body; traced
    bool = both bodies, selected per tile at run time)."""
    if need is True:
        pl.when(run)(masked_fn)
    elif need is False:
        pl.when(run)(clear_fn)
    else:
        pl.when(jnp.logical_and(run, need))(masked_fn)
        pl.when(jnp.logical_and(run, jnp.logical_not(need)))(clear_fn)


def _fwd_kernel(*refs, scale: float, causal: bool, k_len: int,
                window=None, nkw=None, has_seg: bool = False,
                block_len=None):
    """One (batch*head, q_block, k_block) program.

    Block shapes: q_ref [1, bq, D]; k_ref/v_ref [1, bk, D];
    o_ref [1, bq, D]; lse_ref [1, bq, 1] (the trailing singleton keeps the
    block's last-two dims Mosaic-tileable: (bq, 1) with bq % 8 == 0 and 1
    equal to the full array dim — a [1, bq] block fails TPU lowering).
    Scratch m/l [bq, 1], acc [bq, D] persist across the (sequential,
    innermost) k grid axis. Under a sliding window the k grid axis is
    REMAPPED: grid step ``ki`` addresses actual k block
    ``_k_base(qi) + ki`` (see ``_window_kblocks``). With ``has_seg``
    two extra [1, blk, 1] int32 refs carry packed segment ids; scores
    with unequal ids are masked (packed-sequence support).

    ``block_len`` (static; ``_flash_forward`` holds it to a divisor of
    both tile sizes) turns the causal mask into the BLOCK-causal one: a
    query sees every key up to the end of its own block of
    ``block_len`` positions. Tiles are whole blocks, so which tiles
    run and which need a mask is the causal rule unchanged; only the
    mask's comparison differs, and with ``block_len`` None the traced
    kernel is the causal kernel as it was.
    """
    if has_seg:
        (q_ref, k_ref, v_ref, qseg_ref, kseg_ref,
         o_ref, lse_ref, m_ref, l_ref, acc_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
        qseg_ref = kseg_ref = None
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    kb = ki if nkw is None else _k_base(qi, block_q, block_k, nkw) + ki

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # causal: tiles strictly above the diagonal contribute nothing;
    # sliding window: tiles entirely OLDER than any query's window start
    # contribute nothing either
    run = (kb * block_k <= qi * block_q + block_q - 1) if causal \
        else (kb >= 0)
    if window is not None:
        run = jnp.logical_and(
            run, kb * block_k + block_k - 1 > qi * block_q - window)

    def _scores():
        # matmul inputs stay in the STORED dtype (bf16 for bf16 models)
        # with f32 accumulation — the MXU's native mode. Upcasting inputs
        # to f32 forces multi-pass f32 matmuls (~3-6x slower); round 4
        # measured the f32-input kernel at ~22% MXU on v5e. Scale is
        # applied to the f32 scores, not the bf16 q, so no precision is
        # lost relative to the old `q.astype(f32) * scale` form.
        return lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32) * scale

    def _mask(s):
        q_pos = (qi * block_q +
                 lax.broadcasted_iota(jnp.int32, s.shape, 0))
        k_pos = (kb * block_k +
                 lax.broadcasted_iota(jnp.int32, s.shape, 1))
        if causal and block_len is not None:
            q_end = (q_pos // block_len) * block_len + (block_len - 1)
            s = jnp.where(q_end >= k_pos, s, NEG_INF)
        elif causal:
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if window is not None:
            s = jnp.where(k_pos > q_pos - window, s, NEG_INF)
        # mask zero-padded keys past the true sequence end
        if k_len % block_k:
            s = jnp.where(k_pos < k_len, s, NEG_INF)
        if qseg_ref is not None:
            same = qseg_ref[0, :, 0][:, None] == kseg_ref[0, :, 0][None, :]
            s = jnp.where(same, s, NEG_INF)
        return s

    def _merge(s):
        m_prev, l_prev, acc_prev = m_ref[:], l_ref[:], acc_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # p is cast to the value dtype for the PV matmul (f32 accumulate);
        # p in [0, 1] so bf16's relative precision bounds the elementwise
        # error at ~2^-8 of each probability — the flash-on-TPU standard
        acc_ref[:] = acc_prev * alpha + lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # tile-static mask specialization (round 4): the kernels are
    # VPU-bound, not MXU-bound (measured — the bf16-input change moved
    # nothing), so interior tiles skip the whole iota/compare/select
    # chain. A tile needs masking only if the causal diagonal, the
    # window's trailing edge, or the key padding actually intersects it
    # — a predicate of the program ids.
    need = _needs_mask(qi, kb, block_q, block_k, causal, window, k_len,
                       has_seg)
    _mask_dispatch(run, need,
                   lambda: _merge(_mask(_scores())),
                   lambda: _merge(_scores()))

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(l_safe)


def _pad_seq(x, block: int, axis: int = 1):
    s = x.shape[axis]
    pad = (-s) % block
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    return x


def _seg_blocks(segment_ids, sq_p: int, sk_p: int):
    """[B, S] int segment ids -> padded [B, S_p, 1] int32 q/k variants
    (pads get -1: they never match a real segment, and real ``-1``
    padding tokens only reach k pads when no k_len masking applies —
    harmless, those rows are loss-masked)."""
    seg = jnp.asarray(segment_ids, jnp.int32)
    b, s = seg.shape
    segq = jnp.pad(seg, ((0, 0), (0, sq_p - s)), constant_values=-1)
    segk = jnp.pad(seg, ((0, 0), (0, sk_p - s)), constant_values=-1)
    return segq[..., None], segk[..., None]


def _flash_forward(q, k, v, scale: float, causal: bool, block_q: int,
                   block_k: int, interpret: bool, bhsd: bool = False,
                   window=None, segment_ids=None, block_len=None):
    if bhsd:
        b, h, sq, d = q.shape
        sk = k.shape[2]
        seq_axis = 2
    else:
        b, sq, h, d = q.shape
        sk = k.shape[1]
        seq_axis = 1
    # the values' width may differ from the queries' and keys' (latent
    # attention: 192 and 128); the output is as wide as the values
    dv = v.shape[-1]
    # clamp to the (8-rounded) sequence length: Mosaic requires the block's
    # second-to-last dim % 8 == 0, so a raw min(block, seq) would fail to
    # lower for seq in (block, 8k) that isn't a multiple of 8 — the padder
    # below then pads seq up to the rounded block
    # (block-causal: tiles are whole blocks too, so round to both)
    unit = 8 if block_len is None else math.lcm(8, int(block_len))
    round8 = lambda n: max(unit, -(-n // unit) * unit)
    if block_len is not None:
        block_q, block_k = round8(block_q), round8(block_k)
    block_q = min(block_q, round8(sq))
    block_k = min(block_k, round8(sk))
    if block_len is not None and (not causal or window is not None
                                  or block_q % block_len
                                  or block_k % block_len or sq != sk):
        raise ValueError(
            f"block-causal flash attention needs causal=True, no window, "
            f"q and k of one length and tiles of whole blocks (block_len "
            f"{block_len}, tiles {block_q}/{block_k}, lengths {sq}/{sk})")
    qp = _pad_seq(q, block_q, seq_axis)
    kp = _pad_seq(k, block_k, seq_axis)
    vp = _pad_seq(v, block_k, seq_axis)
    sq_p, sk_p = qp.shape[seq_axis], kp.shape[seq_axis]

    if bhsd:
        # BHSD -> (B*H, S, D) is a FREE reshape (no data movement) — the
        # layout the layer uses when it targets this kernel
        qf = qp.reshape(b * h, sq_p, d)
        kf = kp.reshape(b * h, sk_p, d)
        vf = vp.reshape(b * h, sk_p, dv)
    else:
        # BSHD -> (B*H, S, D): one grid row per (batch, head)
        qf = qp.transpose(0, 2, 1, 3).reshape(b * h, sq_p, d)
        kf = kp.transpose(0, 2, 1, 3).reshape(b * h, sk_p, d)
        vf = vp.transpose(0, 2, 1, 3).reshape(b * h, sk_p, dv)

    nk = sk_p // block_k
    nkw = _window_kblocks(block_q, block_k, nk, window,
                          sq_p // block_q)
    remap = nkw < nk
    grid = (b * h, sq_p // block_q, nkw)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               k_len=sk, window=window,
                               nkw=nkw if remap else None,
                               has_seg=segment_ids is not None,
                               block_len=block_len)

    def k_map(bh, qi, ki):
        if remap:
            return (bh, _k_base(qi, block_q, block_k, nkw) + ki, 0)
        return (bh, ki, 0)

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, block_k, d), k_map),
        pl.BlockSpec((1, block_k, dv), k_map),
    ]
    operands = [qf, kf, vf]
    if segment_ids is not None:
        segq, segk = _seg_blocks(segment_ids, sq_p, sk_p)
        # segment ids are per-BATCH: block index maps divide the b*h grid
        # row back down to the batch row
        in_specs += [
            pl.BlockSpec((1, block_q, 1),
                         lambda bh, qi, ki: (bh // h, qi, 0)),
            pl.BlockSpec((1, block_k, 1),
                         lambda bh, qi, ki: (bh // h,) + k_map(bh, qi,
                                                               ki)[1:]),
        ]
        operands += [segq, segk]
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq_p, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        name="flash_fwd", interpret=interpret,
    )(*operands)
    if bhsd:
        out = out.reshape(b, h, sq_p, dv)[:, :, :sq]
    else:
        out = out.reshape(b, h, sq_p, dv).transpose(0, 2, 1, 3)[:, :sq]
    lse = lse.reshape(b, h, sq_p)[:, :, :sq]
    return out, lse


def _bwd_dq_kernel(*refs, scale: float, causal: bool, k_len: int,
                   window=None, nkw=None, has_seg: bool = False):
    """dq pass: one (batch*head, q_block, k_block) program, K innermost.
    ``dq_acc`` [bq, D] f32 persists across the K sweep. Window remap as
    in ``_fwd_kernel``; ``has_seg`` adds packed-segment masking."""
    if has_seg:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, qseg_ref,
         kseg_ref, dq_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref,
         dq_acc) = refs
        qseg_ref = kseg_ref = None
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    kb = ki if nkw is None else _k_base(qi, block_q, block_k, nkw) + ki

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = (kb * block_k <= qi * block_q + block_q - 1) if causal \
        else (kb >= 0)
    if window is not None:
        run = jnp.logical_and(
            run, kb * block_k + block_k - 1
            > qi * block_q - window)

    def _mask(s):
        q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = kb * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if causal:
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if window is not None:
            s = jnp.where(k_pos > q_pos - window, s, NEG_INF)
        if k_len % block_k:
            s = jnp.where(k_pos < k_len, s, NEG_INF)
        if qseg_ref is not None:
            same = qseg_ref[0, :, 0][:, None] == kseg_ref[0, :, 0][None, :]
            s = jnp.where(same, s, NEG_INF)
        return s

    def _compute(mask):
        # bf16 matmul inputs + f32 accumulation throughout (see
        # _fwd_kernel); scale folds into the f32 score/grad tensors
        s = lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if mask:
            s = _mask(s)
        p = jnp.exp(s - lse_ref[0])                        # [bq, bk]
        dp = lax.dot_general(g_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0])).astype(k_ref.dtype)
        dq_acc[:] += lax.dot_general(
            ds, k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    need = _needs_mask(qi, kb, block_q, block_k, causal, window, k_len,
                       qseg_ref is not None)
    _mask_dispatch(run, need,
                   lambda: _compute(True), lambda: _compute(False))

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _window_qblocks(block_q: int, block_k: int, nq: int,
                    window, nk: int) -> int:
    """Mirror of ``_window_kblocks`` for the dk/dv pass: the reachable
    query span per k block is ``block_k + window - 1`` positions. Exact
    trace-time maximum over k blocks."""
    if window is None:
        return nq
    best = 1
    for ki in range(nk):
        first = min(nq - 1, (ki * block_k) // block_q)
        last = min(nq - 1,
                   (ki * block_k + block_k - 1 + window - 1) // block_q)
        best = max(best, last - first + 1)
    return min(nq, best)


def _q_base(ki, block_q: int, block_k: int, nq: int, nqw: int):
    """First q block visited for k block ``ki`` (window remap). Clamped
    from ABOVE to ``nq - nqw`` so every program stays in range without
    any q block appearing twice in one sweep (a double-visit would
    double-count its dk/dv contribution)."""
    return jnp.minimum((ki * block_k) // block_q, nq - nqw)


def _bwd_dkv_kernel(*refs, scale: float, causal: bool, k_len: int,
                    window=None, nq=None, nqw=None, has_seg: bool = False):
    """dk/dv pass: one (batch*head, k_block, q_block) program, Q innermost.
    ``dk_acc``/``dv_acc`` [bk, D] f32 persist across the Q sweep. Window
    remap: grid step ``qi`` addresses actual q block ``_q_base(ki) + qi``."""
    if has_seg:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, qseg_ref,
         kseg_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dk_ref, dv_ref,
         dk_acc, dv_acc) = refs
        qseg_ref = kseg_ref = None
    ki, qi = pl.program_id(1), pl.program_id(2)
    block_k, block_q = k_ref.shape[1], q_ref.shape[1]
    qb = qi if nqw is None else _q_base(ki, block_q, block_k, nq, nqw) + qi

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # causal: q tiles entirely above the diagonal see none of this k
    # block; sliding window: q tiles entirely NEWER than every key's
    # window reach see none of it either
    run = (qb * block_q + block_q - 1 >= ki * block_k) if causal \
        else (qb >= 0)
    if window is not None:
        run = jnp.logical_and(
            run, qb * block_q
            < ki * block_k + block_k - 1 + window)

    def _mask(s):
        q_pos = qb * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = ki * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if causal:
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if window is not None:
            s = jnp.where(k_pos > q_pos - window, s, NEG_INF)
        if k_len % block_k:
            s = jnp.where(k_pos < k_len, s, NEG_INF)
        if qseg_ref is not None:
            same = qseg_ref[0, :, 0][:, None] == kseg_ref[0, :, 0][None, :]
            s = jnp.where(same, s, NEG_INF)
        return s

    def _compute(mask):
        # bf16 matmul inputs + f32 accumulation (see _fwd_kernel); the
        # dk contribution applies scale to the f32 accumulator instead of
        # pre-scaling q (dot(ds, q*scale) == scale * dot(ds, q))
        s = lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if mask:
            s = _mask(s)
        p = jnp.exp(s - lse_ref[0])                        # [bq, bk]
        dv_acc[:] += lax.dot_general(
            p.astype(g_ref.dtype), g_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(g_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0])).astype(q_ref.dtype)
        dk_acc[:] += lax.dot_general(
            ds, q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    need = _needs_mask(qb, ki, block_q, block_k, causal, window, k_len,
                       qseg_ref is not None)
    _mask_dispatch(run, need,
                   lambda: _compute(True), lambda: _compute(False))

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward_pallas(res, g, scale: float, causal: bool,
                           block_q: int, block_k: int, interpret: bool,
                           bhsd: bool = False, window=None):
    """In-kernel backward: the [bq, bk] probability tile lives only in
    VMEM; f32 accumulators carry across the sequential grid axis."""
    q, k, v, out, lse, segment_ids = res
    if bhsd:
        b, h, sq, d = q.shape
        sk = k.shape[2]
        seq_axis = 2
    else:
        b, sq, h, d = q.shape
        sk = k.shape[1]
        seq_axis = 1
    round8 = lambda n: max(8, -(-n // 8) * 8)
    block_q = min(block_q, round8(sq))
    block_k = min(block_k, round8(sk))
    qp, gp = _pad_seq(q, block_q, seq_axis), _pad_seq(g, block_q, seq_axis)
    kp, vp = _pad_seq(k, block_k, seq_axis), _pad_seq(v, block_k, seq_axis)
    sq_p, sk_p = qp.shape[seq_axis], kp.shape[seq_axis]

    # delta_i = rowsum(dO * O) (flash trick); pad rows contribute zeros
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                   # [B, Sq, H] or [B, H, Sq]
    deltaf = (delta if bhsd else delta.transpose(0, 2, 1)) \
        .reshape(b * h, sq, 1)
    lsef = lse.reshape(b * h, sq, 1)
    pad_q = sq_p - sq
    if pad_q:
        deltaf = jnp.pad(deltaf, ((0, 0), (0, pad_q), (0, 0)))
        # pad lse with zeros: padded q rows have g = 0, so p's garbage
        # rows multiply into zero contributions everywhere
        lsef = jnp.pad(lsef, ((0, 0), (0, pad_q), (0, 0)))

    if bhsd:
        to_flat = lambda x: x.reshape(b * h, x.shape[2], d)  # free
    else:
        to_flat = lambda x: x.transpose(0, 2, 1, 3).reshape(
            b * h, x.shape[1], d)
    qf, kf, vf, gf = to_flat(qp), to_flat(kp), to_flat(vp), to_flat(gp)

    nq, nk = sq_p // block_q, sk_p // block_k
    nkw = _window_kblocks(block_q, block_k, nk, window, nq)
    nqw = _window_qblocks(block_q, block_k, nq, window, nk)
    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0))

    def k_map(bh, qi, ki):
        if nkw < nk:
            return (bh, _k_base(qi, block_q, block_k, nkw) + ki, 0)
        return (bh, ki, 0)

    k_spec = pl.BlockSpec((1, block_k, d), k_map)
    row_q = pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0))
    in_specs = [q_spec, k_spec, k_spec, q_spec, row_q, row_q]
    operands = [qf, kf, vf, gf, lsef, deltaf]
    if segment_ids is not None:
        segq, segk = _seg_blocks(segment_ids, sq_p, sk_p)
        in_specs += [
            pl.BlockSpec((1, block_q, 1),
                         lambda bh, qi, ki: (bh // h, qi, 0)),
            pl.BlockSpec((1, block_k, 1),
                         lambda bh, qi, ki: (bh // h,) + k_map(bh, qi,
                                                               ki)[1:]),
        ]
        operands += [segq, segk]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          k_len=sk, window=window,
                          nkw=nkw if nkw < nk else None,
                          has_seg=segment_ids is not None),
        grid=(b * h, nq, nkw),
        in_specs=in_specs,
        out_specs=[q_spec],
        out_shape=[jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        name="flash_bwd_dq", interpret=interpret,
    )(*operands)[0]

    # second pass: k blocks parallel, q innermost (window-remapped)
    def q_map2(bh, ki, qi):
        if nqw < nq:
            return (bh, _q_base(ki, block_q, block_k, nq, nqw) + qi, 0)
        return (bh, qi, 0)

    q_spec2 = pl.BlockSpec((1, block_q, d), q_map2)
    k_spec2 = pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0))
    row_q2 = pl.BlockSpec((1, block_q, 1), q_map2)
    in_specs2 = [q_spec2, k_spec2, k_spec2, q_spec2, row_q2, row_q2]
    operands2 = [qf, kf, vf, gf, lsef, deltaf]
    if segment_ids is not None:
        segq, segk = _seg_blocks(segment_ids, sq_p, sk_p)
        in_specs2 += [
            pl.BlockSpec((1, block_q, 1),
                         lambda bh, ki, qi: (bh // h,) + q_map2(bh, ki,
                                                                qi)[1:]),
            pl.BlockSpec((1, block_k, 1),
                         lambda bh, ki, qi: (bh // h, ki, 0)),
        ]
        operands2 += [segq, segk]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          k_len=sk, window=window,
                          nq=nq if nqw < nq else None,
                          nqw=nqw if nqw < nq else None,
                          has_seg=segment_ids is not None),
        grid=(b * h, nk, nqw),
        in_specs=in_specs2,
        out_specs=[k_spec2, k_spec2],
        out_shape=[jax.ShapeDtypeStruct((b * h, sk_p, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, sk_p, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        name="flash_bwd_dkv", interpret=interpret,
    )(*operands2)

    if bhsd:
        unflat = lambda x, s: x.reshape(b, h, x.shape[1], d)[:, :, :s]
    else:
        unflat = lambda x, s: x.reshape(b, h, x.shape[1], d) \
            .transpose(0, 2, 1, 3)[:, :s]
    return unflat(dq, sq), unflat(dk, sk), unflat(dv, sk)


def _flash_backward(res, g, scale: float, causal: bool, block_k: int,
                    window=None):
    """Blockwise XLA backward: scan over K/V blocks, recompute P from lse."""
    q, k, v, out, lse, segment_ids = res
    b, sq, h, d = q.shape
    sk = k.shape[1]
    block_k = min(block_k, sk)
    pad = (-sk) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    seg = None
    if segment_ids is not None:
        seg = jnp.pad(jnp.asarray(segment_ids, jnp.int32),
                      ((0, 0), (0, pad)), constant_values=-1)

    qf = q.astype(jnp.float32) * scale
    g32 = g.astype(jnp.float32)
    # delta_i = sum_j P_ij dP_ij = rowsum(dO * O)  (flash attention trick)
    delta = jnp.sum(g32 * out.astype(jnp.float32), axis=-1)   # [B, Sq, H]

    nkb = (sk + pad) // block_k

    def body(dq_acc, kb):
        ks = lax.dynamic_slice_in_dim(k, kb * block_k, block_k, axis=1)
        vs = lax.dynamic_slice_in_dim(v, kb * block_k, block_k, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, ks.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        allowed = causal_mask(sq, block_k, k_offset=kb * block_k) \
            if causal else True
        if window is not None:
            q_pos = jnp.arange(sq)[:, None]
            k_pos = (kb * block_k + jnp.arange(block_k))[None, :]
            allowed = jnp.logical_and(allowed, k_pos > q_pos - window)
        k_valid = (kb * block_k + jnp.arange(block_k)) < sk
        mask = jnp.logical_and(allowed, k_valid[None, :]) if causal \
            else k_valid[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
        if seg is not None:
            ksg = lax.dynamic_slice_in_dim(seg, kb * block_k, block_k,
                                           axis=1)
            same = seg[:, :sq, None] == ksg[:, None, :]     # [B, Sq, bk]
            s = jnp.where(same[:, None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                       # [B,H,Sq,bk]
        dv = jnp.einsum("bhqk,bqhd->bkhd", p, g32,
                        preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqhd,bkhd->bhqk", g32, vs.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta.transpose(0, 2, 1)[..., None])   # [B,H,Sq,bk]
        dq_blk = jnp.einsum("bhqk,bkhd->bqhd", ds, ks.astype(jnp.float32),
                            preferred_element_type=jnp.float32) * scale
        dk = jnp.einsum("bhqk,bqhd->bkhd", ds, qf,
                        preferred_element_type=jnp.float32)
        return dq_acc + dq_blk, (dk, dv)

    dq, (dks, dvs) = lax.scan(body, jnp.zeros(q.shape, jnp.float32),
                              jnp.arange(nkb))
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(b, sk + pad, h, d)[:, :sk]
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(b, sk + pad, h, d)[:, :sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, segment_ids, scale, causal, block_q, block_k,
           interpret, bwd, bhsd, window):
    out, _ = _flash_forward(q, k, v, scale, causal, block_q, block_k,
                            interpret, bhsd, window, segment_ids)
    return out


def _flash_fwd_rule(q, k, v, segment_ids, scale, causal, block_q, block_k,
                    interpret, bwd, bhsd, window):
    out, lse = _flash_forward(q, k, v, scale, causal, block_q, block_k,
                              interpret, bhsd, window, segment_ids)
    return out, (q, k, v, out, lse, segment_ids)


def _flash_bwd_rule(scale, causal, block_q, block_k, interpret, bwd, bhsd,
                    window, res, g):
    # segment ids are integer routing data: their cotangent is float0
    seg = res[5]
    dseg = None if seg is None else np.zeros(seg.shape, jax.dtypes.float0)
    if bwd == "pallas":
        dq, dk, dv = _flash_backward_pallas(res, g, scale, causal, block_q,
                                            block_k, interpret, bhsd,
                                            window)
        return dq, dk, dv, dseg
    if bhsd:
        # the scan-backward oracle is written for BSHD; convert around it
        t = lambda x: x.transpose(0, 2, 1, 3)
        q, k, v, out, lse, segment_ids = res
        dq, dk, dv = _flash_backward(
            (t(q), t(k), t(v), t(out), lse, segment_ids),
            t(g), scale, causal, block_k, window)
        return t(dq), t(dk), t(dv), dseg
    dq, dk, dv = _flash_backward(res, g, scale, causal, block_k, window)
    return dq, dk, dv, dseg


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


#: (mesh, batch_axes, head_axis) while a GSPMD trainer traces its step
_PARTITION = contextvars.ContextVar("flash_partition", default=None)


@contextlib.contextmanager
def partitioned(mesh, batch_axes: Sequence[str],
                head_axis: Optional[str] = None):
    """Trace-time scope for programs that GSPMD partitions over
    ``mesh`` (``SPMDTrainer``). XLA cannot split a Mosaic kernel
    ("Mosaic kernels cannot be automatically partitioned. Please wrap
    the call in a shard_map"), so inside this scope
    :func:`flash_attention` runs the kernel under a ``shard_map``: one
    call per device on its shard of the batch (``batch_axes``, the
    trainer's data axes) and of the heads (``head_axis``, its
    tensor-parallel axis). Attention is independent across both, so no
    collective is added."""
    token = _PARTITION.set((mesh, tuple(batch_axes), head_axis))
    try:
        yield
    finally:
        _PARTITION.reset(token)


def _partition_spec(batch: int, heads: int, bhsd: bool):
    """The q/k/v PartitionSpec under the active :func:`partitioned`
    scope, or None outside one (or on a one-device mesh). An axis the
    dimension does not divide over is left replicated — the same rule
    ``parallel.sharding`` applies to the parameters, so the kernel's
    shards line up with what GSPMD already placed."""
    scope = _PARTITION.get()
    if scope is None:
        return None
    mesh, batch_axes, head_axis = scope
    if mesh.size == 1:
        return None
    batch_axes = tuple(a for a in batch_axes if a in mesh.shape)
    if batch % math.prod(mesh.shape[a] for a in batch_axes):
        batch_axes = ()
    if head_axis not in mesh.shape or heads % mesh.shape[head_axis]:
        head_axis = None
    b = batch_axes or None
    return mesh, b, (P(b, head_axis, None, None) if bhsd
                     else P(b, None, head_axis, None))


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    bwd: Optional[str] = None,
                    layout: str = "bshd",
                    window: Optional[int] = None,
                    segment_ids: Optional[jnp.ndarray] = None,
                    block_len: Optional[int] = None
                    ) -> jnp.ndarray:
    """Flash attention, BSHD in/out by default. Differentiable (custom
    VJP). ``layout="bhsd"`` takes/returns [B, H, S, D] — the kernel's
    native flattening is then a free reshape instead of four
    [B,S,H,D]<->[B,H,S,D] transposes per call (the layer's flash path
    produces BHSD directly for exactly this reason).

    ``interpret=None`` auto-selects: real kernel on TPU, interpreter mode
    elsewhere (falling back to the fused-XLA reference for big shapes or
    when ``interpret=False`` is forced off-TPU, where Mosaic can't lower).

    ``bwd``: ``"pallas"`` (in-kernel backward — the TPU default) or
    ``"xla"`` (blockwise-scan recomputation — the interpreter default,
    since interpreted kernels are slow on CPU; also the cross-check
    oracle for the kernel backward's numerics).

    ``block_q``/``block_k`` default adaptively: 512/1024 for full
    attention, except 1024/1024 at exactly d_head 128 causal (both
    measured optima — module header and the round-5 D=128 sweep),
    512/512 under a sliding ``window`` at every d_head — the remapped
    k-grid covers ``~window + block_q + block_k`` keys per q block, so
    the smaller blocks tighten coverage (measured: W=1024 S=8192
    fwd+bwd 1.80x full-causal at 512/512 vs 1.44x at 1024/1024 on
    v5e).

    ``segment_ids``: [B, S] int — packed-sequence masking (attention
    restricted to equal ids) through every path: forward, both Pallas
    backward kernels, the XLA-scan backward, and the fused-XLA fallback.
    See ``ops.attention.dot_product_attention`` for the convention.

    ``block_len``: block-causal attention (a query sees every key up
    to the end of its own block of ``block_len`` positions; needs
    ``causal``, no window, no segment ids). FORWARD ONLY — it is the
    serving prefill of a block-diffusion model and bypasses the custom
    VJP; the causal kernels and their backward are untouched by it.
    """
    if layout not in ("bshd", "bhsd"):
        raise ValueError(f"layout must be 'bshd' or 'bhsd', got {layout!r}")
    if block_q is None:
        # d_head == 128 prefers the square 1024 tile for FULL causal
        # attention: measured fwd+bwd at B4 H16 S2048 D128 (the lm_big
        # shape, round 5) — 1024/1024 4.58 ms vs the d64-tuned 512/1024
        # default's 6.05 (24% faster; 512/512 5.10, 2048-sized tiles
        # fail to compile). Deliberately NARROW: exactly d_head 128 and
        # causal — D=256 would double the measured VMEM footprint into
        # the range that failed to compile at D=128, and non-causal
        # shapes were not swept; both keep the 512/1024 default
        # (documented safe through D=256). WINDOWED attention keeps
        # 512/512 at every d_head — its remapped k-grid covers
        # ~window + block_q + block_k keys per q block, and the bigger
        # q tile widens exactly the overscan 512/512 was measured to
        # avoid.
        block_q = 1024 if (q.shape[-1] == 128 and causal
                           and window is None
                           and segment_ids is None) else DEFAULT_BLOCK_Q
    if block_k is None:
        block_k = DEFAULT_BLOCK_K if window is None else DEFAULT_BLOCK_Q
    bhsd = layout == "bhsd"
    seq_axis = 2 if bhsd else 1
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if window is not None:
        window = int(window)
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if not causal:
            raise ValueError("window requires causal=True")

    if block_len is not None and (not causal or window is not None
                                  or segment_ids is not None):
        raise ValueError("block_len requires causal=True and neither a "
                         "window nor segment ids")

    def _xla_fallback():
        note_path("flash_attention", "xla_reference")
        if bhsd:
            t = lambda x: x.transpose(0, 2, 1, 3)
            return t(dot_product_attention(t(q), t(k), t(v), causal=causal,
                                           scale=scale, window=window,
                                           segment_ids=segment_ids,
                                           block_len=block_len))
        return dot_product_attention(q, k, v, causal=causal, scale=scale,
                                     window=window,
                                     segment_ids=segment_ids,
                                     block_len=block_len)

    on_tpu = backend_is_tpu()
    if interpret is None:
        interpret = not on_tpu
        if interpret and q.shape[seq_axis] * k.shape[seq_axis] > 256 * 256:
            # interpreter is too slow for big shapes; use the XLA reference
            return _xla_fallback()
    if not on_tpu and not interpret:
        return _xla_fallback()
    if bwd is None:
        bwd = "pallas" if not interpret else "xla"
    if bwd not in ("pallas", "xla"):
        raise ValueError(f"bwd must be 'pallas' or 'xla', got {bwd!r}")
    note_path("flash_attention", "interpreted_kernel" if interpret
              else "kernel")
    if block_len is not None:
        return _flash_forward(q, k, v, scale, True, block_q, block_k,
                              interpret, bhsd, block_len=block_len)[0]
    if v.shape[-1] != q.shape[-1]:
        # values of another width than queries and keys (latent
        # attention's prefill): FORWARD ONLY, past the custom VJP, whose
        # backward kernels take one width
        if segment_ids is not None:
            raise ValueError("values of another width than the keys take "
                             "no segment ids")
        return _flash_forward(q, k, v, scale, causal, block_q, block_k,
                              interpret, bhsd, window)[0]
    kernel = functools.partial(
        _flash, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret, bwd=bwd, bhsd=bhsd,
        window=window)
    part = _partition_spec(q.shape[0], q.shape[1 if bhsd else 2], bhsd)
    if part is None:
        return kernel(q, k, v, segment_ids)
    mesh, batch_axes, spec = part
    seg_spec = None if segment_ids is None else P(batch_axes, None)
    return shard_map(kernel, mesh=mesh, in_specs=(spec,) * 3 + (seg_spec,),
                     out_specs=spec)(q, k, v, segment_ids)
