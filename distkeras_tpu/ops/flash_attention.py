"""Flash attention as a Pallas TPU kernel (blockwise, online softmax).

Absent from the reference (no attention models; SURVEY §5.7) — this is the
TPU build's hot-op kernel for the long-context path. The forward pass never
materializes the ``[S, S]`` score matrix: the grid is
``(batch*heads, live tiles)``, the second axis sequential ("arbitrary"),
each step one ``[block_q, block_k]`` tile whose q and k blocks a
scalar-prefetched table names (:func:`_grid_table`); a q block's k tiles
come in order while the online-softmax carry (running max ``m``,
normalizer ``l``, accumulator ``acc``) persists in VMEM scratch. The
table holds only the tiles the mask keeps anything of: a causal tile above
the diagonal, or one older than every query's sliding window, costs no
grid step and no DMA. Sequence lengths that don't divide the block sizes
are zero-padded and the pad keys masked off.

What each tile computes (PR 38): a tile no mask edge crosses runs one
unmasked body. For a plain causal call (no window, segment ids or
``block_len``) a tile the diagonal crosses is done in pieces
(:func:`_diag_plan`, :func:`_diag_rows`, :func:`_diag_cols`): runs of at
most ``SUB_BLOCK_FWD`` query rows forward (``SUB_BLOCK_BWD`` rows in the
dq pass, keys in the dk/dv pass), each an unmasked product over the keys
every row of the run sees and a masked one over the sub-block the
diagonal crosses; keys above the diagonal are not multiplied. At the
train cell's S 2048 (1024 tiles) a (batch, head) computes 2.62M scores
and masks 1.05M forward, 2.36M and 0.52M in each backward pass, where
the whole-tile kernels (512/1024) computed 3.15M and masked 2.10M in
each. Windowed, packed and block-causal calls, and a
grid of one tile, mask each tile the mask crosses whole, as before.
``compat.note_path`` records ``flash_causal=live<steps>of<dense
grid>,sub<fwd>/<bwd>`` for every plain causal call.

The backward pass is in-kernel too (two Pallas kernels: dq sweeps a q
block's k tiles; dk/dv a k block's q tiles, both recomputing probabilities
from the saved log-sum-exp with f32 VMEM accumulators) — the probability
tile never touches HBM. A blockwise XLA-scan backward is retained for
interpreter/CPU runs and as a cross-check oracle (``bwd="xla"``).

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

# Tiles of every call but a causal one up to d_head 128 (which takes
# 1024/1024, see flash_attention): 512/1024 beat 512/512 by ~10-15% in
# the round-4 sweep on a v5e (causal bf16, fwd+bwd, BHSD, at S=2048 B8
# H16 and S=8192 B2 H8) — an earlier backend and JAX, and the whole-tile
# kernels before PR 38; not re-measured for those calls. Score tile at
# 512x1024 f32 is 2 MB of VMEM, safe through D=256.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024

#: the most query rows of a diagonal tile's sub-blocks in the forward
#: pass, and the most query rows (dq) or keys (dk/dv) in the backward
#: passes: measured on a v5e at the train cell's 1024 tiles (PERF.md §6
#: PR 38), below 512 a forward piece costs more than the work it skips,
#: and above 256 a backward one skips too little
SUB_BLOCK_FWD = 512
SUB_BLOCK_BWD = 256

#: a plain causal call gets one static sub-block schedule per distinct
#: offset of its diagonal tiles; past this many it masks them whole
_MAX_DIAG_OFFSETS = 8

#: every kernel here: (batch*head) parallel, the live-tile steps
#: sequential (they carry the VMEM accumulators)
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _tile_live(qi: int, kb: int, block_q: int, block_k: int, causal: bool,
               window) -> bool:
    """Does tile (q block ``qi``, k block ``kb``) hold a score the mask
    keeps? Causal: not wholly above the diagonal; sliding window: not
    wholly older than every query's window start."""
    q0, k0 = qi * block_q, kb * block_k
    if causal and k0 > q0 + block_q - 1:
        return False
    return window is None or k0 + block_k - 1 > q0 - window


def _grid_table(nq: int, nk: int, block_q: int, block_k: int, causal: bool,
                window, kmajor: bool = False) -> np.ndarray:
    """One kernel's grid as the live tiles it visits, in order: a flat
    int32 table of (q block, k block, edge) a step, scalar-prefetched
    into SMEM and read by the block index maps and the kernel. A tile
    the mask wholly discards is not in it, so it costs neither a grid
    step nor a DMA. The outer block (q for the forward and dq passes, k
    for dk/dv) is swept with its live inner blocks ascending; ``edge``
    bit 1 marks the outer block's first step (init), bit 2 its last
    (finalize). An outer block with no live tile keeps one step that
    computes nothing, so its output is still written."""
    n_out, n_in = (nk, nq) if kmajor else (nq, nk)
    rows = []
    for o in range(n_out):
        tiles = [(i, o) if kmajor else (o, i) for i in range(n_in)]
        live = [t for t in tiles
                if _tile_live(*t, block_q, block_k, causal, window)]
        live = live or tiles[:1]
        for j, (qi, kb) in enumerate(live):
            rows += [qi, kb, (j == 0) + 2 * (j == len(live) - 1)]
    return np.asarray(rows, np.int32)


def _diag_plan(tab: np.ndarray, nq: int, nk: int, block_q: int,
               block_k: int, plain_causal: bool):
    """How a plain causal call (no window, segment ids or ``block_len``;
    queries no longer than keys, so the causal mask also hides every
    zero-padded key from every real query) computes the tiles the
    diagonal crosses: ``(offsets, sub_fwd, sub_bwd)`` — a static
    schedule (:func:`_diag_rows`, :func:`_diag_cols`) for each distinct
    offset (query start minus key start) such a tile has in ``tab``,
    with sub-blocks of at most ``SUB_BLOCK_FWD`` / ``SUB_BLOCK_BWD``
    rows and at most half the smaller tile — or None: the whole tile is
    masked, as it is where the grid is ONE tile (measured: a lone
    tile's pieces have no other tile's work to hide behind). Notes
    ``flash_causal=live<steps>of<dense grid>,sub<fwd>/<bwd>`` (or
    ``,whole``) for every plain causal call."""
    if not plain_causal:
        return None
    steps = tab.reshape(-1, 3)
    offs = sorted({o for o in (int(qi) * block_q - int(kb) * block_k
                               for qi, kb, _ in steps)
                   if -block_q < o < block_k - 1})
    # sub-blocks are a multiple of 8 rows (Mosaic's sublane tiling)
    half = max(8, min(block_q, block_k) // 16 * 8)
    subs = (min(SUB_BLOCK_FWD, half), min(SUB_BLOCK_BWD, half))
    ok = (nq * nk > 1 and len(offs) <= _MAX_DIAG_OFFSETS
          and block_q % 8 == 0 and block_k % 8 == 0)
    note_path("flash_causal", f"live{len(steps)}of{nq * nk},"
              + ("sub%d/%d" % subs if ok else "whole"))
    return (tuple(offs),) + subs if ok else None


def _diag_rows(off: int, block_q: int, block_k: int, sub: int):
    """Forward / dq schedule of a diagonal tile whose queries start
    ``off`` positions after its keys: ``(r0, r1, c_a, c_b)`` per run of
    ``sub`` query rows that sees any key — keys ``[0, c_a)`` are seen by
    every row (no mask), ``[c_a, c_b)`` is the sub-block the diagonal
    crosses (masked), keys from ``c_b`` on by no row (skipped)."""
    out = []
    for r0 in range(0, block_q, sub):
        r1 = min(block_q, r0 + sub)
        c_b = min(block_k, off + r1)
        if c_b > 0:
            out.append((r0, r1, min(c_b, max(0, off + r0)), c_b))
    return out


def _diag_cols(off: int, block_q: int, block_k: int, sub: int):
    """dk/dv schedule of the same tile: ``(c0, c1, r_a, r_b)`` per run of
    ``sub`` keys that any query sees — query rows ``[r_a, r_b)`` are the
    sub-block the diagonal crosses (masked), rows from ``r_b`` on see
    every key of the run (no mask), rows before ``r_a`` none."""
    out = []
    for c0 in range(0, block_k, sub):
        c1 = min(block_k, c0 + sub)
        r_a = max(0, c0 - off)
        if r_a < block_q:
            out.append((c0, c1, r_a, min(block_q, max(r_a, c1 - off))))
    return out


def _diag_mask(s, d: int):
    """Causal mask of a sub-block whose first query sits ``d`` positions
    after its first key: entry (i, j) is kept iff ``d + i >= j``."""
    i = lax.broadcasted_iota(jnp.int32, s.shape, 0)
    j = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(i + d >= j, s, NEG_INF)


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


def _tile_dispatch(qi, kb, block_q: int, block_k: int, causal: bool,
                   window, k_len: int, has_seg: bool, diag,
                   masked_fn, clear_fn, diag_fn):
    """Emit a kernel's tile bodies under ``pl.when`` guards. With a
    :func:`_diag_plan`, a tile wholly below the diagonal runs the clear
    body and one the diagonal crosses the ``diag_fn(offset)`` schedule of
    its offset. Otherwise per ``_needs_mask``'s verdict (Python bool =
    one static body; traced bool = both bodies, selected per tile at run
    time), and only on tiles the mask keeps anything of (the one step
    :func:`_grid_table` keeps for an outer block with none runs
    neither)."""
    if diag is not None:
        off = qi * block_q - kb * block_k
        pl.when(off >= block_k - 1)(clear_fn)
        for o in diag[0]:
            pl.when(off == o)(functools.partial(diag_fn, o))
        return
    # causal: tiles strictly above the diagonal contribute nothing;
    # sliding window: tiles entirely OLDER than any query's window start
    # contribute nothing either
    run = (kb * block_k <= qi * block_q + block_q - 1) if causal \
        else (kb >= 0)
    if window is not None:
        run = jnp.logical_and(
            run, kb * block_k + block_k - 1 > qi * block_q - window)
    need = _needs_mask(qi, kb, block_q, block_k, causal, window, k_len,
                       has_seg)
    if need is True:
        pl.when(run)(masked_fn)
    elif need is False:
        pl.when(run)(clear_fn)
    else:
        pl.when(jnp.logical_and(run, need))(masked_fn)
        pl.when(jnp.logical_and(run, jnp.logical_not(need)))(clear_fn)


def _tile_mask(s, qi, kb, causal: bool, k_len: int, window=None,
               qseg_ref=None, kseg_ref=None, block_len=None):
    """The whole-tile mask of the (qi, kb) score tile ``s``: causal (or
    block-causal), window, zero-padded keys and packed segments."""
    block_q, block_k = s.shape
    q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = kb * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
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


def _step(tab_ref):
    """(q block, k block, edge) of this grid step, from the table."""
    i = 3 * pl.program_id(1)
    return tab_ref[i], tab_ref[i + 1], tab_ref[i + 2]


def _fwd_kernel(tab_ref, *refs, scale: float, causal: bool, k_len: int,
                window=None, has_seg: bool = False, block_len=None,
                diag=None):
    """One (batch*head, live tile) program; the tile is read from the
    :func:`_grid_table` in SMEM.

    Block shapes: q_ref [1, bq, D]; k_ref/v_ref [1, bk, D];
    o_ref [1, bq, D]; lse_ref [1, bq, 1] (the trailing singleton keeps the
    block's last-two dims Mosaic-tileable: (bq, 1) with bq % 8 == 0 and 1
    equal to the full array dim — a [1, bq] block fails TPU lowering).
    Scratch m/l [bq, 1], acc [bq, D] persist across the q block's
    (sequential, ascending) k tiles. With ``has_seg`` two extra
    [1, blk, 1] int32 refs carry packed segment ids; scores with unequal
    ids are masked (packed-sequence support).

    ``block_len`` (static; ``_flash_forward`` holds it to a divisor of
    both tile sizes) turns the causal mask into the BLOCK-causal one: a
    query sees every key up to the end of its own block of
    ``block_len`` positions. Tiles are whole blocks, so which tiles
    run and which need a mask is the causal rule unchanged; only the
    mask's comparison differs.

    ``diag`` (a :func:`_diag_plan`): a tile the diagonal crosses is done
    by runs of ``sub`` query rows, each one online-softmax update over
    the keys it wholly sees and the one sub-block the diagonal crosses,
    the only part that builds a mask (:func:`_diag_rows`).
    """
    if has_seg:
        (q_ref, k_ref, v_ref, qseg_ref, kseg_ref,
         o_ref, lse_ref, m_ref, l_ref, acc_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
        qseg_ref = kseg_ref = None
    qi, kb, edge = _step(tab_ref)
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]

    @pl.when(edge % 2 == 1)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _scores(r0, r1, c0, c1):
        # matmul inputs stay in the STORED dtype (bf16 for bf16 models)
        # with f32 accumulation — the MXU's native mode. Upcasting inputs
        # to f32 forces multi-pass f32 matmuls (~3-6x slower); round 4
        # measured the f32-input kernel at ~22% MXU on v5e. Scale is
        # applied to the f32 scores, not the bf16 q, so no precision is
        # lost relative to the old `q.astype(f32) * scale` form.
        return lax.dot_general(q_ref[0, r0:r1], k_ref[0, c0:c1],
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32) * scale

    def _merge(r0, r1, pieces):
        # online-softmax update of rows [r0, r1) by score pieces
        # (s, c0, c1) over keys [c0, c1) of the tile
        m_prev, l_new, acc_new = m_ref[r0:r1], l_ref[r0:r1], acc_ref[r0:r1]
        m_new = m_prev
        for s, _, _ in pieces:
            m_new = jnp.maximum(m_new, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        l_new, acc_new = l_new * alpha, acc_new * alpha
        for s, c0, c1 in pieces:
            p = jnp.exp(s - m_new)
            l_new = l_new + jnp.sum(p, axis=-1, keepdims=True)
            # p is cast to the value dtype for the PV matmul (f32
            # accumulate); p in [0, 1] so bf16's relative precision bounds
            # the elementwise error at ~2^-8 of each probability — the
            # flash-on-TPU standard
            acc_new = acc_new + lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, c0:c1],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[r0:r1], l_ref[r0:r1], acc_ref[r0:r1] = m_new, l_new, acc_new

    def _diag_tile(off):
        for r0, r1, c_a, c_b in _diag_rows(off, block_q, block_k, diag[1]):
            pieces = [(_scores(r0, r1, 0, c_a), 0, c_a)] if c_a else []
            if c_b > c_a:
                pieces.append((_diag_mask(_scores(r0, r1, c_a, c_b),
                                          off + r0 - c_a), c_a, c_b))
            _merge(r0, r1, pieces)

    # tile-static mask specialization (round 4): a tile no mask edge
    # crosses skips the whole iota/compare/select chain, and a diagonal
    # tile builds its mask on the sub-blocks the diagonal crosses alone.
    # At d_head 64 every product runs at half the MXU's width; there
    # (PR 38, PERF.md §6) the two backward passes take 1.15-1.25x their
    # products' time and this one 1.6x, bound by neither
    _tile_dispatch(
        qi, kb, block_q, block_k, causal, window, k_len,
        qseg_ref is not None, diag,
        lambda: _merge(0, block_q, [(_tile_mask(
            _scores(0, block_q, 0, block_k), qi, kb, causal, k_len, window,
            qseg_ref, kseg_ref, block_len), 0, block_k)]),
        lambda: _merge(0, block_q,
                       [(_scores(0, block_q, 0, block_k), 0, block_k)]),
        _diag_tile)

    @pl.when(edge >= 2)
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


def _table_specs(h: int, block_q: int, block_k: int, d: int, dv: int,
                 has_seg: bool):
    """BlockSpecs of q, k, v (and the q/k segment ids) addressed through
    the grid table: the q and k blocks of step ``i`` are ``tab[3i]`` and
    ``tab[3i + 1]``; segment ids are per BATCH, so their maps divide the
    b*h grid row back down to the batch row."""
    q_map = lambda bh, i, tab: (bh, tab[3 * i], 0)
    k_map = lambda bh, i, tab: (bh, tab[3 * i + 1], 0)
    specs = [pl.BlockSpec((1, block_q, d), q_map),
             pl.BlockSpec((1, block_k, d), k_map),
             pl.BlockSpec((1, block_k, dv), k_map)]
    if has_seg:
        specs += [
            pl.BlockSpec((1, block_q, 1),
                         lambda bh, i, tab: (bh // h, tab[3 * i], 0)),
            pl.BlockSpec((1, block_k, 1),
                         lambda bh, i, tab: (bh // h, tab[3 * i + 1], 0)),
        ]
    return specs, q_map


#: the kernels' ``pallas_call`` s are jitted INLINE: a layer's call is
#: traced once for all layers of one shape (the kernel bodies are long
#: Python since PR 38) and still lowered where it stands, so the step
#: holds one named kernel a layer
_inline_jit = functools.partial(jax.jit, inline=True)


@functools.partial(_inline_jit, static_argnames=(
    "scale", "causal", "k_len", "window", "block_len", "diag", "block_q",
    "block_k", "h", "interpret"))
def _fwd_call(tab, qf, kf, vf, *segs, scale, causal, k_len, window,
              block_len, diag, block_q, block_k, h, interpret):
    bh, sq_p, d = qf.shape
    dv = vf.shape[-1]
    in_specs, q_map = _table_specs(h, block_q, block_k, d, dv, bool(segs))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          k_len=k_len, window=window, has_seg=bool(segs),
                          block_len=block_len, diag=diag),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, tab.shape[0] // 3),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, block_q, dv), q_map),
                       pl.BlockSpec((1, block_q, 1), q_map)],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, dv), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_p, dv), qf.dtype),
            jax.ShapeDtypeStruct((bh, sq_p, 1), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        name="flash_fwd", interpret=interpret,
    )(tab, qf, kf, vf, *segs)


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

    has_seg = segment_ids is not None
    nq, nk = sq_p // block_q, sk_p // block_k
    tab = _grid_table(nq, nk, block_q, block_k, causal, window)
    plan = _diag_plan(tab, nq, nk, block_q, block_k,
                      causal and window is None and not has_seg
                      and block_len is None and sq <= sk)
    segs = _seg_blocks(segment_ids, sq_p, sk_p) if has_seg else ()
    out, lse = _fwd_call(
        jnp.asarray(tab), qf, kf, vf, *segs, scale=scale, causal=causal,
        k_len=sk, window=window, block_len=block_len,
        diag=plan and plan[:2], block_q=block_q, block_k=block_k, h=h,
        interpret=interpret)
    if bhsd:
        out = out.reshape(b, h, sq_p, dv)[:, :, :sq]
    else:
        out = out.reshape(b, h, sq_p, dv).transpose(0, 2, 1, 3)[:, :sq]
    lse = lse.reshape(b, h, sq_p)[:, :, :sq]
    return out, lse


def _bwd_dq_kernel(tab_ref, *refs, scale: float, causal: bool, k_len: int,
                   window=None, has_seg: bool = False, diag=None):
    """dq pass: one (batch*head, live tile) program, a q block's k tiles
    in sequence. ``dq_acc`` [bq, D] f32 persists across them. The tile
    dispatch, segment masking and the diagonal tiles' runs of query rows
    are ``_fwd_kernel``'s."""
    if has_seg:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, qseg_ref,
         kseg_ref, dq_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref,
         dq_acc) = refs
        qseg_ref = kseg_ref = None
    qi, kb, edge = _step(tab_ref)
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]

    @pl.when(edge % 2 == 1)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute(r0, r1, pieces):
        # bf16 matmul inputs + f32 accumulation throughout (see
        # _fwd_kernel); scale folds into the f32 score/grad tensors.
        # ``pieces``: (c0, c1, mask) over keys [c0, c1) of the tile
        acc = None
        for c0, c1, mask in pieces:
            s = lax.dot_general(q_ref[0, r0:r1], k_ref[0, c0:c1],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            s = mask(s)
            p = jnp.exp(s - lse_ref[0, r0:r1])                 # [bq, bk]
            dp = lax.dot_general(g_ref[0, r0:r1], v_ref[0, c0:c1],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[0, r0:r1])).astype(k_ref.dtype)
            part = lax.dot_general(ds, k_ref[0, c0:c1],
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
            acc = part if acc is None else acc + part
        dq_acc[r0:r1] += acc * scale

    def _diag_tile(off):
        for r0, r1, c_a, c_b in _diag_rows(off, block_q, block_k, diag[1]):
            pieces = [(0, c_a, lambda s: s)] if c_a else []
            if c_b > c_a:
                pieces.append((c_a, c_b, functools.partial(
                    _diag_mask, d=off + r0 - c_a)))
            _compute(r0, r1, pieces)

    _tile_dispatch(
        qi, kb, block_q, block_k, causal, window, k_len,
        qseg_ref is not None, diag,
        lambda: _compute(0, block_q, [(0, block_k, lambda s: _tile_mask(
            s, qi, kb, causal, k_len, window, qseg_ref, kseg_ref))]),
        lambda: _compute(0, block_q, [(0, block_k, lambda s: s)]),
        _diag_tile)

    @pl.when(edge >= 2)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(tab_ref, *refs, scale: float, causal: bool, k_len: int,
                    window=None, has_seg: bool = False, diag=None):
    """dk/dv pass: one (batch*head, live tile) program, a k block's q
    tiles in sequence. ``dk_acc``/``dv_acc`` [bk, D] f32 persist across
    them. A diagonal tile is done by runs of ``sub`` keys, each over the
    query rows that see all of it and the one sub-block the diagonal
    crosses (:func:`_diag_cols`)."""
    if has_seg:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, qseg_ref,
         kseg_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dk_ref, dv_ref,
         dk_acc, dv_acc) = refs
        qseg_ref = kseg_ref = None
    qb, ki, edge = _step(tab_ref)
    block_k, block_q = k_ref.shape[1], q_ref.shape[1]

    @pl.when(edge % 2 == 1)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute(c0, c1, pieces):
        # bf16 matmul inputs + f32 accumulation (see _fwd_kernel); the
        # dk contribution applies scale to the f32 accumulator instead of
        # pre-scaling q (dot(ds, q*scale) == scale * dot(ds, q)).
        # ``pieces``: (r0, r1, mask) over query rows [r0, r1) of the tile
        dv = dk = None
        for r0, r1, mask in pieces:
            s = lax.dot_general(q_ref[0, r0:r1], k_ref[0, c0:c1],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            s = mask(s)
            p = jnp.exp(s - lse_ref[0, r0:r1])                 # [bq, bk]
            dv_part = lax.dot_general(
                p.astype(g_ref.dtype), g_ref[0, r0:r1],
                (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            dp = lax.dot_general(g_ref[0, r0:r1], v_ref[0, c0:c1],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[0, r0:r1])).astype(q_ref.dtype)
            dk_part = lax.dot_general(
                ds, q_ref[0, r0:r1], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dv = dv_part if dv is None else dv + dv_part
            dk = dk_part if dk is None else dk + dk_part
        dv_acc[c0:c1] += dv
        dk_acc[c0:c1] += dk * scale

    def _diag_tile(off):
        for c0, c1, r_a, r_b in _diag_cols(off, block_q, block_k, diag[1]):
            pieces = [(r_a, r_b, functools.partial(
                _diag_mask, d=off + r_a - c0))] if r_b > r_a else []
            if r_b < block_q:
                pieces.append((r_b, block_q, lambda s: s))
            _compute(c0, c1, pieces)

    _tile_dispatch(
        qb, ki, block_q, block_k, causal, window, k_len,
        qseg_ref is not None, diag,
        lambda: _compute(0, block_k, [(0, block_q, lambda s: _tile_mask(
            s, qb, ki, causal, k_len, window, qseg_ref, kseg_ref))]),
        lambda: _compute(0, block_k, [(0, block_q, lambda s: s)]),
        _diag_tile)

    @pl.when(edge >= 2)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(_inline_jit, static_argnames=(
    "kmajor", "scale", "causal", "k_len", "window", "diag", "block_q",
    "block_k", "h", "interpret"))
def _bwd_call(tab, *operands, kmajor, scale, causal, k_len, window, diag,
              block_q, block_k, h, interpret):
    """One backward pass over ``(q, k, v, dO, lse, delta[, segment
    ids])``: dq (``kmajor`` False) or dk and dv (True)."""
    qf, kf = operands[:2]
    bh, d = qf.shape[0], qf.shape[-1]
    specs, q_map = _table_specs(h, block_q, block_k, d, d,
                                len(operands) > 6)
    q_spec, k_spec = specs[:2]
    row_q = pl.BlockSpec((1, block_q, 1), q_map)
    if kmajor:
        kernel, out_spec, name = _bwd_dkv_kernel, k_spec, "flash_bwd_dkv"
        out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)
                     for x in operands[1:3]]
        scratch = [pltpu.VMEM((block_k, d), jnp.float32)] * 2
    else:
        kernel, out_spec, name = _bwd_dq_kernel, q_spec, "flash_bwd_dq"
        out_shape = [jax.ShapeDtypeStruct(qf.shape, qf.dtype)]
        scratch = [pltpu.VMEM((block_q, d), jnp.float32)]
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, causal=causal, k_len=k_len,
                          window=window, has_seg=len(operands) > 6,
                          diag=diag),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bh, tab.shape[0] // 3),
            in_specs=[q_spec, k_spec, k_spec, q_spec, row_q, row_q]
            + specs[3:],
            out_specs=[out_spec] * len(out_shape), scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=_COMPILER_PARAMS,
        name=name, interpret=interpret,
    )(tab, *operands)


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

    has_seg = segment_ids is not None
    nq, nk = sq_p // block_q, sk_p // block_k
    tab = _grid_table(nq, nk, block_q, block_k, causal, window)
    plan = _diag_plan(tab, nq, nk, block_q, block_k,
                      causal and window is None and not has_seg and sq <= sk)
    segs = _seg_blocks(segment_ids, sq_p, sk_p) if has_seg else ()
    operands = (qf, kf, vf, gf, lsef, deltaf) + tuple(segs)
    kw = dict(scale=scale, causal=causal, k_len=sk, window=window,
              diag=plan and (plan[0], plan[2]), block_q=block_q,
              block_k=block_k, h=h, interpret=interpret)
    # first pass: a q block's k tiles in sequence; second: a k block's
    # q tiles
    dq = _bwd_call(jnp.asarray(tab), *operands, kmajor=False, **kw)[0]
    dk, dv = _bwd_call(jnp.asarray(_grid_table(nq, nk, block_q, block_k,
                                               causal, window, True)),
                       *operands, kmajor=True, **kw)

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

    ``block_q``/``block_k`` default adaptively: 1024/1024 for causal
    attention without a window or segment ids up to d_head 128, 512/1024
    for the rest (wider heads: VMEM; non-causal: not swept), 512/512
    under a sliding ``window`` at every d_head — its grid covers
    ``~window + block_q + block_k`` keys per q block, so the smaller
    blocks tighten coverage (measured: W=1024 S=8192 fwd+bwd 1.80x
    full-causal at 512/512 vs 1.44x at 1024/1024 on v5e, before PR 38).
    Tiles above the diagonal (or outside the window) are not in the
    grid, and a plain causal call does the tiles the diagonal crosses
    in pieces (module docstring); measured on a v5e at B2 H17 S2048 D64
    (PR 38): fwd + dq + dk/dv 1.25 ms a call at 1024/1024, 1.42 at
    512/1024, 1.75 at 512/512; the whole-tile kernels took 1.68 at
    512/1024.

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
        # causal attention without a window or packing takes the square
        # 1024 tile up to d_head 128: measured fwd+bwd on a v5e with
        # the diagonal tiles done in pieces (PR 38) — at B2 H17 S2048
        # D64 1.25 ms a call against 1.42 at 512/1024 (the parent's
        # kernels: 1.62 vs 1.68), and at D128 (round 5, whole masked
        # tiles) 4.58 ms vs 6.05. 2048-sized tiles fail to compile, and
        # D=256 would double the VMEM footprint into that range, so
        # wider heads and non-causal calls keep 512/1024 (documented
        # safe through D=256). WINDOWED attention keeps 512/512 at every
        # d_head — its grid covers ~window + block_q + block_k keys per
        # q block, and the bigger q tile widens exactly the overscan
        # 512/512 was measured to avoid.
        block_q = 1024 if (q.shape[-1] <= 128 and causal
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
