"""True paged-attention decode as a Pallas TPU kernel: K/V read
THROUGH the page table, no materialized logical view.

The paged serving data plane (rounds 12+) stored every layer's cache
as ``[N, Hkv, page_len, D]`` fixed-size pages with per-slot page
tables, but the decode step still paid one large HBM round trip per
iteration: ``models.decoding._gather_pages`` gathered each slot's
pages back into a logically contiguous ``[S, H, L, D]`` view in HBM
before ``_slot_attn_readout`` ran — writing AND re-reading the whole
resident working set every step (ROADMAP item 3a).

This kernel removes that copy. The grid is ``(S, P)`` — one program
per (slot, logical page) — and the PAGE TABLE IS THE INDEX MAP: the
k/v BlockSpecs look up ``table[s, p]`` from the scalar-prefetch
operand and DMA the physical page HBM -> VMEM directly. Scores,
masking, online softmax and the value mix all happen on that one
streaming read; nothing intermediate ever touches HBM. Structure
mirrors the contiguous-cache decode kernel (``ops.decode_attention``):
per-program state in VMEM scratch carried across the ``arbitrary``
page dimension, init at page 0, finalize at the last page, Hkv heads
unrolled inside the program so per-program DMA amortizes.

Feature contract (everything the gather path supports):

  * **GQA** — queries arrive grouped ``[S, W, Hkv, G, D]``; the
    ``W * G`` rows sharing one KV head are the matmul M dimension.
  * **Window-causal [S, W] verify windows** — window query ``j`` of
    slot ``s`` admits cache positions ``<= t[s] + j`` (and
    ``> t[s] + j - window`` for SWA models), exactly
    ``_slot_attn_readout``'s mask, so speculative
    ``verify_step_slots_paged`` rides the same kernel with W > 1.
  * **int8 caches** — per-token scales ``[N, Hkv, page_len]`` ride
    the same page-table index map; dequant happens on the VPU inside
    the kernel (scores * k_scale after the D contraction,
    probabilities * v_scale before the V contraction), so HBM traffic
    stays int8 + scales.
  * **int4 caches** (this PR) — pages arrive nibble-PACKED along the
    position axis (``[N, Hkv, page_len//2, D]`` bytes, two positions
    per byte, ``models.decoding.pack_int4``'s half-split); the kernel
    unpacks each page block on the VPU and dequantizes through the
    same per-token scale planes, halving the payload HBM read again
    vs int8. The packed byte plane must itself satisfy the int8
    sublane rule, hence the ``page_len % 64`` gate.
  * **Sentinels** — a table entry >= N (unallocated logical page)
    clamps in the index map and its program skips compute; pages
    entirely past ``t + W - 1`` (or entirely before a sliding
    window's reach) skip too, so a mostly-empty slot costs its live
    pages only.

Numerics: the page-blocked online softmax is algebraically exact but
reassociates the softmax sums relative to the gather path's one-shot
softmax — the same contract as ``ops.decode_attention`` vs the einsum
oracle (and chunked vs one-pass prefill). Greedy token identity holds
at any realistic argmax margin; ``tests/test_paged_kernel.py`` pins
the kernel against the ``_gather_pages`` reference in interpreter
mode (the off-TPU/CI oracle) across GQA/int8/window/W>1/scrambled
page orders, and end-to-end through the serving engine.

Tiling: the page block's second-to-last dim is ``page_len``, so the
Mosaic sublane rule wants ``page_len % 8 == 0`` for float caches,
``% 32`` for int8, and ``% 64`` for packed int4 (the byte plane is
``page_len // 2`` rows); ``page_aligned`` is the shared gate — callers
fall back to the gather path for unaligned pools (the engine default
``page_len=16`` qualifies for float caches).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.compat import backend_is_tpu, note_path
from distkeras_tpu.ops.attention import NEG_INF


def page_alignment(quantized) -> int:
    """The ``page_len`` divisor the kernel's sublane tiling demands for
    a cache quantization mode. ``quantized`` spans the dtype ladder:
    falsy / a float dtype name -> 8 (f32/bf16 sublane rule), ``True`` /
    ``8`` / ``"int8"`` -> 32 (int8 sublane rule), ``4`` / ``"int4"`` ->
    64 (the packed byte plane is ``page_len // 2`` int8 rows, and THAT
    must hit the % 32 int8 rule)."""
    if isinstance(quantized, str):
        name = quantized.lower()
        if name in ("int4", "4"):
            return 64
        if name == "int8":
            return 32
        if name in ("f32", "float32", "bf16", "bfloat16", "float16"):
            return 8
        raise ValueError(f"unknown cache quantization mode {quantized!r}")
    if quantized == 4:
        return 64
    return 32 if quantized else 8


def page_aligned(page_len: int, quantized=False) -> bool:
    """Can the kernel tile this pool? The page block's sublane dim is
    ``page_len``: Mosaic wants multiples of 8 (f32/bf16) / 32 (int8) /
    64 (int4 — see :func:`page_alignment` for the full matrix)."""
    return int(page_len) % page_alignment(quantized) == 0


def _unpack4(b, dt):
    """In-kernel nibble unpack of a ``[page_len//2, D]`` packed int4
    byte block to ``[page_len, D]`` in the compute dtype. Matches
    ``models.decoding.pack_int4``'s half-split layout (byte row r =
    position r low nibble, position r + page_len//2 high nibble), so
    the sublane concat lands positions in order. All nibble math runs
    in int32 (portable two's complement on VPU and in interpret mode)."""
    b32 = b.astype(jnp.int32) & 255
    lo = b32 & 15
    lo = lo - 16 * (lo > 7)
    hi = (b32 >> 4) & 15
    hi = hi - 16 * (hi > 7)
    return jnp.concatenate([lo, hi], axis=0).astype(dt)


def _kernel(t_ref, tb_ref, *refs, scale: float, page_len: int,
            g: int, w_len: int, hkv: int, window, quantized: bool,
            int4: bool, n_pages: int, tree: bool,
            full_window: bool = False, ring: bool = False):
    if tree:
        anc_ref, refs = refs[0], refs[1:]
    else:
        anc_ref = None
    if quantized:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        ks_ref = vs_ref = None
    si = pl.program_id(0)
    pi = pl.program_id(1)
    npp = pl.num_programs(1)
    t = t_ref[si]
    rows = q_ref.shape[2]                      # W*G, padded to % 8

    @pl.when(pi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    if ring:
        # the table is a RING of ``npp`` columns: column ``pi`` holds
        # the newest logical page at or under the window's top page
        # that is congruent to ``pi`` (a window layer's page group:
        # pages behind the window are given back, so the table is as
        # wide as a window, not as the context)
        top = (t + (w_len - 1)) // page_len
        lp = top - lax.rem(top - pi + npp, npp)
        start = lp * page_len
    else:
        lp = pi
        start = pi * page_len
    # a page participates iff it holds any position some window query
    # admits: the union of the per-query ranges is (t - window, t+W-1]
    # (tree windows too: every node's column lies in [t, t+W-1])
    run = jnp.logical_and(start <= t + (w_len - 1),
                          tb_ref[si, pi] < n_pages)
    if ring:
        run = jnp.logical_and(run, lp >= 0)
    if window is not None:
        run = jnp.logical_and(run, start + page_len - 1 > t - window)

    @pl.when(run)
    def _compute():
        # per-row window index j = row // G (pad rows past W*G read a
        # too-permissive mask — their output is sliced off), per-column
        # global position: the _slot_attn_readout mask, page-local
        j_idx = lax.broadcasted_iota(jnp.int32, (rows, page_len), 0) // g
        pos = start + lax.broadcasted_iota(
            jnp.int32, (rows, page_len), 1)
        if full_window:
            # block-diffusion window: every window query sees the
            # committed prefix and ALL W window positions (a separate
            # specialisation: the causal window's trace is unchanged)
            valid = pos <= t + (w_len - 1)
        elif anc_ref is None:
            valid = pos <= t + j_idx
            if window is not None:
                valid = jnp.logical_and(valid, pos > t + j_idx - window)
        else:
            # tree window (tree-speculation PR): the committed prefix
            # (< t) plus, for window column w2 at position t + w2, the
            # per-ROW ancestor bit — the equality-OR form keeps the
            # gather static (W is small and compile-time)
            anc_blk = anc_ref[0]               # [rows, Wpad] int32
            valid = pos < t
            for w2 in range(w_len):
                valid = jnp.logical_or(
                    valid,
                    jnp.logical_and(anc_blk[:, w2:w2 + 1] != 0,
                                    pos == t + w2))
            if window is not None:
                # each query's own position is t + depth; depth = its
                # ancestor count (self included) minus one
                depth = jnp.sum((anc_blk[:, :w_len] != 0)
                                .astype(jnp.int32),
                                axis=1, keepdims=True) - 1
                valid = jnp.logical_and(valid, pos > t + depth - window)
        # unrolled per-KV-head loop: each h is one independent
        # online-softmax update (static Python unroll, hkv copies —
        # the bh_block amortization of ops.decode_attention)
        for h in range(hkv):
            q = q_ref[0, h]                    # [rows, D]
            if int4:
                # packed page: [page_len//2, D] bytes -> [page_len, D];
                # dequant stays the shared q * scale contract below
                kblk = _unpack4(k_ref[0, h], q.dtype)
            elif quantized:
                kblk = k_ref[0, h].astype(q.dtype)
            else:
                kblk = k_ref[0, h]
            s = lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
                * scale
            if ks_ref is not None:
                s = s * ks_ref[0, h][None, :]  # dequant scores
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[h]
            l_prev = l_ref[h]
            acc_prev = acc_ref[h]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)             # [rows, page_len] f32
            m_ref[h] = m_new
            l_ref[h] = l_prev * alpha + jnp.sum(p, axis=-1,
                                                keepdims=True)
            if vs_ref is not None:
                p = p * vs_ref[0, h][None, :]  # dequant values
            if int4:
                vblk = _unpack4(v_ref[0, h], q.dtype)
            elif quantized:
                vblk = v_ref[0, h].astype(q.dtype)
            else:
                vblk = v_ref[0, h]
            acc_ref[h] = acc_prev * alpha + lax.dot_general(
                p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(pi == npp - 1)
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, t, table, *,
                           scale: Optional[float] = None,
                           window: Optional[int] = None,
                           k_scale=None, v_scale=None, anc=None,
                           full_window: bool = False,
                           ring: bool = False,
                           name: str = "paged_decode_attention",
                           interpret: Optional[bool] = None):
    """Window decode attention straight off the page pool.

    q: ``[S, W, Hkv, G, D]`` (W = 1 for plain decode, k+1 for the
    speculative verify window); k_pages/v_pages: ``[N, Hkv, page_len,
    D]`` (int8 with ``k_scale``/``v_scale`` ``[N, Hkv, page_len]``);
    t: ``[S]`` int32 per-slot window start positions; table:
    ``[S, P]`` int32 page tables (entries >= N are the unallocated
    sentinel — skipped). Returns ``[S, W, Hkv, G, D]`` f32, the
    masked-softmax attention of each window query over its slot's
    cache positions (``window`` adds the SWA band).

    ``anc`` (tree speculation, ``[S, W, W]`` bool): switch the
    window-causal mask to a per-slot token-TREE mask — window query i
    admits the committed prefix (``< t``) plus window column j's
    position ``t + j`` iff ``anc[s, i, j]`` (node j is i or one of its
    ancestors; the engine derives the mask from the draft's
    parent-index vectors). SWA models derive each node's own position
    from its ancestor count (``t + depth``). A lower-triangular ``anc``
    reproduces the plain window-causal mask exactly.

    ``full_window`` (block diffusion): every window query admits the
    committed prefix and ALL ``W`` window positions ``t .. t+W-1`` —
    attention is bidirectional inside the window (no ``window``/``anc``
    with it).

    ``ring`` (a window layer's page group): ``table`` is ``[S, R]``, a
    ring in which column ``c`` holds the slot's newest logical page
    ``p <= (t + W - 1) // page_len`` with ``p % R == c``; needs
    ``window`` (what lies further back than ``R`` pages is never read)
    and takes no tree. ``name`` is the kernel's name in a trace."""
    s, w_len, hkv, g, d = q.shape
    if full_window and (window is not None or anc is not None):
        raise ValueError("full_window takes neither a sliding window "
                         "nor a tree mask")
    if ring and (window is None or anc is not None or full_window):
        raise ValueError("a ring table needs a sliding window and takes "
                         "neither a tree mask nor a full window")
    n_pages, _, payload_rows, _ = k_pages.shape
    n_logical = table.shape[1]
    quantized = k_scale is not None
    # int4 pools arrive nibble-PACKED along the position axis (pack_
    # int4's half-split): the payload block holds page_len // 2 byte
    # rows while the per-position scale plane keeps the true page_len —
    # that shape disagreement IS the int4 signal (no extra flag to
    # thread through jit)
    int4 = quantized and k_scale.shape[2] != payload_rows
    page_len = k_scale.shape[2] if int4 else payload_rows
    if int4 and page_len != 2 * payload_rows:
        raise ValueError(
            f"int4 payload rows {payload_rows} do not match scale "
            f"plane page_len {page_len} (expected page_len // 2)")
    mode = "int4" if int4 else ("int8" if quantized else False)
    if not page_aligned(page_len, mode):
        raise ValueError(
            f"page_len {page_len} is not kernel-tileable "
            f"(% {page_alignment(mode)} for "
            f"{mode or 'float'} pages); "
            "use models.decoding._gather_pages instead")
    if scale is None:
        scale = d ** -0.5
    if anc is not None and w_len > 128:
        raise ValueError(
            f"tree window {w_len} exceeds the kernel's one-tile "
            "ancestor-mask lane budget (128 nodes)")
    if interpret is None:
        interpret = not backend_is_tpu()
    # rows = W*G is the per-head matmul M dim; pad to the 8-row
    # sublane rule (zero rows are independent softmaxes, sliced off)
    rows = w_len * g
    qr = q.transpose(0, 2, 1, 3, 4).reshape(s, hkv, rows, d)
    pad = (-rows) % 8
    if pad:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, pad), (0, 0)))
    rows_p = rows + pad

    def q_map(si, pi, *_):
        return (si, 0, 0, 0)

    def kv_map(si, pi, t_ref, tb_ref):
        # THE page-table indirection: the physical page id is the
        # block index (sentinels clamp; their program skips compute)
        return (jnp.minimum(tb_ref[si, pi], n_pages - 1), 0, 0, 0)

    def sc_map(si, pi, t_ref, tb_ref):
        return (jnp.minimum(tb_ref[si, pi], n_pages - 1), 0, 0)

    def anc_map(si, pi, t_ref, tb_ref):
        return (si, 0, 0)

    in_specs = []
    operands = []
    if anc is not None:
        # the ancestor mask as a per-slot [rows, W] int32 plane: each
        # query row repeats its window node's mask (G query heads share
        # one node), rows padded with the q padding, the node axis
        # padded to the 128-lane tile
        anc_rows = jnp.repeat(jnp.asarray(anc, jnp.int32), g, axis=1)
        anc_rows = jnp.pad(anc_rows,
                           ((0, 0), (0, pad), (0, 128 - w_len)))
        in_specs.append(pl.BlockSpec((1, rows_p, 128), anc_map))
        operands.append(anc_rows)
    in_specs += [
        pl.BlockSpec((1, hkv, rows_p, d), q_map),
        pl.BlockSpec((1, hkv, payload_rows, d), kv_map),
        pl.BlockSpec((1, hkv, payload_rows, d), kv_map),
    ]
    operands += [qr, k_pages, v_pages]
    if quantized:
        in_specs += [pl.BlockSpec((1, hkv, page_len), sc_map),
                     pl.BlockSpec((1, hkv, page_len), sc_map)]
        operands += [k_scale, v_scale]
    kernel = functools.partial(
        _kernel, scale=float(scale), page_len=int(page_len), g=int(g),
        w_len=int(w_len), hkv=int(hkv), window=window,
        quantized=quantized, int4=int4, n_pages=int(n_pages),
        tree=anc is not None, full_window=bool(full_window),
        ring=bool(ring))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, n_logical),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, rows_p, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((hkv, rows_p, 1), jnp.float32),
            pltpu.VMEM((hkv, rows_p, 1), jnp.float32),
            pltpu.VMEM((hkv, rows_p, d), jnp.float32),
        ])
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, hkv, rows_p, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name=name, interpret=interpret,
    )(jnp.asarray(t, jnp.int32), jnp.asarray(table, jnp.int32),
      *operands)
    return out[:, :, :rows].reshape(s, hkv, w_len, g, d) \
        .transpose(0, 2, 1, 3, 4)


# --- latent pages (MLA decode, absorbed form) --------------------------------
#
# A latent-attention layer caches ONE vector a token and no head axis:
# ``[N, C, page_len]``, the latent's ``v_dim`` values and behind them the
# shared rope key, the positions in the lanes (``models.decoding``: the
# latent section says why). In the absorbed form every query head attends that one
# shared key, and the value is the key's first ``v_dim`` columns: the
# kernel reads ONE plane (storing V apart would double the bytes the layer
# exists to save), the ``W * H`` rows of a slot are one matmul M dimension,
# and with the positions in the lanes G pages side by side ARE the ``K^T``
# of ``G * page_len`` positions: a program takes a block of pages, not one.


#: positions a program of the latent kernel walks: a grid step costs as much
#: as a page of 128 positions does in bytes, so a program takes as many
#: consecutive pages of its slot as make about this many
_LATENT_POSITIONS = 1024
#: what a program's buffers may take of the compiler's scoped VMEM (16 MiB
#: on a v5e): half, the rest is the compiler's own temporaries
_LATENT_VMEM = 8 * 2 ** 20


def _latent_vmem_bytes(g: int, page_len: int, c: int, rows: int, v_dim: int,
                       itemsize: int) -> int:
    """VMEM of one program over ``g`` pages: every page double-buffered
    (a page narrower than the 128 lanes is padded to them), the queries
    and the output double-buffered, the float32 state, and the block's
    scores and probabilities in float32 with the probabilities' cast."""
    lanes = pl.cdiv(page_len, 128) * 128
    span = pl.cdiv(g * page_len, 128) * 128
    return (2 * g * c * lanes * itemsize + 2 * rows * c * itemsize
            + 3 * rows * v_dim * 4 + rows * span * (8 + itemsize))


def latent_pages_per_program(page_len: int, n_logical: int, c: int,
                             rows: int, v_dim: int, dtype) -> int:
    """G, the consecutive logical pages of a slot that one program of
    :func:`paged_latent_attention` reads: as many as make about 1,024
    positions (8 pages of 128), no more than the table is wide, and no more
    than fit ``_LATENT_VMEM`` at these widths. From what the kernel is
    handed alone: nothing chooses it from outside."""
    itemsize = jnp.dtype(dtype).itemsize
    g = max(1, min(_LATENT_POSITIONS // page_len, n_logical))
    while g > 1 and _latent_vmem_bytes(g, page_len, c, rows, v_dim,
                                       itemsize) > _LATENT_VMEM:
        g -= 1
    return g


def _latent_kernel(t_ref, tb_ref, q_ref, *refs, scale: float, page_len: int,
                   heads: int, w_len: int, v_dim: int, n_pages: int, g: int):
    c_refs = refs[:g]
    o_ref, m_ref, l_ref, acc_ref = refs[g:]
    si = pl.program_id(0)
    bi = pl.program_id(1)
    nb = pl.num_programs(1)
    t = t_ref[si]
    rows = q_ref.shape[1]                      # W*H, padded to % 8
    span = g * page_len

    @pl.when(bi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # a block takes part iff its first page does: it holds a position some
    # window query admits, and the table holds it (a slot's pages are
    # allocated from the front, so no page behind a sentinel is live)
    start = bi * span
    run = jnp.logical_and(start <= t + (w_len - 1),
                          tb_ref[si, bi * g] < n_pages)

    @pl.when(run)
    def _compute():
        # row r is window query r // H (rows are window-major): it
        # admits positions <= t + r // H, the chain window's mask, which
        # also masks the block's dead pages: they lie past the slot's
        # depth, and their buffers hold whatever was read last
        j_idx = lax.broadcasted_iota(jnp.int32, (rows, span), 0) // heads
        pos = start + lax.broadcasted_iota(jnp.int32, (rows, span), 1)
        q = q_ref[0]                           # [rows, C]
        # the block's pages side by side, positions in the lanes: K^T
        cblk = jnp.concatenate([r[0] for r in c_refs], axis=1)
        s = lax.dot_general(q, cblk, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        s = jnp.where(pos <= t + j_idx, s, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + lax.dot_general(
            p.astype(cblk.dtype), cblk[:v_dim], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(bi == nb - 1)
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def paged_latent_attention(q, c_pages, t, table, *, v_dim: int,
                           scale: float,
                           name: str = "paged_latent_attention",
                           interpret: Optional[bool] = None):
    """Absorbed-form latent attention straight off the latent page plane.

    q: ``[S, W, H, C]`` (the queries with ``Wkvb``'s key half taken in,
    then the roped rope part; W = 1 for plain decode); c_pages:
    ``[N, C, page_len]``, ONE plane: down a column a token's latent
    (``v_dim`` values) and its roped shared key behind it; t: ``[S]`` int32 window start
    positions; table: ``[S, P]`` int32 page tables (entries >= N: the
    unallocated sentinel, skipped). Window query ``j`` of slot ``s``
    admits positions ``<= t[s] + j``. Returns ``[S, W, H, v_dim]``
    float32: each row's softmax over the shared keys, times their first
    ``v_dim`` columns. ``scale`` is the layer's (the root of the
    NON-absorbed query/key width, not of ``C``).

    The grid is ``(S, ceil(P / G))``: one program reads a BLOCK of G
    consecutive logical pages of its slot (:func:`latent_pages_per_program`),
    each through the page table by an index map of its own, and takes the
    block's ``G * page_len`` positions through one score product, one
    softmax update and one value product. A block whose first page is dead
    (past the slot's depth, or a sentinel) is skipped; a dead page inside a
    live block is not read either (its index repeats the one its buffer
    holds) and its positions are masked. A slot's pages are taken to be
    allocated from the front: a sentinel below its depth is no more
    defined here than in the reference, which reads the last page there."""
    s, w_len, heads, c = q.shape
    n_pages, c2, page_len = c_pages.shape
    if c2 != c or not 0 < v_dim <= c:
        raise ValueError(f"queries of width {c}, plane of width {c2}, "
                         f"values of width {v_dim}")
    if not page_aligned(page_len):
        raise ValueError(
            f"page_len {page_len} is not kernel-tileable (% 8); use "
            "paged_latent_attention_reference instead")
    if interpret is None:
        interpret = not backend_is_tpu()
    rows = w_len * heads
    qr = q.astype(c_pages.dtype).reshape(s, rows, c)
    pad = (-rows) % 8
    if pad:
        qr = jnp.pad(qr, ((0, 0), (0, pad), (0, 0)))
    rows_p = rows + pad
    g = latent_pages_per_program(page_len, table.shape[1], c, rows_p, v_dim,
                                 c_pages.dtype)
    note_path("latent_pages_per_program", str(g))
    n_blocks = pl.cdiv(table.shape[1], g)
    table = jnp.asarray(table, jnp.int32)
    if n_blocks * g != table.shape[1]:
        # whole blocks: the columns past the table are sentinels
        table = jnp.pad(table, ((0, 0), (0, n_blocks * g - table.shape[1])),
                        constant_values=n_pages)

    def q_map(si, bi, *_):
        return (si, 0, 0)

    def c_map(j):
        def index(si, bi, t_ref, tb_ref):
            # THE page-table indirection, page j of block bi. Past the
            # slot's top page the column stays in the last block that has
            # a live page j: the index repeats and nothing is fetched
            top = (t_ref[si] + (w_len - 1)) // page_len
            blk = jnp.minimum(bi, lax.div(jnp.maximum(top - j, 0), g))
            return (jnp.minimum(tb_ref[si, blk * g + j], n_pages - 1), 0, 0)
        return index

    kernel = functools.partial(
        _latent_kernel, scale=float(scale), page_len=int(page_len),
        heads=int(heads), w_len=int(w_len), v_dim=int(v_dim),
        n_pages=int(n_pages), g=g)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(s, n_blocks),
            in_specs=[pl.BlockSpec((1, rows_p, c), q_map)]
            + [pl.BlockSpec((1, c, page_len), c_map(j)) for j in range(g)],
            out_specs=pl.BlockSpec((1, rows_p, v_dim), q_map),
            scratch_shapes=[pltpu.VMEM((rows_p, 1), jnp.float32),
                            pltpu.VMEM((rows_p, 1), jnp.float32),
                            pltpu.VMEM((rows_p, v_dim), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((s, rows_p, v_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name=name, interpret=interpret,
    )(jnp.asarray(t, jnp.int32), table, qr, *([c_pages] * g))
    return out[:, :rows].reshape(s, w_len, heads, v_dim)


def paged_latent_attention_reference(q, c_pages, t, table, *, v_dim: int,
                                     scale: float):
    """The same function by a gather of each slot's pages into a
    ``[S, C, P * page_len]`` view and one masked softmax: the kernel's
    oracle and the path off a TPU. Sentinel table entries clamp to the
    last page: garbage the ``<= t + j`` mask never admits."""
    s, w_len, _heads, c = q.shape
    view = c_pages[jnp.minimum(table, c_pages.shape[0] - 1)]
    view = view.transpose(0, 2, 1, 3).reshape(s, c, -1)  # [S, C, L]
    sc = jnp.einsum("swhc,scl->shwl", q.astype(view.dtype), view,
                    preferred_element_type=jnp.float32) * scale
    pos = (t[:, None] + jnp.arange(w_len))[:, None, :, None]
    sc = jnp.where(jnp.arange(view.shape[2])[None, None, None, :] <= pos,
                   sc, NEG_INF)
    w = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("shwl,svl->swhv", w.astype(view.dtype),
                      view[:, :v_dim],
                      preferred_element_type=jnp.float32)
