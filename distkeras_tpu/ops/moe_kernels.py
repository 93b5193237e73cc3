"""Fused Pallas MoE dispatch + grouped expert GEMM (round 6).

The round-5 restructure took the capacity dispatch to XLA's primitive
floor: one [K*N, d] drop/unique scatter builds the [E*C, d] HBM buffer,
one gather reads the combine — both measured at the chip's row-granular
permute rate (~85-110 GB/s, ~8x under streaming; docs/PERF.md SSMoE).
That floor exists because XLA has no primitive that CONSUMES a gather:
the dispatch buffer must round-trip HBM before the expert matmul reads
it. This module is the Pallas lever the round-5 VERDICT asked for
(MegaBlocks-style dropless grouping as prior art): fuse the gather INTO
the expert GEMM so the buffer never exists.

Structure (one ``custom_vjp`` op, ``moe_fused_experts``):

  * forward — ``_gather_gemm1``: grid ``(E, C/block_c)``; each program
    row-DMAs its capacity tile's tokens straight from the [N, d]
    residual stream in HBM into a contiguous VMEM tile (indices come
    from the SAME ``_dispatch_plan`` arrays the XLA path scatters with,
    inverted by one cheap int32 [E*C] scatter), then runs the expert's
    up-projection matmul + bias + activation on the MXU while the next
    rows stream in. Only the [E, C, H] activations touch HBM — the
    [K*N, d] broadcast source and [E*C, d] dispatch buffer of the XLA
    path never materialize. The down-projection stays the stacked
    einsum (measured round 5: the batched-dot emitter beats ragged_dot
    and unrolling there) and the combine stays the structured
    gather + reshape-sum.
  * backward — the combine's transpose is ALSO a gather: the cotangent
    row a buffer slot needs is ``g[src_tok[row]] * gate[row]``, the
    exact mirror of the forward's token gather. ``_bwd_dx`` re-gathers
    x and g per tile, recomputes the pre-activation (MegaBlocks-style
    recompute: FLOPs are cheaper than an [E, C, H] f32 residual),
    and emits ``dx``-rows, ``dz``, the per-row ``<y, g>`` dot the
    router gradient needs, and the gated cotangent ``gy`` — all
    row-granular traffic is a GATHER in both passes; the only scatters
    left anywhere are the two int32/f32 [E*C] plan inversions.
    ``_bwd_dw1`` accumulates ``dw1[e] += x_tile^T @ dz_tile`` across
    the capacity grid in a VMEM-resident f32 block.

What the row gather costs on the chip (first compiled by PR 22; until
then the kernels had only run interpreted): Mosaic slices a ref only
at multiples of its tiling, so rows are gathered from an ``[N, 1, d]``
32-bit view (``_gather_source``) — one XLA pass re-lays the residual
stream out, a bf16 stream is widened to f32 for it, and the gather
then moves 4 bytes per element instead of 2. Correct on the v5e,
forward and backward (``chip_smoke.py``); whether it beats the
``tokens`` path is not measured (ROADMAP S2).

Numerics contract: identical routing, drop, tie-break, and NaN-masking
semantics to ``dispatch="tokens"`` — both consume one ``_dispatch_plan``
and mask gathered rows with ``where(keep, ..., 0)`` BEFORE the gate
multiply. ``tests/test_moe_fused.py`` pins forward AND backward against
the ``dispatch="dense"`` oracle under ``interpret=True`` (the tier-1
CPU gate), including capacity drops and top-k ties.

Backend selection follows the repo-wide convention
(``compat.backend_is_tpu``, trace-time default backend — the documented
contract of ``models.decoding.generate``): on TPU the kernels compile
(``tests/test_tpu_compile.py`` holds them to that for a described
v5e); elsewhere ``MoE`` takes the XLA-floor ``tokens`` path unless a
test forces interpreter mode via ``force_interpret()``.
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.compat import backend_is_tpu
from distkeras_tpu.models.layers import get_activation

#: upper bound on the capacity-tile row count. 128 keeps the worst
#: kernel (``_bwd_dx``: w1 + w2 + h + dz + dxr + two gather tiles)
#: inside VMEM at the bench shape (d=1024, H=2048, bf16).
MAX_BLOCK_C = 128

_FORCE_INTERPRET = False


def _nbytes(shape, dtype) -> int:
    return math.prod(shape) * jnp.dtype(dtype).itemsize


def _compiler_params(pipelined: int, resident: int):
    """Grid = (expert, capacity tile); the capacity sweep carries the
    dw1 accumulator, so it stays sequential. Each kernel keeps a whole
    expert's weight block in VMEM, which at d=1024 / H=4096 is past
    Mosaic's 16 MiB default scoped limit ("Scoped allocation with size
    36.54M and limit 16.00M exceeded"), so the limit is stated from the
    shapes: ``pipelined`` bytes of BlockSpec blocks (double-buffered)
    plus ``resident`` bytes of scratch and f32 temporaries. A shape
    past the chip's VMEM is then the compiler's error, not a guess."""
    need = 2 * pipelined + resident + (4 << 20)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=max(16 << 20, need))


@contextlib.contextmanager
def force_interpret():
    """Run the fused kernels in Pallas interpreter mode regardless of
    backend — the CPU test suite's hook (tier-1 runs JAX_PLATFORMS=cpu,
    where the production path would fall back to ``tokens``)."""
    global _FORCE_INTERPRET
    prev = _FORCE_INTERPRET
    _FORCE_INTERPRET = True
    try:
        yield
    finally:
        _FORCE_INTERPRET = prev


def fused_supported() -> bool:
    """Whether ``dispatch="fused"`` should take the kernel path — the
    single gate ``MoE.apply`` consults (same trace-time convention as
    every Pallas-vs-XLA fork in this repo: ``compat.backend_is_tpu``)."""
    return _FORCE_INTERPRET or backend_is_tpu()


def kernel_capacity(capacity: int) -> int:
    """Per-expert row count as the KERNELS tile it: ``capacity`` rounded
    up to a multiple of 8 (Mosaic wants block second-to-last dims % 8 ==
    0 — the same rule ``decode_attention`` pads its G row axis for). The
    pad rows are real kernel rows but win no dispatch slot: their
    ``src_tok`` stays -1 (zeroed gather) and their gate 0, so they
    contribute exact zeros everywhere. Plan/combine indices stay in the
    UNPADDED ``e * capacity + pos`` space and are remapped at the op
    boundary (``_pad_slots``)."""
    return -(-int(capacity) // 8) * 8


def choose_block_c(capacity: int, cap: int = MAX_BLOCK_C) -> int:
    """Largest divisor of ``capacity`` <= cap, preferring multiples of 8
    (Mosaic's second-to-last-dim tiling rule; always satisfiable for the
    padded ``kernel_capacity`` row counts the fused op tiles). Divisor
    (not cdiv) tiling keeps every block fully in-bounds, so the
    row-gather loop needs no partial-tile masking (mirrors
    ``decode_attention``'s bh_block rounding)."""
    divs = [b for b in range(1, min(capacity, cap) + 1)
            if capacity % b == 0]
    mult8 = [b for b in divs if b % 8 == 0]
    return max(mult8 or divs)


def _slot_tokens(kn: int, k: int):
    """Choice-major slot->token map: ``tile(arange(N), K)`` (slot
    s = k*N + n), the same structure round 5's combine exploits."""
    return jnp.tile(jnp.arange(kn // k, dtype=jnp.int32), k)


# ---------------------------------------------------------------------------
# row gather: HBM -> contiguous VMEM tile, by prefetched plan indices
# ---------------------------------------------------------------------------

def _gather_source(a):
    """[N, d] rows -> the [N, 1, d] 32-bit view the row gather DMAs
    from. Mosaic slices a ref only at multiples of its tiling, and a
    2-D [N, d] ref tiles its row axis (8 rows f32, 16 bf16): a one-row
    ``src.at[tok]`` is refused ("Slice shape along dimension 0 must be
    aligned to tiling (8), but is 1"). With a singleton second-minor
    axis the tile is (1, 128) and the token index lands on an untiled
    leading axis — but only for 32-bit elements (a bf16 [N, 1, d] ref
    tiles (2, 128) and is refused the same way), so narrower inputs are
    widened here (exact) and narrowed back in VMEM (``_tile``)."""
    return a.astype(_gather_dtype(a.dtype))[:, None, :]


def _gather_dtype(dtype):
    return jnp.float32 if jnp.dtype(dtype).itemsize < 4 else dtype


def _gather_scratch(block_c: int, d: int, dtype):
    """VMEM landing tile matching :func:`_gather_source`'s view."""
    return pltpu.VMEM((block_c, 1, d), _gather_dtype(dtype))


def _tile(gathered, dtype):
    """The gathered [block_c, 1, d] VMEM tile as a [block_c, d] value in
    the compute dtype."""
    return gathered[:, 0, :].astype(dtype)


def _gather_tile(idx_ref, src_hbm, dst_vmem, sem, base, rows: int):
    """DMA ``rows`` arbitrary rows of ``src_hbm`` [N, 1, d] into the
    contiguous VMEM tile ``dst_vmem`` [rows, 1, d], indices
    ``idx_ref[base + r]`` (SMEM scalar prefetch). Start-all-then-wait-
    all: every row's DMA is in flight before the first wait, so the
    gather runs at the DMA engines' row rate rather than serial
    round-trip latency. Rows with index < 0 (capacity rows no slot won)
    are zeroed — their downstream garbage is masked by ``keep`` exactly
    as in the tokens path, but zeroing keeps the matmul operands
    finite."""

    def _start(r, carry):
        tok = idx_ref[base + r]

        @pl.when(tok >= 0)
        def _():
            pltpu.make_async_copy(src_hbm.at[tok], dst_vmem.at[r],
                                  sem).start()

        @pl.when(tok < 0)
        def _():
            dst_vmem[r] = jnp.zeros_like(dst_vmem[r])
        return carry

    def _wait(r, carry):
        tok = idx_ref[base + r]

        @pl.when(tok >= 0)
        def _():
            pltpu.make_async_copy(src_hbm.at[tok], dst_vmem.at[r],
                                  sem).wait()
        return carry

    lax.fori_loop(0, rows, _start, 0)
    lax.fori_loop(0, rows, _wait, 0)


# ---------------------------------------------------------------------------
# forward: gather + up-projection GEMM (+ bias + activation)
# ---------------------------------------------------------------------------

def _fwd_kernel(src_ref, x_ref, w1_ref, b1_ref, h_ref, xg, sem, *,
                block_c: int, capacity: int, act_name):
    e, c = pl.program_id(0), pl.program_id(1)
    _gather_tile(src_ref, x_ref, xg, sem, e * capacity + c * block_c,
                 block_c)
    z = jnp.dot(_tile(xg, w1_ref.dtype), w1_ref[0],
                preferred_element_type=jnp.float32) \
        + b1_ref[0].astype(jnp.float32)
    h_ref[0] = get_activation(act_name)(z).astype(h_ref.dtype)


def _gather_gemm1(xt, src_tok, w1, b1, *, capacity: int, block_c: int,
                  act_name: str, interpret: bool):
    """[N, d] tokens + plan indices -> [E, C, H] activated hidden tiles,
    no intermediate HBM buffer."""
    e, d, hid = w1.shape
    grid = (e, capacity // block_c)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),            # x [N, 1, d]
            pl.BlockSpec((1, d, hid), lambda e_, c_, *_: (e_, 0, 0)),
            pl.BlockSpec((1, 1, hid), lambda e_, c_, *_: (e_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_c, hid),
                               lambda e_, c_, *_: (e_, c_, 0)),
        scratch_shapes=[
            _gather_scratch(block_c, d, xt.dtype),
            pltpu.SemaphoreType.DMA,
        ])
    kernel = functools.partial(_fwd_kernel, block_c=block_c,
                               capacity=capacity, act_name=act_name)
    params = _compiler_params(
        _nbytes((d + 1, hid), w1.dtype) + _nbytes((block_c, hid), xt.dtype),
        _nbytes((block_c, d + 2 * hid), jnp.float32))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, capacity, hid), xt.dtype),
        compiler_params=params,
        name="moe_gather_gemm1", interpret=interpret,
    )(src_tok, _gather_source(xt), w1, b1.reshape(e, 1, hid))


# ---------------------------------------------------------------------------
# backward: the gather's transpose is another gather
# ---------------------------------------------------------------------------

def _bwd_dx_kernel(src_ref, x_ref, g_ref, w1_ref, w2_ref, b1_ref, b2_ref,
                   h_ref, rowg_ref, dxr_ref, dz_ref, gy_ref, rowdot_ref,
                   xg, gg, sem, *, block_c: int, capacity: int, act_name):
    """Per capacity tile: gather the OUTPUT cotangent rows its tokens
    received (the combine's transpose — a gather, because
    ``gy[row] = g[src_tok[row]] * gate[row]``), push them back through
    the expert MLP, and re-gather x to recompute the pre-activation."""
    e, c = pl.program_id(0), pl.program_id(1)
    base = e * capacity + c * block_c
    _gather_tile(src_ref, g_ref, gg, sem, base, block_c)
    _gather_tile(src_ref, x_ref, xg, sem, base, block_c)
    ggf = gg[:, 0, :].astype(jnp.float32)
    gy = ggf * rowg_ref[0]                                   # [BC, d] f32
    # router cotangent ingredient: per-row <y, g> (y recomputed from the
    # saved h tile — one extra MXU pass instead of an [E, C, d] residual)
    y = jnp.dot(h_ref[0], w2_ref[0], preferred_element_type=jnp.float32) \
        + b2_ref[0].astype(jnp.float32)
    rowdot_ref[0] = jnp.sum(y * ggf, axis=1, keepdims=True)
    # dh = gy @ w2^T (contract the d axes — no transpose materialized)
    dh = lax.dot_general(gy, w2_ref[0], (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    z = jnp.dot(_tile(xg, w1_ref.dtype), w1_ref[0],
                preferred_element_type=jnp.float32) \
        + b1_ref[0].astype(jnp.float32)
    _, dz = jax.jvp(get_activation(act_name), (z,), (dh,))
    dz_ref[0] = dz.astype(dz_ref.dtype)
    gy_ref[0] = gy.astype(gy_ref.dtype)
    dxr_ref[0] = lax.dot_general(
        dz, w1_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dxr_ref.dtype)


def _bwd_dx(xt, g, src_tok, row_gate, w1, b1, w2, b2, h, *,
            capacity: int, block_c: int, act_name: str, interpret: bool):
    e, d, hid = w1.shape
    grid = (e, capacity // block_c)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),            # x [N, 1, d]
            pl.BlockSpec(memory_space=pl.ANY),            # g [N, 1, d]
            pl.BlockSpec((1, d, hid), lambda e_, c_, *_: (e_, 0, 0)),
            pl.BlockSpec((1, hid, d), lambda e_, c_, *_: (e_, 0, 0)),
            pl.BlockSpec((1, 1, hid), lambda e_, c_, *_: (e_, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda e_, c_, *_: (e_, 0, 0)),
            pl.BlockSpec((1, block_c, hid),
                         lambda e_, c_, *_: (e_, c_, 0)),        # h
            pl.BlockSpec((1, block_c, 1),
                         lambda e_, c_, *_: (e_, c_, 0)),        # row gate
        ],
        out_specs=(
            pl.BlockSpec((1, block_c, d),
                         lambda e_, c_, *_: (e_, c_, 0)),        # dx rows
            pl.BlockSpec((1, block_c, hid),
                         lambda e_, c_, *_: (e_, c_, 0)),        # dz
            pl.BlockSpec((1, block_c, d),
                         lambda e_, c_, *_: (e_, c_, 0)),        # gy
            pl.BlockSpec((1, block_c, 1),
                         lambda e_, c_, *_: (e_, c_, 0)),        # <y, g>
        ),
        scratch_shapes=[
            _gather_scratch(block_c, d, xt.dtype),
            _gather_scratch(block_c, d, g.dtype),
            pltpu.SemaphoreType.DMA,
        ])
    kernel = functools.partial(_bwd_dx_kernel, block_c=block_c,
                               capacity=capacity, act_name=act_name)
    dt = xt.dtype
    params = _compiler_params(
        _nbytes((2 * d + 1, hid), w1.dtype) + _nbytes((1, d), w2.dtype)
        + _nbytes((block_c, 2 * hid + 2 * d + 2), dt),
        _nbytes((block_c, 5 * d + 3 * hid), jnp.float32))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((e, capacity, d), dt),
            jax.ShapeDtypeStruct((e, capacity, hid), dt),
            jax.ShapeDtypeStruct((e, capacity, d), dt),
            jax.ShapeDtypeStruct((e, capacity, 1), jnp.float32),
        ),
        compiler_params=params,
        name="moe_bwd_dx", interpret=interpret,
    )(src_tok, _gather_source(xt), _gather_source(g), w1, w2,
      b1.reshape(e, 1, hid), b2.reshape(e, 1, d), h,
      row_gate.reshape(e, capacity, 1))


def _bwd_dw1_kernel(src_ref, x_ref, dz_ref, dw1_ref, xg, sem, *,
                    block_c: int, capacity: int):
    e, c = pl.program_id(0), pl.program_id(1)
    _gather_tile(src_ref, x_ref, xg, sem, e * capacity + c * block_c,
                 block_c)

    @pl.when(c == 0)
    def _():
        dw1_ref[0] = jnp.zeros_like(dw1_ref[0])

    # dw1[e] += x_tile^T @ dz_tile (contract the capacity axes); the
    # [d, H] f32 accumulator stays VMEM-resident across the c grid
    dw1_ref[0] += lax.dot_general(
        _tile(xg, dz_ref.dtype), dz_ref[0], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _bwd_dw1(xt, dz, src_tok, *, capacity: int, block_c: int,
             interpret: bool):
    e = dz.shape[0]
    d = xt.shape[1]
    hid = dz.shape[2]
    grid = (e, capacity // block_c)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),            # x [N, 1, d]
            pl.BlockSpec((1, block_c, hid),
                         lambda e_, c_, *_: (e_, c_, 0)),        # dz
        ],
        out_specs=pl.BlockSpec((1, d, hid),
                               lambda e_, c_, *_: (e_, 0, 0)),
        scratch_shapes=[
            _gather_scratch(block_c, d, xt.dtype),
            pltpu.SemaphoreType.DMA,
        ])
    kernel = functools.partial(_bwd_dw1_kernel, block_c=block_c,
                               capacity=capacity)
    params = _compiler_params(
        _nbytes((block_c, hid), dz.dtype) + _nbytes((d, hid), jnp.float32),
        _nbytes((block_c + hid, d), jnp.float32))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, d, hid), jnp.float32),
        compiler_params=params,
        name="moe_bwd_dw1", interpret=interpret,
    )(src_tok, _gather_source(xt), dz)


# ---------------------------------------------------------------------------
# the op: custom VJP over the whole dispatched expert block
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def moe_fused_experts(act_name, capacity, block_c, interpret,
                      xt, w1, b1, w2, b2, sg, dest, keep):
    """Dispatch + expert MLP + combine with the fused-gather kernels.

    Positional statics (``nondiff_argnums``): activation name, expert
    capacity C, capacity tile rows, interpreter flag. Tensors: ``xt``
    [N, d] tokens (compute dtype), stacked expert weights
    ``w1`` [E, d, H] / ``b1`` [E, H] / ``w2`` [E, H, d] / ``b2`` [E, d],
    and the ``_dispatch_plan`` arrays ``sg``/``dest``/``keep`` [K*N]
    (choice-major slot order). Returns the combined [N, d] output; use
    ``fused_moe_apply`` for the keyword-friendly wrapper.
    """
    out, _ = _fused_fwd(act_name, capacity, block_c, interpret,
                        xt, w1, b1, w2, b2, sg, dest, keep)
    return out


def _pad_slots(dest, capacity: int, cap_k: int):
    """Remap plan slot ids ``e * capacity + pos`` into the padded kernel
    row space ``e * cap_k + pos``. Out-of-range sentinels (the dropped
    slot ``E * capacity`` and the EP-localization sentinels, both >=
    E * capacity) land >= E * cap_k and keep dropping/clamping exactly
    as before."""
    if cap_k == capacity:
        return dest
    return (dest // capacity) * cap_k + dest % capacity


def _fused_fwd(act_name, capacity, block_c, interpret,
               xt, w1, b1, w2, b2, sg, dest, keep):
    e = w1.shape[0]
    d = xt.shape[1]
    dt = xt.dtype
    cap_k = kernel_capacity(capacity)
    dest_k = _pad_slots(dest, capacity, cap_k)
    src_tok = jnp.full((e * cap_k,), -1, jnp.int32).at[dest_k].set(
        _slot_tokens(dest.shape[0], dest.shape[0] // xt.shape[0]),
        mode="drop", unique_indices=True)
    sgk = jnp.where(keep, sg, 0.0).astype(jnp.float32)
    row_gate = jnp.zeros((e * cap_k,), jnp.float32).at[dest_k].set(
        sgk, mode="drop", unique_indices=True)
    h = _gather_gemm1(xt, src_tok, w1, b1, capacity=cap_k,
                      block_c=block_c, act_name=act_name,
                      interpret=interpret)
    # down-projection: the stacked batched dot (measured round 5: beats
    # ragged_dot and static unrolling on this chip/XLA) ...
    y = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]
    # ... and the round-5 structured combine: gather is the CHEAP
    # direction; where-mask BEFORE the gate multiply (NaN contract, see
    # models/moe.py)
    ye_flat = y.reshape(e * cap_k, d)
    safe = jnp.where(keep[:, None], ye_flat[dest_k], jnp.zeros((), dt))
    contrib = safe * sg[:, None].astype(dt)
    kk = dest.shape[0] // xt.shape[0]
    out = contrib.reshape(kk, xt.shape[0], d).sum(axis=0)
    return out, (xt, w1, b1, w2, b2, sg, dest, keep, src_tok, row_gate, h)


def _fused_bwd(act_name, capacity, block_c, interpret, res, g):
    xt, w1, b1, w2, b2, sg, dest, keep, src_tok, row_gate, h = res
    e = w1.shape[0]
    n, d = xt.shape
    kk = dest.shape[0] // n
    cap_k = kernel_capacity(capacity)
    dest_k = _pad_slots(dest, capacity, cap_k)
    gt = g.astype(xt.dtype)
    dxr, dz, gy, rowdot = _bwd_dx(
        xt, gt, src_tok, row_gate, w1, b1, w2, b2, h,
        capacity=cap_k, block_c=block_c, act_name=act_name,
        interpret=interpret)
    # slot cotangents: both transposes are gathers of the per-row kernel
    # outputs (clamped OOB rows masked by keep, as in forward)
    dxr_flat = dxr.reshape(e * cap_k, d)
    dx_slots = jnp.where(keep[:, None], dxr_flat[dest_k],
                         jnp.zeros((), dxr.dtype))
    dx = dx_slots.reshape(kk, n, d).sum(axis=0)
    dsg = jnp.where(keep, rowdot.reshape(e * cap_k)[dest_k], 0.0)
    # weight cotangents: dw1 in-kernel (needs the gathered x tiles);
    # dw2/db2/db1 are plain stacked contractions of kernel outputs
    dw1 = _bwd_dw1(xt, dz, src_tok, capacity=cap_k, block_c=block_c,
                   interpret=interpret)
    db1 = dz.astype(jnp.float32).sum(axis=1)
    dw2 = jnp.einsum("ech,ecd->ehd", h.astype(jnp.float32),
                     gy.astype(jnp.float32))
    db2 = gy.astype(jnp.float32).sum(axis=1)
    return (dx.astype(xt.dtype), dw1.astype(w1.dtype),
            db1.astype(b1.dtype), dw2.astype(w2.dtype),
            db2.astype(b2.dtype), dsg.astype(sg.dtype), None, None)


moe_fused_experts.defvjp(_fused_fwd, _fused_bwd)


def fused_moe_apply(xt, w1, b1, w2, b2, sg, dest, keep, *,
                    capacity: int, activation: str = "gelu",
                    block_c: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Keyword-friendly entry: resolve the static knobs, then call the
    custom-VJP op. ``interpret=None`` resolves by the repo backend
    convention (interpreter anywhere that is not a TPU — callers that
    want the XLA fallback instead must gate on ``fused_supported()``,
    which is what ``MoE.apply`` does)."""
    if interpret is None:
        interpret = _FORCE_INTERPRET or not backend_is_tpu()
    if block_c is None:
        # tile the PADDED row count (multiple of 8): any capacity —
        # odd, prime, 1 — gets a Mosaic-legal %8 tile
        block_c = choose_block_c(kernel_capacity(capacity))
    if not callable(activation) and activation is not None:
        get_activation(activation)    # fail early on unknown names
    return moe_fused_experts(activation, int(capacity), int(block_c),
                             bool(interpret), xt, w1, b1, w2, b2,
                             sg, dest, keep)


# --- grouped experts: drop-free, rows sorted by expert (serving) -------------
#
# Many small experts with top-k of many (top-8 of 128 at width 768) make
# the capacity construction above a poor fit for serving: a capacity that
# never drops is every row (E x n rows of work for k x n of routing), and
# dense routing computes all E experts for every token. The grouped form
# lays the ``A = n * k`` routed rows out sorted by expert, each expert's
# group padded up to whole tiles of ``block_rows`` rows, and runs one
# program per tile against that tile's expert: every routed row is
# computed exactly once, nothing is dropped whatever shares the batch,
# only experts that own a row are read, and an expert whose rows span
# several consecutive tiles is read once (the block index does not
# change between them). The padding is stated: at most ``E`` tiles' worth
# of rows beyond ``A`` (``grouped_tiles``), whose programs are skipped.


def grouped_block_rows(assignments: int, num_experts: int) -> int:
    """Rows per tile for ``assignments`` routed rows over
    ``num_experts``: the power of two nearest above the mean group,
    held to [16, 128] (16 is a bf16 sublane tile, 128 the MXU's)."""
    mean = max(1, -(-int(assignments) // int(num_experts)))
    return int(min(128, max(16, 1 << (mean - 1).bit_length())))


def grouped_tiles(assignments: int, num_experts: int,
                  block_rows: int) -> int:
    """The static tile count: ``sum_e ceil(c_e / block_rows)`` never
    passes ``A // block_rows + E`` whatever the routing."""
    return int(assignments) // int(block_rows) + int(num_experts)


def grouped_layout(flat_experts, num_experts: int, block_rows: int,
                   valid=None):
    """Where each routed row goes. ``flat_experts`` [A] int32 is the
    expert of every assignment (token-major: assignment ``a`` belongs to
    token ``a // k``). Returns ``(dest [A], tile_expert [T], used,
    counts [E])``: the row of each assignment in the sorted, tile-padded
    layout; the expert each tile computes (tiles past the ``used`` ones
    repeat the last live expert, so they fetch nothing new); the number
    of live tiles; assignments per expert. No sort: the position inside
    an expert's group is an exclusive running count (the
    ``models.moe._dispatch_plan`` construction).

    ``valid`` ([A] bool; a layer that holds ``num_experts`` of the
    experts its router chooses among): assignments whose expert is
    here. The others carry the id ``num_experts``, take no row in the
    layout and get a ``dest`` past its end (each its own: a scatter
    through ``dest`` drops them, a gather has to mask them)."""
    a = flat_experts.shape[0]
    tiles = grouped_tiles(a, num_experts, block_rows)
    onehot = jax.nn.one_hot(flat_experts, num_experts, dtype=jnp.int32)
    ranks = jnp.cumsum(onehot, axis=0) - onehot
    if valid is not None:
        flat_experts = jnp.minimum(flat_experts, num_experts - 1)
    pos = jnp.take_along_axis(ranks, flat_experts[:, None], axis=1)[:, 0]
    counts = onehot.sum(axis=0)
    padded = -(-counts // block_rows) * block_rows
    ends = jnp.cumsum(padded)
    dest = (ends - padded)[flat_experts] + pos
    if valid is not None:
        dest = jnp.where(valid, dest, tiles * block_rows
                         + jnp.arange(a, dtype=dest.dtype))
    used = ends[-1] // block_rows
    last_live = jnp.max(jnp.where(counts > 0,
                                  jnp.arange(num_experts), 0))
    tile_expert = jnp.searchsorted(
        ends, jnp.arange(tiles, dtype=ends.dtype) * block_rows,
        side="right")
    tile_expert = jnp.minimum(tile_expert, last_live).astype(jnp.int32)
    return dest.astype(jnp.int32), tile_expert, used.astype(jnp.int32), \
        counts


def _grouped_kernel(te_ref, used_ref, *refs, act_name: str, gated: bool):
    if gated:
        x_ref, w1_ref, w3_ref, w2_ref, o_ref = refs
    else:
        x_ref, w1_ref, w2_ref, o_ref = refs
        w3_ref = None
    live = pl.program_id(0) < used_ref[0]

    @pl.when(live)
    def _compute():
        x = x_ref[...]
        h = get_activation(act_name)(
            jnp.dot(x, w1_ref[0], preferred_element_type=jnp.float32))
        if gated:
            h = h * jnp.dot(x, w3_ref[0],
                            preferred_element_type=jnp.float32)
        o_ref[...] = jnp.dot(h.astype(x.dtype), w2_ref[0],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _skip():
        o_ref[...] = jnp.zeros_like(o_ref)


#: the most VMEM a grouped tile's double-buffered expert blocks may take
#: before the expert's hidden width is walked in blocks
GROUPED_WEIGHT_VMEM = 48 << 20


def grouped_hidden_block(d: int, f: int, gated: bool, dtype) -> int:
    """The block of an expert's hidden width ``f`` that one program of
    :func:`grouped_experts` holds: all of it where the expert's matrices
    fit VMEM double-buffered (``GROUPED_WEIGHT_VMEM``), else the largest
    multiple of 128 dividing ``f`` that does."""
    per_column = (3 if gated else 2) * _nbytes((d,), dtype)
    if 2 * per_column * f <= GROUPED_WEIGHT_VMEM or f % 128:
        return f
    fits = [b for b in range(128, f, 128)
            if f % b == 0 and 2 * per_column * b <= GROUPED_WEIGHT_VMEM]
    return max(fits) if fits else 128


def _grouped_kernel_blocks(te_ref, used_ref, *refs, act_name: str,
                           gated: bool):
    """As ``_grouped_kernel`` with the expert's hidden width walked in
    blocks along a second, innermost grid axis: each step adds its
    block's share of the down-projection to a float32 accumulator, the
    last one writes the tile."""
    if gated:
        x_ref, w1_ref, w3_ref, w2_ref, o_ref, acc_ref = refs
    else:
        x_ref, w1_ref, w2_ref, o_ref, acc_ref = refs
        w3_ref = None
    fi, nf = pl.program_id(1), pl.num_programs(1)
    live = pl.program_id(0) < used_ref[0]

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _compute():
        x = x_ref[...]
        h = get_activation(act_name)(
            jnp.dot(x, w1_ref[0], preferred_element_type=jnp.float32))
        if gated:
            h = h * jnp.dot(x, w3_ref[0],
                            preferred_element_type=jnp.float32)
        acc_ref[...] += jnp.dot(h.astype(x.dtype), w2_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(fi == nf - 1)
    def _write():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _grouped_experts_blocks(x_rows, tile_expert, used, w1, w2, w3, *,
                            block_rows: int, block_f: int, activation: str,
                            interpret: bool):
    m, d = x_rows.shape
    f = w1.shape[2]
    tiles, nf = m // block_rows, f // block_f
    gated = w3 is not None

    def x_map(t, fi, te, used):
        return (jnp.minimum(t, jnp.maximum(used[0] - 1, 0)), 0)

    def f_of(t, fi, used):
        # a tile past the live ones stays on the last block read: it
        # fetches nothing new
        return jnp.where(t < used[0], fi, nf - 1)

    def up_map(t, fi, te, used):
        return (te[t], 0, f_of(t, fi, used))

    def down_map(t, fi, te, used):
        return (te[t], f_of(t, fi, used), 0)

    in_specs = [pl.BlockSpec((block_rows, d), x_map),
                pl.BlockSpec((1, d, block_f), up_map)]
    operands = [x_rows, w1]
    if gated:
        in_specs.append(pl.BlockSpec((1, d, block_f), up_map))
        operands.append(w3)
    in_specs.append(pl.BlockSpec((1, block_f, d), down_map))
    operands.append(w2)
    wdt, xdt = w1.dtype, x_rows.dtype
    pipelined = ((3 if gated else 2) * _nbytes((d, block_f), wdt)
                 + 2 * _nbytes((block_rows, d), xdt))
    resident = (3 * _nbytes((block_rows, block_f), jnp.float32)
                + 2 * _nbytes((block_rows, d), jnp.float32))
    need = 2 * pipelined + resident + (4 << 20)
    return pl.pallas_call(
        functools.partial(_grouped_kernel_blocks, act_name=activation,
                          gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(tiles, nf), in_specs=in_specs,
            out_specs=pl.BlockSpec((block_rows, d),
                                   lambda t, fi, te, used: (t, 0)),
            scratch_shapes=[pltpu.VMEM((block_rows, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((m, d), xdt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(16 << 20, need)),
        name="moe_grouped_experts", interpret=interpret,
    )(tile_expert, jnp.reshape(used, (1,)).astype(jnp.int32), *operands)


def grouped_experts(x_rows, tile_expert, used, w1, w2, w3=None, *,
                    block_rows: int, activation: str,
                    interpret: Optional[bool] = None,
                    block_f: Optional[int] = None):
    """``act(x w1[e]) (* x w3[e]) w2[e]`` for rows already laid out by
    :func:`grouped_layout`: ``x_rows`` [T * block_rows, d], stacked
    expert weights ``w1``/``w3`` [E, d, f], ``w2`` [E, f, d] (no
    biases). One program per tile, the tile's expert chosen through the
    scalar-prefetched ``tile_expert``; tiles at or past ``used`` write
    zeros and read nothing new. A whole expert (three matrices) sits
    in VMEM, double-buffered: the scoped limit is stated from the
    shapes (``_compiler_params``). An expert too large for that
    (``grouped_hidden_block``; ``block_f`` overrides it) has its hidden
    width walked in blocks by the same tile's further programs."""
    m, d = x_rows.shape
    e, _, f = w1.shape
    tiles = m // block_rows
    gated = w3 is not None
    if interpret is None:
        interpret = _FORCE_INTERPRET or not backend_is_tpu()
    if block_f is None:
        block_f = grouped_hidden_block(d, f, gated, w1.dtype)
    if block_f != f:
        if f % block_f:
            raise ValueError(f"block_f {block_f} does not divide the "
                             f"experts' width {f}")
        return _grouped_experts_blocks(
            x_rows, tile_expert, used, w1, w2, w3, block_rows=block_rows,
            block_f=block_f, activation=activation, interpret=interpret)

    def x_map(t, te, used):
        return (jnp.minimum(t, jnp.maximum(used[0] - 1, 0)), 0)

    def w_map(t, te, used):
        return (te[t], 0, 0)

    in_specs = [pl.BlockSpec((block_rows, d), x_map),
                pl.BlockSpec((1, d, f), w_map)]
    operands = [x_rows, w1]
    if gated:
        in_specs.append(pl.BlockSpec((1, d, f), w_map))
        operands.append(w3)
    in_specs.append(pl.BlockSpec((1, f, d), w_map))
    operands.append(w2)
    wdt, xdt = w1.dtype, x_rows.dtype
    pipelined = ((3 if gated else 2) * _nbytes((d, f), wdt)
                 + 2 * _nbytes((block_rows, d), xdt))
    resident = (3 * _nbytes((block_rows, f), jnp.float32)
                + _nbytes((block_rows, d), jnp.float32))
    need = 2 * pipelined + resident + (4 << 20)
    return pl.pallas_call(
        functools.partial(_grouped_kernel, act_name=activation,
                          gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(tiles,), in_specs=in_specs,
            out_specs=pl.BlockSpec((block_rows, d),
                                   lambda t, te, used: (t, 0))),
        out_shape=jax.ShapeDtypeStruct((m, d), xdt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(16 << 20, need)),
        name="moe_grouped_experts", interpret=interpret,
    )(tile_expert, jnp.reshape(used, (1,)).astype(jnp.int32), *operands)


def grouped_experts_reference(x_rows, tile_expert, used, w1, w2, w3=None,
                              *, block_rows: int, activation: str):
    """The same product in plain XLA, tile by tile with each tile's
    expert gathered: the off-TPU path and the kernel's oracle (it
    gathers ``T`` whole experts, so it is for small shapes)."""
    m, d = x_rows.shape
    tiles = m // block_rows
    xt = x_rows.reshape(tiles, block_rows, d)
    act = get_activation(activation)
    h = act(jnp.einsum("tbd,tdf->tbf", xt, w1[tile_expert],
                       preferred_element_type=jnp.float32))
    if w3 is not None:
        h = h * jnp.einsum("tbd,tdf->tbf", xt, w3[tile_expert],
                           preferred_element_type=jnp.float32)
    y = jnp.einsum("tbf,tfd->tbd", h.astype(x_rows.dtype), w2[tile_expert],
                   preferred_element_type=jnp.float32)
    live = (jnp.arange(tiles) < used)[:, None, None]
    return jnp.where(live, y, 0.0).astype(x_rows.dtype).reshape(m, d)
