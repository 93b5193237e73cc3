"""State-space (Mamba-2) kernels: the chunked SSD scan that a prefill
chunk runs, and the one-token state update that a decode step runs.

A Mamba-2 mixer keeps, per sequence and head ``h``, a state ``S`` of
``[P, N]`` (head width by state width) and updates it one position at a
time::

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t + D_h x_t

with ``B_t`` / ``C_t`` of ``N`` shared by every head (one group), ``dt``
the softplus of the projected step plus its bias, ``A_h = -exp(A_log_h)``.
``x | B | C`` come out of a causal depthwise convolution of width ``K``
over the projected ``xBC`` channels, followed by SiLU; a cache keeps the
last ``K - 1`` inputs of that convolution beside the state.

``ssd_chunk_scan`` (prefill): the chunked dual form. Positions go in
chunks of ``CHUNK``; inside a chunk the output is one masked product
``y = (C B^T * decay * dt) x`` plus the state carried in,
``exp(cumsum(dt A)) C S^T``, and the state leaves the chunk as
``exp(sum(dt A)) S + (x * dt * decay_to_end)^T B``. The Pallas kernel
runs a grid of (head block, chunk) with the state of a head block held
in VMEM from one chunk to the next; everything is laid out so that each
product is a plain or a right-transposed matrix product (``x`` and ``y``
head-major, ``[H * P, positions]``). Products of ``C B^T`` and of the
masked mix with ``x`` take bfloat16 operands; those that read or write
the state are float32 (``HIGHEST``). A padded position has ``dt = 0``:
it leaves the state as it found it.

``ssm_decode_step`` (decode): the convolution over the window and the
new input, the window's shift, and then the Pallas kernel: one read and
one write of every slot's state (aliased in place), a grid of (slot,
head block), the update and the readout of each head. A slot whose
position is not live (the engine's free or prefilling sentinel, ``t >=
live_len``) gets its state and its window back bit for bit.

Both entry points take the raw projection (``xBC`` before the
convolution, ``dt`` before its bias and softplus) and return the
readout ``y`` (with ``D x``, before the output gate) in float32, the new
state and the new window. ``kernel``: None follows the repository's
convention (the Pallas kernel on a TPU, the XLA reference elsewhere;
``force_interpret()`` runs the kernel in interpreter mode anywhere),
True forces the kernel (interpreted off the TPU), False the reference.
The references (``ssd_scan_reference``, ``ssm_step_reference``) are the
sequential recurrence in float32, and the kernels' oracles.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.compat import backend_is_tpu, note_path

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
#: positions of one chunk of the SSD scan
CHUNK = 256
#: heads a program of the prefill scan takes (its x / y rows: 8 x P)
SCAN_HEADS = 8
#: heads a program of the decode step takes (a [16, P, N] state block)
STEP_HEADS = 16

_FORCE_INTERPRET = False


@contextlib.contextmanager
def force_interpret():
    """Run the kernels in Pallas interpreter mode whatever the backend
    (the CPU tests' hook)."""
    global _FORCE_INTERPRET
    prev = _FORCE_INTERPRET
    _FORCE_INTERPRET = True
    try:
        yield
    finally:
        _FORCE_INTERPRET = prev


def _use_kernel(kernel: Optional[bool]):
    """``(take the kernel, interpret it)`` for the tri-state ``kernel``."""
    if kernel is None:
        kernel = _FORCE_INTERPRET or backend_is_tpu()
    return bool(kernel), not backend_is_tpu()


def causal_conv(xbc, window, w, b):
    """The depthwise causal convolution of ``xbc`` [B, Q, C] behind the
    ``K - 1`` inputs of ``window`` [B, K-1, C], taps ``w`` [K, C] (tap
    ``K - 1`` multiplies the current input) and bias ``b`` [C], then
    SiLU, in float32. Returns ``(out [B, Q, C] float32, window)``: the
    new window is the last ``K - 1`` inputs, in the window's type."""
    k, q = w.shape[0], xbc.shape[1]
    full = jnp.concatenate([window.astype(F32), xbc.astype(F32)], axis=1)
    wf = w.astype(F32)
    out = b.astype(F32) + sum(full[:, i:i + q] * wf[i] for i in range(k))
    return jax.nn.silu(out), full[:, q:].astype(window.dtype)


def _split(xs, heads: int, d_state: int):
    """``x | B | C`` of the convolved channels: ``x`` [..., H, P]."""
    hp = xs.shape[-1] - 2 * d_state
    x = xs[..., :hp].reshape(xs.shape[:-1] + (heads, hp // heads))
    return x, xs[..., hp:hp + d_state], xs[..., hp + d_state:]


def _decay(dt, dt_bias, a_log):
    """``(dt after its bias and softplus, A)`` in float32."""
    return (jax.nn.softplus(dt.astype(F32) + dt_bias.astype(F32)),
            -jnp.exp(a_log.astype(F32)))


# --- the references: the sequential recurrence ------------------------------

def ssd_scan_reference(x, bm, cm, dt, a, d, state):
    """The recurrence position by position (``lax.scan``), float32.
    ``x`` [B, Q, H, P], ``bm``/``cm`` [B, Q, N], ``dt`` [B, Q, H] (after
    softplus), ``a``/``d`` [H], ``state`` [B, H, P, N]. Returns ``(y
    [B, Q, H, P], state)``."""
    def step(s, inp):
        xt, bt, ct, dtt = inp                   # [B,H,P] [B,N] [B,N] [B,H]
        s = s * jnp.exp(dtt * a)[..., None, None] \
            + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :]
        y = jnp.einsum("bhpn,bn->bhp", s, ct, precision=HIGHEST) \
            + d[:, None] * xt
        return s, y

    move = lambda v: jnp.moveaxis(v.astype(F32), 1, 0)
    state, y = jax.lax.scan(step, state.astype(F32),
                            (move(x), move(bm), move(cm), move(dt)))
    return jnp.moveaxis(y, 0, 1), state


def ssm_step_reference(x, bm, cm, dt, a, d, state, live):
    """One position of the recurrence for every slot: ``x`` [S, H, P],
    ``bm``/``cm`` [S, N], ``dt`` [S, H], ``live`` [S] bool (a slot that
    is not keeps its state and reads 0). Returns ``(y [S, H, P],
    state)``."""
    y, new = ssd_scan_reference(x[:, None], bm[:, None], cm[:, None],
                                dt[:, None], a, d, state)
    return (jnp.where(live[:, None, None], y[:, 0], 0.0),
            jnp.where(live[:, None, None, None], new, state))


# --- prefill: the chunked scan ----------------------------------------------

def _scan_kernel(xt_ref, dtt_ref, lat_ref, b_ref, c_ref, s0_ref,
                 yt_ref, s1_ref, s_scr, *, heads: int, p: int):
    """One (head block, chunk) program. Refs (leading batch axis 1):
    ``xt`` [HB*P, L] (head-major x), ``dtt`` / ``lat`` [HB, L] (dt and
    dt*A of each head, positions in the lanes), ``b`` / ``c`` [L, N],
    ``s0`` / ``s1`` [HB, P, N]; ``s_scr`` carries the state from one
    chunk to the next."""
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        s_scr[...] = s0_ref[0]

    length = b_ref.shape[1]
    bm, cm = b_ref[0], c_ref[0]                                 # [L, N]
    row = jax.lax.broadcasted_iota(jnp.int32, (length, length), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (length, length), 1)
    causal = col <= row                                          # s <= t
    nt = (((1,), (1,)), ((), ()))
    # G[t, s] = C_t . B_s, shared by every head (one group)
    g = jax.lax.dot_general(cm.astype(jnp.bfloat16), bm.astype(jnp.bfloat16),
                            nt, preferred_element_type=F32)
    lat = lat_ref[0]                                             # [HB, L]
    # inclusive cumulative sums of dt*A along the chunk, as rows [HB, L]
    # and as columns [L, HB] (two products with a triangle of ones)
    tri = causal.astype(F32)
    cum_rows = jax.lax.dot_general(lat, tri, nt, precision=HIGHEST,
                                   preferred_element_type=F32)
    cum_cols = jax.lax.dot_general(tri, lat, nt, precision=HIGHEST,
                                   preferred_element_type=F32)
    # each head's sum over the chunk, repeated along the lanes ([HB, L]
    # and [HB, N]): the decay to the chunk's end
    tot = lambda width: jnp.dot(lat, jnp.ones((length, width), F32),
                                precision=HIGHEST,
                                preferred_element_type=F32)
    tot_l, tot_n = tot(length), tot(bm.shape[1])
    for j in range(heads):
        rows = slice(j * p, (j + 1) * p)
        xt = xt_ref[0, rows, :]                                  # [P, L]
        dt_row = dtt_ref[0, j:j + 1, :]                          # [1, L]
        cr = cum_rows[j:j + 1, :]                                # [1, L]
        cc = cum_cols[:, j:j + 1]                                # [L, 1]
        decay = jnp.exp(jnp.where(causal, cc - cr, -jnp.inf))    # [L, L]
        mix = (g * decay * dt_row).astype(jnp.bfloat16)
        # y^T[p, t] = sum_s x^T[p, s] M[t, s], plus the state carried in
        y = jax.lax.dot_general(xt.astype(jnp.bfloat16), mix, nt,
                                preferred_element_type=F32)
        s = s_scr[j]                                             # [P, N]
        y = y + jnp.exp(cr) * jax.lax.dot_general(
            s, cm, nt, precision=HIGHEST, preferred_element_type=F32)
        yt_ref[0, rows, :] = y
        w = xt.astype(F32) * (dt_row * jnp.exp(tot_l[j:j + 1] - cr))
        s_scr[j] = jnp.exp(tot_n[j:j + 1]) * s + jnp.dot(
            w, bm, precision=HIGHEST, preferred_element_type=F32)

    @pl.when(ci == pl.num_programs(2) - 1)
    def _():
        s1_ref[0] = s_scr[...]


def _scan_call(xt, dtt, lat, bm, cm, state, *, heads_per_block: int,
               chunk: int, interpret: bool):
    bsz, hp, qp = xt.shape
    h, p, n = state.shape[1:]
    hb = min(heads_per_block, h)
    if h % hb:
        raise ValueError(f"heads_per_block {hb} does not divide {h} heads")
    grid = (bsz, h // hb, qp // chunk)
    kernel = functools.partial(_scan_kernel, heads=hb, p=p)
    by_head = lambda bi, hi, ci: (bi, hi, ci)
    by_pos = lambda bi, hi, ci: (bi, ci, 0)
    st = lambda bi, hi, ci: (bi, hi, 0, 0)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, hb * p, chunk), by_head),
                  pl.BlockSpec((1, hb, chunk), by_head),
                  pl.BlockSpec((1, hb, chunk), by_head),
                  pl.BlockSpec((1, chunk, n), by_pos),
                  pl.BlockSpec((1, chunk, n), by_pos),
                  pl.BlockSpec((1, hb, p, n), st)],
        out_specs=[pl.BlockSpec((1, hb * p, chunk), by_head),
                   pl.BlockSpec((1, hb, p, n), st)],
        out_shape=[jax.ShapeDtypeStruct((bsz, hp, qp), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        scratch_shapes=[pltpu.VMEM((hb, p, n), F32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="ssd_chunk_scan", interpret=interpret,
    )(xt, dtt, lat, bm, cm, state)


def ssd_chunk_scan(xbc, dt, window, state, conv_w, conv_b, dt_bias, a_log,
                   d, *, heads: int, d_state: int, chunk: int = CHUNK,
                   heads_per_block: int = SCAN_HEADS,
                   kernel: Optional[bool] = None):
    """A prefill chunk of ``Q`` positions through a Mamba-2 mixer's
    convolution and scan. ``xbc`` [B, Q, C] (``C = heads * P + 2 *
    d_state``, the projection's ``x | B | C`` before the convolution),
    ``dt`` [B, Q, H] (before bias and softplus), ``window`` [B, K-1, C]
    (the inputs before the chunk), ``state`` [B, H, P, N] float32 (the
    state before it). Returns ``(y [B, Q, H, P] float32, state,
    window)``: ``y`` includes ``D x`` and not the gate. ``Q`` is padded
    to a multiple of ``chunk`` inside (``dt = 0`` there)."""
    xs, window = causal_conv(xbc, window, conv_w, conv_b)
    x, bm, cm = _split(xs, heads, d_state)
    dt, a = _decay(dt, dt_bias, a_log)
    d = d.astype(F32)
    use, interpret = _use_kernel(kernel)
    if not use:
        note_path("ssd", "xla_reference")
        y, state = ssd_scan_reference(x, bm, cm, dt, a, d, state)
        return y, state, window
    note_path("ssd", "kernel")
    bsz, q, h, p = x.shape
    qp = -(-q // chunk) * chunk
    pad = lambda v: jnp.pad(v, [(0, 0), (0, qp - q)]
                            + [(0, 0)] * (v.ndim - 2))
    xt = pad(x).reshape(bsz, qp, h * p).transpose(0, 2, 1)
    dtt = pad(dt).transpose(0, 2, 1)
    yt, state = _scan_call(xt, dtt, dtt * a[:, None], pad(bm), pad(cm),
                           state.astype(F32),
                           heads_per_block=heads_per_block, chunk=chunk,
                           interpret=interpret)
    y = yt.transpose(0, 2, 1)[:, :q].reshape(bsz, q, h, p)
    return y + d[:, None] * x, state, window


# --- decode: one position of every slot --------------------------------------

def _step_kernel(t_ref, dt_ref, a_ref, x_ref, b_ref, c_ref, s_ref,
                 y_ref, o_ref, *, heads: int, n_heads: int, live_len: int):
    """One (slot, head block) program. Scalars (SMEM): ``t`` [S], ``dt``
    [S * H] (after softplus), ``a`` [H]. Refs: ``x`` [1, HB, P] (the
    convolved x, a row a head), ``b`` / ``c`` [1, 1, N], ``s`` / ``o``
    [1, HB, P, N] (the state in and out, one buffer), ``y`` [1, HB, P]."""
    si, hi = pl.program_id(0), pl.program_id(1)
    live = t_ref[si] < live_len

    @pl.when(live)
    def _():
        p = x_ref.shape[2]
        nt = (((1,), (1,)), ((), ()))
        eye = (jax.lax.broadcasted_iota(jnp.int32, (p, p), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (p, p), 1)).astype(F32)
        brow = b_ref[0]                                          # [1, N]
        c8 = jnp.broadcast_to(c_ref[0], (8, c_ref.shape[2]))     # [8, N]
        for j in range(heads):
            h = hi * heads + j
            dt = dt_ref[si * n_heads + h]
            x8 = jnp.broadcast_to(x_ref[0, j:j + 1, :], (8, p))  # [8, P]
            # the head's x as a column [P, 1]: a product with the identity
            xcol = jax.lax.dot_general(eye, x8, nt, precision=HIGHEST,
                                       preferred_element_type=F32)[:, :1]
            s = s_ref[0, j] * jnp.exp(dt * a_ref[h]) + (dt * xcol) * brow
            o_ref[0, j] = s
            # y[p] = sum_n S[p, n] C[n], as a row
            y_ref[0, j:j + 1, :] = jax.lax.dot_general(
                c8, s, nt, precision=HIGHEST,
                preferred_element_type=F32)[:1]

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros(y_ref.shape, F32)


def _step_call(t, dt, a, x, bm, cm, state, *, live_len: int,
               heads_per_block: int, interpret: bool):
    s, h, p, n = state.shape
    hb = min(heads_per_block, h)
    if h % hb:
        raise ValueError(f"heads_per_block {hb} does not divide {h} heads")
    kernel = functools.partial(_step_kernel, heads=hb, n_heads=h,
                               live_len=int(live_len))
    by_head = lambda si, hi, *_: (si, hi, 0)
    shared = lambda si, hi, *_: (si, 0, 0)
    st = lambda si, hi, *_: (si, hi, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(s, h // hb),
        in_specs=[pl.BlockSpec((1, hb, p), by_head),
                  pl.BlockSpec((1, 1, n), shared),
                  pl.BlockSpec((1, 1, n), shared),
                  pl.BlockSpec((1, hb, p, n), st)],
        out_specs=[pl.BlockSpec((1, hb, p), by_head),
                   pl.BlockSpec((1, hb, p, n), st)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, h, p), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        # operand 6 (the state, counting the three scalars) is output 1
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="ssm_decode_step", interpret=interpret,
    )(t, dt, a, x, bm, cm, state)


def ssm_decode_step(xbc, dt, window, state, conv_w, conv_b, dt_bias, a_log,
                    d, t, *, heads: int, d_state: int, live_len: int,
                    heads_per_block: int = STEP_HEADS,
                    kernel: Optional[bool] = None):
    """One position of every slot through a Mamba-2 mixer's convolution
    and state. ``xbc`` [S, C], ``dt`` [S, H] (raw), ``window`` [S, K-1,
    C], ``state`` [S, H, P, N] float32, ``t`` [S] the positions (a slot
    at ``t >= live_len`` is not decoding: its state and window come back
    as they went in). Returns ``(y [S, H, P] float32, state, window)``."""
    xs, new_window = causal_conv(xbc[:, None], window, conv_w, conv_b)
    x, bm, cm = _split(xs[:, 0], heads, d_state)
    dt, a = _decay(dt, dt_bias, a_log)
    d = d.astype(F32)
    live = t < live_len
    window = jnp.where(live[:, None, None], new_window, window)
    use, interpret = _use_kernel(kernel)
    if not use:
        note_path("ssm", "xla_reference")
        y, state = ssm_step_reference(x, bm, cm, dt, a, d, state, live)
        return y, state, window
    note_path("ssm", "kernel")
    y, state = _step_call(t.astype(jnp.int32), dt.reshape(-1), a, x,
                          bm[:, None], cm[:, None], state.astype(F32),
                          live_len=live_len, heads_per_block=heads_per_block,
                          interpret=interpret)
    return y + jnp.where(live[:, None, None], d[:, None] * x, 0.0), state, \
        window
