"""Fused dequant-matmul for quantized decode-GEMM weights.

The serving engine's decode step is HBM-bandwidth-bound: at batch
sizes that fit a slot pool, every projection matmul (QKV, attention
out, MLP up/down) streams its whole weight matrix from HBM to multiply
a few rows of activations. Quantizing those weights to int8 halves the
per-step weight traffic vs bf16 (4x vs f32); int4 halves it again.
This module owns the weight-side quantized format and the Pallas
kernel that DEQUANTIZES IN-REGISTER inside the matmul — the int8/int4
bytes are the only thing that ever crosses HBM, the f32 weights never
materialize. It is the decode-shape sibling of ``moe_kernels``'s
grouped expert GEMM and follows the same backend conventions
(``fused_supported`` / ``force_interpret`` / interpreter-mode oracle
tests).

Quantized-weight format (one dict per weight leaf, original leaf
shape preserved so every non-kernel consumer can dequantize blind):

  * int8 — ``{"q": int8 (same shape as w), "scale": f32}``
  * int4 — values on the [-7, 7] grid; when the leading axis is even
    the rows are NIBBLE-PACKED along axis 0 as ``{"q4": int8
    [s0 // 2, ...], "scale": f32}`` (byte row r holds logical row r in
    the low nibble and row ``r + s0//2`` in the high nibble — the same
    half-split ``ops.paged_attention`` uses for int4 KV pages); an odd
    leading axis falls back to one byte per entry under ``"q"`` (same
    4-bit value grid, no packing).

``scale`` is per-output-channel and broadcast-ready against the
TRAILING axes of the unpacked ``q`` (e.g. wq [d, h, e] carries scale
[h, e]; wo [h, e, d] carries scale [d]), so ``dequant_weight`` needs
no shape metadata — which is what lets a whole params tree of these
dicts pass through ``jax.jit`` as a plain argument
(``dequant_params_tree``).

Matmul layout: ``quant_matmul(x, wq)`` contracts ``x [..., K]``
against the 2D view of the weight. Both decode layouts resolve from
shapes alone: ``q.shape[0] == K`` is the projection layout (wq/wk/wv
[d, h, e] -> [d, h*e]); otherwise ``prod(q.shape[:-1]) == K`` is the
output-projection layout (wo [h, e, d] -> [h*e, d]). The axis-0
nibble packing commutes with both flattenings, so the packed kernel's
in-register unpack (concat lo||hi along the contraction axis) is
exact in either layout.

Alignment: the kernel wants K % 128 == 0 (f32 lane tiling of the x
block; also covers the int8 [32, 128] sublane rule for the weight
tile, packed or not) and a block-N divisor of N that is % 128.
``fused_supported(k, n)`` gates; misaligned shapes take
``reference_matmul`` — plain XLA dequant + matmul, also the off-TPU
serving path (XLA fuses the dequant into the consuming matmul, so
int8/int4 stays the HBM-resident form there too).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.compat import backend_is_tpu, note_path

#: upper bound on the output-channel tile. 512 f32 lanes x the whole
#: K column block stays well inside VMEM at decode batch sizes.
MAX_BLOCK_N = 512

_FORCE_INTERPRET = False


@contextlib.contextmanager
def force_interpret():
    """Run the kernel in Pallas interpreter mode regardless of backend
    — the CPU test suite's hook (tier-1 runs JAX_PLATFORMS=cpu, where
    the production path is ``reference_matmul``). Trace-time flag: an
    engine built inside this context bakes the interpreter kernel into
    its compiled decode programs."""
    global _FORCE_INTERPRET
    prev = _FORCE_INTERPRET
    _FORCE_INTERPRET = True
    try:
        yield
    finally:
        _FORCE_INTERPRET = prev


def is_qdict(p) -> bool:
    """Whether a params-tree node is one quantized weight leaf."""
    return (isinstance(p, dict) and "scale" in p
            and ("q" in p or "q4" in p))


def choose_block_n(n: int, cap: int = MAX_BLOCK_N) -> Optional[int]:
    """Largest divisor of ``n`` that is a multiple of 128 and <= cap
    (Mosaic lane tiling; divisor tiling keeps every block fully
    in-bounds). None when no such divisor exists -> reference path."""
    best = None
    for b in range(128, min(n, cap) + 1, 128):
        if n % b == 0:
            best = b
    return best


def kernel_enabled() -> bool:
    """The backend half of the kernel gate — same trace-time
    convention as every Pallas-vs-XLA fork in this repo
    (``compat.backend_is_tpu``, or a test forcing interpreter mode).
    The serving engine consults this once at construction to decide
    whether its decode programs keep attention projections quantized
    (shape misalignments still degrade per-leaf to the reference
    inside :func:`quant_matmul`)."""
    return _FORCE_INTERPRET or backend_is_tpu()


def fused_supported(k: int, n: int) -> bool:
    """Whether a [*, k] @ [k, n] quantized matmul takes the kernel:
    :func:`kernel_enabled` plus the Mosaic alignment rules (see module
    docstring)."""
    if not kernel_enabled():
        return False
    return k % 128 == 0 and choose_block_n(n) is not None


# --- quantize / dequantize ------------------------------------------------


def pack_rows(q: jnp.ndarray) -> jnp.ndarray:
    """Nibble-pack int4-valued int8 rows along axis 0 (even length):
    byte row r = logical row r (low nibble) | row r + s0/2 << 4.
    int32 math for portable two's-complement handling."""
    s0 = q.shape[0]
    lo = q[: s0 // 2].astype(jnp.int32) & 15
    hi = q[s0 // 2:].astype(jnp.int32) & 15
    b = (hi << 4) | lo
    return (b - 256 * (b > 127)).astype(jnp.int8)


def unpack_rows(b: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_rows`: [s0/2, ...] bytes -> [s0, ...]
    int8 values in [-7, 7], low-nibble rows first."""
    b32 = b.astype(jnp.int32) & 255
    lo = b32 & 15
    lo = lo - 16 * (lo > 7)
    hi = (b32 >> 4) & 15
    hi = hi - 16 * (hi > 7)
    return jnp.concatenate([lo, hi], axis=0).astype(jnp.int8)


def quantize_weight(w, bits: int = 8,
                    reduce_axes: Optional[Tuple[int, ...]] = None
                    ) -> Dict[str, np.ndarray]:
    """Symmetric per-channel quantization of one weight matrix.

    ``reduce_axes`` are the CONTRACTION axes the scale absorbs
    (default: all but the last — the ``models.quantize`` convention);
    the scale keeps the non-reduced trailing axes, so ``q * scale``
    broadcasts back to ``w`` without metadata. ``bits=4`` packs along
    axis 0 when its length is even (see module docstring)."""
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    w = np.asarray(w, np.float32)
    if w.ndim < 2:
        raise ValueError(f"need a matrix-shaped weight, got {w.shape}")
    if reduce_axes is None:
        reduce_axes = tuple(range(w.ndim - 1))
    reduce_axes = tuple(sorted(a % w.ndim for a in reduce_axes))
    if reduce_axes != tuple(range(len(reduce_axes))):
        raise ValueError(
            f"reduce_axes must be a leading prefix, got {reduce_axes}")
    qmax = 7.0 if bits == 4 else 127.0
    absmax = np.abs(w).max(axis=reduce_axes, keepdims=True)
    scale = (absmax / qmax).astype(np.float32)
    scale = np.where(scale == 0.0, 1.0, scale)          # all-zero channels
    q = np.clip(np.round(w / scale), -qmax, qmax).astype(np.int8)
    scale = scale.reshape(w.shape[len(reduce_axes):]).astype(np.float32)
    if bits == 4 and q.shape[0] % 2 == 0:
        return {"q4": np.asarray(pack_rows(jnp.asarray(q))),
                "scale": scale}
    return {"q": q, "scale": scale}


def dequant_weight(wq: Dict, dtype=jnp.float32) -> jnp.ndarray:
    """``q * scale`` back to the original weight shape (the in-graph
    consumer of the reference path; XLA fuses it into the next
    matmul so the int bytes stay the HBM-resident form)."""
    q = unpack_rows(wq["q4"]) if "q4" in wq else wq["q"]
    return (q.astype(jnp.float32) * wq["scale"]).astype(dtype)


def quant_error(w, wq) -> Dict[str, float]:
    """Per-leaf reconstruction error of one quantized weight — the
    numbers ``obs.report.weight_quant_report`` aggregates."""
    w = np.asarray(w, np.float32)
    deq = np.asarray(dequant_weight(wq), np.float32).reshape(w.shape)
    err = deq - w
    denom = float(np.sqrt(np.mean(w ** 2))) or 1.0
    return {"max_abs_err": float(np.abs(err).max()),
            "rel_rms": float(np.sqrt(np.mean(err ** 2)) / denom)}


# --- the kernel -----------------------------------------------------------


def _kernel(x_ref, q_ref, s_ref, o_ref, *, int4: bool):
    x = x_ref[...]                                   # [M, K]
    q = q_ref[...]                                   # [K or K/2, bn] int8
    if int4:
        q = unpack_rows(q)                           # [K, bn]
    acc = lax.dot_general(
        x.astype(jnp.float32), q.astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [M, bn]
    o_ref[...] = acc * s_ref[...]                    # scale [1, bn]


def _resolve_2d(x_k: int, wq: Dict):
    """Resolve the weight dict against a contraction length: returns
    ``(q2d, scale1d, int4, n)`` with ``q2d`` the [K or K/2, N] byte
    view. Projection layout (``q.shape[0] == K``) wins; otherwise the
    output-projection layout (leading axes flatten to K)."""
    int4 = "q4" in wq
    q = wq["q4"] if int4 else wq["q"]
    mult = 2 if int4 else 1
    if q.shape[0] * mult == x_k:
        q2d = q.reshape(q.shape[0], -1)
    elif int(np.prod(q.shape[:-1])) * mult == x_k:
        q2d = q.reshape(-1, q.shape[-1])
    else:
        raise ValueError(
            f"quantized weight {q.shape} (packed={int4}) does not "
            f"contract with K={x_k}")
    n = q2d.shape[1]
    scale = wq["scale"].reshape(-1)
    if scale.shape[0] != n:
        raise ValueError(
            f"scale {wq['scale'].shape} does not flatten to the "
            f"{n} output channels of {q.shape}")
    return q2d, scale, int4, n


def reference_matmul(x, wq) -> jnp.ndarray:
    """XLA path: same factored math as the kernel — int-q matmul in
    f32, THEN the per-channel scale (the scale is constant along K, so
    it commutes out of the contraction). f32 result, caller casts."""
    lead, k = x.shape[:-1], x.shape[-1]
    q2d, scale, int4, n = _resolve_2d(k, wq)
    if int4:
        q2d = unpack_rows(q2d)
    out = jnp.dot(x.reshape(-1, k).astype(jnp.float32),
                  q2d.astype(jnp.float32),
                  preferred_element_type=jnp.float32) * scale
    return out.reshape(lead + (n,))


def quant_matmul(x, wq, *, interpret: Optional[bool] = None
                 ) -> jnp.ndarray:
    """``x [..., K] @ dequant(wq) -> [..., N]`` in f32, dequantizing
    in-register on the kernel path. Falls back to
    :func:`reference_matmul` when the shape gate or backend gate says
    no (``fused_supported``), so callers use it unconditionally."""
    lead, k = x.shape[:-1], x.shape[-1]
    q2d, scale, int4, n = _resolve_2d(k, wq)
    if not fused_supported(k, n):
        note_path("quant_matmul", f"xla_reference[k={k},n={n}]")
        return reference_matmul(x, wq)
    note_path("quant_matmul", "kernel")
    if interpret is None:
        interpret = not backend_is_tpu()
    bn = choose_block_n(n)
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    mp = -(-m // 8) * 8                   # Mosaic sublane rule for x/out
    if mp != m:
        x2 = jnp.pad(x2, ((0, mp - m), (0, 0)))
    kq = q2d.shape[0]                     # K or K/2 (packed)
    out = pl.pallas_call(
        functools.partial(_kernel, int4=int4),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((mp, k), lambda i: (0, 0)),
            pl.BlockSpec((kq, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((mp, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((mp, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="quant_matmul", interpret=interpret,
    )(x2, q2d, scale.reshape(1, n))
    return out[:m].reshape(lead + (n,))


# --- params-tree plumbing (the serving engine's weight side) --------------

#: attention projection leaves — the decode programs' kernel
#: consumers; ``dequant_params_tree(keep_attn=True)`` leaves these as
#: qdicts for ``models.decoding._project_qkv`` / ``_attn_out``.
ATTN_PROJ_NAMES = frozenset({"wq", "wk", "wv", "wo"})

#: scale reduction axes per attention leaf (the contraction axes of
#: the decode matmuls): wq/wk/wv [d, h, e] contract d; wo [h, e, d]
#: contracts (h, e). Everything else uses the ``models.quantize``
#: all-but-last default.
_ATTN_REDUCE = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1)}


def quantize_params_tree(params, bits: int = 8):
    """Quantize every ``models.quantize.QUANTIZABLE_NAMES`` leaf of a
    params tree into the qdict format (original shapes preserved);
    other leaves pass through by reference. The serving engine's
    weight-quant initializer."""
    from distkeras_tpu.models.quantize import _is_quantizable

    def walk(p, name=""):
        if isinstance(p, dict):
            return {k: walk(v, k) for k, v in p.items()}
        if isinstance(p, (list, tuple)):
            seq = [walk(v, name) for v in p]
            return seq if isinstance(p, list) else tuple(seq)
        if _is_quantizable(p, name):
            return quantize_weight(np.asarray(jax.device_get(p)), bits,
                                   reduce_axes=_ATTN_REDUCE.get(name))
        return p

    return walk(params)


def dequant_params_tree(params, dtype=jnp.float32, keep_attn=False):
    """In-graph dequant of a quantized params tree — the first op of
    every compiled serving program under ``weight_quant`` (the same
    trick ``models.quantize.QuantizedModel`` uses: int bytes are the
    traced arguments, XLA fuses ``q * scale`` into each consumer).
    ``keep_attn`` leaves the attention projections as qdicts for the
    decode programs' fused kernel path."""
    def walk(p, name=""):
        if isinstance(p, dict):
            if is_qdict(p):
                if keep_attn and name in ATTN_PROJ_NAMES:
                    return p
                return dequant_weight(p, dtype)
            return {k: walk(v, k) for k, v in p.items()}
        if isinstance(p, (list, tuple)):
            seq = [walk(v, name) for v in p]
            return seq if isinstance(p, list) else tuple(seq)
        return p

    return walk(params)


def tree_quant_errors(params, qtree) -> Dict[str, Dict[str, float]]:
    """Path-keyed :func:`quant_error` over every quantized leaf of
    ``qtree`` vs the float master tree — the engine's
    ``weight_quant_error`` payload."""
    out = {}

    def walk(p, q, path):
        if is_qdict(q):
            out["/".join(path)] = quant_error(p, q)
        elif isinstance(q, dict):
            for k in q:
                walk(p[k], q[k], path + [str(k)])
        elif isinstance(q, (list, tuple)):
            for i, v in enumerate(q):
                walk(p[i], v, path + [str(i)])

    walk(params, qtree, [])
    return out
