"""Operations and bytes that the algorithm needs, computed from shapes.

Nothing here is read from ``cost_analysis()``: a compiled program counts its
own recomputation, and a Pallas custom call reports what its author wrote.
``s`` is always ``weights.sizes(config)``. A matrix product of ``[m, k]`` by
``[k, n]`` is ``2*m*k*n`` operations.
"""

from __future__ import annotations

BF16 = 2


def matmul_params(s: dict, head: bool = True) -> int:
    """Parameters that a token is multiplied with: attention and MLP
    matrices of every layer and, with ``head``, the vocabulary head.
    Embedding tables are looked up, not multiplied."""
    per_layer = 4 * s["d"] * s["d"] + 2 * s["d"] * s["ffn"]
    return s["layers"] * per_layer + (s["d"] * s["vocab"] if head else 0)


def attention_flops(s: dict, context: float) -> float:
    """Forward attention operations of ONE query token over ``context`` keys,
    all layers: QK^T and PV, ``2*context*d_model`` each."""
    return 4.0 * context * s["d"] * s["layers"]


def train_flops_per_token(s: dict, seq_len: int) -> float:
    """Forward plus backward for one token of a causal sequence of
    ``seq_len``: three times the forward products (backward is two products
    per forward product), causal attention counted once (a token sees
    ``(seq_len + 1) / 2`` keys on average), no recomputation."""
    fwd = 2.0 * matmul_params(s) + attention_flops(s, (seq_len + 1) / 2.0)
    return 3.0 * fwd


def serve_flops(s: dict, c: dict) -> float:
    """Operations of the prefill and decode work a serving window did, from
    the loop's counters: ``prefill_tokens`` processed (cache hits left out)
    over ``prefill_context`` summed keys, ``prefills`` final positions that
    needed logits, ``decode_tokens`` over ``decode_context`` summed keys."""
    body = 2.0 * matmul_params(s, head=False)
    head = 2.0 * s["d"] * s["vocab"]
    per_key = attention_flops(s, 1.0)
    return (c["prefill_tokens"] * body + c["prefills"] * head
            + c["prefill_context"] * per_key
            + c["decode_tokens"] * (body + head) + c["decode_context"] * per_key)


# --- kernels: (operations, bytes) of the calls a window made ---------------

def flash_train_cost(s: dict, c: dict) -> tuple:
    """``flash_fwd`` + ``flash_bwd_dq`` + ``flash_bwd_dkv`` over the traced
    steps (``c``: ``steps``, ``batch``, ``seq_len``). Per batch row and head
    the causal forward is 2 products of ``seq^2/2 * d_head`` and the backward
    needs 5 (the probabilities are not kept, so one recomputation of QK^T
    belongs to the algorithm; the second that the two-kernel split makes does
    not). Bytes: q, k, v, o read or written once forward; q, k, v, o, do read
    and dq, dk, dv written backward."""
    seq, dh = c["seq_len"], s["d_head"]
    calls = c["steps"] * c["batch"] * s["heads"] * s["layers"]
    product = 2.0 * (seq * seq / 2.0) * dh
    ops = calls * 7.0 * product
    nbytes = calls * (4 + 8) * seq * dh * BF16
    return ops, nbytes


def paged_decode_cost(s: dict, c: dict) -> tuple:
    """``paged_decode_attention`` over the traced decode steps. ``c``:
    ``decode_context`` (keys attended, summed over slots and steps) and
    ``decode_page_tokens`` (the same rounded up to whole pages: what the kernel
    has to read). K and V in bfloat16, every layer; q and the output are
    negligible beside them and left out."""
    ops = attention_flops(s, 1.0) * c["decode_context"]
    nbytes = 2.0 * c["decode_page_tokens"] * s["d"] * BF16 * s["layers"]
    return ops, nbytes


KERNEL_COSTS = {"flash_train": flash_train_cost, "paged_decode": paged_decode_cost}


def least_seconds(ops: float, nbytes: float, peaks: dict) -> tuple:
    """Roofline: the larger of operations over peak FLOP/s and bytes over
    peak bytes/s, and which of the two binds."""
    t_ops, t_bytes = ops / peaks["flops_bf16"], nbytes / peaks["bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
