"""The SDAR family (``sdar_moe``: Qwen3-style attention, every layer a
mixture of gated experts, generation by diffusion over blocks) for the
benchmark: its sizes, its weights from ``--seed``, and the operations and
bytes of its step and kernels, from shapes only. The GPT family's
``weights.py`` / ``flops.py`` know nothing of a head size that is not
``d_model / heads``, of experts or of a pass that is not one token, so every
reader of an SDAR cell takes its sizes from here (``cell_sizes(ctx.cell)``),
never from ``ctx.sizes``, whose ``d_head`` and ``ffn`` are wrong for this
model.

Weights are made leaf by leaf in the type they are served in (bfloat16): a
float32 stack of one layer's experts would be 2.4 GB. ``N(0, 0.02)`` for
every matrix, 1 for every norm scale, as the configuration file states. The
program and the plain reference are handed the SAME leaves under their own
names (``program_tree`` / ``reference_tree``); at full width the two cannot
be on the chip together, so the check makes them again from the seed once
the engine is gone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.weights import seed_key

BF16 = 2


def sizes(cfg: dict) -> dict:
    """Model sizes of an SDAR configuration file (its ``builder.kwargs``)."""
    kw = cfg["builder"]["kwargs"]
    return {"vocab": kw["vocab_size"], "d": kw["d_model"],
            "heads": kw["num_heads"], "kv_heads": kw["num_kv_heads"],
            "d_head": kw["head_dim"], "layers": kw["num_layers"],
            "experts": kw["num_experts"], "top_k": kw["moe_top_k"],
            "ffn": kw["mlp_dim"], "block_len": kw["block_len"],
            "rope_theta": kw["rope_base"], "positions": kw["max_len"]}


def cell_sizes(cell: dict) -> dict:
    """The sizes of a cell's configuration, read from its file as run on
    the chip (``configs/<config>.json``)."""
    import json
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", cell["config"] + ".json")) as f:
        return sizes(json.load(f))


def reference_cfg(s: dict) -> dict:
    """What ``reference/sdar.py`` wants to know of the model."""
    return {"heads": s["heads"], "kv_heads": s["kv_heads"],
            "top_k": s["top_k"], "block_len": s["block_len"],
            "rope_theta": float(s["rope_theta"])}


# --- weights -------------------------------------------------------------------

def _layer_shapes(s: dict) -> dict:
    d, h, hkv, e, f, x = (s["d"], s["heads"], s["kv_heads"], s["d_head"],
                          s["ffn"], s["experts"])
    return {"wq": (d, h, e), "wk": (d, hkv, e), "wv": (d, hkv, e),
            "wo": (h, e, d), "router": (d, x), "wg": (x, d, f),
            "wu": (x, d, f), "wd": (x, f, d)}


def make_leaves(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every matrix of the model, by name (``embed``, ``head``,
    ``L3.wq`` ...), each from one jitted call of its own in ``dtype``."""
    s = sizes(cfg)
    key = seed_key(seed)

    def normal(i, shape):
        return jax.jit(lambda k: (0.02 * jax.random.normal(
            k, shape, jnp.float32)).astype(dtype))(jax.random.fold_in(key, i))

    leaves = {"embed": normal(0, (s["vocab"], s["d"])),
              "head": normal(1, (s["d"], s["vocab"]))}
    for layer in range(s["layers"]):
        for j, (name, shape) in enumerate(sorted(_layer_shapes(s).items())):
            leaves[f"L{layer}.{name}"] = normal(16 * (layer + 1) + j, shape)
    return leaves


def program_tree(leaves: dict, s: dict) -> list:
    """The leaves in ``zoo.transformer_lm``'s layout (no copy)."""
    ones = lambda n: jnp.ones((n,), jnp.float32)
    tree = [{"embeddings": leaves["embed"]}]
    for i in range(s["layers"]):
        w = lambda name: leaves[f"L{i}.{name}"]
        tree.append({
            "norm1": {"scale": ones(s["d"])},
            "attn": {"wq": w("wq"), "wk": w("wk"), "wv": w("wv"),
                     "wo": w("wo"), "q_norm": ones(s["d_head"]),
                     "k_norm": ones(s["d_head"])},
            "norm2": {"scale": ones(s["d"])},
            "mlp": {"gate": w("router"), "w1": w("wg"), "w2": w("wd"),
                    "w3": w("wu")}})
    tree += [{"scale": ones(s["d"])}, {"kernel": leaves["head"]}]
    return tree


def reference_tree(leaves: dict, s: dict) -> dict:
    """The leaves under ``reference/sdar.py``'s names (no copy)."""
    ones = lambda n: jnp.ones((n,), jnp.float32)
    layers = []
    for i in range(s["layers"]):
        lw = {name: leaves[f"L{i}.{name}"] for name in _layer_shapes(s)}
        lw.update(n1=ones(s["d"]), n2=ones(s["d"]), qn=ones(s["d_head"]),
                  kn=ones(s["d_head"]))
        layers.append(lw)
    return {"embed": leaves["embed"], "layers": layers,
            "final_norm": ones(s["d"]), "head": leaves["head"]}


def build_model(cfg: dict, seed: int, seq_len: int):
    """The program's ``Model`` around the benchmark's weights, after the
    shapes the program would have made itself are compared."""
    from distkeras_tpu.models import Model
    from harness import common
    module = common.build_module(cfg)
    box = {}

    def init(key):
        params, state, box["out"] = module.init(key, (seq_len,))
        return params, state

    want, state = jax.eval_shape(init, jax.random.PRNGKey(0))
    params = program_tree(make_leaves(cfg, seed), sizes(cfg))
    shape = lambda tree: jax.tree_util.tree_map(lambda a: a.shape, tree)
    if shape(want) != shape(params):
        raise RuntimeError("the benchmark's weights do not match the shapes "
                           f"{cfg['builder']['function']} makes")
    state = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype), state)
    return Model(module, params, state, (seq_len,), box["out"])


# --- operations and bytes, from shapes only -----------------------------------

def layer_matmul_params(s: dict) -> int:
    """Parameters one token is multiplied with in one layer: attention
    projections, the router, and its ``top_k`` ROUTED experts only."""
    attn = 2 * s["d"] * s["heads"] * s["d_head"] \
        + 2 * s["d"] * s["kv_heads"] * s["d_head"]
    return attn + s["d"] * s["experts"] + s["top_k"] * 3 * s["d"] * s["ffn"]


def attention_flops(s: dict, keys: float) -> float:
    """QK^T and PV of ONE query row over ``keys`` keys, all layers."""
    return 4.0 * keys * s["heads"] * s["d_head"] * s["layers"]


def step_flops(s: dict, c: dict) -> float:
    """Model operations of the work a serving window did, from the loop's
    counters: ``prefill_tokens`` processed (cache hits left out) over
    ``prefill_context`` summed keys; ``pass_rows`` rows of live slots run
    by passes over ``pass_context`` summed keys; the vocabulary head for
    ``denoise_rows`` of them (a slot that only commits needs none). A
    prefill yields no logits (position ``i`` predicts token ``i``), so of
    the LAST layer the model needs the key and value projections and no
    more: ``layers - 1`` whole layers are counted, whatever a program
    runs (``prefill_chunk_step`` does stop there)."""
    layer = 2.0 * layer_matmul_params(s)
    whole = s["layers"] - 1
    prefill = whole * layer + 2.0 * 2 * s["d"] * s["kv_heads"] * s["d_head"]
    head = 2.0 * s["d"] * s["vocab"]
    per_key = attention_flops(s, 1.0)
    return (c["prefill_tokens"] * prefill + c["pass_rows"] * s["layers"] * layer
            + c["denoise_rows"] * head
            + (c["prefill_context"] * whole / s["layers"]
               + c["pass_context"]) * per_key)


def experts_cost(s: dict, c: dict) -> tuple:
    """``moe_grouped_experts`` over the traced window, from the programs'
    own counts (the engine's ``summary()["block_diffusion"]``; every pass
    and every prefill returns them): operations of the rows ROUTED
    (``routed_rows``, every row of every pass, live or not, and every
    prefill token, times ``top_k``, summed over the expert layers that
    ran), and the bytes of the experts that owned at least one row
    (``experts_touched``, summed likewise; three matrices each; rows in
    and out are small beside them and left out)."""
    ops = c["routed_rows"] * 6.0 * s["d"] * s["ffn"]
    nbytes = c["experts_touched"] * 3.0 * s["d"] * s["ffn"] * BF16
    return ops, nbytes


def block_attention_cost(s: dict, c: dict) -> tuple:
    """``paged_decode_attention`` under the block mask over the traced
    passes: ``pass_context`` keys attended (every row of a live slot's block
    sees the cached prefix and the block) and ``pass_page_tokens``, per
    pass and slot the positions of the pages the kernel has to read (K and
    V, bfloat16, every layer)."""
    ops = attention_flops(s, 1.0) * c["pass_context"]
    nbytes = 2.0 * c["pass_page_tokens"] * s["kv_heads"] * s["d_head"] \
        * BF16 * s["layers"]
    return ops, nbytes


KERNEL_COSTS = {"experts": experts_cost, "block_attention": block_attention_cost}
