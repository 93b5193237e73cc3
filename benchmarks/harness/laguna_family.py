"""The Laguna family (``laguna``: window and full attention layers side by
side with their own head counts and RoPEs, a leading dense layer, then
sigmoid-routed gated experts beside a shared expert; served one token a
step) for the benchmark: its sizes, its weights from ``--seed``, the program's
builder arguments and the reference's configuration (both from the SAME keys
of the configuration file, ``builder.kwargs`` for the sizes and
``assumed_values`` for what the source leaves open), the comparison that
decides ``correct``, and the operations and bytes of its step and kernels,
from shapes only. Every reader of a Laguna cell takes its sizes from here
(``cell_sizes(ctx.cell)``), never from ``ctx.sizes``, whose one head count,
``d_head`` and ``ffn`` are wrong for this model.

This module is also what ``serve_family_driver`` asks of ANY family (the
configuration file names it under ``family``): ``sizes``, ``build_model``,
``PROGRAM_PATHS``, ``health_check``, ``prefill_counts``, ``decode_counts``,
``engine_counters``, ``serve_numbers``. A further family brings a module
with those names and no driver.

Weights are made leaf by leaf in the type they are served in (bfloat16): a
float32 stack of one layer's experts would be 3.2 GB. ``N(0, 0.02)`` for
every matrix, 1 for every norm scale, as the configuration file states. The
program and the plain reference are handed the SAME leaves under their own
names (``program_tree`` / ``reference_tree``); at full width the two cannot
be on the chip together, so the check makes them again from the seed once
the engine is gone.
"""

from __future__ import annotations

import gc
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import seed_key

BF16 = 2


def sizes(cfg: dict) -> dict:
    """Model sizes of a Laguna configuration file, per layer where the
    layers differ: ``heads[l]``, ``window[l]`` (None: the whole context),
    ``sparse[l]``."""
    kw = cfg["builder"]["kwargs"]
    n = kw["num_layers"]
    kinds = kw["attn_kinds"]
    types = kw["layer_types"][:n]
    window = kinds["sliding_attention"]["attn_window"]
    if not cfg["assumed_values"]["window_includes_query"]:
        window += 1                    # the window's positions and its own
    return {"vocab": kw["vocab_size"], "d": kw["d_model"],
            "kv_heads": kw["num_kv_heads"], "d_head": kw["head_dim"],
            "layers": n, "layer_types": types,
            "heads": [kinds[t].get("num_heads", kw["num_heads"])
                      for t in types],
            "window": [window if t == "sliding_attention" else None
                       for t in types],
            "sparse": [t == "sparse" for t in kw["mlp_layer_types"][:n]],
            "experts": kw["num_experts"], "top_k": kw["moe_top_k"],
            "ffn": kw["mlp_dim"], "dense_ffn": kw["dense_mlp_dim"],
            "shared_ffn": kw["moe_shared_dim"],
            "positions": kw["max_len"]}


def cell_sizes(cell: dict) -> dict:
    """The sizes of a cell's configuration, read from its file as run on
    the chip (``configs/<config>.json``)."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", cell["config"] + ".json")) as f:
        return sizes(json.load(f))


def program_kwargs(cfg: dict) -> dict:
    """``zoo.transformer_lm``'s arguments: the file's ``builder.kwargs``
    and, from ``assumed_values``, what the source leaves open."""
    a = cfg["assumed_values"]
    if a["router_selection_bias"] or a["attention_output_gate"]:
        raise NotImplementedError(
            "a router selection bias or an attention output gate is not "
            "built: assumed_values says the model has neither")
    kw = json.loads(json.dumps(cfg["builder"]["kwargs"]))
    window = [w for w in sizes(cfg)["window"] if w is not None][0]
    kw["attn_kinds"]["sliding_attention"]["attn_window"] = window
    kw.update(mlp_activation=a["hidden_act"], mlp_gated=a["mlp_gated"],
              moe_score=a["router_score"],
              moe_norm_topk=a["router_norm_topk"], qk_norm=a["qk_norm"])
    return kw


def reference_cfg(cfg: dict, window=None) -> dict:
    """What ``reference/laguna.py`` wants to know of the model, from the
    same keys. ``window`` plants another sliding window (a control)."""
    a, s, kw = cfg["assumed_values"], sizes(cfg), cfg["builder"]["kwargs"]
    if not a["mlp_gated"] or a["qk_norm"]:
        raise NotImplementedError("the reference is of gated blocks "
                                  "without q/k norm, as assumed_values says")
    kinds = {}
    for kind, k in kw["attn_kinds"].items():
        if "rope_yarn" in k:
            rope = {"rope_type": "yarn", "rope_theta": k["rope_base"],
                    "partial_rotary_factor":
                        k.get("rotary_dim", s["d_head"]) / s["d_head"],
                    **k["rope_yarn"]}
        else:
            rope = {"rope_type": "default", "rope_theta": k["rope_base"],
                    "partial_rotary_factor":
                        k.get("rotary_dim", s["d_head"]) / s["d_head"]}
        win = None
        if kind == "sliding_attention":
            win = [w for w in s["window"] if w is not None][0] \
                if window is None else int(window)
        kinds[kind] = {"window": win, "rope": rope}
    return {"layer_types": tuple(s["layer_types"]),
            "sparse": tuple(s["sparse"]), "kinds": kinds,
            "kv_heads": s["kv_heads"], "top_k": s["top_k"],
            "router": {"score": a["router_score"],
                       "norm_topk": a["router_norm_topk"],
                       "scale": kw["moe_route_scale"]},
            "act": a["hidden_act"], "eps": cfg["rms_norm_eps"]}


# --- weights -------------------------------------------------------------------

def _layer_shapes(s: dict, layer: int) -> dict:
    d, h, hkv, e = s["d"], s["heads"][layer], s["kv_heads"], s["d_head"]
    out = {"wq": (d, h, e), "wk": (d, hkv, e), "wv": (d, hkv, e),
           "wo": (h, e, d)}
    if s["sparse"][layer]:
        x, f, sf = s["experts"], s["ffn"], s["shared_ffn"]
        out.update(router=(d, x), wg=(x, d, f), wu=(x, d, f), wd=(x, f, d),
                   sg=(d, sf), su=(d, sf), sd=(sf, d))
    else:
        f = s["dense_ffn"]
        out.update(wg=(d, f), wu=(d, f), wd=(f, d))
    return out


def parameters(s: dict) -> int:
    """Parameters of the model as built (matrices; norm scales left out)."""
    return 2 * s["vocab"] * s["d"] + sum(
        int(np.prod(shape)) for layer in range(s["layers"])
        for shape in _layer_shapes(s, layer).values())


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
        for j, (name, shape) in enumerate(
                sorted(_layer_shapes(s, layer).items())):
            leaves[f"L{layer}.{name}"] = normal(16 * (layer + 1) + j, shape)
    return leaves


def program_tree(leaves: dict, s: dict) -> list:
    """The leaves in ``zoo.transformer_lm``'s layout (no copy)."""
    ones = lambda n: jnp.ones((n,), jnp.float32)
    tree = [{"embeddings": leaves["embed"]}]
    for i in range(s["layers"]):
        w = lambda name: leaves[f"L{i}.{name}"]
        if s["sparse"][i]:
            mlp = {"gate": w("router"), "w1": w("wg"), "w2": w("wd"),
                   "w3": w("wu"),
                   "shared": {"w1": w("sg"), "w2": w("sd"), "w3": w("su")}}
        else:
            mlp = {"w1": w("wg"), "w2": w("wd"), "w3": w("wu")}
        tree.append({
            "norm1": {"scale": ones(s["d"])},
            "attn": {"wq": w("wq"), "wk": w("wk"), "wv": w("wv"),
                     "wo": w("wo")},
            "norm2": {"scale": ones(s["d"])},
            "mlp": mlp})
    tree += [{"scale": ones(s["d"])}, {"kernel": leaves["head"]}]
    return tree


def reference_tree(leaves: dict, s: dict) -> dict:
    """The leaves under ``reference/laguna.py``'s names (no copy)."""
    ones = lambda n: jnp.ones((n,), jnp.float32)
    layers = []
    for i in range(s["layers"]):
        lw = {name: leaves[f"L{i}.{name}"] for name in _layer_shapes(s, i)}
        lw.update(n1=ones(s["d"]), n2=ones(s["d"]))
        layers.append(lw)
    return {"embed": leaves["embed"], "layers": layers,
            "final_norm": ones(s["d"]), "head": leaves["head"]}


def build_module(cfg: dict):
    from distkeras_tpu.models import zoo
    kw = program_kwargs(cfg)
    return zoo.transformer_lm(kw.pop("vocab_size"), **kw)


def build_model(cfg: dict, seed: int, seq_len: int, dtype=jnp.bfloat16):
    """The program's ``Model`` around the benchmark's weights, after the
    shapes the program would have made itself are compared."""
    from distkeras_tpu.models import Model
    module = build_module(cfg)
    box = {}

    def init(key):
        params, state, box["out"] = module.init(key, (seq_len,))
        return params, state

    want, state = jax.eval_shape(init, jax.random.PRNGKey(0))
    params = program_tree(make_leaves(cfg, seed, dtype), sizes(cfg))
    shape = lambda tree: jax.tree_util.tree_map(lambda a: a.shape, tree)
    if shape(want) != shape(params):
        raise RuntimeError("the benchmark's weights do not match the shapes "
                           f"{cfg['builder']['function']} makes")
    state = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype), state)
    return Model(module, params, state, (seq_len,), box["out"])


# --- what the serve driver asks of a family ------------------------------------

#: per program of ``health()["programs"]``, the paths a chip run has to
#: have taken
PROGRAM_PATHS = {"prefill": ("flash_attention=kernel", "moe=grouped_kernel"),
                 "decode_greedy": ("paged_attention=kernel",
                                   "moe=grouped_kernel")}


def health_check(health: dict) -> None:
    """Beyond the paths: two page groups, the window group's ring as wide
    as one window and not as the context."""
    groups = health.get("kv_groups") or {}
    if len(groups) != 2 or "full" not in groups:
        raise RuntimeError(f"the cell needs two page groups, got {groups}")


def prefill_counts(s: dict, traffic: dict, p: int, shared: int) -> dict:
    """What one prefill processed, by kind of layer: of a prompt of ``p``
    tokens the ``p - shared`` past the cache hit. ``full_keys``: keys a
    full layer attends (every token all before it and itself);
    ``window_keys``: a sliding layer's (at most a window);
    ``window_flash_keys``: those of them that ``flash_fwd`` computes (the
    keys inside the token's own chunk; the band before the chunk is a
    plain masked product)."""
    window = [w for w in s["window"] if w is not None][0]
    chunk = traffic["engine"].get("prefill_chunk") or p
    pos = np.arange(shared, p)
    in_chunk = (pos - shared) % chunk + 1
    return {"prefill_full_keys": int((pos + 1).sum()),
            "prefill_window_keys": int(np.minimum(pos + 1, window).sum()),
            "prefill_window_flash_keys":
                int(np.minimum(in_chunk, window).sum())}


def decode_counts(s: dict, ctx: int, page_len: int) -> dict:
    """What one decode step of a slot at context ``ctx`` (the position it
    writes) reads, by kind of layer: keys attended, and the positions of
    the pages the paged kernel has to read for them."""
    window = [w for w in s["window"] if w is not None][0]
    keys = ctx + 1
    first = max(0, ctx - window + 1) // page_len
    return {"decode_full_keys": keys,
            "decode_full_page_tokens": (ctx // page_len + 1) * page_len,
            "decode_window_keys": min(keys, window),
            "decode_window_page_tokens":
                (ctx // page_len + 1 - first) * page_len}


def engine_counters(engine) -> dict:
    """The engine's own counters the readers need, cumulative: what the
    expert layers routed (``summary()["routing"]``) and the pages the
    window group's slots gave back (``summary()["kv_groups"]``)."""
    summary = engine.metrics.summary()
    out = dict.fromkeys(("rows_routed", "experts_touched",
                         "prefill_rows_routed", "prefill_experts_touched"), 0)
    out.update(summary.get("routing") or {})
    groups = engine.health().get("kv_groups") or {}
    out["window_pages_released"] = sum(
        g["pages_released"] for g in groups.values())
    return out


#: a checked sequence is padded to the next multiple of this: the mix's
#: four prompt lengths with their outputs make four compiled shapes
PAD_STEP = 512


def serve_numbers(cfg, seed, sample, traffic, control=None):
    """``served_gap_mean``: the mean gap by which a served token's logit
    lies below the reference's best, over every served token of ``sample``
    (``[(prompt ids, served ids)]``), by one full forward of
    ``reference/laguna.py`` per request. With ``control`` the gaps are
    read instead for the token that a planted fault puts first at each of
    the same positions: a precision (``"int8"``: the reference with every
    product in int8) or ``"window<n>"`` (the reference with a sliding
    window of ``n``: the check has to see the sliding layers)."""
    from reference import laguna
    s = sizes(cfg)
    rcfg = reference_cfg(cfg)
    planted = None
    if control is not None and control.startswith("window"):
        planted = (reference_cfg(cfg, window=int(control[6:])), "float32")
    elif control is not None:
        planted = (rcfg, control)
    w = reference_tree(make_leaves(cfg, seed), s)
    max_out = traffic["output"]["max"]
    sound, low_gaps = [], []
    for prompt, served in sample:
        n = len(served)
        total = len(prompt) + n - 1
        seq = np.zeros(-(-total // PAD_STEP) * PAD_STEP, np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):total] = served[:-1]
        pos = np.minimum(len(prompt) - 1 + np.arange(max_out), total - 1)
        ref = np.asarray(laguna.logits_at(w, rcfg, seq, pos))[:n]
        sound.append(ref.max(-1) - ref[np.arange(n), np.asarray(served)])
        if planted is not None:
            low = np.asarray(laguna.logits_at(
                w, planted[0], seq, pos, precision=planted[1]))[:n]
            low_gaps.append(ref.max(-1) - ref[np.arange(n), low.argmax(-1)])
    del w
    gc.collect()

    def readings(gaps):
        gaps = np.concatenate(gaps)
        return {"widest": float(gaps.max()), "mean": float(gaps.mean()),
                "tokens": int(gaps.size),
                "argmax_agreement": float((gaps == 0).mean())}

    where = {"requests": len(sample),
             "longest": max(len(p) + len(g) for p, g in sample),
             **readings(sound)}
    if planted is not None:
        where = {**where, **readings(low_gaps), "program": readings(sound)}
    return {"served_gap": where["widest"], "served_gap_mean": where["mean"],
            "_where": where}


# --- operations and bytes, from shapes only -----------------------------------

def _layer_matmul_params(s: dict, layer: int) -> int:
    """Parameters one token is multiplied with in one layer: attention
    projections and, in a sparse layer, the router, its ``top_k`` ROUTED
    experts and the shared expert; in a dense one its MLP."""
    attn = 2 * s["d"] * s["heads"][layer] * s["d_head"] \
        + 2 * s["d"] * s["kv_heads"] * s["d_head"]
    if s["sparse"][layer]:
        return attn + s["d"] * s["experts"] \
            + 3 * s["d"] * (s["top_k"] * s["ffn"] + s["shared_ffn"])
    return attn + 3 * s["d"] * s["dense_ffn"]


def _heads_by_kind(s: dict, layers=None):
    """``(sum of query heads over the full layers, over the window
    layers)`` among the first ``layers`` layers (default: all)."""
    n = s["layers"] if layers is None else layers
    full = sum(h for h, w in zip(s["heads"][:n], s["window"][:n])
               if w is None)
    return full, sum(s["heads"][:n]) - full


def step_flops(s: dict, c: dict) -> float:
    """Model operations of the work a serving window did, from the loop's
    counters: ``decode_tokens`` through every layer's matrices and the
    vocabulary head; ``prefill_tokens`` processed (cache hits left out)
    through every layer but the LAST, of which a prefill needs the key and
    value projections and no more (only its final position yields logits:
    one token's worth of the last layer and the head a prefill, whatever a
    program runs); attention over the keys each kind of layer attends
    (``*_full_keys``, ``*_window_keys``: QK^T and PV, ``4 * head_dim`` a
    key and query head)."""
    layer = [2.0 * _layer_matmul_params(s, l) for l in range(s["layers"])]
    last_kv = 2.0 * 2 * s["d"] * s["kv_heads"] * s["d_head"]
    head = 2.0 * s["d"] * s["vocab"]
    full, window = _heads_by_kind(s)
    pre_full, pre_window = _heads_by_kind(s, s["layers"] - 1)
    per_key = 4.0 * s["d_head"]
    return (c["decode_tokens"] * (sum(layer) + head)
            + c["prefill_tokens"] * (sum(layer[:-1]) + last_kv)
            + c["prefills"] * (layer[-1] + head)
            + per_key * (c["decode_full_keys"] * full
                         + c["decode_window_keys"] * window
                         + c["prefill_full_keys"] * pre_full
                         + c["prefill_window_keys"] * pre_window))


def experts_cost(s: dict, c: dict) -> tuple:
    """``moe_grouped_experts`` over the traced window, from the programs'
    own counts: operations of the rows ROUTED (``routed_rows``: every row of
    every decode step, live or not, and every prefill token, times
    ``top_k``, summed over the sparse layers that ran), and the bytes of the
    experts that owned at least one row (``experts_touched``, summed
    likewise; three matrices each; rows in and out are small beside them
    and left out). The shared expert is not this kernel's."""
    ops = c["routed_rows"] * 6.0 * s["d"] * s["ffn"]
    nbytes = c["experts_touched"] * 3.0 * s["d"] * s["ffn"] * BF16
    return ops, nbytes


def _kv_layers(s: dict, windowed: bool) -> int:
    return sum((w is not None) == windowed for w in s["window"])


def paged_full_cost(s: dict, c: dict) -> tuple:
    """``paged_decode_attention`` (the full layers) over the traced decode
    steps: keys attended and the positions of the pages read (K and V,
    bfloat16, every full layer)."""
    full, _ = _heads_by_kind(s)
    ops = 4.0 * s["d_head"] * full * c["keys"]
    nbytes = 2.0 * c["page_tokens"] * s["kv_heads"] * s["d_head"] * BF16 \
        * _kv_layers(s, False)
    return ops, nbytes


def paged_window_cost(s: dict, c: dict) -> tuple:
    """``paged_window_attention`` (the sliding layers): as above over the
    window's keys and the ring's pages."""
    _, window = _heads_by_kind(s)
    ops = 4.0 * s["d_head"] * window * c["keys"]
    nbytes = 2.0 * c["page_tokens"] * s["kv_heads"] * s["d_head"] * BF16 \
        * _kv_layers(s, True)
    return ops, nbytes


def flash_prefill_cost(s: dict, c: dict) -> tuple:
    """``flash_fwd`` over the traced prefills: QK^T and PV over the keys
    attended under each mask (a full layer's: all before the token; a
    sliding layer's: those of its window inside the token's chunk, which
    is what the kernel is given), in every layer but the last (as
    ``step_flops``). Bytes: q, k, v read and the output written once a
    processed token and layer."""
    n = s["layers"] - 1
    full, window = _heads_by_kind(s, n)
    ops = 4.0 * s["d_head"] * (full * c["full_keys"]
                               + window * c["window_keys"])
    nbytes = c["tokens"] * BF16 * s["d_head"] * (
        2.0 * sum(s["heads"][:n]) + 2.0 * n * s["kv_heads"])
    return ops, nbytes


KERNEL_COSTS = {"experts": experts_cost, "paged_full": paged_full_cost,
                "paged_window": paged_window_cost,
                "flash_prefill": flash_prefill_cost}
