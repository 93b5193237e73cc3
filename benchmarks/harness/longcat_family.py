"""The LongCat-Flash family (double layers of two latent-attention blocks and
two dense gated MLPs round one shortcut-connected expert layer whose router
also has identity experts; ONE CHIP'S SHARE of an expert-parallel
deployment: the experts held here, a slice of the vocabulary; served one
token a step from latent pages) for the benchmark: its sizes, its weights
from ``--seed``, the program's builder arguments and the reference's
configuration (both from the SAME keys of the configuration file,
``builder.kwargs`` for the sizes and ``assumed_values`` for what the source
leaves open), the comparison that decides ``correct``, and the operations
and bytes of its step and kernels, from shapes only. It is what
``serve_family_driver`` asks of a family (``FAMILIES.md``); every reader of
a LongCat cell takes its sizes from here (``cell_sizes(ctx.cell)``).

Weights are made leaf by leaf in the type they are served in (bfloat16):
``N(0, weight_std)`` for every matrix, 1 for every norm scale, ``N(0,
selection_bias_std)`` in float32 for the router's selection bias. The
program and the plain reference are handed the SAME leaves under their own
names. At full width a whole model in float32 is 20.7 GB, so the check walks
the stack a double layer at a time, making that layer's leaves from the
seed, once the engine is gone.
"""

from __future__ import annotations

import gc
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import seed_key

BF16 = 2


def sizes(cfg: dict) -> dict:
    """Model sizes of a LongCat configuration file. ``layers`` counts
    DOUBLE layers (two attention blocks each)."""
    kw = cfg["builder"]["kwargs"]
    lat = kw["attn_kinds"]["latent_attention"]["latent"]
    d = kw["d_model"]
    return {"vocab": kw["vocab_size"], "d": d, "heads": kw["num_heads"],
            "layers": kw["num_layers"],
            "q_lora": lat["q_lora_rank"], "kv_lora": lat["kv_lora_rank"],
            "dn": lat["qk_nope_head_dim"], "dr": lat["qk_rope_head_dim"],
            "dv": lat["v_head_dim"],
            "latent": lat["kv_lora_rank"] + lat["qk_rope_head_dim"],
            "q_scale": math.sqrt(d / lat["q_lora_rank"])
            if cfg["mla_scale_q_lora"] else 1.0,
            "kv_scale": math.sqrt(d / lat["kv_lora_rank"])
            if cfg["mla_scale_kv_lora"] else 1.0,
            "rope_theta": kw["attn_kinds"]["latent_attention"]["rope_base"],
            "dense_ffn": kw["dense_mlp_dim"], "ffn": kw["mlp_dim"],
            "experts": kw["num_experts"],
            "zero_experts": kw["moe_zero_experts"],
            "held": tuple(kw["moe_experts_held"]), "top_k": kw["moe_top_k"],
            "route_scale": float(cfg["routed_scaling_factor"]),
            "positions": kw["max_len"]}


def cell_sizes(cell: dict) -> dict:
    """The sizes of a cell's configuration, read from its file as run on
    the chip (``configs/<config>.json``)."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", cell["config"] + ".json")) as f:
        return sizes(json.load(f))


def _check_assumed(cfg: dict) -> dict:
    a = cfg["assumed_values"]
    if a["router_bias"] or a["tie_word_embeddings"] or not a["mlp_gated"] \
            or a["router_score"] != "softmax" \
            or a["mla_scales_on"] != "normed_low_rank" \
            or cfg["zero_expert_type"] != "identity" \
            or cfg["attention_method"] != "MLA":
        raise NotImplementedError(
            "built: a bias-free softmax router, an untied head, gated "
            "blocks, the scales on the normed low-rank values, identity "
            "zero experts, MLA: as assumed_values says")
    return a


def program_kwargs(cfg: dict) -> dict:
    """``zoo.transformer_lm``'s arguments: the file's ``builder.kwargs``
    and, from ``assumed_values``, what the source leaves open."""
    a, s = _check_assumed(cfg), sizes(cfg)
    kw = json.loads(json.dumps(cfg["builder"]["kwargs"]))
    kw["attn_kinds"]["latent_attention"]["latent"].update(
        q_scale=s["q_scale"], kv_scale=s["kv_scale"])
    kw.update(mlp_activation=a["hidden_act"], mlp_gated=a["mlp_gated"],
              moe_score=a["router_score"], moe_norm_topk=a["norm_topk_prob"],
              moe_select_bias=a["router_selection_bias"])
    return kw


def reference_cfg(cfg: dict, kv_scale=None, use_select_bias=None,
                  norm_topk=None) -> dict:
    """What ``reference/longcat.py`` wants to know of the model, from the
    same keys. ``kv_scale`` / ``use_select_bias`` / ``norm_topk`` plant a
    fault (the controls)."""
    a, s = _check_assumed(cfg), sizes(cfg)
    if a["softmax_scale_dim"] \
            != "qk_nope_head_dim + qk_rope_head_dim":
        raise NotImplementedError(
            "the reference scales scores by the root of the query/key width")
    return {"qk_nope_head_dim": s["dn"], "kv_lora_rank": s["kv_lora"],
            "rope_theta": s["rope_theta"], "q_scale": s["q_scale"],
            "kv_scale": s["kv_scale"] if kv_scale is None else kv_scale,
            "eps": cfg["rms_norm_eps"], "act": a["hidden_act"],
            "experts": s["experts"], "zero_experts": s["zero_experts"],
            "held": list(s["held"]), "top_k": s["top_k"],
            "route_scale": s["route_scale"],
            "use_select_bias": a["router_selection_bias"]
            if use_select_bias is None else use_select_bias,
            "norm_topk": a["norm_topk_prob"] if norm_topk is None
            else norm_topk}


# --- weights -------------------------------------------------------------------

def _block_shapes(s: dict) -> dict:
    d, h, f = s["d"], s["heads"], s["dense_ffn"]
    return {"wqa": (d, s["q_lora"]),
            "wqb": (s["q_lora"], h, s["dn"] + s["dr"]),
            "wkva": (d, s["latent"]),
            "wkvb": (s["kv_lora"], h, s["dn"] + s["dv"]),
            "wo": (h, s["dv"], d), "wg": (d, f), "wu": (d, f), "wd": (f, d)}


def _layer_shapes(s: dict) -> dict:
    """Every matrix of one double layer by name: ``b0.*`` / ``b1.*`` the
    two blocks, then the router, its selection bias and the held experts."""
    d, f, n = s["d"], s["ffn"], s["held"][1]
    out = {f"b{i}.{name}": shape for i in (0, 1)
           for name, shape in _block_shapes(s).items()}
    out.update(router=(d, s["experts"] + s["zero_experts"]),
               bias=(s["experts"] + s["zero_experts"],),
               eg=(n, d, f), eu=(n, d, f), ed=(n, f, d))
    return out


def parameters(s: dict) -> int:
    """Parameters of the model as built (norm scales left out)."""
    return 2 * s["vocab"] * s["d"] + s["layers"] * sum(
        int(np.prod(shape)) for shape in _layer_shapes(s).values())


def make_leaves(cfg: dict, seed: int, dtype=jnp.bfloat16, only=None) -> dict:
    """The model's matrices by name (``embed``, ``head``, ``L3.b0.wqa``,
    ``L3.router`` ...), each from one jitted call of its own in ``dtype``
    (the selection bias: float32 at its own scale). ``only``: the names'
    first part to make (``"L2"``, ``"embed"``, ``"head"``); None: all."""
    s, a = sizes(cfg), cfg["assumed_values"]
    key = seed_key(seed)

    def normal(i, shape, std, dt):
        return jax.jit(lambda k: (std * jax.random.normal(
            k, shape, jnp.float32)).astype(dt))(jax.random.fold_in(key, i))

    want = lambda part: only is None or part in only
    leaves = {}
    if want("embed"):
        leaves["embed"] = normal(0, (s["vocab"], s["d"]), a["weight_std"],
                                 dtype)
    if want("head"):
        leaves["head"] = normal(1, (s["d"], s["vocab"]), a["weight_std"],
                                dtype)
    for layer in range(s["layers"]):
        if not want(f"L{layer}"):
            continue
        for j, (name, shape) in enumerate(sorted(_layer_shapes(s).items())):
            bias = name == "bias"
            leaves[f"L{layer}.{name}"] = normal(
                64 * (layer + 1) + j, shape,
                a["selection_bias_std"] if bias else a["weight_std"],
                jnp.float32 if bias else dtype)
    return leaves


def program_tree(leaves: dict, s: dict) -> list:
    """The leaves in ``zoo.transformer_lm``'s layout (no copy): two blocks
    a double layer, the expert layer under the first's ``shortcut``."""
    ones = lambda n: jnp.ones((n,), jnp.float32)
    tree = [{"embeddings": leaves["embed"]}]
    for l in range(s["layers"]):
        for i in (0, 1):
            w = lambda name: leaves[f"L{l}.b{i}.{name}"]
            block = {
                "norm1": {"scale": ones(s["d"])},
                "attn": {"wqa": w("wqa"), "q_norm": ones(s["q_lora"]),
                         "wqb": w("wqb"), "wkva": w("wkva"),
                         "kv_norm": ones(s["kv_lora"]), "wkvb": w("wkvb"),
                         "wo": w("wo")},
                "norm2": {"scale": ones(s["d"])},
                "mlp": {"w1": w("wg"), "w2": w("wd"), "w3": w("wu")}}
            if i == 0:
                e = lambda name: leaves[f"L{l}.{name}"]
                block["shortcut"] = {
                    "gate": e("router"), "w1": e("eg"), "w2": e("ed"),
                    "select_bias": e("bias"), "w3": e("eu")}
            tree.append(block)
    tree += [{"scale": ones(s["d"])}, {"kernel": leaves["head"]}]
    return tree


def reference_layer(leaves: dict, s: dict, layer: int) -> dict:
    """One double layer's leaves under ``reference/longcat.py``'s names."""
    ones = lambda n: jnp.ones((n,), jnp.float32)
    blocks = []
    for i in (0, 1):
        bw = {name: leaves[f"L{layer}.b{i}.{name}"]
              for name in _block_shapes(s)}
        bw.update(n1=ones(s["d"]), n2=ones(s["d"]), qn=ones(s["q_lora"]),
                  kvn=ones(s["kv_lora"]))
        blocks.append(bw)
    return {"blocks": blocks,
            **{k: leaves[f"L{layer}.{k}"]
               for k in ("router", "bias", "eg", "eu", "ed")}}


def reference_tree(leaves: dict, s: dict) -> dict:
    """The whole model under the reference's names (small sizes: tests)."""
    return {"embed": leaves["embed"],
            "layers": [reference_layer(leaves, s, l)
                       for l in range(s["layers"])],
            "final_norm": jnp.ones((s["d"],), jnp.float32),
            "head": leaves["head"]}


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
    """Beyond the paths: ONE page group, and it holds latents."""
    groups = health.get("kv_groups") or {}
    if list(groups) != ["latent"]:
        raise RuntimeError(f"the cell needs one latent page group, got {groups}")


def prefill_counts(s: dict, traffic: dict, p: int, shared: int) -> dict:
    """What one prefill processed: of a prompt of ``p`` tokens the ``p -
    shared`` past the cache hit. ``prefill_latent_keys``: keys attended
    (every token all before it and itself); ``prompt_tokens`` /
    ``prompt_tokens_cached``: the prompt, and what of it came from cached
    pages."""
    pos = np.arange(shared, p)
    return {"prefill_latent_keys": int((pos + 1).sum()),
            "prompt_tokens": int(p), "prompt_tokens_cached": int(shared)}


def decode_counts(s: dict, ctx: int, page_len: int) -> dict:
    """What one decode step of a slot at context ``ctx`` (the position it
    writes) reads: keys attended, and the positions of the pages the paged
    kernel has to read for them."""
    return {"decode_latent_keys": ctx + 1,
            "decode_latent_page_tokens": (ctx // page_len + 1) * page_len}


_ROUTING_KEYS = ("rows_routed", "experts_touched", "prefill_rows_routed",
                 "prefill_experts_touched", "decode_programs", "rows_held",
                 "rows_absent", "rows_zero", "prefill_rows_held",
                 "prefill_rows_absent", "prefill_rows_zero")


def engine_counters(engine) -> dict:
    """The engine's own counters the readers need, cumulative: what the
    expert layers routed and where the rows went
    (``summary()["routing"]``), and ``held_expert_steps``: the decode
    programs that reported times the experts held in all layers (the rows a
    held expert gets a step is ``rows_held`` over it)."""
    out = dict.fromkeys(_ROUTING_KEYS, 0)
    routing = engine.metrics.summary().get("routing") or {}
    out.update({k: routing[k] for k in _ROUTING_KEYS if k in routing})
    held = sum(m.num_held for m in engine._moe)
    out["held_expert_steps"] = out["decode_programs"] * held
    return out


#: a checked sequence is padded to the next multiple of this: the mix's
#: three prompt lengths with their outputs make few compiled shapes
PAD_STEP = 512


def _forward(cfg, seed, rcfgs, seqs, positions):
    """``[[logits of sequence i under rcfgs[j]]]``: the reference's walk, a
    double layer at a time with that layer's leaves made from the seed,
    every sequence through it under every ``(rcfg, precision)``."""
    from reference import longcat
    s = sizes(cfg)
    table = make_leaves(cfg, seed, only=("embed",))["embed"]
    hs = [[longcat.embed(table, seq) for seq in seqs] for _ in rcfgs]
    del table
    for layer in range(s["layers"]):
        lw = reference_layer(make_leaves(cfg, seed, only=(f"L{layer}",)), s,
                             layer)
        hs = [[longcat.double_layer(h, lw, rcfg, precision) for h in row]
              for row, (rcfg, precision) in zip(hs, rcfgs)]
        jax.block_until_ready(hs)
        del lw
        gc.collect()
    head = make_leaves(cfg, seed, only=("head",))["head"]
    norm = jnp.ones((s["d"],), jnp.float32)
    return [[np.asarray(longcat.head(h, norm, head, pos, rcfg, precision))
             for h, pos in zip(row, positions)]
            for row, (rcfg, precision) in zip(hs, rcfgs)]


def serve_numbers(cfg, seed, sample, traffic, control=None):
    """``served_gap_mean``: the mean gap by which a served token's logit
    lies below the reference's best, over every served token of ``sample``
    (``[(prompt ids, served ids)]``), by one full forward of
    ``reference/longcat.py`` per request. With ``control`` the gaps are read
    instead for the token that a planted fault puts first at each of the
    same positions: a precision (``"int8"``, ``"bfloat16"``: the reference
    with every product in it), ``"noscale"`` (the reference without the
    scale on the normed latent: the check has to see the scale
    corrections), ``"nobias"`` (the reference's router without the
    selection bias: what chooses the experts) or ``"normtopk"`` (the
    reference's router normalising the chosen experts' weights, which the
    model does not: the check has to see the expert layer)."""
    rcfg = reference_cfg(cfg)
    rcfgs = [(rcfg, "float32")]
    if control == "noscale":
        rcfgs.append((reference_cfg(cfg, kv_scale=1.0), "float32"))
    elif control == "nobias":
        rcfgs.append((reference_cfg(cfg, use_select_bias=False), "float32"))
    elif control == "normtopk":
        rcfgs.append((reference_cfg(cfg, norm_topk=True), "float32"))
    elif control is not None:
        rcfgs.append((rcfg, control))
    max_out = traffic["output"]["max"]
    seqs, positions = [], []
    for prompt, served in sample:
        total = len(prompt) + len(served) - 1
        seq = np.zeros(-(-total // PAD_STEP) * PAD_STEP, np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):total] = served[:-1]
        seqs.append(seq)
        positions.append(np.minimum(len(prompt) - 1 + np.arange(max_out),
                                    total - 1))
    logits = _forward(cfg, seed, rcfgs, seqs, positions)
    sound, low_gaps = [], []
    for i, (_prompt, served) in enumerate(sample):
        n = len(served)
        ref = logits[0][i][:n]
        sound.append(ref.max(-1) - ref[np.arange(n), np.asarray(served)])
        if len(rcfgs) > 1:
            low = logits[1][i][:n]
            low_gaps.append(ref.max(-1) - ref[np.arange(n), low.argmax(-1)])

    def readings(gaps):
        gaps = np.concatenate(gaps)
        return {"widest": float(gaps.max()), "mean": float(gaps.mean()),
                "tokens": int(gaps.size),
                "argmax_agreement": float((gaps == 0).mean())}

    where = {"requests": len(sample),
             "longest": max(len(p) + len(g) for p, g in sample),
             **readings(sound)}
    if low_gaps:
        where = {**where, **readings(low_gaps), "program": readings(sound)}
    return {"served_gap": where["widest"], "served_gap_mean": where["mean"],
            "_where": where}


# --- operations and bytes, from shapes only -----------------------------------

def _attn_params(s: dict) -> int:
    """Parameters one token is multiplied with in one attention block:
    the low-rank query pair, the latent's down-projection and ``Wo``, and
    ``Wkvb`` once: on the token's own latent (prefill rebuilds its key and
    value) or, absorbed, its key half on the query and its value half on
    the output (the same count)."""
    h = s["heads"]
    return (s["d"] * s["q_lora"] + s["q_lora"] * h * (s["dn"] + s["dr"])
            + s["d"] * s["latent"] + s["kv_lora"] * h * (s["dn"] + s["dv"])
            + h * s["dv"] * s["d"])


def _block_params(s: dict) -> int:
    return _attn_params(s) + 3 * s["d"] * s["dense_ffn"]


def step_flops(s: dict, c: dict) -> float:
    """Model operations of the work a serving window did, from the loop's
    and the engine's counters: ``decode_tokens`` through both blocks of
    every double layer (attention projections, the dense MLP), the router
    and the vocabulary head; ``prefill_tokens`` processed (cache hits left
    out) through every block but the LAST, of which a prefill needs the
    latent's projection and no more (only its final position yields logits:
    one token's worth of the last block and the head a prefill, whatever a
    program runs); the rows routed to experts HELD here through one expert
    each (``rows_held`` / ``prefill_rows_held``: the identity experts are 0,
    the absent ones are not this chip's); attention over the keys attended:
    decode in the absorbed form (a query head over a key of ``latent`` and a
    value of ``kv_lora``), prefill over rebuilt keys and values (``dn + dr``
    and ``dv``)."""
    blocks = 2 * s["layers"]
    block = 2.0 * _block_params(s)
    router = 2.0 * s["d"] * (s["experts"] + s["zero_experts"])
    head = 2.0 * s["d"] * s["vocab"]
    last_kv = 2.0 * s["d"] * s["latent"]
    expert_row = 6.0 * s["d"] * s["ffn"]
    return (c["decode_tokens"] * (blocks * block + s["layers"] * router + head)
            + c["prefill_tokens"] * ((blocks - 1) * block + last_kv
                                     + s["layers"] * router)
            + c["prefills"] * (block + head)
            + expert_row * (c["rows_held"] + c["prefill_rows_held"])
            + 2.0 * s["heads"] * (
                c["decode_latent_keys"] * (s["latent"] + s["kv_lora"]) * blocks
                + c["prefill_latent_keys"] * (s["dn"] + s["dr"] + s["dv"])
                * (blocks - 1)))


def experts_cost(s: dict, c: dict) -> tuple:
    """``moe_grouped_experts`` over the traced window, from the programs'
    own counts: operations of the rows routed to HELD experts (the only
    rows in the layout), and the bytes of the held experts that owned at
    least one row (three matrices each; rows in and out are small beside
    them and left out)."""
    ops = c["rows_held"] * 6.0 * s["d"] * s["ffn"]
    nbytes = c["experts_touched"] * 3.0 * s["d"] * s["ffn"] * BF16
    return ops, nbytes


def paged_latent_cost(s: dict, c: dict) -> tuple:
    """``paged_latent_attention`` over the traced decode steps, every
    attention block: a query head over each key attended is a product of
    ``latent`` (the score) and one of ``kv_lora`` (the value); the bytes are
    the positions of the pages read, ONE plane of ``latent`` values."""
    blocks = 2 * s["layers"]
    ops = 2.0 * s["heads"] * c["keys"] * (s["latent"] + s["kv_lora"]) * blocks
    nbytes = 1.0 * c["page_tokens"] * s["latent"] * BF16 * blocks
    return ops, nbytes


def flash_prefill_cost(s: dict, c: dict) -> tuple:
    """``flash_fwd`` over the traced prefills: QK^T at ``dn + dr`` and PV at
    ``dv`` over the keys attended, in every block but the last (as
    ``step_flops``). Bytes: q, k, v read and the output written once a
    processed token and block (a chunk's re-read of the prefix's rebuilt
    keys and values is left out: the kernel is bound by its operations)."""
    blocks = 2 * s["layers"] - 1
    ops = 2.0 * s["heads"] * c["keys"] * (s["dn"] + s["dr"] + s["dv"]) * blocks
    nbytes = c["tokens"] * BF16 * s["heads"] * blocks \
        * 2.0 * (s["dn"] + s["dr"] + s["dv"])
    return ops, nbytes


KERNEL_COSTS = {"experts": experts_cost, "paged_latent": paged_latent_cost,
                "flash_prefill": flash_prefill_cost}
