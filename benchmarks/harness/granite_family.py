"""The Granite-4.0-H family (Mamba-2 state-space layers and, in one layer
of ten, GQA attention with no position embedding, each followed by
softmax-routed experts and a shared expert; ONE CHIP'S SHARE of an
expert-parallel deployment: the experts held here, half the vocabulary,
one period of the layer pattern; served one token a step with a state a
slot beside the attention layer's pages) for the benchmark: its sizes,
its weights from ``--seed``, the program's builder arguments and the
reference's configuration (both from the SAME keys of the configuration
file, ``builder.kwargs`` for the sizes and ``assumed_values`` for what
the source leaves open), the comparison that decides ``correct``, and
the operations and bytes of its step and kernels, from shapes only. It
is what ``serve_family_driver`` asks of a family (``FAMILIES.md``); every
reader of a Granite cell takes its sizes from here
(``cell_sizes(ctx.cell)``).

Weights are made leaf by leaf in the type they are served in: every
matrix ``N(0, weight_std)`` in bfloat16 (the router too; the tied
embedding ``N(0, embed_std)``: ``assumed`` says why), the
convolution's taps uniform in bfloat16 and its bias in float32, the
mixer's vectors (``dt_bias``, ``A_log``, ``D``) as Mamba-2 initialises
them in float32, norm scales 1 (``assumed``). The program and the plain
reference are handed the SAME leaves under their own names; the tied
head is the embedding's own array. The check walks the stack a layer at
a time, making that layer's leaves from the seed, once the engine is
gone.
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
F32 = 4


def sizes(cfg: dict) -> dict:
    """Model sizes of a Granite configuration file."""
    kw = cfg["builder"]["kwargs"]
    m = kw["attn_kinds"]["mamba"]["mamba"]
    kinds = ["mamba" if "mamba" in kw["attn_kinds"].get(t, {})
             else "attention" for t in kw["layer_types"][:kw["num_layers"]]]
    return {"vocab": kw["vocab_size"], "d": kw["d_model"],
            "heads": kw["num_heads"], "kv_heads": kw["num_kv_heads"],
            "head_dim": kw["head_dim"], "layers": kw["num_layers"],
            "kinds": kinds, "mamba_layers": kinds.count("mamba"),
            "attn_layers": kinds.count("attention"),
            "m_heads": m["num_heads"], "m_head_dim": m["head_dim"],
            "d_state": m["d_state"], "d_conv": m["d_conv"],
            "chunk": m["chunk"],
            "inner": m["num_heads"] * m["head_dim"],
            "conv_dim": m["num_heads"] * m["head_dim"] + 2 * m["d_state"],
            "ffn": kw["mlp_dim"], "shared_ffn": kw["moe_shared_dim"],
            "experts": kw["num_experts"],
            "held": tuple(kw["moe_experts_held"]), "top_k": kw["moe_top_k"],
            "positions": kw["max_len"]}


def cell_sizes(cell: dict) -> dict:
    """The sizes of a cell's configuration, read from its file as run on
    the chip (``configs/<config>.json``)."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", cell["config"] + ".json")) as f:
        return sizes(json.load(f))


def _check_assumed(cfg: dict) -> dict:
    a, kw = cfg["assumed_values"], cfg["builder"]["kwargs"]
    if a["input_linear_order"] != "activation_first" \
            or a["ssm_state_dtype"] != "float32" \
            or cfg["position_embedding_type"] != "nope" \
            or cfg["hidden_act"] != kw.get("mlp_activation", "silu") \
            or not cfg["tie_word_embeddings"] or cfg["mamba_n_groups"] != 1:
        raise NotImplementedError(
            "built: the activated half first, a float32 state, no position "
            "embedding, silu, a tied head, one group: as assumed_values and "
            "the configuration say")
    return a


def program_kwargs(cfg: dict) -> dict:
    """``zoo.transformer_lm``'s arguments: the file's ``builder.kwargs``."""
    _check_assumed(cfg)
    return json.loads(json.dumps(cfg["builder"]["kwargs"]))


def reference_cfg(cfg: dict, resets=(), gate=True,
                  state_dtype="float32") -> dict:
    """What ``reference/granite.py`` wants to know of the model, from the
    same keys. ``resets`` / ``gate`` / ``state_dtype`` plant a fault (the
    controls)."""
    _check_assumed(cfg)
    s, kw = sizes(cfg), cfg["builder"]["kwargs"]
    return {"kinds": s["kinds"], "eps": kw["norm_eps"],
            "embed_mult": float(kw["embedding_multiplier"]),
            "residual_mult": float(kw["residual_multiplier"]),
            "logits_scaling": float(kw["logits_scaling"]),
            "attn_scale": kw["attn_kinds"]["attention"]["attn_scale"],
            "mamba_heads": s["m_heads"], "mamba_head_dim": s["m_head_dim"],
            "d_state": s["d_state"], "top_k": s["top_k"],
            "held": list(s["held"]), "resets": [int(p) for p in resets],
            "gate": bool(gate), "state_dtype": state_dtype}


# --- weights -------------------------------------------------------------------

def _mamba_shapes(s: dict) -> dict:
    h = s["m_heads"]
    return {"in_proj": (s["d"], s["inner"] + s["conv_dim"] + h),
            "conv_w": (s["d_conv"], s["conv_dim"]), "conv_b": (s["conv_dim"],),
            "dt_bias": (h,), "A_log": (h,), "D": (h,),
            "out_proj": (s["inner"], s["d"])}


def _attn_shapes(s: dict) -> dict:
    d, e = s["d"], s["head_dim"]
    return {"wq": (d, s["heads"], e), "wk": (d, s["kv_heads"], e),
            "wv": (d, s["kv_heads"], e), "wo": (s["heads"], e, d)}


def _moe_shapes(s: dict) -> dict:
    d, f, n, fs = s["d"], s["ffn"], s["held"][1], s["shared_ffn"]
    return {"router": (d, s["experts"]), "e1": (n, d, f), "e3": (n, d, f),
            "e2": (n, f, d), "s1": (d, fs), "s3": (d, fs), "s2": (fs, d)}


def _layer_shapes(s: dict, kind: str) -> dict:
    """Every leaf of one layer by name: the mixer's, then the experts'."""
    mixer = _mamba_shapes(s) if kind == "mamba" else _attn_shapes(s)
    return {**mixer, **_moe_shapes(s)}


def parameters(s: dict) -> int:
    """Parameters of the model as built (norm scales left out; the head
    is the embedding)."""
    return s["vocab"] * s["d"] + sum(
        int(np.prod(shape)) for kind in s["kinds"]
        for shape in _layer_shapes(s, kind).values())


def make_leaves(cfg: dict, seed: int, dtype=jnp.bfloat16, only=None) -> dict:
    """The model's leaves by name (``embed``, ``L3.in_proj``, ``L5.wq``,
    ``L3.e1`` ...), each from one jitted call of its own (module doc).
    ``dtype``: the matrices' type. ``only``: the names' first part to
    make (``"L2"``, ``"embed"``); None: all."""
    s, a = sizes(cfg), cfg["assumed_values"]
    key = seed_key(seed)
    std = a["weight_std"]

    def draw(i, shape, how, dt):
        def f(k):
            if how in ("normal", "embed"):
                scale = a["embed_std"] if how == "embed" else std
                return scale * jax.random.normal(k, shape, jnp.float32)
            if how == "conv":
                return jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5)
            # dt_bias: the inverse softplus of a step log-uniform in
            # [1e-3, 1e-1] (Mamba-2's initialisation)
            u = jax.random.uniform(k, shape, jnp.float32)
            step = jnp.exp(u * (math.log(0.1) - math.log(1e-3))
                           + math.log(1e-3))
            return step + jnp.log(-jnp.expm1(-step))
        return jax.jit(lambda k: f(k).astype(dt))(jax.random.fold_in(key, i))

    want = lambda part: only is None or part in only
    leaves = {}
    if want("embed"):
        leaves["embed"] = draw(0, (s["vocab"], s["d"]), "embed", dtype)
    for layer, kind in enumerate(s["kinds"]):
        if not want(f"L{layer}"):
            continue
        for j, (name, shape) in enumerate(
                sorted(_layer_shapes(s, kind).items())):
            name_l = f"L{layer}.{name}"
            if name == "A_log":
                leaves[name_l] = jnp.log(jnp.arange(1, shape[0] + 1,
                                                    dtype=jnp.float32))
            elif name == "D":
                leaves[name_l] = jnp.full(shape, a["d_init"], jnp.float32)
            elif name == "dt_bias":
                leaves[name_l] = draw(64 * (layer + 1) + j, shape, "dt",
                                      jnp.float32)
            elif name in ("conv_w", "conv_b"):
                leaves[name_l] = draw(64 * (layer + 1) + j, shape, "conv",
                                      dtype if name == "conv_w"
                                      else jnp.float32)
            else:
                leaves[name_l] = draw(64 * (layer + 1) + j, shape, "normal",
                                      dtype)
    return leaves


def _ones(n):
    return jnp.ones((n,), jnp.float32)


def program_tree(leaves: dict, s: dict) -> list:
    """The leaves in ``zoo.transformer_lm``'s layout (no copy): the
    embedding, its multiplier, the blocks, the final norm and the tied
    head holding the embedding's own array."""
    tree = [{"embeddings": leaves["embed"]}, {}]
    for l, kind in enumerate(s["kinds"]):
        w = lambda name: leaves[f"L{l}.{name}"]
        if kind == "mamba":
            mixer = {name: w(name) for name in _mamba_shapes(s)}
            mixer["norm"] = _ones(s["inner"])
        else:
            mixer = {name: w(name) for name in _attn_shapes(s)}
        tree.append({
            "norm1": {"scale": _ones(s["d"])}, "attn": mixer,
            "norm2": {"scale": _ones(s["d"])},
            "mlp": {"gate": w("router"), "w1": w("e1"), "w2": w("e2"),
                    "w3": w("e3"),
                    "shared": {"w1": w("s1"), "w2": w("s2"),
                               "w3": w("s3")}}})
    tree += [{"scale": _ones(s["d"])}, {"embeddings": leaves["embed"]}]
    return tree


def reference_layer(leaves: dict, s: dict, layer: int) -> dict:
    """One layer's leaves under ``reference/granite.py``'s names."""
    kind = s["kinds"][layer]
    lw = {name: leaves[f"L{layer}.{name}"]
          for name in _layer_shapes(s, kind)}
    lw.update(n1=_ones(s["d"]), n2=_ones(s["d"]))
    if kind == "mamba":
        lw["norm"] = _ones(s["inner"])
    return lw


def reference_tree(leaves: dict, s: dict) -> dict:
    """The whole model under the reference's names (small sizes: tests)."""
    return {"embed": leaves["embed"],
            "layers": [reference_layer(leaves, s, l)
                       for l in range(s["layers"])],
            "final_norm": _ones(s["d"])}


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
PROGRAM_PATHS = {"prefill": ("flash_attention=kernel", "moe=grouped_kernel",
                             "ssd=kernel"),
                 "decode_greedy": ("paged_attention=kernel",
                                   "moe=grouped_kernel", "ssm=kernel")}


def health_check(health: dict) -> None:
    """Beyond the paths: the attention layer's page group and the state
    group beside it, and every program donating the cache."""
    groups = health.get("kv_groups") or {}
    if sorted(groups) != ["full", "ssm"] or not groups["ssm"].get("bytes"):
        raise RuntimeError(f"the cell needs a page group and a state group, "
                           f"got {groups}")
    kept = [p for p, paths in health["programs"].items()
            if "kv_cache=donated" not in paths]
    if kept:
        raise RuntimeError(f"programs that do not donate the cache: {kept}")


def prefill_counts(s: dict, traffic: dict, p: int, shared: int) -> dict:
    """What one prefill processed: the keys the attention layers attended
    (every token all before it and itself), and the prompt."""
    pos = np.arange(shared, p)
    return {"prefill_full_keys": int((pos + 1).sum()) * s["attn_layers"],
            "prompt_tokens": int(p)}


def decode_counts(s: dict, ctx: int, page_len: int) -> dict:
    """What one decode step of a slot at context ``ctx`` (the position it
    writes) reads in the attention layers: keys attended, and the
    positions of the pages the paged kernel reads for them."""
    return {"decode_full_keys": (ctx + 1) * s["attn_layers"],
            "decode_full_page_tokens":
                (ctx // page_len + 1) * page_len * s["attn_layers"]}


_ROUTING_KEYS = ("rows_routed", "experts_touched", "prefill_rows_routed",
                 "prefill_experts_touched", "decode_programs", "rows_held",
                 "rows_absent", "prefill_rows_held", "prefill_rows_absent")
_SSM_KEYS = ("ssm_state_slot_steps", "ssm_prefill_positions",
             "ssm_state_resets")


def engine_counters(engine) -> dict:
    """The engine's own counters the readers need, cumulative: what the
    expert layers routed and where the rows went (``summary()
    ["routing"]``), ``held_expert_steps`` (the decode programs times the
    experts held in all layers), and the state group's
    (``ServingEngine.ssm_counters``: slot x layer updates by decode,
    positions scanned by prefill summed over layers, resets)."""
    out = dict.fromkeys(_ROUTING_KEYS + _SSM_KEYS, 0)
    routing = engine.metrics.summary().get("routing") or {}
    out.update({k: routing[k] for k in _ROUTING_KEYS if k in routing})
    held = sum(m.num_held for m in engine._moe)
    out["held_expert_steps"] = out["decode_programs"] * held
    out.update({k: engine.ssm_counters[k] for k in _SSM_KEYS})
    return out


#: a checked sequence is padded to the next multiple of this
PAD_STEP = 512


def _forward(cfg, seed, rcfgs, seqs, positions):
    """``[[logits of sequence i under rcfgs[j][i]]]``: the reference's
    walk, a layer at a time with that layer's leaves made from the seed,
    every sequence through it under every ``([rcfg a sequence],
    precision)``."""
    from reference import granite
    s = sizes(cfg)
    table = make_leaves(cfg, seed, only=("embed",))["embed"]
    hs = [[granite.embed(table, seq, rc) for seq, rc in zip(seqs, row)]
          for row, _ in rcfgs]
    for layer, kind in enumerate(s["kinds"]):
        lw = reference_layer(make_leaves(cfg, seed, only=(f"L{layer}",)), s,
                             layer)
        hs = [[granite.layer(h, lw, kind, rc, precision)
               for h, rc in zip(row, rcs)]
              for row, (rcs, precision) in zip(hs, rcfgs)]
        jax.block_until_ready(hs)
        del lw
        gc.collect()
    norm = _ones(s["d"])
    return [[np.asarray(granite.head(h, norm, table, pos, rc, precision))
             for h, pos, rc in zip(row, positions, rcs)]
            for row, (rcs, precision) in zip(hs, rcfgs)]


def serve_numbers(cfg, seed, sample, traffic, control=None):
    """``served_gap_mean``: the mean gap by which a served token's logit
    lies below the reference's best, over every served token of ``sample``
    (``[(prompt ids, served ids)]``), by one full forward of
    ``reference/granite.py`` per request. With ``control`` the gaps are
    read instead for the token that a planted fault puts first at each of
    the same positions: a precision (``"int8"``, ``"bfloat16"``: the
    reference with every product in it), ``"statereset"`` (the reference
    restarting the state and the convolution's window where the engine's
    second prefill chunk begins, ``prefill_chunk``, and where decode
    begins: the check has to see the state carried across prefill chunks
    and into decode), ``"nogate"`` (the
    reference's mixer output without ``* silu(z)``: the check has to see
    the state-space layers) or ``"bf16state"`` (the reference's state and
    convolution window rounded to bfloat16 at every position, below the
    float32 state the configuration states)."""
    plain = reference_cfg(cfg)
    rcfgs = [([plain] * len(sample), "float32")]
    if control == "statereset":
        at = traffic["engine"]["prefill_chunk"]
        rcfgs.append(([reference_cfg(cfg, resets=(at, len(p)))
                       for p, _ in sample], "float32"))
    elif control == "nogate":
        rcfgs.append(([reference_cfg(cfg, gate=False)] * len(sample),
                      "float32"))
    elif control == "bf16state":
        rcfgs.append(([reference_cfg(cfg, state_dtype="bfloat16")]
                      * len(sample), "float32"))
    elif control is not None:
        rcfgs.append(([plain] * len(sample), control))
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

def _mamba_params(s: dict) -> int:
    """Parameters one token is multiplied with in a mamba mixer: the in
    and out projections."""
    return s["d"] * (s["inner"] + s["conv_dim"] + s["m_heads"]) \
        + s["inner"] * s["d"]


def _attn_params(s: dict) -> int:
    return s["d"] * (s["heads"] + 2 * s["kv_heads"]) * s["head_dim"] \
        + s["heads"] * s["head_dim"] * s["d"]


def _common_params(s: dict) -> int:
    """A layer's router and shared expert."""
    return s["d"] * s["experts"] + 3 * s["d"] * s["shared_ffn"]


def scan_flops_per_position(s: dict) -> float:
    """Operations of the chunked scan per position and mamba layer: per
    head the masked mix with x (``chunk * P`` products a position), the
    state's readout (``N * P``) and update (``P * N``); and once a
    position the ``C . B`` products of its chunk (``chunk * N``)."""
    h, p, n, c = s["m_heads"], s["m_head_dim"], s["d_state"], s["chunk"]
    return 2.0 * (h * (c * p + 2 * n * p) + c * n)


def step_flops(s: dict, c: dict) -> float:
    """Model operations of the work a serving window did, from the loop's
    and the engine's counters: ``decode_tokens`` through every mixer, the
    router and shared expert of every layer and the vocabulary head;
    ``prefill_tokens`` processed through every layer's mixer, router and
    shared expert (the last position of a prefill through the head:
    ``prefills``); the rows routed to experts HELD here through one expert
    each (``rows_held`` / ``prefill_rows_held``); attention over the keys
    attended (``*_full_keys``, summed over the attention layers); the
    state: a decode update and readout of every head (``4 P N`` a slot
    and layer: ``ssm_state_slot_steps``) and the scan of every prefill
    position (``ssm_prefill_positions``)."""
    layer = 2.0 * (s["mamba_layers"] * _mamba_params(s)
                   + s["attn_layers"] * _attn_params(s)
                   + s["layers"] * _common_params(s))
    head = 2.0 * s["d"] * s["vocab"]
    expert_row = 6.0 * s["d"] * s["ffn"]
    qk = 4.0 * s["heads"] * s["head_dim"]
    return (c["decode_tokens"] * (layer + head)
            + c["prefill_tokens"] * layer + c["prefills"] * head
            + expert_row * (c["rows_held"] + c["prefill_rows_held"])
            + qk * (c["decode_full_keys"] + c["prefill_full_keys"])
            + 4.0 * s["m_heads"] * s["m_head_dim"] * s["d_state"]
            * c["ssm_state_slot_steps"]
            + scan_flops_per_position(s) * c["ssm_prefill_positions"])


def experts_cost(s: dict, c: dict) -> tuple:
    """``moe_grouped_experts`` over the traced window, from the programs'
    own counts: operations of the rows routed to HELD experts, and the
    bytes of the held experts that owned at least one row (three matrices
    each; rows in and out are small beside them and left out)."""
    ops = c["rows_held"] * 6.0 * s["d"] * s["ffn"]
    nbytes = c["experts_touched"] * 3.0 * s["d"] * s["ffn"] * BF16
    return ops, nbytes


def ssm_step_cost(s: dict, c: dict) -> tuple:
    """``ssm_decode_step`` over the traced decode steps: every slot and
    mamba layer of a step (``slot_steps``: the kernel runs every slot and
    gives a slot that is not decoding its state back) reads and writes
    its float32 state, ``H P N``, and updates and reads it out: ``4 H P
    N`` operations. The slots that were not decoding cost their bytes
    too, and are left out of the count: the share is of the work done."""
    state = s["m_heads"] * s["m_head_dim"] * s["d_state"]
    return 4.0 * state * c["slot_steps"], 2.0 * F32 * state * c["slot_steps"]


def ssd_scan_cost(s: dict, c: dict) -> tuple:
    """``ssd_chunk_scan`` over the traced prefill positions (summed over
    mamba layers): ``scan_flops_per_position`` a position; bytes: x read
    and y written in float32 (``H P`` each), B, C, dt and dt*A
    (``2 N + 2 H``), a position. The state in and out once a chunk is
    small beside them and left out."""
    h, p, n = s["m_heads"], s["m_head_dim"], s["d_state"]
    per = F32 * (2 * h * p + 2 * n + 2 * h)
    return scan_flops_per_position(s) * c["positions"], per * c["positions"]


def paged_full_cost(s: dict, c: dict) -> tuple:
    """``paged_attention`` over the traced decode steps of the attention
    layers: QK^T and PV over the keys attended (``keys``, summed over
    attention layers), and the K and V pages' positions read."""
    ops = 4.0 * s["heads"] * s["head_dim"] * c["keys"]
    nbytes = 2.0 * s["kv_heads"] * s["head_dim"] * BF16 * c["page_tokens"]
    return ops, nbytes


KERNEL_COSTS = {"experts": experts_cost, "ssm_step": ssm_step_cost,
                "ssd_scan": ssd_scan_cost, "paged_full": paged_full_cost}
