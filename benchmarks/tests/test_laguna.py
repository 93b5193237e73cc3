"""The Laguna family's own pieces: the configuration file against the
catalog row it was taken from, sizes and operation counts, the reference
against itself (a window that covers everything is no window; a planted
window and a lower precision read over the program), the family driver's
counts, and the new readers on records that lack what they read (the
parent commit's, another driver's)."""

import json
import os
import types

import numpy as np
import pytest

from conftest import BENCH

from harness import common, laguna_family as fam
from readers import counter_ratio, family_kernel_roofline, family_mfu
from reference import laguna

with open(os.path.join(BENCH, "configs", "laguna-xs.2.json")) as f:
    CFG = json.load(f)
S = fam.sizes(CFG)
SMALL = common.config_for(CFG, rehearse=True)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = {"name": "serve-laguna-xs2-l5-mixedlen-closed32",
        "config": "laguna-xs.2", "chips": 1}


def test_file_holds_every_number_of_the_catalog_row():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the guides here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
    assert CFG["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if CFG.get(k) != v]
    assert differs == CFG["reduced"] == ["num_hidden_layers"]
    assert CFG["published"]["num_hidden_layers"] == row["config"]["num_hidden_layers"]


def test_sizes_are_the_published_widths_at_layers_0_to_4():
    assert (S["d"], S["kv_heads"], S["d_head"], S["experts"], S["top_k"],
            S["ffn"], S["dense_ffn"], S["shared_ffn"], S["vocab"]) \
        == (2048, 8, 128, 256, 8, 512, 8192, 512, 100352)
    n = CFG["num_hidden_layers"]
    assert S["heads"] == CFG["num_attention_heads_per_layer"][:n] \
        == [48, 64, 64, 64, 48]
    assert S["window"] == [None, 512, 512, 512, None]
    assert S["sparse"] == [False, True, True, True, True]
    assert S["layer_types"] == CFG["layer_types"][:n]
    assert round(fam.parameters(S) / 1e9, 2) == 3.87          # 7.74 GB of bf16
    # the whole model, from the published keys: 33.44B, the stated 33.4B
    full, sliding = 29_360_128, 37_748_736
    assert (np.prod(fam._layer_shapes(S, 0)["wq"]) * 2
            + np.prod(fam._layer_shapes(S, 0)["wk"]) * 2) == full
    sparse = 256 * 3 * 2048 * 512 + 3 * 2048 * 512 + 2048 * 256
    whole = 2 * 100352 * 2048 + 10 * full + 30 * sliding \
        + 3 * 2048 * 8192 + 39 * sparse
    assert round(whole / 1e9, 2) == 33.44
    kv_token = 2 * S["kv_heads"] * S["d_head"] * 2
    assert kv_token == 4096                   # bytes a token a layer


def test_program_and_reference_read_the_assumed_values_from_one_place():
    kw, rcfg = fam.program_kwargs(CFG), fam.reference_cfg(CFG)
    a = CFG["assumed_values"]
    assert (kw["mlp_activation"], kw["moe_score"], kw["moe_norm_topk"],
            kw["mlp_gated"], kw["qk_norm"]) \
        == (a["hidden_act"], a["router_score"], a["router_norm_topk"],
            a["mlp_gated"], a["qk_norm"])
    assert rcfg["router"] == {"score": a["router_score"],
                              "norm_topk": a["router_norm_topk"],
                              "scale": CFG["moe_routed_scaling_factor"]}
    assert rcfg["act"] == a["hidden_act"] and rcfg["eps"] == CFG["rms_norm_eps"]
    assert kw["attn_kinds"]["sliding_attention"]["attn_window"] \
        == rcfg["kinds"]["sliding_attention"]["window"] == CFG["sliding_window"]
    rope = rcfg["kinds"]["full_attention"]["rope"]
    pub = CFG["rope_parameters"]["full_attention"]
    assert {k: rope[k] for k in pub} == pub
    assert rcfg["kinds"]["sliding_attention"]["rope"]["rope_theta"] \
        == CFG["rope_parameters"]["sliding_attention"]["rope_theta"]
    # one edit: a window that leaves the query's position out is one wider
    other = json.loads(json.dumps(CFG))
    other["assumed_values"]["window_includes_query"] = False
    assert fam.program_kwargs(other)["attn_kinds"]["sliding_attention"][
        "attn_window"] == fam.reference_cfg(other)["kinds"][
        "sliding_attention"]["window"] == 513
    other["assumed_values"]["router_selection_bias"] = True
    with pytest.raises(NotImplementedError):
        fam.program_kwargs(other)


def test_step_flops_count_routed_and_shared_experts_and_each_kinds_keys():
    zero = dict.fromkeys(("prefill_tokens", "decode_tokens", "prefills",
                          "prefill_full_keys", "decode_full_keys",
                          "prefill_window_keys", "decode_window_keys"), 0)
    token = fam.step_flops(S, {**zero, "decode_tokens": 1})
    experts = 4 * 9 * 6 * 2048 * 512             # top-8 and the shared one
    attn = 2 * (2 * 29_360_128 + 3 * 37_748_736)
    dense, head = 6 * 2048 * 8192, 2 * 2048 * 100352
    routers = 4 * 2 * 2048 * 256
    assert token == experts + attn + dense + head + routers
    prefill = fam.step_flops(S, {**zero, "prefill_tokens": 1})
    last = 2 * 29_360_128 + 9 * 6 * 2048 * 512 + 2 * 2048 * 256
    assert prefill == token - head - last + 2 * 2 * 2048 * 8 * 128
    keys = fam.step_flops(S, {**zero, "decode_full_keys": 1,
                              "decode_window_keys": 1})
    assert keys == 4 * 128 * (2 * 48 + 3 * 64)
    assert fam.step_flops(S, {**zero, "prefill_full_keys": 1,
                              "prefill_window_keys": 1}) \
        == 4 * 128 * (48 + 3 * 64)             # the last layer: one position
    ops, nbytes = fam.experts_cost(S, {"routed_rows": 1024,
                                       "experts_touched": 600})
    assert ops == 1024 * 6.0 * 2048 * 512 and nbytes == 600 * 3 * 2048 * 512 * 2
    ops, nbytes = fam.paged_window_cost(S, {"keys": 512, "page_tokens": 640})
    assert nbytes == 640 * 4096 * 3 and ops == 4 * 128 * 192 * 512
    ops, nbytes = fam.paged_full_cost(S, {"keys": 9000, "page_tokens": 9088})
    assert nbytes == 9088 * 4096 * 2 and ops == 4 * 128 * 96 * 9000


def test_loop_counts_by_kind_of_layer():
    traffic = {"engine": {"prefill_chunk": 2048}}
    c = fam.prefill_counts(S, traffic, 3000, 512)
    assert c["prefill_full_keys"] == sum(range(513, 3001))
    assert c["prefill_window_keys"] == 512 * (3000 - 512)
    # two chunks (2,048 and 440 tokens): the first 511 of each see less
    # than a window inside it
    assert c["prefill_window_flash_keys"] \
        == sum(min(i + 1, 512) for i in range(2048)) \
        + sum(min(i + 1, 512) for i in range(440))
    assert fam.prefill_counts(S, traffic, 300, 0)["prefill_window_keys"] \
        == sum(range(1, 301))
    d = fam.decode_counts(S, 1000, 128)         # writes position 1000
    assert d == {"decode_full_keys": 1001, "decode_full_page_tokens": 1024,
                 "decode_window_keys": 512,
                 "decode_window_page_tokens": 5 * 128}   # pages 3..7
    assert fam.decode_counts(S, 100, 128)["decode_window_page_tokens"] == 128


def _tiny_weights(seed=3):
    s = fam.sizes(SMALL)
    return s, fam.reference_tree(
        fam.make_leaves(SMALL, seed, dtype=np.float32), s)


def test_reference_window_that_covers_everything_is_causal_attention():
    s, w = _tiny_weights()
    rcfg = fam.reference_cfg(SMALL)
    toks = np.random.default_rng(0).integers(0, s["vocab"], 256)
    pos = np.array([3, 7, 8, 40, 255])
    base = np.asarray(laguna.logits_at(w, rcfg, toks, pos))
    wide = np.asarray(laguna.logits_at(
        w, fam.reference_cfg(SMALL, window=256), toks, pos))
    every = dict(rcfg, kinds={
        k: {**v, "window": None} for k, v in rcfg["kinds"].items()})
    none = np.asarray(laguna.logits_at(w, every, toks, pos))
    np.testing.assert_allclose(wide, none, atol=1e-6)
    # inside the first window all agree; past it the window is seen
    np.testing.assert_allclose(base[:2], none[:2], atol=1e-6)
    assert np.abs(base[2:] - none[2:]).max() > 1e-3
    with pytest.raises(ValueError, match="multiple"):
        laguna.logits_at(w, rcfg, toks[:100], pos[:1])


def test_planted_faults_read_over_the_program(monkeypatch):
    """The reference's own greedy tokens read 0; a planted window of 4 and
    the reference in int8 read over it, from the same positions."""
    s, w = _tiny_weights()
    rcfg = fam.reference_cfg(SMALL)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, s["vocab"], 40)
    served = []
    for _ in range(12):
        seq = np.zeros(256, np.int64)
        seq[:40 + len(served)] = np.concatenate([prompt, served])
        lg = laguna.logits_at(w, rcfg, seq, np.array([39 + len(served)]))
        served.append(int(np.asarray(lg)[0].argmax()))
    monkeypatch.setattr(fam, "make_leaves", lambda cfg, seed: None)
    monkeypatch.setattr(fam, "reference_tree", lambda leaves, sizes: w)
    traffic = {"output": {"max": 16}}
    read = lambda control: fam.serve_numbers(
        SMALL, 0, [(prompt, served)], traffic, control=control)
    sound = read(None)
    assert sound["served_gap_mean"] == 0.0
    assert sound["_where"]["tokens"] == 12 and sound["_where"]["longest"] == 52
    window = read("window4")
    assert window["served_gap_mean"] > 1e-3
    assert window["_where"]["program"]["mean"] == 0.0
    assert read("int8")["served_gap_mean"] >= 0.0


def _ctx(trace_counters, counters=None, kernels=()):
    trace = types.SimpleNamespace(
        device_ops={"/device:TPU:0": [(0, 1_000_000, f"%{k}.1 = f32[] custom-call()")
                                      for k in kernels]},
        device_programs={}, host_spans=[], chips=1)
    rec = types.SimpleNamespace(trace_counters=trace_counters,
                                counters=counters or {}, trace_window_s=4.0)
    return types.SimpleNamespace(
        record=rec, trace=trace, cell=CELL, chips=1,
        peaks={"flops_bf16": 197e12, "bytes_per_s": 819e9})


def test_new_readers_find_nothing_on_another_drivers_record():
    """The parent's program has no routing counts, no window group and no
    kernel of the window's name: each reader leaves its metric out."""
    chat = {"prefill_tokens": 10, "decode_tokens": 10, "prefills": 1}
    assert family_mfu.read(_ctx(chat), needs=["decode_full_keys"]) is None
    assert family_kernel_roofline.read(
        _ctx(chat), ["paged_window_attention"], "paged_window",
        {"keys": ["decode_window_keys"],
         "page_tokens": ["decode_window_page_tokens"]}) is None
    assert family_kernel_roofline.read(
        _ctx(chat, kernels=["moe_grouped_experts"]), ["moe_grouped_experts"],
        "experts", {"routed_rows": ["rows_routed"],
                    "experts_touched": ["experts_touched"]}) is None
    assert counter_ratio.read(_ctx({}, {"seen_tokens": 5}),
                              ["window_pages_released"], ["seen_tokens"]) is None
    gpt = dict(CELL, config="cerebras-gpt-1.3b")
    ctx = _ctx({"decode_full_keys": 1})
    ctx.cell = gpt
    assert family_mfu.read(ctx, needs=["decode_full_keys"]) is None


def test_new_readers_read_the_familys_counts():
    c = {"prefill_tokens": 4096, "decode_tokens": 2000, "prefills": 2,
         "prefill_full_keys": 8_000_000, "prefill_window_keys": 2_000_000,
         "decode_full_keys": 9_000_000, "decode_window_keys": 1_000_000,
         "decode_window_page_tokens": 1_200_000,
         "rows_routed": 60_000, "prefill_rows_routed": 90_000,
         "experts_touched": 40_000, "prefill_experts_touched": 2_000}
    mfu = family_mfu.read(_ctx(c), needs=["decode_full_keys"])
    assert mfu == pytest.approx(100 * fam.step_flops(S, c) / 4.0 / 197e12)
    got = family_kernel_roofline.read(
        _ctx(c, kernels=["paged_window_attention"]),
        ["paged_window_attention"], "paged_window",
        {"keys": ["decode_window_keys"],
         "page_tokens": ["decode_window_page_tokens"]})
    least = 1_200_000 * 4096 * 3 / 819e9
    assert got == pytest.approx(100 * least / 1e-3)
    got = family_kernel_roofline.read(
        _ctx(c, kernels=["moe_grouped_experts"]), ["moe_grouped_experts"],
        "experts", {"routed_rows": ["rows_routed", "prefill_rows_routed"],
                    "experts_touched": ["experts_touched",
                                        "prefill_experts_touched"]})
    assert got == pytest.approx(
        100 * (42_000 * 3 * 2048 * 512 * 2 / 819e9) / 1e-3)
