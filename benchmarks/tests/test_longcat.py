"""The LongCat-Flash family's own pieces: the configuration file against the
catalog row it was taken from, sizes and operation counts against hand
counts, program and reference built from one place, the reference's controls
reading over the program, the family driver's counts, the readers on the
family's record, and the rehearsals of the two cells this configuration's PR
brought (``run.execute`` at the rehearsal size on the CPU)."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as bench_run
from conftest import BENCH, ROOT

from harness import common, longcat_family as fam
from readers import counter_ratio, family_kernel_roofline, family_mfu
from reference import longcat

with open(os.path.join(BENCH, "configs", "longcat-flash-chat.json")) as f:
    CFG = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
S = fam.sizes(CFG)
SMALL = common.config_for(CFG, rehearse=True)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = {"name": "serve-longcat-flash-l4-docqa-closed32",
        "config": "longcat-flash-chat", "chips": 1}
PREFILL_CELL = "serve-cgpt1.3b-prefill-closed16"


def test_file_holds_every_number_of_the_catalog_row():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the guides here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LongCat-Flash-Chat")
    assert CFG["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if CFG.get(k) != v]
    assert sorted(differs) == sorted(CFG["reduced"]) \
        == ["n_routed_experts", "num_layers", "vocab_size"]
    assert {k: CFG["published"][k] for k in differs} \
        == {k: row["config"][k] for k in differs}


def test_sizes_are_the_published_widths_and_the_chips_share():
    assert (S["d"], S["heads"], S["q_lora"], S["kv_lora"], S["dn"], S["dr"],
            S["dv"], S["dense_ffn"], S["ffn"], S["experts"],
            S["zero_experts"], S["top_k"], S["latent"]) \
        == (6144, 64, 1536, 512, 128, 64, 128, 12288, 2048, 512, 256, 12, 576)
    assert (S["layers"], S["held"], S["vocab"]) == (4, (0, 16), 16384)
    assert (S["q_scale"], S["kv_scale"]) == (2.0, pytest.approx(12 ** 0.5))
    # one attention block, by hand: 90.57M; a double layer outside the
    # experts 638.8M; the 16 held experts 604.0M
    attn = 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256 \
        + 8192 * 6144
    assert attn == fam._attn_params(S) == 90_570_752
    outside = 2 * attn + 2 * 3 * 6144 * 12288 + 6144 * 768 + 768
    held = 16 * 3 * 6144 * 2048
    shapes = fam._layer_shapes(S)
    assert sum(int(np.prod(s)) for s in shapes.values()) == outside + held
    assert round(outside / 1e6, 1) == 638.8 and round(held / 1e6, 1) == 604.0
    # 5.17B as built = 10.35 GB of bfloat16; the whole model 560B
    assert round(fam.parameters(S) / 1e9, 2) == 5.17
    whole = 28 * (outside + 512 * 3 * 6144 * 2048) + 2 * 131072 * 6144
    assert round(whole / 1e9) == 561
    # a token's cache: 576 values an attention, 8 attentions
    assert 2 * S["layers"] * S["latent"] * 2 == 9216


def test_program_and_reference_read_the_assumed_values_from_one_place():
    kw = fam.program_kwargs(CFG)
    lat = kw["attn_kinds"]["latent_attention"]["latent"]
    assert (lat["q_scale"], kw["moe_norm_topk"], kw["moe_select_bias"],
            kw["mlp_activation"], kw["mlp_gated"], kw["moe_score"]) \
        == (2.0, False, True, "silu", True, "softmax")
    r = fam.reference_cfg(CFG)
    assert (r["q_scale"], r["kv_scale"], r["route_scale"], r["held"],
            r["use_select_bias"], r["eps"]) \
        == (2.0, lat["kv_scale"], 6.0, [0, 16], True, 1e-5)
    assert fam.reference_cfg(CFG, kv_scale=1.0)["kv_scale"] == 1.0
    assert not r["norm_topk"] and fam.reference_cfg(
        CFG, norm_topk=True)["norm_topk"]
    broken = json.loads(json.dumps(CFG))
    broken["assumed_values"]["router_bias"] = True
    with pytest.raises(NotImplementedError):
        fam.program_kwargs(broken)
    module = fam.build_module(SMALL)
    assert len(module.layers) == 1 + 2 * 3 + 2


def test_step_flops_and_kernel_costs_against_hand_counts():
    block = 2 * (90_570_752 + 3 * 6144 * 12288)
    router, head = 2 * 6144 * 768, 2 * 6144 * 16384
    zero = dict.fromkeys(("decode_tokens", "prefill_tokens", "prefills",
                          "rows_held", "prefill_rows_held",
                          "decode_latent_keys", "prefill_latent_keys"), 0)
    assert fam.step_flops(S, {**zero, "decode_tokens": 1}) \
        == 8 * block + 4 * router + head
    # a prefill token: 7 blocks, the last one's latent, the routers
    assert fam.step_flops(S, {**zero, "prefill_tokens": 1}) \
        == 7 * block + 2 * 6144 * 576 + 4 * router
    assert fam.step_flops(S, {**zero, "prefills": 1}) == block + head
    # an identity expert is 0; a held expert's row is three products
    assert fam.step_flops(S, {**zero, "rows_held": 1}) == 6 * 6144 * 2048
    # decode attends the latent: 576 + 512 a key and head; prefill 192 + 128
    assert fam.step_flops(S, {**zero, "decode_latent_keys": 1}) \
        == 2 * 64 * (576 + 512) * 8
    assert fam.step_flops(S, {**zero, "prefill_latent_keys": 1}) \
        == 2 * 64 * 320 * 7
    ops, nbytes = fam.paged_latent_cost(S, {"keys": 10, "page_tokens": 128})
    assert ops == 10 * 64 * (576 + 512) * 2 * 8
    assert nbytes == 128 * 576 * 2 * 8          # ONE plane: no separate V
    ops, nbytes = fam.experts_cost(S, {"rows_held": 5, "experts_touched": 3})
    assert (ops, nbytes) == (5 * 6 * 6144 * 2048, 3 * 3 * 6144 * 2048 * 2)
    ops, nbytes = fam.flash_prefill_cost(S, {"keys": 100, "tokens": 10})
    assert ops == 100 * 64 * 2 * 320 * 7
    assert nbytes == 10 * 2 * 64 * 7 * 2 * 320


def test_loop_counts():
    traffic = {"engine": {"prefill_chunk": 2048}}
    got = fam.prefill_counts(S, traffic, 12544, 12288)
    assert got == {"prefill_latent_keys": sum(range(12289, 12545)),
                   "prompt_tokens": 12544, "prompt_tokens_cached": 12288}
    assert fam.prefill_counts(S, traffic, 4, 0)["prefill_latent_keys"] == 10
    assert fam.decode_counts(S, 13000, 128) \
        == {"decode_latent_keys": 13001, "decode_latent_page_tokens": 13056}


def _tiny():
    leaves = fam.make_leaves(SMALL, 5, jnp.float32)
    s = fam.sizes(SMALL)
    return fam.reference_tree(leaves, s), s


def test_leaves_are_the_same_made_whole_or_a_layer_at_a_time():
    whole = fam.make_leaves(SMALL, 5, jnp.float32)
    part = fam.make_leaves(SMALL, 5, jnp.float32, only=("L1", "head"))
    assert set(part) == {k for k in whole if k.startswith("L1.")} | {"head"}
    assert all(bool(jnp.array_equal(part[k], whole[k])) for k in part)
    assert whole["L0.bias"].dtype == jnp.float32
    assert float(jnp.std(whole["L0.bias"])) < 3e-3


def test_planted_faults_read_over_the_program():
    """The controls change what the reference computes at this size: no
    scale on the latent, no selection bias, normalised expert weights,
    int8 products."""
    w, s = _tiny()
    rng = np.random.default_rng(2)
    seq = np.zeros(256, np.int32)
    seq[:40] = rng.integers(0, s["vocab"], 40)
    pos = np.arange(8, 40)
    rcfg = fam.reference_cfg(SMALL)
    sound = np.asarray(longcat.logits_at(w, rcfg, seq, pos))
    for planted in (fam.reference_cfg(SMALL, kv_scale=1.0),
                    fam.reference_cfg(SMALL, use_select_bias=False),
                    fam.reference_cfg(SMALL, norm_topk=True)):
        low = np.asarray(longcat.logits_at(w, planted, seq, pos))
        assert float(np.abs(low - sound).max()) > 1e-4
    int8 = np.asarray(longcat.logits_at(w, rcfg, seq, pos, precision="int8"))
    bf16 = np.asarray(longcat.logits_at(w, rcfg, seq, pos,
                                        precision="bfloat16"))
    assert float(np.abs(int8 - sound).max()) > float(np.abs(bf16 - sound).max()) \
        > 0


def test_serve_numbers_walk_the_stack_a_layer_at_a_time():
    """``serve_numbers`` (leaves of one double layer at a time) reads what
    ``logits_at`` on the whole tree reads; a served token that is the
    reference's own best has no gap, another has one."""
    w, s = _tiny()
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, s["vocab"], 30).astype(np.int32)
    seq = np.zeros(512, np.int32)
    seq[:30] = prompt
    best = []
    for i in range(4):                        # the reference's greedy tokens
        lg = np.asarray(longcat.logits_at(
            w, fam.reference_cfg(SMALL), seq[:256], [29 + i]))[0]
        best.append(int(lg.argmax()))
        seq[30 + i] = best[-1]
    traffic = {"output": {"max": 8}}
    out = fam.serve_numbers(SMALL, 5, [(prompt, best)], traffic)
    assert out["served_gap_mean"] == 0.0 and out["_where"]["tokens"] == 4
    worse = best[:3] + [(best[3] + 1) % s["vocab"]]
    out = fam.serve_numbers(SMALL, 5, [(prompt, worse)], traffic)
    assert out["served_gap_mean"] > 0
    ctl = fam.serve_numbers(SMALL, 5, [(prompt, best)], traffic,
                            control="noscale")
    assert ctl["_where"]["program"]["mean"] == 0.0


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


def test_readers_read_the_familys_counts_and_nothing_elsewhere():
    with open(os.path.join(BENCH, "metrics", "step_mfu.longcat.json")) as f:
        needs = json.load(f)["args"]["needs"]
    c = {"prefill_tokens": 4096, "decode_tokens": 2000, "prefills": 2,
         "prefill_latent_keys": 8_000_000, "decode_latent_keys": 9_000_000,
         "rows_held": 500, "prefill_rows_held": 1024}
    assert family_mfu.read(_ctx(c), needs=needs) \
        == pytest.approx(100 * fam.step_flops(S, c) / 4.0 / 197e12)
    # the parent's record (no share counters) and another driver's: nothing
    assert family_mfu.read(_ctx({k: v for k, v in c.items()
                                 if "held" not in k}), needs=needs) is None
    assert counter_ratio.read(_ctx({}, {"rows_held": 8}), ["rows_held"],
                              ["held_expert_steps"]) is None
    assert counter_ratio.read(
        _ctx({}, {"rows_held": 32, "held_expert_steps": 64}), ["rows_held"],
        ["held_expert_steps"]) == 0.5
    for name in ("paged_latent_roofline", "flash_prefill_roofline",
                 "moe_experts_roofline"):
        with open(os.path.join(BENCH, "metrics", name + ".longcat.json")) as f:
            spec = json.load(f)
        assert spec["args"]["cost"] in fam.KERNEL_COSTS
        assert family_kernel_roofline.read(_ctx({}), **spec["args"]) is None
    got = family_kernel_roofline.read(
        _ctx({"decode_latent_keys": 400_000,
              "decode_latent_page_tokens": 410_000},
             kernels=["paged_latent_attention"]),
        ["paged_latent_attention"], "paged_latent",
        {"keys": ["decode_latent_keys"],
         "page_tokens": ["decode_latent_page_tokens"]})
    least = max(410_000 * 576 * 2 * 8 / 819e9,
                400_000 * 64 * 1088 * 2 * 8 / 197e12)
    assert got == pytest.approx(100 * least / 1e-3)


@pytest.mark.parametrize("workload", [CELL["name"], PREFILL_CELL])
def test_rehearsal_of_the_new_cells_is_correct(workload):
    args = bench_run.parse(["--workload", workload, "--seed",
                            str(2 ** 31 + 35), "--seconds", "0.5",
                            "--trace", "0", "--rehearse"])
    line = bench_run.execute(BENCHMARK, args, jax.devices())
    assert line["correct"] is True and line["failed"] == 0 \
        and line["attempted"] > 0
    assert "metrics" not in line and line["rehearsal"] is True


def test_new_cells_report_what_the_issue_lists():
    def cells_of(name, key="per_layer"):
        return next(m for m in BENCHMARK[key] if m["name"] == name)["workloads"]
    both = [CELL["name"], PREFILL_CELL]
    assert cells_of("serve_tokens_per_s", "end_to_end")[-2:] == both
    for name in ("slot_occupancy.serve", "device_idle_share.serve",
                 "ttft_p95_ms.serve", "prefill_share.serve"):
        assert cells_of(name)[-2:] == both
    for name in ("step_mfu.serve", "paged_attn_roofline.serve"):
        assert cells_of(name)[-1] == PREFILL_CELL \
            and CELL["name"] not in cells_of(name)
    for name in ("step_mfu.longcat", "paged_latent_roofline.longcat",
                 "flash_prefill_roofline.longcat",
                 "moe_experts_roofline.longcat",
                 "expert_rows_per_step.longcat", "prefix_hit_share.longcat"):
        assert cells_of(name) == [CELL["name"]]
