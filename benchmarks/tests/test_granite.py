"""The Granite-4.0-H family's own pieces: the configuration file against the
catalog row it was taken from, sizes and operation counts against hand
counts, program and reference built from one place, the reference's
controls reading over the program, the family driver's counts, the readers
on the family's record, and the rehearsal of the cell this configuration's
PR brought (``run.execute`` at the rehearsal size on the CPU), with each
control reading not correct where the tiny model can show it."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as bench_run
from conftest import BENCH, ROOT

from harness import common, granite_family as fam
from readers import family_kernel_roofline, family_mfu
from reference import granite

with open(os.path.join(BENCH, "configs", "granite-4.0-h-small.json")) as f:
    CFG = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
S = fam.sizes(CFG)
SMALL = common.config_for(CFG, rehearse=True)
# the catalog of published architectures (JSON lines), where one is at hand
CATALOG = os.environ.get("MODEL_CATALOG", "")
CELL = {"name": "serve-granite4h-l10-chat-closed64",
        "config": "granite-4.0-h-small", "chips": 1}


def test_file_holds_every_number_of_the_catalog_row():
    if not os.path.isfile(CATALOG):
        pytest.skip("MODEL_CATALOG names no catalog file")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-small")
    assert CFG["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if CFG.get(k) != v]
    assert sorted(differs) == sorted(CFG["reduced"]) \
        == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert {k: CFG["published"][k] for k in differs} \
        == {k: row["config"][k] for k in differs}


def test_sizes_are_the_published_widths_and_the_chips_share():
    assert (S["d"], S["heads"], S["kv_heads"], S["head_dim"], S["m_heads"],
            S["m_head_dim"], S["d_state"], S["d_conv"], S["chunk"],
            S["inner"], S["conv_dim"], S["ffn"], S["shared_ffn"],
            S["experts"], S["top_k"]) \
        == (4096, 32, 8, 128, 128, 64, 128, 4, 256, 8192, 8448, 768, 1536,
            72, 10)
    assert (S["layers"], S["held"], S["vocab"]) == (10, (0, 36), 50176)
    assert S["kinds"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    # a mamba layer outside the experts 102.3M, an attention layer 41.9M,
    # the router and shared expert 19.2M, 36 held experts 339.7M
    mamba = sum(int(np.prod(v)) for v in fam._mamba_shapes(S).values())
    attn = sum(int(np.prod(v)) for v in fam._attn_shapes(S).values())
    assert round(mamba / 1e6, 1) == 102.3 and round(attn / 1e6, 1) == 41.9
    assert round(fam._common_params(S) / 1e6, 1) == 19.2
    assert 36 * 3 * 4096 * 768 == 339_738_624
    # 4.76B as built = 9.51 GB of bfloat16 (the head is the embedding)
    assert round(fam.parameters(S) * 2 / 1e9, 2) == 9.51
    # the state group: 64 slots x 9 layers x 4 MB of float32 = 2.42 GB
    assert 64 * 9 * 128 * 64 * 128 * 4 == 2_415_919_104


def test_program_and_reference_read_the_assumed_values_from_one_place():
    kw = fam.program_kwargs(CFG)
    assert (kw["tie_embeddings"], kw["embedding_multiplier"],
            kw["residual_multiplier"], kw["logits_scaling"],
            kw["attn_kinds"]["attention"]) \
        == (True, 12.0, 0.22, 16.0,
            {"use_rope": False, "attn_scale": 0.0078125})
    r = fam.reference_cfg(CFG)
    assert (r["embed_mult"], r["residual_mult"], r["logits_scaling"],
            r["attn_scale"], r["held"], r["top_k"], r["eps"], r["gate"],
            r["resets"]) == (12.0, 0.22, 16.0, 0.0078125, [0, 36], 10, 1e-5,
                             True, [])
    assert fam.reference_cfg(CFG, resets=(1024, 300))["resets"] == [1024, 300]
    broken = json.loads(json.dumps(CFG))
    broken["assumed_values"]["input_linear_order"] = "activation_second"
    with pytest.raises(NotImplementedError):
        fam.program_kwargs(broken)
    module = fam.build_module(SMALL)
    assert len(module.layers) == 2 + 4 + 2


def test_step_flops_and_kernel_costs_against_hand_counts():
    zero = dict.fromkeys(("decode_tokens", "prefill_tokens", "prefills",
                          "rows_held", "prefill_rows_held",
                          "decode_full_keys", "prefill_full_keys",
                          "ssm_state_slot_steps", "ssm_prefill_positions"),
                         0)
    mamba = 4096 * (8192 + 8448 + 128) + 8192 * 4096
    attn = 4096 * 48 * 128 + 4096 * 4096
    common_ = 4096 * 72 + 3 * 4096 * 1536
    layer = 2 * (9 * mamba + attn + 10 * common_)
    head = 2 * 4096 * 50176
    assert fam.step_flops(S, {**zero, "decode_tokens": 1}) == layer + head
    assert fam.step_flops(S, {**zero, "prefill_tokens": 1}) == layer
    assert fam.step_flops(S, {**zero, "prefills": 1}) == head
    assert fam.step_flops(S, {**zero, "rows_held": 1}) == 6 * 4096 * 768
    assert fam.step_flops(S, {**zero, "decode_full_keys": 1}) \
        == 4 * 32 * 128
    assert fam.step_flops(S, {**zero, "ssm_state_slot_steps": 1}) \
        == 4 * 128 * 64 * 128
    # a scanned position: per head the mix (256 x 64) and the state's two
    # products (2 x 128 x 64), and C.B over its chunk (256 x 128)
    per = 2 * (128 * (256 * 64 + 2 * 128 * 64) + 256 * 128)
    assert fam.step_flops(S, {**zero, "ssm_prefill_positions": 1}) == per
    ops, nbytes = fam.ssm_step_cost(S, {"slot_steps": 3})
    assert (ops, nbytes) == (3 * 4 * 128 * 64 * 128,
                             3 * 2 * 4 * 128 * 64 * 128)
    ops, nbytes = fam.ssd_scan_cost(S, {"positions": 10})
    assert ops == 10 * per
    assert nbytes == 10 * 4 * (2 * 8192 + 2 * 128 + 2 * 128)
    ops, nbytes = fam.experts_cost(S, {"rows_held": 5, "experts_touched": 3})
    assert (ops, nbytes) == (5 * 6 * 4096 * 768, 3 * 3 * 4096 * 768 * 2)


def test_loop_counts():
    traffic = {"engine": {"prefill_chunk": 1024}}
    got = fam.prefill_counts(S, traffic, 2048, 0)
    assert got == {"prefill_full_keys": sum(range(1, 2049)),
                   "prompt_tokens": 2048}
    assert fam.decode_counts(S, 300, 128) \
        == {"decode_full_keys": 301, "decode_full_page_tokens": 384}


def _tiny(cfg=SMALL):
    leaves = fam.make_leaves(cfg, 5, jnp.float32)
    s = fam.sizes(cfg)
    return fam.reference_tree(leaves, s), s


def test_leaves_are_the_same_made_whole_or_a_layer_at_a_time():
    whole = fam.make_leaves(SMALL, 5, jnp.float32)
    part = fam.make_leaves(SMALL, 5, jnp.float32, only=("L2", "embed"))
    assert set(part) == {k for k in whole if k.startswith("L2.")} | {"embed"}
    assert all(bool(jnp.array_equal(part[k], whole[k])) for k in part)
    assert "L2.wq" in whole and "L0.in_proj" in whole
    assert whole["L0.dt_bias"].dtype == jnp.float32
    step = jax.nn.softplus(whole["L0.dt_bias"])
    assert float(step.min()) >= 1e-3 - 1e-6 and float(step.max()) <= 0.1 + 1e-6
    assert float(jnp.std(whole["embed"])) < 3e-3


def test_planted_faults_read_over_the_program():
    """The controls change what the reference computes at this size: the
    state restarted, the gate left out, the state held in bfloat16, int8
    products (which move the logits more than bfloat16 products do)."""
    w, s = _tiny()
    rng = np.random.default_rng(2)
    seq = np.zeros(256, np.int32)
    seq[:40] = rng.integers(0, s["vocab"], 40)
    pos = np.arange(8, 40)
    rcfg = fam.reference_cfg(SMALL)
    sound = np.asarray(granite.logits_at(w, rcfg, seq, pos))
    scale = float(np.abs(sound).max())
    for planted in (fam.reference_cfg(SMALL, resets=(20,)),
                    fam.reference_cfg(SMALL, gate=False)):
        low = np.asarray(granite.logits_at(w, planted, seq, pos))
        assert float(np.abs(low - sound).max()) > 0.05 * scale
    # a restart at 20 leaves the positions before it as they were
    low = np.asarray(granite.logits_at(w, fam.reference_cfg(
        SMALL, resets=(20,)), seq, np.arange(8, 20)))
    assert np.array_equal(low, sound[:12])
    # a bfloat16 state moves the logits, and leaves the first position as it was
    low = np.asarray(granite.logits_at(w, fam.reference_cfg(
        SMALL, state_dtype="bfloat16"), seq, pos))
    assert float(np.abs(low - sound).max()) > 0
    assert fam.reference_cfg(SMALL)["state_dtype"] == "float32"
    int8 = np.asarray(granite.logits_at(w, rcfg, seq, pos, precision="int8"))
    bf16 = np.asarray(granite.logits_at(w, rcfg, seq, pos,
                                        precision="bfloat16"))
    assert float(np.abs(int8 - sound).max()) \
        > float(np.abs(bf16 - sound).max()) > 0


def test_serve_numbers_walk_the_stack_a_layer_at_a_time():
    """``serve_numbers`` (leaves of one layer at a time) reads what
    ``logits_at`` on the whole tree reads; a served token that is the
    reference's own best has no gap, another has one."""
    w, s = _tiny()
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, s["vocab"], 30).astype(np.int32)
    seq = np.zeros(256, np.int32)
    seq[:30] = prompt
    best = []
    for i in range(4):                        # the reference's greedy tokens
        lg = np.asarray(granite.logits_at(
            w, fam.reference_cfg(SMALL), seq, [29 + i]))[0]
        best.append(int(lg.argmax()))
        seq[30 + i] = best[-1]
    traffic = {"output": {"max": 8}, "engine": {"prefill_chunk": 16}}
    out = fam.serve_numbers(SMALL, 5, [(prompt, best)], traffic)
    assert out["served_gap_mean"] == 0.0 and out["_where"]["tokens"] == 4
    worse = best[:3] + [(best[3] + 1) % s["vocab"]]
    out = fam.serve_numbers(SMALL, 5, [(prompt, worse)], traffic)
    assert out["served_gap_mean"] > 0
    ctl = fam.serve_numbers(SMALL, 5, [(prompt, best)], traffic,
                            control="statereset")
    assert ctl["_where"]["program"]["mean"] == 0.0


def _ctx(trace_counters, kernels=()):
    trace = types.SimpleNamespace(
        device_ops={"/device:TPU:0": [(0, 1_000_000, f"%{k}.1 = f32[] custom-call()")
                                      for k in kernels]},
        device_programs={}, host_spans=[], chips=1)
    rec = types.SimpleNamespace(trace_counters=trace_counters, counters={},
                                trace_window_s=4.0)
    return types.SimpleNamespace(
        record=rec, trace=trace, cell=CELL, chips=1,
        peaks={"flops_bf16": 197e12, "bytes_per_s": 819e9})


def test_readers_read_the_familys_counts_and_nothing_elsewhere():
    with open(os.path.join(BENCH, "metrics", "step_mfu.granite.json")) as f:
        needs = json.load(f)["args"]["needs"]
    c = {"prefill_tokens": 4096, "decode_tokens": 2000, "prefills": 6,
         "prefill_full_keys": 3_000_000, "decode_full_keys": 600_000,
         "rows_held": 9000, "prefill_rows_held": 20_000,
         "ssm_state_slot_steps": 18_000, "ssm_prefill_positions": 36_864}
    assert family_mfu.read(_ctx(c), needs=needs) \
        == pytest.approx(100 * fam.step_flops(S, c) / 4.0 / 197e12)
    # the parent's record (no state counters): nothing
    assert family_mfu.read(_ctx({k: v for k, v in c.items()
                                 if "ssm" not in k}), needs=needs) is None
    for name, kernel in (("ssm_decode_roofline", "ssm_decode_step"),
                         ("ssd_prefill_roofline", "ssd_chunk_scan"),
                         ("moe_experts_roofline", "moe_grouped_experts"),
                         ("paged_full_roofline", "paged_decode_attention")):
        with open(os.path.join(BENCH, "metrics", name + ".granite.json")) as f:
            spec = json.load(f)
        assert spec["args"]["cost"] in fam.KERNEL_COSTS
        assert spec["args"]["kernels"] == [kernel]
        assert family_kernel_roofline.read(_ctx({}), **spec["args"]) is None
    got = family_kernel_roofline.read(
        _ctx({"ssm_state_slot_steps": 576}, kernels=["ssm_decode_step"]),
        ["ssm_decode_step"], "ssm_step",
        {"slot_steps": ["ssm_state_slot_steps"]})
    least = 576 * 2 * 4 * 128 * 64 * 128 / 819e9     # bound by the bytes
    assert got == pytest.approx(100 * least / 1e-3)
    # the attention layer's decode: 32 query heads over 8 KV heads of 128
    got = family_kernel_roofline.read(
        _ctx({"decode_full_keys": 600_000, "decode_full_page_tokens": 640_000},
             kernels=["paged_decode_attention"]),
        ["paged_decode_attention"], "paged_full",
        {"keys": ["decode_full_keys"], "page_tokens": ["decode_full_page_tokens"]})
    least = max(4 * 32 * 128 * 600_000 / 197e12,
                2 * 8 * 128 * 2 * 640_000 / 819e9)
    assert got == pytest.approx(100 * least / 1e-3)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_new_cell_is_correct(trace):
    """Untraced and traced: a traced run also reads the configuration's
    sizes (``weights.sizes``) and the device trace."""
    args = bench_run.parse(["--workload", CELL["name"], "--seed",
                            str(2 ** 31 + 44), "--seconds", "0.5",
                            "--trace", str(trace), "--rehearse"])
    line = bench_run.execute(BENCHMARK, args, jax.devices())
    assert line["correct"] is True and line["failed"] == 0 \
        and line["attempted"] > 0
    assert "metrics" not in line and line["rehearsal"] is True


@pytest.mark.parametrize("control", ["ref-statereset", "ref-nogate"])
def test_rehearsal_of_a_control_is_not_correct(control):
    """The restarted state and the missing gate read not correct through
    ``run.py`` at the rehearsal size; int8 products move no argmax there
    (``test_planted_faults_read_over_the_program`` holds that they move
    the logits)."""
    args = bench_run.parse(["--workload", CELL["name"], "--seed",
                            str(2 ** 31 + 44), "--seconds", "0.5",
                            "--trace", "0", "--rehearse", "--control",
                            control])
    line = bench_run.execute(BENCHMARK, args, jax.devices())
    assert line["correct"] is False and line["failed"] == 0


def test_new_cell_reports_its_metrics():
    def cells_of(name, key="per_layer"):
        return next(m for m in BENCHMARK[key] if m["name"] == name)["workloads"]
    cell = CELL["name"]
    assert cells_of("serve_tokens_per_s", "end_to_end")[-1] == cell
    for name in ("slot_occupancy.serve", "device_idle_share.serve",
                 "ttft_p95_ms.serve", "prefill_share.serve",
                 "programs.setup", "engine_init_s.serve"):
        assert cells_of(name)[-1] == cell
    for name in ("step_mfu.serve", "paged_attn_roofline.serve"):
        assert cell not in cells_of(name)
    for name in ("ssm_decode_roofline.granite", "ssd_prefill_roofline.granite",
                 "moe_experts_roofline.granite", "step_mfu.granite",
                 "paged_full_roofline.granite"):
        assert cells_of(name) == [cell]
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == cell)
    assert "8.9" in entry["why"] and "17.8" in entry["why"] \
        and "attention" in entry["why"]
