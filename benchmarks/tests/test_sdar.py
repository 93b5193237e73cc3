"""The SDAR family's own pieces: sizes and operation counts from the
configuration file, the states the check rebuilds from ``fixed_pass``, the
reference's generation against its own full forward, and the new readers on
records that lack what they read (the parent commit's)."""

import json
import os
import types

import numpy as np
import pytest

from conftest import BENCH

from harness import sdar_check, sdar_family
from readers import counter_ratio, op_share, sdar_kernel_roofline, sdar_mfu
from reference import sdar

with open(os.path.join(BENCH, "configs", "sdar-30b-a3b-chat.json")) as f:
    CFG = json.load(f)
S = sdar_family.sizes(CFG)


def test_sizes_are_the_published_widths_at_six_layers():
    assert (S["d"], S["heads"], S["kv_heads"], S["d_head"], S["experts"],
            S["top_k"], S["ffn"], S["vocab"], S["layers"]) \
        == (2048, 32, 4, 128, 128, 8, 768, 151936, 6)
    per_layer = sum(int(np.prod(shape))
                    for shape in sdar_family._layer_shapes(S).values())
    assert per_layer == 18_874_368 + 262_144 + 603_979_776
    served = 2 * (S["layers"] * per_layer + 2 * S["vocab"] * S["d"])
    assert round(served / 1e9, 2) == 8.72          # GB of bf16 matrices
    kv_token = S["layers"] * 2 * S["kv_heads"] * S["d_head"] * 2
    assert kv_token == 12_288


def test_step_flops_count_routed_experts_only():
    one = {"prefill_tokens": 0, "pass_rows": 1, "denoise_rows": 0,
           "prefill_context": 0, "pass_context": 0}
    body = sdar_family.step_flops(S, one)
    routed = S["layers"] * S["top_k"] * 6 * S["d"] * S["ffn"]
    assert routed < body < 1.3 * routed + 2 * S["layers"] * 2 * 18_874_368
    head = sdar_family.step_flops(S, {**one, "denoise_rows": 1}) - body
    assert head == 2.0 * S["d"] * S["vocab"]
    ops, nbytes = sdar_family.experts_cost(
        S, {"routed_rows": 1024, "experts_touched": 128})
    assert ops == 1024 * 6.0 * 2048 * 768 and nbytes == 128 * 3 * 2048 * 768 * 2


def test_block_states_rebuild_every_denoising_state():
    prompt, served = [5, 6, 7, 8, 9, 10], [11, 12, 13, 14, 15, 16, 17]
    fixed = [1, 0, 2, 0, 3, 1, 0]          # 2 + 4 whole, 1 of a cut block
    blocks = sdar_check.block_states(prompt, served, fixed, 4, 4)
    assert [b[0] for b in blocks] == [4, 8]
    start, final, states = blocks[0]
    assert final.tolist() == [9, 10, 11, 12]
    assert [(m.tolist(), f.tolist()) for m, f in states] == [
        ([False, False, True, True], [3]), ([False, False, True, False], [2])]
    _, final, states = blocks[1]
    assert final.tolist() == [13, 14, 15, 16]
    assert [f.tolist() for _, f in states] == [[1], [3], [0], [2]]
    assert states[2][0].tolist() == [True, False, True, False]


def test_planted_faults_read_over_the_program(monkeypatch):
    """A sound trajectory reads 0 and 0; the planted selection rule (the
    least confident position fixed) moves ``position_gap_mean`` alone, and
    a rule that ignores confidence lies between; all from the same states."""
    w = _tiny_weights()
    cfg = {"heads": 4, "kv_heads": 2, "top_k": 2, "block_len": 4,
           "rope_theta": 1e6}
    prompt = [3, 4, 5, 6, 7, 8]
    served, fixed, _ = sdar.generate(w, cfg, prompt, 18, 49)
    monkeypatch.setattr(sdar_family, "sizes", lambda _cfg: {"block_len": 4})
    monkeypatch.setattr(sdar_family, "reference_cfg", lambda _s: cfg)
    monkeypatch.setattr(sdar_family, "make_leaves", lambda _cfg, _seed: None)
    monkeypatch.setattr(sdar_family, "reference_tree", lambda _l, _s: w)
    read = lambda control: sdar_check.serve_numbers(
        None, 0, [(prompt, served, fixed)], {"mask_token": 49}, 8, 40,
        control=control)
    sound, turned = read(None), read("position")
    assert sound["served_gap_mean"] == 0 and sound["position_gap_mean"] == 0
    assert sound["_where"]["tokens"] == 18   # the opening block's 2, 4 x 4
    assert turned["served_gap_mean"] == 0
    assert turned["position_gap_mean"] \
        == sound["_where"]["least_confident_position_gap"] \
        >= sound["_where"]["first_masked_position_gap"] > 0
    assert turned["_where"]["program"] == {"served": 0.0, "position": 0.0}
    low = read("int8")
    assert low["served_gap_mean"] > 0 or low["position_gap_mean"] > 0


def _tiny_weights(seed=0):
    rng = np.random.default_rng(seed)
    n = lambda *shape: (0.3 * rng.standard_normal(shape)).astype(np.float32)
    layer = lambda: {"n1": np.ones(16, np.float32), "n2": np.ones(16, np.float32),
                     "qn": np.ones(8, np.float32), "kn": np.ones(8, np.float32),
                     "wq": n(16, 4, 8), "wk": n(16, 2, 8), "wv": n(16, 2, 8),
                     "wo": n(4, 8, 16), "router": n(16, 6), "wg": n(6, 16, 12),
                     "wu": n(6, 16, 12), "wd": n(6, 12, 16)}
    return {"embed": n(50, 16), "layers": [layer(), layer()],
            "final_norm": np.ones(16, np.float32), "head": 3 * n(16, 50)}


def test_reference_generate_is_its_own_forward_and_pads_freely():
    w = _tiny_weights()
    cfg = {"heads": 4, "kv_heads": 2, "top_k": 2, "block_len": 4,
           "rope_theta": 1e6}
    prompt = [3, 4, 5, 6, 7, 8]
    tokens, fixed, trajectory = sdar.generate(w, cfg, prompt, 9, 49)
    again, fixed2, _ = sdar.generate(w, cfg, prompt, 9, 49, pad_to=32)
    assert tokens == again and fixed == fixed2 and len(tokens) == 9
    assert sorted(fixed[:2]) == [0, 1] and sorted(fixed[2:6]) == [0, 1, 2, 3]
    # the last denoising state of the first whole block, by a full forward
    start, pos, toks, lg = trajectory[5]
    seq = np.array(prompt + tokens[:6] + [49] * 4)
    seq[[p for p in range(8, 12) if p != pos[0]]] = [
        t for p, t in zip(range(8, 12), tokens[2:6]) if p != pos[0]]
    seq[pos[0]] = 49
    want = np.asarray(sdar.logits_at(w, cfg, seq[:12], np.arange(8, 12)))
    np.testing.assert_allclose(lg, want, rtol=1e-5, atol=1e-5)
    # the mask is block-causal: a later block changes nothing before it
    a = np.asarray(sdar.logits_at(w, cfg, seq[:12], np.arange(8)))
    b = np.asarray(sdar.logits_at(w, cfg, seq[:8], np.arange(8)))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("read", [
    lambda ctx: counter_ratio.read(ctx, ["slot_passes_denoise"], ["tokens_committed"]),
    lambda ctx: op_share.read(ctx, ["all-reduce"]),
    lambda ctx: sdar_mfu.read(ctx),
    lambda ctx: sdar_kernel_roofline.read(
        ctx, ["moe_grouped_experts"], "experts",
        {"routed_rows": ["rows_routed"], "experts_touched": ["experts_touched"]}),
])
def test_new_readers_find_nothing_on_a_record_without_their_counters(read):
    from harness import trace_reduce
    record = types.SimpleNamespace(counters={}, trace_counters={},
                                   trace_window_s=4.0)
    ctx = types.SimpleNamespace(record=record, trace=trace_reduce.Trace(),
                                cell={"config": "sdar-30b-a3b-chat"}, chips=1,
                                peaks={"flops_bf16": 197e12, "bytes_per_s": 819e9})
    assert read(ctx) is None


def test_counter_ratio_is_a_plain_ratio():
    record = types.SimpleNamespace(counters={"a": 5, "b": 1, "c": 4})
    ctx = types.SimpleNamespace(record=record)
    assert counter_ratio.read(ctx, ["a", "b"], ["c"]) == 1.5
    assert counter_ratio.read(ctx, ["a"], ["missing"]) is None
