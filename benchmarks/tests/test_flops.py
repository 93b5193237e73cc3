"""``harness/flops.py`` against numbers worked by hand from the two
configurations' published sizes."""

import json
import os

import pytest

from harness import flops, peaks, weights

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def sizes(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return weights.sizes(json.load(f))


def test_256m_by_hand():
    s = sizes("cerebras-gpt-256m")
    assert (s["d"], s["heads"], s["d_head"], s["layers"], s["ffn"]) == (1088, 17, 64, 14, 4352)
    # per layer 4*1088^2 + 2*1088*4352 = 4,734,976 + 9,469,952; head 1088*50257
    assert flops.matmul_params(s) == 14 * 14_204_928 + 54_679_616 == 253_548_608
    # causal attention, forward: 4 * 1024.5 keys * 1088 * 14 layers
    assert flops.attention_flops(s, 1024.5) == pytest.approx(62_420_736)
    assert flops.train_flops_per_token(s, 2048) == pytest.approx(
        3 * (2 * 253_548_608 + 62_420_736))          # 1.7086e9, the issue's 1.71 GFLOP
    # parameters as built: + embeddings, positions, norms, MLP biases
    built = 253_548_608 + 50257 * 1088 + 2048 * 1088 + 14 * (4 * 1088 + 4352 + 1088) + 2 * 1088
    assert built == 310_595_712     # "310M as built"


def test_1p3b_by_hand():
    s = sizes("cerebras-gpt-1.3b")
    assert (s["d"], s["heads"], s["d_head"], s["layers"], s["ffn"]) == (2048, 16, 128, 24, 8192)
    assert flops.matmul_params(s, head=False) == 24 * (4 * 2048 ** 2 + 2 * 2048 * 8192) == 1_207_959_552
    kv_bytes_per_token = 2 * s["d"] * flops.BF16 * s["layers"]
    assert kv_bytes_per_token == 196_608
    c = {"prefill_tokens": 100, "prefills": 1, "prefill_context": 5050,
         "decode_tokens": 10, "decode_context": 1000}
    body, head, key = 2 * 1_207_959_552, 2 * 2048 * 50257, 4 * 2048 * 24
    assert flops.serve_flops(s, c) == pytest.approx(
        100 * body + head + 5050 * key + 10 * (body + head) + 1000 * key)


def test_kernel_costs_and_roofline():
    s = sizes("cerebras-gpt-256m")
    ops, nbytes = flops.flash_train_cost(s, {"steps": 1, "batch": 4, "seq_len": 2048})
    calls = 4 * 17 * 14
    assert ops == pytest.approx(calls * 7 * 2 * (2048 * 2048 / 2) * 64)
    assert nbytes == calls * 12 * 2048 * 64 * 2
    v5e = peaks.peaks_for("TPU v5 lite")
    assert flops.least_seconds(ops, nbytes, v5e)[1] == "compute"
    s = sizes("cerebras-gpt-1.3b")
    ops, nbytes = flops.paged_decode_cost(s, {"decode_context": 16 * 650,
                                              "decode_page_tokens": 16 * 656})
    assert nbytes == 16 * 656 * 196_608
    least, bound = flops.least_seconds(ops, nbytes, v5e)
    assert bound == "memory" and least == pytest.approx(nbytes / 819e9)
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9000")
