"""Driver-facing bench.py helpers: the serving footprint model, batch
sizing, spreads, and the cumulative summary line. These shape the
BENCH record the driver captures — regressions here silently corrupt
the round's evidence, so they get unit coverage even though bench.py
itself only runs on the chip."""

import json
import os
import sys

import jax
import pytest

# repo root (bench.py is not in the package) — cwd-independent
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import bench


def test_serving_footprint_monotonic_in_batch():
    f4 = bench._serving_footprint_gb(4, 16, 8192, 256, False, bench.LM_CFG)
    f8 = bench._serving_footprint_gb(8, 16, 8192, 256, False, bench.LM_CFG)
    assert f8 > f4 > 0


def test_serving_batch_reproduces_round4_edge():
    """The footprint budget was calibrated so MHA-bf16 P=8192 sizes to
    batch 4 (the measured round-4 OOM edge) while gqa4-int8 gets the
    headroom its 16x smaller cache earns."""
    mha = bench._serving_batch(16, 8192, 256, False, bench.LM_CFG)
    gqa_i8 = bench._serving_batch(4, 8192, 256, True, bench.LM_CFG)
    assert mha == 4
    assert gqa_i8 >= 8
    # max_batch caps the ladder (the CPU smoke path)
    assert bench._serving_batch(4, 8192, 256, True, bench.LM_CFG,
                                max_batch=2) == 2


def test_serving_cap_matches_generate_rounding():
    """Footprint cache sizes must mirror generate()'s block rounding, or
    the batch choice is for a different buffer than the one allocated."""
    from distkeras_tpu.ops.decode_attention import (MIN_KERNEL_LEN,
                                                    choose_block)
    total = 8192 + 257
    bl = choose_block(total)
    assert bench._serving_cap(total) == -(-total // bl) * bl
    assert bench._serving_cap(MIN_KERNEL_LEN - 1) == MIN_KERNEL_LEN - 1


def test_lm_param_count_against_known_configs():
    # 218M headline config and the 838M lm_big config (docs/PERF.md)
    assert round(bench._lm_param_count(bench.LM_CFG) / 1e6) == 218
    assert round(bench._lm_param_count(bench.LM_BIG_CFG) / 1e6) == 839
    # GQA shrinks only the kv projections
    full = bench._lm_param_count(bench.LM_CFG)
    gqa = bench._lm_param_count(bench.LM_CFG, kv_heads=4)
    assert 0 < full - gqa < full * 0.1


def test_spread_is_min_median_max():
    assert bench._spread([3.0, 1.0, 2.0]) == [1.0, 2.0, 3.0]


def test_summary_line_carries_every_headline_and_stays_compact():
    records = [
        {"metric": "resnet50_train_imgs_per_sec_per_chip", "value": 2571.0,
         "vs_baseline": 2.571, "unit": "imgs/sec", "mfu": 0.313},
        {"metric": "lm_train_tokens_per_sec_per_chip", "value": 64156.0,
         "vs_baseline": 2.14, "mfu": 0.363},
        {"metric": "lm_generate_new_tokens_per_sec_per_chip",
         "value": 6809.0, "vs_baseline": 1.0},
        {"metric": "lm_generate_p8192_decode_tokens_per_sec_per_chip",
         "value": 4449.0, "vs_baseline": 6.2,
         "headline_variant": "gqa4_p8192_int8"},
        {"metric": "moe_lm_train_tokens_per_sec_per_chip",
         "value": 47218.0, "vs_baseline": 0.73},
        {"metric": "lm_big_train_tokens_per_sec_per_chip",
         "value": 20679.0, "vs_baseline": 1.54, "mfu": 0.559},
    ]
    line = bench._summary_line(records, "TPU v5 lite")
    parsed = json.loads(line)
    assert len(parsed["headlines"]) == 6
    assert parsed["headlines"][
        "lm_generate_p8192_decode_tokens_per_sec_per_chip"][
        "headline_variant"] == "gqa4_p8192_int8"
    # the whole point: the line must fit the driver's 2,000-char tail
    # capture window with room for the preceding family line
    assert len(line) < 1500, len(line)
    # first record doubles as the line's own metric fields
    assert parsed["value"] == 2571.0 and parsed["unit"] == "imgs/sec"


# -- regression tripwire (overlap PR) ----------------------------------------

def test_prev_headlines_reads_newest_round():
    import glob
    import re
    root = os.path.dirname(bench.__file__)
    rounds = [int(re.search(r"BENCH_r(\d+)\.json$", p).group(1))
              for p in glob.glob(os.path.join(root, "BENCH_r*.json"))]
    heads, src, kind = bench._prev_headlines(root)
    # whatever rounds the repo carries, the newest must win (r05 as of
    # this test's writing; hardcoding it would break on every new round)
    assert src == f"BENCH_r{max(rounds):02d}.json"
    assert isinstance(heads, dict) and heads
    assert isinstance(kind, str) and kind  # gate for cross-hw comparisons


def test_regression_check_flags_value_drop():
    prev = {"m": {"value": 1000.0, "vs_baseline": 2.0}}
    rec = {"metric": "m", "value": 850.0, "vs_baseline": 2.0}
    out = bench._regression_check(rec, prev, "BENCH_r05.json")
    assert out["value_vs_prev"] == 0.85
    assert any("value dropped" in f for f in out["flags"])


def test_regression_check_passes_within_tolerance():
    prev = {"m": {"value": 1000.0, "vs_baseline": 2.0}}
    rec = {"metric": "m", "value": 950.0, "vs_baseline": 1.95}
    out = bench._regression_check(rec, prev, "BENCH_r05.json")
    assert out is not None and "flags" not in out
    assert out["value_vs_prev"] == 0.95


def test_regression_check_flags_the_known_moe_below_anchor():
    """The standing moe_lm_train 0.735x regression (BENCH_r05's
    numbers, pinned here as a synthetic prev record so the test
    outlives the repo's BENCH files): even when the value matches the
    previous round exactly, the below-anchor flag keeps it visible
    instead of letting two matching rounds silently normalize it."""
    heads = {"moe_lm_train_tokens_per_sec_per_chip":
             {"value": 47156.5, "vs_baseline": 0.735}}
    rec = {"metric": "moe_lm_train_tokens_per_sec_per_chip",
           "value": 47156.5, "vs_baseline": 0.735}
    out = bench._regression_check(rec, heads, "BENCH_r05.json")
    assert any("below_anchor" in f for f in out["flags"])
    assert "value dropped" not in " ".join(out["flags"])  # value held


def test_regression_check_none_without_history_or_flags():
    rec = {"metric": "m", "value": 100.0, "vs_baseline": 1.2}
    assert bench._regression_check(rec, None, None) is None


def test_summary_line_surfaces_regression_flags():
    records = [
        {"metric": "a", "value": 1.0, "vs_baseline": 1.0,
         "regression": {"flags": ["value dropped to 0.850x of r05"]}},
        {"metric": "b", "value": 2.0, "vs_baseline": 1.5,
         "regression": None},
    ]
    parsed = json.loads(bench._summary_line(records, "cpu"))
    assert parsed["regressions"] == {
        "a": ["value dropped to 0.850x of r05"]}


def test_regression_check_skips_cross_hardware_comparison():
    """A CPU smoke run vs a TPU-captured record must not flag a bogus
    100x 'drop' — anchors carry device_kind, and a prior-round record
    from different hardware reports as a STALE ANCHOR instead of
    flagging every run (the in-run below-anchor check still applies)."""
    prev = {"m": {"value": 64000.0, "vs_baseline": 2.0}}
    rec = {"metric": "m", "value": 600.0, "vs_baseline": 2.0,
           "device_kind": "cpu"}
    out = bench._regression_check(rec, prev, "BENCH_r05.json",
                                  prev_kind="TPU v5 lite")
    assert "flags" not in out and "value_vs_prev" not in out
    assert "device_kind" in out["stale_anchor"]
    assert "stale" in out["stale_anchor"]
    # same hardware: the comparison runs and flags
    out = bench._regression_check(dict(rec, device_kind="TPU v5 lite"),
                                  prev, "BENCH_r05.json",
                                  prev_kind="TPU v5 lite")
    assert any("dropped" in f for f in out["flags"])


def test_summary_line_surfaces_stale_anchors():
    """The cumulative summary line names the families whose prior-round
    anchor came from different hardware (one shared note, not flags)."""
    records = [
        {"metric": "a", "value": 1.0, "vs_baseline": 1.1,
         "regression": {"stale_anchor":
                        "BENCH_r05.json was captured on device_kind "
                        "'TPU v5 lite', this run is 'cpu': cross-device "
                        "anchor is stale, vs-prev comparison skipped"}},
        {"metric": "b", "value": 2.0, "vs_baseline": 1.5,
         "regression": None},
    ]
    parsed = json.loads(bench._summary_line(records, "cpu"))
    assert parsed["stale_anchors"] == ["a"]
    assert "stale" in parsed["stale_anchor_note"]
    assert "regressions" not in parsed


def test_regression_check_inverts_for_lower_is_better_metric():
    """overlap_train_ckpt_overhead_x is lower-is-better: an improvement
    (value drop) must NOT flag, a >11% rise must."""
    metric = "overlap_train_ckpt_overhead_x"
    assert metric in bench.LOWER_IS_BETTER
    prev = {metric: {"value": 1.2, "vs_baseline": 0.833}}
    improved = {"metric": metric, "value": 1.0, "vs_baseline": 1.0}
    out = bench._regression_check(improved, prev, "BENCH_r05.json")
    assert "flags" not in out, out
    worse = {"metric": metric, "value": 1.4, "vs_baseline": 0.714}
    out = bench._regression_check(worse, prev, "BENCH_r05.json")
    assert any("rose" in f for f in out["flags"])
    assert any("below_anchor" in f for f in out["flags"])


def test_regression_check_flags_pre_serving_era_anchor():
    """A prior record whose headline roster is entirely pre-serving
    families (the real BENCH_r05 shape) is a stale anchor: the moe
    0.735x comparison against it is archaeology, not a regression.
    The in-run below-anchor tripwire still applies."""
    metric = "moe_lm_train_tokens_per_sec_per_chip"
    prev = {metric: {"value": 47156.5, "vs_baseline": 0.735},
            "lm_train_tokens_per_sec_per_chip":
                {"value": 100.0, "vs_baseline": 1.0}}
    assert set(prev) <= bench.PRE_SERVING_FAMILIES
    rec = {"metric": metric, "value": 20000.0, "vs_baseline": 0.735}
    out = bench._regression_check(rec, prev, "BENCH_r05.json")
    assert "predates the serving stack" in out["stale_anchor"]
    assert "value_vs_prev" not in out          # comparison skipped
    assert any("below_anchor" in f for f in out["flags"])  # in-run


def test_regression_check_runs_against_serving_era_anchor():
    """One serving-era family in the prior roster means the record
    postdates the stack: comparisons run (and flag) normally."""
    prev = {"lm_train_tokens_per_sec_per_chip":
                {"value": 1000.0, "vs_baseline": 2.0},
            "serving_steady_decode_tokens_per_sec_per_chip":
                {"value": 50.0, "vs_baseline": 0.95}}
    rec = {"metric": "lm_train_tokens_per_sec_per_chip",
           "value": 850.0, "vs_baseline": 2.0}
    out = bench._regression_check(rec, prev, "BENCH_r06.json")
    assert "stale_anchor" not in out
    assert out["value_vs_prev"] == 0.85
    assert any("dropped" in f for f in out["flags"])


def test_footprint_cache_dtype_ladder():
    """int4 pages are half of int8's payload; both quantized rungs pay
    the f32 scale planes; the legacy bool knob still means int8."""
    args = (8, 16, 8192, 256)
    bf16 = bench._serving_footprint_gb(*args, "auto", bench.LM_CFG)
    i8 = bench._serving_footprint_gb(*args, "int8", bench.LM_CFG)
    i4 = bench._serving_footprint_gb(*args, "int4", bench.LM_CFG)
    assert bf16 > i8 > i4
    assert i8 == bench._serving_footprint_gb(*args, True, bench.LM_CFG)
    assert bf16 == bench._serving_footprint_gb(*args, False,
                                               bench.LM_CFG)
    # int4 sizes at least the int8 batch at the same config
    assert bench._serving_batch(4, 8192, 256, "int4", bench.LM_CFG) >= \
        bench._serving_batch(4, 8192, 256, "int8", bench.LM_CFG)


def test_quant_ladder_covers_every_rung():
    names = [n for n, _ in bench.QUANT_LADDER]
    assert names[0] == "bf16" and bench.QUANT_LADDER[0][1] == {}
    assert {"w_int8", "w_int4", "kv_int8", "kv_int4",
            "w4kv4"} <= set(names)
    corner = dict(bench.QUANT_LADDER)["w4kv4"]
    assert corner == {"weights_dtype": "int4", "cache_dtype": "int4"}


def test_quant_hbm_math_rider():
    """The untimed byte rider: int4 weights ~halve int8's bytes; the
    KV bytes/token ladder ordering holds with scale planes counted."""
    from distkeras_tpu.models import Model, zoo

    cfg = dict(vocab=64, d_model=32, num_heads=4, num_layers=2,
               mlp_ratio=2, seq=16)
    model = Model.build(zoo.transformer_lm(
        cfg["vocab"], d_model=cfg["d_model"],
        num_heads=cfg["num_heads"], num_layers=cfg["num_layers"],
        mlp_ratio=cfg["mlp_ratio"], use_rope=True), (16,), seed=0)
    hm = bench._quant_hbm_math(model, cfg)
    wb, kv = hm["weight_bytes"], hm["kv_bytes_per_token"]
    assert wb["int8"] < wb["bf16"] * 0.75
    assert wb["int4"] < wb["int8"]
    assert kv["bf16"] > kv["int8"] > kv["int4"]


@pytest.mark.parametrize("exc,oom", [
    (jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 1GiB"), True),
    # a kernel Mosaic refuses mentions memory too: NOT an OOM
    (jax.errors.JaxRuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel: exceeds VMEM "
        "memory"), False),
    (RuntimeError("RESOURCE_EXHAUSTED: out of memory"), False),
    (ValueError("memory"), False),
])
def test_is_oom_is_typed_and_resource_exhausted_only(exc, oom):
    assert bench._is_oom(exc) is oom


def test_batch_ladder_steps_down_on_oom_only():
    """OOM -> the next smaller batch; a compile refusal propagates at
    once instead of becoming a smaller headline batch."""
    oom = jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: hbm")
    tried = []

    def fn(b):
        tried.append(b)
        if b > 8:
            raise oom
        return b * 10

    assert bench._with_fallbacks(fn, [32, 16, 8, 4], "x") == (80, 8)
    assert tried == [32, 16, 8]

    def refuses(b):
        tried.append(b)
        raise jax.errors.JaxRuntimeError("Mosaic failed to compile")

    tried.clear()
    with pytest.raises(jax.errors.JaxRuntimeError, match="Mosaic"):
        bench._with_fallbacks(refuses, [32, 16], "x")
    assert tried == [32]                   # no retry, no smaller batch
    with pytest.raises(RuntimeError, match="all batch sizes failed"):
        bench._with_fallbacks(lambda b: (_ for _ in ()).throw(oom),
                              [2, 1], "x")


def test_bench_starts_no_child_that_needs_the_chip():
    """One process per chip: the only ``subprocess`` use left in
    bench.py is the CPU-forced expert-parallel child."""
    import ast
    src = open(bench.__file__).read()
    users = {fn.name for fn in ast.walk(ast.parse(src))
             if isinstance(fn, ast.FunctionDef)
             and any(isinstance(n, ast.Name) and n.id == "subprocess"
                     for n in ast.walk(fn))}
    assert users == {"_serving_moe_ep_subprocess"}
    assert 'env["JAX_PLATFORMS"] = "cpu"' in src


@pytest.mark.parametrize("env_dir", [None, "/some/dir"])
def test_compile_cache_dir_env_wins_else_repo_local(monkeypatch, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` set: nothing is set in code.
    Unset: ``<repo>/.jax_cache`` — fixed, inside the checkout."""
    from distkeras_tpu import compat
    updates = []
    monkeypatch.setattr(compat.jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(os.path.dirname(os.path.abspath(
            bench.__file__)), ".jax_cache")
        assert compat.enable_compile_cache() == want
        assert updates == [("jax_compilation_cache_dir", want)]
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert compat.enable_compile_cache() == env_dir
        assert updates == []


def test_bench_serving_probe_survives_a_second_pass(monkeypatch):
    """The raw-loop probe drives the donating decode program by hand on
    an engine it reuses across passes: it has to rebind the probe's
    pool as the engine does, or the second pass prefills into deleted
    buffers (one pass, as the CPU smoke runs, cannot see it)."""
    monkeypatch.setattr(bench, "LM_CFG", dict(
        d_model=32, num_heads=2, num_layers=1, mlp_ratio=2, vocab=64,
        seq=32))
    rates, raws, summaries, _slo, _trace = bench.bench_serving(
        num_slots=2, prompt_len=4, new_tokens=12, n_requests=3,
        n_passes=2)
    assert len(rates) == len(raws) == 2 and min(raws) > 0
    assert all(s["requests_cancelled"] == 0 for s in summaries)
