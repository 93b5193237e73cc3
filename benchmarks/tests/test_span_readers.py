"""The readers of the program's own spans and program names
(``readers/span_idle.py``, ``span_time.py``, ``program_share.py``) on a trace
made by hand, where every number can be worked out on paper; on the trace
recorded on the chip, which holds no such span; and on a profiler trace of a
tiny engine on the CPU, which shows where the spans land."""

import os
import types

import pytest

from harness import trace_reduce as tr
from readers import idle_share, program_share, span_idle, span_time

MS = 10 ** 6          # nanoseconds
RECORDED = os.path.join(os.path.dirname(__file__), "recorded_trace.xplane.pb")
SERVE = {"prefixes": ["serving."]}


def ctx_of(trace, window_s=1.0):
    return types.SimpleNamespace(
        trace=trace, record=types.SimpleNamespace(trace_window_s=window_s))


@pytest.fixture()
def serving():
    """One second, four programs, three gaps: 100-150 ms (the host waits for
    a first token), 400-480 ms (mostly inside the decode dispatch), 700-760 ms
    (straddles admit and flush of a second step), and 100 ms of edge."""
    ops = [(0, 100 * MS, "%fusion.1 = f32[8]{0} fusion(...)"),
           (150 * MS, 400 * MS, "%copy.2 = f32[8]{0} copy(...)"),
           (480 * MS, 700 * MS, "%fusion.3 = f32[8]{0} fusion(...)"),
           (760 * MS, 900 * MS, "%copy.4 = f32[8]{0} copy(...)")]
    names = ["jit_serving_prefill(1)", "jit_serving_decode_greedy(2)",
             "jit_serving_decode_greedy(2)", "jit__write_pages(3)"]
    spans = [(90, 500, "bench.step"), (95, 495, "serving.step"),
             (98, 160, "serving.prefill"),
             (99, 149, "serving.prefill.first_token"),
             (380, 490, "serving.decode"),
             (400, 430, "serving.decode.tables"),
             (430, 475, "serving.decode.dispatch"),
             (476, 486, "serving.decode.fetch"),
             (590, 955, "bench.step"), (600, 950, "serving.step"),
             (690, 715, "serving.admit"), (715, 730, "serving.flush"),
             (960, 970, "serving.decode.fetch"),      # outside any step
             (10, 12, "train.data_wait"), (500, 503, "train.data_wait"),
             (0, 1000, "ThreadpoolListener::Run")]
    return ctx_of(tr.Trace(
        device_ops={"/device:TPU:0": ops},
        device_programs={"/device:TPU:0": [
            (a, b, n) for (a, b, _), n in zip(ops, names)]},
        host_spans=[(a * MS, b * MS, n) for a, b, n in spans]))


def test_idle_by_span_adds_up_to_the_idle_share(serving, capsys):
    prefill = span_idle.read(serving, under="serving.prefill", **SERVE)
    decode = span_idle.read(serving, under="serving.decode", **SERVE)
    elsewhere = span_idle.read(
        serving, under=None, minus=["serving.prefill", "serving.decode"],
        **SERVE)
    assert prefill == pytest.approx(5.0)       # gap 1, by first_token
    assert decode == pytest.approx(8.0)        # gap 2, by decode.dispatch
    assert elsewhere == pytest.approx(16.0)    # gap 3 (serving.step) + edge
    assert prefill + decode + elsewhere \
        == pytest.approx(idle_share.read(serving)) == pytest.approx(29.0)
    err = capsys.readouterr().err
    assert err.count("idle by span") == 1      # the table: once a run
    assert "serving.prefill.first_token" in err \
        and "serving.decode.dispatch" in err and "serving.step" in err
    assert "between programs 0.290000s, inside them 0.000000s" in err


def test_idle_by_span_of_a_training_gap():
    ctx = ctx_of(tr.Trace(
        device_ops={"/device:TPU:0": [(0, 400 * MS, "%fusion.1 = ..."),
                                      (430 * MS, 1000 * MS, "%fusion.1 = ...")]},
        host_spans=[(a * MS, b * MS, n) for a, b, n in (
            (300, 401, "train.fetch"), (401, 403, "train.epoch_end"),
            (403, 404, "train.data_wait"), (404, 431, "train.dispatch"))]))
    assert span_idle.read(ctx, prefixes=["train."], under="train.dispatch") \
        == pytest.approx(3.0)
    assert span_idle.read(ctx, prefixes=["train."], under="train.fetch") \
        == pytest.approx(0.0)
    assert span_idle.read(ctx, prefixes=["serving."], under="serving.decode") \
        is None


def test_span_time(serving):
    # two steps of 400 and 350 ms, less 50 ms of first_token and 10 ms of the
    # one fetch that lies inside a step
    assert span_time.read(
        serving, span="serving.step", per="serving.step",
        minus=["serving.decode.fetch", "serving.prefill.first_token"]) \
        == pytest.approx(345.0)
    assert span_time.read(serving, span="train.data_wait",
                          share_of_window=True) == pytest.approx(0.5)
    assert span_time.read(serving, span="serving.decode.fetch") \
        == pytest.approx(0.020)
    assert span_time.read(serving, span="train.validation") is None


def test_program_share(serving):
    assert program_share.read(
        serving, contains=["serving_prefill", "serving_sample_first",
                           "_write_pages", "_load_pages"]) \
        == pytest.approx(100.0 * 240 / 710)
    assert program_share.read(serving, contains=["train_epoch"]) is None


@pytest.mark.parametrize("read", [
    lambda c: span_idle.read(c, under="serving.decode", **SERVE),
    lambda c: span_idle.read(c, under=None, minus=["serving.prefill"], **SERVE),
    lambda c: span_idle.read(c, prefixes=["train."], under="train.dispatch"),
    lambda c: span_time.read(c, span="serving.step", per="serving.step"),
    lambda c: span_time.read(c, span="train.data_wait", share_of_window=True),
    lambda c: program_share.read(c, contains=["serving_prefill", "_write_pages"]),
], ids=["idle_in", "idle_elsewhere", "idle_train", "host_work", "data_wait",
        "prefill_share"])
def test_nothing_to_read_on_the_recorded_chip_trace(read):
    """The parent commit has none of the spans and program names: every new
    metric is left out there, and nothing raises."""
    assert read(ctx_of(tr.load(RECORDED), window_s=0.05)) is None


def test_engine_spans_land_on_the_host_plane(tmp_path):
    """A tiny paged engine under the profiler, as ``run.py --trace 1`` starts
    it: the spans of ``obs.span`` are on the host plane under their own names,
    each inside its parent in time, one ``serving.step`` a step."""
    import numpy as np
    from distkeras_tpu.models import Model, zoo
    from distkeras_tpu.serving import ServingEngine
    from harness import common

    model = Model.build(zoo.transformer_lm(
        16, d_model=32, num_heads=4, num_layers=2, mlp_ratio=2), (32,), seed=0)
    eng = ServingEngine(model, num_slots=2, max_len=32, page_len=4)
    prompt = np.array([3, 1, 4, 1, 5, 9, 2, 6, 5])
    eng.submit(prompt[:6], 8)
    eng.run()                                   # compiles outside the trace
    profiler = common.Profiler(True, str(tmp_path))
    profiler.start()
    first = eng._iters
    eng.submit(prompt[:6], 12)
    eng.submit(prompt, 10)
    for _ in range(12):
        eng.step()
    profiler.stop()
    trace = tr.load(str(tmp_path))
    by_name = {}
    for a, b, name in trace.host_spans:
        if name.startswith("serving."):
            by_name.setdefault(name, []).append((a, b))
    assert len(by_name["serving.step"]) == eng._iters - first == 12
    assert len(by_name["serving.prefill"]) == 2
    parents = {"serving.admit": "serving.step", "serving.flush": "serving.step",
               "serving.prefill": "serving.step", "serving.decode": "serving.step",
               "serving.prefill.stage": "serving.prefill",
               "serving.prefill.dispatch": "serving.prefill",
               "serving.prefill.insert": "serving.prefill",
               "serving.prefill.first_token": "serving.prefill",
               "serving.decode.pages": "serving.decode",
               "serving.decode.tables": "serving.decode",
               "serving.decode.dispatch": "serving.decode",
               "serving.decode.fetch": "serving.decode",
               "serving.decode.consume": "serving.decode"}
    assert set(by_name) == set(parents) | {"serving.step"}
    for child, parent in parents.items():
        for a, b in by_name[child]:
            assert any(pa <= a and b <= pb for pa, pb in by_name[parent]), child
    # the reader of the host's work per step reads this trace as it stands
    ms = span_time.read(
        ctx_of(trace), span="serving.step", per="serving.step",
        minus=["serving.decode.fetch", "serving.prefill.first_token"])
    assert 0 < ms < 1e3 * profiler.window_s / 12
