"""The two readers of set-up (``readers/compile_log.py``, ``span_total.py``)
on a planted compile log and planted span records, where every number can be
worked out on paper; on a program that has no compile log; and once on the
real log and the real spans of a tiny engine on the CPU."""

import types

import pytest

from distkeras_tpu import obs
from readers import compile_log, span_total


def entry(program, backend_s, trace_s, lower_s, cache,
          span=("serving.step", "serving.prefill")):
    return {"program": program, "backend_s": backend_s, "trace_s": trace_s,
            "lower_s": lower_s, "cache": cache, "t_end": 0.0, "span": span}


#: set-up: one program of the harness's weights (outside any span), three of
#: the program's own; then the reference's one after the window
LOG = [entry("_make_weights", 32.0, 16.0, 16.0, "miss", span=()),
       entry("broadcast_in_dim", 2.0, 0.5, 0.25, "hit",
             span=("serving.init", "serving.init.pool")),
       entry("serving_prefill", 4.0, 1.0, 0.5, "miss"),
       entry("serving_decode_greedy", 1.0, 0.125, 0.125, "hit",
             span=("serving.step", "serving.decode")),
       entry("reference_forward", 64.0, 8.0, 8.0, "miss",
             span=("serving.step",))]


def ctx_of(**notes):
    return types.SimpleNamespace(record=types.SimpleNamespace(notes=notes))


@pytest.fixture()
def planted(monkeypatch):
    def plant(log=LOG, overflow=0):
        monkeypatch.setattr(obs, "compile_log", lambda: [dict(e) for e in log])
        monkeypatch.setattr(obs, "compile_totals", lambda: {
            "count": len(log) + overflow, "overflow": overflow})
    plant()
    return plant


@pytest.mark.parametrize("what,value", [
    ("programs", 3), ("hit_share", 100.0 * 2 / 3), ("backend_s", 7.0),
    ("trace_lower_s", 2.5)])
def test_set_up_is_what_fell_in_a_span_among_the_first_n_entries(
        planted, what, value):
    assert compile_log.read(ctx_of(compiles_in_setup=4), what) \
        == pytest.approx(value)


def test_a_log_shorter_than_the_count_is_read_as_far_as_it_goes(planted):
    assert compile_log.read(ctx_of(compiles_in_setup=9), "programs") == 4
    assert compile_log.read(ctx_of(compiles_in_setup=9), "backend_s") == 71.0


def test_zero_is_a_value(planted):
    for n in (0, 1):         # nothing yet; the harness's weights alone
        ctx = ctx_of(compiles_in_setup=n)
        assert compile_log.read(ctx, "programs") == 0
        assert compile_log.read(ctx, "backend_s") == 0
        assert compile_log.read(ctx, "trace_lower_s") == 0
        assert compile_log.read(ctx, "hit_share") is None  # a share of none
    planted(log=[entry("f", 0.0, 0.0, 0.0, "miss")])
    ctx = ctx_of(compiles_in_setup=1)
    assert compile_log.read(ctx, "hit_share") == 0.0
    assert compile_log.read(ctx, "backend_s") == 0.0


def test_no_persistent_cache_asked_gives_no_hit_share(planted):
    planted(log=[entry("f", 1.0, 0.0, 0.0, None)])
    ctx = ctx_of(compiles_in_setup=1)
    assert compile_log.read(ctx, "hit_share") is None   # not "all missed"
    assert compile_log.read(ctx, "programs") == 1
    planted(log=[entry("f", 1.0, 0.0, 0.0, None),
                 entry("g", 1.0, 0.0, 0.0, "hit")])
    assert compile_log.read(ctx_of(compiles_in_setup=2), "hit_share") == 100.0


@pytest.mark.parametrize("what", ["programs", "hit_share", "backend_s",
                                  "trace_lower_s"])
def test_no_log_no_note_no_spans_or_a_log_that_overflowed_gives_none(
        planted, monkeypatch, what):
    assert compile_log.read(ctx_of(), what) is None        # no note
    planted(overflow=2)                   # its first entries are gone
    assert compile_log.read(ctx_of(compiles_in_setup=4), what) is None
    planted()
    monkeypatch.setattr(obs, "enabled", lambda: False)   # every span is ()
    assert compile_log.read(ctx_of(compiles_in_setup=4), what) is None
    monkeypatch.undo()
    planted()
    monkeypatch.delattr(obs, "compile_log")        # an older distkeras_tpu
    assert compile_log.read(ctx_of(compiles_in_setup=4), what) is None


def test_span_total_sums_the_paths_that_end_in_a_name(monkeypatch):
    records = [(("serving.init",), 3.0, 1),
               (("serving.init", "serving.init.pool"), 2.0, 2),
               (("router.start", "serving.init"), 1.5, 1),
               (("serving.step",), 40.0, 900),
               (("train.setup",), 0.0, 1)]
    monkeypatch.setattr(obs, "span_records", lambda: records)
    assert span_total.read(None, names=["serving.init"]) == 4.5
    assert span_total.read(None, names=["serving.init.pool"]) == 2.0
    assert span_total.read(None, names=["train.setup"]) == 0.0   # a value
    assert span_total.read(None, names=["engine.epoch"]) is None
    monkeypatch.setattr(obs, "span_records", lambda: [])
    assert span_total.read(None, names=["serving.init"]) is None


def test_both_read_a_tiny_engine_on_the_cpu():
    """The real log and the real spans: what the drivers do, at a tiny size."""
    from distkeras_tpu.models import Model, zoo
    from distkeras_tpu.serving import ServingEngine
    obs.reset_spans()
    before = obs.compile_totals()["count"]       # other tests' compiles
    model = Model.build(
        zoo.transformer_lm(29, d_model=16, num_heads=2, num_layers=1,
                           max_len=32), (12,), seed=0)
    engine = ServingEngine(model, num_slots=2, max_len=32, page_len=4)
    engine.submit([1, 2, 3, 4, 5], 6)
    engine.run(max_steps=50)
    count = obs.compile_totals()["count"]
    ctx = ctx_of(compiles_in_setup=count)
    if obs.compile_totals()["overflow"]:
        pytest.skip("this process has compiled more than the log holds")
    mine = [e for e in obs.compile_log() if e["span"]]
    assert {"serving_prefill", "serving_decode_greedy"} \
        <= {e["program"] for e in mine[-(count - before):]}
    assert all(e["span"][0] in ("serving.init", "serving.step")
               for e in obs.compile_log()[before:] if e["span"])
    assert compile_log.read(ctx, "programs") == len(mine) <= count
    assert compile_log.read(ctx, "backend_s") \
        == pytest.approx(sum(e["backend_s"] for e in mine))
    assert compile_log.read(ctx, "trace_lower_s") > 0
    share = compile_log.read(ctx, "hit_share")    # None without a cache
    assert share is None or 0 <= share <= 100
    init = span_total.read(ctx, names=["serving.init"])
    assert 0 < init == obs.span_summary()["serving.init"]["total_s"]
    assert span_total.read(ctx, names=["train.setup"]) is None
