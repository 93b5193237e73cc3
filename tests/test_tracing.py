"""Request-level tracing (``obs.tracing``): the per-request timeline,
its token-exact duration accounting, the Chrome/Perfetto trace export,
and the serving-engine integration points."""

import json
import re

import numpy as np
import pytest

from distkeras_tpu import obs
from distkeras_tpu.models import Model, zoo
from distkeras_tpu.obs.tracing import (NULL_TRACER, RequestTracer,
                                       resolve_tracer)
from distkeras_tpu.parallel.worker import make_epoch_runner
from distkeras_tpu.serving import ServingEngine, ServingMetrics


class FakeClock:
    """Deterministic injectable clock (monotonic; advance() moves it)."""

    def __init__(self):
        self.t = 100.0

    def advance(self, dt):
        self.t += dt

    def __call__(self):
        return self.t


# --- tracer unit behavior ---------------------------------------------------


def test_timeline_durations_sum_exactly_to_latency():
    clk = FakeClock()
    tr = RequestTracer(clock=clk)
    tr.on_submit(0, queue_depth=3)
    clk.advance(0.5)                       # queued
    tr.on_admit(0, slot=1, queue_depth=2)
    clk.advance(0.25)                      # prefill
    tr.on_first_token(0)
    clk.advance(1.25)                      # decode
    tr.on_terminal(0, "finished", n_tokens=10)
    s = tr.summaries()[0]
    d = s["durations"]
    assert d["queued_s"] == pytest.approx(0.5)
    assert d["prefill_s"] == pytest.approx(0.25)
    assert d["ttft_s"] == pytest.approx(0.75)
    assert d["decode_s"] == pytest.approx(1.25)
    assert d["total_s"] == pytest.approx(2.0)
    # the token-exactness identity: phases partition the latency
    assert d["queued_s"] + d["prefill_s"] + d["decode_s"] \
        == pytest.approx(d["total_s"], abs=1e-12)
    assert s["slot"] == 1
    assert s["queue_depth_at_submit"] == 3
    assert s["queue_depth_at_admit"] == 2
    assert s["state"] == "finished" and s["n_tokens"] == 10


def test_timeline_terminated_mid_prefill_still_partitions_latency():
    """A request that dies after admission but before its first token
    attributes the admit->end span to prefill, so the sum-exactly
    invariant holds on every terminal path."""
    clk = FakeClock()
    tr = RequestTracer(clock=clk)
    tr.on_submit(0, 1)
    clk.advance(0.5)
    tr.on_admit(0, slot=0, queue_depth=0)
    clk.advance(0.75)                      # dies ingesting its prompt
    tr.on_terminal(0, "cancelled", 0)
    d = tr.summaries()[0]["durations"]
    assert d == {"queued_s": pytest.approx(0.5),
                 "prefill_s": pytest.approx(0.75),
                 "total_s": pytest.approx(1.25)}
    assert "ttft_s" not in d and "decode_s" not in d


def test_timeline_terminated_while_queued_has_no_slot_phases():
    clk = FakeClock()
    tr = RequestTracer(clock=clk)
    tr.on_submit(5, queue_depth=9)
    clk.advance(2.0)
    tr.on_terminal(5, "timed_out", n_tokens=0)
    d = tr.summaries()[5]["durations"]
    assert d == {"queued_s": pytest.approx(2.0),
                 "total_s": pytest.approx(2.0)}


def test_decode_events_aggregate_per_n_iterations():
    clk = FakeClock()
    tr = RequestTracer(clock=clk, decode_agg=4)
    tr.on_submit(0, 0)
    tr.on_admit(0, 0, 0)
    tr.on_first_token(0)
    for _ in range(10):
        clk.advance(0.01)
        tr.on_decode([0])
    tr.on_terminal(0, "finished", 11)
    (tl,) = tr.timelines()
    decode_events = [e for e in tl.events if e["name"] == "decode"]
    # 10 iterations at agg=4: two full windows + one terminal flush
    assert [e["iters"] for e in decode_events] == [4, 4, 2]
    assert tl.decode_iters == 10


def test_tracer_bounds_completed_timelines_and_events():
    tr = RequestTracer(max_requests=3, max_events=8)
    for rid in range(5):
        tr.on_submit(rid, 0)
        tr.on_admit(rid, 0, 0)
        for c in range(20):                 # far past max_events
            tr.on_prefill_chunk(rid, c, 1)
        tr.on_terminal(rid, "finished", 1)
    tls = tr.timelines()
    assert [t.rid for t in tls] == [2, 3, 4]   # ring: oldest evicted
    for t in tls:
        assert len(t.events) == 8
        assert t.summary()["dropped_events"] > 0
        assert t.prefill_chunks == 20           # counters stay exact


def test_events_for_unknown_rid_are_ignored():
    tr = RequestTracer()
    tr.on_first_token(42)
    tr.on_decode([42])
    tr.on_terminal(42, "finished", 1)
    assert tr.summaries() == {}


def test_resolve_tracer_policy():
    assert resolve_tracer(False) is NULL_TRACER
    t = RequestTracer()
    assert resolve_tracer(t) is t
    assert resolve_tracer(None).enabled
    obs.disable()
    try:
        assert resolve_tracer(None) is NULL_TRACER
    finally:
        obs.enable()


# --- Chrome trace export ----------------------------------------------------


def _flows(events, ph):
    return [e for e in events if e.get("ph") == ph]


def test_chrome_trace_one_complete_flow_per_request():
    clk = FakeClock()
    tr = RequestTracer(clock=clk)
    for rid in (0, 1):
        tr.on_submit(rid, rid)
        clk.advance(0.1)
        tr.on_admit(rid, rid, 0)
        clk.advance(0.1)
        tr.on_first_token(rid)
        clk.advance(0.1)
        tr.on_terminal(rid, "finished", 3)
    # a third request sheds in the queue: still one complete flow
    tr.on_submit(2, 5)
    clk.advance(0.05)
    tr.on_terminal(2, "cancelled", 0)
    ct = tr.chrome_trace()
    ct = json.loads(json.dumps(ct))        # validates as JSON
    events = ct["traceEvents"]
    starts, finishes = _flows(events, "s"), _flows(events, "f")
    assert sorted(e["id"] for e in starts) == [0, 1, 2]
    assert sorted(e["id"] for e in finishes) == [0, 1, 2]
    for s in starts:                       # each start has its finish
        (f,) = [f for f in finishes if f["id"] == s["id"]]
        assert f["ts"] >= s["ts"]
    # request tracks carry the three phase slices; slot tracks the
    # occupancy interval; a queued-only request has just "queued"
    names = {(e["pid"], e["tid"], e["name"]) for e in events
             if e.get("ph") == "X"}
    for rid in (0, 1):
        assert (1, rid, "queued") in names
        assert (1, rid, "prefill") in names
        assert (1, rid, "decode") in names
        assert (0, rid, f"req {rid}") in names
    assert (1, 2, "queued") in names
    assert not any(t == (1, 2, "prefill") for t in names)
    # durations are microseconds on the shared clock
    (q0,) = [e for e in events if e.get("ph") == "X"
             and e["pid"] == 1 and e["tid"] == 0
             and e["name"] == "queued"]
    assert q0["dur"] == pytest.approx(0.1 * 1e6)


def test_chrome_trace_dump_is_loadable_json(tmp_path):
    tr = RequestTracer()
    tr.on_submit(0, 0)
    tr.on_admit(0, 0, 0)
    tr.on_first_token(0)
    tr.on_terminal(0, "finished", 2)
    path = tr.dump_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        ct = json.load(f)
    assert ct["displayTimeUnit"] == "ms"
    assert any(e.get("ph") == "M" for e in ct["traceEvents"])


# --- engine integration -----------------------------------------------------

V, S = 29, 12
PATTERN = np.array([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8])


@pytest.fixture(scope="module")
def tiny_lm():
    """Untrained tiny LM: tracing asserts timelines, not token values."""
    return Model.build(
        zoo.transformer_lm(V, d_model=32, num_heads=4, num_layers=2,
                           mlp_ratio=2, use_rope=True), (S,), seed=0)


def test_engine_timelines_are_token_exact_under_staggered_arrivals(
        tiny_lm):
    """The acceptance shape: a staggered-arrival run's per-request
    traces show admitted -> TTFT -> finish with durations summing
    (exactly — same clock on both sides) to the measured latency, and
    every request's decode-iteration count equals its generated tokens
    minus the prefill-sampled first one."""
    eng = ServingEngine(tiny_lm, num_slots=2, max_len=32)
    rids = [eng.submit(PATTERN[:4], 6), eng.submit(PATTERN[:6], 5)]
    eng.step()
    eng.step()
    rids += [eng.submit(PATTERN[:3], 7), eng.submit(PATTERN[:5], 4)]
    out = eng.run(max_steps=500)
    assert sorted(out) == sorted(rids)
    summ = eng.tracer.summaries()
    for i, rid in enumerate(rids):
        s = summ[rid]
        d = s["durations"]
        assert s["state"] == "finished"
        assert s["slot"] in (0, 1)
        # phases partition the request's life exactly
        assert d["queued_s"] + d["prefill_s"] + d["decode_s"] \
            == pytest.approx(d["total_s"], abs=1e-9)
        assert d["ttft_s"] == pytest.approx(
            d["queued_s"] + d["prefill_s"], abs=1e-9)
        # token-exact: one decode iteration per generated token after
        # the prefill-sampled first
        budget = [6, 5, 7, 4][i]
        assert s["n_tokens"] == budget
        assert s["decode_iters"] == budget - 1
    # the engine-measured latency histogram and the timeline totals are
    # the same numbers on the same clock; the edges are adjacent (not
    # shared) clock reads, so agreement is within clock tolerance
    lats = sorted(eng.metrics.latencies())
    totals = sorted(s["durations"]["total_s"] for s in summ.values())
    assert lats == pytest.approx(totals, abs=5e-3)
    # Chrome trace: one complete flow per request
    ct = json.loads(json.dumps(eng.tracer.chrome_trace()))
    starts = _flows(ct["traceEvents"], "s")
    finishes = _flows(ct["traceEvents"], "f")
    assert sorted(e["id"] for e in starts) == sorted(rids)
    assert sorted(e["id"] for e in finishes) == sorted(rids)


def test_engine_merges_request_summaries_into_component(tiny_lm):
    eng = ServingEngine(tiny_lm, num_slots=1, max_len=24)
    rid = eng.submit(PATTERN[:4], 3)
    eng.run(max_steps=200)
    # earlier engines may still be alive, own the plain "serving" name
    # and hold the same rid (rids start at 0 in every engine): THIS
    # engine's component is the one registered under its own name
    mine = obs.telemetry_snapshot()["components"][eng._component_name]
    assert mine["requests"][rid]["state"] == "finished"


def test_engine_tracer_records_queue_depth_and_slot(tiny_lm):
    eng = ServingEngine(tiny_lm, num_slots=1, max_len=24)
    r0 = eng.submit(PATTERN[:4], 3)
    r1 = eng.submit(PATTERN[:4], 3)      # waits behind r0
    eng.run(max_steps=300)
    s0, s1 = eng.tracer.summaries()[r0], eng.tracer.summaries()[r1]
    assert s0["queue_depth_at_submit"] == 1   # itself, pre-admission
    assert s1["queue_depth_at_submit"] == 2
    assert s0["slot"] == 0 and s1["slot"] == 0  # slot recycled
    assert s1["durations"]["queued_s"] > 0


def test_engine_with_disabled_obs_uses_null_tracer(tiny_lm):
    obs.disable()
    try:
        eng = ServingEngine(tiny_lm, num_slots=1, max_len=24)
        assert eng.tracer is NULL_TRACER
        assert eng.scheduler.tracer is None
        eng.submit(PATTERN[:4], 2)
        eng.run(max_steps=200)
        assert eng.tracer.summaries() == {}
    finally:
        obs.enable()


def test_engine_cancel_and_timeout_land_in_timeline(tiny_lm):
    clk = FakeClock()
    metrics = ServingMetrics(clock=clk)
    eng = ServingEngine(tiny_lm, num_slots=1, max_len=24,
                        metrics=metrics)
    # tracer auto-created on the SAME injectable clock
    assert eng.tracer.clock is clk
    r0 = eng.submit(PATTERN[:4], 5, deadline_s=1.0)
    clk.advance(2.0)                       # expire before any work
    eng.step()
    s = eng.tracer.summaries()[r0]
    assert s["state"] == "timed_out"
    assert s["durations"]["total_s"] == pytest.approx(2.0)
    r1 = eng.submit(PATTERN[:4], 5)
    eng.cancel(r1)
    assert eng.tracer.summaries()[r1]["state"] == "cancelled"


# --- spans on the profiler's clock (obs.span) and program names -------------

SERVING_SPANS = {
    "serving.admit": {}, "serving.flush": {},
    "serving.prefill": {"serving.prefill.stage", "serving.prefill.dispatch",
                        "serving.prefill.insert",
                        "serving.prefill.first_token"},
    "serving.decode": {"serving.decode.pages", "serving.decode.tables",
                       "serving.decode.dispatch", "serving.decode.fetch",
                       "serving.decode.consume"}}


def _paged_engine_30_steps(tiny_lm):
    eng = ServingEngine(tiny_lm, num_slots=2, max_len=32, page_len=4)
    eng.submit(PATTERN[:6], 12)
    eng.submit(PATTERN[:9], 10)
    for _ in range(30):
        eng.step()
    return eng


def test_engine_step_opens_the_documented_spans(tiny_lm):
    obs.reset_spans()
    eng = _paged_engine_30_steps(tiny_lm)
    tree = obs.span_summary()
    assert set(tree) == {"serving.init", "serving.step"}
    step = tree["serving.step"]
    assert step["count"] == eng._iters == 30
    kids = step["children"]
    assert set(kids) == set(SERVING_SPANS)
    for name, below in SERVING_SPANS.items():
        assert set(kids[name]["children"]) == set(below), name
        assert sum(c["total_s"] for c in kids[name]["children"].values()) \
            <= kids[name]["total_s"]
    assert sum(c["total_s"] for c in kids.values()) <= step["total_s"]
    assert kids["serving.admit"]["count"] == 30
    assert kids["serving.flush"]["count"] == 30
    # the count of a span is the count of that work: two prompts, one
    # whole-prompt chunk each; a step launched for every decode iteration
    # and all but the last fetched one iteration later
    assert kids["serving.prefill"]["count"] == 2
    decode = kids["serving.decode"]
    n = decode["count"]
    assert decode["children"]["serving.decode.dispatch"]["count"] == n
    assert decode["children"]["serving.decode.fetch"]["count"] == n - 1
    assert decode["children"]["serving.decode.consume"]["count"] == n - 1


def test_engine_constructor_opens_serving_init_with_its_children(tiny_lm):
    obs.reset_spans()
    ServingEngine(tiny_lm, num_slots=2, max_len=32, page_len=4)
    tree = obs.span_summary()
    assert set(tree) == {"serving.init"}
    init = tree["serving.init"]
    assert init["count"] == 1
    kids = init["children"]
    assert set(kids) == {"serving.init.weights", "serving.init.pool"}
    # the pool, then the staging cache
    assert kids["serving.init.pool"]["count"] == 2
    assert sum(c["total_s"] for c in kids.values()) <= init["total_s"]
    # a constructor that raises closes its spans all the same
    with pytest.raises(ValueError, match="decode_kernel"):
        ServingEngine(tiny_lm, max_len=32, decode_kernel="nope")
    assert obs.current_path() == ()
    assert obs.span_summary()["serving.init"]["count"] == 2


def test_health_lists_a_program_compiled_after_warm_with_its_span(tiny_lm):
    import jax
    import jax.numpy as jnp
    eng = _paged_engine_30_steps(tiny_lm)
    health = eng.health()
    assert health["compiles"] == {"after_warm": []}
    assert health["telemetry"]["compile"]["count"] \
        == obs.compile_totals()["count"] > 0
    # a dtype drift in the weights: the warm decode step retraces
    eng._params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), eng._params)
    eng.submit(PATTERN[:6], 4)
    with pytest.warns(obs.RecompileWarning,
                      match=r"serving_decode_greedy \d+\.\d+ s .* in "
                      "serving.step/serving.decode/"):
        for _ in range(8):
            eng.step()
        eng._recompile.check()
    after = eng.health()["compiles"]["after_warm"]
    assert [c["program"] for c in after] == ["serving_decode_greedy"]
    assert after[0]["span"][:2] == ("serving.step", "serving.decode")
    assert after[0]["span"][-1] == "serving.decode.dispatch"
    assert after[0]["seconds"] > 0
    assert set(after[0]) == {"program", "seconds", "cache", "span"}


def test_engine_step_opens_no_span_with_obs_disabled(tiny_lm):
    obs.reset_spans()
    obs.disable()
    try:
        eng = _paged_engine_30_steps(tiny_lm)
        assert eng._iters == 30
    finally:
        obs.enable()
    assert obs.span_summary() == {}


def test_span_with_step_nests_like_any_other():
    obs.reset_spans()
    with obs.span("outer", step=3):
        assert obs.current_path() == ("outer",)
        with obs.span("inner"):
            assert obs.current_path() == ("outer", "inner")
        with obs.span("inner", step=4):
            pass
    assert obs.current_path() == ()
    tree = obs.span_summary()
    assert tree["outer"]["count"] == 1
    assert tree["outer"]["children"]["inner"]["count"] == 2
    assert tree["outer"]["children"]["inner"]["total_s"] \
        <= tree["outer"]["total_s"]


def _train_two_epochs(**kw):
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.parallel.trainers import SingleTrainer
    rs = np.random.RandomState(0)
    X = rs.rand(128, 8).astype(np.float32)
    y = (X.sum(axis=1) > 4).astype(np.int32)
    model = Model.build(zoo.mlp((8,), num_classes=2), (8,), seed=0)
    tr = SingleTrainer(
        model, worker_optimizer="sgd", learning_rate=0.1,
        loss="sparse_categorical_crossentropy_from_logits",
        batch_size=32, num_epoch=2, **kw)
    tr.train(Dataset({"features": X, "label": y}))
    return tr


def test_trainer_epoch_loop_opens_the_train_spans(tmp_path):
    obs.reset_spans()
    tr = _train_two_epochs(checkpoint_dir=str(tmp_path))
    tree = obs.span_summary()
    assert set(tree) == {"train.setup", "train.data_wait", "train.dispatch",
                         "train.fetch", "train.epoch_end"}
    # everything before the first dispatch, once a train() call
    assert tree["train.setup"]["count"] == 1
    assert tree["train.setup"]["children"] == {}
    # the epoch program compiled in the first dispatch, not in set-up
    assert [e["span"] for e in obs.compile_log()
            if e["program"] == "train_epoch"][-1] == ("train.dispatch",)
    # one epoch program an epoch; the stream is asked once more at its end
    assert tree["train.dispatch"]["count"] == 2
    assert tree["train.fetch"]["count"] == 2
    assert tree["train.data_wait"]["count"] == 3
    assert tree["train.epoch_end"]["count"] == 2
    assert set(tree["train.epoch_end"]["children"]) == {"train.checkpoint"}
    # the tape's own account is what it was: one ``device`` phase
    phases = tr.tape.snapshot()["phases_s"]
    assert set(phases) == {"data_wait", "device", "checkpoint"}
    assert phases["device"] == pytest.approx(
        tree["train.dispatch"]["total_s"] + tree["train.fetch"]["total_s"],
        rel=0.05)


@pytest.mark.parametrize("how", ["telemetry_false", "obs_disabled"])
def test_trainer_opens_no_span_without_telemetry(how):
    obs.reset_spans()
    if how == "telemetry_false":
        _train_two_epochs(telemetry=False)
    else:
        obs.disable()
        try:
            _train_two_epochs()
        finally:
            obs.enable()
    assert obs.span_summary() == {}


def _spmd_train_two_epochs(**kw):
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.parallel.mesh import make_mesh_2d
    from distkeras_tpu.parallel.spmd import SPMDTrainer
    rs = np.random.RandomState(0)
    X = rs.rand(128, 8).astype(np.float32)
    y = (X.sum(axis=1) > 4).astype(np.int32)
    model = Model.build(zoo.mlp((8,), num_classes=2), (8,), seed=0)
    tr = SPMDTrainer(
        model, mesh=make_mesh_2d({"workers": 2, "tp": 1}),
        worker_optimizer="sgd", learning_rate=0.1,
        loss="sparse_categorical_crossentropy_from_logits",
        batch_size=32, num_epoch=2, **kw)
    tr.train(Dataset({"features": X, "label": y}))
    return tr


def test_spmd_trainer_opens_train_setup_once():
    obs.reset_spans()
    tr = _spmd_train_two_epochs()
    tree = obs.span_summary()
    assert set(tree) == {"train.setup", "train.data_wait", "train.dispatch",
                         "train.fetch", "train.epoch_end"}
    assert tree["train.setup"]["count"] == 1
    assert tree["train.dispatch"]["count"] == 2
    # the SPMD epoch program compiled in the first dispatch too
    assert [e["span"] for e in obs.compile_log()
            if e["program"] == "run_epoch"][-1] == ("train.dispatch",)


@pytest.mark.parametrize("how", ["telemetry_false", "obs_disabled"])
def test_spmd_trainer_opens_no_span_without_telemetry(how):
    obs.reset_spans()
    if how == "telemetry_false":
        _spmd_train_two_epochs(telemetry=False)
    else:
        obs.disable()
        try:
            _spmd_train_two_epochs()
        finally:
            obs.enable()
    assert obs.span_summary() == {}


def _spec_engine(tiny_lm, **kw):
    from distkeras_tpu.serving import NgramDraft
    return ServingEngine(tiny_lm, num_slots=2, max_len=32, page_len=4,
                         draft=NgramDraft(), spec_k=3, **kw)


@pytest.mark.parametrize("build,name", [
    (lambda m: ServingEngine(m, max_len=32)._decode_fn(True),
     "serving_decode_greedy"),
    (lambda m: ServingEngine(m, max_len=32)._decode_fn(False),
     "serving_decode_sampled"),
    (lambda m: ServingEngine(m, max_len=32, fuse_steps=4)._fused_fn(True),
     "serving_decode_fused_greedy"),
    (lambda m: ServingEngine(m, max_len=32)._prefill_fn(6, 0, True),
     "serving_prefill"),
    (lambda m: ServingEngine(m, max_len=32)._sample_first_fn(),
     "serving_sample_first"),
    (lambda m: _spec_engine(m)._verify_fn(True), "serving_verify_greedy"),
    (lambda m: _spec_engine(m, spec_tree=True, spec_width=2)
     ._verify_tree_fn(False), "serving_verify_tree_sampled"),
    (lambda m: make_epoch_runner(lambda c, b: (c, 0.0)), "train_epoch"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_programs_are_named_for_the_profiler(tiny_lm, build, name):
    """What ``XLA Modules`` of a profiler trace and the compile log print:
    ``jit_<name>``."""
    assert build(tiny_lm).__name__ == name


def test_decode_logits_program_name_is_an_identifier(tiny_lm):
    eng = ServingEngine(tiny_lm, num_slots=2, max_len=32, page_len=4)
    eng.submit(PATTERN[:6], 4)
    eng.step()
    eng.decode_logits(decode_kernel="off")
    fn = eng._logits_fns["off", None]
    assert fn.__name__ == "serving_decode_logits_off_None_"
    assert fn.__name__.isidentifier()


@pytest.fixture(scope="module")
def step_program_texts(tiny_lm):
    """The lowered train step and paged decode step of the tiny LM, with the
    operations' names (``jit(<program>)/<scope>/...``)."""
    import jax
    from distkeras_tpu.ops.losses import get_loss
    from distkeras_tpu.ops.optimizers import get_optimizer
    from distkeras_tpu.parallel.worker import (TrainCarry, make_epoch_runner,
                                               make_train_step)
    opt = get_optimizer("adam", learning_rate=1e-3)
    step = make_train_step(
        tiny_lm.module,
        get_loss("sparse_categorical_crossentropy_from_logits"), opt)
    carry = TrainCarry(tiny_lm.params, tiny_lm.state,
                       opt.init(tiny_lm.params), jax.random.PRNGKey(0))
    x = np.zeros((2, 2, S), np.int32)
    train = make_epoch_runner(step).lower(carry, x, x).as_text(
        debug_info=True)
    eng = ServingEngine(tiny_lm, num_slots=2, max_len=32, page_len=4)
    decode = eng._decode_fn(True).lower(
        eng._params, eng._state, eng.pool.cache, eng._tok, eng._t,
        eng.pool.device_tables()).as_text(debug_info=True)
    return {"train_epoch": train, "serving_decode_greedy": decode,
            "train_epoch_carry_leaves": len(jax.tree_util.tree_leaves(carry))}


@pytest.mark.parametrize("program,scope", [
    ("train_epoch", "embed"), ("train_epoch", "attn"),
    ("train_epoch", "mlp"), ("train_epoch", "head"),
    ("train_epoch", "loss"), ("train_epoch", "optimizer"),
    ("serving_decode_greedy", "embed"), ("serving_decode_greedy", "attn"),
    ("serving_decode_greedy", "mlp"), ("serving_decode_greedy", "head"),
    ("serving_decode_greedy", "sample")])
def test_step_programs_carry_the_named_scopes(step_program_texts, program,
                                              scope):
    """For XProf's op profile; nothing in ``benchmarks/`` reads them yet."""
    text = step_program_texts[program]
    assert f"module @jit_{program}" in text
    # ``.../attn/...``, or ``jvp(attn)/...`` where the step differentiates
    assert re.search(rf'[/("]{scope}[/)"]', text)


def test_epoch_program_donates_its_carry_and_not_its_data(step_program_texts):
    """``make_epoch_runner``: every leaf of the carry (parameters, Adam's
    moments and count, the key) is aliased to the result it becomes; the two
    data arguments are not, the ``Prefetcher`` owns them."""
    text = step_program_texts["train_epoch"]
    head = text[text.index("func.func public @main("):].split(") -> (", 1)[0]
    args = re.split(r",\s*(?=%arg\d+:)", head)
    donated = ["tf.aliasing_output" in a or "jax.buffer_donor" in a
               for a in args]
    assert len(args) == step_program_texts["train_epoch_carry_leaves"] + 2
    assert all(donated[:-2]), args
    assert not any(donated[-2:]), args[-2:]
