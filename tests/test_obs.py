"""Unified telemetry layer (``distkeras_tpu.obs``): spans, registry,
recompile detector, exporters, tape, and the integration points
(trainer logs, serving summary compat, prefetch gauges)."""

import json
import threading
import sys
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import obs
from distkeras_tpu.obs import exporters
from distkeras_tpu.obs.registry import MetricsRegistry

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))


# --- spans ------------------------------------------------------------------

def test_span_nesting_builds_tree_with_self_time():
    obs.reset_spans()
    with obs.span("outer"):
        with obs.span("inner"):
            pass
        with obs.span("inner"):
            pass
        with obs.span("other"):
            pass
    t = obs.span_summary()
    assert t["outer"]["count"] == 1
    assert t["outer"]["children"]["inner"]["count"] == 2
    assert t["outer"]["children"]["other"]["count"] == 1
    child = (t["outer"]["children"]["inner"]["total_s"]
             + t["outer"]["children"]["other"]["total_s"])
    assert t["outer"]["total_s"] >= child
    assert t["outer"]["self_s"] == pytest.approx(
        t["outer"]["total_s"] - child)


def test_span_exception_path_pops_stack_and_records():
    obs.reset_spans()
    with pytest.raises(ValueError):
        with obs.span("boom"):
            with obs.span("deep"):
                raise ValueError("x")
    assert obs.current_path() == ()          # stack unwound
    t = obs.span_summary()
    assert t["boom"]["count"] == 1           # partial duration recorded
    assert t["boom"]["children"]["deep"]["count"] == 1
    # and the tree is reusable afterwards
    with obs.span("boom"):
        pass
    assert obs.span_summary()["boom"]["count"] == 2


def test_spans_from_threads_share_one_tree():
    obs.reset_spans()

    def work(name):
        with obs.span(name):
            with obs.span("leaf"):
                pass

    ts = [threading.Thread(target=work, args=(f"t{i % 2}",))
          for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    tree = obs.span_summary()
    assert tree["t0"]["count"] + tree["t1"]["count"] == 8
    assert tree["t0"]["children"]["leaf"]["count"] == tree["t0"]["count"]


def test_span_disabled_is_noop():
    obs.reset_spans()
    obs.disable()
    try:
        with obs.span("hidden"):
            pass
    finally:
        obs.enable()
    assert "hidden" not in obs.span_summary()


# --- registry ---------------------------------------------------------------

def test_registry_counter_gauge_histogram_basics():
    r = MetricsRegistry()
    c = r.counter("c")
    c.inc()
    c.inc(2.5, route="x")
    assert c.value() == 1.0 and c.value(route="x") == 2.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("g")
    g.set(5)
    g.set(3)
    assert g.value() == 3 and g.max() == 5
    h = r.histogram("h")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    s = h.stats()
    assert s["count"] == 4 and s["mean"] == 2.5
    assert s["min"] == 1.0 and s["max"] == 4.0
    assert s["p50"] == pytest.approx(2.5)
    # same name returns the same instrument; a kind clash raises
    assert r.counter("c") is c
    with pytest.raises(TypeError):
        r.gauge("c")


def test_registry_histogram_reservoir_is_bounded_and_exact_extremes():
    r = MetricsRegistry(reservoir_size=64)
    h = r.histogram("h")
    for v in range(10_000):
        h.observe(float(v))
    s = h.stats()
    assert s["count"] == 10_000                 # streaming stats exact
    assert s["min"] == 0.0 and s["max"] == 9999.0
    assert s["mean"] == pytest.approx(4999.5)
    assert len(h.samples()) == 64               # memory bounded
    assert 2000 < s["p50"] < 8000               # sampled percentile sane


def test_registry_label_cardinality_caps_with_overflow_series():
    r = MetricsRegistry(max_series=4)
    c = r.counter("cap")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for i in range(20):
            c.inc(rid=i)
    assert sum("max_series" in str(x.message) for x in w) == 1
    vals = c.values()
    assert len(vals) == 5                       # 4 real + overflow
    assert vals["overflow=true"] == 16          # nothing lost
    assert sum(vals.values()) == 20


def test_label_flattening_roundtrips_hostile_values():
    from distkeras_tpu.obs.registry import (label_string,
                                            parse_label_string)
    # the TPU device-string shape: '=' and ',' inside the value
    key = (("device", "TPU_0(process=0,(0,0,0,0))"), ("k", r"a\b=c,d"))
    assert parse_label_string(label_string(key)) == list(key)
    assert parse_label_string(label_string(())) == []


def test_prometheus_escapes_device_style_labels():
    r = MetricsRegistry()
    r.gauge("device.bytes_in_use").set(
        123, device="TPU_0(process=0,(0,0,0,0))")
    text = exporters.prometheus_text(r.snapshot())
    line = [ln for ln in text.splitlines() if ln.endswith(" 123.0")]
    assert line == ['distkeras_device_bytes_in_use'
                    '{process_index="0",'
                    'device="TPU_0(process=0,(0,0,0,0))"} 123.0'], text


def test_prometheus_every_line_carries_process_index():
    """Satellite (multi-host groundwork): every exported series line —
    labeled or not — carries the process_index label from the single
    registry.process_label() helper, with no per-call-site plumbing."""
    from distkeras_tpu.obs.registry import process_label
    assert process_label() == ("process_index", "0")
    r = MetricsRegistry()
    r.counter("a.b").inc()                     # unlabeled
    r.gauge("c.d").set(1.0, k="v")             # labeled
    r.histogram("e.f").observe(2.0)
    text = exporters.prometheus_text(r.snapshot())
    for ln in text.splitlines():
        if ln.startswith("#") or not ln:
            continue
        assert 'process_index="0"' in ln, ln
    # a series carrying its OWN process_index label wins — a duplicate
    # label name is invalid exposition format (fails the whole scrape)
    r2 = MetricsRegistry()
    r2.counter("a.b").inc(process_index="3")
    (line,) = [ln for ln in exporters.prometheus_text(
        r2.snapshot()).splitlines() if not ln.startswith("#")]
    assert line == 'distkeras_a_b_total{process_index="3"} 1.0'


def test_registry_snapshot_shape():
    r = MetricsRegistry()
    r.counter("a").inc(3, k="v")
    r.gauge("b").set(1.5)
    r.histogram("c").observe(2.0)
    s = r.snapshot()
    assert s["counters"]["a"] == {"k=v": 3.0}
    assert s["gauges"]["b"][""] == {"value": 1.5, "max": 1.5}
    assert s["histograms"]["c"][""]["count"] == 1


# --- recompile detector -----------------------------------------------------

def test_recompile_detector_fires_on_shape_unstable_jit():
    r = MetricsRegistry()
    det = obs.RecompileDetector(r)
    f = jax.jit(lambda x: x * 2)
    det.watch("hot", f)
    f(jnp.ones(3))
    det.mark_warm()
    f(jnp.ones(3))                              # cache hit: quiet
    assert det.check() == {}
    with pytest.warns(obs.RecompileWarning, match="hot"):
        f(jnp.ones(7))                          # shape leak
        grew = det.check()
    assert grew == {"hot": 1}
    # warned once per growth step, not once per check
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        det.check()
    assert not w
    assert det.counts()["hot"] == 2
    assert r.gauge("jit.compile_count").value(fn="hot") == 2


def test_recompile_detector_stays_silent_on_stable_jit():
    det = obs.RecompileDetector(MetricsRegistry())
    f = jax.jit(lambda x: x + 1)
    det.watch("stable", f)
    f(jnp.ones(4))
    det.mark_warm()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for _ in range(5):
            f(jnp.ones(4))
            assert det.check() == {}
    assert not w


def test_recompile_counts_survive_function_gc():
    det = obs.RecompileDetector(MetricsRegistry())
    f = jax.jit(lambda x: x + 1)
    det.watch("gone", f)
    f(jnp.ones(2))
    assert det.counts() == {"gone": 1}
    del f
    import gc
    gc.collect()
    assert det.counts() == {"gone": 1}          # last-known size kept


def test_compile_totals_increase_on_fresh_compile():
    before = obs.compile_totals()
    jax.jit(lambda x: x * 3.5 + 1)(jnp.ones(11))
    after = obs.compile_totals()
    assert after["count"] > before["count"]
    assert after["seconds"] > before["seconds"]


# --- the compile log --------------------------------------------------------

def _logged(program):
    return [e for e in obs.compile_log() if e["program"] == program]


@pytest.fixture
def fresh_compile_log(monkeypatch):
    """Totals at zero and an empty log for one test (the process's own
    come back after it): what an earlier test compiled, or dropped at
    the bound, is not in the sums."""
    import collections
    from distkeras_tpu.obs import collectors

    def fresh(maxlen=collectors.MAX_LOG):
        monkeypatch.setattr(collectors, "_log",
                            collections.deque(maxlen=maxlen))
        monkeypatch.setattr(collectors, "_totals",
                            dict.fromkeys(collectors._totals, 0))
    fresh()
    return fresh


def test_compile_log_names_the_program_its_stages_and_its_span():
    @jax.jit
    def logged_fresh_fn(x):
        return jnp.tanh(x) @ x

    x = jnp.ones((5, 5))                 # made outside the span
    with obs.span("outer"):
        with obs.span("inner"):
            logged_fresh_fn(x)
    (entry,) = _logged("logged_fresh_fn")
    assert set(entry) == {"program", "backend_s", "trace_s", "lower_s",
                          "cache", "t_end", "span"}
    assert entry["backend_s"] > 0
    assert entry["trace_s"] > 0 and entry["lower_s"] > 0
    assert entry["span"] == ("outer", "inner")
    assert entry["cache"] in (None, "hit", "miss")
    from distkeras_tpu.utils.profiling import now
    assert entry["t_end"] <= now()
    # a second call at the same shapes: the function's own cache answers
    logged_fresh_fn(x)
    assert len(_logged("logged_fresh_fn")) == 1
    # a new shape is a new entry, outside any span this time
    logged_fresh_fn(jnp.ones((6, 6)))
    assert [e["span"] for e in _logged("logged_fresh_fn")] \
        == [("outer", "inner"), ()]


def test_compile_log_agrees_with_compile_totals(fresh_compile_log):
    jax.jit(lambda x: x * 2.5 - 1)(jnp.ones(13))
    jax.jit(lambda x: x * 2.5 + 1).lower(jnp.ones(13))
    totals, log = obs.compile_totals(), obs.compile_log()
    assert not totals["overflow"]
    assert len(log) == totals["count"] >= 1
    assert sum(e["backend_s"] for e in log) \
        == pytest.approx(totals["seconds"])
    assert all(e[k] >= 0 for e in log
               for k in ("backend_s", "trace_s", "lower_s"))
    # entries hold the outermost functions' stages only: never more than
    # the totals, which also have what led to no compile
    for k in ("trace_s", "lower_s"):
        assert sum(e[k] for e in log) <= totals[k] + 1e-9
    assert totals["hits"] == sum(e["cache"] == "hit" for e in log)
    assert totals["misses"] == sum(e["cache"] == "miss" for e in log)
    assert obs.telemetry_snapshot()["compile"]["count"] >= totals["count"]


def test_lower_alone_adds_to_the_totals_and_to_no_entry():
    def lowered_only_fn(x):
        return jnp.cos(x) + 1

    before, n = obs.compile_totals(), len(obs.compile_log())
    x = jax.ShapeDtypeStruct((7,), jnp.float32)
    jax.jit(lowered_only_fn).lower(x)
    jax.eval_shape(lowered_only_fn, x)
    after = obs.compile_totals()
    assert after["lower_s"] > before["lower_s"]
    assert after["trace_s"] > before["trace_s"]
    assert after["count"] == before["count"]
    assert len(obs.compile_log()) == n
    assert not _logged("lowered_only_fn")


def test_compile_log_records_with_telemetry_disabled_but_no_span():
    @jax.jit
    def logged_while_disabled_fn(x):
        return x * 3 + 2

    x = jnp.ones(9)
    obs.disable()
    try:
        with obs.span("not_recorded"):
            logged_while_disabled_fn(x)
    finally:
        obs.enable()
    (entry,) = _logged("logged_while_disabled_fn")
    assert entry["span"] == ()


def test_compile_log_is_bounded_and_counts_its_overflow(fresh_compile_log):
    from distkeras_tpu.obs import collectors
    assert collectors._log.maxlen == collectors.MAX_LOG == 4096
    fresh_compile_log(maxlen=3)
    f = jax.jit(lambda x: x - 0.25)
    for n in range(2, 7):                # five shapes, five programs
        f(jnp.ones(n))
    totals, log = obs.compile_totals(), obs.compile_log()
    assert totals["count"] >= 5
    assert len(log) == 3 and log[-1]["program"] == "<lambda>"
    assert totals["overflow"] == totals["count"] - 3


def test_compile_log_reads_a_persistent_cache_miss_then_hit(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc
    if not getattr(jax.devices()[0].client,
                   "supports_executable_serialization", True):
        pytest.skip("this backend cannot serialize an executable: it has "
                    "no persistent compile cache")
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}

    @jax.jit
    def cached_across_runs_fn(x):
        return jnp.sinh(x) * 1.75

    try:
        cc.reset_cache()
        for n, v in zip(names, (str(tmp_path), 0, -1)):
            jax.config.update(n, v)
        cached_across_runs_fn(jnp.ones(17))
        if not any(tmp_path.iterdir()):
            pytest.skip("this backend wrote no persistent cache entry")
        jax.clear_caches()               # as a new process would find it
        cached_across_runs_fn(jnp.ones(17))
    finally:
        cc.reset_cache()
        for n, v in saved.items():
            jax.config.update(n, v)
    first, second = _logged("cached_across_runs_fn")
    assert first["cache"] == "miss" and second["cache"] == "hit"


def test_recompile_warning_says_what_the_compile_cost_and_where():
    det = obs.RecompileDetector(MetricsRegistry())

    @jax.jit
    def leaky_hot_fn(x):
        return x * 2

    det.watch("hot", leaky_hot_fn)
    a, b = jnp.ones(3), jnp.ones(8)
    leaky_hot_fn(a)
    det.mark_warm()
    assert det.after_warm() == []
    with pytest.warns(obs.RecompileWarning,
                      match=r"leaky_hot_fn \d+\.\d+ s .* in step/dispatch"):
        with obs.span("step"):
            with obs.span("dispatch"):
                leaky_hot_fn(b)          # shape leak
        det.check()
    (cost,) = det.after_warm()
    assert cost["program"] == "leaky_hot_fn"
    assert cost["span"] == ("step", "dispatch")
    assert cost["seconds"] > 0 and cost["cache"] in (None, "hit", "miss")


# --- exporters --------------------------------------------------------------

def _populated_registry():
    r = MetricsRegistry()
    r.counter("req.total").inc(7, route="gen")
    r.gauge("depth").set(3)
    h = r.histogram("lat.s")
    for v in (0.1, 0.2, 0.3):
        h.observe(v, route="gen")
    return r


def test_jsonl_roundtrip_reproduces_snapshot(tmp_path):
    r = _populated_registry()
    obs.reset_spans()
    with obs.span("a"):
        with obs.span("b"):
            pass
    path = str(tmp_path / "t.jsonl")
    exporters.JsonlExporter(path).export(r.snapshot())
    snap, span_recs = exporters.read_jsonl(path)
    # float-exact round trip through JSON
    assert snap == json.loads(json.dumps(r.snapshot()))
    assert {p for p, _t, _c in span_recs} == {("a",), ("a", "b")}


def test_jsonl_header_carries_schema_version(tmp_path):
    """Satellite: the meta header versions the format so trace/recorder
    consumers can evolve it without breaking old logs."""
    r = MetricsRegistry()
    r.counter("a.b").inc()
    path = str(tmp_path / "t.jsonl")
    exporters.JsonlExporter(path).export(r.snapshot(), spans=[])
    with open(path) as f:
        meta = json.loads(f.readline())
    assert meta["type"] == "meta"
    assert meta["schema_version"] == exporters.SCHEMA_VERSION
    assert obs.telemetry_snapshot()["schema_version"] \
        == exporters.SCHEMA_VERSION


def test_read_jsonl_tolerates_unknown_types_and_keys(tmp_path):
    """Forward compatibility: a NEWER writer's log (unknown record
    types, extra top-level keys, keyless lines) still yields the series
    this reader understands — no KeyError, nothing dropped."""
    path = str(tmp_path / "t.jsonl")
    lines = [
        {"type": "meta", "seq": 0, "schema_version": 99,
         "written_by": "future-version"},
        {"type": "counter", "seq": 0, "name": "a.b", "labels": "",
         "value": 3.0, "future_field": {"x": 1}},
        {"type": "request_trace", "seq": 0, "rid": 7},   # unknown type
        {"note": "a line with no type key at all"},
        {"type": "span", "seq": 0, "path": ["x"], "total_s": 1.0,
         "count": 2, "self_s": 0.5},
    ]
    with open(path, "w") as f:
        for rec in lines:
            f.write(json.dumps(rec) + "\n")
    snap, spans = exporters.read_jsonl(path)
    assert snap["counters"]["a.b"][""] == 3.0
    assert spans == [(("x",), 1.0, 2)]


def test_jsonl_latest_seq_wins(tmp_path):
    r = MetricsRegistry()
    c = r.counter("n")
    path = str(tmp_path / "t.jsonl")
    exp = exporters.JsonlExporter(path)
    c.inc()
    exp.export(r.snapshot(), spans=[])
    c.inc()
    exp.export(r.snapshot(), spans=[])
    snap, _ = exporters.read_jsonl(path)
    assert snap["counters"]["n"][""] == 2.0
    snap0, _ = exporters.read_jsonl(path, seq=0)
    assert snap0["counters"]["n"][""] == 1.0


def test_prometheus_text_format():
    text = exporters.prometheus_text(_populated_registry().snapshot())
    assert "# TYPE distkeras_req_total_total counter" in text
    assert ('distkeras_req_total_total{process_index="0",route="gen"} '
            "7.0") in text
    assert "# TYPE distkeras_depth gauge" in text
    q50 = [ln for ln in text.splitlines()
           if ln.startswith('distkeras_lat_s{process_index="0",'
                            'route="gen",quantile="0.5"}')]
    assert len(q50) == 1
    assert float(q50[0].rsplit(" ", 1)[1]) == pytest.approx(0.2)
    assert ('distkeras_lat_s_count{process_index="0",route="gen"} 3'
            in text)


def test_xprof_tool_renders_span_table(tmp_path):
    from xprof_op_table import load_span_records, render_span_table
    obs.reset_spans()
    with obs.span("train"):
        with obs.span("device"):
            pass
    path = str(tmp_path / "t.jsonl")
    exporters.JsonlExporter(path).export(MetricsRegistry().snapshot())
    recs = load_span_records(path)
    assert set(recs) == set(obs.span_records())
    table = render_span_table(recs)
    assert "| `train` |" in table
    assert "| `train / device` |" in table
    assert "share" in table


# --- StepTimer thread-safety + reset ---------------------------------------

def test_steptimer_threadsafe_and_reset():
    from distkeras_tpu.utils.profiling import StepTimer
    t = StepTimer()

    def work():
        for _ in range(200):
            with t.phase("p"):
                pass

    ts = [threading.Thread(target=work) for _ in range(4)]
    for th in ts:
        th.start()
    for th in ts:
        th.join()
    assert t.summary()["p"]["count"] == 800   # no torn updates
    t.reset()
    assert t.summary() == {}
    with t.phase("q"):
        pass
    assert t.summary()["q"]["count"] == 1


# --- training tape ----------------------------------------------------------

def test_tape_phase_breakdown_goodput_and_logs():
    tape = obs.TrainingTape(name="t", unit="imgs",
                            registry=MetricsRegistry(),
                            flops_per_example=1e6, peak_flops=1e12)
    tape.train_begin()
    with tape.phase("data_wait"):
        pass
    with tape.phase("device"):
        pass
    logs = tape.epoch_end(examples=640)
    for key in ("imgs_per_sec", "data_wait_s", "device_s", "host_s",
                "goodput", "mfu", "checkpoint_s", "validation_s"):
        # checkpoint/validation present (0.0) even when the phase
        # didn't run — CSVLogger freezes its header on epoch 0's keys
        assert key in logs, key
        assert isinstance(logs[key], float)
    assert logs["checkpoint_s"] == 0.0
    assert 0.0 <= logs["goodput"] <= 1.0
    snap = tape.snapshot()
    assert snap["examples"] == 640 and snap["epochs"] == 1
    assert set(snap["phases_s"]) == {"data_wait", "device"}
    tape.train_end()
    frozen = tape.snapshot()["wall_s"]
    assert tape.snapshot()["wall_s"] == frozen   # window frozen


def test_timed_stream_charges_data_wait():
    tape = obs.TrainingTape(name="ts", registry=MetricsRegistry())
    tape.train_begin()
    assert list(obs.timed_stream(iter([1, 2, 3]), tape)) == [1, 2, 3]
    logs = tape.epoch_end(examples=3)
    assert logs["data_wait_s"] >= 0.0
    hist = tape.registry.histogram("ts.phase_s")
    # 3 item waits + the final exhaustion probe (also a real wait)
    assert hist.stats(phase="data_wait")["count"] == 4


def test_goodput_not_deflated_by_compiles_outside_device_phase():
    tape = obs.TrainingTape(name="gp", registry=MetricsRegistry())
    tape.train_begin()
    with tape.phase("device"):
        sum(range(1000))                     # tiny but nonzero
    with tape.phase("validation"):
        # a fresh compile OUTSIDE the device phase (unique constants
        # force a new program); its seconds must charge the wall
        # denominator, not the device numerator
        jax.jit(lambda x: x * 1.23456 + 9.87)(jnp.ones(17))
    logs = tape.epoch_end(examples=10)
    assert logs["goodput"] > 0.0


def test_histogram_reservoir_seed_is_process_stable():
    import random
    import zlib
    # the seed formula must not involve salted str hashing: crc32 of
    # the series identity is identical in every process
    r = MetricsRegistry(reservoir_size=4)
    h = r.histogram("seed.check")
    for v in range(100):
        h.observe(float(v))
    expect = random.Random(zlib.crc32(b"seed.check:0"))
    res = []
    for n, v in enumerate(float(v) for v in range(100)):
        if len(res) < 4:
            res.append(v)
        else:
            j = expect.randrange(n + 1)
            if j < 4:
                res[j] = v
    assert h.samples() == res


@pytest.mark.parametrize("platform,kind,want", [
    ("tpu", "TPU v5 lite", 197e12),
    ("tpu", "TPU v4", 275e12),
    ("cpu", "cpu", None),                 # MFU is not a CPU metric
    ("tpu", "TPU v5 lite pod-ish", ValueError),   # no substring guesses
    ("tpu", "TPU v9", ValueError),
])
def test_peak_flops_exact_device_kinds_only(platform, kind, want):
    """The chip peak comes from an exact ``device_kind`` match: an
    unknown TPU raises instead of inheriting a neighbour's peak."""
    from distkeras_tpu.obs.tape import peak_flops_of
    if want is ValueError:
        with pytest.raises(ValueError, match="unknown TPU device_kind"):
            peak_flops_of(platform, kind)
    else:
        assert peak_flops_of(platform, kind) == want


def test_null_tape_is_inert():
    t = obs.NULL_TAPE
    t.train_begin()
    with t.phase("device"):
        pass
    assert t.epoch_end(10) == {}
    assert t.snapshot() == {}
    t.train_end()


# --- integration: trainer logs ----------------------------------------------

def test_single_trainer_feeds_tape_logs_to_callbacks():
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.models import Model, zoo
    from distkeras_tpu.parallel.trainers import SingleTrainer
    from distkeras_tpu.utils.callbacks import LambdaCallback

    rs = np.random.RandomState(0)
    X = rs.rand(256, 8).astype(np.float32)
    y = (X.sum(axis=1) > 4).astype(np.int32)
    model = Model.build(zoo.mlp((16,), num_classes=2), (8,), seed=0)
    seen = []
    tr = SingleTrainer(
        model, worker_optimizer="sgd", learning_rate=0.1,
        loss="sparse_categorical_crossentropy_from_logits",
        batch_size=32, num_epoch=2,
        callbacks=[LambdaCallback(
            on_epoch_end=lambda e, logs: seen.append(dict(logs)))])
    tr.train(Dataset({"features": X, "label": y}))
    assert len(seen) == 2
    for logs in seen:
        for key in ("loss", "examples_per_sec", "data_wait_s",
                    "device_s", "host_s", "goodput"):
            assert key in logs, (key, sorted(logs))
    assert tr.tape.snapshot()["epochs"] == 2
    assert "SingleTrainer.epoch" in tr.tape.detector.counts()


def test_trainer_telemetry_false_disables_tape():
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.models import Model, zoo
    from distkeras_tpu.parallel.trainers import SingleTrainer
    from distkeras_tpu.utils.callbacks import LambdaCallback

    rs = np.random.RandomState(0)
    X = rs.rand(64, 8).astype(np.float32)
    y = (X.sum(axis=1) > 4).astype(np.int32)
    model = Model.build(zoo.mlp((8,), num_classes=2), (8,), seed=0)
    seen = []
    tr = SingleTrainer(
        model, worker_optimizer="sgd", learning_rate=0.1,
        loss="sparse_categorical_crossentropy_from_logits",
        batch_size=32, num_epoch=1, telemetry=False,
        callbacks=[LambdaCallback(
            on_epoch_end=lambda e, logs: seen.append(dict(logs)))])
    tr.train(Dataset({"features": X, "label": y}))
    assert tr.tape is obs.NULL_TAPE
    assert "goodput" not in seen[0]


# --- integration: serving metrics compat + bounded growth -------------------

def test_serving_metrics_growth_is_bounded():
    import itertools

    from distkeras_tpu.serving.metrics import ServingMetrics
    # an unbounded 0.25s-tick clock; a materialized arange big enough
    # to never exhaust would be a multi-GB allocation that dominates
    # the test's runtime in kernel page faults
    clock = itertools.count(0.0, 0.25)
    m = ServingMetrics(clock=lambda: float(next(clock)), reservoir=128)
    for rid in range(5000):
        m.record_submit(rid)
        m.record_first_token(rid)
        m.record_iteration(queue_depth=rid % 7, occupied=1, num_slots=2)
        m.record_decode(n_decoding=2, dt=0.01)
        m.record_finish(rid, n_generated=3)
    assert m.submit_ts == {}                    # finished state evicted
    assert len(m.ttfts()) <= 128               # reservoir-bounded
    assert len(m.latencies()) <= 128
    assert len(m.decode_samples) <= 128
    s = m.summary()
    assert s["requests_finished"] == 5000       # exact streaming counts
    assert s["tokens_generated"] == 15000
    assert s["queue_depth"]["max"] == 6.0
    assert s["ttft_s"]["p50"] == pytest.approx(0.25)
    assert m.decode_tokens_per_sec(min_occupancy=2) \
        == pytest.approx(200.0)                 # exact over ALL samples


def test_serving_summary_keys_are_backward_compatible():
    from distkeras_tpu.serving.metrics import ServingMetrics
    s = ServingMetrics().summary()
    assert set(s) == {
        "requests_finished", "tokens_generated", "tokens_per_sec",
        "decode_tokens_per_sec", "ttft_s", "latency_s", "queue_depth",
        "slot_occupancy", "prefill_chunks", "phases",
        # degradation tally ADDED by the resilience PR (pre-existing
        # keys above are the frozen compat contract)
        "requests_rejected", "requests_timed_out", "requests_cancelled",
        # per-token decode cadence ADDED by the tracing/SLO PR (feeds
        # the tpot_p99 objective)
        "tpot_s",
        # paged-KV tally ADDED by the paged-cache PR ("pages" is None
        # before any iteration)
        "requests_preempted", "pages", "prefix_cache",
        # speculative decoding ADDED by the spec-decode PR
        # ("acceptance_rate" is None before any verify ran)
        "acceptance_rate", "speculation",
        # expert-load tally ADDED by the MoE-serving PR ("moe" is None
        # on MoE-free / dense-baseline engines)
        "moe",
        # live departures to another replica ADDED by the
        # serving-router PR (transfer_out handoffs/rebalances)
        "requests_transferred",
        # host KV offload tally ADDED by the offload PR (page-swap
        # traffic + per-path resume latencies; zeros/None without a
        # host tier)
        "offload",
        # passes, blocks and tokens of a block-diffusion engine ADDED
        # by the block-diffusion PR (None until a pass ran)
        "block_diffusion",
        # what the one-token programs' expert layers routed, and the
        # page groups by attention kind, ADDED with the page groups
        # (None until a program reported / for a pool of one group)
        "routing", "kv_groups"}


# --- integration: prefetch gauges -------------------------------------------

def test_prefetcher_records_queue_depth_and_stall():
    from distkeras_tpu.utils.prefetch import Prefetcher
    reg = obs.reset_registry()
    out = list(Prefetcher(lambda x: x * 2, range(5), name="teststream"))
    assert [v for _, v in out] == [0, 2, 4, 6, 8]
    stats = reg.histogram("prefetch.stall_s").stats(stream="teststream")
    assert stats is not None and stats["count"] == 5
    assert reg.gauge("prefetch.queue_depth").max(
        stream="teststream") is not None


def test_prefetcher_respects_disable_toggle_mid_run():
    from distkeras_tpu.utils.prefetch import Prefetcher
    reg = obs.reset_registry()
    obs.disable()
    try:
        # built while disabled: records nothing...
        list(Prefetcher(lambda x: x, range(3), name="toggled"))
        assert reg.histogram("prefetch.stall_s").stats(
            stream="toggled") is None
    finally:
        obs.enable()
    # ...but the gate is per-consume, not frozen at construction
    list(Prefetcher(lambda x: x, range(3), name="toggled"))
    assert reg.histogram("prefetch.stall_s").stats(
        stream="toggled")["count"] == 3


# --- the unified snapshot ---------------------------------------------------

def test_telemetry_snapshot_unifies_components():
    reg = obs.reset_registry()
    reg.counter("x").inc()
    obs.reset_spans()
    with obs.span("s"):
        pass
    obs.attach("widget", lambda: {"ok": 1})
    try:
        snap = obs.telemetry_snapshot()
    finally:
        obs.detach("widget")
    assert snap["metrics"]["counters"]["x"][""] == 1.0
    assert "s" in snap["spans"]
    assert snap["compile"]["count"] >= 0
    assert snap["components"]["widget"] == {"ok": 1}


def test_second_serving_engine_gets_unique_component_name():
    import gc
    from distkeras_tpu.models import Model, zoo
    from distkeras_tpu.serving import ServingEngine
    for n in list(obs.components()):        # isolate from leaked engines
        if n.startswith("serving"):
            obs.detach(n)
    lm = Model.build(
        zoo.transformer_lm(13, d_model=8, num_heads=2, num_layers=1,
                           mlp_ratio=2, use_rope=True), (8,), seed=0)
    a = ServingEngine(lm, num_slots=1, max_len=8)
    b = ServingEngine(lm, num_slots=1, max_len=8)
    names = [n for n in obs.components() if n.startswith("serving")]
    assert "serving" in names and len(names) == 2
    del b
    gc.collect()
    # the FIRST engine keeps the plain name through the second's GC
    assert "serving" in obs.components()
    assert a is not None
    del a
    gc.collect()
    assert "serving" not in obs.components()


def test_attach_bound_method_does_not_keep_owner_alive():
    import gc
    import weakref

    class Owner:
        def snapshot(self):
            return {"v": 7}

    o = Owner()
    wr = weakref.ref(o)
    obs.attach("boundcomp", o.snapshot, owner=o)
    assert obs.telemetry_snapshot()["components"]["boundcomp"] == {"v": 7}
    del o
    gc.collect()
    # the natural attach(n, self.method, owner=self) pattern must not
    # leak the owner through the component registry
    assert wr() is None
    assert "boundcomp" not in obs.telemetry_snapshot()["components"]


def test_distributed_engine_run_epoch_after_external_build():
    from distkeras_tpu.models import Dense, Model, Sequential
    from distkeras_tpu.ops.losses import get_loss
    from distkeras_tpu.ops.optimizers import get_optimizer
    from distkeras_tpu.parallel.engine import (DistributedEngine,
                                               DownpourAlgo, EngineConfig)
    from distkeras_tpu.parallel.mesh import make_mesh
    W = 8
    model = Model.build(Sequential([Dense(4), Dense(2)]), (6,), seed=0)
    eng = DistributedEngine(
        model.module,
        get_loss("sparse_categorical_crossentropy_from_logits"),
        get_optimizer("sgd", learning_rate=0.05), DownpourAlgo(),
        make_mesh(W), EngineConfig(num_workers=W, window=2))
    eng._build()                    # tests/tools call _build() directly
    rs = np.random.RandomState(0)
    X = jnp.asarray(rs.randn(2, W, 2, 6).astype(np.float32))
    Y = jnp.asarray(rs.randint(0, 2, (2, W, 2)))
    state = jax.device_put(
        eng.init_state(model.params, model.state, jax.random.PRNGKey(0)),
        eng.shardings())
    state, outs = eng.run_epoch(state, X, Y)    # was AttributeError
    state, outs = eng.run_epoch(state, X, Y)    # warm path checks quietly
    assert eng._recompile.counts()["engine.epoch"] >= 1


def test_attach_with_owner_detaches_on_gc():
    class Owner:
        pass
    o = Owner()
    obs.attach("ephemeral", lambda: {"v": 2}, owner=o)
    assert obs.telemetry_snapshot()["components"].get(
        "ephemeral") == {"v": 2}
    del o
    import gc
    gc.collect()
    assert "ephemeral" not in obs.telemetry_snapshot()["components"]
