"""Telemetry tour: train briefly, serve briefly, print ONE unified
snapshot.

The point of ``distkeras_tpu.obs``: a single ``telemetry_snapshot()``
answers, for the whole process, where the step time went (span tree +
the training tape's data/host/device breakdown), whether anything
recompiled after warm-up (per-jitted-function compile counts), whether
the input pipeline stalled (prefetch queue depth/stall gauges), how
fast training ran (imgs/sec, MFU, goodput) and what serving latency
looked like (TTFT/latency percentiles) — numbers that previously lived
in four disconnected fragments.

Run:
    JAX_PLATFORMS=cpu python examples/telemetry_tour.py
"""

from __future__ import annotations

import json

import numpy as np

PATTERN = np.array([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8])


def main():
    import jax
    from distkeras_tpu import obs
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.models import Model, zoo
    from distkeras_tpu.parallel.trainers import SingleTrainer
    from distkeras_tpu.serving import ServingEngine

    # ---- 1. train briefly, with an MFU-capable tape -------------------
    rs = np.random.RandomState(0)
    X = rs.rand(2048, 16).astype(np.float32)
    y = (X.sum(axis=1) > 8).astype(np.int32)
    model = Model.build(zoo.mlp((64, 32), num_classes=2), (16,), seed=0)

    # FLOPs per example from XLA's own cost analysis of one jitted
    # train step — the honest numerator for MFU
    from distkeras_tpu.ops import get_loss, get_optimizer
    from distkeras_tpu.parallel.worker import TrainCarry, make_train_step
    step = make_train_step(
        model.module,
        get_loss("sparse_categorical_crossentropy_from_logits"),
        get_optimizer("sgd", learning_rate=0.1))
    opt = get_optimizer("sgd", learning_rate=0.1)
    carry = TrainCarry(model.params, model.state,
                       opt.init(model.params), jax.random.PRNGKey(0))
    batch = 64
    lowered = jax.jit(step).lower(
        carry, (np.zeros((batch, 16), np.float32),
                np.zeros((batch,), np.int32)))
    flops_per_example = float(
        lowered.compile().cost_analysis().get("flops", 0.0)) / batch

    peak, kind = obs.detect_peak_flops()
    if peak is None:
        # no spec-sheet peak for this chip (e.g. the CPU smoke config):
        # supply a nominal peak so the MFU plumbing is visible end to
        # end — the number is then RELATIVE to that stated peak
        peak = 1e12
    tape = obs.TrainingTape(name="tour", unit="imgs",
                            flops_per_example=flops_per_example,
                            peak_flops=peak)

    trainer = SingleTrainer(
        model, worker_optimizer="sgd", learning_rate=0.1,
        loss="sparse_categorical_crossentropy_from_logits",
        batch_size=batch, num_epoch=3, telemetry=tape)
    with obs.span("tour.train"):
        trained = trainer.train(Dataset({"features": X, "label": y}))

    # ---- 2. serve briefly --------------------------------------------
    V, S = 29, 12
    Xlm = np.tile(PATTERN, (128, 1))
    lm = Model.build(
        zoo.transformer_lm(V, d_model=32, num_heads=4, num_layers=2,
                           mlp_ratio=2, use_rope=True), (S,), seed=2)
    lm.fit(Xlm[:, :-1], Xlm[:, 1:], optimizer="adam", learning_rate=5e-3,
           batch_size=64, epochs=3,
           loss="sparse_categorical_crossentropy_from_logits")
    engine = ServingEngine(lm, num_slots=2, max_len=32, prefill_chunk=4)
    with obs.span("tour.serve"):
        for k in range(4):
            engine.submit(PATTERN[: 3 + k], max_new_tokens=5)
        engine.run(max_steps=500)

    # ---- 3. the unified snapshot -------------------------------------
    snap = obs.telemetry_snapshot()
    tour = tape.snapshot()
    serving = snap["components"]["serving"]
    print("=== unified telemetry snapshot ===")
    print(json.dumps({
        "train": {
            "imgs_per_sec": round(
                snap["metrics"]["gauges"]["tour.imgs_per_sec"][""]
                ["value"], 1),
            "goodput": round(tour["goodput"], 4),
            "mfu": round(tour["mfu"], 6),
            "phases_s": {k: round(v, 4)
                         for k, v in tour["phases_s"].items()},
            "recompiles": tour["recompiles"],
        },
        "prefetch": {
            "queue_depth_max": snap["metrics"]["gauges"]
            ["prefetch.queue_depth"]["stream=prefetch"]["max"],
            "stall_s_total": round(
                snap["metrics"]["histograms"]["prefetch.stall_s"]
                ["stream=prefetch"]["sum"], 4),
        },
        "serving": {
            "requests_finished": serving["requests_finished"],
            "ttft_s_p50": round(serving["ttft_s"]["p50"], 4),
            "latency_s_p50": round(serving["latency_s"]["p50"], 4),
        },
        "compile": {"count": snap["compile"]["count"],
                    "seconds": round(snap["compile"]["seconds"], 2)},
        "spans": sorted(snap["spans"]),
    }, indent=1))

    # the same snapshot, through the exporters
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        path = f"{d}/telemetry.jsonl"
        obs.exporters.JsonlExporter(path).export()
        snap2, spans2 = obs.exporters.read_jsonl(path)
        assert snap2 == json.loads(json.dumps(snap["metrics"]))
        # serving metrics live on the engine's WINDOW registry (a fresh
        # ServingMetrics per reporting interval); export that window
        prom = obs.exporters.prometheus_text(
            engine.metrics.registry.snapshot())
        assert "distkeras_serving_ttft_s" in prom
        assert "quantile=" in prom
    print("exporters: JSONL round-trip OK, prometheus text OK")

    acc = float((np.argmax(trained.predict(X), axis=1) == y).mean())
    print(f"trained accuracy {acc:.3f}; tour complete")
    return acc


if __name__ == "__main__":
    main()
