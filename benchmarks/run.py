#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data that this file finds by the names
in ``BENCHMARK.json``: ``configs/<config>.json``, ``traffic/<mix>.json``,
``limits/<cell>.json``, and for each per-layer metric ``metrics/<name>.json``,
which names a reader under ``readers/`` and its arguments. See ``README.md``.

Needs a TPU with at least the chips the cell asks for and exits 2, with no
result, without one. ``--rehearse`` runs the same control flow at a tiny size
on the CPU (``JAX_PLATFORMS=cpu``) and prints no metric under a device
metric's name.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()     # process start, as near as Python gives it

import argparse
import importlib
import json
import os
import shutil
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def _named(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


def load_cell(bench: dict, workload: str, rehearse: bool = False):
    """``(cell, configuration, traffic mix)`` of a workload, the two files as
    they are run (with ``rehearse``: at their tiny CPU sizes)."""
    from harness import common, traffic as traffic_mod
    cell = _named(bench["workloads"], workload, "workload")
    entry = _named(bench["configs"], cell["config"], "config")
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = common.config_for(json.load(f), rehearse)
    traffic = traffic_mod.effective(_load("traffic", cell["traffic"] + ".json"),
                                    rehearse)
    return cell, cfg, traffic


def _reported(bench, cell) -> list:
    """Names of the end-to-end metrics this cell reports."""
    return [m["name"] for m in bench["end_to_end"]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def _per_layer(bench, cell, ctx) -> dict:
    """Every per-layer metric that lists this cell, read by its reader. A
    reader that finds nothing to read returns None and the metric is left
    out of the line."""
    out = {}
    reported = _reported(bench, cell)
    for m in bench["per_layer"]:
        # without a "workloads" key a metric is for every cell that reports
        # the end-to-end metric it moves
        if cell["name"] not in m.get("workloads", [cell["name"]]) \
                or m["moves"] not in reported:
            continue
        spec = _load("metrics", m["name"] + ".json")
        reader = importlib.import_module("readers." + spec["reader"])
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def execute(bench: dict, args, devices) -> dict:
    """The rest of a run, once the look for a chip is done: drive the cell,
    read the metrics, decide ``correct``; returns the result's line."""
    from harness import check, peaks, weights
    cell, cfg, traffic = load_cell(bench, args.workload, args.rehearse)
    # a traffic mix names its driver: harness/<driver>_driver.py
    driver = importlib.import_module(f"harness.{traffic['driver']}_driver")
    trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    rec = driver.run(cell, cfg, traffic, args, T_START, trace_dir)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": rec.memory_peak_bytes}
    line = {"correct": False, "attempted": rec.attempted, "failed": rec.failed}
    if args.trace:
        from harness import trace_reduce
        t_load = time.perf_counter()
        trace = trace_reduce.load(rec.trace_dir)
        rec.notes["trace_load_s"] = time.perf_counter() - t_load
        shutil.rmtree(rec.trace_dir, ignore_errors=True)
        ctx = types.SimpleNamespace(
            record=rec, trace=trace, cell=cell, sizes=weights.sizes(cfg),
            traffic=traffic, chips=cell["chips"],
            peaks=None if args.rehearse else peaks.peaks_for(device["kind"]))
        metrics = {} if args.rehearse else _per_layer(bench, cell, ctx)
        device.update(busy_s=trace_reduce.busy_seconds(trace),
                      window_s=rec.trace_window_s)
        line["breakdown"] = {"device_ops": trace_reduce.top_ops(trace),
                             "idle_gaps": trace_reduce.idle_gaps(trace)}
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        wanted = _reported(bench, cell)
        missing = [n for n in wanted if n not in rec.end_to_end]
        if missing:
            raise SystemExit(f"run.py: the window gave no {missing}")
        metrics = {n: {"value": rec.end_to_end[n], "unit": units[n]} for n in wanted}
    where = rec.numbers.pop("_where", None)
    print(json.dumps({"counters": rec.counters, "trace_counters": rec.trace_counters,
                      "notes": rec.notes, "numbers": rec.numbers, "where": where}),
          file=sys.stderr)
    ok, compared = check.verdict(
        rec.numbers, check.load_limits(HERE, cell["name"], args.rehearse))
    line["correct"] = bool(ok and rec.failed == 0 and rec.attempted > 0)
    if args.rehearse:      # CPU numbers never stand under a device metric's name
        line.update(rehearsal=True, cpu_values=rec.end_to_end)
    else:
        line["metrics"] = metrics
    line.update(device=device, compared=compared)
    return line


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU: control flow only, no device metric")
    ap.add_argument("--control", default=None,
                    help="serve cells: 'ref-int8' or 'ref-fp8' put the reference at "
                    "that precision in the program's place (the check has to "
                    "fail); 'int8' serves with the program's own weight_quant")
    return ap.parse_args(argv)


def main() -> int:
    args = parse()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = _named(bench["workloads"], args.workload, "workload")

    import jax
    devices = jax.devices()
    if args.rehearse:
        if devices[0].platform != "cpu":
            raise SystemExit("run.py: --rehearse is for JAX_PLATFORMS=cpu")
    elif devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"run.py: {args.workload} needs {cell['chips']} TPU chip(s); JAX reports "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 2
    from distkeras_tpu.compat import enable_compile_cache
    enable_compile_cache()      # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    print(json.dumps(execute(bench, args, devices)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
