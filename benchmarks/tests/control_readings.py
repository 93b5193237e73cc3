"""Readings that a training cell's limits are set from, taken on the chip at
the cell's own size with the reference put in the program's place:

    chiprun --chips 1 -- python3 benchmarks/tests/control_readings.py \
        --workload train-cgpt256m-1chip --seeds 11 12 13

For each seed: the control (the reference with every matrix product in int8,
the step below the bfloat16 the configuration states), the half-batch fault
(the mean taken over the first half of each step's rows), and a bfloat16
witness (the reference at the configuration's own precision). A state left
unchanged reads 1 by construction and needs no run. Serving cells take their
control from the program's own lower precision: ``run.py --control int8``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--variants", nargs="+", default=["int8", "half_batch", "bfloat16"])
    args = ap.parse_args()
    import run as bench_run
    from harness import check, traffic as T, weights
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    _, cfg, traffic = bench_run.load_cell(bench, args.workload, args.rehearse)
    import jax
    from distkeras_tpu.compat import enable_compile_cache
    enable_compile_cache()
    batch = traffic["sequences_per_chip_step"]
    for seed in args.seeds:
        x, y = T.train_rows(traffic, weights.sizes(cfg)["vocab"], seed)
        ref = check.reference_observed(cfg, traffic, x, y, seed)
        for variant in args.variants:
            kw = {"rows": (0, max(batch // 2, 1))} if variant == "half_batch" \
                else {"precision": variant}
            got = check.reference_observed(cfg, traffic, x, y, seed, **kw)
            numbers = check.train_numbers(got, ref)
            where = numbers.pop("_where")
            print(json.dumps({"seed": seed, "variant": variant, **numbers,
                              "where": {k: v for k, v in where.items() if k != "left_out"},
                              "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
