"""Records the small device trace that ``test_trace_reduce.py`` reads.

Run on the chip (``chiprun --chips 1 -- python benchmarks/tests/record_trace.py``):
it traces three calls of a small program that holds the three flash kernels,
the paged decode kernel and a matrix multiplication, writes the profiler's
``.xplane.pb`` to ``chiprun_out/recorded_trace.xplane.pb`` and prints the
planes, lines and most frequent event names it found.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distkeras_tpu.ops.flash_attention import flash_attention
    from distkeras_tpu.ops.paged_attention import paged_decode_attention

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")
    out = os.path.join("chiprun_out", "trace_probe")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)

    def loss(q, k, v, w):
        o = flash_attention(q, k, v, causal=True, layout="bhsd")
        return jnp.sum((o.reshape(-1, 64) @ w).astype(jnp.float32) ** 2)

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(kk, (2, 4, 512, 64), jnp.bfloat16)
               for kk in jax.random.split(key, 3))
    w = jax.random.normal(key, (64, 256), jnp.bfloat16)

    slots, hkv, d, page_len, pages = 4, 2, 128, 16, 64
    qd = jax.random.normal(key, (slots, 1, hkv, 1, d), jnp.bfloat16)
    kp = jax.random.normal(key, (pages, hkv, page_len, d), jnp.bfloat16)
    table = jnp.asarray(np.arange(slots * 8).reshape(slots, 8), jnp.int32)
    t = jnp.asarray([100, 64, 17, 120], jnp.int32)
    decode = jax.jit(lambda q, kp, t, tb: paged_decode_attention(q, kp, kp, t, tb))

    jax.block_until_ready((step(q, k, v, w), decode(qd, kp, t, table)))
    jax.profiler.start_trace(out)
    t0 = time.perf_counter()
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.step", step=i):
            jax.block_until_ready(step(q, k, v, w))
            time.sleep(0.01)
            jax.block_until_ready(decode(qd, kp, t, table))
    window = time.perf_counter() - t0
    jax.profiler.stop_trace()

    path = sorted(glob.glob(out + "/**/*.xplane.pb", recursive=True))[-1]
    shutil.copy(path, os.path.join("chiprun_out", "recorded_trace.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    report = {"window_s": window, "bytes": os.path.getsize(path), "planes": []}
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            names: dict = {}
            first = None
            n = 0
            for ev in line.events:
                n += 1
                names[ev.name] = names.get(ev.name, 0) + 1
                if first is None:
                    first = {"name": ev.name, "start_ns": ev.start_ns,
                             "duration_ns": ev.duration_ns,
                             "stats": [(str(a), str(b))[:2] for a, b in list(ev.stats)[:12]]}
            top = sorted(names.items(), key=lambda kv: -kv[1])[:40]
            lines.append({"line": line.name, "events": n, "first": first, "names": top})
        report["planes"].append({"plane": plane.name, "lines": lines})
    with open(os.path.join("chiprun_out", "trace_probe.json"), "w") as f:
        json.dump(report, f, indent=1)
    shutil.rmtree(out, ignore_errors=True)
    for p in report["planes"]:
        print(p["plane"], [(l["line"], l["events"]) for l in p["lines"]])
    return 0


if __name__ == "__main__":
    sys.exit(main())
