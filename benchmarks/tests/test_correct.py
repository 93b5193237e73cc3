"""``correct`` has to come out false where it should. These tests skip the
harness's look for a chip and drive the rest of a run (``run.execute``) at the
rehearsal size on the CPU, with the cell's own limits, once sound and once for
each fault the cell can have, planted in the program underneath: a step that
returns its state unchanged, half of the batch left out with the mean taken over
the rest, a token altered where it is produced. The controls (the next precision
down, in the program's place) have to fail too."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

import run as bench_run
from conftest import ROOT
from harness import check, traffic as T, weights

TRAIN, SERVE = "train-cgpt256m-1chip", "serve-cgpt1.3b-chat-closed16"

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)


def execute(workload, seed, *extra):
    args = bench_run.parse(["--workload", workload, "--seed", str(seed),
                            "--seconds", "0.5", "--trace", "0", "--rehearse", *extra])
    return bench_run.execute(BENCHMARK, args, jax.devices())


def numbers(line):
    return {c["name"]: c["value"] for c in line["compared"]}


@pytest.mark.parametrize("workload", [TRAIN, SERVE])
def test_sound_run_is_correct(workload):
    line = execute(workload, 2 ** 31 + 21)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert "metrics" not in line and line["rehearsal"] is True
    assert list(line)[-1] == "compared" and all(c["ok"] for c in line["compared"])


def _break_train_step(monkeypatch, how):
    from distkeras_tpu.parallel import trainers
    real = trainers.make_train_step

    def broken(*a, **kw):
        step = real(*a, **kw)
        if how == "state_unchanged":
            return lambda carry, batch: (carry, step(carry, batch)[1])
        return lambda carry, batch: step(carry, jax.tree_util.tree_map(
            lambda b: b[: b.shape[0] // 2], batch))

    monkeypatch.setattr(trainers, "make_train_step", broken)


@pytest.mark.parametrize("how", ["state_unchanged", "half_batch"])
def test_broken_train_step_is_not_correct(monkeypatch, how):
    _break_train_step(monkeypatch, how)
    line = execute(TRAIN, 22)
    assert line["correct"] is False
    if how == "state_unchanged":          # nothing moved: all three read 1
        for name in ("grad_gap", "update_gap", "update_err"):
            assert numbers(line)[name] == pytest.approx(1.0)


def test_altered_token_is_not_correct(monkeypatch):
    from distkeras_tpu.serving import engine
    real = engine.decode_step_slots_paged

    def altered(*a, **kw):
        out = real(*a, **kw)
        return (jnp.roll(out[0], 1, axis=-1),) + tuple(out[1:])

    monkeypatch.setattr(engine, "decode_step_slots_paged", altered)
    line = execute(SERVE, 23)
    assert line["correct"] is False
    assert numbers(line)["served_gap_mean"] > 10 * check.load_limits(
        bench_run.HERE, SERVE, True)["served_gap_mean"]["limit"]


def test_train_control_is_not_correct():
    """The reference with int8 products, put in the program's place: it
    fails ``update_err``, the number a lower precision moves, and no other."""
    _, cfg, traffic = bench_run.load_cell(BENCHMARK, TRAIN, True)
    x, y = T.train_rows(traffic, weights.sizes(cfg)["vocab"], 24)
    ref = check.reference_observed(cfg, traffic, x, y, 24)
    control = check.reference_observed(cfg, traffic, x, y, 24, precision="int8")
    got = check.train_numbers(control, ref)
    got.pop("_where")
    ok, compared = check.verdict(got, check.load_limits(bench_run.HERE, TRAIN, True))
    assert not ok
    assert [c["name"] for c in compared if not c["ok"]] == ["update_err"]


def test_serve_control_is_not_correct():
    """The reference in float8, put in the program's place at the served
    positions (at this size int8 reads like the program; on the chip both
    controls fail, see ``limits/``)."""
    line = execute(SERVE, 25, "--control", "ref-fp8")
    assert line["correct"] is False
