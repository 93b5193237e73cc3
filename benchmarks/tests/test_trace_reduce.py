"""``harness/trace_reduce.py`` on a small trace recorded on a TPU v5e by
``record_trace.py``: three calls of a program holding the three flash kernels
and a matrix product, each followed by a 10 ms sleep and one call of the paged
decode kernel, inside ``bench.step`` host spans."""

import os

import pytest

from harness import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(__file__), "recorded_trace.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return tr.load(TRACE)


def test_planes_and_lines(trace):
    assert trace.chips == 1
    assert len(trace.device_ops["/device:TPU:0"]) == 78
    assert len(trace.device_programs["/device:TPU:0"]) == 6
    assert any(name == "bench.step" for _, _, name in trace.host_spans)


def test_union_and_busy(trace):
    assert tr.union([(0, 10, "a"), (5, 20, "b"), (30, 40, "c")]) == [[0, 20], [30, 40]]
    ops = trace.device_ops["/device:TPU:0"]
    busy = tr.busy_seconds(trace)
    summed = sum(b - a for a, b, _ in ops) / 1e9
    span = (max(b for _, b, _ in ops) - min(a for a, _, _ in ops)) / 1e9
    assert 0 < busy <= summed          # async copies overlap compute: union < sum
    assert busy < 0.02 * span          # three 10 ms sleeps: the device sat idle
    assert busy == pytest.approx(221.3e-6, rel=0.01)
    assert tr.program_seconds(trace) >= busy


def test_clip_cuts_the_window(trace):
    ops = trace.device_ops["/device:TPU:0"]
    mid = (min(a for a, _, _ in ops) + max(b for _, b, _ in ops)) / 2
    assert tr.busy_seconds(trace, t1=mid) + tr.busy_seconds(trace, t0=mid) \
        == pytest.approx(tr.busy_seconds(trace))


def test_kernel_time_by_name(trace):
    names = ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
             "paged_decode_attention", "not_a_kernel"]
    got = tr.kernel_seconds(trace, names)
    assert set(got) == set(names) - {"not_a_kernel"}     # nothing read: left out
    assert all(n == 3 for _, n in got.values())
    assert got["flash_fwd"][0] == pytest.approx(54.8e-6, rel=0.01)
    assert got["flash_bwd_dq"][0] < got["flash_bwd_dkv"][0]   # two names, not one


def test_breakdown(trace):
    top = tr.top_ops(trace, 3)
    assert [k for k, _ in top][:2] == ["jvp_flash_fwd", "transpose_jvp_flash_bwd_dkv"]
    gaps = tr.idle_gaps(trace)
    assert gaps[0][0] == "bench.step" and gaps[0][1] > 0.03   # the sleeps
    assert tr.op_kind("%fusion.12 = f32[8]{0} fusion(...)") == "fusion"
