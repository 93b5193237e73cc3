"""Dispatched (capacity-based, sort/scatter) MoE vs the masked-dense
oracle: exactness at sufficient capacity, drop semantics, expert-parallel
paths (replicated-token slice + token-sharded all_to_all), and the
compute-sparsity claim asserted via XLA cost analysis."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from distkeras_tpu.compat import shard_map

from distkeras_tpu.models.moe import MoE, moe_all_to_all
from distkeras_tpu.ops import moe_kernels


def _run_ctx(dispatch):
    """Execution context per dispatch mode: the fused path needs the
    Pallas interpreter on the CPU test backend (otherwise it would
    silently measure its tokens fallback — see moe_kernels)."""
    if dispatch == "fused":
        return moe_kernels.force_interpret()
    import contextlib
    return contextlib.nullcontext()


def _program_flops(moe, params, x):
    """XLA cost-analysis FLOPs of the jitted apply (per-device program
    when the inputs carry GSPMD shardings)."""
    f = jax.jit(lambda p, xx: moe.apply(p, {}, xx)[0])
    return f.lower(params, x).compile().cost_analysis()["flops"]


def _mk(e=8, d=16, hid=32, k=2, **kw):
    moe = MoE(e, hid, top_k=k, **kw)
    params, state, _ = moe.init(jax.random.PRNGKey(0), (4, d))
    return moe, params, state


@pytest.mark.parametrize("dispatch", ["dense", "tokens", "fused"])
@pytest.mark.parametrize("top_k", [1, 2, 4])
def test_dispatched_matches_dense_when_capacity_sufficient(top_k, dispatch):
    e, d = 8, 16
    dense, params, _ = _mk(e=e, d=d, k=top_k)
    disp = MoE(e, 32, top_k=top_k, dispatch=dispatch,
               capacity_factor=float(e) / top_k)  # capacity >= N: no drops
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, d))
    ref, _ = dense.apply(params, {}, x)
    with _run_ctx(dispatch):
        out, _ = disp.apply(params, {}, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_dispatched_drops_over_capacity_choice_major():
    """With capacity 1 per expert, each expert serves exactly its first
    arriving slot; all first choices outrank all second choices."""
    e, d = 4, 8
    moe = MoE(e, 16, top_k=2, dispatch="tokens", capacity_factor=1e-9)
    params, _, _ = moe.init(jax.random.PRNGKey(2), (4, d))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 6, d))
    assert moe._capacity(6) == 1
    out, _ = moe.apply(params, {}, x)
    assert np.isfinite(np.asarray(out)).all()
    # total kept slots <= E * capacity
    dense, = [MoE(e, 16, top_k=2)]
    ref, _ = dense.apply(params, {}, x)
    assert not np.allclose(np.asarray(out), np.asarray(ref))


def test_dispatched_expert_parallel_matches_dense(devices):
    n = len(devices)
    mesh = Mesh(np.array(devices), ("expert",))
    e, d = 2 * n, 8
    dense = MoE(e, 16, top_k=2)
    disp_ep = MoE(e, 16, top_k=2, dispatch="tokens",
                  capacity_factor=float(e) / 2, expert_axis_name="expert")
    params, _, _ = dense.init(jax.random.PRNGKey(4), (4, d))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 4, d))
    ref, _ = dense.apply(params, {}, x)

    ep_fn = shard_map(
        lambda p, xx: disp_ep.apply(p, {}, xx)[0],
        mesh=mesh,
        in_specs=({"gate": P(), "w1": P("expert"), "b1": P("expert"),
                   "w2": P("expert"), "b2": P("expert")}, P()),
        out_specs=P())
    out = jax.jit(ep_fn)(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_moe_all_to_all_token_sharded_matches_dense(devices):
    """Token-sharded EP: batch sharded over the SAME axis as experts, the
    GShard all_to_all exchange. Generous capacity -> must equal dense."""
    n = len(devices)
    mesh = Mesh(np.array(devices), ("ep",))
    e, d = 2 * n, 8
    dense = MoE(e, 16, top_k=2)
    disp = MoE(e, 16, top_k=2, dispatch="tokens",
               capacity_factor=float(e) / 2)
    params, _, _ = dense.init(jax.random.PRNGKey(6), (4, d))
    x = jax.random.normal(jax.random.PRNGKey(7), (n * 2, 4, d))
    ref, _ = dense.apply(params, {}, x)

    a2a = shard_map(
        lambda p, xx: moe_all_to_all(disp, p, xx, axis_name="ep")[0],
        mesh=mesh,
        in_specs=({"gate": P(), "w1": P("ep"), "b1": P("ep"),
                   "w2": P("ep"), "b2": P("ep")}, P("ep")),
        out_specs=P("ep"))
    out = jax.jit(a2a)(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_dispatched_expert_flops_proportional_to_topk():
    """The economics claim: dispatched per-step FLOPs ~ top_k/E of the
    masked-dense path's (XLA cost analysis on the jitted apply)."""
    e, d, hid, k = 8, 128, 512, 2
    x = jax.random.normal(jax.random.PRNGKey(8), (4, 256, d))
    dense = MoE(e, hid, top_k=k)
    disp = MoE(e, hid, top_k=k, dispatch="tokens", capacity_factor=1.0)
    params, _, _ = dense.init(jax.random.PRNGKey(9), (256, d))

    fd = _program_flops(dense, params, x)
    fs = _program_flops(disp, params, x)
    # expert matmuls dominate at this size; allow routing/scatter overhead
    assert fs < fd * (k / e + 0.15), (fs, fd, fs / fd)


@pytest.mark.parametrize("dispatch", ["dense", "tokens", "fused"])
def test_dispatched_trains_and_grads_flow(dispatch):
    e, d = 4, 16
    moe = MoE(e, 32, top_k=2, dispatch=dispatch, capacity_factor=2.0)
    params, _, _ = moe.init(jax.random.PRNGKey(10), (8, d))
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 8, d))

    def loss(p):
        out, _ = moe.apply(p, {}, x, training=True)
        return jnp.sum(jnp.square(out))

    with _run_ctx(dispatch):
        g = jax.grad(loss)(params)
    flat = jax.tree_util.tree_leaves(g)
    assert all(np.isfinite(np.asarray(t)).all() for t in flat)
    # every expert weight gets gradient signal at generous capacity
    assert float(jnp.abs(g["w1"]).sum()) > 0
    assert float(jnp.abs(g["gate"]).sum()) > 0


def test_expert_unroll_warns_and_falls_back_under_gspmd_sharding(devices):
    """Round-6 runtime guard (ADVICE r5): expert_unroll=True with
    GSPMD-sharded stacked expert weights warns and takes the batched
    expert dot instead of paying per-expert cross-shard resharding."""
    from jax.sharding import NamedSharding

    n = len(devices)
    mesh = Mesh(np.array(devices), ("ep",))
    e, d = 2 * n, 16
    moe_u = MoE(e, 32, top_k=2, dispatch="tokens", capacity_factor=2.0,
                expert_unroll=True)
    moe_ref = MoE(e, 32, top_k=2, dispatch="tokens", capacity_factor=2.0,
                  expert_unroll=False)
    params, _, _ = moe_u.init(jax.random.PRNGKey(30), (8, d))
    spec = {"gate": P(), "w1": P("ep"), "b1": P("ep"),
            "w2": P("ep"), "b2": P("ep")}
    sharded = {kk: jax.device_put(v, NamedSharding(mesh, spec[kk]))
               for kk, v in params.items()}
    x = jax.random.normal(jax.random.PRNGKey(31), (2, 8, d))
    ref, _ = moe_ref.apply(params, {}, x)
    with pytest.warns(UserWarning, match="expert_unroll"):
        out, _ = moe_u.apply(sharded, {}, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    # replicated weights don't trigger the guard
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        moe_u.apply(params, {}, x)


def test_expert_unroll_warns_at_spec_derivation_under_ep(devices):
    """The eager guard above cannot fire inside a jitted SPMD train step
    (tracers carry no sharding), so the GSPMD path warns where concrete
    config meets the expert axis: param_specs at trainer setup."""
    from distkeras_tpu.models import Sequential
    from distkeras_tpu.parallel.sharding import param_specs

    n = len(devices)
    mesh = Mesh(np.array(devices), ("ep",))
    e, d = 2 * n, 16
    moe_u = MoE(e, 32, top_k=2, dispatch="tokens", expert_unroll=True)
    params, _, _ = moe_u.init(jax.random.PRNGKey(32), (8, d))
    module = Sequential([moe_u])
    with pytest.warns(UserWarning, match="expert_unroll"):
        param_specs(module, [params], mesh, tp_axis=None, ep_axis="ep")
    # no expert axis in play -> silent
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        param_specs(module, [params], mesh, tp_axis=None, ep_axis=None)


def test_dispatch_config_roundtrip():
    moe = MoE(4, 8, dispatch="tokens", capacity_factor=1.5)
    cfg = moe.get_config()
    assert cfg["dispatch"] == "tokens" and cfg["capacity_factor"] == 1.5
    moe2 = MoE(**cfg)
    assert moe2.dispatch == "tokens"
    with pytest.raises(ValueError, match="dispatch"):
        MoE(4, 8, dispatch="bogus")


def test_dispatched_ep_per_device_flops_under_gspmd(devices):
    """Expert-parallel compute sparsity end to end: on an 8-way ep mesh
    with GSPMD-sharded expert weights, the PER-DEVICE program FLOPs of
    the dispatched path must be a small fraction of the dense path's
    (dense-EP already divides by A; dispatch must further cut top_k/E)."""
    from jax.sharding import NamedSharding

    n = len(devices)
    mesh = Mesh(np.array(devices), ("ep",))
    e, d, hid, k = 2 * n, 128, 512, 2
    x = jax.random.normal(jax.random.PRNGKey(20), (4, 256, d))
    dense = MoE(e, hid, top_k=k)
    # expert_unroll=False: the GSPMD contract (round 5) — unrolled
    # per-expert slicing of a sharded stacked axis defeats partitioning
    disp = MoE(e, hid, top_k=k, dispatch="tokens", capacity_factor=1.0,
               expert_unroll=False)
    params, _, _ = dense.init(jax.random.PRNGKey(21), (256, d))
    shard = {"gate": P(), "w1": P("ep"), "b1": P("ep"),
             "w2": P("ep"), "b2": P("ep")}
    sharded = {kk: jax.device_put(v, NamedSharding(mesh, shard[kk]))
               for kk, v in params.items()}

    fd = _program_flops(dense, sharded, x)
    fs = _program_flops(disp, sharded, x)
    assert fs < fd * (k / e + 0.2), (fs, fd, fs / fd)
