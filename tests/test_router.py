"""Horizontal serving tier (serving-router PR): the token-identity
oracle over replicated engines — requests scattered across replicas,
handed between prefill/decode pools, failed over after replica death
or drained under SLO pressure must produce byte-identical streams to a
single engine / ``generate()`` — plus lifecycle, placement-policy,
drain/shed, controller and per-engine record-separability coverage."""

import numpy as np
import pytest

from distkeras_tpu import obs
from distkeras_tpu.models import Model, zoo
from distkeras_tpu.models.decoding import generate
from distkeras_tpu.obs.recorder import get_recorder, reset_recorder
from distkeras_tpu.obs.slo import ttft_p99
from distkeras_tpu.resilience import faults
from distkeras_tpu.serving import (AdmissionRejected, EngineReplica,
                                   LeastLoaded, ReplicaState,
                                   ReplicaUnavailable, RequestState,
                                   Router, ServingEngine,
                                   ServingMetrics, SLOBurnController)

V, S = 29, 12
PATTERN = np.array([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8])


@pytest.fixture(scope="module")
def memorized_lm(pattern_lm):
    """The shared session-scoped overfit-PATTERN LM (conftest pattern_lm): huge greedy argmax margins keep token-identity assertions robust; trained once per test session."""
    return pattern_lm


def _engine(m, eid, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 32)
    return ServingEngine(m, engine_id=eid, **kw)


def _steps(router, n, out=None):
    """Advance ``n`` fleet steps, collecting {grid: Request}."""
    out = {} if out is None else out
    for _ in range(n):
        for g, req in router.step().items():
            out[g] = req
    return out


def _drive(router, warm_steps=0):
    """Collect {grid: Request} across manual steps + a full drain."""
    out = _steps(router, warm_steps)
    while router.pending:
        for g, req in router.step().items():
            out[g] = req
    return out


PROMPTS = [PATTERN[:4], PATTERN[:6], PATTERN[:3], PATTERN[:5],
           PATTERN[:7], PATTERN[:5]]
BUDGETS = [7, 5, 9, 6, 4, 8]


def _refs(m):
    return [generate(m, PROMPTS[i][None], max_new_tokens=BUDGETS[i],
                     temperature=0.0)[0] for i in range(len(PROMPTS))]


def _sampled_ref(m, prompt, budget, seed):
    eng = ServingEngine(m, num_slots=1, max_len=32)
    rid = eng.submit(prompt, budget, temperature=0.9, top_p=0.95,
                     seed=seed)
    return eng.run(max_steps=500)[rid]


# --- the oracle: routed == single engine == generate() ----------------------


def test_router_oracle_scattered_requests(memorized_lm):
    """Greedy + sampled requests scattered across 2 replicas (more
    requests than any one replica's slots, staggered arrivals): every
    stream byte-identical to the single-engine path."""
    m = memorized_lm
    r = Router([EngineReplica(_engine(m, "o0")),
                EngineReplica(_engine(m, "o1"))])
    grids = [r.submit(PROMPTS[i], BUDGETS[i]) for i in range(3)]
    out = _steps(r, 2)                  # in-flight before late arrivals
    grids += [r.submit(PROMPTS[i], BUDGETS[i]) for i in range(3, 6)]
    gs = r.submit(PATTERN[:5], 6, temperature=0.9, top_p=0.95, seed=5)
    out.update({g: req for g, req in _drive(r).items()})
    refs = _refs(m)
    for i, g in enumerate(grids):
        np.testing.assert_array_equal(out[g].tokens, refs[i])
    np.testing.assert_array_equal(
        out[gs].tokens, _sampled_ref(m, PATTERN[:5], 6, seed=5))
    # both replicas actually served traffic
    assert all(rep.engine.metrics.requests_finished > 0
               or rep.engine.metrics.requests_transferred > 0
               for rep in r.replicas)
    assert r.counters()["dispatched"] == 7


def test_router_run_returns_tokens_dict(memorized_lm):
    m = memorized_lm
    r = Router([EngineReplica(_engine(m, "t0")),
                EngineReplica(_engine(m, "t1"))])
    g = r.submit(PROMPTS[0], BUDGETS[0])
    out = r.run(max_steps=500)
    np.testing.assert_array_equal(out[g], _refs(m)[0])


def test_router_stream_matches_generate(memorized_lm):
    m = memorized_lm
    r = Router([EngineReplica(_engine(m, "st0"))])
    g = r.submit(PROMPTS[0], BUDGETS[0])
    toks = list(r.stream(g))
    np.testing.assert_array_equal(
        np.concatenate([PROMPTS[0], toks]), _refs(m)[0])


def test_prefix_affinity_routes_templates_apart(memorized_lm):
    """Two prompt templates through the affinity policy: repeats of a
    template land on the replica whose PrefixCache holds it (hit rate
    > 0 there), and the two templates end up on DIFFERENT replicas
    (the fleet partitions its cache capacity)."""
    m = memorized_lm
    r = Router([EngineReplica(_engine(m, "pa0", page_len=4)),
                EngineReplica(_engine(m, "pa1", page_len=4))],
               policy="prefix_affinity")
    t_a = np.tile(PATTERN, 2)[:8]
    t_b = np.tile(PATTERN[::-1], 2)[:8]
    homes = {}
    for kind, tpl in (("a", t_a), ("b", t_b)):
        for _ in range(3):
            g = r.submit(tpl, 4)
            homes.setdefault(kind, []).append(r._requests[g].replica)
            r.run(max_steps=500)   # drain so pages register
    # repeats stick to the first server of their template...
    assert len({rep.name for rep in homes["a"][1:]}) == 1
    assert len({rep.name for rep in homes["b"][1:]}) == 1
    # ...and the two templates live on different replicas
    assert homes["a"][1].name != homes["b"][1].name
    hit_rates = [rep.engine.metrics.prefix_hit_rate
                 for rep in r.replicas]
    assert any(hr is not None and hr > 0 for hr in hit_rates)
    # the affinity accessors themselves
    cache = homes["a"][1].engine.prefix
    key = cache.affinity_key(t_a)
    assert cache.probe(key) is not None and cache.probe(key) >= 1
    assert cache.probe(b"no-such-prefix") is None


def test_least_loaded_policy_order(memorized_lm):
    m = memorized_lm
    e0, e1 = _engine(m, "ll0"), _engine(m, "ll1")
    r0, r1 = EngineReplica(e0), EngineReplica(e1)
    r0.start(), r1.start()
    # load r0: one queued request (its queue is deeper)
    e0.submit(PROMPTS[0], 4)
    ranked = LeastLoaded().rank([r0, r1], PROMPTS[1])
    assert ranked[0] is r1


# --- replica death: mass failover, token-identical ---------------------------


def test_replica_kill_chaos_completes_token_identical(memorized_lm):
    """Kill a replica mid-flight (armed ``replica.die``): every
    in-flight request — greedy AND a sampled stream mid-decode —
    completes on the surviving replica byte-identically. The failover
    uses only the router's request log (host token mirror +
    seed-replayed sampling key), never dead-engine state."""
    m = memorized_lm
    try:
        r = Router([EngineReplica(_engine(m, "kc0")),
                    EngineReplica(_engine(m, "kc1"))])
        grids = [r.submit(PROMPTS[i], BUDGETS[i]) for i in range(4)]
        gs = r.submit(PATTERN[:5], 8, temperature=0.9, top_p=0.95,
                      seed=5)
        out = _steps(r, 4)              # streams decoding on both
        faults.inject("replica.die", nth=1)
        out.update(_drive(r))
        refs = _refs(m)
        for i, g in enumerate(grids):
            np.testing.assert_array_equal(out[g].tokens, refs[i])
        np.testing.assert_array_equal(
            out[gs].tokens, _sampled_ref(m, PATTERN[:5], 8, seed=5))
        dead = [x for x in r.replicas
                if x.state is ReplicaState.DEAD]
        assert len(dead) == 1
        assert r.counters()["failovers"] >= 1
        assert r.health()["status"] == "degraded"   # dead but serving
    finally:
        faults.reset()


def test_dead_replica_never_stepped_again(memorized_lm):
    m = memorized_lm
    try:
        r = Router([EngineReplica(_engine(m, "dd0")),
                    EngineReplica(_engine(m, "dd1"))])
        g = r.submit(PROMPTS[0], BUDGETS[0])
        faults.inject("replica.die", nth=1)
        out = _drive(r)
        dead = next(x for x in r.replicas
                    if x.state is ReplicaState.DEAD)
        steps_at_death = dead.steps
        assert out[g].state is RequestState.FINISHED
        assert dead.steps == steps_at_death
        with pytest.raises(Exception):
            dead.step()
    finally:
        faults.reset()


def test_router_dispatch_fault_leaves_router_consistent(memorized_lm):
    """An armed ``router.dispatch`` fault surfaces from submit()
    BEFORE any placement state mutates: the failed submit registers
    nothing, and the next submit works."""
    m = memorized_lm
    try:
        r = Router([EngineReplica(_engine(m, "df0"))])
        faults.inject("router.dispatch", nth=1)
        with pytest.raises(faults.InjectedFault):
            r.submit(PROMPTS[0], 4)
        assert not r.pending and not r._requests
        g = r.submit(PROMPTS[0], BUDGETS[0])
        out = r.run(max_steps=500)
        np.testing.assert_array_equal(out[g], _refs(m)[0])
    finally:
        faults.reset()


# --- disaggregated prefill/decode --------------------------------------------


def test_prefill_decode_handoff_oracle(memorized_lm):
    """Disaggregated pools: every stream prefills on the prefill-class
    replica, hands off at first token (token-identical re-prefill
    re-entry on the decode replica) and finishes byte-identical to the
    single-engine path — chunked prefill and a sampled stream
    included."""
    m = memorized_lm
    r = Router([EngineReplica(_engine(m, "hp0", prefill_chunk=3),
                              role="prefill"),
                EngineReplica(_engine(m, "hd0"), role="decode")])
    assert r.disaggregated
    grids = [r.submit(PROMPTS[i], BUDGETS[i]) for i in range(4)]
    gs = r.submit(PATTERN[:5], 6, temperature=0.9, top_p=0.95, seed=5)
    out = _drive(r)
    refs = _refs(m)
    for i, g in enumerate(grids):
        np.testing.assert_array_equal(out[g].tokens, refs[i])
    np.testing.assert_array_equal(
        out[gs].tokens, _sampled_ref(m, PATTERN[:5], 6, seed=5))
    assert r.counters()["handoffs"] == 5
    # the decode replica finished everything; prefill replica none
    pre, dec = r.replica("hp0"), r.replica("hd0")
    assert dec.engine.metrics.requests_finished == 5
    assert pre.engine.metrics.requests_finished == 0
    assert pre.engine.metrics.requests_transferred == 5


def test_transfer_roundtrip_mid_decode_token_identity(memorized_lm):
    """The engine-level handoff primitive on its own: detach a stream
    mid-decode (transfer_out) and adopt it on a second engine
    (transfer_in) — the continuation is byte-identical, sampled
    included."""
    m = memorized_lm
    src = _engine(m, "tr-src")
    dst = _engine(m, "tr-dst")
    rid_g = src.submit(PROMPTS[0], BUDGETS[0])
    rid_s = src.submit(PATTERN[:5], 8, temperature=0.9, top_p=0.95,
                       seed=5)
    finished = {}
    for _ in range(5):                   # both decoding, mid-stream
        for req in src.step():
            finished[req.rid] = req
    moved = {}
    for rid in (rid_g, rid_s):
        if rid in finished:
            continue
        req = src.transfer_out(rid)
        assert req is not None and req.state is RequestState.QUEUED
        moved[rid] = dst.transfer_in(req)
    while src.scheduler.pending or src._finish_buf:
        for req in src.step():
            finished[req.rid] = req
    res = {}
    while dst.scheduler.pending or dst._finish_buf:
        for req in dst.step():
            res[req.rid] = req
    np.testing.assert_array_equal(
        (finished.get(rid_g) or res[moved[rid_g]]).tokens, _refs(m)[0])
    np.testing.assert_array_equal(
        (finished.get(rid_s) or res[moved[rid_s]]).tokens,
        _sampled_ref(m, PATTERN[:5], 8, seed=5))


# --- drain semantics --------------------------------------------------------


def test_drain_sheds_and_finishes_inflight(memorized_lm):
    """A draining replica sheds new admissions with
    ``ReplicaUnavailable`` (an ``AdmissionRejected``) while its
    in-flight streams run to completion; the router routes new work
    around it; with the whole fleet draining the router itself
    sheds."""
    m = memorized_lm
    r = Router([EngineReplica(_engine(m, "dr0")),
                EngineReplica(_engine(m, "dr1"))],
               policy="least_loaded")
    g0 = r.submit(PROMPTS[0], BUDGETS[0])
    rep = r._requests[g0].replica
    for _ in range(3):
        r.step()                          # g0 decoding on rep
    rep.drain()
    with pytest.raises(AdmissionRejected):
        rep.submit(PROMPTS[1], 4)        # direct submit sheds
    g1 = r.submit(PROMPTS[1], BUDGETS[1])   # router routes around
    other = r._requests[g1].replica
    assert other is not rep
    out = _drive(r)
    np.testing.assert_array_equal(out[g0].tokens, _refs(m)[0])
    np.testing.assert_array_equal(out[g1].tokens, _refs(m)[1])
    assert rep.drained
    other.drain()
    with pytest.raises(AdmissionRejected):
        r.submit(PROMPTS[2], 4)           # fleet-wide shed
    rep.resume()
    g2 = r.submit(PROMPTS[2], BUDGETS[2])
    out = r.run(max_steps=1000)
    np.testing.assert_array_equal(out[g2], _refs(m)[2])


def test_rebalance_moves_queued_off_draining(memorized_lm):
    """Queued (not yet admitted) work on a draining replica moves to
    the rest of the fleet token-identically."""
    m = memorized_lm
    # 1-slot replicas: the second submit to a replica queues
    r = Router([EngineReplica(_engine(m, "rb0", num_slots=1)),
                EngineReplica(_engine(m, "rb1", num_slots=1))],
               policy="least_loaded")
    grids = [r.submit(PROMPTS[i], BUDGETS[i]) for i in range(4)]
    queued = [g for g in grids
              if r._requests[g].req.state is RequestState.QUEUED]
    assert queued
    victim = r._requests[queued[0]].replica
    victim.drain()
    moved = r.rebalance_queued(victim)
    assert moved >= 1
    assert r._requests[queued[0]].replica is not victim
    out = _drive(r)
    refs = _refs(m)
    for i, g in enumerate(grids):
        np.testing.assert_array_equal(out[g].tokens, refs[i])
    assert r.counters()["rebalanced"] == moved


# --- SLO-burn controller ----------------------------------------------------


def test_slo_burn_controller_drains_and_resumes(memorized_lm):
    """A replica breaching its TTFT objective (burn above the drain
    threshold) is drained by the controller; after its metrics window
    recovers (fresh window, clean samples) it resumes."""
    m = memorized_lm
    e0 = _engine(m, "slo0", slo=[ttft_p99(1e-9)])   # unmeetable
    e1 = _engine(m, "slo1")
    r = Router([EngineReplica(e0), EngineReplica(e1)],
               policy="least_loaded")
    ctl = SLOBurnController(r, drain_above=2.0, resume_below=1.0,
                            min_serving=1)
    # force traffic onto e0 so it records a breaching TTFT
    g = r.replica("slo0").submit(PROMPTS[0], 4)
    tr_req = e0[g]
    while tr_req.state is not RequestState.DECODING:
        e0.step()
    assert (e0.slo.evaluate(e0.metrics, record=False)["ttft_p99"]
            ["burn_rate"]) > 2.0
    actions = ctl.tick()
    assert actions.get("slo0") == "drain"
    assert r.replica("slo0").state is ReplicaState.DRAINING
    # still drains its in-flight stream
    while e0.scheduler.pending:
        e0.step()
    # recovery: a fresh metrics window has no bad samples
    e0.metrics = ServingMetrics()
    actions = ctl.tick()
    assert actions.get("slo0") == "resume"
    assert r.replica("slo0").state is ReplicaState.SERVING


def test_controller_respects_min_serving(memorized_lm):
    m = memorized_lm
    e0 = _engine(m, "ms0", slo=[ttft_p99(1e-9)])
    r = Router([EngineReplica(e0)], policy="least_loaded")
    ctl = SLOBurnController(r, min_serving=1)
    rid = r.replica("ms0").submit(PROMPTS[0], 4)
    e0.run(max_steps=500)
    assert ctl.tick() == {}              # lone replica never drained
    assert r.replica("ms0").state is ReplicaState.SERVING


# --- per-engine record separability (satellite regression) -------------------


def test_flight_recorder_records_separable_by_engine(memorized_lm):
    """With two live engines sharing the process-global ring, every
    serving record carries the engine id — the regression that ring
    entries from N engines interleave indistinguishably."""
    m = memorized_lm
    reset_recorder()
    try:
        rec = get_recorder()
        e0 = _engine(m, "sep0")
        e1 = _engine(m, "sep1")
        e0.submit(PROMPTS[0], 4)
        e1.submit(PROMPTS[1], 4)
        for _ in range(3):
            e0.step()
            e1.step()
        records = [rc for rc in rec.records()
                   if rc["kind"].startswith("serving.")]
        assert records
        engines = {rc.get("engine") for rc in records}
        assert engines == {"sep0", "sep1"}
        # separable: filtering by tag yields each engine's own stream
        for tag in ("sep0", "sep1"):
            own = [rc for rc in records if rc.get("engine") == tag]
            assert own
    finally:
        reset_recorder()


def test_tracer_timelines_tagged_with_engine(memorized_lm):
    """Each engine's tracer stamps its engine id on every summary (and
    the Chrome-trace track names), so two engines' rid-0 timelines
    stay distinguishable in cross-replica aggregations."""
    m = memorized_lm
    e0 = _engine(m, "tag0")
    e1 = _engine(m, "tag1")
    e0.submit(PROMPTS[0], 4)
    e1.submit(PROMPTS[1], 4)
    e0.run(max_steps=500)
    e1.run(max_steps=500)
    s0, s1 = e0.tracer.summaries(), e1.tracer.summaries()
    assert all(s["engine"] == "tag0" for s in s0.values())
    assert all(s["engine"] == "tag1" for s in s1.values())
    # same local rid on both engines, separable by the tag
    assert set(s0) & set(s1)
    names = [ev["args"]["name"]
             for ev in e0.tracer.chrome_trace()["traceEvents"]
             if ev.get("name") == "process_name"]
    assert any("tag0" in n for n in names)


def test_aggregate_serving_totals(memorized_lm):
    """obs.aggregate_serving: per-replica components keyed by engine id
    plus summed fleet totals."""
    m = memorized_lm
    e0 = _engine(m, "ag0")
    e1 = _engine(m, "ag1")
    e0.submit(PROMPTS[0], 4)
    e1.submit(PROMPTS[1], 5)
    e0.run(max_steps=500)
    e1.run(max_steps=500)
    agg = obs.aggregate_serving()
    assert "serving[ag0]" in agg["replicas"]
    assert "serving[ag1]" in agg["replicas"]
    both = (agg["replicas"]["serving[ag0]"]["requests_finished"]
            + agg["replicas"]["serving[ag1]"]["requests_finished"])
    assert agg["totals"]["requests_finished"] >= both >= 2
    assert agg["totals"]["tokens_generated"] >= 9


def test_router_telemetry_and_health_views(memorized_lm):
    m = memorized_lm
    r = Router([EngineReplica(_engine(m, "tv0")),
                EngineReplica(_engine(m, "tv1"))])
    g = r.submit(PROMPTS[0], BUDGETS[0])
    r.run(max_steps=500)
    h = r.health()
    assert h["status"] == "ok" and h["accepting"]
    assert set(h["replicas"]) == {"tv0", "tv1"}
    assert all(st["replica"] in ("tv0", "tv1")
               for st in h["replicas"].values())
    t = r.telemetry()
    assert t["states"] == {"tv0": "serving", "tv1": "serving"}
    assert t["router"]["dispatched"] == 1
    assert "totals" in t and "replicas" in t


# --- validation / lifecycle units -------------------------------------------


def test_replica_validation(memorized_lm):
    m = memorized_lm
    with pytest.raises(ValueError, match="role"):
        EngineReplica(_engine(m, "rv0"), role="verifier")
    with pytest.raises(ValueError, match="duplicate"):
        Router([EngineReplica(_engine(m, "x"), name="same"),
                EngineReplica(_engine(m, "y"), name="same")])
    with pytest.raises(ValueError, match="decode-capable"):
        Router([EngineReplica(_engine(m, "z"), role="prefill")])
    with pytest.raises(ValueError, match="policy"):
        Router([EngineReplica(_engine(m, "w"))], policy="round_robin")


def test_replica_unavailable_is_admission_rejected(memorized_lm):
    m = memorized_lm
    rep = EngineReplica(_engine(m, "un0"))
    assert rep.state is ReplicaState.STARTING
    with pytest.raises(AdmissionRejected):
        rep.submit(PROMPTS[0], 4)        # STARTING sheds too
    rep.start()
    assert rep.accepting
    rep.drain()
    with pytest.raises(ReplicaUnavailable):
        rep.submit(PROMPTS[0], 4)
    assert isinstance(ReplicaUnavailable("x", ReplicaState.DRAINING),
                      AdmissionRejected)
