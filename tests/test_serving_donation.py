"""KV-cache donation (PR 27): every serving program that takes a KV
cache and returns its successor DONATES it — one pool on the device,
written in place — and ``decode_logits()`` is the one program that
keeps its pool. The word in ``health()["programs"]`` (``kv_cache=
donated`` / ``kv_cache=kept``) is tied to the fact here: the compiled
program's ``input_output_alias`` covers every cache leaf, the arrays
passed in are deleted and their buffers come back in the result.

The CPU backend honours donation (a donated array reads
``is_deleted()`` and the result reuses its buffer), so a handle kept
across a donating call fails here and not first on the chip."""

import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models.decoding import generate
from distkeras_tpu.resilience import faults
from distkeras_tpu.serving import (DraftModel, NgramDraft, RequestState,
                                   ServingEngine, kv_pool)

pytestmark = pytest.mark.filterwarnings(
    "error:Some donated buffers were not usable")

PATTERN = np.array([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8])
REP = np.tile(PATTERN, 3)
#: plain / int8 / int4 pools (``None``: the model's compute dtype)
per_pool_dtype = pytest.mark.parametrize(
    "cache_dtype", [None, "int8", "int4"], ids=["plain", "int8", "int4"])


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


def _pointers(leaves):
    return sorted(s.data.unsafe_buffer_pointer()
                  for leaf in leaves for s in leaf.addressable_shards)


def _aliased(compiled) -> int:
    """Entries of the compiled program's ``input_output_alias``."""
    head = compiled.as_text().split("\n", 1)[0]
    m = re.search(r"input_output_alias=\{(.*?)\}, entry_computation",
                  head)
    return 0 if m is None else len(
        re.findall(r"(?:may|must)-alias", m.group(1)))


class _Spy:
    """Stands where one jitted serving program stood and checks every
    call of it: the argument at ``donates`` (None: no argument) is the
    only one lowered as donated, the compiled program aliases each of
    its leaves to an output, after the call its arrays are deleted and
    the result sits in their buffers; every other argument lives on."""

    def __init__(self, fn, name, donates, log):
        self.fn, self.name, self.donates, self.log = fn, name, donates, log
        self._compiled = set()

    def __getattr__(self, attr):          # _cache_size, lower, ...
        return getattr(self.fn, attr)

    def __call__(self, *args):
        given = [] if self.donates is None else _leaves(args[self.donates])
        kept = [leaf for i, a in enumerate(args) if i != self.donates
                for leaf in _leaves(a) if isinstance(leaf, jax.Array)]
        shapes = tuple((a.shape, str(a.dtype)) for a in _leaves(args)
                       if hasattr(a, "shape"))
        if shapes not in self._compiled:
            self._compiled.add(shapes)
            lowered = self.fn.lower(*args)
            flags = [[i.donated for i in _leaves(info)]
                     for info in lowered.args_info[0]]
            for i, fl in enumerate(flags):
                assert all(fl) if i == self.donates else not any(fl), \
                    (self.name, i, fl)
            assert _aliased(lowered.compile()) == len(given), self.name
        before = _pointers(given)
        out = self.fn(*args)
        assert all(leaf.is_deleted() for leaf in given), self.name
        assert not any(leaf.is_deleted() for leaf in kept), self.name
        after = set(_pointers(_leaves(out)))
        assert all(p in after for p in before), self.name
        self.log[self.name] = self.log.get(self.name, 0) + 1
        return out


@pytest.fixture()
def spied(monkeypatch):
    """Every serving program behind a ``_Spy``; yields the log
    ``{program: calls that passed the checks}``."""
    log = {}
    jit_serving = ServingEngine._jit_serving

    def engine_program(self, f, n_args, name, keep_attn=False,
                       donate_cache=True):
        fn = jit_serving(self, f, n_args, name, keep_attn=keep_attn,
                         donate_cache=donate_cache)
        return _Spy(fn, name.split("[")[0],
                    2 if donate_cache else None, log)

    monkeypatch.setattr(ServingEngine, "_jit_serving", engine_program)
    for name, donates in (("_write_pages", 0),
                          ("_scatter_rows", 0), ("_load_pages", 0),
                          ("_gather_rows", None)):
        monkeypatch.setattr(kv_pool, name, _Spy(getattr(kv_pool, name),
                                                name, donates, log))
    for name, method in (("draft_prefill", "_prefill_fn"),
                         ("draft_decode", "_decode_fn")):
        def build(self, *a, _orig=getattr(DraftModel, method), _n=name):
            fn = _orig(self, *a)
            return fn if isinstance(fn, _Spy) else _Spy(fn, _n, 2, log)
        monkeypatch.setattr(DraftModel, method, build)
    return log


def _serve(m, waves, **engine_kw):
    """Drain each wave of requests (prompt, budget, submit kwargs)
    through one engine; greedy streams must equal ``generate()``."""
    eng = ServingEngine(m, num_slots=2, max_len=40, **engine_kw)
    for wave in waves:
        rids = [eng.submit(p, n, **kw) for p, n, kw in wave]
        out = eng.run(max_steps=2000)
        for rid, (p, n, kw) in zip(rids, wave):
            if not kw:
                np.testing.assert_array_equal(
                    out[rid], generate(m, p[None], n, temperature=0.0)[0])
    return eng


GREEDY = [(REP[:13], 14, {}), (REP[:14], 12, {})]
SAMPLED = [(REP[:6], 12, {"temperature": 0.9, "seed": 3})]
SPECULATED = [(REP[:12], 8, {"temperature": 0.7, "seed": 5,
                             "speculate": True})]

#: scenario -> (engine options, waves, programs it must reach)
SCENARIOS = {
    "paged": (dict(page_len=4), [GREEDY, SAMPLED],
              ["decode_greedy", "decode_sampled", "prefill",
               "_write_pages", "_load_pages"]),
    "fused": (dict(page_len=4, fuse_steps=4), [GREEDY, SAMPLED],
              ["decode_fused_greedy", "decode_fused_sampled"]),
    "verify": (dict(page_len=4, draft=NgramDraft(), spec_k=3),
               [GREEDY, SPECULATED], ["verify_greedy", "verify_sampled"]),
    "tree": (dict(page_len=4, spec_k=2, spec_tree=True, spec_width=2),
             [GREEDY, SPECULATED],
             ["verify_tree_greedy", "verify_tree_sampled",
              "draft_prefill", "draft_decode"]),
    "weight_quant": (dict(page_len=4, weight_quant="int8"), [GREEDY],
                     ["decode_greedy", "prefill"]),
    "offload": (dict(page_len=4, num_pages=8, prefix_cache=False,
                     host_kv_pages=16),
                [[(REP[:5], 16, {}), (REP[:6], 15, {})]],
                ["_gather_rows", "_scatter_rows"]),
}


@per_pool_dtype
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_every_program_aliases_its_cache(pattern_lm, spied, scenario,
                                         cache_dtype):
    """(a) Each program the scenario reaches ran behind a ``_Spy``
    (donated flags, ``input_output_alias`` over every cache leaf,
    deleted inputs, reused buffers — the marker leaf and the scale
    planes included), no donation went unused, and the engine says
    ``kv_cache=donated`` for each of its own."""
    options, waves, programs = SCENARIOS[scenario]
    if "spec_tree" in options:
        options = dict(options, draft=DraftModel(pattern_lm, page_len=4))
    eng = _serve(pattern_lm, waves, cache_dtype=cache_dtype, **options)
    for name in programs:
        assert spied.get(name, 0) >= 1, (name, spied)
    said = eng.health()["programs"]
    assert said and all("kv_cache=donated" in v for v in said.values())
    assert set(said) >= {p for p in programs if p[0] != "_"
                         and not p.startswith("draft_")}
    if scenario == "offload":
        assert eng.metrics.requests_preempted >= 1
        assert eng.pool.pages_restored >= 1


def test_expert_parallel_programs_donate_under_shard_map(
        pattern_moe_lm, spied, devices):
    """``jit(shard_map(f))`` under ``ep_mesh`` donates argument 2 like
    the plain programs: every shard of every cache leaf is given away
    and comes back."""
    from jax.sharding import Mesh
    from distkeras_tpu.models import Model, zoo
    m = Model.build(
        zoo.transformer_lm(29, d_model=32, num_heads=4, num_layers=2,
                           mlp_ratio=2, use_rope=True, moe_every=1,
                           num_experts=8, moe_expert_axis="expert"),
        (12,), seed=2).replace(params=pattern_moe_lm.params,
                               state=pattern_moe_lm.state)
    eng = ServingEngine(m, num_slots=2, max_len=32, page_len=4,
                        ep_mesh=Mesh(np.array(devices), ("expert",)))
    rid = eng.submit(PATTERN[:5], 6)
    np.testing.assert_array_equal(
        eng.run(max_steps=500)[rid],
        generate(pattern_moe_lm, PATTERN[None, :5], 6,
                 temperature=0.0)[0])
    assert spied["decode_greedy"] >= 1 and spied["prefill"] >= 1
    assert all("kv_cache=donated" in v
               for v in eng.health()["programs"].values())


@per_pool_dtype
def test_step_deletes_the_old_pool_and_reuses_its_buffers(pattern_lm,
                                                          cache_dtype):
    """(b) One pool on the device: across ``step()`` the leaves held
    from before are deleted and the new ones sit where they sat."""
    eng = ServingEngine(pattern_lm, num_slots=2, max_len=32, page_len=4,
                        cache_dtype=cache_dtype)
    rid = eng.submit(PATTERN[:5], 12)
    while not eng.scheduler.running:
        eng.step()
    for _ in range(3):
        old = _leaves(eng.pool.cache)
        where = _pointers(old)
        eng.step()
        assert all(leaf.is_deleted() for leaf in old)
        assert _pointers(_leaves(eng.pool.cache)) == where
    np.testing.assert_array_equal(
        eng.run(max_steps=500)[rid],
        generate(pattern_lm, PATTERN[None, :5], 12, temperature=0.0)[0])


def test_decode_logits_keeps_the_pool(pattern_lm, spied):
    """(c) ``decode_logits()`` is the one program compiled without
    donation: it says so, the pool survives it, and the engine then
    serves what an engine that never called it serves."""
    m = pattern_lm

    def drive(peek):
        eng = ServingEngine(m, num_slots=2, max_len=32, page_len=4)
        rids = [eng.submit(PATTERN[:4], 7), eng.submit(PATTERN[:6], 5)]
        while len(eng.scheduler.running) < 2:
            eng.step()
        if peek:
            pool = _leaves(eng.pool.cache)
            where = _pointers(pool)
            first = eng.decode_logits()
            np.testing.assert_array_equal(first, eng.decode_logits())
            assert not any(leaf.is_deleted() for leaf in pool)
            assert _pointers(_leaves(eng.pool.cache)) == where
        out = eng.run(max_steps=500)
        return eng, [out[r] for r in rids]

    eng, peeked = drive(True)
    assert spied["decode_logits"] == 2
    said = eng.health()["programs"]
    assert "kv_cache=kept" in said["decode_logits[None,None]"]
    assert [k for k, v in said.items() if "kv_cache=donated" not in v] \
        == ["decode_logits[None,None]"]
    for a, b in zip(peeked, drive(False)[1]):
        np.testing.assert_array_equal(a, b)


def test_offload_snapshot_survives_donating_steps(pattern_lm):
    """(d) preempt -> offload -> donating steps -> restore: the
    ``_gather_rows`` snapshot is a buffer of its own that the steps in
    between neither delete nor change, and the restored pages hold its
    bytes."""
    m = pattern_lm
    eng = ServingEngine(m, num_slots=2, max_len=32, page_len=4,
                        num_pages=8, prefix_cache=False, host_kv_pages=16)
    r0 = eng.submit(REP[:5], 16)
    eng.step()
    eng.step()
    r1 = eng.submit(REP[:6], 15)
    done, snap, steps_between, restored = {}, None, 0, []
    restore_pages = eng.pool.restore_pages

    def restore(host_ids, dev_ids):
        restore_pages(host_ids, dev_ids)
        ids = np.asarray(dev_ids)
        restored.append((list(host_ids), jax.tree_util.tree_map(
            lambda a: np.array(a)[ids], eng.pool.cache)))

    eng.pool.restore_pages = restore
    while eng.scheduler.pending:
        pending = eng.pool._pending_host
        if pending and snap is None:
            snap = (pending[0], jax.tree_util.tree_map(
                lambda a: np.array(a, copy=True), pending[0]["dev"]))
        before = _leaves(eng.pool.cache)
        for r in eng.step():
            done[r.rid] = r
        if snap is not None and not restored \
                and before[0].is_deleted():
            steps_between += 1
            for kept, was in zip(_leaves(snap[0]["dev"]),
                                 _leaves(snap[1])):
                assert not kept.is_deleted()
                np.testing.assert_array_equal(np.asarray(kept), was)
    assert eng.metrics.requests_preempted >= 1 and steps_between >= 1
    hids, pages = restored[0]
    assert hids == snap[0]["hids"]
    for got, was in zip(_leaves(pages), _leaves(snap[1])):
        np.testing.assert_array_equal(got, was)
    for rid, p, n in ((r0, REP[:5], 16), (r1, REP[:6], 15)):
        np.testing.assert_array_equal(
            done[rid].tokens, generate(m, p[None], n, temperature=0.0)[0])


def test_prefill_fault_leaves_pool_and_staging_alive(pattern_lm):
    """(e) ``faults.point("serving.prefill")`` fires before any
    dispatch: the poisoned request is cancelled with pool and staging
    cache alive, and the next request is served as ``generate()``
    serves it."""
    m = pattern_lm
    eng = ServingEngine(m, num_slots=1, max_len=32, page_len=4)
    held = _leaves(eng.pool.cache) + _leaves(eng._staging)
    faults.inject("serving.prefill", nth=1, error=ValueError("bad"))
    try:
        bad = eng.submit(PATTERN[:4], 4)
        (req,) = eng.step()
    finally:
        faults.reset()
    assert req.rid == bad and req.state is RequestState.CANCELLED
    assert not any(leaf.is_deleted() for leaf in held)
    assert _leaves(eng.pool.cache)[0] is held[0]
    ok = eng.submit(PATTERN[:5], 6)
    np.testing.assert_array_equal(
        eng.run(max_steps=500)[ok],
        generate(m, PATTERN[None, :5], 6, temperature=0.0)[0])


def _fails_once_dispatched(build):
    """``build`` with programs that run (consuming the donated cache)
    and then raise, as a fault on the device would."""
    def wrapped(*key):
        fn = build(*key)

        def run(*args):
            fn(*args)
            raise RuntimeError("device fault")
        return run
    return wrapped


def test_prefill_failure_after_dispatch_rebuilds_staging(pattern_lm,
                                                         monkeypatch):
    """A prefill that fails once dispatched has consumed the donated
    staging cache: the request is cancelled, the staging cache is
    rebuilt, and the next request is served as ``generate()`` serves
    it."""
    m = pattern_lm
    eng = ServingEngine(m, num_slots=1, max_len=32, page_len=4)
    with monkeypatch.context() as mp:
        mp.setattr(eng, "_prefill_fn",
                   _fails_once_dispatched(eng._prefill_fn))
        bad = eng.submit(PATTERN[:4], 4)
        (req,) = eng.step()
    assert req.rid == bad and req.state is RequestState.CANCELLED
    assert not any(leaf.is_deleted() for leaf in _leaves(eng._staging))
    ok = eng.submit(PATTERN[:5], 6)
    np.testing.assert_array_equal(
        eng.run(max_steps=500)[ok],
        generate(m, PATTERN[None, :5], 6, temperature=0.0)[0])


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_lost_pool_fails_loudly(pattern_lm, monkeypatch, phase):
    """A program that takes the donated pool with it (the decode step,
    or ``_write_pages`` at the end of a prefill) leaves nothing to
    serve from: ``step()`` says so, in both phases, instead of
    cancelling request after request on "Array has been deleted"."""
    eng = ServingEngine(pattern_lm, num_slots=2, max_len=32, page_len=4)
    eng.submit(PATTERN[:4], 6)
    if phase == "decode":
        while not eng.scheduler.running:
            eng.step()
        monkeypatch.setattr(eng, "_decode_fn",
                            _fails_once_dispatched(eng._decode_fn))
    else:
        monkeypatch.setattr(kv_pool, "_write_pages",
                            _fails_once_dispatched(
                                lambda: kv_pool._write_pages)())
    with pytest.raises(RuntimeError, match="donated KV pool") as e:
        eng.step()
    assert "device fault" in str(e.value.__cause__)


def test_pool_programs_donate_by_name():
    """The pool's own programs keep the names the benchmark reads
    (``prefill_share.serve``: ``_write_pages``, ``_load_pages``) and a
    kept handle fails loudly, not quietly."""
    pool = {"k": jnp.zeros((4, 2, 4, 8))}
    for name in ("_write_pages", "_load_pages",
                 "_scatter_rows", "_gather_rows"):
        assert getattr(kv_pool, name).__name__ == name
    new = kv_pool._scatter_rows(pool, jnp.asarray([1]),
                                {"k": jnp.ones((1, 2, 4, 8))})
    assert float(new["k"][1].min()) == 1.0
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(pool["k"])
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        rows = kv_pool._gather_rows(new, jnp.asarray([1]))
    assert not new["k"].is_deleted() and rows["k"].shape[0] == 1


@pytest.mark.parametrize("dtype,rows", [(jnp.float32, 4), (jnp.int8, 8),
                                        (jnp.int8, 2)],
                         ids=["plain", "int8", "int4-packed"])
def test_page_rows_write_equals_the_two_index_scatter(dtype, rows):
    """``_write_page_rows`` (one scatter of rows into the flattened
    plane, in place on the chip) lands the bytes ``plane.at[pp, :,
    off].set(vals, mode="drop")`` lands, the dropped sentinel page
    included."""
    from distkeras_tpu.models.decoding import _write_page_rows
    rs = np.random.RandomState(rows)
    n, h, d, s = 6, 3, 8, 5
    plane = jnp.asarray(rs.randint(-90, 90, (n, h, rows, d)), dtype)
    vals = jnp.asarray(rs.randint(-90, 90, (s, h, d)), dtype)
    pp = jnp.asarray([4, 0, n, 2, n], jnp.int32)      # n: the sentinel
    off = jnp.asarray(rs.randint(0, rows, s), jnp.int32)
    want = plane.at[pp, :, off].set(vals, mode="drop")
    got = jax.jit(_write_page_rows)(plane, pp, off, vals)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.array_equal(np.asarray(got), np.asarray(plane))
    # a scale plane [N, H, rows] takes the same write, and the int4
    # pool's read-modify-write reads rows the same way
    from distkeras_tpu.models.decoding import _read_page_rows
    got = jax.jit(_write_page_rows)(plane[..., 0], pp, off, vals[..., 0])
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want[..., 0]))
    gp = jnp.clip(pp, 0, n - 1)
    np.testing.assert_array_equal(
        np.asarray(_read_page_rows(plane, gp, off)),
        np.asarray(plane[gp, :, off]))
