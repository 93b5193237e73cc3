"""The general generator: one seed gives one stream, another seed another
order of the same sizes, and no prompt can match a cached one by part."""

import json
import os

import numpy as np

from harness import traffic as T

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


def mix(name, rehearse=False):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return T.effective(json.load(f), rehearse)


def stream(seed, n):
    g = T.ClosedLoopRequests(mix("chat-closed16"), 50257, seed)
    return g, [next(g) for _ in range(n)]


def test_same_seed_same_stream_other_seed_other_order():
    _, a = stream(2 ** 31 + 12345, 60)
    _, b = stream(2 ** 31 + 12345, 60)
    _, c = stream(7, 60)
    assert all(np.array_equal(p, q) and o == r for (p, o), (q, r) in zip(a, b))
    assert [len(p) for p, _ in a] != [len(p) for p, _ in c]
    # whole cycles (20 requests) hold the same sizes whatever the seed
    assert sorted(len(p) for p, _ in a) == sorted(len(p) for p, _ in c)
    assert sorted(o for _, o in a) == sorted(o for _, o in c)


def test_shares_lengths_and_templates():
    g, reqs = stream(3, 200)
    lengths = [len(p) for p, _ in reqs]
    assert {n: lengths.count(n) for n in set(lengths)} == {256: 80, 512: 60, 1024: 40, 1536: 20}
    outs = [o for _, o in reqs]
    assert min(outs) >= 16 and max(outs) <= 128 and 40 <= np.median(outs) <= 56
    templated = [p for p, _ in reqs
                 if any(np.array_equal(p[:128], t) for t in g.templates)]
    assert len(templated) == 100
    # the first id that is not shared is unique, so a match is a whole template or nothing
    firsts = [int(p[128]) for p in templated] + [
        int(p[0]) for p, _ in reqs if not any(p is q for q in templated)]
    assert len(set(firsts)) == len(firsts)
    assert not set(firsts) & {int(t[0]) for t in g.templates}
    assert g.shapes_possible() == [(n, t) for n in (256, 512, 1024, 1536) for t in (False, True)]


def test_train_rows_differ_and_repeat():
    m = mix("pretrain-s2048")
    x, y = T.train_rows(m, 50257, 2 ** 31 + 5)
    x2, _ = T.train_rows(m, 50257, 2 ** 31 + 5)
    x3, _ = T.train_rows(m, 50257, 6)
    assert x.shape == (16, 2048) and x.dtype == np.int32
    assert np.array_equal(x, x2) and not np.array_equal(x, x3)
    assert len({r.tobytes() for r in x}) == 16
    assert np.array_equal(y[:, :-1], x[:, 1:]) and np.array_equal(y[:, -1], x[:, 0])
