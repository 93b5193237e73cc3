"""The one general traffic generator: it reads a mix's parameters from
``traffic/<mix>.json`` and makes inputs from ``--seed``.

Every seed gets the same multiset of sizes in another order (and other token
ids), so that the seed does not change the work: sizes come in cycles that
hold each prompt length in exactly its stated share and the output lengths at
evenly spaced quantiles of their distribution; the seed shuffles each cycle.
Length/template synthesis follows ``distkeras_tpu/serving/loadgen.py::
synthesize`` in idea (lengths, shared templates); the wall clock is the
driver's, not the generator's.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def effective(traffic: dict, rehearse: bool) -> dict:
    """The mix's parameters, with its ``rehearse`` overrides for a CPU
    rehearsal at a tiny size (one level of nesting is merged)."""
    out = {k: v for k, v in traffic.items() if k != "rehearse"}
    if rehearse:
        for k, v in traffic.get("rehearse", {}).items():
            out[k] = {**out[k], **v} if isinstance(v, dict) and k in out else v
    return out


def train_rows(traffic: dict, vocab: int, seed: int, chips: int = 1):
    """``(features, labels)`` of one epoch: rows that all differ, every
    position's label the next token (the last one wraps)."""
    rows = traffic["steps_per_epoch"] * traffic["sequences_per_chip_step"] * chips
    x = np.random.default_rng(seed).integers(
        0, vocab, (rows, traffic["seq_len"]), dtype=np.int32)
    return x, np.roll(x, -1, axis=1)


def _output_lengths(spec: dict, n: int) -> list:
    """``n`` lengths at evenly spaced quantiles of the stated distribution."""
    if spec["distribution"] != "lognormal":
        raise ValueError(f"unknown output distribution {spec['distribution']!r}")
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = round(spec["median"] * math.exp(spec["sigma"] * z))
        out.append(int(min(max(v, spec["min"]), spec["max"])))
    return out


def _cycle(traffic: dict) -> list:
    """One cycle of ``(prompt_len, templated, output_len)`` in a fixed order:
    the smallest number of requests that holds every prompt length in its
    share, half of each (``template_share``) behind a template."""
    weights = traffic["prompt_weights"]
    share = traffic["template_share"]
    n = 1
    while not all(abs(w * n * share - round(w * n * share)) < 1e-9
                  and abs(w * n - round(w * n)) < 1e-9 for w in weights):
        n += 1
        if n > 4096:
            raise ValueError("prompt weights and template share have no common cycle")
    shapes = []
    for length, w in zip(traffic["prompt_lengths"], weights):
        k = round(w * n)
        t = round(w * n * share)
        shapes += [(length, i < t) for i in range(k)]
    return shapes


class ClosedLoopRequests:
    """An endless, seeded stream of requests ``(prompt ids, output_len)``.

    Templated prompts start with one of ``templates`` fixed runs of
    ``template_len`` ids. The first id that is not shared (position 0, or
    the one after the template) is unique in the stream, so a prefix cache can
    match a prompt by a whole template or not at all and the set of prefill
    shapes stays the one that set-up warmed."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.rng = np.random.default_rng(seed)
        self.vocab = vocab
        self.tlen = traffic["template_len"]
        self.templates = self.rng.integers(
            0, vocab, (traffic["templates"], self.tlen), dtype=np.int32)
        taken = set(int(t[0]) for t in self.templates)
        self.unique = iter([int(i) for i in self.rng.permutation(vocab)
                            if int(i) not in taken])
        self.shapes = _cycle(traffic)
        self.outputs = _output_lengths(traffic["output"], len(self.shapes))
        self.queue: list = []
        self.made = 0

    def shapes_possible(self) -> list:
        """Every ``(prompt_len, templated)`` the stream can produce."""
        return sorted(set(self.shapes))

    def make(self, length: int, templated: bool, output_len: int, template=None):
        first = next(self.unique, None)
        if first is None:
            raise RuntimeError("traffic: out of unique ids; vocabulary too small")
        body = self.rng.integers(0, self.vocab, length, dtype=np.int32)
        if templated:
            t = int(self.rng.integers(len(self.templates))) if template is None else template
            body[:self.tlen] = self.templates[t]
            body[self.tlen] = first
        else:
            body[0] = first
        self.made += 1
        return body, int(output_len)

    def __next__(self):
        if not self.queue:
            order = self.rng.permutation(len(self.shapes))
            outs = self.rng.permutation(self.outputs)
            self.queue = [(self.shapes[i], int(o)) for i, o in zip(order, outs)]
        (length, templated), out = self.queue.pop()
        return self.make(length, templated, out)
