"""``correct`` for a block-diffusion cell: what the timed path fixed, pass by
pass, against the plain reference (``reference/sdar.py``) at full width.

The engine keeps for every generated token the denoising pass of its block
that fixed it. From that, for a seeded sample of the whole blocks of a sample
of finished requests, every denoising state of the block is rebuilt (the
earlier blocks hold their final tokens, the block holds mask tokens where a
later pass fixed the token) and the reference's logits at the block's
positions are read by a full forward pass over the sequence up to the block
(padded with mask tokens, which no row of the block sees, to the next of a
few lengths). Two numbers, both of logits, not of tokens:

* ``served_gap_mean``: how far the fixed token's logit lies under the
  reference's best at that position, mean over the fixed tokens checked;
* ``position_gap_mean``: how far the fixed position's log-confidence (its best
  logit less the log-sum-exp) lies under that of the reference's choice among
  the still-masked positions (with ``n`` positions fixed in a pass: under the
  ``n``-th most confident), mean over the same tokens.

With ``control`` both are read instead for what a planted fault would have
fixed in each of the same states: a precision (``"int8"``) puts the reference
at that lower precision in the program's place (its own most confident
positions and tokens); ``"position"`` keeps the reference's tokens and turns
the selection rule round (the LEAST confident masked positions are fixed).
Every run also reads, at no further forward pass, what that and a rule that
ignores confidence (the first masked positions) would have read: ``_where``
carries them beside the limits' own numbers.
"""

from __future__ import annotations

import gc

import numpy as np

from harness import sdar_family
from reference import sdar


def block_states(prompt, served, fixed_pass, block_len: int, steps: int):
    """``[(block_start, [(state tokens [B], masked [B] bool, fixed in this
    pass [positions in block])])]`` for every WHOLE generated block of one
    request: the block as it stood before each denoising pass."""
    p = len(prompt)
    seq = np.concatenate([np.asarray(prompt, np.int64),
                          np.asarray(served, np.int64)])
    passes = np.concatenate([np.full(p, -1), np.asarray(fixed_pass)])
    out = []
    for start in range(p // block_len * block_len,
                       len(seq) - block_len + 1, block_len):
        span = slice(start, start + block_len)
        states = []
        for k in range(steps):
            masked = passes[span] >= k
            fixed = np.nonzero(passes[span] == k)[0]
            if len(fixed):
                states.append((masked, fixed))
        out.append((start, seq[span], states))
    return out


def _kth_best(conf, masked, n):
    return np.sort(np.where(masked, conf, -np.inf))[::-1][n - 1]


#: a state's sequence is padded to the next multiple of this (and at most
#: to the cell's longest): a few compiled shapes, and no forward pass over
#: 1,408 positions for a block at position 200
PAD_STEP = 256


def serve_numbers(cfg, seed, sample, engine_kw, n_blocks, pad_to,
                  control=None):
    """The two means over ``sample`` (``[(prompt, served, fixed_pass)]``):
    ``n_blocks`` whole blocks of each request, drawn from the seed, every
    denoising state of each."""
    s = sdar_family.sizes(cfg)
    rcfg = sdar_family.reference_cfg(s)
    b, mask = s["block_len"], engine_kw["mask_token"]
    steps = engine_kw.get("denoising_steps") or b
    w = sdar_family.reference_tree(sdar_family.make_leaves(cfg, seed), s)
    rng = np.random.default_rng(seed)
    pad_to = -(-pad_to // b) * b
    # per fixed token: (served gap, position gap) of the program, of the
    # control, and the position gap of the two planted selection rules
    program, planted, least, first = [], [], [], []
    states_read = 0
    for prompt, served, fixed_pass in sample:
        blocks = block_states(prompt, served, fixed_pass, b, steps)
        picks = rng.permutation(len(blocks))[:n_blocks]
        for start, final, states in (blocks[i] for i in sorted(picks)):
            seq = np.full(min(pad_to, -(-(start + b) // PAD_STEP) * PAD_STEP),
                          mask, np.int64)
            seq[:start] = np.concatenate([prompt, served])[:start]
            span = np.arange(start, start + b)
            for masked, fixed in states:
                seq[span] = np.where(masked, mask, final)
                lg = np.asarray(sdar.logits_at(w, rcfg, seq, span))
                best, conf = sdar.confidences(lg)
                states_read += 1
                n = len(fixed)
                kth = _kth_best(conf, masked, n)
                gaps = lambda pos, tok: [
                    (lg[p].max() - lg[p, t], max(kth - conf[p], 0.0))
                    for p, t in zip(pos, tok)]
                program += gaps(fixed, final[fixed])
                turned = np.argsort(np.where(masked, conf, np.inf),
                                    kind="stable")[:n]
                least += gaps(turned, best[turned])
                in_order = np.nonzero(masked)[0][:n]
                first += gaps(in_order, best[in_order])
                if control == "position":
                    planted += gaps(turned, best[turned])
                elif control is not None:
                    low = np.asarray(sdar.logits_at(w, rcfg, seq, span,
                                                    precision=control))
                    low_best, low_conf = sdar.confidences(low)
                    order = np.argsort(np.where(masked, -low_conf, np.inf),
                                       kind="stable")[:n]
                    planted += gaps(order, low_best[order])
    del w
    gc.collect()
    if not program:
        return {}
    means = lambda pairs: [float(v) for v in np.mean(pairs, axis=0)]
    compared = np.array(program if control is None else planted)
    where = {"requests": len(sample), "states": states_read,
             "tokens": len(compared),
             "widest_served_gap": float(compared[:, 0].max()),
             "widest_position_gap": float(compared[:, 1].max()),
             "argmax_agreement": float(np.mean(np.array(program)[:, 0] == 0)),
             "least_confident_position_gap": means(least)[1],
             "first_masked_position_gap": means(first)[1]}
    if control is not None:
        where["program"] = dict(zip(("served", "position"), means(program)))
    served_gap, position_gap = means(compared)
    return {"served_gap_mean": served_gap, "position_gap_mean": position_gap,
            "_where": where}
