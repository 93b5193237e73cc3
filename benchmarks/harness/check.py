"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, each number beside a limit of its own.

Training: the first epoch program's steps (its per-step losses, and Adam's
first moment and the parameters' change as the carry holds them once that
program has run) against the reference following the same steps from the
same weights and rows. Serving: the logit gap of every served token of a
sample of finished requests, under the reference's full forward pass.
Which numbers have a limit, and the readings behind it: ``limits/<cell>.json``.
"""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights
from reference import gpt

def load_limits(root: str, workload: str, rehearse: bool = False) -> dict:
    """The cell's limits; for a CPU rehearsal its ``rehearse_limits``, where
    the tiny model reads otherwise than the real one."""
    with open(os.path.join(root, "limits", workload + ".json")) as f:
        spec = json.load(f)
    return spec.get("rehearse_limits", spec["limits"]) if rehearse else spec["limits"]


def verdict(numbers: dict, limits: dict):
    """``(correct, compared)``: every limit needs its number, every number
    has to be finite and at or under its limit. ``compared`` lists each number
    beside its limit; it is also printed as the last lines of stderr."""
    compared, ok = [], True
    for name, spec in limits.items():
        value = numbers.get(name)
        good = value is not None and np.isfinite(value) and value <= spec["limit"]
        ok = ok and bool(good)
        compared.append({"name": name,
                         "value": None if value is None else float(value),
                         "limit": spec["limit"], "ok": bool(good)})
    for c in compared:
        print(f"check {c['name']}: {c['value']} (limit {c['limit']}) "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    return ok, compared


# --- training --------------------------------------------------------------

SAMPLE = 4096


def sample(a):
    """Up to ``SAMPLE`` evenly spaced elements of one leaf, in float32."""
    flat = a.reshape(-1)
    return flat[::max(1, flat.size // SAMPLE)][:SAMPLE].astype(jnp.float32)


def _by_leaf(tree: dict) -> dict:
    """Canonical (layer-stacked) tree -> ``{"wq/3": leaf, "wte": leaf}``."""
    out = {}
    for name, a in tree.items():
        if name in gpt.PER_LAYER:
            out.update({f"{name}/{i}": a[i] for i in range(a.shape[0])})
        else:
            out[name] = a
    return out


def leaf_norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))


@jax.jit
def _reference_readings(moment, change):
    tm = jax.tree_util.tree_map
    return {"moment": tm(leaf_norm, _by_leaf(moment)),
            "change": tm(leaf_norm, _by_leaf(change)),
            "change_samples": tm(sample, _by_leaf(change))}


def program_leaves(tree: list) -> dict:
    """The program's tree (any per-leaf values) -> the same names."""
    t = jax.device_get(tree)
    out = {"wte": t[0]["embeddings"], "wpe": t[1]["embeddings"],
           "lnf_g": t[-2]["scale"], "lnf_b": t[-2]["offset"],
           "head": t[-1]["kernel"]}
    for i, layer in enumerate(t[2:-2]):
        flat = {"ln1_g": layer["norm1"]["scale"], "ln1_b": layer["norm1"]["offset"],
                "ln2_g": layer["norm2"]["scale"], "ln2_b": layer["norm2"]["offset"],
                **layer["attn"], **layer["mlp"]}
        out.update({f"{k}/{i}": v for k, v in flat.items()})
    return out


def reference_observed(cfg, traffic, x, y, seed, precision="float32", rows=None):
    """What the reference makes of the first epoch program: per-step losses,
    and once its steps are done per-leaf norms of Adam's first moment
    (``moment``) and of the parameters' change (``change``), and a sample of
    each leaf's change. ``rows`` plants the half-batch fault."""
    steps, batch = traffic["steps_per_epoch"], x.shape[0] // traffic["steps_per_epoch"]
    lr = float(traffic["optimizer_kwargs"]["learning_rate"])
    w = weights.make_canonical(cfg, seed)
    opt = gpt.adam_init(w)
    xs = jnp.asarray(x).reshape(steps, batch, -1)
    ys = jnp.asarray(y).reshape(steps, batch, -1)
    losses = []
    for i in range(steps):
        w, opt, l = gpt.train_step(w, opt, xs[i], ys[i], lr=lr,
                                   precision=precision, rows=rows)
        losses.append(float(l))
    w0 = weights.make_canonical(cfg, seed)
    change = jax.tree_util.tree_map(jnp.subtract, w, w0)
    return {"losses": losses,
            **jax.device_get(_reference_readings(opt["m"], change))}


def _worst_gap(got: dict, ref: dict, keep=None):
    """Worst leaf: gap between the two norms over the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    median = float(np.median([float(v) for v in ref.values()]))
    worst, where = 0.0, None
    for name, r in ref.items():
        r = float(r)
        if keep is not None and name not in keep:
            continue
        gap = abs(float(got[name]) - r) / max(r, median)
        if not np.isfinite(gap):
            return float("inf"), name
        if gap > worst:
            worst, where = gap, name
    return worst, where


def _sample_error(got: dict, ref: dict) -> float:
    """Relative error of the sampled elements, all leaves pooled: the norm of
    the difference over the reference's norm. Unlike a gap of norms it sees
    noise that leaves the norms alone, which is what a lower precision adds."""
    err = sum(float(np.sum(np.square(got[k] - ref[k]))) for k in ref)
    return float(np.sqrt(err / sum(float(np.sum(np.square(v))) for v in ref.values())))


def train_numbers(observed: dict, ref: dict) -> dict:
    """The numbers compared for a training cell, and where the worst leaf is.
    Leaves whose reference gradient (Adam's first moment here) is under a
    thousandth of the median leaf's move by round-off alone under Adam and are
    left out of ``update_gap``. ``update_err`` is the one number that a
    lower precision moves (``_sample_error`` of the parameters' change)."""
    lo, lr = np.asarray(observed["losses"]), np.asarray(ref["losses"])
    n = min(len(lo), len(lr))
    m_ref = ref["moment"]
    median_m = float(np.median([float(v) for v in m_ref.values()]))
    moved = {k for k, v in m_ref.items() if float(v) >= 1e-3 * median_m}
    grad_gap, grad_at = _worst_gap(observed["moment"], m_ref)
    upd_gap, upd_at = _worst_gap(observed["change"], ref["change"], keep=moved)
    return {"loss_gap": float(np.max(np.abs(lo[:n] - lr[:n]) / np.abs(lr[:n]))),
            "grad_gap": grad_gap, "update_gap": upd_gap,
            "update_err": _sample_error(observed["change_samples"],
                                        ref["change_samples"]),
            "_where": {"grad_gap": grad_at, "update_gap": upd_at,
                       "left_out": sorted(set(m_ref) - moved)}}


# --- serving ---------------------------------------------------------------

def _gap_readings(gaps) -> dict:
    gaps = np.concatenate(gaps)
    return {"widest": float(gaps.max()), "mean": float(gaps.mean()),
            "tokens": int(gaps.size), "argmax_agreement": float((gaps == 0).mean())}


def serve_numbers(cfg, seed, sample, pad_to, max_out, precision="float32",
                  control=None):
    """``served_gap``: the widest gap by which a served token's logit lies
    below the reference's best, over every served token of ``sample``
    (``[(prompt ids, served ids)]``); ``served_gap_mean``: the mean of those
    gaps. With ``control`` (a precision) the gaps are read instead for the
    token that the lower precision puts first at each of the same positions."""
    w = weights.make_canonical(cfg, seed, served_dtype=jnp.bfloat16)
    sound, low_gaps = [], []
    for prompt, served in sample:
        seq = np.zeros(pad_to, np.int32)
        n = len(served)
        seq[:len(prompt)] = prompt
        seq[len(prompt):len(prompt) + n - 1] = served[:-1]
        pos = np.minimum(len(prompt) - 1 + np.arange(max_out), len(prompt) + n - 2)
        seq, pos = jnp.asarray(seq), jnp.asarray(pos)
        ref = np.asarray(gpt.served_logits(w, seq, pos, precision=precision))[:n]
        sound.append(ref.max(-1) - ref[np.arange(n), np.asarray(served)])
        if control is not None:
            low = np.asarray(gpt.served_logits(w, seq, pos, precision=control))[:n]
            low_gaps.append(ref.max(-1) - ref[np.arange(n), low.argmax(-1)])
    where = {"requests": len(sample), **_gap_readings(sound)}
    if control is not None:
        where = {"requests": len(sample), **_gap_readings(low_gaps), "program": where}
    return {"served_gap": where["widest"], "served_gap_mean": where["mean"],
            "_where": where}
