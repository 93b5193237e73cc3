"""The benchmark's own weights: made on the device from ``--seed`` in one
jitted call, by the GPT-2 convention the configuration files state. Neither
the program nor the plain reference makes a weight: both are handed these.

The canonical form stacks every per-layer leaf over the layers (the plain
reference scans over them); ``program_tree`` is the same numbers in the
layout ``zoo.transformer_lm`` keeps (a list of per-layer dictionaries).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def sizes(cfg: dict) -> dict:
    """Model sizes of a configuration file (its ``builder.kwargs``)."""
    kw = cfg["builder"]["kwargs"]
    d, h = kw["d_model"], kw["num_heads"]
    return {"vocab": kw["vocab_size"], "d": d, "heads": h, "d_head": d // h,
            "layers": kw["num_layers"], "ffn": kw["mlp_ratio"] * d,
            "positions": kw["max_len"]}


def seed_key(seed: int) -> jax.Array:
    """A key for any whole-number seed (the driver's pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _shapes(s: dict) -> dict:
    v, d, h, e, n, f, p = (s["vocab"], s["d"], s["heads"], s["d_head"],
                           s["layers"], s["ffn"], s["positions"])
    out_std = 0.02 / math.sqrt(2 * n)
    return {  # name: (shape, std) ; std None = constant
        "wte": ((v, d), 0.02), "wpe": ((p, d), 0.02),
        "wq": ((n, d, h, e), 0.02), "wk": ((n, d, h, e), 0.02),
        "wv": ((n, d, h, e), 0.02), "wo": ((n, h, e, d), out_std),
        "w1": ((n, d, f), 0.02), "w2": ((n, f, d), out_std),
        "head": ((d, v), 0.02),
        "ln1_g": ((n, d), 1.0), "ln1_b": ((n, d), 0.0),
        "ln2_g": ((n, d), 1.0), "ln2_b": ((n, d), 0.0),
        "b1": ((n, f), 0.0), "b2": ((n, d), 0.0),
        "lnf_g": ((d,), 1.0), "lnf_b": ((d,), 0.0),
    }


_RANDOM = ("wte", "wpe", "wq", "wk", "wv", "wo", "w1", "w2", "head")


def canonical(cfg: dict, key, served_dtype=None) -> dict:
    """The weights, stacked over layers, in float32. ``served_dtype`` rounds
    every matrix (two or more axes before stacking) to the type it is served
    in and widens it again, so both sides hold the same numbers."""
    out = {}
    for i, (name, (shape, std)) in enumerate(sorted(_shapes(sizes(cfg)).items())):
        if name in _RANDOM:
            w = std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            if served_dtype is not None:
                w = w.astype(served_dtype).astype(jnp.float32)
        else:
            w = jnp.full(shape, std, jnp.float32)
        out[name] = w
    return out


def program_tree(w: dict, served_dtype=None) -> list:
    """The canonical weights in ``zoo.transformer_lm``'s layout. With
    ``served_dtype`` matrices are stored in it (vectors stay float32), which
    is what ``ServingEngine`` would make of float32 parameters itself."""
    cast = (lambda a: a) if served_dtype is None else (lambda a: a.astype(served_dtype))
    layers = [{"embeddings": cast(w["wte"])}, {"embeddings": cast(w["wpe"])}]
    for i in range(w["wq"].shape[0]):
        layers.append({
            "norm1": {"scale": w["ln1_g"][i], "offset": w["ln1_b"][i]},
            "attn": {k: cast(w[k][i]) for k in ("wq", "wk", "wv", "wo")},
            "norm2": {"scale": w["ln2_g"][i], "offset": w["ln2_b"][i]},
            "mlp": {"w1": cast(w["w1"][i]), "b1": w["b1"][i],
                    "w2": cast(w["w2"][i]), "b2": w["b2"][i]},
        })
    layers.append({"scale": w["lnf_g"], "offset": w["lnf_b"]})
    layers.append({"kernel": cast(w["head"])})
    return layers


def make_program_params(cfg: dict, seed: int, served_dtype=None) -> list:
    """One jitted call: seed -> the program's parameter tree on the device."""
    return jax.jit(lambda k: program_tree(canonical(cfg, k, served_dtype),
                                          served_dtype))(seed_key(seed))


def make_canonical(cfg: dict, seed: int, served_dtype=None) -> dict:
    return jax.jit(lambda k: canonical(cfg, k, served_dtype))(seed_key(seed))
