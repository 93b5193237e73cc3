"""Tensor/expert-parallel sharding rules: params pytree -> PartitionSpec tree.

The reference has no tensor parallelism of any kind (SURVEY §2.3: TP is
"absent in the reference" — dist-keras workers each hold a FULL model
replica). This module is the TPU-native capability ADD that makes models
larger than one chip's HBM trainable: it walks a ``models.core.Layer`` tree
and produces a ``PartitionSpec`` pytree mirroring the params/opt-state
pytrees, which the ``SPMDTrainer`` (``parallel/spmd.py``) turns into
``NamedSharding``s for ``jax.jit`` — XLA's GSPMD partitioner then inserts
the all-gathers/reduce-scatters over ICI automatically (scaling-book recipe:
pick a mesh, annotate shardings, let XLA place collectives).

Rules follow the Megatron-LM column→row convention so that, within one
transformer block, GSPMD needs exactly two collectives per residual branch:

  * attention: wq/wk/wv shard the HEADS axis (column-parallel), wo shards
    its heads INPUT axis (row-parallel) → one psum after wo;
  * MLP: w1 column-parallel [d, hidden/tp], w2 row-parallel [hidden/tp, d]
    → one psum after w2;
  * MoE: experts shard the EXPERT axis (expert parallelism); gate stays
    replicated. w1/w2 may additionally shard hidden on tp;
  * Embedding / final Dense head: shard the model/vocab dim.

A dimension is only sharded when the mesh axis divides it; otherwise the
rule degrades to replicated for that dim (never an error — small models on
big meshes just replicate).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Pytree = Any


def _axis_size(mesh: Mesh, axis) -> int:
    """Total size of a (possibly tuple) mesh-axis spec entry."""
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        size = 1
        for a in axis:
            size *= mesh.shape[a]
        return size
    return mesh.shape[axis]


class ShardingRules:
    """Produces a PartitionSpec pytree for a module's params/state.

    ``tp_axis``/``ep_axis`` name mesh axes (or None to disable). ``fsdp_axis``
    optionally ZeRO-shards otherwise-replicated large kernels along their
    biggest divisible dim (fully-sharded data parallelism over the data
    axis — params are all-gathered by GSPMD just-in-time per layer).
    """

    def __init__(self, mesh: Mesh, tp_axis: Optional[str] = "tp",
                 ep_axis: Optional[str] = None,
                 fsdp_axis: Optional[str] = None,
                 min_fsdp_size: int = 2 ** 16):
        def present(a):
            return a if a is not None and a in mesh.shape else None
        self.mesh = mesh
        self.tp = present(tp_axis)
        self.ep = present(ep_axis)
        self.fsdp = present(fsdp_axis)
        self.min_fsdp_size = int(min_fsdp_size)

    # -- helpers -----------------------------------------------------------
    def _fits(self, axis, dim: int) -> bool:
        return axis is not None and dim % _axis_size(self.mesh, axis) == 0

    def _tp(self, dim: int):
        return self.tp if self._fits(self.tp, dim) else None

    def _ep(self, dim: int):
        return self.ep if self._fits(self.ep, dim) else None

    def _maybe_fsdp(self, spec: P, shape: Tuple[int, ...]) -> P:
        """Shard the largest still-replicated dim over the fsdp axis."""
        if self.fsdp is None or not shape:
            return spec
        import numpy as np
        if int(np.prod(shape)) < self.min_fsdp_size:
            return spec
        entries = list(spec) + [None] * (len(shape) - len(spec))
        cands = [(shape[i], i) for i, e in enumerate(entries)
                 if e is None and self._fits(self.fsdp, shape[i])]
        if not cands:
            return spec
        _, i = max(cands)
        entries[i] = self.fsdp
        return P(*entries)

    # -- per-layer rules ---------------------------------------------------
    def specs_for(self, layer, params: Pytree) -> Pytree:
        """PartitionSpec tree mirroring ``params`` of ``layer``."""
        name = type(layer).__name__
        rule = getattr(self, f"_rule_{name}", None)
        if rule is not None:
            return rule(layer, params)
        return self._generic(layer, params)

    def _generic(self, layer, params):
        """Containers: recurse by matching param keys to child-layer attrs.
        Leaves with no rule: replicated (+ optional fsdp)."""
        from distkeras_tpu.models.core import Layer, Sequential

        if isinstance(layer, Sequential) and isinstance(params, (list, tuple)):
            return [self.specs_for(l, p)
                    for l, p in zip(layer.layers, params)]
        if isinstance(params, dict) and layer is not None:
            out = {}
            for key, sub in params.items():
                child = getattr(layer, key, None)
                if isinstance(child, Layer):
                    out[key] = self.specs_for(child, sub)
                else:
                    out[key] = self._replicated(sub)
            return out
        return self._replicated(params)

    def _replicated(self, tree):
        return jax.tree_util.tree_map(
            lambda x: self._maybe_fsdp(P(), x.shape), tree)

    # Dense [in, units]: column-parallel on units (head matmuls / generic
    # projections). GSPMD reshards activations between mismatched layers.
    def _rule_Dense(self, layer, params):
        out = {}
        if "kernel" in params:
            units = params["kernel"].shape[-1]
            tp = self._tp(units)
            out["kernel"] = self._maybe_fsdp(P(None, tp),
                                             params["kernel"].shape)
        if "bias" in params:
            out["bias"] = P(self._tp(params["bias"].shape[-1]))
        return out

    # Conv2D [kh, kw, cin, cout]: shard output channels.
    def _rule_Conv2D(self, layer, params):
        out = {}
        if "kernel" in params:
            cout = params["kernel"].shape[-1]
            tp = self._tp(cout)
            out["kernel"] = self._maybe_fsdp(P(None, None, None, tp),
                                             params["kernel"].shape)
        if "bias" in params:
            out["bias"] = P(self._tp(params["bias"].shape[-1]))
        return out

    # Embedding [vocab, d]: shard the model dim (keeps the token gather
    # local; the d-shards concatenate for free downstream).
    def _rule_Embedding(self, layer, params):
        d = params["embeddings"].shape[-1]
        return {"embeddings": self._maybe_fsdp(
            P(None, self._tp(d)), params["embeddings"].shape)}

    def _rule_PositionalEmbedding(self, layer, params):
        d = params["embeddings"].shape[-1]
        return {"embeddings": P(None, self._tp(d))}

    # MHA: wq/wk/wv [d, H, Dh] column-parallel on heads; wo [H, Dh, d]
    # row-parallel on heads (Megatron split — one psum per attention).
    # GQA: wk/wv carry only kv_heads heads, so their shard decision uses
    # THEIR head count — tp > kv_heads degrades those two to replicated
    # (never an error), while wq/wo still shard on the full head axis.
    def _rule_MultiHeadAttention(self, layer, params):
        tp_q = self._tp(params["wq"].shape[1])
        tp_kv = self._tp(params["wk"].shape[1])
        out = {
            "wq": self._maybe_fsdp(P(None, tp_q, None), params["wq"].shape),
            "wk": self._maybe_fsdp(P(None, tp_kv, None),
                                   params["wk"].shape),
            "wv": self._maybe_fsdp(P(None, tp_kv, None),
                                   params["wv"].shape),
            "wo": self._maybe_fsdp(P(tp_q, None, None), params["wo"].shape),
        }
        # per-head q/k norm scales [Dh]: replicated
        out.update({k: P() for k in ("q_norm", "k_norm") if k in params})
        return out

    # Transformer MLP: w1 [d, hidden] column, w2 [hidden, d] row.
    def _rule_TransformerMLP(self, layer, params):
        hidden = params["w1"].shape[-1]
        tp = self._tp(hidden)
        specs = {
            "w1": self._maybe_fsdp(P(None, tp), params["w1"].shape),
            "b1": P(tp),
            "w2": self._maybe_fsdp(P(tp, None), params["w2"].shape),
            "b2": P(),
        }
        if "w3" in params:                # gated: the second column half
            specs["w3"] = self._maybe_fsdp(P(None, tp), params["w3"].shape)
        return {k: v for k, v in specs.items() if k in params}

    # MoE: expert-parallel on the expert axis; hidden additionally tp-sharded
    # (the column→row split inside each expert).
    def _rule_MoE(self, layer, params):
        e = params["w1"].shape[0]
        hidden = params["w1"].shape[-1]
        ep, tp = self._ep(e), self._tp(hidden)
        if ep is not None and getattr(layer, "expert_unroll", False):
            # Warn HERE, at spec-derivation time (trainer setup), because
            # this is where layer config and expert-axis sharding meet on
            # concrete values: inside the jitted train step the layer's
            # own guard sees only tracers (no .sharding) and cannot fire,
            # so the unroll WILL run there and pay per-expert cross-shard
            # resharding collectives every step.
            import warnings
            warnings.warn(
                "MoE(expert_unroll=True) with GSPMD expert-axis sharding "
                f"(axis {self.ep!r}): per-expert slices of the "
                "expert-sharded stacked weights force cross-shard "
                "resharding collectives every step. Set "
                "expert_unroll=False for GSPMD expert parallelism, or "
                "use shard_map EP (expert_axis_name) where the unroll "
                "is safe.", stacklevel=2)
        specs = {
            "gate": P(),
            "w1": P(ep, None, tp),
            "b1": P(ep, tp),
            "w2": P(ep, tp, None),
            "b2": P(ep, None),
            "w3": P(ep, None, tp),        # gated experts' up-projection
        }
        return {k: v for k, v in specs.items() if k in params}

    # Remat is a transparent wrapper: its params ARE the inner layer's
    def _rule_Remat(self, layer, params):
        return self.specs_for(layer.inner, params)

    # LSTM/GRU: wx [in, G*units], wh [units, G*units] — gate blocks make
    # naive column sharding wrong across the gate boundary UNLESS units is
    # divisible: [*, G*units] with units % tp == 0 shards each gate block
    # identically, which is exactly the valid column-parallel split.
    def _rule_LSTM(self, layer, params):
        units = params["wh"].shape[0]
        tp = self._tp(units)
        return {"wx": P(None, tp), "wh": P(None, tp), "b": P(tp)}

    _rule_GRU = _rule_LSTM


def param_specs(module, params: Pytree, mesh: Mesh,
                tp_axis: Optional[str] = "tp",
                ep_axis: Optional[str] = None,
                fsdp_axis: Optional[str] = None) -> Pytree:
    """PartitionSpec pytree for ``params`` of ``module`` (see ShardingRules)."""
    rules = ShardingRules(mesh, tp_axis=tp_axis, ep_axis=ep_axis,
                          fsdp_axis=fsdp_axis)
    return rules.specs_for(module, params)


def named_shardings(spec_tree: Pytree, mesh: Mesh) -> Pytree:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def shard_params(params: Pytree, spec_tree: Pytree, mesh: Mesh) -> Pytree:
    """device_put the params according to the spec tree."""
    sh = named_shardings(spec_tree, mesh)
    return jax.tree_util.tree_map(jax.device_put, params, sh)
