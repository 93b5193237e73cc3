"""Core model substrate: Layer protocol, Sequential container, Model handle.

This replaces the reference's dependency on Keras for per-worker compute
(reference: ``distkeras/workers.py :: Worker.prepare_model`` deserializes and
compiles a Keras model inside every Spark executor). Here a model is a pure
spec (layer list) plus pytree variables; ``apply`` is a pure function suitable
for ``jax.jit`` / ``jax.grad`` / ``shard_map``.

Design notes (TPU-first):
  * Variables are split into ``params`` (differentiated) and ``state``
    (non-differentiated collections such as BatchNorm running stats). Both are
    plain pytrees (lists of dicts aligned with the layer list), so they shard
    transparently under ``jax.sharding`` and stack transparently under
    ``vmap`` (used by EnsembleTrainer).
  * ``apply`` is functional: it returns ``(y, new_state)``; nothing mutates.
  * Shapes are static: ``init`` threads a concrete ``input_shape`` through the
    layer stack once, so everything under ``jit`` has static shapes and XLA
    can tile matmuls/convs onto the MXU.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Registry: layer class name -> class, used by serialization to rebuild specs.
LAYER_REGISTRY: Dict[str, type] = {}


# Reserved state-dict key: a layer may publish a scalar auxiliary TRAINING
# loss (e.g. the MoE router balance loss) under this key in its returned
# state; ``collect_aux_losses`` below sums every occurrence, and
# ``parallel.worker.make_train_step`` adds that sum to the optimized loss.
# State is the one channel that already flows out of ``apply`` through
# every jit/vmap/shard_map wrapper, so regularizer-style terms need no
# signature change anywhere.
AUX_LOSS_KEY = "__aux_loss__"


def collect_aux_losses(state) -> jax.Array:
    """Sum of every ``AUX_LOSS_KEY`` leaf in a state pytree (0.0 if none)."""
    total = 0.0
    flat, _ = jax.tree_util.tree_flatten_with_path(state)
    for path, leaf in flat:
        if any(getattr(k, "key", None) == AUX_LOSS_KEY for k in path):
            total = total + leaf
    return total


def user_float(y: jax.Array) -> jax.Array:
    """User-facing output dtype policy: low-precision compute dtypes
    (bf16/f16) stay internal — predictions handed back to the host are f32.
    Non-float outputs (int predictions, bools) pass through untouched."""
    if jnp.issubdtype(y.dtype, jnp.floating) and y.dtype != jnp.float32:
        return y.astype(jnp.float32)
    return y


def register_layer(cls: type) -> type:
    """Class decorator adding a Layer subclass to the serialization registry."""
    LAYER_REGISTRY[cls.__name__] = cls
    return cls


def layer_spec(layer):
    """Layer -> registry spec dict (None passes through) — the one encoding
    every container (Sequential/Residual/TransformerBlock/...) uses."""
    if layer is None:
        return None
    return {"class": layer.name, "config": layer.get_config()}


def layer_from_spec(spec):
    """Registry spec dict -> Layer (None passes through)."""
    if spec is None:
        return None
    return LAYER_REGISTRY[spec["class"]].from_config(spec["config"])


class Layer:
    """Base layer: a pure init/apply pair plus a JSON-able config.

    Subclasses implement:
      init(rng, input_shape) -> (params, state, output_shape)
      apply(params, state, x, *, training, rng) -> (y, new_state)
      get_config() -> dict of constructor kwargs (JSON-serializable)
    ``input_shape``/``output_shape`` exclude the batch dimension.
    """

    #: Keras-style freezing: set False BEFORE training and the layer's
    #: params (its whole subtree, for containers) receive no updates —
    #: every trainer masks the gradients, so optimizer moments stay zero
    #: too. Like Keras, this is a training-time attribute, not part of
    #: the serialized architecture config.
    trainable: bool = True

    #: ``jax.named_scope`` of this layer's operations in a step program
    #: (``embed``; a TransformerBlock opens ``attn`` and ``mlp`` itself):
    #: what a profiler's op names are grouped by. None: no scope.
    scope: Optional[str] = None

    def init(self, rng: jax.Array, input_shape: Tuple[int, ...]):
        return {}, {}, input_shape

    def apply(self, params, state, x, *, training: bool = False,
              rng: Optional[jax.Array] = None):
        return x, state

    def get_config(self) -> Dict[str, Any]:
        return {}

    @classmethod
    def from_config(cls, config: Dict[str, Any]) -> "Layer":
        return cls(**config)

    @property
    def name(self) -> str:
        return type(self).__name__

    def __repr__(self) -> str:
        cfg = ", ".join(f"{k}={v!r}" for k, v in self.get_config().items())
        return f"{self.name}({cfg})"


def scoped(name: Optional[str]):
    """``jax.named_scope(name)``, or nothing for ``None``. Scopes change
    the names of a program's operations and no compiled code."""
    return jax.named_scope(name) if name else contextlib.nullcontext()


@register_layer
class Sequential(Layer):
    """Ordered stack of layers — the Keras ``Sequential`` equivalent.

    The reference builds Keras Sequential models in every example and ships
    them serialized to executors (reference: ``distkeras/utils.py ::
    serialize_keras_model``). Here the spec is pure Python data; variables are
    created explicitly by ``init`` and travel separately.
    """

    def __init__(self, layers: Optional[Sequence[Layer]] = None):
        self.layers: List[Layer] = list(layers) if layers else []

    def add(self, layer: Layer) -> "Sequential":
        self.layers.append(layer)
        return self

    def init(self, rng, input_shape):
        params, state = [], []
        shape = tuple(input_shape)
        for layer in self.layers:
            rng, sub = jax.random.split(rng)
            p, s, shape = layer.init(sub, shape)
            params.append(p)
            state.append(s)
        return params, state, shape

    @property
    def accepts_segment_ids(self) -> bool:
        return any(getattr(l, "accepts_segment_ids", False)
                   for l in self.layers)

    def scope_of(self, i: int) -> Optional[str]:
        """The named scope of layer ``i``: its own, and ``head`` for the
        last layer of a stack that starts with an embedding (a language
        model's vocabulary projection)."""
        layers = self.layers
        if 0 < i == len(layers) - 1 and layers[0].scope == "embed":
            return "head"
        return layers[i].scope

    def apply(self, params, state, x, *, training=False, rng=None,
              segment_ids=None):
        """``segment_ids`` ([B, S] int, packed/variable-length sequences)
        is forwarded to layers that declare ``accepts_segment_ids``
        (TransformerBlock -> attention masking; containers like Remat /
        Residual / nested Sequential forward recursively); other layers
        are position-wise and need no mask — the LOSS masks padded
        positions (``losses.masked_sparse_categorical_crossentropy_
        from_logits``). Passing segment_ids into a stack where NO layer
        accepts them is an error, not a silent unmasked run.
        """
        if segment_ids is not None and not self.accepts_segment_ids:
            raise ValueError(
                "segment_ids passed, but no layer in this Sequential "
                "accepts them (packed-sequence masking needs a "
                "TransformerBlock-family layer)")
        new_state = []
        for i, layer in enumerate(self.layers):
            if rng is not None:
                rng, sub = jax.random.split(rng)
            else:
                sub = None
            kw = ({"segment_ids": segment_ids}
                  if segment_ids is not None
                  and getattr(layer, "accepts_segment_ids", False) else {})
            with scoped(self.scope_of(i)):
                x, s = layer.apply(params[i], state[i], x,
                                   training=training, rng=sub, **kw)
            new_state.append(s)
        return x, new_state

    def get_config(self):
        return {"layers": [layer_spec(l) for l in self.layers]}

    @classmethod
    def from_config(cls, config):
        return cls([layer_from_spec(spec) for spec in config["layers"]])


class Model:
    """A built model: spec + variables + loss/optimizer metadata.

    Plays the role of a compiled Keras model in the reference API surface
    (what ``Trainer.train`` returns; what ``Predictor`` consumes). The object
    is a thin handle — all compute goes through the pure functions so that
    trainers can jit/shard them freely.
    """

    def __init__(self, module: Layer, params, state, input_shape,
                 output_shape):
        self.module = module
        self.params = params
        self.state = state
        self.input_shape = tuple(input_shape)
        self.output_shape = tuple(output_shape)
        self._jit_fwd = None  # cached jitted forward for predict()

    # -- construction -----------------------------------------------------
    @classmethod
    def build(cls, module: Layer, input_shape: Tuple[int, ...],
              rng: Optional[jax.Array] = None, seed: int = 0) -> "Model":
        if rng is None:
            rng = jax.random.PRNGKey(seed)
        # Jit the whole init: one compiled program instead of hundreds of
        # small eager dispatches (a deep ResNet has ~500 init ops; eager
        # dispatch per op is prohibitively slow on remote/TPU backends).
        captured = {}

        def initf(rng):
            params, state, out_shape = module.init(rng, tuple(input_shape))
            captured["out_shape"] = out_shape  # static python tuple
            return params, state

        params, state = jax.jit(initf)(rng)
        return cls(module, params, state, input_shape, captured["out_shape"])

    # -- compute ----------------------------------------------------------
    def apply(self, params, state, x, *, training=False, rng=None):
        return self.module.apply(params, state, x, training=training, rng=rng)

    def predict(self, x, batch_size: Optional[int] = None) -> np.ndarray:
        """Convenience host-side inference (see inference.predictors for the
        sharded/batched path the reference's Predictor corresponds to)."""
        x = jnp.asarray(x)
        if self._jit_fwd is None:
            self._jit_fwd = jax.jit(lambda p, s, b: user_float(
                self.module.apply(p, s, b, training=False)[0]))
        fn = self._jit_fwd
        if batch_size is None:
            return np.asarray(fn(self.params, self.state, x))
        n = x.shape[0]
        outs = []
        for i in range(0, n, batch_size):
            xb = x[i:i + batch_size]
            pad = batch_size - xb.shape[0]
            if pad:  # pad the remainder so every call shares ONE jit shape
                xb = jnp.concatenate(
                    [xb, jnp.zeros((pad,) + xb.shape[1:], xb.dtype)])
            yb = np.asarray(fn(self.params, self.state, xb))
            outs.append(yb[:batch_size - pad] if pad else yb)
        return np.concatenate(outs, axis=0)

    # -- Keras-style conveniences ----------------------------------------
    def fit(self, x, y=None, *, optimizer="sgd", loss="mean_squared_error",
            batch_size: int = 32, epochs: int = 1, metrics=None,
            validation_data=None, validation_split: float = 0.0,
            seed: int = 0, **trainer_kwargs):
        """Keras-style ``model.fit`` — a thin wrapper over SingleTrainer
        (use the trainer classes directly for distributed training).

        ``x`` may be a ``data.Dataset`` (with the default feature/label
        columns) or a feature array with ``y`` labels. Trains IN PLACE
        (this model's params/state are updated) and returns the History.

        ``validation_split``: Keras semantics — hold out the LAST fraction
        of the (unshuffled) data as validation (mutually exclusive with
        ``validation_data``; not available for ShardedDataset).
        """
        from distkeras_tpu.data.dataset import Dataset
        from distkeras_tpu.data.sharded import ShardedDataset
        from distkeras_tpu.parallel.trainers import SingleTrainer

        if isinstance(x, (Dataset, ShardedDataset)):
            ds = x
        else:
            if y is None:
                raise ValueError("fit(x, y): y is required for array input")
            ds = Dataset({"features": np.asarray(x), "label": np.asarray(y)})
        if validation_split:
            if validation_data is not None:
                raise ValueError(
                    "pass validation_split OR validation_data, not both")
            if not 0.0 < validation_split < 1.0:
                raise ValueError(
                    f"validation_split must be in (0, 1), got "
                    f"{validation_split}")
            if isinstance(ds, ShardedDataset):
                raise ValueError(
                    "validation_split needs in-memory data; hold out "
                    "shards yourself for a ShardedDataset")
            ds, validation_data = ds.split(1.0 - validation_split)
        trainer = SingleTrainer(
            self, worker_optimizer=optimizer, loss=loss,
            batch_size=batch_size, num_epoch=epochs, metrics=metrics,
            validation_data=validation_data, seed=seed, **trainer_kwargs)
        trained = trainer.train(ds)
        self.params, self.state = trained.params, trained.state
        self._jit_fwd = None  # old closure captured nothing, but be tidy
        return trainer.get_history()

    def evaluate(self, x, y=None, *, loss="mean_squared_error",
                 metrics=("accuracy",), batch_size: int = 1024,
                 features_col: str = "features", label_col: str = "label"):
        """Keras-style ``model.evaluate``: ``{"loss": ..., metric: ...}``
        over the full set (batched host-side forward)."""
        from distkeras_tpu.data.dataset import Dataset, coerce_column
        from distkeras_tpu.data.sharded import ShardedDataset
        from distkeras_tpu.ops.losses import get_loss
        from distkeras_tpu.ops.metrics import get_metric, metric_name

        if isinstance(x, ShardedDataset):
            # shard-by-shard, weighted by shard size — only one shard in
            # host memory at a time (matches the out-of-core fit path).
            # Only row-decomposable metrics are EXACT under size-weighted
            # averaging; pooled metrics (macro precision/recall/f1) are
            # not, so refuse rather than return a plausible wrong number.
            decomposable = {"accuracy", "top_5_accuracy", "mse"}
            bad = [metric_name(m) for m in (metrics or ())
                   if metric_name(m) not in decomposable]
            if bad:
                raise ValueError(
                    f"metrics {bad} are not decomposable across shards "
                    "(a size-weighted mean of per-shard macro scores is "
                    "not the pooled score); evaluate them on an in-memory "
                    "Dataset, or use decomposable metrics "
                    f"({sorted(decomposable)}) here")
            totals, n_total = {}, 0
            for i in range(x.num_shards):
                shard = x.load_shard(i)
                res = self.evaluate(shard, loss=loss, metrics=metrics,
                                    batch_size=batch_size,
                                    features_col=features_col,
                                    label_col=label_col)
                n = len(shard)
                n_total += n
                for k, v in res.items():
                    totals[k] = totals.get(k, 0.0) + n * v
            return {k: v / n_total for k, v in totals.items()}
        if isinstance(x, Dataset):
            X, yv = x.arrays(features_col, label_col)
            if yv is None:
                raise ValueError(
                    f"evaluate(dataset): label column {label_col!r} not in "
                    f"dataset (columns: {x.columns})")
        else:
            if y is None:
                raise ValueError("evaluate(x, y): y is required")
            X, yv = coerce_column(x), coerce_column(y)
        preds = self.predict(X, batch_size=batch_size)
        res = {"loss": float(get_loss(loss)(yv, jnp.asarray(preds)))}
        for m in (metrics or ()):
            res[metric_name(m)] = float(get_metric(m)(yv, preds))
        return res

    def save(self, path: str, quantize: bool = False) -> None:
        """Keras-style ``model.save`` (see ``models.serialization
        .save_model``; writes ``<path>.json`` + ``<path>.npz``)."""
        from distkeras_tpu.models.serialization import save_model
        save_model(self, path, quantize=quantize)

    @staticmethod
    def load(path: str, keep_quantized: bool = False):
        """Keras-style loader (``models.serialization.load_model``)."""
        from distkeras_tpu.models.serialization import load_model
        return load_model(path, keep_quantized=keep_quantized)

    def generate(self, prompts, max_new_tokens: int, **kwargs):
        """Keras-style convenience over ``models.decoding.generate`` (KV-
        cache autoregressive sampling for transformer-LM-shaped models)."""
        from distkeras_tpu.models.decoding import generate
        return generate(self, prompts, max_new_tokens, **kwargs)

    def get_weights(self) -> List[np.ndarray]:
        """Keras-style flat weight list: params THEN state leaves (host
        numpy, pytree leaf order). State is included so BatchNorm running
        stats round-trip — as Keras's moving_mean/moving_variance do."""
        return [np.asarray(w) for w in
                jax.tree_util.tree_leaves((self.params, self.state))]

    def set_weights(self, weights: Sequence[np.ndarray]) -> None:
        """Keras-style inverse of :meth:`get_weights` — shapes must match
        leaf-for-leaf."""
        leaves, treedef = jax.tree_util.tree_flatten(
            (self.params, self.state))
        if len(weights) != len(leaves):
            raise ValueError(
                f"set_weights got {len(weights)} arrays, model has "
                f"{len(leaves)} weight tensors (params + state)")
        new = []
        for i, (leaf, w) in enumerate(zip(leaves, weights)):
            w = jnp.asarray(w, dtype=leaf.dtype)
            if tuple(w.shape) != tuple(leaf.shape):
                raise ValueError(
                    f"set_weights: tensor {i} has shape {w.shape}, "
                    f"expected {leaf.shape}")
            new.append(w)
        self.params, self.state = jax.tree_util.tree_unflatten(treedef, new)
        self._jit_fwd = None

    # -- bookkeeping ------------------------------------------------------
    def num_params(self) -> int:
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.params))

    def summary(self) -> str:
        """Keras-style per-layer table (layer, config, params). Printed
        AND returned."""
        rows = []
        if isinstance(self.module, Sequential):
            for layer, p in zip(self.module.layers, self.params):
                n = sum(int(np.prod(l.shape))
                        for l in jax.tree_util.tree_leaves(p))
                rows.append((repr(layer), n))
        else:
            rows.append((repr(self.module), self.num_params()))
        name_w = min(72, max([len(r[0]) for r in rows] + [10]))
        lines = [f"Model: in={self.input_shape} out={self.output_shape}",
                 "-" * (name_w + 14)]
        for name, n in rows:
            disp = name if len(name) <= name_w else name[:name_w - 1] + "…"
            lines.append(f"{disp:<{name_w}}  {n:>12,}")
        lines.append("-" * (name_w + 14))
        lines.append(f"{'total':<{name_w}}  {self.num_params():>12,}")
        out = "\n".join(lines)
        print(out)
        return out

    def replace(self, params=None, state=None) -> "Model":
        return Model(self.module,
                     params if params is not None else self.params,
                     state if state is not None else self.state,
                     self.input_shape, self.output_shape)

    def __repr__(self):
        return (f"Model({self.module.name}, in={self.input_shape}, "
                f"out={self.output_shape}, params={self.num_params():,})")


def trainable_mask(module: Layer, tree):
    """Boolean pytree matching ``tree`` (params OR state — containers lay
    both out identically): True where updates may flow.

    Returns ``None`` when every layer is trainable (the common case — the
    trainers then skip the masking entirely). Keras container semantics:
    a layer with ``trainable = False`` freezes its WHOLE subtree;
    ``Sequential`` recurses per sublayer, and composite containers that
    implement ``sub_layers() -> {subtree_key: Layer}`` (Residual,
    TransformerBlock, ...) recurse through it, so freezing e.g. only a
    block's attention works. Custom containers without ``sub_layers`` are
    atomic: only their own flag counts.
    """
    def walk(layer, sub, enabled):
        enabled = enabled and getattr(layer, "trainable", True)
        if isinstance(layer, Sequential):
            return [walk(l, p, enabled)
                    for l, p in zip(layer.layers, sub)]
        subs = getattr(layer, "sub_layers", None)
        if callable(subs) and isinstance(sub, dict):
            named = subs()
            return {key: (walk(named[key], child, enabled)
                          if key in named
                          else jax.tree_util.tree_map(
                              lambda _: enabled, child))
                    for key, child in sub.items()}
        return jax.tree_util.tree_map(lambda _: enabled, sub)

    mask = walk(module, tree, True)
    if all(jax.tree_util.tree_leaves(mask)):
        return None
    return mask
