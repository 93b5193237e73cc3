"""Standard layers (Keras-equivalent surface, TPU-first internals).

Covers the layer vocabulary the reference's examples use to build models
(Dense/Conv2D/MaxPooling2D/Flatten/Dropout/Activation/Embedding — reference:
``examples/`` MNIST + ATLAS notebooks build Keras Sequential models from
exactly these), plus BatchNorm for the ResNet-50 north-star config.

TPU notes:
  * Conv uses NHWC with ``lax.conv_general_dilated`` — XLA's native layout for
    TPU convolutions (maps onto the MXU).
  * Compute dtype is configurable per layer (``dtype=jnp.bfloat16``) while
    params stay float32 — the standard TPU mixed-precision recipe.
  * Everything is shape-static and control-flow-free so layers fuse cleanly
    under jit.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from distkeras_tpu.models.core import Layer, register_layer

# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------

ACTIVATIONS = {
    "linear": lambda x: x,
    "relu": jax.nn.relu,
    "relu6": jax.nn.relu6,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
    "softmax": jax.nn.softmax,
    "log_softmax": jax.nn.log_softmax,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "elu": jax.nn.elu,
    "leaky_relu": jax.nn.leaky_relu,
    "softplus": jax.nn.softplus,
}


def get_activation(name):
    if callable(name):
        return name
    if name is None:
        return ACTIVATIONS["linear"]
    try:
        return ACTIVATIONS[name]
    except KeyError:
        raise ValueError(
            f"Unknown activation {name!r}; known: {sorted(ACTIVATIONS)}")


# ---------------------------------------------------------------------------
# initializers (Keras-compatible names)
# ---------------------------------------------------------------------------

def _fans(shape):
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    # conv kernels HWIO: receptive field * channels
    receptive = int(np.prod(shape[:-2]))
    return shape[-2] * receptive, shape[-1] * receptive


def init_weights(name: str, rng, shape, dtype=jnp.float32):
    fan_in, fan_out = _fans(shape)
    if name == "zeros":
        return jnp.zeros(shape, dtype)
    if name == "ones":
        return jnp.ones(shape, dtype)
    if name == "glorot_uniform":
        limit = np.sqrt(6.0 / (fan_in + fan_out))
        return jax.random.uniform(rng, shape, dtype, -limit, limit)
    if name == "glorot_normal":
        std = np.sqrt(2.0 / (fan_in + fan_out))
        return jax.random.normal(rng, shape, dtype) * std
    if name == "he_normal":
        std = np.sqrt(2.0 / fan_in)
        return jax.random.normal(rng, shape, dtype) * std
    if name == "he_uniform":
        limit = np.sqrt(6.0 / fan_in)
        return jax.random.uniform(rng, shape, dtype, -limit, limit)
    if name == "lecun_normal":
        std = np.sqrt(1.0 / fan_in)
        return jax.random.normal(rng, shape, dtype) * std
    if name == "uniform_scaling":
        return jax.random.uniform(rng, shape, dtype, -0.05, 0.05)
    raise ValueError(f"Unknown initializer {name!r}")


# ---------------------------------------------------------------------------
# dense / activation / dropout / reshape
# ---------------------------------------------------------------------------

@register_layer
class Dense(Layer):
    """Fully-connected layer. Keras ``Dense`` equivalent.

    ``dtype`` selects the compute/matmul dtype (bf16 recommended on TPU);
    parameters are stored float32 and cast at apply time.
    """

    def __init__(self, units: int, activation=None, use_bias: bool = True,
                 kernel_init: str = "glorot_uniform", dtype: str = "float32"):
        self.units = int(units)
        get_activation(activation)  # fail at construction, not first forward
        self.activation = activation
        self.use_bias = use_bias
        self.kernel_init = kernel_init
        self.dtype = dtype

    def init(self, rng, input_shape):
        in_dim = input_shape[-1]
        params = {"kernel": init_weights(self.kernel_init, rng,
                                         (in_dim, self.units))}
        if self.use_bias:
            params["bias"] = jnp.zeros((self.units,))
        return params, {}, tuple(input_shape[:-1]) + (self.units,)

    def apply(self, params, state, x, *, training=False, rng=None):
        dt = jnp.dtype(self.dtype)
        y = jnp.matmul(x.astype(dt), params["kernel"].astype(dt))
        if self.use_bias:
            y = y + params["bias"].astype(dt)
        y = get_activation(self.activation)(y)
        # mixed-precision policy: params live in f32, activations FLOW in
        # the compute dtype — bf16 activations halve HBM traffic between
        # fusions (measured 3.3x on ResNet-50/v5e); f32 casts happen only
        # where numerics demand it (norm stats, softmax, losses)
        return y, state

    def get_config(self):
        return {"units": self.units, "activation": self.activation,
                "use_bias": self.use_bias, "kernel_init": self.kernel_init,
                "dtype": self.dtype}


@register_layer
class Activation(Layer):
    def __init__(self, activation: str):
        get_activation(activation)  # fail at construction, not first forward
        self.activation = activation

    def apply(self, params, state, x, *, training=False, rng=None):
        return get_activation(self.activation)(x), state

    def get_config(self):
        return {"activation": self.activation}


@register_layer
class Dropout(Layer):
    """Inverted dropout; identity when not training or rng is None."""

    def __init__(self, rate: float):
        self.rate = float(rate)

    def apply(self, params, state, x, *, training=False, rng=None):
        if not training or rng is None or self.rate <= 0.0:
            return x, state
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0), state

    def get_config(self):
        return {"rate": self.rate}


@register_layer
class Flatten(Layer):
    def init(self, rng, input_shape):
        return {}, {}, (int(np.prod(input_shape)),)

    def apply(self, params, state, x, *, training=False, rng=None):
        return x.reshape(x.shape[0], -1), state


@register_layer
class Reshape(Layer):
    def __init__(self, target_shape: Sequence[int]):
        self.target_shape = tuple(int(d) for d in target_shape)

    def init(self, rng, input_shape):
        return {}, {}, self.target_shape

    def apply(self, params, state, x, *, training=False, rng=None):
        return x.reshape((x.shape[0],) + self.target_shape), state

    def get_config(self):
        return {"target_shape": list(self.target_shape)}


# ---------------------------------------------------------------------------
# convolution / pooling (NHWC)
# ---------------------------------------------------------------------------

def _pair(v):
    return (v, v) if isinstance(v, int) else tuple(v)


class _ConvND(Layer):
    """Shared N-D convolution core; subclasses fix the spatial rank /
    channels-last ``dimension_numbers`` (XLA's native TPU conv layout) and
    may override the kernel shape, output-channel count, and conv
    primitive (depthwise, transpose)."""

    _dims: tuple  # e.g. ("NHWC", "HWIO", "NHWC")

    def __init__(self, filters: int, kernel_size, strides=1, padding="SAME",
                 activation=None, use_bias: bool = True,
                 kernel_init: str = "he_normal", dtype: str = "float32"):
        get_activation(activation)  # fail at construction, not first forward
        self.filters = int(filters)
        self.kernel_size = self._spatial(kernel_size)
        self.strides = self._spatial(strides)
        self.padding = padding.upper()
        self.activation = activation
        self.use_bias = use_bias
        self.kernel_init = kernel_init
        self.dtype = dtype

    def _spatial(self, v) -> tuple:
        """Normalize an int / sequence to the layer's spatial rank — a bare
        int broadcasts; a sequence must match the rank exactly (a clear
        error here beats an opaque conv shape mismatch at build time)."""
        n = len(self._dims[0]) - 2  # spatial rank from the layout string
        if isinstance(v, (tuple, list)):
            if len(v) != n:
                raise ValueError(
                    f"{type(self).__name__} expects {n} spatial dim(s), "
                    f"got {v}")
            return tuple(int(e) for e in v)
        return (int(v),) * n

    # -- subclass hooks -----------------------------------------------------
    def _kernel_shape(self, c: int) -> tuple:
        return self.kernel_size + (c, self.filters)

    def _out_channels(self, c: int) -> int:
        return self.filters

    def _conv(self, x, k):
        return lax.conv_general_dilated(
            x, k, self.strides, self.padding, dimension_numbers=self._dims)

    # -- shared body --------------------------------------------------------
    def init(self, rng, input_shape):
        c = input_shape[-1]
        kshape = self._kernel_shape(c)
        params = {"kernel": init_weights(self.kernel_init, rng, kshape)}
        if self.use_bias:
            params["bias"] = jnp.zeros((self._out_channels(c),))
        out = jax.eval_shape(
            self._conv,
            jax.ShapeDtypeStruct((1,) + tuple(input_shape), jnp.float32),
            jax.ShapeDtypeStruct(kshape, jnp.float32))
        return params, {}, tuple(out.shape[1:])

    def apply(self, params, state, x, *, training=False, rng=None):
        dt = jnp.dtype(self.dtype)
        y = self._conv(x.astype(dt), params["kernel"].astype(dt))
        if self.use_bias:
            y = y + params["bias"].astype(dt)
        y = get_activation(self.activation)(y)
        return y, state  # stays in compute dtype (see Dense.apply)

    def get_config(self):
        ks, st = self.kernel_size, self.strides
        return {"filters": self.filters,
                "kernel_size": list(ks) if len(ks) > 1 else ks[0],
                "strides": list(st) if len(st) > 1 else st[0],
                "padding": self.padding,
                "activation": self.activation, "use_bias": self.use_bias,
                "kernel_init": self.kernel_init, "dtype": self.dtype}


@register_layer
class Conv2D(_ConvND):
    """2-D convolution over [B, H, W, C]."""

    _dims = ("NHWC", "HWIO", "NHWC")


@register_layer
class Conv1D(_ConvND):
    """1-D convolution over [B, W, C] (text-CNN / signal models)."""

    _dims = ("NWC", "WIO", "NWC")


@register_layer
class DepthwiseConv2D(_ConvND):
    """Depthwise 2-D convolution (each input channel convolved with its
    own ``depth_multiplier`` filters) — the MobileNet-era Keras staple.
    Lowered with ``feature_group_count = C`` so XLA picks its native
    grouped-conv path."""

    _dims = ("NHWC", "HWIO", "NHWC")

    def __init__(self, kernel_size, strides=1, padding: str = "SAME",
                 depth_multiplier: int = 1, activation=None,
                 use_bias: bool = True, kernel_init: str = "he_normal",
                 dtype: str = "float32"):
        # filters is unused (output width derives from C × multiplier) but
        # kept so the base get_config can read it before we pop the key
        super().__init__(filters=0, kernel_size=kernel_size,
                         strides=strides, padding=padding,
                         activation=activation, use_bias=use_bias,
                         kernel_init=kernel_init, dtype=dtype)
        self.depth_multiplier = int(depth_multiplier)

    def _kernel_shape(self, c):
        # HWIO with I=1 per group (feature_group_count = C)
        return self.kernel_size + (1, c * self.depth_multiplier)

    def _out_channels(self, c):
        return c * self.depth_multiplier

    def _conv(self, x, k):
        return lax.conv_general_dilated(
            x, k, self.strides, self.padding, dimension_numbers=self._dims,
            feature_group_count=x.shape[-1])

    def get_config(self):
        cfg = super().get_config()
        cfg.pop("filters")
        cfg["depth_multiplier"] = self.depth_multiplier
        return cfg


@register_layer
class SeparableConv2D(Layer):
    """Depthwise-separable convolution (Keras ``SeparableConv2D``): a
    ``DepthwiseConv2D`` followed by a 1×1 pointwise ``Conv2D`` — the
    MobileNet/Xception building block as one layer."""

    def __init__(self, filters: int, kernel_size, strides=1,
                 padding: str = "SAME", depth_multiplier: int = 1,
                 activation=None, use_bias: bool = True,
                 kernel_init: str = "he_normal", dtype: str = "float32"):
        self.filters = int(filters)
        self.depth_multiplier = int(depth_multiplier)
        self.activation = activation
        self.use_bias = use_bias
        self.kernel_init = kernel_init
        self.dtype = dtype
        self.depthwise = DepthwiseConv2D(
            kernel_size, strides=strides, padding=padding,
            depth_multiplier=depth_multiplier, use_bias=False,
            kernel_init=kernel_init, dtype=dtype)
        # activation/bias live on the pointwise half, Keras-style
        self.pointwise = Conv2D(filters, 1, activation=activation,
                                use_bias=use_bias, kernel_init=kernel_init,
                                dtype=dtype)

    def init(self, rng, input_shape):
        k1, k2 = jax.random.split(rng)
        pd, _, shape = self.depthwise.init(k1, input_shape)
        pp, _, shape = self.pointwise.init(k2, shape)
        return {"depthwise": pd, "pointwise": pp}, {}, shape

    def sub_layers(self):
        return {"depthwise": self.depthwise, "pointwise": self.pointwise}

    def apply(self, params, state, x, *, training=False, rng=None):
        y, _ = self.depthwise.apply(params["depthwise"], {}, x,
                                    training=training)
        y, _ = self.pointwise.apply(params["pointwise"], {}, y,
                                    training=training)
        return y, state

    def get_config(self):
        # spatial formatting delegated to the depthwise sublayer's base
        cfg = _ConvND.get_config(self.depthwise)
        cfg.pop("filters")
        cfg.update(filters=self.filters,
                   depth_multiplier=self.depth_multiplier,
                   activation=self.activation, use_bias=self.use_bias)
        return cfg


@register_layer
class Conv2DTranspose(_ConvND):
    """Transposed 2-D convolution (learned upsampling for decoder /
    segmentation heads) via ``lax.conv_transpose``."""

    _dims = ("NHWC", "HWIO", "NHWC")

    def _conv(self, x, k):
        return lax.conv_transpose(x, k, self.strides, self.padding,
                                  dimension_numbers=self._dims)


@register_layer
class UpSampling2D(Layer):
    """Nearest-neighbor spatial upsampling ([B, H, W, C] -> [B, rH, rW, C])
    — a pure repeat, no parameters."""

    def __init__(self, size=2):
        if isinstance(size, (tuple, list)) and len(size) != 2:
            raise ValueError(
                f"UpSampling2D expects 2 spatial factors, got {size}")
        self.size = _pair(size)

    def init(self, rng, input_shape):
        h, w, c = input_shape
        return {}, {}, (h * self.size[0], w * self.size[1], c)

    def apply(self, params, state, x, *, training=False, rng=None):
        y = jnp.repeat(jnp.repeat(x, self.size[0], axis=1),
                       self.size[1], axis=2)
        return y, state

    def get_config(self):
        return {"size": list(self.size)}


class _Pool2D(Layer):
    def __init__(self, pool_size=2, strides=None, padding="VALID"):
        self.pool_size = _pair(pool_size)
        self.strides = _pair(strides) if strides is not None else self.pool_size
        self.padding = padding.upper()

    def _reduce(self, x):
        raise NotImplementedError

    def init(self, rng, input_shape):
        out = jax.eval_shape(
            lambda x: self._reduce(x),
            jax.ShapeDtypeStruct((1,) + tuple(input_shape), jnp.float32))
        return {}, {}, tuple(out.shape[1:])

    def apply(self, params, state, x, *, training=False, rng=None):
        return self._reduce(x), state

    def get_config(self):
        return {"pool_size": list(self.pool_size),
                "strides": list(self.strides), "padding": self.padding}


@register_layer
class MaxPooling2D(_Pool2D):
    def _reduce(self, x):
        return lax.reduce_window(
            x, -jnp.inf, lax.max, (1,) + self.pool_size + (1,),
            (1,) + self.strides + (1,), self.padding)


@register_layer
class AveragePooling2D(_Pool2D):
    def _reduce(self, x):
        ones = lax.reduce_window(
            jnp.ones_like(x), 0.0, lax.add, (1,) + self.pool_size + (1,),
            (1,) + self.strides + (1,), self.padding)
        summed = lax.reduce_window(
            x, 0.0, lax.add, (1,) + self.pool_size + (1,),
            (1,) + self.strides + (1,), self.padding)
        return summed / ones


@register_layer
class GlobalAveragePooling2D(Layer):
    def init(self, rng, input_shape):
        return {}, {}, (input_shape[-1],)

    def apply(self, params, state, x, *, training=False, rng=None):
        return jnp.mean(x, axis=(1, 2)), state


@register_layer
class GlobalAveragePooling1D(Layer):
    """Mean over the sequence axis of a [B, S, D] input (ViT/BERT heads)."""

    def init(self, rng, input_shape):
        return {}, {}, (input_shape[-1],)

    def apply(self, params, state, x, *, training=False, rng=None):
        return jnp.mean(x, axis=1), state


# ---------------------------------------------------------------------------
# batch norm
# ---------------------------------------------------------------------------

@register_layer
class BatchNorm(Layer):
    """Batch normalization with functional running stats.

    Running mean/var live in the ``state`` collection and are returned
    (not mutated) from ``apply`` — this is what lets BN work unchanged under
    jit/shard_map in the distributed trainers. When training under a
    data-parallel mesh axis, pass ``axis_name`` so batch statistics are
    all-reduced over ICI (the cross-replica BN the reference could never do —
    each Spark executor normalized over its local batch only).
    """

    def __init__(self, momentum: float = 0.99, epsilon: float = 1e-3,
                 axis_name: Optional[str] = None,
                 virtual_batch_size: Optional[int] = None):
        self.momentum = float(momentum)
        self.epsilon = float(epsilon)
        self.axis_name = axis_name
        # ghost batch norm (Hoffer et al. 2017; Keras' virtual_batch_size):
        # each sub-batch of this size normalizes by its OWN stats — a
        # regularizer at large batch, and what per-worker BN looked like in
        # the reference (each Spark executor normalized its local batch)
        self.virtual_batch_size = (None if virtual_batch_size is None
                                   else int(virtual_batch_size))
        if self.virtual_batch_size is not None and axis_name is not None:
            raise ValueError(
                "virtual_batch_size (deliberately LOCAL ghost stats) and "
                "axis_name (cross-replica stats) contradict each other; "
                "pick one")

    def init(self, rng, input_shape):
        dim = input_shape[-1]
        params = {"scale": jnp.ones((dim,)), "offset": jnp.zeros((dim,))}
        state = {"mean": jnp.zeros((dim,)), "var": jnp.ones((dim,))}
        return params, state, tuple(input_shape)

    def apply(self, params, state, x, *, training=False, rng=None):
        xf = x.astype(jnp.float32)  # stats in f32 even for bf16 activations
        if training and self.virtual_batch_size is not None:
            v = self.virtual_batch_size
            if x.shape[0] % v:
                raise ValueError(
                    f"batch size {x.shape[0]} not divisible by "
                    f"virtual_batch_size {v}")
            g = x.shape[0] // v
            xg = xf.reshape((g, v) + x.shape[1:])       # ghost groups
            gaxes = tuple(range(1, xg.ndim - 1))        # within-group stats
            mean_g = jnp.mean(xg, axis=gaxes)           # [g, C]
            var_g = jnp.mean(jnp.square(xg), axis=gaxes) - jnp.square(mean_g)
            sh = (g,) + (1,) * (xg.ndim - 2) + (-1,)
            inv = lax.rsqrt(var_g.reshape(sh) + self.epsilon) \
                * params["scale"]
            y = (xg - mean_g.reshape(sh)) * inv + params["offset"]
            m = self.momentum
            new_state = {
                "mean": m * state["mean"] + (1 - m) * mean_g.mean(axis=0),
                "var": m * state["var"] + (1 - m) * var_g.mean(axis=0)}
            return y.reshape(x.shape).astype(x.dtype), new_state
        axes = tuple(range(x.ndim - 1))
        if training:
            mean = jnp.mean(xf, axis=axes)
            mean2 = jnp.mean(jnp.square(xf), axis=axes)
            if self.axis_name is not None:
                mean = lax.pmean(mean, self.axis_name)
                mean2 = lax.pmean(mean2, self.axis_name)
            var = mean2 - jnp.square(mean)
            m = self.momentum
            new_state = {"mean": m * state["mean"] + (1 - m) * mean,
                         "var": m * state["var"] + (1 - m) * var}
            # hand-derived 2-reduction backward (ops/normalization.py):
            # autodiff through the expression below produced ~5 full-tensor
            # f32 reduce chains per BN that made ResNet backward convs
            # VPU-bound (60 of 98 ms/step in the round-2 profile)
            from distkeras_tpu.ops.normalization import bn_train_apply
            y = bn_train_apply(x, params["scale"], params["offset"],
                               mean, var, self.epsilon, axes,
                               self.axis_name)
            return y, new_state
        mean, var = state["mean"], state["var"]
        inv = lax.rsqrt(var + self.epsilon) * params["scale"]
        y = (xf - mean) * inv + params["offset"]
        return y.astype(x.dtype), state

    def get_config(self):
        return {"momentum": self.momentum, "epsilon": self.epsilon,
                "axis_name": self.axis_name,
                "virtual_batch_size": self.virtual_batch_size}


@register_layer
class GroupNorm(Layer):
    """Group normalization (Wu & He 2018) over the channel axis of a
    [B, ..., C] input: batch-size-independent (no running stats, identical
    train/eval), the usual BN replacement when per-device batches are
    small. Stats are computed in f32 per (sample, group) over all spatial
    positions and the group's channels."""

    def __init__(self, groups: int = 32, epsilon: float = 1e-5):
        self.groups = int(groups)
        self.epsilon = float(epsilon)

    def init(self, rng, input_shape):
        dim = input_shape[-1]
        if dim % self.groups:
            raise ValueError(
                f"channels {dim} not divisible by groups {self.groups}")
        params = {"scale": jnp.ones((dim,)), "offset": jnp.zeros((dim,))}
        return params, {}, tuple(input_shape)

    def apply(self, params, state, x, *, training=False, rng=None):
        g = self.groups
        xf = x.astype(jnp.float32)
        xg = xf.reshape(x.shape[:-1] + (g, x.shape[-1] // g))
        axes = tuple(range(1, x.ndim - 1)) + (x.ndim,)  # spatial + in-group
        mean = jnp.mean(xg, axis=axes, keepdims=True)
        var = jnp.var(xg, axis=axes, keepdims=True)
        y = ((xg - mean) * lax.rsqrt(var + self.epsilon)).reshape(x.shape)
        y = y * params["scale"] + params["offset"]
        return y.astype(x.dtype), state

    def get_config(self):
        return {"groups": self.groups, "epsilon": self.epsilon}


# ---------------------------------------------------------------------------
# embedding
# ---------------------------------------------------------------------------

@register_layer
class Embedding(Layer):
    scope = "embed"

    def __init__(self, vocab_size: int, dim: int,
                 embeddings_init: str = "uniform_scaling"):
        self.vocab_size = int(vocab_size)
        self.dim = int(dim)
        self.embeddings_init = embeddings_init

    def init(self, rng, input_shape):
        params = {"embeddings": init_weights(self.embeddings_init, rng,
                                             (self.vocab_size, self.dim))}
        return params, {}, tuple(input_shape) + (self.dim,)

    def apply(self, params, state, x, *, training=False, rng=None):
        return jnp.take(params["embeddings"], x.astype(jnp.int32), axis=0), \
            state

    def get_config(self):
        return {"vocab_size": self.vocab_size, "dim": self.dim,
                "embeddings_init": self.embeddings_init}


@register_layer
class Scale(Layer):
    """``x * factor`` in the input's type: a fixed multiplier with no
    parameters (a language model's embedding multiplier)."""

    def __init__(self, factor: float):
        self.factor = float(factor)

    def init(self, rng, input_shape):
        return {}, {}, tuple(input_shape)

    def apply(self, params, state, x, *, training=False, rng=None):
        return (x * self.factor).astype(x.dtype), state

    def get_config(self):
        return {"factor": self.factor}


@register_layer
class TiedHead(Layer):
    """The vocabulary head of a model whose head IS its embedding: logits
    ``(x E^T) * scale`` over a table ``E`` [vocab, d] laid out as the
    ``Embedding``'s. ``Sequential.init`` hands it the parameters of the
    layer ``tied_to`` names (the stack's first), so both are one table;
    ``scale`` is the logits' multiplier (``1 / logits_scaling``). The
    tie holds for serving: a trainer sees two leaves of one value and
    would update them apart."""

    #: the layer of the stack whose parameters this one shares
    tied_to = 0

    def __init__(self, vocab_size: int, scale: float = 1.0,
                 dtype: str = "float32"):
        self.vocab_size = int(vocab_size)
        self.scale = float(scale)
        self.dtype = dtype

    def init(self, rng, input_shape):
        params = {"embeddings": init_weights(
            "uniform_scaling", rng, (self.vocab_size, input_shape[-1]))}
        return params, {}, tuple(input_shape[:-1]) + (self.vocab_size,)

    def apply(self, params, state, x, *, training=False, rng=None):
        dt = jnp.dtype(self.dtype)
        y = jnp.einsum("...d,vd->...v", x.astype(dt),
                       params["embeddings"].astype(dt))
        return (y if self.scale == 1.0 else y * self.scale), state

    def get_config(self):
        return {"vocab_size": self.vocab_size, "scale": self.scale,
                "dtype": self.dtype}
