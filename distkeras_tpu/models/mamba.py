"""The Mamba-2 mixer: a state-space layer in the place of attention.

``Mamba2Mixer`` takes a ``TransformerBlock``'s attention slot
(``TransformerBlock(attn_layer=Mamba2Mixer(...))``; ``zoo.transformer_lm``
builds it for the layers whose ``attn_kinds`` entry has a ``"mamba"``
group). On the normed input ``a`` of a block, with ``H`` heads of ``P``,
a state of ``N`` and one group::

    [z | xBC | dt] = a W_in                       (H*P | H*P + 2N | H)
    xBC            = silu(causal_depthwise_conv_K(xBC) + b)
    [x | B | C]    = xBC                          (H*P | N | N)
    dt             = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t            = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T      per head, [P, N]
    y_t            = S_t C_t + D x_t
    out            = RMSNorm_w(y * silu(z)) W_out               (over all H*P)

No bias but the convolution's. What a serving cache keeps of a sequence
is its state (``[H, P, N]`` float32) and the last ``K - 1`` inputs of
the convolution: a fixed size, whatever the length
(``models.decoding`` / ``serving.kv_pool``: the state group). The scan
and the one-token update are ``ops.ssm_kernels``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models.core import Layer, register_layer
from distkeras_tpu.models.layers import init_weights
from distkeras_tpu.ops import ssm_kernels

F32 = jnp.float32


@register_layer
class Mamba2Mixer(Layer):
    """A Mamba-2 mixer over [B, S, d_model] (module doc).

    ``num_heads`` heads of ``head_dim`` (``P``), a state of ``d_state``
    (``N``) shared by one group, a causal convolution of ``d_conv``
    taps, the scan in chunks of ``chunk``; ``norm_eps`` is the gated
    norm's epsilon. Parameters: ``in_proj`` [d, 2*H*P + 2*N + H],
    ``conv_w`` [d_conv, H*P + 2*N] (tap ``d_conv - 1`` multiplies the
    current input), ``conv_b``, ``dt_bias`` / ``A_log`` / ``D`` [H],
    ``norm`` [H*P], ``out_proj`` [H*P, d]."""

    accepts_segment_ids = False
    #: what the serving paths read of any attention slot
    attn_window = None
    block_len = None
    use_rope = False
    kv_heads = 0

    def __init__(self, num_heads: int, head_dim: int, d_state: int,
                 d_conv: int = 4, n_groups: int = 1, chunk: int = 256,
                 norm_eps: float = 1e-5, dtype: str = "float32",
                 kernel_init: str = "glorot_uniform"):
        if int(n_groups) != 1:
            raise NotImplementedError("the mixer is built for one group "
                                      "of B and C (n_groups=1)")
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.d_state = int(d_state)
        self.d_conv = int(d_conv)
        self.n_groups = 1
        self.chunk = int(chunk)
        self.norm_eps = float(norm_eps)
        self.dtype = dtype
        self.kernel_init = kernel_init
        #: the x channels, and all the convolved ones (x | B | C)
        self.inner = self.num_heads * self.head_dim
        self.conv_dim = self.inner + 2 * self.d_state

    def state_shapes(self, batch: int, dtype):
        """What a cache keeps of ``batch`` sequences: ``{"ssm": ([B, H,
        P, N], float32), "conv": ([B, K-1, conv_dim], dtype)}``."""
        return {"ssm": ((batch, self.num_heads, self.head_dim,
                         self.d_state), F32),
                "conv": ((batch, self.d_conv - 1, self.conv_dim), dtype)}

    def init(self, rng, input_shape):
        d = input_shape[-1]
        h = self.num_heads
        ks = jax.random.split(rng, 4)
        dt = jnp.exp(jax.random.uniform(ks[2], (h,), F32) *
                     (np.log(0.1) - np.log(0.001)) + np.log(0.001))
        params = {
            "in_proj": init_weights(self.kernel_init, ks[0],
                                    (d, self.inner + self.conv_dim + h)),
            "conv_w": 0.5 * jax.random.uniform(
                ks[1], (self.d_conv, self.conv_dim), F32, -1.0, 1.0),
            "conv_b": jnp.zeros((self.conv_dim,)),
            # dt_bias: the inverse softplus of a step in [1e-3, 1e-1]
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jnp.arange(1, h + 1, dtype=F32)),
            "D": jnp.ones((h,)),
            "norm": jnp.ones((self.inner,)),
            "out_proj": init_weights(self.kernel_init, ks[3],
                                     (self.inner, d)),
        }
        return params, {}, tuple(input_shape)

    def project(self, params, xc):
        """``(z, xBC, dt)`` of ``xc`` [..., d] in the compute type."""
        proj = xc @ params["in_proj"].astype(xc.dtype)
        i, c = self.inner, self.conv_dim
        return proj[..., :i], proj[..., i:i + c], proj[..., i + c:]

    def scan_args(self, params):
        """The parameters ``ssm_kernels`` takes, in its order."""
        return (params["conv_w"], params["conv_b"], params["dt_bias"],
                params["A_log"], params["D"])

    def out(self, params, y, z, dt):
        """``RMSNorm_w(y * silu(z)) W_out``: ``y`` [..., H, P] float32,
        ``z`` [..., H*P]; the gate and the norm in float32, the product
        in ``dt``."""
        g = y.reshape(z.shape).astype(F32) * jax.nn.silu(z.astype(F32))
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                              + self.norm_eps)
        g = (g * params["norm"].astype(F32)).astype(dt)
        return g @ params["out_proj"].astype(dt)

    def apply(self, params, state, x, *, training=False, rng=None):
        """The whole sequence from a zero state (the chunked scan)."""
        dt = jnp.dtype(self.dtype)
        z, xbc, step = self.project(params, x.astype(dt))
        b = x.shape[0]
        zeros = {k: jnp.zeros(shape, t) for k, (shape, t)
                 in self.state_shapes(b, dt).items()}
        y, _, _ = ssm_kernels.ssd_chunk_scan(
            xbc, step, zeros["conv"], zeros["ssm"], *self.scan_args(params),
            heads=self.num_heads, d_state=self.d_state, chunk=self.chunk)
        return self.out(params, y, z, dt).astype(x.dtype), state

    def get_config(self):
        return {"num_heads": self.num_heads, "head_dim": self.head_dim,
                "d_state": self.d_state, "d_conv": self.d_conv,
                "n_groups": self.n_groups, "chunk": self.chunk,
                "norm_eps": self.norm_eps, "dtype": self.dtype,
                "kernel_init": self.kernel_init}
