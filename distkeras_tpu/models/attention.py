"""Transformer layers: norms, multi-head attention, transformer block.

The reference has no attention/transformer models (SURVEY §5.7 — dist-keras
predates transformers; examples stop at (Bi)LSTM). These layers are the TPU
build's long-context model family, designed mesh-first:

  * Attention projection params are stored as ``[d_model, heads, head_dim]``
    so tensor parallelism is a single ``PartitionSpec(None, "tensor", None)``
    on the heads axis (see ``parallel.sharding``).
  * The MLP keeps its two matmuls as explicit ``w1``/``w2`` for the standard
    column→row TP split.
  * ``attn_impl`` selects the compute path per layer: ``"auto"`` (the
    default: the Pallas flash kernel on TPU — measured 2.15x faster than
    fused XLA attention at seq 2048 on v5e, ``bench.py --model lm`` —
    and XLA elsewhere), ``"xla"`` (fused reference), ``"flash"`` (Pallas
    kernel, forced), ``"ring"`` (sequence-parallel ring attention over a
    mesh axis — set by the SPMD trainer), or
    ``"ulysses"``/``"ulysses_flash"`` (all-to-all head-scatter sequence
    parallelism, ``ops.ulysses``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from distkeras_tpu.compat import backend_is_tpu, note_path
from distkeras_tpu.models.core import (Layer, layer_from_spec, layer_spec,
                                       register_layer)
from distkeras_tpu.models.layers import Dropout, get_activation, init_weights
from distkeras_tpu.ops.attention import apply_rope, dot_product_attention


@register_layer
class LayerNorm(Layer):
    def __init__(self, epsilon: float = 1e-5):
        self.epsilon = float(epsilon)

    def init(self, rng, input_shape):
        dim = input_shape[-1]
        return {"scale": jnp.ones((dim,)), "offset": jnp.zeros((dim,))}, {}, \
            tuple(input_shape)

    def apply(self, params, state, x, *, training=False, rng=None):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.epsilon)
        y = y * params["scale"] + params["offset"]
        return y.astype(x.dtype), state

    def get_config(self):
        return {"epsilon": self.epsilon}


@register_layer
class RMSNorm(Layer):
    def __init__(self, epsilon: float = 1e-6):
        self.epsilon = float(epsilon)

    def init(self, rng, input_shape):
        dim = input_shape[-1]
        return {"scale": jnp.ones((dim,))}, {}, tuple(input_shape)

    def apply(self, params, state, x, *, training=False, rng=None):
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(
            jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + self.epsilon)
        return (y * params["scale"]).astype(x.dtype), state

    def get_config(self):
        return {"epsilon": self.epsilon}


@register_layer
class PositionalEmbedding(Layer):
    """Learned absolute position embeddings added to a [B, S, D] input.

    Under sequence parallelism the input holds one shard of the sequence, so
    set ``seq_axis_name`` to the mesh axis the sequence is sharded over: the
    layer then offsets into the table by ``axis_index * shard_len`` to use
    GLOBAL positions (mirroring the RoPE handling in MultiHeadAttention).
    """

    scope = "embed"

    def __init__(self, max_len: int, seq_axis_name: Optional[str] = None):
        self.max_len = int(max_len)
        self.seq_axis_name = seq_axis_name

    def init(self, rng, input_shape):
        dim = input_shape[-1]
        params = {"embeddings": init_weights("uniform_scaling", rng,
                                             (self.max_len, dim))}
        return params, {}, tuple(input_shape)

    def apply(self, params, state, x, *, training=False, rng=None):
        s = x.shape[1]
        if self.seq_axis_name and self._axis_bound():
            # fail loudly if the table can't cover the GLOBAL sequence —
            # dynamic_slice would silently clamp out-of-range shard starts
            global_len = s * jax.lax.axis_size(self.seq_axis_name)
            if global_len > self.max_len:
                raise ValueError(
                    f"PositionalEmbedding(max_len={self.max_len}) is too "
                    f"small for global sequence length {global_len} "
                    f"({s} per shard over axis '{self.seq_axis_name}')")
            start = jax.lax.axis_index(self.seq_axis_name) * s
            emb = jax.lax.dynamic_slice_in_dim(params["embeddings"],
                                               start, s, axis=0)
        else:
            emb = params["embeddings"][:s]
        return x + emb[None].astype(x.dtype), state

    def _axis_bound(self) -> bool:
        """True when tracing inside a shard_map that binds the axis. Outside
        (e.g. unsharded eval via model.predict) the input holds the FULL
        sequence, so shard-local slicing is the correct behavior."""
        try:
            jax.lax.axis_size(self.seq_axis_name)
            return True
        except NameError:
            return False

    def get_config(self):
        return {"max_len": self.max_len,
                "seq_axis_name": self.seq_axis_name}


def _attention_compute(q, k, v, *, causal, impl, axis_name=None,
                       ring_block_size=None, window=None,
                       segment_ids=None, block_len=None, scale=None):
    """Dispatch on attention implementation. q/k/v are BSHD.

    ``segment_ids`` (packed sequences) flows to EVERY impl (round 4):
    flash/xla mask in-kernel; ring rotates the k-side ids with their K/V
    shards; Ulysses all-gathers the ids alongside its head-scatter. For
    the sequence-parallel impls the ids are the local [B, S_local] shard.
    ``scale`` multiplies the scores (None: the root of the head width);
    the sequence-parallel impls take the default only.
    """
    if impl == "auto":
        # measured on TPU v5e (bench.py --model lm): the Pallas flash
        # kernel (in-kernel backward) trains 2.15x faster than fused XLA
        # attention at seq 2048; off-TPU the kernel only runs in
        # interpreter mode, where XLA wins
        impl = "flash" if backend_is_tpu() else "xla"
    if impl == "flash":
        from distkeras_tpu.ops.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, window=window,
                               segment_ids=segment_ids,
                               block_len=block_len, scale=scale)
    if scale is not None and impl != "xla":
        raise ValueError(f"an attention scale is not built for "
                         f"attn_impl={impl!r}")
    if block_len is not None and impl != "xla":
        raise ValueError(
            f"block-causal attention is not supported with "
            f"attn_impl={impl!r}")
    if window is not None and impl in ("ring", "ulysses",
                                       "ulysses_flash"):
        raise ValueError(
            f"attn_window is not supported with attn_impl={impl!r} "
            "(sequence-parallel paths have no windowed variant yet)")
    if impl == "ring":
        if not axis_name:
            raise ValueError(
                "attn_impl='ring' requires seq_axis_name (the mesh axis the "
                "sequence is sharded over, e.g. 'sp' from parallel.mesh); "
                "without it RoPE positions and causal masks would silently "
                "use shard-local coordinates")
        from distkeras_tpu.ops.ring_attention import ring_attention
        return ring_attention(q, k, v, axis_name=axis_name, causal=causal,
                              block_size=ring_block_size,
                              segment_ids=segment_ids)
    if impl in ("ulysses", "ulysses_flash"):
        if not axis_name:
            raise ValueError(
                "attn_impl='ulysses' requires seq_axis_name (the mesh axis "
                "the sequence is sharded over); without it RoPE positions "
                "and causal masks would silently use shard-local "
                "coordinates")
        from distkeras_tpu.ops.ulysses import ulysses_attention
        return ulysses_attention(
            q, k, v, axis_name=axis_name, causal=causal,
            impl="flash" if impl == "ulysses_flash" else "xla",
            segment_ids=segment_ids)
    note_path("flash_attention", "xla_reference")
    return dot_product_attention(q, k, v, causal=causal, window=window,
                                 segment_ids=segment_ids,
                                 block_len=block_len, scale=scale)


@register_layer
class MultiHeadAttention(Layer):
    """Multi-head self-attention over [B, S, d_model].

    Projections are single einsums against ``[d_model, H, Dh]`` tensors —
    one MXU matmul each; the heads axis is the TP shard axis.

    ``num_kv_heads < num_heads`` gives grouped-query attention (GQA;
    ``num_kv_heads=1`` is multi-query): K/V project to fewer heads, each
    shared by ``num_heads // num_kv_heads`` query heads. Training-side
    the shared heads are broadcast before the kernel (compute is
    matmul-dominated either way); the payoff is serving — the KV cache
    shrinks by the group factor (``models.decoding`` sizes it by
    ``num_kv_heads``).

    ``qk_norm`` adds an RMSNorm (epsilon 1e-6, one learned scale of
    ``head_dim``) over every head's query and key before RoPE.
    ``attn_scale`` multiplies the scores in place of ``head_dim ** -0.5``
    (a published attention multiplier); ``use_rope=False`` with no
    position layer in the stack is attention with no position
    embedding at all (NoPE).
    ``rope_base`` is RoPE's theta; ``rotary_dim`` rotates only the
    first so many dimensions of each head, ``rope_yarn`` gives YaRN's
    frequencies and attention factor (``ops.attention.yarn_inv_freq``).
    ``block_len=B`` makes the causal
    mask BLOCK-causal (position ``i`` sees key ``j`` iff
    ``j // B <= i // B``): the attention of a block-diffusion language
    model, which ``ServingEngine`` decodes a block at a time. Not with
    a window or a sequence-parallel ``attn_impl``; the flash kernel
    runs it forward only.
    """

    def __init__(self, num_heads: int, head_dim: Optional[int] = None,
                 causal: bool = True, use_rope: bool = True,
                 dtype: str = "float32", attn_impl: str = "auto",
                 seq_axis_name: Optional[str] = None,
                 kernel_init: str = "glorot_uniform",
                 ring_block_size: Optional[int] = None,
                 num_kv_heads: Optional[int] = None,
                 rope_scale: float = 1.0,
                 attn_window: Optional[int] = None,
                 qk_norm: bool = False, rope_base: float = 10000.0,
                 block_len: Optional[int] = None,
                 rotary_dim: Optional[int] = None,
                 rope_yarn: Optional[dict] = None,
                 attn_scale: Optional[float] = None):
        #: the scores' multiplier (None: the root of the head width)
        self.attn_scale = None if attn_scale is None else float(attn_scale)
        self.rope_scale = float(rope_scale)
        self.rope_base = float(rope_base)
        #: RoPE over the first ``rotary_dim`` dimensions of each head
        #: only (None: the whole head)
        self.rotary_dim = None if rotary_dim is None else int(rotary_dim)
        #: YaRN: ``{"factor", "original_max_position_embeddings",
        #: "beta_fast", "beta_slow", "attention_factor"}`` (the last
        #: three optional), as a published ``rope_parameters`` group
        self.rope_yarn = None if rope_yarn is None else dict(rope_yarn)
        self._rope_tables = {}           # rotary width -> (inv_freq, mscale)
        self.qk_norm = bool(qk_norm)
        self.block_len = None if block_len is None else int(block_len)
        if self.block_len is not None and (
                self.block_len < 1 or not causal
                or attn_window is not None):
            raise ValueError(
                "block_len must be >= 1 and needs causal=True and no "
                "attn_window")
        #: causal sliding window (Mistral-style SWA): each query attends
        #: to at most the last attn_window keys. None = full causal.
        self.attn_window = (int(attn_window) if attn_window is not None
                            else None)
        if self.attn_window is not None and not causal:
            raise ValueError("attn_window requires causal=True")
        self.num_heads = int(num_heads)
        self.num_kv_heads = (int(num_kv_heads) if num_kv_heads is not None
                             else None)
        kv = self.num_kv_heads if self.num_kv_heads is not None \
            else self.num_heads
        if kv < 1 or self.num_heads % kv:
            raise ValueError(
                f"num_kv_heads must be a positive divisor of num_heads "
                f"{self.num_heads}, got {kv}")
        self.head_dim = head_dim if head_dim is None else int(head_dim)
        self.causal = bool(causal)
        self.use_rope = bool(use_rope)
        self.dtype = dtype
        self.attn_impl = attn_impl
        self.seq_axis_name = seq_axis_name
        self.kernel_init = kernel_init
        self.ring_block_size = ring_block_size  # inner k-blocking (memory)

    #: packed-sequence capability marker (Sequential forwards segment_ids
    #: only to layers declaring this — containers forward recursively)
    accepts_segment_ids = True

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    def softmax_scale(self, head_dim: int) -> float:
        """The scores' multiplier: ``attn_scale``, else the root of the
        head width."""
        if self.attn_scale is not None:
            return self.attn_scale
        return (self.head_dim or head_dim) ** -0.5

    def init(self, rng, input_shape):
        d_model = input_shape[-1]
        h, dh = self.num_heads, self.head_dim or d_model // self.num_heads
        hkv = self.kv_heads
        ks = jax.random.split(rng, 4)
        # initialize as the LOGICAL 2D matrices and reshape: the generic
        # fan rules would treat [d_model, H, Dh] as a conv kernel and
        # inflate both fans by the leading axis, shrinking the init scale
        w2d = lambda k, m, n: init_weights(self.kernel_init, k, (m, n))
        params = {
            "wq": w2d(ks[0], d_model, h * dh).reshape(d_model, h, dh),
            "wk": w2d(ks[1], d_model, hkv * dh).reshape(d_model, hkv, dh),
            "wv": w2d(ks[2], d_model, hkv * dh).reshape(d_model, hkv, dh),
            "wo": w2d(ks[3], h * dh, d_model).reshape(h, dh, d_model),
        }
        if self.qk_norm:
            params["q_norm"] = jnp.ones((dh,))
            params["k_norm"] = jnp.ones((dh,))
        return params, {}, tuple(input_shape)

    def rope(self, x, positions, layout: str = "bshd"):
        """RoPE with this layer's base, position scale, rotary share
        and (YaRN) frequency table."""
        if self.rotary_dim is None and self.rope_yarn is None:
            return apply_rope(x, positions, base=self.rope_base,
                              layout=layout, scale=self.rope_scale)
        rot = self.rotary_dim or x.shape[-1]
        if rot not in self._rope_tables:
            inv, mscale = None, 1.0
            if self.rope_yarn is not None:
                from distkeras_tpu.ops.attention import (
                    yarn_attention_factor, yarn_inv_freq)
                y = self.rope_yarn
                inv = yarn_inv_freq(
                    rot, self.rope_base, y["factor"],
                    y["original_max_position_embeddings"],
                    y.get("beta_fast") or 32.0, y.get("beta_slow") or 1.0,
                    y.get("truncate", True))
                mscale = y.get("attention_factor")
                if mscale is None:
                    mscale = yarn_attention_factor(y["factor"])
            self._rope_tables[rot] = (inv, float(mscale))
        inv, mscale = self._rope_tables[rot]
        return apply_rope(x, positions, base=self.rope_base, layout=layout,
                          scale=self.rope_scale, rotary_dim=rot,
                          inv_freq=inv, mscale=mscale)

    def normed_qk(self, params, q, k):
        """The per-head RMSNorm of queries and keys (``qk_norm``); the
        head dimension is last in every layout."""
        if not self.qk_norm:
            return q, k
        norm = RMSNorm(1e-6)
        return (norm.apply({"scale": params["q_norm"]}, {}, q)[0],
                norm.apply({"scale": params["k_norm"]}, {}, k)[0])

    def _expand_kv(self, t, head_axis: int):
        """Broadcast grouped K/V heads up to num_heads for the kernels."""
        reps = self.num_heads // self.kv_heads
        return t if reps == 1 else jnp.repeat(t, reps, axis=head_axis)

    def apply(self, params, state, x, *, training=False, rng=None,
              segment_ids=None):
        dt = jnp.dtype(self.dtype)
        xc = x.astype(dt)
        impl = self.attn_impl
        if impl == "auto":
            impl = "flash" if backend_is_tpu() else "xla"
        positions = None
        if (self.use_rope
                and impl in ("ring", "ulysses", "ulysses_flash")
                and self.seq_axis_name):
            # global positions for this sequence shard
            idx = jax.lax.axis_index(self.seq_axis_name)
            positions = idx * x.shape[1] + jnp.arange(x.shape[1])

        if impl == "flash":
            # project straight to BHSD: the flash kernel's (B*H, S, D)
            # flattening is then a free reshape — no [B,S,H,D]<->[B,H,S,D]
            # transposes around the kernel in either pass (measured ~15%
            # of LM step time as explicit transpose ops)
            q = jnp.einsum("bsd,dhe->bhse", xc, params["wq"].astype(dt))
            k = jnp.einsum("bsd,dhe->bhse", xc, params["wk"].astype(dt))
            v = jnp.einsum("bsd,dhe->bhse", xc, params["wv"].astype(dt))
            q, k = self.normed_qk(params, q, k)
            if self.use_rope:
                q = self.rope(q, positions, layout="bhsd")
                k = self.rope(k, positions, layout="bhsd")
            k, v = self._expand_kv(k, 1), self._expand_kv(v, 1)
            from distkeras_tpu.ops.flash_attention import flash_attention
            out = flash_attention(q, k, v, causal=self.causal,
                                  layout="bhsd", window=self.attn_window,
                                  segment_ids=segment_ids,
                                  block_len=self.block_len,
                                  scale=self.attn_scale)
            y = jnp.einsum("bhse,hed->bsd", out, params["wo"].astype(dt))
            return y.astype(x.dtype), state

        q = jnp.einsum("bsd,dhe->bshe", xc, params["wq"].astype(dt))
        k = jnp.einsum("bsd,dhe->bshe", xc, params["wk"].astype(dt))
        v = jnp.einsum("bsd,dhe->bshe", xc, params["wv"].astype(dt))
        q, k = self.normed_qk(params, q, k)
        if self.use_rope:
            q = self.rope(q, positions)
            k = self.rope(k, positions)
        k, v = self._expand_kv(k, 2), self._expand_kv(v, 2)
        out = _attention_compute(q, k, v, causal=self.causal,
                                 impl=impl,
                                 axis_name=self.seq_axis_name,
                                 ring_block_size=self.ring_block_size,
                                 window=self.attn_window,
                                 segment_ids=segment_ids,
                                 block_len=self.block_len,
                                 scale=self.attn_scale)
        y = jnp.einsum("bshe,hed->bsd", out, params["wo"].astype(dt))
        return y.astype(x.dtype), state

    def get_config(self):
        return {"num_heads": self.num_heads, "head_dim": self.head_dim,
                "causal": self.causal, "use_rope": self.use_rope,
                "dtype": self.dtype, "attn_impl": self.attn_impl,
                "seq_axis_name": self.seq_axis_name,
                "kernel_init": self.kernel_init,
                "ring_block_size": self.ring_block_size,
                "num_kv_heads": self.num_kv_heads,
                "rope_scale": self.rope_scale,
                "attn_window": self.attn_window,
                "qk_norm": self.qk_norm, "rope_base": self.rope_base,
                "block_len": self.block_len,
                "rotary_dim": self.rotary_dim, "rope_yarn": self.rope_yarn,
                "attn_scale": self.attn_scale}


@register_layer
class LatentAttention(Layer):
    """Multi-head LATENT attention (MLA) over [B, S, d_model]: queries
    through a low-rank pair with a norm between, and ONE shared latent
    of ``kv_lora_rank`` values plus ONE shared rope key of
    ``qk_rope_head_dim`` a token, from which every head's key and
    value are up-projected::

        cq        = q_scale  * n_q(x Wqa)                 [q_lora_rank]
        [qn | qr] = cq Wqb              per head          [H, dn | dr]
        [ckv| kr] = x Wkva              ONE per token     [kv_lora_rank | dr]
        c         = kv_scale * n_kv(ckv)
        [kn | v]  = c Wkvb              per head          [H, dn | dv]
        score     = (qn.kn + RoPE(qr).RoPE(kr)) / sqrt(dn + dr), causal

    What a serving cache keeps of a token is ``(c, RoPE(kr))``:
    ``latent_dim = kv_lora_rank + dr`` values and no head axis
    (``models.decoding`` / ``serving.kv_pool``: a latent page plane).
    Prefill attends the per-head keys and values rebuilt from the
    latent (query/key width ``dn + dr``, value width ``dv``); decode
    takes ``Wkvb``'s key half into the query and its value half into
    the output (:meth:`absorb_q` / :meth:`unabsorb_v`) and attends the
    latent itself, every head over the one shared key whose first
    ``kv_lora_rank`` values are also the value. Same function, other
    association. ``q_scale`` / ``kv_scale`` multiply the normed
    low-rank query and the normed latent (so ``kn`` and ``v``, not
    ``kr``). No bias anywhere; RoPE rotates pairs ``(2i, 2i+1)`` as
    ``ops.attention.apply_rope``.
    """

    accepts_segment_ids = False
    #: what the serving paths read of any attention layer
    attn_window = None
    block_len = None
    use_rope = True
    kv_heads = 1

    def __init__(self, num_heads: int, q_lora_rank: int, kv_lora_rank: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int,
                 v_head_dim: int, rope_base: float = 10000.0,
                 q_scale: float = 1.0, kv_scale: float = 1.0,
                 norm_eps: float = 1e-6, dtype: str = "float32",
                 attn_impl: str = "auto",
                 kernel_init: str = "glorot_uniform"):
        self.num_heads = int(num_heads)
        self.q_lora_rank = int(q_lora_rank)
        self.kv_lora_rank = int(kv_lora_rank)
        self.qk_nope_head_dim = int(qk_nope_head_dim)
        self.qk_rope_head_dim = int(qk_rope_head_dim)
        self.v_head_dim = int(v_head_dim)
        self.rope_base = float(rope_base)
        self.q_scale = float(q_scale)
        self.kv_scale = float(kv_scale)
        self.norm_eps = float(norm_eps)
        self.dtype = dtype
        if attn_impl not in ("auto", "xla", "flash"):
            raise ValueError(
                f"latent attention runs attn_impl 'auto', 'xla' or "
                f"'flash', got {attn_impl!r}")
        self.attn_impl = attn_impl
        self.kernel_init = kernel_init
        #: query/key width of a head: the softmax scale is its root
        self.head_dim = self.qk_nope_head_dim + self.qk_rope_head_dim
        #: values a token leaves in a serving cache
        self.latent_dim = self.kv_lora_rank + self.qk_rope_head_dim
        self.scale = self.head_dim ** -0.5

    def init(self, rng, input_shape):
        d = input_shape[-1]
        h, dn, dr, dv = (self.num_heads, self.qk_nope_head_dim,
                         self.qk_rope_head_dim, self.v_head_dim)
        qr, kr = self.q_lora_rank, self.kv_lora_rank
        ks = jax.random.split(rng, 5)
        w2d = lambda k, m, n: init_weights(self.kernel_init, k, (m, n))
        params = {
            "wqa": w2d(ks[0], d, qr), "q_norm": jnp.ones((qr,)),
            "wqb": w2d(ks[1], qr, h * (dn + dr)).reshape(qr, h, dn + dr),
            "wkva": w2d(ks[2], d, kr + dr), "kv_norm": jnp.ones((kr,)),
            "wkvb": w2d(ks[3], kr, h * (dn + dv)).reshape(kr, h, dn + dv),
            "wo": w2d(ks[4], h * dv, d).reshape(h, dv, d),
        }
        return params, {}, tuple(input_shape)

    def _norm(self, scale, x, mult: float):
        y, _ = RMSNorm(self.norm_eps).apply({"scale": scale}, {}, x)
        return y if mult == 1.0 else (y.astype(jnp.float32)
                                      * mult).astype(y.dtype)

    def project(self, params, xc, positions=None):
        """``xc`` [B, S, d] in the compute dtype, at ``positions`` ([S]
        or [B, S]; None: 0..S-1). Returns ``(qn [B, S, H, dn], qr
        [B, S, H, dr] after RoPE, entry [B, S, latent_dim])``: the
        queries, and what the cache keeps of each token (the scaled
        normed latent, then the roped shared key)."""
        dt = xc.dtype
        dn = self.qk_nope_head_dim
        cq = self._norm(params["q_norm"], xc @ params["wqa"].astype(dt),
                        self.q_scale)
        q = jnp.einsum("bsr,rhe->bshe", cq, params["wqb"].astype(dt))
        ckv = xc @ params["wkva"].astype(dt)
        c = self._norm(params["kv_norm"], ckv[..., :self.kv_lora_rank],
                       self.kv_scale)
        kr = apply_rope(ckv[..., None, self.kv_lora_rank:], positions,
                        base=self.rope_base)[..., 0, :]
        qr = apply_rope(q[..., dn:], positions, base=self.rope_base)
        return q[..., :dn], qr, jnp.concatenate([c, kr], axis=-1)

    def expand_kv(self, params, entry, dt):
        """Per-head keys and values of cached tokens ``entry``
        [B, T, latent_dim], head-major: ``(k [B, H, T, dn + dr],
        v [B, H, T, dv])``; the shared rope key repeats under every
        head."""
        dn, r = self.qk_nope_head_dim, self.kv_lora_rank
        kv = jnp.einsum("btc,che->bhte", entry[..., :r].astype(dt),
                        params["wkvb"].astype(dt))
        kr = jnp.broadcast_to(
            entry[:, None, :, r:].astype(dt),
            kv.shape[:3] + (self.qk_rope_head_dim,))
        return jnp.concatenate([kv[..., :dn], kr], axis=-1), kv[..., dn:]

    def absorb_q(self, params, qn, qr):
        """The decode form's queries against the latent itself:
        ``Wkvb``'s key half taken into ``qn``; [..., H, latent_dim]."""
        wk = params["wkvb"][..., :self.qk_nope_head_dim].astype(qn.dtype)
        return jnp.concatenate(
            [jnp.einsum("bshn,chn->bshc", qn, wk), qr], axis=-1)

    def unabsorb_v(self, params, o_lat, dt):
        """Attention output over the latent ``o_lat`` [B, S, H,
        kv_lora_rank] through ``Wkvb``'s value half and ``Wo``:
        [B, S, d]."""
        wv = params["wkvb"][..., self.qk_nope_head_dim:].astype(dt)
        v = jnp.einsum("bshc,chv->bshv", o_lat.astype(dt), wv)
        return jnp.einsum("bshv,hvd->bsd", v, params["wo"].astype(dt))

    def apply(self, params, state, x, *, training=False, rng=None):
        dt = jnp.dtype(self.dtype)
        qn, qr, entry = self.project(params, x.astype(dt))
        k, v = self.expand_kv(params, entry, dt)
        q = jnp.concatenate([qn, qr], axis=-1)
        out = _attention_compute(
            q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            causal=True, impl=self.attn_impl)
        y = jnp.einsum("bshv,hvd->bsd", out.astype(dt),
                       params["wo"].astype(dt))
        return y.astype(x.dtype), state

    def get_config(self):
        return {"num_heads": self.num_heads,
                "q_lora_rank": self.q_lora_rank,
                "kv_lora_rank": self.kv_lora_rank,
                "qk_nope_head_dim": self.qk_nope_head_dim,
                "qk_rope_head_dim": self.qk_rope_head_dim,
                "v_head_dim": self.v_head_dim,
                "rope_base": self.rope_base, "q_scale": self.q_scale,
                "kv_scale": self.kv_scale, "norm_eps": self.norm_eps,
                "dtype": self.dtype, "attn_impl": self.attn_impl,
                "kernel_init": self.kernel_init}


@register_layer
class TransformerMLP(Layer):
    """Position-wise MLP with the standard column→row TP-splittable pair.

    ``gated`` makes it the gated form (SwiGLU with ``activation="silu"``):
    ``w2(act(x w1) * (x w3))``, three matrices; ``use_bias=False`` drops
    ``b1``/``b2`` (gated MLPs are published without)."""

    def __init__(self, hidden_dim: int, activation: str = "gelu",
                 dtype: str = "float32",
                 kernel_init: str = "glorot_uniform",
                 gated: bool = False, use_bias: bool = True):
        self.hidden_dim = int(hidden_dim)
        self.activation = activation
        self.dtype = dtype
        self.kernel_init = kernel_init
        self.gated = bool(gated)
        self.use_bias = bool(use_bias)

    def init(self, rng, input_shape):
        d = input_shape[-1]
        k1, k2, k3 = jax.random.split(rng, 3)
        params = {
            "w1": init_weights(self.kernel_init, k1, (d, self.hidden_dim)),
            "w2": init_weights(self.kernel_init, k2, (self.hidden_dim, d)),
        }
        if self.gated:
            params["w3"] = init_weights(self.kernel_init, k3,
                                        (d, self.hidden_dim))
        if self.use_bias:
            params["b1"] = jnp.zeros((self.hidden_dim,))
            params["b2"] = jnp.zeros((d,))
        return params, {}, tuple(input_shape)

    def apply(self, params, state, x, *, training=False, rng=None):
        dt = jnp.dtype(self.dtype)
        act = get_activation(self.activation)
        xc = x.astype(dt)
        h = xc @ params["w1"].astype(dt)
        if self.use_bias:
            h = h + params["b1"].astype(dt)
        h = act(h)
        if self.gated:
            h = h * (xc @ params["w3"].astype(dt))
        y = h @ params["w2"].astype(dt)
        if self.use_bias:
            y = y + params["b2"].astype(dt)
        return y.astype(x.dtype), state

    def get_config(self):
        return {"hidden_dim": self.hidden_dim, "activation": self.activation,
                "dtype": self.dtype, "kernel_init": self.kernel_init,
                "gated": self.gated, "use_bias": self.use_bias}


@register_layer
class TransformerBlock(Layer):
    """Pre-norm residual block: x + attn(norm(x)); x + mlp(norm(x)).

    ``mlp`` may be a ``TransformerMLP`` or a ``models.moe.MoE`` (expert
    parallelism); both expose the same Layer protocol. ``attn_layer``
    takes the attention's place likewise (a ``LatentAttention``, or a
    ``models.mamba.Mamba2Mixer``: a state-space layer);
    ``norm_eps`` states the norms' epsilon; ``residual_scale``
    multiplies both branches before they join the residual stream
    (``x + s * attn(n1(x))``, then ``x + s * mlp(n2(x))``);
    ``attn_scale`` is the attention's ``attn_scale``.

    A SHORTCUT-CONNECTED expert layer spans two blocks. The first
    (``shortcut_layer=``) computes it from its own post-attention norm,
    beside its MLP, and hands the result on beside the residual
    stream: it returns the pair ``(x, m)``. The second
    (``shortcut_add=True``) takes that pair and adds ``m`` after its
    own MLP::

        a = h + attn_0(n1(h));  u = n2(a);  m = experts(u);  h = a + mlp_0(u)
        a = h + attn_1(n1(h));  u = n2(a);  h = a + mlp_1(u) + m
    """

    accepts_segment_ids = True

    def __init__(self, num_heads: int, mlp_ratio: int = 4,
                 head_dim: Optional[int] = None, causal: bool = True,
                 use_rope: bool = True, activation: str = "gelu",
                 norm: str = "rmsnorm", dtype: str = "float32",
                 attn_impl: str = "auto",
                 seq_axis_name: Optional[str] = None,
                 mlp_layer: Optional[Layer] = None,
                 dropout_rate: float = 0.0,
                 ring_block_size: Optional[int] = None,
                 num_kv_heads: Optional[int] = None,
                 rope_scale: float = 1.0,
                 attn_window: Optional[int] = None,
                 qk_norm: bool = False, rope_base: float = 10000.0,
                 block_len: Optional[int] = None,
                 mlp_dim: Optional[int] = None, mlp_gated: bool = False,
                 mlp_bias: bool = True,
                 rotary_dim: Optional[int] = None,
                 rope_yarn: Optional[dict] = None,
                 attn_layer: Optional[Layer] = None,
                 shortcut_layer: Optional[Layer] = None,
                 shortcut_add: bool = False,
                 norm_eps: Optional[float] = None,
                 residual_scale: float = 1.0,
                 attn_scale: Optional[float] = None):
        self.num_heads = int(num_heads)
        self.residual_scale = float(residual_scale)
        self.attn_scale = attn_scale
        self.rotary_dim = rotary_dim
        self.rope_yarn = rope_yarn
        self.norm_eps = None if norm_eps is None else float(norm_eps)
        #: the expert layer this block computes beside its MLP and hands
        #: on (class doc), and whether it takes one handed on
        self.shortcut = shortcut_layer
        self.shortcut_add = bool(shortcut_add)
        if self.shortcut is not None and self.shortcut_add:
            raise ValueError("a block hands a shortcut layer's output on "
                             "or adds one, not both")
        self._attn_override = attn_layer
        self.qk_norm = bool(qk_norm)
        self.rope_base = float(rope_base)
        self.block_len = block_len
        #: the MLP's stated hidden width (None: ``mlp_ratio * d_model``)
        self.mlp_dim = None if mlp_dim is None else int(mlp_dim)
        self.mlp_gated = bool(mlp_gated)
        self.mlp_bias = bool(mlp_bias)
        self.num_kv_heads = num_kv_heads
        self.rope_scale = float(rope_scale)
        self.attn_window = attn_window
        self.mlp_ratio = int(mlp_ratio)
        self.head_dim = head_dim
        self.causal = causal
        self.use_rope = use_rope
        self.activation = activation
        self.norm = norm
        self.dtype = dtype
        self.attn_impl = attn_impl
        self.seq_axis_name = seq_axis_name
        self.dropout_rate = float(dropout_rate)
        self.ring_block_size = ring_block_size
        self._mlp_override = mlp_layer

        norm_cls = RMSNorm if norm == "rmsnorm" else LayerNorm
        norm_kw = {} if norm_eps is None else {"epsilon": norm_eps}
        self.norm1 = norm_cls(**norm_kw)
        self.norm2 = norm_cls(**norm_kw)
        self._dropout = Dropout(self.dropout_rate)
        self.attn = attn_layer or MultiHeadAttention(
            num_heads, head_dim=head_dim, causal=causal, use_rope=use_rope,
            dtype=dtype, attn_impl=attn_impl, seq_axis_name=seq_axis_name,
            ring_block_size=ring_block_size, num_kv_heads=num_kv_heads,
            rope_scale=rope_scale, attn_window=attn_window,
            qk_norm=qk_norm, rope_base=rope_base, block_len=block_len,
            rotary_dim=rotary_dim, rope_yarn=rope_yarn,
            attn_scale=attn_scale)
        self.mlp = mlp_layer  # resolved in init once d_model is known

    def init(self, rng, input_shape):
        d_model = input_shape[-1]
        if self._mlp_override is None:
            # re-resolve on every init: the hidden dim tracks d_model, so a
            # block instance re-initialized at a different width must not
            # keep the previous width's MLP
            self.mlp = TransformerMLP(
                self.mlp_dim or self.mlp_ratio * d_model,
                activation=self.activation, dtype=self.dtype,
                gated=self.mlp_gated, use_bias=self.mlp_bias)
        ks = jax.random.split(rng, 4)
        p, s = {}, {}
        for name, layer, k in (("norm1", self.norm1, ks[0]),
                               ("attn", self.attn, ks[1]),
                               ("norm2", self.norm2, ks[2]),
                               ("mlp", self.mlp, ks[3])):
            p[name], s[name], _ = layer.init(k, tuple(input_shape))
        if self.shortcut is not None:
            p["shortcut"], s["shortcut"], _ = self.shortcut.init(
                jax.random.fold_in(ks[3], 1), tuple(input_shape))
        return p, s, tuple(input_shape)

    def residual(self, x, y):
        """``x + y`` on the residual stream, ``y`` times
        ``residual_scale``, in the stream's type."""
        if self.residual_scale != 1.0:
            y = y * self.residual_scale
        return x + y.astype(x.dtype)

    def apply(self, params, state, x, *, training=False, rng=None,
              segment_ids=None):
        new_state = dict(state)
        carry = None
        if self.shortcut_add:
            x, carry = x
        seg_kw = {"segment_ids": segment_ids} if getattr(
            self.attn, "accepts_segment_ids", False) else {}
        with jax.named_scope("attn"):
            h, new_state["norm1"] = self.norm1.apply(
                params["norm1"], state["norm1"], x, training=training)
            a, new_state["attn"] = self.attn.apply(
                params["attn"], state["attn"], h, training=training,
                **seg_kw)

        def drop(y, key):  # both residual branches share the Dropout layer
            return self._dropout.apply({}, {}, y, training=training,
                                       rng=key)[0]

        # independent keys per consumer: an rng-consuming mlp_layer must not
        # derive keys that collide with the block's own dropout keys
        k_drop1 = k_mlp = k_drop2 = None
        if rng is not None:
            k_drop1, k_mlp, k_drop2 = jax.random.split(rng, 3)
        use_dropout = self.dropout_rate and training and rng is not None
        if use_dropout:
            a = drop(a, k_drop1)
        x = self.residual(x, a)
        with jax.named_scope("mlp"):
            h, new_state["norm2"] = self.norm2.apply(
                params["norm2"], state["norm2"], x, training=training)
            m, new_state["mlp"] = self.mlp.apply(
                params["mlp"], state["mlp"], h, training=training,
                rng=k_mlp)
            if self.shortcut is not None:
                handed, new_state["shortcut"] = self.shortcut.apply(
                    params["shortcut"], state["shortcut"], h,
                    training=training, rng=k_mlp)
        if use_dropout:
            m = drop(m, k_drop2)
        if self.shortcut is not None:
            return (x + m, handed), new_state
        if carry is not None:
            return x + m + carry, new_state
        return self.residual(x, m), new_state

    def sub_layers(self):
        subs = {"norm1": self.norm1, "attn": self.attn,
                "norm2": self.norm2, "mlp": self.mlp}
        if self.shortcut is not None:
            subs["shortcut"] = self.shortcut
        return subs

    def get_config(self):
        cfg = {"num_heads": self.num_heads, "mlp_ratio": self.mlp_ratio,
               "head_dim": self.head_dim, "causal": self.causal,
               "use_rope": self.use_rope, "activation": self.activation,
               "norm": self.norm, "dtype": self.dtype,
               "attn_impl": self.attn_impl,
               "seq_axis_name": self.seq_axis_name,
               "dropout_rate": self.dropout_rate,
               "ring_block_size": self.ring_block_size,
               "num_kv_heads": self.num_kv_heads,
               "rope_scale": self.rope_scale,
               "attn_window": self.attn_window,
               "qk_norm": self.qk_norm, "rope_base": self.rope_base,
               "block_len": self.block_len, "mlp_dim": self.mlp_dim,
               "mlp_gated": self.mlp_gated, "mlp_bias": self.mlp_bias,
               "rotary_dim": self.rotary_dim, "rope_yarn": self.rope_yarn}
        if self.norm_eps is not None:
            cfg["norm_eps"] = self.norm_eps
        if self.residual_scale != 1.0:
            cfg["residual_scale"] = self.residual_scale
        if self.attn_scale is not None:
            cfg["attn_scale"] = self.attn_scale
        if self.shortcut_add:
            cfg["shortcut_add"] = True
        for key, layer in (("mlp_layer", self._mlp_override),
                           ("attn_layer", self._attn_override),
                           ("shortcut_layer", self.shortcut)):
            if layer is not None:
                cfg[key] = layer_spec(layer)
        return cfg

    @classmethod
    def from_config(cls, config):
        config = dict(config)
        for key in ("mlp_layer", "attn_layer", "shortcut_layer"):
            spec = config.pop(key, None)
            if spec is not None:
                config[key] = layer_from_spec(spec)
        return cls(**config)
