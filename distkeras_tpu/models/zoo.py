"""Model zoo: builders for every BASELINE evaluation config.

  1. ``mlp``               — SingleTrainer MNIST MLP (config 1)
  2. ``lenet5``            — ADAG LeNet-5 on CIFAR-10 (config 2)
  3. ``resnet50``          — AEASGD ResNet-50 on ImageNet (config 3)
  4. ``wide_and_deep``     — DOWNPOUR wide&deep on Criteo (config 4)
  5. ``bilstm_classifier`` — Predictor batched BiLSTM inference (config 5)

The reference builds these ad hoc in example notebooks; here they are
first-class builders returning ``Sequential`` specs (build with
``Model.build(spec, input_shape)``).

TPU notes: convs/matmuls accept ``dtype='bfloat16'`` for MXU-friendly mixed
precision; ResNet uses NHWC + BatchNorm with optional cross-replica
``axis_name``.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

from distkeras_tpu.models.blocks import Residual, WideAndDeep
from distkeras_tpu.models.core import Sequential
from distkeras_tpu.models.layers import (
    Activation, BatchNorm, Conv2D, Dense, DepthwiseConv2D, Dropout,
    Embedding, Flatten, GlobalAveragePooling2D, MaxPooling2D)
from distkeras_tpu.models.recurrent import LSTM, Bidirectional


def mlp(hidden: Sequence[int] = (512, 256), num_classes: int = 10,
        activation: str = "relu", dropout: float = 0.0,
        dtype: str = "float32") -> Sequential:
    """MNIST-style MLP (BASELINE config 1; the reference's
    ``examples/mnist.py`` MLP equivalent)."""
    layers = []
    for h in hidden:
        layers.append(Dense(h, activation=activation, dtype=dtype))
        if dropout > 0:
            layers.append(Dropout(dropout))
    layers.append(Dense(num_classes, dtype=dtype))
    return Sequential(layers)


def lenet5(num_classes: int = 10, dtype: str = "float32") -> Sequential:
    """LeNet-5 (BASELINE config 2: ADAG on CIFAR-10). Classic topology,
    NHWC, tanh activations as in the original."""
    return Sequential([
        Conv2D(6, 5, padding="SAME", activation="tanh", dtype=dtype),
        MaxPooling2D(2),
        Conv2D(16, 5, padding="VALID", activation="tanh", dtype=dtype),
        MaxPooling2D(2),
        Flatten(),
        Dense(120, activation="tanh", dtype=dtype),
        Dense(84, activation="tanh", dtype=dtype),
        Dense(num_classes, dtype=dtype),
    ])


def _resnet_norm(norm: str, bn_axis_name: Optional[str],
                 norm_groups: int = 32):
    """Norm factory for the resnet family: ``"batch"`` (reference-standard
    BN) or ``"group"`` (GroupNorm-32, Wu & He 2018 — no batch statistics,
    so no cross-replica stats axis, identical train/eval, and on TPU no
    f32 stats-reduction epilogue fused after every conv; see docs/PERF.md
    for the measured profile share of BN statistics)."""
    if norm == "batch":
        return lambda: BatchNorm(axis_name=bn_axis_name)
    if norm == "group":
        from distkeras_tpu.models.layers import GroupNorm
        return lambda: GroupNorm(groups=norm_groups)
    raise ValueError(f"norm must be 'batch' or 'group', got {norm!r}")


def _bottleneck(filters: int, stride: int, project: bool,
                dtype: str, bn_axis_name: Optional[str],
                norm: str = "batch", norm_groups: int = 32) -> Residual:
    """ResNet-v1.5 bottleneck: 1x1 -> 3x3(stride) -> 1x1(4f), norm after
    each conv, relu after the residual add."""
    bn = _resnet_norm(norm, bn_axis_name, norm_groups)
    main = Sequential([
        Conv2D(filters, 1, use_bias=False, dtype=dtype), bn(),
        Activation("relu"),
        Conv2D(filters, 3, strides=stride, use_bias=False, dtype=dtype),
        bn(), Activation("relu"),
        Conv2D(4 * filters, 1, use_bias=False, dtype=dtype), bn(),
    ])
    shortcut = None
    if project:
        shortcut = Sequential([
            Conv2D(4 * filters, 1, strides=stride, use_bias=False,
                   dtype=dtype), bn(),
        ])
    return Residual(main, shortcut, activation="relu")


def resnet(stage_sizes: Sequence[int], num_classes: int = 1000,
           width: int = 64, dtype: str = "float32",
           bn_axis_name: Optional[str] = None,
           norm: str = "batch", norm_groups: int = 32) -> Sequential:
    """ResNet-v1.5 family over bottleneck blocks (NHWC). ``norm_groups``
    only applies to ``norm="group"`` and must divide every stage width."""
    layers = [
        Conv2D(width, 7, strides=2, use_bias=False, dtype=dtype),
        _resnet_norm(norm, bn_axis_name, norm_groups)(), Activation("relu"),
        MaxPooling2D(3, strides=2, padding="SAME"),
    ]
    filters = width
    for stage, blocks in enumerate(stage_sizes):
        for block in range(blocks):
            stride = 2 if (stage > 0 and block == 0) else 1
            project = (block == 0)
            layers.append(_bottleneck(filters, stride, project, dtype,
                                      bn_axis_name, norm, norm_groups))
        filters *= 2
    layers += [GlobalAveragePooling2D(), Dense(num_classes, dtype=dtype)]
    return Sequential(layers)


def resnet50(num_classes: int = 1000, dtype: str = "float32",
             bn_axis_name: Optional[str] = None,
             norm: str = "batch") -> Sequential:
    """ResNet-50 (BASELINE config 3 / the north-star model). ``norm=
    "group"`` gives the GroupNorm variant (different numerics — a model
    choice, not a drop-in BN replacement)."""
    return resnet([3, 4, 6, 3], num_classes, 64, dtype, bn_axis_name,
                  norm)


def resnet18_thin(num_classes: int = 10, width: int = 8,
                  dtype: str = "float32") -> Sequential:
    """A few-block thin ResNet for CPU-mesh tests (same topology family)."""
    return resnet([1, 1], num_classes, width, dtype)


def bilstm_classifier(units: int = 64, num_classes: int = 2,
                      dtype: str = "float32") -> Sequential:
    """BiLSTM sequence classifier (BASELINE config 5: batched Predictor
    inference over sharded data)."""
    return Sequential([
        Bidirectional(LSTM(units, return_sequences=True, dtype=dtype)),
        Bidirectional(LSTM(units, dtype=dtype)),
        Dense(num_classes, dtype=dtype),
    ])


def wide_and_deep(wide_dim: int, deep_hidden: Sequence[int] = (256, 128),
                  num_classes: int = 2, dtype: str = "float32") -> Sequential:
    """Wide & Deep for Criteo-style CTR (BASELINE config 4)."""
    return Sequential([
        WideAndDeep(wide_dim, deep_hidden, num_classes, dtype=dtype)])


def transformer_lm(vocab_size: int, d_model: int = 512, num_heads: int = 8,
                   num_layers: int = 6, mlp_ratio: int = 4,
                   max_len: Optional[int] = None, use_rope: bool = True,
                   norm: str = "rmsnorm", dtype: str = "float32",
                   attn_impl: str = "auto",
                   seq_axis_name: Optional[str] = None,
                   num_kv_heads: Optional[int] = None,
                   rope_scale: float = 1.0,
                   attn_window: Optional[int] = None,
                   moe_every: int = 0, num_experts: int = 0,
                   moe_expert_axis: Optional[str] = None,
                   moe_aux_loss_weight: float = 0.0,
                   moe_dispatch: str = "dense",
                   moe_capacity_factor: float = 1.25,
                   moe_expert_unroll: bool = False,
                   remat: Optional[str] = None,
                   head_dim: Optional[int] = None, qk_norm: bool = False,
                   rope_base: float = 10000.0,
                   block_len: Optional[int] = None,
                   mlp_dim: Optional[int] = None,
                   mlp_activation: str = "gelu", mlp_gated: bool = False,
                   mlp_bias: bool = True,
                   moe_top_k: int = 2,
                   layer_types: Optional[Sequence[str]] = None,
                   attn_kinds: Optional[dict] = None,
                   mlp_layer_types: Optional[Sequence[str]] = None,
                   dense_mlp_dim: Optional[int] = None,
                   moe_score: str = "softmax",
                   moe_norm_topk: bool = True,
                   moe_route_scale: float = 1.0,
                   moe_shared_dim: Optional[int] = None,
                   moe_zero_experts: int = 0,
                   moe_experts_held: Optional[Sequence[int]] = None,
                   moe_select_bias: bool = False,
                   norm_eps: Optional[float] = None,
                   embedding_multiplier: float = 1.0,
                   residual_multiplier: float = 1.0,
                   logits_scaling: float = 1.0,
                   tie_embeddings: bool = False) -> Sequential:
    """Decoder-only causal transformer LM — the long-context flagship.

    Absent from the reference (no attention models; SURVEY §5.7); this is
    the model the TP/SP/EP parallelism layers are exercised on. Tokens
    [B, S] int in, logits [B, S, vocab] out.

    ``moe_every=k`` (with ``num_experts``) swaps every k-th block's MLP for
    a mixture-of-experts layer (expert-parallel over ``moe_expert_axis``);
    ``moe_dispatch="tokens"`` uses the capacity-based cumsum dispatch
    (per-token expert FLOPs ~ top_k x ``moe_capacity_factor`` MLPs instead
    of all ``num_experts`` — see ``models/moe.py``).
    ``moe_expert_unroll=True`` unrolls the expert dots into small groups
    (a measured per-op MXU win that OOMs the 12-layer training graph at
    batch 8 and forces resharding under GSPMD expert sharding — opt-in
    only; see ``MoE.__init__``).
    ``num_kv_heads < num_heads`` builds a grouped-query (GQA) model — the
    KV cache at serving time shrinks by the group factor.
    ``remat`` wraps every transformer block in ``blocks.Remat`` with that
    checkpoint policy ("nothing" | "dots" | "dots_no_batch") — the
    explicit activation-memory policy for deep/long-context training
    (see ``Remat``'s docstring for the trade-offs).

    ``head_dim`` states a head size other than ``d_model // num_heads``;
    ``qk_norm`` puts an RMSNorm over every head's query and key before
    RoPE; ``rope_base`` is RoPE's theta. ``block_len=B`` makes attention
    BLOCK-causal (``j // B <= i // B``): a block-diffusion language
    model, which ``ServingEngine`` decodes a block of ``B`` tokens at a
    time by denoising (docs/serving.md §Block diffusion).
    ``mlp_dim`` states the MLP's (or each expert's) hidden width instead
    of ``mlp_ratio * d_model``; ``mlp_gated`` makes it the gated form
    ``w2(act(x w1) * (x w3))`` (SwiGLU with ``mlp_activation="silu"``),
    ``mlp_bias=False`` drops its biases. ``moe_top_k`` experts of
    ``num_experts`` take each token (gates renormalised over the k);
    ``moe_dispatch="grouped"`` is the drop-free serving dispatch for
    many small experts (``models/moe.py``).

    Layers of several kinds in one stack: ``layer_types`` names each
    layer's attention kind (its first ``num_layers`` entries are read)
    and ``attn_kinds[kind]`` overrides, for the layers of that kind,
    any of ``num_heads``, ``num_kv_heads``, ``head_dim``,
    ``attn_window``, ``rope_base``, ``rope_scale``, ``rotary_dim``,
    ``rope_yarn`` and ``qk_norm`` (what it leaves out is the argument
    of the same name above). ``ServingEngine`` gives layers with a
    window and layers without one page groups of their own
    (docs/serving.md §Page groups). ``mlp_layer_types`` says per layer
    ``"dense"`` (a plain MLP of width ``dense_mlp_dim``, else
    ``mlp_dim``) or ``"sparse"`` (the experts) and takes the place of
    ``moe_every``. ``moe_score`` / ``moe_norm_topk`` /
    ``moe_route_scale`` / ``moe_shared_dim``: the router's score
    function, its normalisation over the chosen experts, a scale on
    the gates and a shared expert (``models.moe.MoE``);
    ``moe_zero_experts`` identity experts behind the router's last
    outputs, ``moe_select_bias`` a bias that chooses and does not
    weight, and ``moe_experts_held=(lo, n)`` ONE CHIP'S SHARE of an
    expert-parallel layer: the router keeps all its outputs, the layer
    holds and computes experts ``lo .. lo + n - 1`` (and every identity
    expert) and leaves the others' part out.

    LATENT attention: an ``attn_kinds[kind]`` with a ``"latent"`` entry
    (``{"q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "q_scale", "kv_scale"}``) makes
    the layers of that kind ``models.attention.LatentAttention``
    (``num_heads`` and ``rope_base`` as for the other kinds);
    ``ServingEngine`` keeps one latent a token for them
    (docs/serving.md §Latent pages). ``mlp_layer_types[i] ==
    "shortcut"`` makes entry ``i`` of ``num_layers`` a DOUBLE layer:
    two attention blocks and two dense MLPs (width ``dense_mlp_dim``)
    round a shortcut-connected expert layer, computed from the first
    block's post-attention norm and added after the second block's MLP
    (``TransformerBlock``'s class doc); it is two blocks of the
    ``Sequential`` and two entries of a serving cache. ``norm_eps``
    states every norm's epsilon.

    STATE-SPACE layers: an ``attn_kinds[kind]`` with a ``"mamba"`` entry
    (``{"num_heads", "head_dim", "d_state", "d_conv", "n_groups",
    "chunk"}``) makes the layers of that kind ``models.mamba.
    Mamba2Mixer`` blocks (the mixer in attention's place, the block's
    MLP or experts after it); ``ServingEngine`` keeps a state a slot for
    them (docs/serving.md §State groups). An attention kind may also
    state ``use_rope`` (False: no position embedding at all, NoPE, when
    the stack has no position layer) and ``attn_scale`` (the scores'
    multiplier). ``embedding_multiplier`` scales the embedding's output,
    ``residual_multiplier`` both branches of every block before they
    join the residual stream, ``logits_scaling`` divides the logits,
    and ``tie_embeddings`` makes the head the embedding's own table
    (``layers.TiedHead``; ``logits_scaling`` needs it).
    """
    from distkeras_tpu.models.attention import (
        LayerNorm, PositionalEmbedding, RMSNorm, TransformerBlock)

    layers = [Embedding(vocab_size, d_model)]
    if embedding_multiplier != 1.0:
        from distkeras_tpu.models.layers import Scale
        layers.append(Scale(embedding_multiplier))
    if logits_scaling != 1.0 and not tie_embeddings:
        raise ValueError("logits_scaling is built for a tied head "
                         "(tie_embeddings=True)")
    if not use_rope:
        if max_len is None:
            raise ValueError("max_len required when use_rope=False")
        # thread the sequence axis through so positions are global under
        # sequence parallelism (shard-local positions would be silently wrong)
        layers.append(PositionalEmbedding(max_len,
                                          seq_axis_name=seq_axis_name))
    for name, kinds in (("layer_types", layer_types),
                        ("mlp_layer_types", mlp_layer_types)):
        if kinds is not None and len(kinds) < num_layers:
            raise ValueError(f"{name} names {len(kinds)} layers of "
                             f"{num_layers}")
    if layer_types is None and attn_kinds:
        raise ValueError("attn_kinds needs layer_types")
    if mlp_layer_types is not None and set(mlp_layer_types) \
            - {"dense", "sparse", "shortcut"}:
        raise ValueError("mlp_layer_types entries are 'dense', 'sparse' "
                         "or 'shortcut'")
    for i in range(num_layers):
        attn = dict(num_heads=num_heads, head_dim=head_dim,
                    num_kv_heads=num_kv_heads, rope_scale=rope_scale,
                    attn_window=attn_window, qk_norm=qk_norm,
                    rope_base=rope_base)
        if layer_types is not None:
            kind = dict((attn_kinds or {}).get(layer_types[i], {}))
            unknown = set(kind) - set(attn) - {"rotary_dim", "rope_yarn",
                                               "latent", "mamba",
                                               "use_rope", "attn_scale"}
            if unknown:
                raise ValueError(
                    f"attn_kinds[{layer_types[i]!r}] has unknown keys "
                    f"{sorted(unknown)}")
            attn.update(kind)
        latent = attn.pop("latent", None)
        mamba = attn.pop("mamba", None)
        layer_rope = attn.pop("use_rope", use_rope)
        if (latent is not None or mamba is not None) \
                and block_len is not None:
            raise ValueError("block_len (block-causal attention) is not "
                             "built for latent attention or state-space "
                             "layers")
        if mlp_layer_types is None:
            sparse = bool(moe_every and num_experts
                          and (i + 1) % moe_every == 0)
        else:
            sparse = mlp_layer_types[i] in ("sparse", "shortcut")
        shortcut = mlp_layer_types is not None \
            and mlp_layer_types[i] == "shortcut"
        mlp_layer = None
        layer_mlp_dim = mlp_dim
        if sparse:
            from distkeras_tpu.models.moe import MoE
            routing = {}
            if moe_score != "softmax" or not moe_norm_topk \
                    or moe_route_scale != 1.0 or moe_shared_dim:
                routing = dict(score=moe_score, norm_topk=moe_norm_topk,
                               route_scale=moe_route_scale,
                               shared_dim=moe_shared_dim)
            if moe_zero_experts or moe_experts_held or moe_select_bias:
                routing.update(
                    zero_experts=moe_zero_experts,
                    experts_held=None if moe_experts_held is None
                    else tuple(moe_experts_held),
                    select_bias=moe_select_bias)
            mlp_layer = MoE(num_experts, mlp_dim or mlp_ratio * d_model,
                            top_k=moe_top_k, activation=mlp_activation,
                            dtype=dtype, expert_axis_name=moe_expert_axis,
                            aux_loss_weight=moe_aux_loss_weight,
                            dispatch=moe_dispatch,
                            capacity_factor=moe_capacity_factor,
                            expert_unroll=moe_expert_unroll,
                            gated=mlp_gated, use_bias=mlp_bias, **routing)
        elif mlp_layer_types is not None and dense_mlp_dim:
            layer_mlp_dim = dense_mlp_dim
        extra = {} if norm_eps is None else {"norm_eps": norm_eps}
        if residual_multiplier != 1.0:
            extra["residual_scale"] = residual_multiplier

        def attention():
            if mamba is not None:
                from distkeras_tpu.models.mamba import Mamba2Mixer
                return {"attn_layer": Mamba2Mixer(
                    norm_eps=1e-5 if norm_eps is None else norm_eps,
                    dtype=dtype, **mamba)}
            if latent is None:
                return {}
            from distkeras_tpu.models.attention import LatentAttention
            return {"attn_layer": LatentAttention(
                attn["num_heads"], rope_base=attn["rope_base"],
                norm_eps=1e-6 if norm_eps is None else norm_eps,
                dtype=dtype,
                attn_impl=attn_impl if attn_impl in ("xla", "flash")
                else "auto", **latent)}

        def block_of(**kw):
            block = TransformerBlock(
                mlp_ratio=mlp_ratio, causal=True,
                use_rope=layer_rope, activation=mlp_activation,
                norm=norm, dtype=dtype, attn_impl=attn_impl,
                seq_axis_name=seq_axis_name,
                block_len=block_len,
                mlp_gated=mlp_gated, mlp_bias=mlp_bias, **attn, **kw)
            if remat is not None:
                from distkeras_tpu.models.blocks import Remat
                block = Remat(block, policy=remat)
            return block

        if shortcut:
            # the double layer: two blocks, the expert layer handed from
            # the first to the second beside the residual stream
            width = dense_mlp_dim or mlp_dim
            layers.append(block_of(mlp_dim=width, shortcut_layer=mlp_layer,
                                   **attention(), **extra))
            layers.append(block_of(mlp_dim=width, shortcut_add=True,
                                   **attention(), **extra))
            continue
        layers.append(block_of(mlp_layer=mlp_layer, mlp_dim=layer_mlp_dim,
                               **attention(), **extra))
    if norm_eps is not None:
        layers.append(RMSNorm(norm_eps) if norm == "rmsnorm"
                      else LayerNorm(norm_eps))
    else:
        layers.append(RMSNorm() if norm == "rmsnorm" else LayerNorm())
    if tie_embeddings:
        from distkeras_tpu.models.layers import TiedHead
        layers.append(TiedHead(vocab_size, 1.0 / logits_scaling,
                               dtype=dtype))
    else:
        layers.append(Dense(vocab_size, use_bias=False, dtype=dtype))
    return Sequential(layers)


def vit(image_size: int = 224, patch_size: int = 16, d_model: int = 384,
        num_heads: int = 6, num_layers: int = 12, mlp_ratio: int = 4,
        num_classes: int = 1000, dtype: str = "float32",
        dropout_rate: float = 0.0) -> Sequential:
    """Vision Transformer (ViT; Dosovitskiy et al. 2020) — capability ADD
    (the reference predates transformers, SURVEY §5.7). Patchify is ONE
    strided conv (a single MXU matmul per patch grid), then mean-pooled
    pre-norm encoder blocks; GAP head instead of a class token keeps the
    whole model a ``Sequential``.
    """
    from distkeras_tpu.models.attention import (LayerNorm,
                                                PositionalEmbedding,
                                                TransformerBlock)
    from distkeras_tpu.models.layers import GlobalAveragePooling1D, Reshape

    if image_size % patch_size:
        raise ValueError(
            f"image_size {image_size} not divisible by patch_size "
            f"{patch_size}")
    n_patches = (image_size // patch_size) ** 2
    layers = [
        Conv2D(d_model, patch_size, strides=patch_size, padding="VALID",
               dtype=dtype),
        Reshape((n_patches, d_model)),
        PositionalEmbedding(n_patches),
    ]
    for _ in range(num_layers):
        layers.append(TransformerBlock(
            num_heads, mlp_ratio=mlp_ratio, causal=False, use_rope=False,
            norm="layernorm", dtype=dtype, dropout_rate=dropout_rate))
    layers += [LayerNorm(), GlobalAveragePooling1D(),
               Dense(num_classes, dtype=dtype)]
    return Sequential(layers)


def mobilenet(num_classes: int = 1000, width_mult: float = 1.0,
              dtype: str = "float32",
              bn_axis_name: Optional[str] = None) -> Sequential:
    """MobileNet-v1 (Howard et al. 2017) — depthwise-separable CNN built
    on ``DepthwiseConv2D``; the classic efficient-inference counterpart to
    ``resnet50`` (capability ADD: the reference's CNN examples stop at
    LeNet-scale). NHWC, BN after every conv, ``width_mult`` scales every
    channel count."""
    from distkeras_tpu.models.layers import DepthwiseConv2D

    def ch(c):
        return max(8, int(c * width_mult))

    bn = lambda: BatchNorm(axis_name=bn_axis_name)
    layers = [Conv2D(ch(32), 3, strides=2, use_bias=False, dtype=dtype),
              bn(), Activation("relu")]
    # (pointwise out-channels, stride) per separable block
    plan = [(64, 1), (128, 2), (128, 1), (256, 2), (256, 1), (512, 2),
            (512, 1), (512, 1), (512, 1), (512, 1), (512, 1), (1024, 2),
            (1024, 1)]
    for out_c, stride in plan:
        layers += [
            DepthwiseConv2D(3, strides=stride, use_bias=False, dtype=dtype),
            bn(), Activation("relu"),
            Conv2D(ch(out_c), 1, use_bias=False, dtype=dtype),
            bn(), Activation("relu"),
        ]
    layers += [GlobalAveragePooling2D(), Dense(num_classes, dtype=dtype)]
    return Sequential(layers)
