"""Compile-only guard: the kernels of the main path, at real widths,
must lower through Mosaic and XLA for a TPU v5e that is DESCRIBED, not
attached (``jax.experimental.topologies``). Interpret-mode oracles pin
what a kernel computes; they cannot see what the chip's compiler
refuses — a slice off the tiling, a primitive Mosaic does not lower,
a kernel XLA is asked to partition. Nothing here runs, so nothing here
says a kernel is right or fast: a compile that passes is not a chip
run (``chip_smoke.py`` is).

Every public Pallas entry point in ``ops/`` has to appear in THIS file
(``tools/lint_kernel_oracles.py``), so an interpret-only kernel cannot
land again.

The topology is described inside a module-scoped fixture that skips
where it cannot be: only one process may load the TPU's library, so
the call must not happen at import, in a ``skipif`` or in
``parametrize`` — every xdist worker imports this file, only the one
that runs it reaches the fixture. The compiles happen in this process,
with the persistent compile cache off around them (an entry written
for a described chip cannot be read back without one).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_tpu(monkeypatch):
    """Steer the repo's trace-time backend convention to its TPU branch
    (``compat.backend_is_tpu`` sees the CPU here): every module that
    imported the predicate gets the patched one, so the PUBLIC entry
    points pick their kernels with ``interpret=False`` — the program
    the chip would be handed."""
    import distkeras_tpu.compat as compat
    from distkeras_tpu.models import attention, decoding
    from distkeras_tpu.ops import (decode_attention, flash_attention,
                                   moe_kernels, paged_attention,
                                   quant_matmul, sampling)
    for mod in (compat, attention, decoding, decode_attention,
                flash_attention, moe_kernels, paged_attention,
                quant_matmul, sampling):
        monkeypatch.setattr(mod, "backend_is_tpu", lambda: True)


def _compile(fn, *args):
    """Compile for the described chip; returns (kernel count, text)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"'), text


def _spec(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


# --- flash attention -------------------------------------------------------

#: the LM_CFG attention shape (bench.py): B8 H16 S2048 D64 bf16, BHSD
QKV = (8, 16, 2048, 64)


def _flash_loss(q, k, v):
    from distkeras_tpu.ops.flash_attention import flash_attention
    return flash_attention(q, k, v, causal=True, layout="bhsd") \
        .astype(jnp.float32).sum()


def test_flash_attention_forward(one_chip, as_tpu):
    q = _spec(one_chip)(QKV, jnp.bfloat16)
    n, _ = _compile(_flash_loss, q, q, q)
    assert n == 1


def test_flash_attention_forward_backward(one_chip, as_tpu):
    q = _spec(one_chip)(QKV, jnp.bfloat16)
    n, _ = _compile(jax.grad(_flash_loss, argnums=(0, 1, 2)), q, q, q)
    assert n == 3                       # fwd, dq, dkv


def test_flash_attention_under_dp4_mesh(topo, as_tpu):
    """XLA refuses to partition a Mosaic kernel; inside
    ``flash_attention.partitioned`` (what ``SPMDTrainer`` traces under)
    the kernel runs per shard: all three custom calls in the partition,
    q/k/v never all-gathered."""
    from distkeras_tpu.ops.flash_attention import partitioned
    mesh = Mesh(np.array(topo.devices).reshape(4), ("workers",))
    q = _spec(NamedSharding(mesh, P("workers")))(QKV, jnp.bfloat16)
    grad = jax.grad(_flash_loss, argnums=(0, 1, 2))
    with pytest.raises(NotImplementedError,
                       match="cannot be automatically partitioned"):
        _compile(grad, q, q, q)

    def wrapped(q, k, v):
        with partitioned(mesh, ("workers",), "tp"):
            return grad(q, k, v)

    n, text = _compile(wrapped, q, q, q)
    assert n == 3
    assert "all-gather" not in text


def test_train_step_flash_kernels_at_the_cell_shape(one_chip, as_tpu):
    """The one-chip training cell's step (Cerebras-GPT 256M, all 14
    layers, 2 x 2048 tokens, Adam) lowered for the chip: each layer holds
    one call of each flash kernel (the harness fails a run on another
    count), and the plain causal counter reads the live grid at the
    default 1024 tiles — 3 of the 2 x 2 tiles a (batch, head), the one
    above the diagonal gone, the two on it done in pieces of 512 rows
    forward and 256 backward."""
    import re
    from distkeras_tpu.compat import record_paths
    from distkeras_tpu.models import zoo
    from distkeras_tpu.ops.losses import get_loss
    from distkeras_tpu.ops.optimizers import get_optimizer
    from distkeras_tpu.parallel.worker import TrainCarry, make_train_step
    module = zoo.transformer_lm(
        50257, d_model=1088, num_heads=17, num_layers=14, mlp_ratio=4,
        max_len=2048, use_rope=False, norm="layernorm", dtype="bfloat16")
    params, state = jax.eval_shape(
        lambda key: module.init(key, (2048,))[:2], jax.random.PRNGKey(0))
    opt = get_optimizer("adam", learning_rate=1e-4)
    step = make_train_step(
        module, get_loss("sparse_categorical_crossentropy_from_logits"), opt)
    s = _spec(one_chip)
    carry = jax.tree_util.tree_map(
        lambda a: s(a.shape, a.dtype),
        TrainCarry(params, state, jax.eval_shape(opt.init, params),
                   jax.ShapeDtypeStruct((2,), np.uint32)))
    x = s((2, 2048), jnp.int32)
    with record_paths() as paths:
        text = jax.jit(step).lower(carry, (x, x)).as_text()
    names = re.findall(r'kernel_name = "([^"]+)"', text)
    assert {n: names.count(n) for n in set(names)} == {
        "flash_fwd": 14, "flash_bwd_dq": 14, "flash_bwd_dkv": 14}
    assert {p for p in paths if p.startswith("flash")} == {
        "flash_attention=kernel", "flash_causal=live3of4,sub512/256"}


def test_train_epoch_updates_its_carry_in_place(one_chip, as_tpu):
    """``SingleTrainer``'s epoch program at the one-chip training cell's
    shapes (Cerebras-GPT 256M widths, 8 steps of 2 x 2048 tokens, Adam; two
    layers of its 14) on a DONATED carry: every leaf of parameters, moments
    and key is aliased to the result, so the program allocates no second
    carry, and the flash kernels are in it."""
    from distkeras_tpu.models import zoo
    from distkeras_tpu.ops.losses import get_loss
    from distkeras_tpu.ops.optimizers import get_optimizer
    from distkeras_tpu.parallel.worker import (TrainCarry, make_epoch_runner,
                                               make_train_step)
    module = zoo.transformer_lm(
        50257, d_model=1088, num_heads=17, num_layers=2, mlp_ratio=4,
        max_len=2048, use_rope=False, norm="layernorm", dtype="bfloat16")
    params, state = jax.eval_shape(
        lambda key: module.init(key, (2048,))[:2], jax.random.PRNGKey(0))
    opt = get_optimizer("adam", learning_rate=1e-4)
    step = make_train_step(
        module, get_loss("sparse_categorical_crossentropy_from_logits"), opt)
    s = _spec(one_chip)
    carry = jax.tree_util.tree_map(
        lambda a: s(a.shape, a.dtype),
        TrainCarry(params, state, jax.eval_shape(opt.init, params),
                   jax.ShapeDtypeStruct((2,), np.uint32)))
    x = s((8, 2, 2048), jnp.int32)
    compiled = make_epoch_runner(step).lower(carry, x, x).compile()
    text = compiled.as_text()
    leaves = jax.tree_util.tree_leaves(carry)
    assert text.split("\n", 1)[0].count("may-alias") == len(leaves)
    carry_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in leaves)
    mem = compiled.memory_analysis()
    # whole leaves alias (each padded to its tile), the losses do not
    assert carry_bytes <= mem.alias_size_in_bytes <= 1.01 * carry_bytes
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 1 << 12
    assert text.count('custom_call_target="tpu_custom_call"') == 3 * 2


# --- decode attention ------------------------------------------------------

@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_decode_attention(one_chip, as_tpu, int8):
    from distkeras_tpu.ops.decode_attention import decode_attention
    s = _spec(one_chip)
    bh, g, d, L = 8 * 16, 1, 64, 2560
    q = s((bh, g, d), jnp.bfloat16)
    kv = s((bh, L, d), jnp.int8 if int8 else jnp.bfloat16)
    t = s((), jnp.int32)
    if int8:
        sc = s((bh, L), jnp.float32)
        fn = lambda q, k, v, t, ks, vs: decode_attention(
            q, k, v, t, k_scale=ks, v_scale=vs)
        n, _ = _compile(fn, q, kv, kv, t, sc, sc)
    else:
        n, _ = _compile(decode_attention, q, kv, kv, t)
    assert n == 1


@pytest.mark.parametrize("page_len,int8", [(16, False), (32, True)],
                         ids=["bf16-page16", "int8-page32"])
def test_paged_decode_attention(one_chip, as_tpu, page_len, int8):
    """The serving default (8 slots, max_len 2304, ``page_len=16``)
    and the int8 pool's tiling (``page_len % 32``)."""
    from distkeras_tpu.ops.paged_attention import paged_decode_attention
    s = _spec(one_chip)
    slots, hkv, d = 8, 16, 64
    per_slot = 2304 // page_len
    n_pages = slots * per_slot
    q = s((slots, 1, hkv, 1, d), jnp.float32)
    pages = s((n_pages, hkv, page_len, d),
              jnp.int8 if int8 else jnp.bfloat16)
    t = s((slots,), jnp.int32)
    table = s((slots, per_slot), jnp.int32)
    if int8:
        sc = s((n_pages, hkv, page_len), jnp.float32)
        fn = lambda q, k, v, t, tb, ks, vs: paged_decode_attention(
            q, k, v, t, tb, k_scale=ks, v_scale=vs)
        n, _ = _compile(fn, q, pages, pages, t, table, sc, sc)
    else:
        n, _ = _compile(paged_decode_attention, q, pages, pages, t, table)
    assert n == 1


@pytest.mark.parametrize("dtype,rows", [(jnp.bfloat16, 16), (jnp.int8, 32),
                                        (jnp.int8, 8)],
                         ids=["bf16", "int8", "int4-packed"])
def test_page_write_moves_no_plane(one_chip, dtype, rows):
    """The decode step's KV write into a DONATED pool plane at the
    serve cell's size ([2048, 16, 16, 128] bf16, 16 slots) is compiled
    in place: the plane is aliased to the result and the program holds
    no copy of it. (``plane.at[pp, :, off].set(...)`` compiles to a
    relayout of the whole plane and back: 45 ms of ``copy`` a decode
    step at this size, ``PERF.md`` §6, PR 27.)"""
    from distkeras_tpu.models.decoding import _write_page_rows
    s = _spec(one_chip)
    plane = (2048, 16, rows, 128)
    compiled = jax.jit(_write_page_rows, donate_argnums=0).lower(
        s(plane, dtype), s((16,), jnp.int32), s((16,), jnp.int32),
        s((16, 16, 128), dtype)).compile()
    text = compiled.as_text()
    assert "may-alias" in text.split("\n", 1)[0]
    dims = ",".join(map(str, plane))
    moved = [line for line in text.splitlines()
             if " copy(" in line and (f"[{dims}]" in line
                                      or f"[{2048 * 16 * rows},128]" in line)]
    assert not moved, moved[:2]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize(
    "dtype,page_len", [(jnp.bfloat16, 16), (jnp.bfloat16, 8), ("int8", 32),
                       ("int4", 64), ("int4", 32)],
    ids=["bf16", "bf16-half-tile", "int8", "int4", "int4-gather-path"])
def test_decode_step_moves_no_pool_plane(one_chip, as_tpu, dtype, page_len):
    """The WHOLE paged decode step at the serve cell's widths (16 slots
    x 2048 positions, 16 heads of 128) on a donated pool: every cache
    leaf is aliased to the result and no copy in the program is the
    size of a payload plane — the int4 pool's read-modify-write gather
    and a page shorter than a bf16 tile included. (The f32 scale
    planes, 1/32 of an int8 pool's bytes, enter in a layout of the
    compiler's choosing and pay one copy in and one out whichever way
    they are indexed. int4 at page_len 32 packs to 16 rows, which the
    kernel refuses: that case is the ``_gather_pages`` readout.)"""
    import re
    from distkeras_tpu.models import Model, zoo
    from distkeras_tpu.models.decoding import (_resolve_head_dims,
                                               decode_step_slots_paged,
                                               init_cache)
    m = Model.build(zoo.transformer_lm(
        256, d_model=2048, num_heads=16, num_layers=1, mlp_ratio=1,
        max_len=2048, use_rope=False, norm="layernorm",
        dtype="bfloat16"), (16,), seed=0)
    module = m.module
    _resolve_head_dims(module, m.params)
    slots, s = 16, _spec(one_chip)
    cache = jax.eval_shape(lambda: init_cache(
        module, slots * 2048 // page_len, page_len, dtype, check_len=2048))
    if dtype == "int4":              # PagedKVPool packs two rows a byte
        cache = [kv and {k: jax.ShapeDtypeStruct(
            a.shape[:2] + (a.shape[2] // 2,) + a.shape[3:], a.dtype)
            if k in "kv" else a for k, a in kv.items()} for kv in cache]

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda a: s(a.shape, a.dtype), tree)

    def step(params, state, cache, tok, t, table):
        return decode_step_slots_paged(module, params, state, cache, tok,
                                       t, table, page_len)

    text = jax.jit(step, donate_argnums=2).lower(
        on_chip(m.params), on_chip(m.state), on_chip(cache),
        s((slots,), jnp.int32), s((slots,), jnp.int32),
        s((slots, 2048 // page_len), jnp.int32)).compile().as_text()
    leaves = jax.tree_util.tree_leaves(cache)
    assert text.split("\n", 1)[0].count("may-alias") == len(leaves)
    planes = {int(np.prod(a.shape)) for a in leaves if a.ndim == 4}
    moved = [line.strip()[:160] for line in text.splitlines()
             if (dims := re.search(r"= \w+\[([\d,]+)\]\S* copy\(", line))
             and int(np.prod(list(map(int, dims.group(1).split(",")))))
             in planes]
    assert not moved, moved[:2]
    kernels = text.count('custom_call_target="tpu_custom_call"')
    assert kernels == (0 if (dtype, page_len) == ("int4", 32) else 1)


# --- quantized matmul ------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
def test_quant_matmul(one_chip, as_tpu, bits):
    from distkeras_tpu.ops.quant_matmul import quant_matmul
    s = _spec(one_chip)
    m, k, n = 8, 1024, 3072
    wq = {"q4": s((k // 2, n), jnp.int8)} if bits == 4 \
        else {"q": s((k, n), jnp.int8)}
    wq["scale"] = s((n,), jnp.float32)
    calls, _ = _compile(quant_matmul, s((m, k), jnp.bfloat16), wq)
    assert calls == 1


# --- fused MoE -------------------------------------------------------------

def _moe_args(s, n, d, hid, e, k, dt):
    return (s((n, d), dt), s((e, d, hid), dt), s((e, hid), dt),
            s((e, hid, d), dt), s((e, d), dt), s((k * n,), jnp.float32),
            s((k * n,), jnp.int32), s((k * n,), jnp.bool_))


@pytest.mark.parametrize("dt", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_fused_moe_apply_training_shape(one_chip, as_tpu, dt, grad):
    """N=16384 tokens, d=1024, H=4096, E=8, top-2 at capacity factor
    1.25 — the shape whose one-row gather Mosaic refused (f32: "Slice
    shape along dimension 0 must be aligned to tiling (8), but is 1";
    bf16: "cannot statically prove that index in dimension 0 is a
    multiple of 8")."""
    from distkeras_tpu.ops.moe_kernels import fused_moe_apply
    n, d, hid, e, k = 16384, 1024, 4096, 8, 2
    cap = int(1.25 * k * n / e)

    def fwd(xt, w1, b1, w2, b2, sg, dest, keep):
        return fused_moe_apply(xt, w1, b1, w2, b2, sg, dest, keep,
                               capacity=cap)

    def bwd(xt, w1, b1, w2, b2, sg, dest, keep):
        return jax.grad(
            lambda *a: fwd(*a, dest, keep).astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3, 4, 5))(xt, w1, b1, w2, b2, sg)

    calls, _ = _compile(bwd if grad else fwd,
                        *_moe_args(_spec(one_chip), n, d, hid, e, k, dt))
    assert calls == (3 if grad else 1)  # gather-GEMM1 (+ dx, dw1)


@pytest.mark.parametrize("tokens", [4, 8, 32])
def test_moe_fused_experts_decode_shape(one_chip, as_tpu, tokens):
    """``MOE_SERVE_CFG`` (bench.py: d=512, expert width 1024, E=8,
    bf16) at the engine's decode sizes — 4 and 8 slots, and a
    speculative verify window (8 slots x 4) — capacity = token count
    (``MoE.decode_apply``: drop-free by construction)."""
    from distkeras_tpu.ops.moe_kernels import (choose_block_c,
                                               kernel_capacity,
                                               moe_fused_experts)
    block_c = choose_block_c(kernel_capacity(tokens))
    calls, _ = _compile(
        lambda *a: moe_fused_experts("gelu", tokens, block_c, False, *a),
        *_moe_args(_spec(one_chip), tokens, 512, 1024, 8, 2,
                   jnp.bfloat16))
    assert calls == 1


# --- fused sampling --------------------------------------------------------

def test_sample_epilogue_real_vocab(one_chip, as_tpu):
    """S=8, V=32768 — the shape whose in-kernel prefix sums Mosaic
    refused ("Unimplemented primitive in Pallas TPU lowering for
    KernelType.TC: cumsum")."""
    from distkeras_tpu.ops.sampling import fused_supported, sample_epilogue
    s = _spec(one_chip)
    slots, vocab = 8, 32768
    assert fused_supported(vocab)
    calls, _ = _compile(
        sample_epilogue, s((slots, vocab), jnp.float32),
        s((slots,), jnp.float32), s((slots,), jnp.int32),
        s((slots,), jnp.float32), s((slots, vocab), jnp.float32))
    assert calls == 1


def test_sample_tokens_with_keys(one_chip, as_tpu):
    """The engine's ``fused_sampling=True`` sampler: per-slot keys ->
    gumbel field -> the epilogue kernel, rows padded from 5 to 8."""
    from distkeras_tpu.ops.sampling import sample_tokens
    s = _spec(one_chip)
    slots, vocab = 5, 1024
    calls, _ = _compile(
        sample_tokens, s((slots, vocab), jnp.bfloat16),
        s((slots,), jnp.float32), s((slots,), jnp.int32),
        s((slots,), jnp.float32), s((slots, 2), jnp.uint32))
    assert calls == 1


# --- block diffusion at the SDAR cell's widths -----------------------------
# d_model 2048, 32 query heads over 4 KV heads of 128, 128 gated experts of
# width 768 with top-8, vocabulary 151,936, blocks of 4, 32 slots x 2048.

@pytest.mark.parametrize("tokens", [128, 1024], ids=["pass", "prefill"])
def test_grouped_experts_sdar_widths(one_chip, as_tpu, tokens):
    """The grouped expert product: one pass of 32 slots x 4 positions and a
    1,024-token prefill, top-8 of 128 (a whole expert, three matrices of
    2048 x 768, sits in VMEM double-buffered: past the 16 MiB default)."""
    from distkeras_tpu.ops.moe_kernels import (grouped_block_rows,
                                               grouped_experts,
                                               grouped_tiles)
    s = _spec(one_chip)
    e, d, f, a = 128, 2048, 768, tokens * 8
    rows = grouped_block_rows(a, e)
    tiles = grouped_tiles(a, e, rows)
    assert rows == (16 if tokens == 128 else 64)
    fn = lambda x, te, used, w1, w2, w3: grouped_experts(
        x, te, used, w1, w2, w3, block_rows=rows, activation="silu")
    n, _ = _compile(fn, s((tiles * rows, d), jnp.bfloat16),
                    s((tiles,), jnp.int32), s((), jnp.int32),
                    s((e, d, f), jnp.bfloat16), s((e, f, d), jnp.bfloat16),
                    s((e, d, f), jnp.bfloat16))
    assert n == 1


def test_paged_full_window_sdar_widths(one_chip, as_tpu):
    from distkeras_tpu.ops.paged_attention import paged_decode_attention
    s = _spec(one_chip)
    slots, hkv, g, d, w = 32, 4, 8, 128, 4
    pages = s((slots * 128, hkv, 16, d), jnp.bfloat16)
    fn = lambda q, k, v, t, tb: paged_decode_attention(
        q, k, v, t, tb, full_window=True)
    n, _ = _compile(fn, s((slots, w, hkv, g, d), jnp.float32), pages, pages,
                    s((slots,), jnp.int32), s((slots, 128), jnp.int32))
    assert n == 1


@pytest.mark.parametrize("seq", [1024, 896, 4])
def test_block_causal_flash_sdar_widths(one_chip, as_tpu, seq):
    from distkeras_tpu.ops.flash_attention import flash_attention
    q = _spec(one_chip)((1, seq, 32, 128), jnp.bfloat16)
    fn = lambda q, k, v: flash_attention(q, k, v, causal=True, block_len=4)
    n, _ = _compile(fn, q, q, q)
    assert n == 1


@pytest.mark.parametrize("head", [True, False], ids=["denoise", "commit"])
def test_block_pass_moves_no_pool_plane(one_chip, as_tpu, head):
    """One whole block-diffusion pass (one layer of the cell's widths, the
    whole vocabulary; the denoising pass with its choice of positions, as
    the engine's program holds it) on a donated pool: both kernels in the
    program, every cache leaf aliased, no copy the size of a pool plane.
    (Without the head the deepest block stops at its K/V write: the commit
    program of this one-layer model holds no kernel at all.)"""
    import re
    from distkeras_tpu.models import zoo
    from distkeras_tpu.models.decoding import (_resolve_head_dims,
                                               block_denoise_slots_paged,
                                               block_pass_slots_paged,
                                               init_cache)
    module = zoo.transformer_lm(
        151936, d_model=2048, num_heads=32, num_layers=1, max_len=2048,
        num_kv_heads=4, head_dim=128, qk_norm=True, rope_base=1e6,
        block_len=4, mlp_dim=768, mlp_activation="silu", mlp_gated=True,
        mlp_bias=False, moe_every=1, num_experts=128, moe_top_k=8,
        moe_dispatch="grouped", dtype="bfloat16")
    params, state = jax.eval_shape(
        lambda k: module.init(k, (16,))[:2], jax.random.PRNGKey(0))
    slots, page_len, s = 32, 16, _spec(one_chip)
    cache = jax.eval_shape(lambda: init_cache(
        module, slots * 2048 // page_len, page_len, jnp.bfloat16,
        check_len=2048))

    def on_chip(tree, dtype=None):
        return jax.tree_util.tree_map(
            lambda a: s(a.shape, dtype if dtype is not None and a.ndim >= 2
                        else a.dtype), tree)

    def commit(params, state, cache, toks, t, table):
        return block_pass_slots_paged(module, params, state, cache, toks,
                                      t, table, page_len, head=False)

    def denoise(params, state, cache, toks, masked, fixed_pass, n_fix,
                step, t, table):
        return block_denoise_slots_paged(
            module, params, state, cache, toks, masked, fixed_pass, n_fix,
            step, t, table, page_len)

    blk, per_slot = s((slots, 4), jnp.int32), s((slots,), jnp.int32)
    state_args = (blk, s((slots, 4), jnp.bool_), blk, per_slot, per_slot) \
        if head else (blk,)
    text = jax.jit(denoise if head else commit, donate_argnums=2).lower(
        on_chip(params, jnp.bfloat16), on_chip(state), on_chip(cache),
        *state_args, per_slot,
        s((slots, 2048 // page_len), jnp.int32)).compile().as_text()
    leaves = jax.tree_util.tree_leaves(cache)
    assert text.split("\n", 1)[0].count("may-alias") == len(leaves)
    planes = {int(np.prod(a.shape)) for a in leaves if a.ndim == 4}
    moved = [line.strip()[:160] for line in text.splitlines()
             if (dims := re.search(r"= \w+\[([\d,]+)\]\S* copy\(", line))
             and int(np.prod(list(map(int, dims.group(1).split(",")))))
             in planes]
    assert not moved, moved[:2]
    assert text.count('custom_call_target="tpu_custom_call"') \
        == (2 if head else 0)


@pytest.mark.parametrize("t0,chunk", [(0, 1024), (128, 896)],
                         ids=["whole", "after_a_shared_template"])
def test_block_diffusion_prefill_counts_what_it_runs(one_chip, as_tpu, t0,
                                                     chunk):
    """The block-diffusion engine's prefill at the cell's widths, two layers:
    no head, the deepest block stops at its K/V write, and the program
    returns what its expert layers routed. So the kernels in it are the
    first layer's alone: its flash passes (the chunk itself; the cached
    prefix too where there is one) and its grouped experts."""
    from distkeras_tpu.models import zoo
    from distkeras_tpu.models.decoding import (init_cache, prefill_chunk_step,
                                               routing_counts)
    module = zoo.transformer_lm(
        151936, d_model=2048, num_heads=32, num_layers=2, max_len=2048,
        num_kv_heads=4, head_dim=128, qk_norm=True, rope_base=1e6,
        block_len=4, mlp_dim=768, mlp_activation="silu", mlp_gated=True,
        mlp_bias=False, moe_every=1, num_experts=128, moe_top_k=8,
        moe_dispatch="grouped", dtype="bfloat16")
    params, state = jax.eval_shape(
        lambda k: module.init(k, (16,))[:2], jax.random.PRNGKey(0))
    s = _spec(one_chip)
    cache = jax.eval_shape(
        lambda: init_cache(module, 1, 2048, jnp.bfloat16))

    def on_chip(tree, dtype=None):
        return jax.tree_util.tree_map(
            lambda a: s(a.shape, dtype if dtype is not None and a.ndim >= 2
                        else a.dtype), tree)

    def step(params, state, cache, toks):
        routing = []
        _, cache = prefill_chunk_step(module, params, state, cache, toks,
                                      t0, final=False, routing=routing)
        assert len(routing) == 1
        return cache, routing_counts(routing)

    text = jax.jit(step, donate_argnums=2).lower(
        on_chip(params, jnp.bfloat16), on_chip(state), on_chip(cache),
        s((1, chunk), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') \
        == (3 if t0 else 2)



# --- window and full attention layers side by side (Laguna-XS.2's widths) ----

@pytest.mark.parametrize("tokens", [32, 2048], ids=["step", "prefill_chunk"])
def test_grouped_experts_laguna_widths(one_chip, as_tpu, tokens):
    """Top-8 of 256 experts of width 512: a decode step of 32 slots (one
    row an expert on average) and a prefill chunk of 2,048 tokens."""
    from distkeras_tpu.ops.moe_kernels import (grouped_block_rows,
                                               grouped_experts,
                                               grouped_tiles)
    s = _spec(one_chip)
    e, d, f, a = 256, 2048, 512, tokens * 8
    rows = grouped_block_rows(a, e)
    tiles = grouped_tiles(a, e, rows)
    assert rows == (16 if tokens == 32 else 64)
    fn = lambda x, te, used, w1, w2, w3: grouped_experts(
        x, te, used, w1, w2, w3, block_rows=rows, activation="silu")
    n, _ = _compile(fn, s((tiles * rows, d), jnp.bfloat16),
                    s((tiles,), jnp.int32), s((), jnp.int32),
                    s((e, d, f), jnp.bfloat16), s((e, f, d), jnp.bfloat16),
                    s((e, d, f), jnp.bfloat16))
    assert n == 1


@pytest.mark.parametrize("kind", ["full", "window"])
def test_paged_attention_laguna_kinds(one_chip, as_tpu, kind):
    """The paged kernel as each kind of layer calls it, 32 slots of 16,384
    positions in pages of 128: 6 query heads a KV head over the whole
    table, 8 over a ring of 5 columns under its own name."""
    from distkeras_tpu.ops.paged_attention import paged_decode_attention
    s = _spec(one_chip)
    slots, hkv, d, page_len = 32, 8, 128, 128
    if kind == "full":
        g, pages, width, kw = 6, slots * 128, 128, {}
    else:
        g, pages, width = 8, slots * 9, 5
        kw = dict(window=512, ring=True, name="paged_window_attention")
    pool = s((pages, hkv, page_len, d), jnp.bfloat16)
    fn = lambda q, k, v, t, tb: paged_decode_attention(q, k, v, t, tb, **kw)
    n, text = _compile(fn, s((slots, 1, hkv, g, d), jnp.float32), pool, pool,
                       s((slots,), jnp.int32), s((slots, width), jnp.int32))
    assert n == 1
    assert ("paged_window_attention" in text) == (kind == "window")


@pytest.mark.parametrize("heads,window", [(48, None), (64, 512)],
                         ids=["full", "window"])
def test_flash_prefill_laguna_kinds(one_chip, as_tpu, heads, window):
    from distkeras_tpu.ops.flash_attention import flash_attention
    q = _spec(one_chip)((1, 2048, heads, 128), jnp.bfloat16)
    fn = lambda q, k, v: flash_attention(q, k, v, causal=True, window=window)
    n, _ = _compile(fn, q, q, q)
    assert n == 1


def test_decode_step_over_two_page_groups(one_chip, as_tpu):
    """One decode step of the five layers at the cell's widths over a
    donated pool with a page group a kind: five paged kernels (two over the
    whole table, three over the ring, told apart by name), four grouped
    expert kernels, every cache leaf aliased, no copy the size of a plane,
    and the window layers' planes a window's worth."""
    import re
    from distkeras_tpu.models import zoo
    from distkeras_tpu.models.decoding import (decode_step_slots_paged,
                                               init_cache)
    sliding = {"num_heads": 64, "attn_window": 512}
    full = {"num_heads": 48, "rope_base": 5e5, "rotary_dim": 64,
            "rope_yarn": {"factor": 64,
                          "original_max_position_embeddings": 4096,
                          "beta_fast": 64, "beta_slow": 1}}
    module = zoo.transformer_lm(
        100352, d_model=2048, num_heads=64, num_layers=5, max_len=16384,
        num_kv_heads=8, head_dim=128, dtype="bfloat16",
        layer_types=["f", "s", "s", "s", "f"],
        attn_kinds={"f": full, "s": sliding},
        mlp_layer_types=["dense"] + ["sparse"] * 4, dense_mlp_dim=8192,
        mlp_dim=512, mlp_activation="silu", mlp_gated=True, mlp_bias=False,
        num_experts=256, moe_top_k=8, moe_dispatch="grouped",
        moe_score="sigmoid", moe_route_scale=2.5, moe_shared_dim=512)
    params, state = jax.eval_shape(
        lambda k: module.init(k, (16,))[:2], jax.random.PRNGKey(0))
    slots, page_len, s = 32, 128, _spec(one_chip)
    wide, ring = 16384 // page_len, 5
    groups = (None, (0, None), (1, wide), (1, wide), (1, wide), (0, None),
              None, None)
    probe = jax.eval_shape(lambda: init_cache(module, 1, page_len,
                                              jnp.bfloat16, check_len=16384))
    cache = [None if kv is None else
             {k: s(((slots * wide if groups[i][0] == 0 else slots * 9),)
                   + a.shape[1:], a.dtype) for k, a in kv.items()}
             for i, kv in enumerate(probe)]

    def on_chip(tree, dtype=None):
        return jax.tree_util.tree_map(
            lambda a: s(a.shape, dtype if dtype is not None and a.ndim >= 2
                        else a.dtype), tree)

    def step(params, state, cache, tok, t, tables):
        logits, cache, moe = decode_step_slots_paged(
            module, params, state, cache, tok, t, tables, page_len,
            moe_stats=16384, groups=groups)
        return jnp.argmax(logits, -1), cache, moe["routed"]

    per_slot = s((slots,), jnp.int32)
    compiled = jax.jit(step, donate_argnums=2).lower(
        on_chip(params, jnp.bfloat16), on_chip(state), cache, per_slot,
        per_slot, (s((slots, wide), jnp.int32),
                   s((slots, ring), jnp.int32))).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 9
    assert len(re.findall(r"paged_window_attention", text)) >= 3
    leaves = jax.tree_util.tree_leaves(cache)
    assert text.split("\n", 1)[0].count("may-alias") == len(leaves)
    planes = {int(np.prod(a.shape)) for a in leaves}
    moved = [line.strip()[:160] for line in text.splitlines()
             if (dims := re.search(r"= \w+\[([\d,]+)\]\S* copy\(", line))
             and int(np.prod(list(map(int, dims.group(1).split(",")))))
             in planes]
    assert not moved, moved[:2]
    # the pool is 4.75 GB where one group for all five layers is 10.7
    assert compiled.memory_analysis().alias_size_in_bytes < 4.8e9


# --- latent attention and a held share of experts (LongCat-Flash's widths) ---

def _kernel_grid_and_vmem(text, name):
    """From a compiled program's text, the grid of the Mosaic kernel
    ``name`` (the ``iteration_bounds`` of the module the custom call
    carries) and the scoped VMEM the compiler gave it, in bytes."""
    import base64
    import re
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir
    call, = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and f" %{name}" in line.split(" = ", 1)[0]]
    body = base64.b64decode(re.search(r'"body":"([^"]+)"', call).group(1))
    ctx = jax_mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = str(ir.Module.parse(body))
    grid = re.search(r"iteration_bounds = array<i64: ([\d, ]+)>", module)
    vmem = re.search(r'"used_scoped_memory_configs":\[\{"memory_space":"1",'
                     r'"offset":"0","size":"(\d+)"\}\]', call)
    return tuple(map(int, grid.group(1).split(","))), int(vmem.group(1))


def test_paged_latent_attention_longcat_widths(one_chip, as_tpu):
    """The latent kernel as a decode step calls it: 32 slots of 16,384
    positions in pages of 128, 64 query heads over ONE plane of 576
    values a token (down a column: the positions are the lanes) whose
    first 512 are the value. A program reads G = 8 pages: an eighth of
    the (slot, page) pairs in the grid, inside the compiler's scoped
    VMEM (16 MiB on a v5e)."""
    from distkeras_tpu.ops.paged_attention import (latent_pages_per_program,
                                                   paged_latent_attention)
    s = _spec(one_chip)
    slots, heads, c, page_len = 32, 64, 576, 128
    fn = lambda q, pages, t, tb: paged_latent_attention(
        q, pages, t, tb, v_dim=512, scale=192 ** -0.5)
    n, text = _compile(fn, s((slots, 1, heads, c), jnp.bfloat16),
                       s((3072, c, page_len), jnp.bfloat16),
                       s((slots,), jnp.int32), s((slots, 128), jnp.int32))
    assert n == 1 and "paged_latent_attention" in text
    g = latent_pages_per_program(page_len, 128, c, heads, 512, jnp.bfloat16)
    grid, vmem = _kernel_grid_and_vmem(text, "paged_latent_attention")
    assert g == 8 and int(np.prod(grid)) <= slots * 128 // g
    assert g * c * page_len * 2 * 2 < vmem < 16 * 2 ** 20


@pytest.mark.parametrize("keys,causal", [(2048, True), (12288, False)],
                         ids=["chunk", "prefix"])
def test_flash_prefill_two_widths(one_chip, as_tpu, keys, causal):
    """Flash forward with queries and keys of 192 and values of 128: a
    prefill chunk of 2,048 on itself, and over a cached prefix of 12,288
    rebuilt from the latent (head-major, with its log-sum-exp)."""
    from distkeras_tpu.models.decoding import _attn_lse
    s = _spec(one_chip)
    fn = lambda q, k, v: _attn_lse(q, k, v, causal=causal,
                                   scale=192 ** -0.5, layout="bhsd")
    n, _ = _compile(fn, s((1, 64, 2048, 192), jnp.bfloat16),
                    s((1, 64, keys, 192), jnp.bfloat16),
                    s((1, 64, keys, 128), jnp.bfloat16))
    assert n == 1


@pytest.mark.parametrize("tokens", [32, 2048], ids=["step", "prefill_chunk"])
def test_grouped_experts_held_share(one_chip, as_tpu, tokens):
    """Top-12 of 768 router outputs with 16 experts of width 2048 held:
    the layout's tiles as tall as the router's mean group, one tile an
    expert past the assignments' worst case."""
    from distkeras_tpu.ops.moe_kernels import (grouped_block_rows,
                                               grouped_experts,
                                               grouped_tiles)
    s = _spec(one_chip)
    e, d, f, a = 16, 6144, 2048, tokens * 12
    rows = grouped_block_rows(a, 768)
    tiles = grouped_tiles(a, e, rows)
    assert rows == (16 if tokens == 32 else 32)
    fn = lambda x, te, used, w1, w2, w3: grouped_experts(
        x, te, used, w1, w2, w3, block_rows=rows, activation="silu")
    n, _ = _compile(fn, s((tiles * rows, d), jnp.bfloat16),
                    s((tiles,), jnp.int32), s((), jnp.int32),
                    s((e, d, f), jnp.bfloat16), s((e, f, d), jnp.bfloat16),
                    s((e, d, f), jnp.bfloat16))
    assert n == 1


def _longcat_module(layers):
    from distkeras_tpu.models import zoo
    latent = dict(q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, q_scale=2.0,
                  kv_scale=12 ** 0.5)
    return zoo.transformer_lm(
        16384, d_model=6144, num_heads=64, num_layers=layers, max_len=16384,
        dtype="bfloat16", norm_eps=1e-5, layer_types=["mla"] * layers,
        attn_kinds={"mla": {"rope_base": 1e7, "latent": latent}},
        mlp_layer_types=["shortcut"] * layers, dense_mlp_dim=12288,
        mlp_dim=2048, mlp_activation="silu", mlp_gated=True, mlp_bias=False,
        num_experts=512, moe_top_k=12, moe_dispatch="grouped",
        moe_norm_topk=False, moe_route_scale=6.0, moe_zero_experts=256,
        moe_experts_held=(0, 16), moe_select_bias=True)


def _on_chip(tree, s, dtype=None):
    return jax.tree_util.tree_map(
        lambda a: s(a.shape, dtype if dtype is not None and a.ndim >= 2
                    else a.dtype), tree)


def test_decode_step_over_latent_pages(one_chip, as_tpu):
    """One decode step of two double layers at the cell's widths over a
    donated pool of latent pages: four latent kernels and two grouped
    expert kernels, every plane aliased and none copied, the planes one
    vector of 576 a token with no head axis."""
    import re
    from distkeras_tpu.models.decoding import (decode_step_slots_paged,
                                               init_cache)
    module = _longcat_module(2)
    params, state = jax.eval_shape(
        lambda k: module.init(k, (16,))[:2], jax.random.PRNGKey(0))
    slots, page_len, pages, s = 32, 128, 3072, _spec(one_chip)
    probe = jax.eval_shape(lambda: init_cache(module, pages, page_len,
                                              jnp.bfloat16, check_len=16384))
    cache = [None if kv is None else
             {k: s(a.shape, a.dtype) for k, a in kv.items()} for kv in probe]
    assert {a.shape for kv in cache if kv for a in kv.values()} \
        == {(pages, 576, page_len)}

    def step(params, state, cache, tok, t, tables):
        logits, cache, moe = decode_step_slots_paged(
            module, params, state, cache, tok, t, tables, page_len,
            moe_stats=16384)
        return jnp.argmax(logits, -1), cache, moe["routed"]

    per_slot = s((slots,), jnp.int32)
    text = jax.jit(step, donate_argnums=2).lower(
        _on_chip(params, s, jnp.bfloat16), _on_chip(state, s), cache,
        per_slot, per_slot, s((slots, 128), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 6
    assert len(re.findall(r"paged_latent_attention", text)) >= 4
    leaves = jax.tree_util.tree_leaves(cache)
    assert text.split("\n", 1)[0].count("may-alias") == len(leaves)
    plane = pages * page_len * 576
    moved = [line.strip()[:160] for line in text.splitlines()
             if (dims := re.search(r"= \w+\[([\d,]+)\]\S* copy\(", line))
             and int(np.prod(list(map(int, dims.group(1).split(","))))) == plane]
    assert not moved, moved[:2]


@pytest.mark.parametrize("t0,final", [(0, False), (12288, True)],
                         ids=["cold_chunk", "question_over_prefix"])
def test_prefill_chunk_over_latent_prefix(one_chip, as_tpu, t0, final):
    """A prefill chunk of 2,048 through one double layer at the cell's
    widths against the batch-1 staging cache: the first of a cold document
    (one flash pass a block) and a question over a cached prefix of 12,288
    latents (two a block, merged), with what the expert layer routed."""
    from distkeras_tpu.models.decoding import (init_cache,
                                               prefill_chunk_step,
                                               routing_counts)
    module = _longcat_module(1)
    params, state = jax.eval_shape(
        lambda k: module.init(k, (16,))[:2], jax.random.PRNGKey(0))
    s = _spec(one_chip)
    cache = _on_chip(jax.eval_shape(
        lambda: init_cache(module, 1, 16384, jnp.bfloat16)), s)

    def chunk(params, state, cache, toks):
        routing = []
        logits, cache = prefill_chunk_step(module, params, state, cache,
                                           toks, t0, final=final,
                                           routing=routing)
        return logits, cache, routing_counts(routing)

    text = jax.jit(chunk, donate_argnums=2).lower(
        _on_chip(params, s, jnp.bfloat16), _on_chip(state, s), cache,
        s((1, 2048), jnp.int32)).compile().as_text()
    # flash: one a block, two over a prefix. The last block of a chunk that
    # is not the final one only writes its latents, and nothing reads what
    # the expert layer before it hands on: its kernel is not in the program
    flash = (2 if t0 else 1) * (2 if final else 1)
    assert text.count('custom_call_target="tpu_custom_call"') \
        == flash + (1 if final else 0)
