#!/usr/bin/env python
"""The quickest proof that the trainer and the server still start on
the chip: drive both main paths once through the entry points a user
calls, at the full width of the models ``bench.py`` measures (random
weights from ``--seed``), and check what comes out by the repo's own
means.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # the mesh paths, nothing else

One process, JAX touched once, no children. Every phase prints one
JSON line (name, seconds, compile seconds, what it checked); a phase
that fails raises and the exit code is not 0. Without a TPU the script
exits non-zero before any phase and prints no result. The last line of
stdout is ``{"ok": true, "device": {...}}`` with the device as JAX
reports it. Seconds printed here are smoke timings (compile included),
not performance numbers.
"""

from __future__ import annotations

import argparse
import gc
import json
import re
import sys
import time

import numpy as np


def require(ok, what: str) -> None:
    """A check that fails the phase (never ``assert``: -O strips it)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def run_phase(name: str, fn, **kwargs) -> None:
    from distkeras_tpu import obs
    comp0, t0 = obs.compile_totals(), time.perf_counter()
    checked = fn(**kwargs)
    comp = obs.compile_totals()
    print(json.dumps({
        "phase": name,
        "seconds": round(time.perf_counter() - t0, 2),
        "compile_seconds": round(comp["seconds"] - comp0["seconds"], 2),
        "compiles": comp["count"] - comp0["count"],
        "checked": checked}), flush=True)
    gc.collect()          # the next phase needs this one's device memory


def _host(tree):
    """Host copy of a parameter tree (trainers may donate the original)."""
    import jax
    return jax.tree_util.tree_map(np.array, tree)


def _changed_fraction(before, after) -> float:
    import jax
    b, a = jax.tree_util.tree_leaves(before), jax.tree_util.tree_leaves(after)
    return float(np.mean([not np.array_equal(x, np.asarray(y))
                          for x, y in zip(b, a)]))


def _training_checks(trainer, before, trained, steps: int) -> dict:
    """Every trainer phase: ``steps`` finite losses, params moved."""
    losses = np.ravel(trainer.get_history().losses())  # [step(, worker)]
    changed = _changed_fraction(before, trained.params)
    require(losses.size == steps, f"{steps} losses, got {losses}")
    require(np.isfinite(losses).all(), f"finite losses, got {losses}")
    require(changed > 0.9, f"params changed ({changed:.2f} of leaves)")
    return {"losses": [round(float(x), 4) for x in losses],
            "param_leaves_changed": round(changed, 3)}


def _kernel_names(text: str) -> dict:
    """Pallas kernels in a lowered/compiled program's text, by the
    stable ``name=`` every ``pallas_call`` in ``ops/`` carries."""
    names: dict = {}
    for n in re.findall(r'kernel_name = "([^"]+)"', text):
        names[n] = names.get(n, 0) + 1
    return names


# --- models ----------------------------------------------------------------

def lm_module(cfg):
    from distkeras_tpu.models import zoo
    return zoo.transformer_lm(
        cfg["vocab"], d_model=cfg["d_model"], num_heads=cfg["num_heads"],
        num_layers=cfg["num_layers"], mlp_ratio=cfg["mlp_ratio"],
        use_rope=True, dtype="bfloat16", attn_impl="auto")


def lm_dataset(cfg, seed: int, n: int):
    from distkeras_tpu.data import Dataset
    rs = np.random.RandomState(seed)
    X = rs.randint(0, cfg["vocab"], (n, cfg["seq"])).astype(np.int32)
    return Dataset({"features": X, "label": np.roll(X, -1, axis=1)})


LM_TRAIN = dict(worker_optimizer="adam",
                optimizer_kwargs={"learning_rate": 1e-4},
                loss="sparse_categorical_crossentropy_from_logits",
                num_epoch=1, shuffle_each_epoch=False)


def image_dataset(seed: int, n: int, image: int, classes: int):
    from distkeras_tpu.data import Dataset
    rs = np.random.RandomState(seed)
    return Dataset({
        "features": rs.rand(n, image, image, 3).astype(np.float32),
        "label": rs.randint(0, classes, n)})


# --- one-chip phases -------------------------------------------------------

def train_resnet50(seed: int, batch: int = 64, steps: int = 4,
                   image: int = 224, classes: int = 1000):
    from distkeras_tpu.models import Model, zoo
    from distkeras_tpu.parallel import SingleTrainer
    model = Model.build(zoo.resnet50(num_classes=classes, dtype="bfloat16"),
                        (image, image, 3), seed=seed)
    before = _host(model.params)
    trainer = SingleTrainer(
        model, worker_optimizer="momentum",
        optimizer_kwargs={"learning_rate": 0.01},
        loss="sparse_categorical_crossentropy_from_logits",
        batch_size=batch, num_epoch=1, seed=seed)
    trained = trainer.train(image_dataset(seed, batch * steps, image,
                                          classes))
    return {"model": f"resnet50 {image}x{image}x3 bf16", "batch": batch,
            **_training_checks(trainer, before, trained, steps)}


def train_lm(seed: int, cfg, box: dict, batch: int = 8, steps: int = 4,
             kernels: bool = True):
    import jax
    from distkeras_tpu.models import Model
    from distkeras_tpu.parallel import SingleTrainer
    from distkeras_tpu.parallel.worker import TrainCarry, make_train_step
    module = lm_module(cfg)
    model = Model.build(module, (cfg["seq"],), seed=seed)
    before = _host(model.params)
    trainer = SingleTrainer(model, batch_size=batch, seed=seed, **LM_TRAIN)
    trained = trainer.train(lm_dataset(cfg, seed, batch * steps))
    checked = _training_checks(trainer, before, trained, steps)
    # the kernel ran, not ops.attention: lower the step the trainer
    # builds at the same shapes and read the kernels out of its text
    spec = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    opt = trainer.worker_optimizer
    carry = TrainCarry(spec(model.params), spec(model.state),
                       jax.eval_shape(opt.init, model.params),
                       jax.ShapeDtypeStruct((2,), np.uint32))
    xb = jax.ShapeDtypeStruct((batch, cfg["seq"]), np.int32)
    found = _kernel_names(jax.jit(make_train_step(
        module, trainer.loss, opt)).lower(carry, (xb, xb)).as_text())
    if kernels:
        for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
            require(found.get(k) == cfg["num_layers"],
                    f"{cfg['num_layers']} {k} kernels in the train step, "
                    f"found {found}")
    box["lm"] = trained
    return {"model": "transformer_lm " + json.dumps(cfg), "batch": batch,
            **checked, "train_step_kernels": found}


def _drain(engine, prompts, new_tokens: int):
    """Submit, step until every slot decodes, read one decode step's
    logits three ways on that state, then drain; every request must
    finish with its tokens. Returns ``(requests, logits_by_variant)``."""
    from distkeras_tpu.serving.scheduler import RequestState
    rids = [engine.submit(p, new_tokens) for p in prompts]
    done = {}
    for _ in range(4 * len(prompts) + 8):
        for r in engine.step():
            done[r.rid] = r
        if len(engine.scheduler.running) == len(prompts) \
                and engine.scheduler.next_prefill() is None:
            break
    require(len(engine.scheduler.running) == len(prompts),
            "every request decoding at once")
    logits = {"own": engine.decode_logits(),
              "gather": engine.decode_logits(decode_kernel="off")}
    if engine.health().get("moe"):
        logits["dense"] = engine.decode_logits(moe_decode="dense")
    steps = 0
    while engine.scheduler.pending:
        for r in engine.step():
            done[r.rid] = r
        steps += 1
        require(steps < 64 * new_tokens, "engine drains")
    reqs = [done[r] for r in rids]
    for r in reqs:
        require(r.state is RequestState.FINISHED
                and len(r.generated) == new_tokens,
                f"request {r.rid} finished with {new_tokens} tokens "
                f"({r.state}, {len(r.generated)})")
    return reqs, logits


def _agreement(a, b, what: str) -> dict:
    """``a`` against its reference ``b``, within ``LOGIT_TOL_ULPS``
    bf16 ulps at the logits' own magnitude."""
    diff = float(np.abs(a - b).max())
    agree = float((a.argmax(-1) == b.argmax(-1)).mean())
    peak = float(np.abs(b).max())
    tol = LOGIT_TOL_ULPS * 2.0 ** -8 * 2.0 ** np.ceil(np.log2(peak))
    require(np.isfinite(a).all() and np.isfinite(b).all(),
            f"{what}: finite logits")
    require(diff <= tol, f"{what}: max |dlogit| {diff} <= {tol} "
            f"({LOGIT_TOL_ULPS} bf16 ulps at |logit| {peak})")
    return {"max_abs_diff": round(diff, 5), "tol": round(tol, 5),
            "argmax_agreement": round(agree, 3),
            "logit_abs_max": round(peak, 3)}


def _tokens_match(reqs_a, reqs_b) -> float:
    same = [np.mean(np.asarray(a.generated) == np.asarray(b.generated))
            for a, b in zip(reqs_a, reqs_b)]
    return round(float(np.mean(same)), 3)


#: the models compute in bf16, so a kernel and its reference differ by
#: reduction order plus a bf16 rounding per layer: a few units in the
#: last place of the (bf16-valued) logits. First chip run: 1 and 1.5.
LOGIT_TOL_ULPS = 4


def serve_lm(seed: int, cfg, box: dict, num_slots: int = 8,
             max_len: int = 2304, lengths=(512, 768, 1024, 1536),
             prefix: int = 256, new_tokens: int = 32, kernels: bool = True):
    from distkeras_tpu.models import Model
    from distkeras_tpu.serving import ServingEngine
    model = box.pop("lm", None) or Model.build(
        lm_module(cfg), (cfg["seq"],), seed=seed)
    rs = np.random.RandomState(seed + 1)
    shared = rs.randint(0, cfg["vocab"], prefix)
    prompts = [np.concatenate([shared, rs.randint(
        0, cfg["vocab"], lengths[i % len(lengths)] - prefix)])
        .astype(np.int32) for i in range(num_slots)]

    engine = ServingEngine(model, num_slots=num_slots, max_len=max_len)
    reqs, logits = _drain(engine, prompts, new_tokens)
    summary, health = engine.metrics.summary(), engine.health()
    require(summary["prefix_cache"]["hits"] >= 1,
            f"prefix cache hit ({summary['prefix_cache']})")
    programs = health["programs"]
    if kernels:
        require("paged_attention=kernel" in programs["decode_greedy"],
                f"decode program holds the paged kernel ({programs})")
        require("flash_attention=kernel" in programs["prefill"],
                f"prefill program holds the flash kernel ({programs})")
    kernel_vs_gather = _agreement(logits["own"], logits["gather"],
                                  "paged kernel vs gather")
    del engine
    gc.collect()
    # the gather reference, end to end on the same prompts
    ref = ServingEngine(model, num_slots=num_slots, max_len=max_len,
                        decode_kernel="off")
    ref_reqs, _ = _drain(ref, prompts, new_tokens)
    require("paged_attention=gather_reference"
            in ref.health()["programs"]["decode_greedy"],
            "decode_kernel='off' engine took the gather path")
    return {"requests": len(reqs), "new_tokens": new_tokens,
            "prompt_lengths": [len(p) for p in prompts],
            "prefix_cache": summary["prefix_cache"],
            "programs": programs,
            "decode_step_kernel_vs_gather": kernel_vs_gather,
            "token_agreement_vs_gather_engine":
                _tokens_match(reqs, ref_reqs)}


def serve_moe(seed: int, cfg, num_slots: int = 4, max_len: int = 256,
              new_tokens: int = 16, kernels: bool = True):
    from distkeras_tpu.models import Model, zoo
    from distkeras_tpu.serving import ServingEngine
    model = Model.build(zoo.transformer_lm(
        cfg["vocab"], d_model=cfg["d_model"], num_heads=cfg["num_heads"],
        num_layers=cfg["num_layers"], mlp_ratio=cfg["mlp_ratio"],
        use_rope=True, dtype="bfloat16", moe_every=1,
        num_experts=cfg["num_experts"]), (64,), seed=seed)
    rs = np.random.RandomState(seed + 2)
    prompts = [rs.randint(0, cfg["vocab"], n).astype(np.int32)
               for n in (64, 96, 128, 160)[:num_slots]]
    engine = ServingEngine(model, num_slots=num_slots, max_len=max_len)
    reqs, logits = _drain(engine, prompts, new_tokens)
    programs = engine.health()["programs"]
    if kernels:
        require("moe=fused_kernel" in programs["decode_greedy"],
                f"dispatched decode took the fused kernel ({programs})")
    return {"requests": len(reqs), "new_tokens": new_tokens,
            "model": json.dumps(cfg), "programs": programs,
            "expert_path": [p for p in programs["decode_greedy"].split(", ")
                            if p.startswith("moe=")],
            "decode_step_dispatched_vs_dense": _agreement(
                logits["own"], logits["dense"],
                "dispatched vs dense MoE decode"),
            "decode_step_kernel_vs_gather": _agreement(
                logits["own"], logits["gather"],
                "paged kernel vs gather")}


def kernel_moe_fused(seed: int, tokens: int = 4096, d: int = 512,
                     hidden: int = 1024, experts: int = 8):
    """The repaired gather-GEMM kernels, forward AND backward, against
    the XLA ``tokens`` dispatch they replace (same plan, same drops)."""
    import jax
    import jax.numpy as jnp
    from distkeras_tpu.models.moe import MoE
    x = jax.random.normal(jax.random.PRNGKey(seed), (4, tokens // 4, d),
                          jnp.bfloat16)
    out = {}
    for dispatch in ("tokens", "fused"):
        moe = MoE(experts, hidden, top_k=2, dtype="bfloat16",
                  dispatch=dispatch)
        params, state, _ = moe.init(jax.random.PRNGKey(seed + 1),
                                    x.shape)

        def loss(p, x):
            y, _ = moe.apply(p, state, x, training=True)
            return jnp.mean(jnp.square(y.astype(jnp.float32))), y

        (_, y), g = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(params, x)
        out[dispatch] = jax.tree_util.tree_map(
            lambda a: np.asarray(a, np.float32), (y, g))
    rel = {}
    flat = lambda t: jax.tree_util.tree_leaves_with_path(t)
    for (path, a), (_, b) in zip(flat(out["tokens"]), flat(out["fused"])):
        name = jax.tree_util.keystr(path)
        require(np.isfinite(b).all(), f"fused {name} finite")
        rel[name] = float(np.linalg.norm(a - b)
                          / max(np.linalg.norm(a), 1e-30))
    worst = max(rel.values())
    require(worst <= 2e-2, f"fused vs tokens rel. error {rel}")
    return {"shape": {"tokens": tokens, "d": d, "hidden": hidden,
                      "experts": experts, "dtype": "bfloat16"},
            "worst_rel_error_vs_tokens_path": round(worst, 5), "tol": 2e-2}


def kernel_sampling(seed: int, slots: int = 8, vocab: int = 32768):
    """The repaired sampling epilogue: byte-identical tokens to the
    unfused sampler (the existing contract), now on the chip."""
    import jax
    import jax.numpy as jnp
    from distkeras_tpu.compat import record_paths
    from distkeras_tpu.models.decoding import _sample_vec
    from distkeras_tpu.ops.sampling import sample_tokens
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(seed),
                                     (slots, vocab), jnp.float32)
    temp = jnp.asarray(np.linspace(0.0, 1.4, slots), jnp.float32)
    topk = jnp.asarray([0, 5, 0, 50, 1, 0, 400, 7][:slots], jnp.int32)
    topp = jnp.asarray([1.0, 0.9, 0.5, 1.0, 0.8, 0.95, 0.7, 1.0][:slots],
                       jnp.float32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(slots) + seed)
    with record_paths() as paths:
        fused = np.asarray(jax.jit(sample_tokens)(logits, temp, topk, topp,
                                                  keys))
    plain = np.asarray(jax.jit(_sample_vec)(logits, temp, topk, topp, keys))
    require((fused == plain).all(),
            f"fused sampler byte-identical: {fused} vs {plain}")
    return {"slots": slots, "vocab": vocab, "path": sorted(paths),
            "tokens_identical": True}


# --- four-chip phases ------------------------------------------------------

def spmd_lm_vs_single(seed: int, cfg, batch: int = 8, steps: int = 4,
                      chips: int = 4, tol: float = 0.01,
                      kernels: bool = True):
    """SPMDTrainer over a dp mesh against SingleTrainer on one chip:
    same seed, same batches, per-step losses within ``tol`` (0.1% of a
    loss of ~10.4: the forward is the same per-example math, gradients
    all-reduce in bf16; the first four-chip run differed by 2e-5)."""
    from distkeras_tpu.models import Model
    from distkeras_tpu.parallel import SingleTrainer, SPMDTrainer, make_mesh
    data = lm_dataset(cfg, seed, batch * steps)

    single = SingleTrainer(Model.build(lm_module(cfg), (cfg["seq"],),
                                       seed=seed),
                           batch_size=batch, seed=seed, **LM_TRAIN)
    single.train(data)
    ref = single.get_history().losses()
    del single
    gc.collect()

    spmd = SPMDTrainer(Model.build(lm_module(cfg), (cfg["seq"],), seed=seed),
                       mesh=make_mesh(chips), batch_size=batch, seed=seed,
                       **LM_TRAIN)
    spmd.train(data)
    got = spmd.get_history().losses()
    diff = float(np.abs(np.asarray(got) - np.asarray(ref)).max())
    require(np.isfinite(got).all() and len(got) == steps,
            f"{steps} finite SPMD losses, got {got}")
    require(diff <= tol, f"dp={chips} losses {got} vs one chip {ref}: "
            f"max diff {diff} <= {tol}")
    want = list(range(chips))
    require(spmd.placement.get("params") == want
            and spmd.placement.get("batch") == want,
            f"params and batch on devices {want}: {spmd.placement}")
    checked = {"one_chip_losses": [round(float(x), 4) for x in ref],
               f"dp{chips}_losses": [round(float(x), 4) for x in got],
               "max_abs_loss_diff": round(diff, 5), "tol": tol,
               "placement": spmd.placement}
    if kernels:
        text = spmd.lower_epoch().compile().as_text()
        calls = len(re.findall(r'custom_call_target="tpu_custom_call"',
                               text))
        # q/k/v reach the kernel batch-sharded: an all-gather of a
        # [.., heads, seq, head_dim] operand would undo the shard_map
        head_dim = cfg["d_model"] // cfg["num_heads"]
        qkv_gathers = [ln.strip()[:160] for ln in text.splitlines()
                       if re.search(rf",{cfg['seq']},{head_dim}\]\S* "
                                    r"all-gather(-start)?\(", ln)]
        require(calls == 3 * cfg["num_layers"],
                f"{3 * cfg['num_layers']} flash custom calls per "
                f"partition, found {calls}")
        require(not qkv_gathers, f"no q/k/v all-gather: {qkv_gathers}")
        checked.update(flash_custom_calls_per_partition=calls,
                       qkv_all_gathers=0,
                       all_reduces=len(re.findall(r" all-reduce(-start)?\(",
                                                  text)))
    return checked


def aeasgd_resnet50(seed: int, chips: int = 4, batch: int = 16,
                    window: int = 4, image: int = 224, classes: int = 1000):
    """The reference's flagship: AEASGD, one communication window."""
    from distkeras_tpu.models import Model, zoo
    from distkeras_tpu.parallel import AEASGD, make_mesh
    model = Model.build(zoo.resnet50(num_classes=classes, dtype="bfloat16"),
                        (image, image, 3), seed=seed)
    before = _host(model.params)
    trainer = AEASGD(
        model, num_workers=chips, mesh=make_mesh(chips),
        communication_window=window, learning_rate=0.01,
        worker_optimizer="sgd",
        loss="sparse_categorical_crossentropy_from_logits",
        batch_size=batch, num_epoch=1, seed=seed)
    trained = trainer.train(image_dataset(seed, chips * batch * window,
                                          image, classes))
    checked = _training_checks(trainer, before, trained, chips * window)
    require(trainer.placement.get("worker_state") == list(range(chips)),
            f"worker state on {chips} devices: {trainer.placement}")
    return {"model": f"resnet50 {image}x{image}x3 bf16",
            "workers": chips, "window": window, "batch_per_worker": batch,
            **checked, "placement": trainer.placement}


# --- driver ----------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the mesh paths (SPMD dp=4 vs one chip, "
                    "AEASGD over 4 workers) and nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{device.platform!r}); nothing was run")
    if len(jax.devices()) != args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX reports "
                 f"{len(jax.devices())} devices")

    from bench import LM_CFG, MOE_SERVE_CFG   # the widths bench.py measures
    from distkeras_tpu.compat import enable_compile_cache
    from distkeras_tpu.data.native import native_status
    result = {"platform": device.platform, "kind": device.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({"phase": "device", **result,
                      "jax": jax.__version__,
                      "compile_cache": enable_compile_cache(),
                      "native": native_status()}), flush=True)

    seed = args.seed
    if args.chips == 1:
        box: dict = {}
        run_phase("train/resnet50", train_resnet50, seed=seed)
        run_phase("train/lm", train_lm, seed=seed, cfg=LM_CFG, box=box)
        run_phase("serve/lm", serve_lm, seed=seed, cfg=LM_CFG, box=box)
        run_phase("kernel/moe_fused", kernel_moe_fused, seed=seed)
        run_phase("serve/moe", serve_moe, seed=seed, cfg=MOE_SERVE_CFG)
        run_phase("kernel/sampling", kernel_sampling, seed=seed)
    else:
        run_phase("train/spmd_lm_dp4_vs_one_chip", spmd_lm_vs_single,
                  seed=seed, cfg=LM_CFG, chips=args.chips)
        run_phase("train/aeasgd_resnet50", aeasgd_resnet50, seed=seed,
                  chips=args.chips)
    print(json.dumps({"ok": True, "device": result}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
