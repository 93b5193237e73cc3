"""Mixture-of-experts MLP with expert parallelism over a mesh axis.

Absent from the reference (SURVEY §2.3: expert parallelism "out of scope"
for the Spark design) — this is a TPU-native addition. Design:

  * Expert weights are stacked on a leading ``[num_experts, ...]`` axis, so
    expert parallelism is a single ``PartitionSpec("expert", ...)`` shard of
    that axis (see ``parallel.sharding``).
  * Two routing executions share one router:

    - ``dispatch="dense"``: static-shape masked top-k — the router's
      softmax is masked to the top-k experts per token and every (local)
      expert runs on every token. No gather/scatter, no capacity drops,
      exact — but every token pays ALL experts' FLOPs (E/top_k× the
      dispatched cost). Kept as the numerics oracle and for tiny shapes
      where dispatch bookkeeping dominates.
    - ``dispatch="tokens"`` (round 3; round 4 made it sort-free; round 5
      took the dispatch traffic to its primitive floor): the
      capacity-based GShard/Switch construction with static shapes.
      Each slot's position within its expert comes from an exclusive
      cumsum over one-hot masks in choice-major order (every token's
      first choice outranks all second choices); each expert takes its
      first ``capacity`` arrivals, dropped slots contribute nothing.
      Per-token expert FLOPs are ``top_k * capacity_factor`` MLPs
      instead of ``E`` — the compute-sparse economics the name
      promises. Round 5 exploits the choice-major slot structure
      (slot->token map = ``tile(arange(N), K)``): the buffer build is a
      free broadcast into ONE drop-mode unique-indices scatter, and the
      combine is a gather + reshape-sum — one big scatter and one big
      gather per direction, measured at the chip's gather/scatter
      primitive rate (docs/PERF.md §MoE has the per-category table and
      the measured-negative ragged_dot/unroll alternatives).
    - ``dispatch="fused"`` (round 6): the Pallas fused path
      (``ops/moe_kernels.py``) — the dispatch gather happens INSIDE the
      expert up-projection kernel (token rows are DMA'd from the
      residual stream straight into contiguous VMEM tiles, MegaBlocks-
      style), so the ``tokens`` path's [K*N, d] scatter and [E*C, d]
      HBM dispatch buffer never materialize; the backward pass is the
      gather's transpose in a custom VJP (also gathers — see the kernel
      module doc). Identical routing/drop/tie-break/NaN semantics to
      ``tokens`` (both consume one ``_dispatch_plan``). Off-TPU the
      layer automatically falls back to the ``tokens`` XLA floor
      (``compat.backend_is_tpu`` — the repo's one backend convention);
      tests force the interpreter via ``moe_kernels.force_interpret``.

    - ``dispatch="grouped"`` (serving of many small experts: top-8 of
      128 at width 768): DROP-FREE without a capacity. The ``n * k``
      routed rows are laid out sorted by expert, each expert's group
      padded to whole tiles (at most ``E`` tiles of padding, skipped),
      and one program per tile multiplies it with that tile's expert
      (``ops/moe_kernels.py::grouped_experts``; off a TPU the same
      layout through plain XLA). Every routed row is computed once,
      only experts that own a row are read, and a token's output never
      depends on who shares its batch. Bias-free experts only, gated
      or not; inference only (no custom gradient).

  * Expert parallelism: under GSPMD (``SPMDTrainer``) the stacked expert
    einsums partition on the expert axis automatically from the weight
    shardings. Under ``shard_map`` (``expert_axis_name``) tokens are
    replicated across the axis, so each shard slices its experts' rows of
    the dispatch tensor — strictly cheaper than an all_to_all — computes
    its ``E/A`` experts, and the combined outputs are ``psum``'d. For
    token-sharded meshes (ep doubling as a data axis) see
    ``moe_all_to_all`` below: the full GShard all_to_all exchange.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from distkeras_tpu.compat import note_path
from distkeras_tpu.models.core import (AUX_LOSS_KEY, Layer,
                                       register_layer)
from distkeras_tpu.models.layers import get_activation, init_weights


def _dispatch_plan(experts, gates, num_experts: int, capacity: int,
                   valid=None):
    """Static-shape dispatch bookkeeping.

    ``valid`` ([N, K] bool; a layer that holds a share of the experts):
    assignments to experts that are not here. Their ``experts`` entry is
    ``num_experts`` (one past the last), they take no place in any
    expert's buffer and come back dropped.

    experts/gates: [N, K] top-k expert ids / combine weights per token.
    Returns (dest, token, weight, keep) flat [N*K] slot arrays in
    choice-major slot order: ``dest`` indexes an [E*C (+1 overflow)]
    buffer.
    Priority is choice-major (slot s = k*N + n): all first choices beat
    all second choices, ties broken by token order — the GShard rule.
    """
    n, k = experts.shape
    slot_e = experts.T.reshape(-1)                      # [K*N] choice-major
    slot_t = jnp.tile(jnp.arange(n, dtype=jnp.int32), k)
    slot_g = gates.T.reshape(-1)
    # position-in-expert via an exclusive cumsum over one-hot masks (the
    # GShard/Switch construction) — round 4: this replaced a stable
    # argsort over the [K*N] slot keys, which on TPU lowers to a
    # many-pass bitonic sort and dominated the dispatch wall clock; the
    # cumsum is a cheap log-depth scan and needs no reordering at all
    # (slots stay in choice-major order, which IS the priority order).
    onehot = jax.nn.one_hot(slot_e, num_experts, dtype=jnp.int32)
    ranks = jnp.cumsum(onehot, axis=0) - onehot         # [K*N, E] exclusive
    if valid is None:
        pos = jnp.take_along_axis(ranks, slot_e[:, None], axis=1)[:, 0]
        keep = pos < capacity
    else:
        pos = jnp.take_along_axis(
            ranks, jnp.minimum(slot_e, num_experts - 1)[:, None],
            axis=1)[:, 0]
        keep = jnp.logical_and(pos < capacity, valid.T.reshape(-1))
    # dropped slots get UNIQUE out-of-range sentinels (E*C + slot index),
    # not one shared overflow value: the consumers scatter with
    # unique_indices=True, a promise a shared sentinel would break
    # (implementation-defined behavior per the XLA scatter contract —
    # review r5); mode="drop" discards every OOB row either way
    dest = jnp.where(keep, slot_e * capacity + pos,
                     num_experts * capacity
                     + jnp.arange(n * k, dtype=pos.dtype))
    return dest, slot_t, slot_g, keep


@register_layer
class MoE(Layer):
    """Top-k gated mixture of expert MLPs over [B, S, d_model]."""

    def __init__(self, num_experts: int, hidden_dim: int, top_k: int = 2,
                 activation: str = "gelu", dtype: str = "float32",
                 expert_axis_name: Optional[str] = None,
                 kernel_init: str = "glorot_uniform",
                 aux_loss_weight: float = 0.0,
                 dispatch: str = "dense",
                 capacity_factor: float = 1.25,
                 expert_unroll: bool = False,
                 gated: bool = False, use_bias: bool = True,
                 score: str = "softmax", norm_topk: bool = True,
                 route_scale: float = 1.0,
                 shared_dim: Optional[int] = None,
                 zero_experts: int = 0,
                 experts_held: Optional[tuple] = None,
                 select_bias: bool = False):
        self.num_experts = int(num_experts)
        #: ZERO-COMPUTE experts: router outputs ``num_experts ..
        #: num_experts + zero_experts - 1`` are identity experts, a token
        #: that picks one gets ``gate * x`` from it, no weights, no work
        self.zero_experts = int(zero_experts)
        #: ``(lo, n)``: this layer HOLDS experts ``lo .. lo + n - 1`` of
        #: the ``num_experts`` the router chooses among (one chip's share
        #: of an expert-parallel deployment, on one chip: no axis, no
        #: exchange). The stacked weights are ``[n, ...]``; what the
        #: absent experts would add is left out, the identity experts
        #: are all computed. None: all of them
        self.experts_held = None
        if experts_held is not None:
            lo, n = (int(v) for v in experts_held)
            if lo < 0 or n < 1 or lo + n > self.num_experts:
                raise ValueError(
                    f"experts_held {experts_held} is not a range of the "
                    f"{self.num_experts} experts")
            self.experts_held = (lo, n)
        #: a per-output bias added to the scores to CHOOSE the top k; the
        #: gates are the scores without it (``params["select_bias"]``)
        self.select_bias = bool(select_bias)
        #: outputs of the router, and experts whose weights are here
        self.router_dim = self.num_experts + self.zero_experts
        self._held_lo, self.num_held = self.experts_held \
            or (0, self.num_experts)
        self._partial = bool(self.zero_experts or self.experts_held)
        if self._partial and (expert_axis_name or aux_loss_weight
                              or dispatch == "fused"):
            raise ValueError(
                "zero_experts / experts_held serve on one chip: no "
                "expert_axis_name, no aux_loss_weight, not "
                "dispatch='fused'")
        #: how a router logit becomes a gate: ``"softmax"`` over the k
        #: chosen logits (renormalised by construction; with
        #: ``norm_topk=False`` or a ``select_bias`` the softmax is over
        #: ALL outputs and the k chosen keep their probabilities as they
        #: are), or ``"sigmoid"`` of each logit, divided by the sum over
        #: the k chosen (``norm_topk``); either way times ``route_scale``
        if score not in ("softmax", "sigmoid"):
            raise ValueError(
                f"score must be 'softmax' or 'sigmoid', got {score!r}")
        self.score = score
        self.norm_topk = bool(norm_topk)
        self.route_scale = float(route_scale)
        #: a shared expert of this width (the experts' own form: gated
        #: or not, biased or not) that every token takes beside its
        #: routed ones, unweighted; None: no shared expert
        self.shared_dim = None if shared_dim is None else int(shared_dim)
        self.shared = None
        if self.shared_dim:
            from distkeras_tpu.models.attention import TransformerMLP
            self.shared = TransformerMLP(
                self.shared_dim, activation=activation, dtype=dtype,
                kernel_init=kernel_init, gated=gated, use_bias=use_bias)
        #: gated experts (SwiGLU with ``activation="silu"``):
        #: ``w2(act(x w1) * (x w3))``; ``use_bias=False`` drops b1/b2
        self.gated = bool(gated)
        self.use_bias = bool(use_bias)
        self.hidden_dim = int(hidden_dim)
        self.top_k = int(top_k)
        self.activation = activation
        self.dtype = dtype
        self.expert_axis_name = expert_axis_name
        self.kernel_init = kernel_init
        # Switch/GShard load-balancing loss coefficient: adds
        # ``weight · E · Σ_e f_e·P_e`` to the TRAINING loss (f_e = fraction
        # of routing slots sent to expert e, P_e = mean router prob),
        # pushing the router away from expert collapse. Published via the
        # AUX_LOSS_KEY state channel (parallel.worker picks it up).
        self.aux_loss_weight = float(aux_loss_weight)
        if dispatch not in ("dense", "tokens", "fused", "grouped"):
            raise ValueError(
                "dispatch must be 'dense', 'tokens', 'fused' or "
                f"'grouped', got {dispatch!r}")
        if dispatch == "grouped" and (use_bias or expert_axis_name):
            raise ValueError(
                "dispatch='grouped' runs bias-free experts on one chip "
                "(use_bias=False, no expert_axis_name); one chip's share "
                "of an expert-parallel layer is experts_held=(lo, n), "
                "which needs no axis")
        self.dispatch = dispatch
        # expert capacity = ceil(top_k * tokens / E) * capacity_factor:
        # at 1.0 a perfectly balanced router drops nothing; the default
        # headroom absorbs imbalance while training the balance loss down
        self.capacity_factor = float(capacity_factor)
        # round 5, measured on v5e and left OPT-IN: the stacked
        # [E, C, d] x [E, d, f] einsum lowers to XLA's batched-dot
        # emitter (EmitAllBatchInSublanes), ~40% MXU; statically
        # unrolling into groups of small clean dots microbenches 25-32%
        # faster (3.1 vs 3.9-4.4 ms fwd at E=8/C=4096) — but in the
        # 12-layer training graph the per-group slices + concat defeat
        # XLA's buffer aliasing and the step OOMs by ~1 GB at batch 8
        # (both 2 and 4 groups; full unroll also blows the compile
        # helper). Default stays False; the option remains for shapes
        # with spare HBM. Also keep False under GSPMD expert-axis
        # sharding (SPMDTrainer): per-expert slices of a sharded stacked
        # axis force cross-shard resharding — the shard_map path
        # (expert_axis_name) is unaffected, its weights arrive
        # pre-sliced.
        self.expert_unroll = bool(expert_unroll)

    def init(self, rng, input_shape):
        d = input_shape[-1]
        e, hid = self.num_held, self.hidden_dim
        kg, k1, k2 = jax.random.split(rng, 3)
        # per-expert init: split so experts start decorrelated
        w1 = jnp.stack([init_weights(self.kernel_init, k, (d, hid))
                        for k in jax.random.split(k1, e)])
        w2 = jnp.stack([init_weights(self.kernel_init, k, (hid, d))
                        for k in jax.random.split(k2, e)])
        params = {"gate": init_weights(self.kernel_init, kg,
                                       (d, self.router_dim)),
                  "w1": w1, "w2": w2}
        if self.select_bias:
            params["select_bias"] = jnp.zeros((self.router_dim,))
        if self.gated:
            params["w3"] = jnp.stack(
                [init_weights(self.kernel_init, k, (d, hid))
                 for k in jax.random.split(jax.random.fold_in(k1, 1), e)])
        if self.use_bias:
            params["b1"] = jnp.zeros((e, hid))
            params["b2"] = jnp.zeros((e, d))
        if self.shared is not None:
            params["shared"] = self.shared.init(
                jax.random.fold_in(kg, 1), input_shape)[0]
        state = {}
        if self.aux_loss_weight:
            state[AUX_LOSS_KEY] = jnp.zeros((), jnp.float32)
        return params, state, tuple(input_shape)

    def _route(self, x, gate, select_bias=None):
        """Shared router: ``(full, topi, gates, mask)`` — full softmax
        [B, S, E], top-k expert ids + their renormalized weights [B, S, K]
        (softmax over the k logits == the masked-softmax restriction, so
        the dense and dispatched paths combine with IDENTICAL weights),
        and the top-k slot mask for the balance loss (None at k == E).
        Top-k INDICES, not a >= kth-value test: on tied logits the value
        test would admit every tied expert."""
        # f32 router on purpose: routing decisions deserve full
        # precision, and a bf16-input variant was MEASURED at identical
        # wall clock (47.2K tok/s both ways, round 5) — the f32 upcast
        # is off the critical path, so there is no speed to buy here
        logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                            gate.astype(jnp.float32))
        if self.score == "sigmoid":
            # the k largest scores are the k largest logits; ``full``
            # (balance loss, entropy telemetry) is the scores as a
            # distribution over all experts
            scores = jax.nn.sigmoid(logits)
            full = scores / jnp.sum(scores, axis=-1, keepdims=True)
            topv, topi = lax.top_k(logits, self.top_k)
            gates = jax.nn.sigmoid(topv)
            if self.norm_topk:
                gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        elif self.norm_topk and select_bias is None:
            full = jax.nn.softmax(logits, axis=-1)
            topv, topi = lax.top_k(logits, self.top_k)
            gates = jax.nn.softmax(topv, axis=-1)
        else:
            # softmax over ALL outputs; the bias chooses and does not
            # weight; the chosen keep their probabilities
            full = jax.nn.softmax(logits, axis=-1)
            choose = full if select_bias is None \
                else full + select_bias.astype(jnp.float32)
            _, topi = lax.top_k(choose, self.top_k)
            gates = jnp.take_along_axis(full, topi, axis=-1)
            if self.norm_topk:
                gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        if self.route_scale != 1.0:
            gates = gates * self.route_scale
        mask = None
        if self.top_k < self.router_dim:
            mask = jax.nn.one_hot(topi, self.router_dim,
                                  dtype=jnp.bool_).any(axis=-2)
        return full, topi, gates, mask

    def _route_params(self, params, x):
        return self._route(x, params["gate"], params.get("select_bias"))

    def _local(self, topi, gates):
        """A partial layer's view of the routing: ``(ids, held,
        zero_w)``: each assignment's index among the experts HELD
        (``num_held``, one past the last, where its expert is not
        here), whether it is held, and per token the sum of the gates
        of the identity experts it chose."""
        local = topi - self._held_lo
        held = jnp.logical_and(local >= 0, local < self.num_held)
        zero_w = jnp.sum(jnp.where(topi >= self.num_experts, gates, 0.0),
                         axis=-1)
        return jnp.where(held, local, self.num_held), held, zero_w

    def routing_share(self):
        """``(lo, n, num_experts)`` of a partial layer (what
        ``models.decoding.routing_counts`` splits the rows by), or
        None."""
        if not self._partial:
            return None
        return (self._held_lo, self.num_held, self.num_experts)

    def _with_shared(self, params, x, out):
        """``out`` plus the shared expert's output for ``x`` (every
        token, unweighted), where the layer has one."""
        if self.shared is None:
            return out
        y, _ = self.shared.apply(params["shared"], {}, x)
        return out + y.astype(out.dtype)

    def _gate_probs(self, x, gate):
        """Routing weights [B, S, E] (softmax over top-k logits, 0
        elsewhere) plus the full softmax and slot mask for the balance
        loss (the dense path's view of ``_route``)."""
        full, topi, gates, mask = self._route(x, gate)
        probs = jnp.einsum(
            "bske,bsk->bse",
            jax.nn.one_hot(topi, self.num_experts, dtype=gates.dtype),
            gates)
        return probs, full, mask

    def _balance_loss(self, full, mask):
        """E · Σ_e f_e·P_e (Switch eq. 4, GShard): minimized at uniform
        routing, where it equals 1."""
        e = self.num_experts
        if mask is None:            # top_k == E: every slot hits every expert
            frac = jnp.full((e,), 1.0 / e)
        else:
            frac = jnp.mean(mask.astype(jnp.float32), axis=(0, 1)) \
                / self.top_k        # fraction of routing slots per expert
        pmean = jnp.mean(full, axis=(0, 1))
        return e * jnp.sum(frac * pmean)

    def _capacity(self, n_tokens: int) -> int:
        per = -(-self.top_k * n_tokens // self.num_experts)  # ceil
        return max(1, int(per * self.capacity_factor))

    @staticmethod
    def _expert_axis_sharded(w) -> bool:
        """Best-effort: True when a CONCRETE stacked expert weight
        carries a non-replicated GSPMD sharding on its leading (expert)
        axis — the configuration where ``expert_unroll``'s per-expert
        slices force cross-shard resharding collectives every step
        (see ``__init__``). Mirrors the ``replicated()`` probe in
        ``decoding._fuse_qkv_params``; inside jit/shard_map the weights
        are tracers with no sharding attribute and this stays False
        (the shard_map path's weights arrive pre-sliced and are safe;
        the GSPMD-trainer path is covered at SETUP time instead, where
        ``parallel.sharding._rule_MoE`` warns on the concrete
        layer-config x expert-axis combination)."""
        sh = getattr(w, "sharding", None)
        if sh is None or getattr(sh, "is_fully_replicated", True):
            return False
        spec = getattr(sh, "spec", None)
        return bool(spec) and spec[0] is not None

    def _expert_mlp(self, xe, params):
        """Run the stacked expert MLP on [E(_local), C, d]. Under
        shard_map expert parallelism the weights arrive pre-sliced to the
        shard's experts; under GSPMD the einsums partition on ``e`` from
        the weight shardings automatically (set ``expert_unroll=False``
        there — see __init__)."""
        dt = jnp.dtype(self.dtype)
        act = get_activation(self.activation)
        w1 = params["w1"].astype(dt)
        w2 = params["w2"].astype(dt)
        e_here = xe.shape[0]
        if self.gated or not self.use_bias:
            # gated and bias-free experts: the plain stacked products
            h = act(jnp.einsum("ecd,edf->ecf", xe, w1)
                    + (params["b1"].astype(dt)[:, None, :]
                       if self.use_bias else 0))
            if self.gated:
                h = h * jnp.einsum("ecd,edf->ecf", xe,
                                   params["w3"].astype(dt))
            return jnp.einsum("ecf,efd->ecd", h, w2) \
                + (params["b2"].astype(dt)[:, None, :]
                   if self.use_bias else 0)
        b1 = params["b1"].astype(dt)
        b2 = params["b2"].astype(dt)
        unroll = self.expert_unroll
        if unroll and self._expert_axis_sharded(params["w1"]):
            import warnings
            warnings.warn(
                "MoE(expert_unroll=True) with expert-axis-sharded "
                "stacked weights (GSPMD): per-expert slices of a "
                "sharded axis pay cross-shard resharding collectives "
                "every step — falling back to the batched expert dot "
                "for this call. Replicate the expert weights or use "
                "shard_map expert parallelism (expert_axis_name) to "
                "unroll.", stacklevel=3)
            unroll = False
        if unroll and e_here > 1:
            # static unroll into small groups of batched dots: measured
            # sweep on v5e (E=8, C=4096) — 4 groups 3.1/3.4 ms fwd/f+g
            # vs 3.9/4.0 for the single batched dot; FULL unroll (8
            # groups) microbenches the same but its 12-layer training
            # graph blows past the compile helper / HBM (round 5), so
            # groups are capped at 4
            ng = 4 if e_here % 4 == 0 else (2 if e_here % 2 == 0 else 1)
            gsz = e_here // ng
            outs = []
            for g in range(ng):
                sl = slice(g * gsz, (g + 1) * gsz)
                if gsz == 1:
                    h = act(xe[g * gsz] @ w1[g * gsz] + b1[g * gsz])
                    outs.append((h @ w2[g * gsz] + b2[g * gsz])[None])
                else:
                    h = act(jnp.einsum("ecd,edf->ecf", xe[sl], w1[sl])
                            + b1[sl][:, None, :])
                    outs.append(jnp.einsum("ecf,efd->ecd", h, w2[sl])
                                + b2[sl][:, None, :])
            return jnp.concatenate(outs, axis=0)
        h = act(jnp.einsum("ecd,edf->ecf", xe, w1) + b1[:, None, :])
        return jnp.einsum("ecf,efd->ecd", h, w2) + b2[:, None, :]

    def _apply_dispatched(self, params, x, *, fused=False, capacity=None,
                          return_routing=False):
        """Capacity-based (sort-free) dispatch — static shapes; see
        module doc. ``capacity`` overrides the training-time
        ``_capacity`` formula (the decode path passes the full token
        count — drop-free by construction, see :meth:`decode_apply`);
        ``return_routing`` appends the top-k expert ids ``[B, S, K]``
        to the return tuple (the serving engine's expert-load
        telemetry reads them).

        Round 5 (dispatch-traffic restructure, measured in docs/PERF.md
        §MoE): slot ``s = k*N + n`` is CHOICE-major, so the slot->token
        map is ``tile(arange(N), K)`` — pure structure. Exploiting it:

          * the slot-input build is a free ``broadcast_to`` (round 4
            gathered ``xt[st]``, a real [K*N, d] gather whose transpose
            was a real scatter-add);
          * the combine is ``reshape(K, N, d).sum(0)`` (round 4
            scatter-added into ``zeros.at[st]``, whose transpose was
            another gather).

        One [K*N, d] scatter (buffer build) + one gather (combine read)
        remain per direction — half the round-4 traffic; their cost is
        the dispatch's irreducible price on one chip — UNLESS the
        Pallas fused path takes over (``fused=True``, round 6): there
        the SAME plan's indices drive in-kernel row DMA instead, and
        neither the scatter nor the [E*C, d] buffer exists
        (``ops/moe_kernels.py``)."""
        dt = jnp.dtype(self.dtype)
        b, s, d = x.shape
        n = b * s
        e, k = self.num_held, self.top_k
        c = self._capacity(n) if capacity is None else int(capacity)
        full, topi, gates, mask = self._route_params(params, x)
        # the fused kernels are written for biased single-activation
        # experts; gated or bias-free ones take the XLA tokens floor
        fused = fused and self.use_bias and not self.gated \
            and not self._partial

        if self._partial:
            ids, held, zero_w = self._local(topi, gates)
            dest, _st, sg, keep = _dispatch_plan(
                ids.reshape(n, k), gates.reshape(n, k), e, c,
                valid=held.reshape(n, k))
        else:
            dest, _st, sg, keep = _dispatch_plan(
                topi.reshape(n, k), gates.reshape(n, k), e, c)
        xt = x.reshape(n, d).astype(dt)

        note_path("moe", "fused_kernel" if fused else "tokens_xla")
        if fused:
            from distkeras_tpu.ops import moe_kernels
            w1 = params["w1"].astype(dt)
            b1 = params["b1"].astype(dt)
            w2 = params["w2"].astype(dt)
            b2 = params["b2"].astype(dt)
            if self.expert_axis_name is None:
                out = moe_kernels.fused_moe_apply(
                    xt, w1, b1, w2, b2, sg, dest, keep,
                    capacity=c, activation=self.activation)
            else:
                # tokens replicated across the axis (as in the XLA path
                # below): each shard runs the kernel over ITS experts
                # only. The global plan localizes by offsetting ``dest``
                # into this shard's rows; slots belonging to other
                # shards get unique OUT-OF-RANGE sentinels (negative
                # indices would WRAP in the plan-inversion scatter) and
                # a cleared ``keep``, so they contribute exact zeros and
                # the psum over the axis reassembles the full combine.
                el = params["w1"].shape[0]
                idx = lax.axis_index(self.expert_axis_name)
                dest_l = dest - idx * el * c
                keep_l = jnp.logical_and(
                    keep, jnp.logical_and(dest_l >= 0, dest_l < el * c))
                dest_l = jnp.where(
                    keep_l, dest_l,
                    el * c + jnp.arange(n * k, dtype=dest.dtype))
                out = moe_kernels.fused_moe_apply(
                    xt, w1, b1, w2, b2, sg, dest_l, keep_l,
                    capacity=c, activation=self.activation)
                out = lax.psum(out, self.expert_axis_name)
            if return_routing:
                return out.reshape(b, s, d), full, mask, topi
            return out.reshape(b, s, d), full, mask

        src = jnp.broadcast_to(xt[None], (k, n, d)).reshape(k * n, d)
        # dropped slots (dest == E*C) fall off via mode="drop";
        # unique_indices lets XLA skip collision handling (the overflow-
        # row form made every dropped slot collide on one row: measured
        # 3.15 -> 2.46 ms for the [32K, 1024] scatter on v5e, round 5)
        xe = jnp.zeros((e * c, d), dt).at[dest].set(
            src, mode="drop", unique_indices=True)

        if self.expert_axis_name is None:
            ye = self._expert_mlp(xe.reshape(e, c, d), params)
            # combine in the COMPUTE dtype (round 4): the f32 combine
            # buffers ([E*C, d] twice per layer) doubled the dispatch
            # HBM traffic and fed XLA's memory-pressure remat; at most
            # top_k contributions sum per token, well within bf16
            ye_flat = ye.reshape(e * c, d)
        else:
            # tokens are replicated across the axis: each shard runs only
            # its pre-sliced experts on its rows of the dispatch buffer,
            # then the flat outputs are psum-combined (disjoint supports)
            el = params["w1"].shape[0]
            idx = lax.axis_index(self.expert_axis_name)
            xe_l = lax.dynamic_slice_in_dim(
                xe.reshape(e, c, d), idx * el, el, 0)
            ye_l = self._expert_mlp(xe_l, params)
            ye_flat = jnp.zeros((e * c, d), dt) \
                .at[jnp.arange(el * c, dtype=jnp.int32) + idx * el * c] \
                .set(ye_l.reshape(el * c, d))
            ye_flat = lax.psum(ye_flat, self.expert_axis_name)
        # dropped slots' dest clamps into range on the gather; the WHERE
        # (not a bare keep-multiply) forces their contribution to exact
        # zero even if the clamped-into expert row is inf/NaN (inf * 0
        # would poison the dropped token — review r5). Masking the
        # GATHERED ROWS, then multiplying by the gate, keeps the
        # backward clean too: where(keep, row*sg, 0) would still send
        # d(sg) = 0 * inf = NaN into the router gradient.
        safe = jnp.where(keep[:, None], ye_flat[dest], jnp.zeros((), dt))
        contrib = safe * sg[:, None].astype(dt)
        out = contrib.reshape(k, n, d).sum(axis=0)
        if self.zero_experts:
            out = (out.astype(jnp.float32) + zero_w.reshape(n, 1)
                   * xt.astype(jnp.float32)).astype(dt)
        if return_routing:
            return out.reshape(b, s, d), full, mask, topi
        return out.reshape(b, s, d), full, mask

    def _apply_grouped(self, params, x):
        """``dispatch="grouped"``: route, lay the ``n * k`` routed rows
        out by expert in whole tiles, multiply each tile with its
        expert, and sum each token's k rows under its gates (module
        doc). Returns ``(out [B, S, d], full, mask, topi)`` like
        ``_apply_dispatched(return_routing=True)``."""
        from distkeras_tpu.ops import moe_kernels
        dt = jnp.dtype(self.dtype)
        b, s, d = x.shape
        n, e, k = b * s, self.num_held, self.top_k
        full, topi, gates, mask = self._route_params(params, x)
        # a tile is as tall as the mean group: of the router's outputs,
        # where the layer holds a share of them
        rows = moe_kernels.grouped_block_rows(n * k, self.router_dim)
        m = moe_kernels.grouped_tiles(n * k, e, rows) * rows
        if self._partial:
            # rows routed to experts that are not here have no place in
            # the layout: nothing gathers them, no tile computes them
            ids, held, zero_w = self._local(topi, gates)
            dest, tile_expert, used, _counts = moe_kernels.grouped_layout(
                ids.reshape(n * k).astype(jnp.int32), e, rows,
                valid=held.reshape(n * k))
            row_token = jnp.zeros((m,), jnp.int32).at[dest].set(
                jnp.arange(n * k, dtype=jnp.int32) // k,
                unique_indices=True, mode="drop")
        else:
            dest, tile_expert, used, _counts = moe_kernels.grouped_layout(
                topi.reshape(n * k).astype(jnp.int32), e, rows)
            # row -> token (padding rows read token 0: finite, never
            # summed)
            row_token = jnp.zeros((m,), jnp.int32).at[dest].set(
                jnp.arange(n * k, dtype=jnp.int32) // k,
                unique_indices=True)
        x_rows = x.reshape(n, d).astype(dt)[row_token]
        kw = dict(block_rows=rows, activation=self.activation)
        w = [params[name].astype(dt) for name in
             (("w1", "w2", "w3") if self.gated else ("w1", "w2"))]
        if moe_kernels.fused_supported():
            note_path("moe", "grouped_kernel")
            y_rows = moe_kernels.grouped_experts(
                x_rows, tile_expert, used, *w, **kw)
        else:
            note_path("moe", "grouped_xla_reference")
            y_rows = moe_kernels.grouped_experts_reference(
                x_rows, tile_expert, used, *w, **kw)
        y = y_rows[dest].reshape(n, k, d).astype(jnp.float32)
        if self._partial:
            y = jnp.where(held.reshape(n, k, 1), y, 0.0)
        out = jnp.sum(y * gates.reshape(n, k, 1), axis=1)
        if self.zero_experts:
            out = out + zero_w.reshape(n, 1) \
                * x.reshape(n, d).astype(jnp.float32)
        return out.reshape(b, s, d).astype(dt), full, mask, topi

    def decode_apply(self, params, x, *, return_routing=False):
        """Decode-specialized dispatched MoE (the serving engine's
        per-step path; MoE-serving PR).

        ``x`` is the ``[S, W, d]`` slot-token batch of one decode step
        (W = 1) or speculative-verify window (W = k+1). Capacity is
        sized to the FULL token count ``n = S * W``: a token's top-k
        expert ids are distinct, so no expert can receive more than
        ``n`` arrivals — the dispatch is drop-free BY CONSTRUCTION and
        the output equals dense routing exactly (same ``_route``
        weights, same per-token dot products), up to fp reassociation.
        That is the serving correctness contract: routing can never
        alter a stream's tokens, and a slot's output is independent of
        which neighbours share the batch (a dropped slot's keep-flag
        would otherwise flip with batch composition).

        Execution ignores the layer's configured ``dispatch`` mode —
        decode-time dispatch is the ENGINE's choice: the fused Pallas
        gather-into-GEMM runs at decode shapes on TPU
        (``moe_kernels.fused_supported``, same plan, same %8-padded
        capacity), the XLA ``tokens`` floor everywhere else. At the
        small-n decode regime both beat the dense path's
        ``[S, E, W, f]`` broadcast einsums (measured ~1.1-1.8x per
        layer on CPU; docs/serving.md §MoE serving has the table).

        Under shard_map expert parallelism (``expert_axis_name``) the
        weights arrive pre-sliced and the combine psums over the axis
        — per-chip expert-weight traffic shrinks with the mesh.

        Returns ``[S, W, d]`` (no aux-loss state: decode never
        trains); with ``return_routing`` also ``(topi [S, W, K], full
        [S, W, E])`` — the top-k expert ids and the full router softmax
        — for expert-load/entropy telemetry."""
        from distkeras_tpu.ops import moe_kernels
        b, s, _d = x.shape
        if self.dispatch == "grouped":
            # drop-free already, and with no capacity to size
            out, full, _mask, topi = self._apply_grouped(params, x)
        else:
            out, full, _mask, topi = self._apply_dispatched(
                params, x, fused=moe_kernels.fused_supported(),
                capacity=b * s, return_routing=True)
        out = self._with_shared(params, x, out.astype(x.dtype))
        if return_routing:
            return out, (topi, full)
        return out

    def apply(self, params, state, x, *, training=False, rng=None):
        dt = jnp.dtype(self.dtype)

        if self.dispatch == "grouped":
            out, full, mask, _topi = self._apply_grouped(params, x)
            out = self._with_shared(params, x, out.astype(x.dtype))
            new_state = state
            if self.aux_loss_weight and training:
                new_state = dict(state)
                new_state[AUX_LOSS_KEY] = (self.aux_loss_weight *
                                           self._balance_loss(full, mask))
            return out.astype(x.dtype), new_state

        if self.dispatch in ("tokens", "fused"):
            use_fused = False
            if self.dispatch == "fused":
                # one backend convention repo-wide (compat.backend_is_tpu,
                # consulted inside fused_supported): kernels on TPU or
                # under a test's force_interpret; the XLA-floor tokens
                # path — same plan, same numerics — everywhere else
                from distkeras_tpu.ops import moe_kernels
                use_fused = moe_kernels.fused_supported()
            out, full, mask = self._apply_dispatched(params, x,
                                                     fused=use_fused)
            out = self._with_shared(params, x, out.astype(x.dtype))
            new_state = state
            if self.aux_loss_weight and training:
                new_state = dict(state)
                new_state[AUX_LOSS_KEY] = (self.aux_loss_weight *
                                           self._balance_loss(full, mask))
            return out.astype(x.dtype), new_state

        note_path("moe", "dense_xla")
        zero_w = None
        if self._partial or self.select_bias:
            # gates over the experts HELD (zero outside the token's
            # choice), and the identity experts' share of the token
            full, topi, gates, mask = self._route_params(params, x)
            ids, _held, zero_w = self._local(topi, gates)
            probs = jnp.einsum(
                "bske,bsk->bse",
                jax.nn.one_hot(ids, self.num_held, dtype=gates.dtype),
                gates)
        else:
            probs, full, mask = self._gate_probs(x, params["gate"])  # f32

        xc = x.astype(dt)
        # local experts: [El, ...] slice when sharded over the expert axis
        h = jnp.einsum("bsd,edf->besf", xc, params["w1"].astype(dt))
        act = get_activation(self.activation)
        if self.use_bias:
            h = h + params["b1"].astype(dt)[None, :, None, :]
        h = act(h)
        if self.gated:
            h = h * jnp.einsum("bsd,edf->besf", xc,
                               params["w3"].astype(dt))
        y = jnp.einsum("besf,efd->besd", h, params["w2"].astype(dt))
        if self.use_bias:
            y = y + params["b2"].astype(dt)[None, :, None, :]

        if self.expert_axis_name is None:
            out = jnp.einsum("bse,besd->bsd", probs.astype(dt), y)
        else:
            # Sharded: this shard holds experts [idx*El, (idx+1)*El); pick
            # the matching slice of the (replicated) router probabilities,
            # then combine across the axis.
            el = y.shape[1]
            idx = lax.axis_index(self.expert_axis_name)
            local = lax.dynamic_slice_in_dim(probs, idx * el, el, axis=-1)
            out = jnp.einsum("bse,besd->bsd", local.astype(dt), y)
            out = lax.psum(out, self.expert_axis_name)
        if zero_w is not None:
            out = out + (zero_w[..., None] * x).astype(out.dtype)
        out = self._with_shared(params, x, out.astype(x.dtype))
        new_state = state
        if self.aux_loss_weight and training:
            # router inputs/gate are replicated under expert sharding, so
            # this value is identical on every shard — no psum needed
            new_state = dict(state)
            new_state[AUX_LOSS_KEY] = (self.aux_loss_weight *
                                       self._balance_loss(full, mask))
        return out.astype(x.dtype), new_state

    def get_config(self):
        return {"num_experts": self.num_experts, "hidden_dim": self.hidden_dim,
                "top_k": self.top_k, "activation": self.activation,
                "dtype": self.dtype,
                "expert_axis_name": self.expert_axis_name,
                "kernel_init": self.kernel_init,
                "aux_loss_weight": self.aux_loss_weight,
                "dispatch": self.dispatch,
                "capacity_factor": self.capacity_factor,
                "expert_unroll": self.expert_unroll,
                "gated": self.gated, "use_bias": self.use_bias,
                "score": self.score, "norm_topk": self.norm_topk,
                "route_scale": self.route_scale,
                "shared_dim": self.shared_dim,
                "zero_experts": self.zero_experts,
                "experts_held": self.experts_held,
                "select_bias": self.select_bias}


def moe_all_to_all(moe: MoE, params, x, *, axis_name: str):
    """Token-SHARDED expert parallelism: the full GShard all_to_all
    exchange, for meshes where the expert axis doubles as a data axis
    (each shard holds DIFFERENT tokens and ``E/A`` experts).

    Must be called inside a ``shard_map`` where ``x`` is batch-sharded and
    the expert-stacked weights are sharded over ``axis_name``. Flow per
    shard: route the local tokens; build the local [E, Cs, d] dispatch
    buffer (Cs = local capacity); ``all_to_all`` so each shard receives
    every source's rows for ITS experts ([El, A*Cs, d]); run the local
    experts; ``all_to_all`` back; combine locally. Compute AND tokens both
    scale 1/A per device — contrast ``MoE.apply``'s replicated-token
    path, where only compute does.

    Returns ``(out, aux)`` with ``aux = (full_probs, topk_mask)`` for the
    balance loss (which must then be ``lax.pmean``'d over ``axis_name`` —
    shards see different tokens).
    """
    if moe.dispatch not in ("tokens", "fused"):
        raise ValueError(
            "moe_all_to_all requires dispatch='tokens' (or 'fused', "
            "which composes identically here: the exchange buffer is "
            "materialized BY the all_to_all, so there is no dispatch "
            "scatter for the fused kernel to remove)")
    dt = jnp.dtype(moe.dtype)
    b, s, d = x.shape
    n = b * s                                       # LOCAL tokens
    e, k = moe.num_experts, moe.top_k
    a = lax.psum(1, axis_name)
    el = params["w1"].shape[0]
    if el * a != e:
        raise ValueError(
            f"num_experts {e} != local experts {el} x axis size {a}")
    cs = moe._capacity(n)                           # per-source capacity

    full, topi, gates, mask = moe._route(x, params["gate"])

    dest, _st, sg, keep = _dispatch_plan(
        topi.reshape(n, k), gates.reshape(n, k), e, cs)
    xt = x.reshape(n, d).astype(dt)
    # choice-major structure exploited as in _apply_dispatched (round 5):
    # broadcast build + drop/unique scatter + reshape-sum combine
    src = jnp.broadcast_to(xt[None], (k, n, d)).reshape(k * n, d)
    xe = jnp.zeros((e * cs, d), dt).at[dest].set(
        src, mode="drop", unique_indices=True)
    # [E, Cs, d] -> exchange: send expert-block a' to shard a', receive
    # one block per source concatenated on the capacity axis
    xe = xe.reshape(e, cs, d)
    recv = lax.all_to_all(xe, axis_name, split_axis=0, concat_axis=1,
                          tiled=True)               # [El, A*Cs, d]
    ye_l = moe._expert_mlp(recv, params)            # local experts
    back = lax.all_to_all(ye_l, axis_name, split_axis=1, concat_axis=0,
                          tiled=True)               # [E, Cs, d]
    ye_flat = back.reshape(e * cs, d).astype(jnp.float32)
    # mask the gathered rows BEFORE the gate multiply: exact zero for
    # dropped slots in forward AND backward even when the clamped gather
    # row is non-finite (see _apply_dispatched)
    contrib = jnp.where(keep[:, None], ye_flat[dest], 0.0) * sg[:, None]
    out = contrib.reshape(k, n, d).sum(axis=0)
    return out.reshape(b, s, d).astype(x.dtype), (full, mask)
