"""Expert-parallel MoE FFN: top-k gating, capacity bucketing, all-to-all.

The layer replaces a transformer block's dense FFN (every
``moe_layer_freq``-th block — models/transformer.py wires it behind
``TransformerConfig.moe``). Design:

- **Routing** is a linear router + softmax + ``top_k`` (k in {1, 2});
  the kept gates renormalize to sum 1 (GShard top-2). Routing math runs
  in fp32 regardless of the compute dtype.
- **Capacity bucketing** gives ONE compiled shape regardless of routing:
  each device builds a ``[E, C, H]`` dispatch buffer (C =
  ``ceil(capacity_factor * k * T / E)`` for its T local tokens) by
  scatter; tokens beyond an expert's capacity are DROPPED — their
  combine contribution is exactly 0, so they ride the block's residual
  path untouched. Assignment priority is j-major (every token's first
  choice before any second choice), position-in-expert by running count.
- **Expert parallelism**: with ``expert_parallel_size`` (ep) > 1 the
  whole token path runs under a fully-manual ``shard_map`` over the
  mesh (old-jax safe: no partial-auto axes) — the batch enters sharded
  over ``(expert, data)``, expert weights enter as their ``expert``-axis
  shards, and dispatch/combine are real ``lax.all_to_all`` collectives
  over the ``expert`` axis (tiled, split=concat=0; applying the same
  exchange twice is the identity, which is exactly the combine). The
  shard_map transpose gives expert-weight gradients their psum over
  ``data`` ONLY — experts are not replicas, and a dense all-reduce
  across the expert axis is the seeded-violation case the
  collective_placement lint pass catches.
- **Losses/stats**: the load-balance aux loss (Switch/GShard:
  ``E * sum(f_e * P_e)``, f from the routed counts treated as constant,
  P the mean router probability) and the router z-loss
  (``mean(logsumexp(logits)^2)``) come back as stats alongside the
  per-expert routed token counts and the drop fraction; the model adds
  the weighted losses to its objective and the engine rides the stats
  on the telemetry drain (no extra syncs).

``num_experts=1, top_k=1`` with unbounded capacity reduces to the dense
FFN bit-for-bit: the single gate renormalizes to exactly 1.0, every
token keeps its slot in order, and the expert einsum contracts the same
[H] axis the dense matmul does (tests/test_moe.py asserts bitwise
equality against the dense block).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.fused_elementwise import bias_gelu
from ..ops.grouped_gemm import grouped_ffn, grouped_gemm_enabled
from ..parallel import comm
from ..parallel.topology import DP_AXIS, EP_AXIS

# The block-param keys the MoE FFN owns (models/transformer.py routes a
# per-layer params dict containing these through moe_ffn instead of the
# dense FFN). Stacked leading axis = the MoE layers only.
MOE_PARAM_KEYS = frozenset({
    "router_kernel", "moe_fc_kernel", "moe_fc_bias",
    "moe_out_kernel", "moe_out_bias",
})


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Model-side MoE hyperparameters (``TransformerConfig.moe``).

    Mirrors the ``moe`` ds_config block (constants.py) — build one from
    it with ``MoEConfig.from_ds_config`` so the engine's expert mesh and
    the model's expert count cannot drift apart.
    """
    num_experts: int = 8
    top_k: int = 2                      # k in {1, 2}
    capacity_factor: float = 1.25       # inf => no token ever drops
    aux_loss_weight: float = 1e-2
    z_loss_weight: float = 1e-3
    expert_parallel_size: int = 1       # ep — the `expert` mesh axis size
    # Expert-FFN compute path: "auto" = the grouped-GEMM Pallas kernel
    # on TPU / the einsum path on CPU (DS_GROUPED_GEMM=0/1 overrides),
    # True/False force. cfg-static exactly like TransformerConfig.
    # fused_kernels — flipping it changes the program, never the
    # compiled signature or the checkpoint state.
    grouped_gemm: Any = "auto"

    def __post_init__(self):
        assert self.num_experts >= 1, "num_experts must be >= 1"
        assert self.top_k in (1, 2), "top_k must be 1 or 2"
        assert self.top_k <= self.num_experts
        assert self.capacity_factor > 0
        assert self.expert_parallel_size >= 1
        assert self.num_experts % self.expert_parallel_size == 0, \
            (f"num_experts={self.num_experts} not divisible by "
             f"expert_parallel_size={self.expert_parallel_size}")
        assert self.grouped_gemm in (True, False, "auto"), \
            f"grouped_gemm must be True/False/'auto', got " \
            f"{self.grouped_gemm!r}"

    @classmethod
    def from_ds_config(cls, moe_cfg) -> "MoEConfig":
        """From a parsed ``runtime.config.MoeConfig`` (the ds_config
        ``moe`` block)."""
        return cls(num_experts=moe_cfg.num_experts, top_k=moe_cfg.top_k,
                   capacity_factor=moe_cfg.capacity_factor,
                   aux_loss_weight=moe_cfg.aux_loss_weight,
                   z_loss_weight=moe_cfg.z_loss_weight,
                   expert_parallel_size=moe_cfg.expert_parallel_size,
                   grouped_gemm=getattr(moe_cfg, "grouped_gemm", "auto"))


def expert_capacity(tokens: int, num_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    """Per-expert slot count C for a device routing ``tokens`` local
    tokens: ``ceil(cf * k * T / E)``, clamped to [1, T] (an expert can
    receive at most T distinct tokens from one device — top-k choices
    are distinct experts). ``inf`` capacity => C = T, nothing drops."""
    if math.isinf(capacity_factor):
        return max(1, tokens)
    c = int(math.ceil(capacity_factor * top_k * tokens / num_experts))
    return max(1, min(c, tokens))


def moe_layer_indices(num_layers: int, moe_layer_freq: int) -> List[int]:
    """Which block indices carry the MoE FFN: every ``freq``-th block,
    counting from the first (layer freq-1, 2*freq-1, ...)."""
    assert moe_layer_freq >= 1
    return [i for i in range(num_layers) if (i + 1) % moe_layer_freq == 0]


def router_topk(x32: jnp.ndarray, router_kernel: jnp.ndarray, top_k: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                           jnp.ndarray]:
    """fp32 routing: ``(gates [T,k], expert_idx [T,k], probs [T,E],
    logits [T,E])``. Gates renormalize over the kept k (exactly 1.0 for
    k=1 — IEEE x/x — which is what makes the E=1 path bit-identical to
    dense)."""
    logits = x32 @ router_kernel.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, idx = lax.top_k(probs, top_k)
    gates = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    return gates, idx, probs, logits


def _dispatch_plan(idx: jnp.ndarray, num_experts: int, capacity: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Token -> bucket-slot assignment. ``idx``: [T, k] expert choices.

    Returns ``(dest [k*T] int32 in [0, E*C] (E*C = dropped), keep [k*T]
    bool, routed_counts [E] f32)`` in j-major order (choice 0 of every
    token outranks any choice 1 — the GShard priority)."""
    T, k = idx.shape
    idx_j = idx.T.reshape(-1)                                   # [k*T]
    oh = jax.nn.one_hot(idx_j, num_experts, dtype=jnp.float32)  # [k*T, E]
    prior = jnp.cumsum(oh, axis=0) - oh
    pos_in_e = jnp.sum(prior * oh, axis=-1).astype(jnp.int32)
    keep = pos_in_e < capacity
    dest = jnp.where(keep, idx_j * capacity + pos_in_e,
                     num_experts * capacity)
    return dest, keep, jnp.sum(oh, axis=0)


def _moe_tokens(params: Dict[str, jnp.ndarray], xt: jnp.ndarray,
                moe: MoEConfig, gelu_approx: bool, ep: int
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The per-device token path: route -> bucket -> (all-to-all) ->
    expert FFN -> (all-to-all) -> weighted combine. ``xt``: [T, H] local
    tokens in the compute dtype; expert weights arrive ep-sliced
    ([E/ep, ...]) when ep > 1. Returns (y [T, H], local stats)."""
    T, H = xt.shape
    E, k = moe.num_experts, moe.top_k
    C = expert_capacity(T, E, k, moe.capacity_factor)
    gates, idx, probs, logits = router_topk(
        xt.astype(jnp.float32), params["router_kernel"], k)
    dest, keep, counts = _dispatch_plan(idx, E, C)

    # Scatter into the fixed [E, C, H] dispatch buffer (row E*C is the
    # drop bin; (e, pos) slots are unique by construction).
    xk = jnp.tile(xt, (k, 1))                                   # [k*T, H]
    buckets = jnp.zeros((E * C + 1, H), xt.dtype).at[dest].set(xk)
    b = buckets[:E * C].reshape(E, C, H)

    if ep > 1:
        # Dispatch: expert-major split. After the tiled exchange, row
        # s*E_loc + j on member r holds source member s's bucket for
        # local expert j — regroup to [E_loc, ep*C, H] so each local
        # expert sees every source's candidates.
        e_loc = E // ep
        b = comm.all_to_all(b, EP_AXIS, 0, 0)
        b = b.reshape(ep, e_loc, C, H).transpose(1, 0, 2, 3) \
             .reshape(e_loc, ep * C, H)

    w1 = params["moe_fc_kernel"].astype(xt.dtype)
    b1 = params["moe_fc_bias"].astype(xt.dtype)
    w2 = params["moe_out_kernel"].astype(xt.dtype)
    b2 = params["moe_out_bias"].astype(xt.dtype)
    if grouped_gemm_enabled(moe.grouped_gemm):
        # One Pallas grouped GEMM per projection: grid over experts x
        # row blocks x col blocks, fp32 MXU accumulation, bias + GELU
        # fused in-register (ops/grouped_gemm.py). Shard-LOCAL: under
        # ep > 1 this runs inside the `expert` shard_map scope on the
        # [E/ep, ...] slices — no collective moves for the kernel.
        y = grouped_ffn(b, w1, b1, w2, b2, not gelu_approx)
    else:
        # The dense FFN's own function, so that one expert IS the dense
        # layer bit for bit (tests/test_moe.py::TestDenseParity).
        h = bias_gelu(jnp.einsum("ech,ehf->ecf", b, w1), b1[:, None, :],
                      not gelu_approx)
        y = jnp.einsum("ecf,efh->ech", h, w2) + b2[:, None, :]

    if ep > 1:
        # Combine: the inverse regroup + the SAME tiled all-to-all (the
        # exchange is an involution), landing each expert output back on
        # its source member in the original [E, C, H] bucket layout.
        e_loc = E // ep
        y = y.reshape(e_loc, ep, C, H).transpose(1, 0, 2, 3) \
             .reshape(E, C, H)
        y = comm.all_to_all(y, EP_AXIS, 0, 0)

    # Gather back per token; dropped tokens hit the appended zero row,
    # so their FFN delta is exactly 0 (pure residual).
    yf = jnp.concatenate([y.reshape(E * C, H),
                          jnp.zeros((1, H), y.dtype)], axis=0)
    yk = yf[dest]                                               # [k*T, H]
    gk = gates.T.reshape(-1).astype(yf.dtype)
    out = jnp.sum((yk * gk[:, None]).reshape(k, T, H), axis=0)

    frac = lax.stop_gradient(counts) / (k * T)
    stats = {
        "expert_tokens": counts,                                # [E] f32
        "drop_fraction":
            1.0 - jnp.sum(keep.astype(jnp.float32)) / (k * T),
        "aux_loss": E * jnp.sum(frac * jnp.mean(probs, axis=0)),
        "z_loss": jnp.mean(jnp.square(
            jax.scipy.special.logsumexp(logits, axis=-1))),
    }
    return out, stats


def moe_ffn(params: Dict[str, jnp.ndarray], x: jnp.ndarray, cfg,
            mesh=None) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The MoE FFN sublayer. ``x``: [B, S, H] (compute dtype); ``params``
    holds this layer's ``MOE_PARAM_KEYS`` (no stacking axis); ``cfg`` is
    the ``TransformerConfig`` (reads ``cfg.moe`` and ``cfg.gelu_exact``).

    ep == 1 runs the plain jnp path (no collectives; GSPMD partitions
    the token math over ``data`` as usual). ep > 1 needs ``mesh`` and
    runs fully-manual shard_map: batch over ``(expert, data)``, expert
    weights over ``expert``, stats psum/pmean'd to replicated. Returns
    ``(y [B, S, H], stats)`` with GLOBAL stats either way — on the
    engine's explicit-shard_map path (which runs this per-dp-rank with
    ep == 1) the engine reduces the stats itself."""
    moe: MoEConfig = cfg.moe
    gelu_approx = not cfg.gelu_exact
    B, S, H = x.shape
    ep = moe.expert_parallel_size
    if ep <= 1:
        y, stats = _moe_tokens(params, x.reshape(B * S, H), moe,
                               gelu_approx, ep=1)
        return y.reshape(B, S, H), stats

    if comm.axis_in_scope(EP_AXIS):
        # Already INSIDE a fully-manual shard_map over (expert, data) —
        # the engine's factored explicit-gradient path runs the whole
        # loss that way so dense grads can reduce-scatter over `data`
        # (the stage-2 declarative regression this closes). Params
        # arrived as their expert-axis shards and ``x`` is the local
        # batch slab: run the token path bare; dispatch/combine bind to
        # the in-scope `expert` axis directly and the stats psum to
        # global exactly like the self-wrapped path below.
        y, stats = _moe_tokens(params, x.reshape(B * S, H), moe,
                               gelu_approx, ep=ep)
        axes = (EP_AXIS, DP_AXIS)
        stats = {
            "expert_tokens": lax.psum(stats["expert_tokens"], axes),
            "drop_fraction": lax.pmean(stats["drop_fraction"], axes),
            "aux_loss": lax.pmean(stats["aux_loss"], axes),
            "z_loss": lax.pmean(stats["z_loss"], axes),
        }
        return y.reshape(B, S, H), stats

    if mesh is None:
        # No mesh (eval/serving on fully-addressable params —
        # gpt2_apply on a fetched tree): every expert is local, so the
        # ep == 1 path computes the same routed FFN with no collective.
        # Drop margins can differ from the sharded step (capacity
        # derives from the GLOBAL token count here vs per-device there);
        # training always passes the mesh.
        y, stats = _moe_tokens(params, x.reshape(B * S, H), moe,
                               gelu_approx, ep=1)
        return y.reshape(B, S, H), stats
    if EP_AXIS not in mesh.shape or int(mesh.shape[EP_AXIS]) != ep:
        raise ValueError(
            f"mesh has no '{EP_AXIS}' axis of size {ep} "
            f"(mesh shape: {dict(mesh.shape)}); build it with "
            f"build_mesh(ep={ep}, ...)")
    for ax, size in mesh.shape.items():
        if ax not in (EP_AXIS, DP_AXIS) and int(size) > 1:
            raise NotImplementedError(
                f"moe expert parallelism composes with expert x data "
                f"meshes only for now (live '{ax}' axis of size {size})")

    def local(rk, w1, b1, w2, b2, xl):
        bl, sl, hl = xl.shape
        p = {"router_kernel": rk, "moe_fc_kernel": w1, "moe_fc_bias": b1,
             "moe_out_kernel": w2, "moe_out_bias": b2}
        y, stats = _moe_tokens(p, xl.reshape(bl * sl, hl), moe,
                               gelu_approx, ep=ep)
        # Global stats, replicated out: counts SUM over every member
        # (they are counts), the rest mean.
        axes = (EP_AXIS, DP_AXIS)
        stats = {
            "expert_tokens": lax.psum(stats["expert_tokens"], axes),
            "drop_fraction": lax.pmean(stats["drop_fraction"], axes),
            "aux_loss": lax.pmean(stats["aux_loss"], axes),
            "z_loss": lax.pmean(stats["z_loss"], axes),
        }
        return y.reshape(bl, sl, hl), stats

    batch_spec = P((EP_AXIS, DP_AXIS))
    fn = comm.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(EP_AXIS), P(EP_AXIS), P(EP_AXIS), P(EP_AXIS),
                  batch_spec),
        out_specs=(batch_spec, P()), check_vma=False)
    return fn(params["router_kernel"], params["moe_fc_kernel"],
              params["moe_fc_bias"], params["moe_out_kernel"],
              params["moe_out_bias"], x)


def aggregate_moe_stats(stacked: Dict[str, jnp.ndarray]
                        ) -> Dict[str, jnp.ndarray]:
    """Reduce per-MoE-layer stats (leading layer axis, from the block
    scan's ys or a stacked unrolled list) to the per-step record:
    counts/fractions/losses average over the MoE layers."""
    return jax.tree_util.tree_map(lambda a: jnp.mean(a, axis=0), stacked)
