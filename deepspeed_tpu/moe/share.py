"""An expert layer that holds a SHARE of its experts, routed by one of
two published rules (``models/blocks.Routing.rule``): sigmoid scores with a
selection bias, as the ``deepseek_v3`` family publishes it (and ``afmoe``,
the same rule with one group), or the largest logits weighted by a softmax
over the chosen (``smallthinker``).

Beside ``moe/layer.py`` (GShard: softmax top-1/2 into ``[E, C, H]``
capacity buffers, overflow dropped, all-to-all) this is the layer a
serving chip of an expert-parallel deployment runs:

- **Routing** over ALL ``n_routed_experts`` exactly as published, fp32
  throughout.  ``"sigmoid_bias"`` (``noaux_tc``): ``s = sigmoid(x Wg)``;
  ``c = s + b`` (the selection bias, for choosing only); a group's score
  is the sum of its two largest ``c``; the ``topk_group`` best groups
  stay; the ``num_experts_per_tok`` largest ``c`` inside them are chosen;
  the weights are ``s`` at the chosen.  ``"softmax_topk"``: the
  ``per_tok`` largest logits ``x Wg`` are chosen; the weights are a
  softmax over THOSE logits; no bias, no groups.  Either way the weights
  are divided by their sum (+ ``norm_eps``: 1e-20 in ``deepseek_v3``, 1e-6
  in ``lfm2_moe``, 0 in ``smallthinker``) when ``norm_topk_prob``, times
  ``routed_scaling_factor``.
- **The router's input** is the experts' input unless a family says
  otherwise: ``smallthinker`` routes from the block's normed INPUT, before
  its attention, and its experts read the post-attention norm.  The
  choice, the counting sort and the tiles (``plan_routes``) then depend on
  nothing the attention computes and may stand before it in a program; the
  product and the combine (``apply_routes``) come after.
- **The share**: told ``held = (first, count)``, the layer computes the
  weighted outputs of the pairs (token, expert) whose expert it holds —
  every one of them: rows are grouped by expert (a counting sort, each
  group padded to the row tile) and go through one grouped gated product
  per layer (``ops.grouped_gemm.grouped_swiglu``, which reads each tile's
  rows out of the tokens through the sort's ``src``; the gate's activation
  SiLU, or ReLU for ``smallthinker``).  There is no
  capacity, no ``[E, C, H]`` buffer and no dropped token: the row buffer
  is sized for the worst routing (every pair held).  What the absent
  experts would add is left out; no code stands in for the other chips or
  their exchange.  ``held = (0, n_routed_experts)`` is the whole layer.
- **The shared expert** is added for every token (each chip computes it
  alike; the sum over shares counts it once) by ``expert_layer``; a
  family without one (``lfm2_moe``, ``smallthinker``) calls
  ``routed_share`` alone.

``routed_share`` returns, beside the output, the held experts' row
counts: the engine's ``decode`` / ``prefill`` span args and the
benchmark's load metrics read them.

What the layer needs of a model is a ``Routing`` (``models/blocks.py``):
the rule, its numbers and the share.  A family's config has one
(``cfg.routing``) and its served model passes it.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models.blocks import Routing, swiglu
from ..ops import grouped_gemm


def route(x: jax.Array, router: jax.Array, bias: Optional[jax.Array],
          r: Routing) -> Tuple[jax.Array, jax.Array]:
    """x [T, H] -> (expert ids [T, k] int32, weights [T, k] fp32), by
    ``r.rule`` (``bias``: the selection bias of ``"sigmoid_bias"``; the
    other rule has none)."""
    E, n_group, k = r.experts, r.n_group, r.per_tok
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if r.rule == "softmax_topk":
        top, idx = jax.lax.top_k(logits, k)
        idx, w = idx.astype(jnp.int32), jax.nn.softmax(top, axis=-1)
    elif r.rule == "sigmoid_bias":
        s = jax.nn.sigmoid(logits)                             # [T, E]
        cand = s + bias.astype(jnp.float32)
        if r.topk_group < n_group:
            grouped = cand.reshape(-1, n_group, E // n_group)
            group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)  # [T, n_group]
            kept = jax.lax.top_k(group_score, r.topk_group)[1]  # [T, topk_g]
            keep = jnp.zeros(group_score.shape, bool).at[
                jnp.arange(kept.shape[0])[:, None], kept].set(True)
            # As published: the dropped groups' scores are masked to 0
            # (not -inf) before the top-k.
            cand = jnp.where(jnp.repeat(keep, E // n_group, axis=1), cand,
                             0.0)
        idx = jax.lax.top_k(cand, k)[1].astype(jnp.int32)      # [T, k]
        w = jnp.take_along_axis(s, idx, axis=1)
    else:
        raise ValueError(f"no routing rule {r.rule!r}")
    if r.norm:
        w = w / (w.sum(-1, keepdims=True) + r.norm_eps)
    return idx, w * r.scale


def _row_tile(tokens: int, r: Routing) -> int:
    """Rows a grouped-product tile holds: twice the mean rows an expert
    gets, as a power of two within [16, 128] (16 = a packed bf16 tile)."""
    mean = tokens * r.per_tok / r.experts
    tm = 16
    while tm < 128 and tm < 2 * mean:
        tm *= 2
    return tm


def dispatch(idx: jax.Array, r: Routing, tm: int, row_live=None):
    """Group the held pairs by expert (of the rows ``row_live [T]`` marks,
    where given: a serving program's dead slots and padding rows are not
    traffic and get no buffer row).  idx [T, k] -> dict:
    ``src`` [M] token of each buffer row (0 for an empty row), ``pos``
    [T, k] buffer row of each pair (0 where not held), ``on`` [T, k] the
    pair's expert is held, ``tile_expert`` [M / tm], ``n_live_tiles``,
    ``counts`` [count] rows per held expert.  M = the worst case: every
    pair held, each expert's group padded to ``tm``."""
    first, count = r.held
    T, k = idx.shape
    P = T * k
    M = -(-P // tm) * tm + count * tm
    local = idx.reshape(P) - first
    on = (local >= 0) & (local < count)
    if row_live is not None:
        on = on & jnp.repeat(row_live, k)
    oh = (jnp.where(on, local, count)[:, None]
          == jnp.arange(count, dtype=jnp.int32)[None]).astype(jnp.int32)
    ranks = jnp.cumsum(oh, axis=0)                             # [P, count]
    counts = ranks[-1]
    size = -(-counts // tm) * tm                               # padded
    end = jnp.cumsum(size)
    start = end - size
    pos = ((start[None] + ranks - 1) * oh).sum(-1)             # [P]
    token = jnp.arange(P, dtype=jnp.int32) // k
    src = jnp.zeros((M,), jnp.int32).at[jnp.where(on, pos, M)].set(
        token, mode="drop")
    tile_expert = jnp.searchsorted(
        end, jnp.arange(M // tm, dtype=jnp.int32) * tm, side="right")
    return {"src": src, "pos": pos.reshape(T, k), "on": on.reshape(T, k),
            "tile_expert": jnp.minimum(tile_expert, count - 1),
            "n_live_tiles": end[-1] // tm, "counts": counts}


def _tile_rows(d) -> jax.Array:
    """Rows each tile of the plan ``d`` holds ``[M / tm]``: an expert's
    ``counts`` rows fill its tiles from the first, the last one in part; a
    tile past the live ones holds none."""
    tm, counts, te = d["tm"], d["counts"], d["tile_expert"]
    size = -(-counts // tm) * tm
    held_to = jnp.cumsum(size) - size + counts      # past an expert's last row
    at = jnp.arange(te.shape[0], dtype=jnp.int32) * tm
    return jnp.clip(held_to[te] - at, 0, tm)


def _gate_act(act: str):
    return {"silu": jax.nn.silu, "relu": jax.nn.relu}[act]


def _experts_jnp(xs, p, tile_expert, n_live_tiles, tm, act: str = "silu"):
    """The grouped product without the kernel (off-TPU path and the
    kernel's test reference): each tile against its expert's weights."""
    M, H = xs.shape
    xt = xs.reshape(M // tm, tm, H)
    nt = (((2,), (2,)), ((0,), (0,)))

    def prod(w):
        return jax.lax.dot_general(xt, w[tile_expert].astype(xs.dtype), nt,
                                   preferred_element_type=jnp.float32)
    h = (_gate_act(act)(prod(p["w_gate"]))
         * prod(p["w_up"])).astype(xs.dtype)
    out = jnp.einsum("ntf,nfh->nth", h,
                     p["w_down"][tile_expert].astype(xs.dtype),
                     preferred_element_type=jnp.float32)
    live = jnp.arange(M // tm)[:, None, None] < n_live_tiles
    return jnp.where(live, out, 0.0).astype(xs.dtype).reshape(M, H)


def plan_routes(p: Dict[str, jax.Array], x: jax.Array, r: Routing,
                row_live=None) -> Dict[str, jax.Array]:
    """The part of the layer that reads the ROUTER's input ``x [T, H]``
    alone: the choice and its weights (scope ``router``), the counting
    sort and the tiles (scope ``dispatch``: ``dispatch``'s dict plus ``w``
    [T, k] and the row tile ``tm``).  ``apply_routes`` takes it."""
    with jax.named_scope("router"):
        idx, w = route(x, p["router"], p.get("router_bias"), r)
    with jax.named_scope("dispatch"):
        tm = _row_tile(x.shape[0], r)
        d = dispatch(idx, r, tm, row_live)
    return dict(d, w=w, tm=tm)


def _expert_stack(p: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """The three expert matrices as ``[experts (x layers), F, H]``."""
    return {k: p[k].reshape((-1,) + p[k].shape[-2:])
            for k in ("w_gate", "w_up", "w_down")}


def _apply(experts, x, d, r: Routing, kernel, layer, act: str):
    """``apply_routes`` over ``_expert_stack(p)`` (``routed_share`` stacks
    BEFORE it plans, the order its one-call form always traced in)."""
    if kernel is None:
        kernel = grouped_gemm.grouped_gemm_enabled("auto")
    tm = d["tm"]
    with jax.named_scope("dispatch"):
        # The kernel reads its rows through the plan; the plain arm gathers.
        xs = None if kernel else x[d["src"]]
        tile_expert = d["tile_expert"] if layer is None else \
            d["tile_expert"] + layer * r.held[1]
        tile_rows = _tile_rows(d) if kernel else None
    with jax.named_scope("experts"):
        if kernel:
            out = grouped_gemm.grouped_swiglu(
                x, d["src"], experts["w_gate"], experts["w_up"],
                experts["w_down"], tile_expert, tile_rows,
                d["n_live_tiles"], tm=tm, act=act)
        else:
            out = _experts_jnp(xs, experts, tile_expert,
                               d["n_live_tiles"], tm, act)
    with jax.named_scope("combine"):
        rows = out[d["pos"]].astype(jnp.float32)               # [T, k, H]
        y = jnp.einsum("tk,tkh->th", jnp.where(d["on"], d["w"], 0.0),
                       jnp.where(d["on"][..., None], rows, 0.0))
    return y.astype(x.dtype), d["counts"]


def apply_routes(p: Dict[str, jax.Array], x: jax.Array,
                 d: Dict[str, jax.Array], r: Routing,
                 kernel: Optional[bool] = None, layer=None,
                 act: str = "silu") -> Tuple[jax.Array, jax.Array]:
    """The held experts over THEIR input ``x [T, H]`` under the plan ``d``:
    the grouped gated product over the rows the plan names (``experts``:
    the kernel reads ``x`` through ``d["src"]`` itself; only the plain arm
    gathers ``x[src]`` first, scope ``dispatch``) and the weighted sum
    (``combine``).  Returns
    (y [T, H], rows per held expert).  ``kernel``, ``layer``, ``act``: see
    ``routed_share``."""
    return _apply(_expert_stack(p), x, d, r, kernel, layer, act)


def routed_share(p: Dict[str, jax.Array], x: jax.Array,
                 r: Routing, kernel: Optional[bool] = None,
                 layer=None, row_live=None, router_input=None,
                 act: str = "silu") -> Tuple[jax.Array, jax.Array]:
    """x [T, H] -> (what the held experts add [T, H], rows per held
    expert [count]; ``row_live [T]``: see ``dispatch``).  ``kernel``: the Pallas grouped product (default: on a
    TPU; a serving program passes its ``paged_kernel``).  With ``layer`` (a traced
    index) the expert weights in ``p`` are the STACK of all layers'
    ``[Le, E_held, F, H]`` and the product names an expert by ``layer *
    E_held + e``: a layer sliced out of the stack for a kernel would be
    copied, 1.4 GB a layer at the published widths.  ``router_input [T,
    H]``: the tensor the router reads where it is not the experts' own
    (default: ``x``); ``act``: the gate's activation, ``"silu"`` or
    ``"relu"`` (static)."""
    experts = _expert_stack(p)
    d = plan_routes(p, x if router_input is None else router_input, r,
                    row_live)
    return _apply(experts, x, d, r, kernel, layer, act)


def expert_layer(p: Dict[str, jax.Array], x: jax.Array,
                 r: Routing, kernel: Optional[bool] = None,
                 layer=None, row_live=None, router_input=None,
                 act: str = "silu") -> Tuple[jax.Array, jax.Array]:
    """The whole FFN of an expert layer for normed ``x [T, H]``: the held
    share of the routed experts plus the shared expert (a gated SiLU
    whatever ``act``).  Returns (y, rows per held expert).  ``layer``,
    ``row_live``, ``router_input``, ``act``: see ``routed_share``."""
    with jax.named_scope("moe"):
        y, counts = routed_share(p, x, r, kernel, layer, row_live,
                                 router_input, act)
        with jax.named_scope("shared"):
            y = y + swiglu(x, p["shared_gate"], p["shared_up"],
                           p["shared_down"])
    return y, counts


__all__ = ["route", "dispatch", "plan_routes", "apply_routes",
           "routed_share", "expert_layer"]
